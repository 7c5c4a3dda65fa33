"""The paper's two FL task models (Section V.A), in JAX.

* CNN: 2x (5x5 conv -> 2x2 maxpool) -> FC(512) ReLU -> softmax(10)
  (McMahan et al. 2017 MNIST CNN, lr 0.002, cross-entropy).
* LSTM: 8-dim char embedding -> 2x LSTM(256) -> softmax per char
  (the stacked character LSTM, lr 0.3 in the paper).

Beside them, ``LoRALMTask``: federated low-rank adaptation of a frozen
language model (a registry ``ModelConfig``), the payload being the adapters.

Each task exposes the interface DAG-FL core consumes:
  init(key) -> params
  eval_fn(params, batch) -> accuracy in [0,1]
  train_fn(params, batch, key) -> (params, metrics)   # one epoch/minibatch
Sizes are configurable so benches can run a scaled-down variant on CPU.
A task with frozen device state besides its params also exposes
  frozen -> pytree of arrays;  bind(frozen) -> the task computing with it
so that the jitted stages take that state as an argument (``fl/systems.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lora as lora_lib
from repro.models.layers import softmax_xent
from repro.models.transformer import Model


# ---------------------------------------------------------------------------
# CNN task
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CNNTask:
    image_size: int = 28
    channels: Tuple[int, int] = (32, 64)
    kernel: int = 5
    fc_units: int = 512
    num_classes: int = 10
    learning_rate: float = 0.002

    def init(self, key) -> Dict:
        c1, c2 = self.channels
        k = self.kernel
        ks = jax.random.split(key, 4)
        fm = self.image_size // 4                   # two 2x2 pools
        fan1 = k * k * 1
        fan2 = k * k * c1
        fan3 = fm * fm * c2
        return {
            "conv1": jax.random.normal(ks[0], (k, k, 1, c1)) / math.sqrt(fan1),
            "b1": jnp.zeros((c1,)),
            "conv2": jax.random.normal(ks[1], (k, k, c1, c2)) / math.sqrt(fan2),
            "b2": jnp.zeros((c2,)),
            "fc": jax.random.normal(ks[2], (fan3, self.fc_units)) / math.sqrt(fan3),
            "bfc": jnp.zeros((self.fc_units,)),
            "out": jax.random.normal(ks[3], (self.fc_units, self.num_classes))
            / math.sqrt(self.fc_units),
            "bout": jnp.zeros((self.num_classes,)),
        }

    def logits(self, params, x):
        def conv(h, w, b):
            h = jax.lax.conv_general_dilated(
                h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
            )
            h = jax.nn.relu(h + b)
            return jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )

        h = conv(x, params["conv1"], params["b1"])
        h = conv(h, params["conv2"], params["b2"])
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.relu(h @ params["fc"] + params["bfc"])
        return h @ params["out"] + params["bout"]

    def loss(self, params, batch):
        logits = self.logits(params, batch["x"])
        return softmax_xent(logits, batch["y"])

    def eval_fn(self, params, batch) -> jnp.ndarray:
        logits = self.logits(params, batch["x"])
        return jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))

    def train_fn(self, params, batch, key):
        loss, grads = jax.value_and_grad(self.loss)(params, batch)
        params = jax.tree_util.tree_map(
            lambda p, g: p - self.learning_rate * g, params, grads
        )
        return params, {"loss": loss}

    def attack_success_rate(self, params, batch, target_shift: int = 1):
        """Backdoor metric (Table III): triggered images classified as y+1."""
        logits = self.logits(params, batch["x"])
        target = (batch["y"] + target_shift) % self.num_classes
        return jnp.mean((jnp.argmax(logits, -1) == target).astype(jnp.float32))


# ---------------------------------------------------------------------------
# LSTM task
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LSTMTask:
    vocab: int = 90
    embed_dim: int = 8
    hidden: int = 256
    num_layers: int = 2
    learning_rate: float = 0.3

    def init(self, key) -> Dict:
        ks = jax.random.split(key, 2 + self.num_layers)
        params = {
            "embed": jax.random.normal(ks[0], (self.vocab, self.embed_dim)) * 0.1,
            "out": jax.random.normal(ks[1], (self.hidden, self.vocab))
            / math.sqrt(self.hidden),
            "bout": jnp.zeros((self.vocab,)),
        }
        inp = self.embed_dim
        for l in range(self.num_layers):
            fan = inp + self.hidden
            params[f"lstm{l}"] = {
                "w": jax.random.normal(ks[2 + l], (fan, 4 * self.hidden)) / math.sqrt(fan),
                "b": jnp.zeros((4 * self.hidden,)),
            }
            inp = self.hidden
        return params

    def _lstm_layer(self, p, xs):
        """xs: (T, B, in) -> (T, B, hidden)."""
        B = xs.shape[1]
        h0 = jnp.zeros((B, self.hidden))
        c0 = jnp.zeros((B, self.hidden))

        def step(carry, x):
            h, c = carry
            z = jnp.concatenate([x, h], axis=-1) @ p["w"] + p["b"]
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        _, hs = jax.lax.scan(step, (h0, c0), xs)
        return hs

    def logits(self, params, tokens):
        """tokens (B, T) -> (B, T, V)."""
        x = params["embed"][tokens]                       # (B,T,E)
        xs = jnp.moveaxis(x, 1, 0)
        for l in range(self.num_layers):
            xs = self._lstm_layer(params[f"lstm{l}"], xs)
        hs = jnp.moveaxis(xs, 0, 1)
        return hs @ params["out"] + params["bout"]

    def loss(self, params, batch):
        tokens = batch["tokens"]
        logits = self.logits(params, tokens)[:, :-1]
        return softmax_xent(logits, tokens[:, 1:])

    def eval_fn(self, params, batch) -> jnp.ndarray:
        tokens = batch["tokens"]
        logits = self.logits(params, tokens)[:, :-1]
        pred = jnp.argmax(logits, -1)
        return jnp.mean((pred == tokens[:, 1:]).astype(jnp.float32))

    def train_fn(self, params, batch, key):
        loss, grads = jax.value_and_grad(self.loss)(params, batch)
        params = jax.tree_util.tree_map(
            lambda p, g: p - self.learning_rate * g, params, grads
        )
        return params, {"loss": loss}


# ---------------------------------------------------------------------------
# LoRA fine-tuning of a frozen language model
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _base_shapes(cfg: ModelConfig):
    return jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def frozen_base(cfg: ModelConfig, base_seed: int):
    """The frozen base weights of ``cfg``, built once per process on the
    default device: ``lora.random_base`` from ``PRNGKey(base_seed)`` (one
    key per leaf by its path via ``fold_in``, fan-in scaled), in one jitted
    call that takes the key as an argument (a key the compiler sees as a
    constant lets it fold the draws into the executable)."""
    draw = jax.jit(functools.partial(lora_lib.random_base, shapes=_base_shapes(cfg)))
    base = draw(jax.random.PRNGKey(base_seed))
    if any(isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(base)):
        raise RuntimeError("build the frozen base outside a trace (task.frozen)")
    return base


@functools.partial(jax.jit, static_argnums=(0, 1))
def _lm_accuracy(model: Model, lora: lora_lib.LoRAConfig, base, adapters, tokens):
    logits, _ = model.forward(lora_lib.merge(base, adapters, lora), tokens)
    pred = jnp.argmax(logits[:, :-1], -1)
    return jnp.mean((pred == tokens[:, 1:]).astype(jnp.float32))


@dataclass(frozen=True, eq=False)
class LoRALMTask:
    """Federated instruction tuning in the FedIT / OpenFedLLM manner
    (arXiv:2305.05644, arXiv:2402.06954): each node trains low-rank adapters
    (``models/lora.py``) over a frozen base shared by all nodes, and the
    DAG-FL transaction is the adapters alone.

    * ``init(key)`` returns adapters (``a`` seeded, ``b`` zero); the bank
      stores them.
    * ``eval_fn`` is next-token top-1 accuracy over ``batch["tokens"]``.
    * ``train_fn`` is one plain SGD step on the adapters. The loss is the
      next-token cross-entropy alone: the router is frozen with the base, so
      its load-balance auxiliary loss has nothing to train and is left out.

    The base (``frozen``) lives on the device, built once per process from
    ``base_seed`` (``frozen_base``); ``bind`` gives a task that computes
    with a given base, which is how the jitted stages receive it as an
    argument instead of a constant baked into the program. The task hashes
    by identity, so a run's episodes share one trace of the stages.
    """

    cfg: ModelConfig
    base_seed: int = 0
    learning_rate: float = 0.05
    lora: lora_lib.LoRAConfig = lora_lib.LoRAConfig()
    base: Optional[Any] = None          # None: frozen_base(cfg, base_seed)

    @property
    def model(self) -> Model:
        return Model(self.cfg, remat=False)

    @property
    def frozen(self):
        return self.base if self.base is not None else frozen_base(self.cfg, self.base_seed)

    def bind(self, base) -> "LoRALMTask":
        return dataclasses.replace(self, base=base)

    def init(self, key) -> Dict:
        return lora_lib.init_adapters(key, _base_shapes(self.cfg), self.lora)

    def logits(self, adapters, tokens):
        logits, _ = self.model.forward(lora_lib.merge(self.frozen, adapters, self.lora),
                                       tokens)
        return logits

    def loss(self, adapters, batch):
        tokens = batch["tokens"]
        return softmax_xent(self.logits(adapters, tokens)[:, :-1], tokens[:, 1:])

    def eval_fn(self, params, batch) -> jnp.ndarray:
        return _lm_accuracy(self.model, self.lora, self.frozen, params, batch["tokens"])

    def train_fn(self, params, batch, key):
        loss, grads = jax.value_and_grad(self.loss)(params, batch)
        params = jax.tree_util.tree_map(
            lambda p, g: p - self.learning_rate * g, params, grads
        )
        return params, {"loss": loss}


def make_epoch_train(task):
    """One 'iteration' trains over several minibatches (an epoch, §V.A.1).

    Returns train_fn(params, batch, key) where each leaf of ``batch`` has a
    leading steps axis; single-step training is scanned over it.
    """

    def train(params, batch, key):
        steps = jax.tree_util.tree_leaves(batch)[0].shape[0]
        keys = jax.random.split(key, steps)

        def body(p, xs):
            kb, mb = xs
            p, m = task.train_fn(p, mb, kb)
            return p, m["loss"]

        params, losses = jax.lax.scan(body, params, (keys, batch))
        return params, {"loss": losses[-1]}

    return train


def bench_cnn_task() -> CNNTask:
    """Scaled-down CNN for CPU benches (EXPERIMENTS.md notes the scaling).

    lr 0.05 instead of the full-size task's 0.002: the scaled model needs a
    hotter step, but 0.2 diverges on the class-skewed paper partition (each
    node's epoch yanks the model toward its dominant class and accuracy
    oscillates at chance), which is what kept test_system xfailed.
    """
    return CNNTask(image_size=16, channels=(8, 16), fc_units=64, learning_rate=0.05)


def bench_lstm_task() -> LSTMTask:
    return LSTMTask(hidden=64, num_layers=2, learning_rate=0.3)
