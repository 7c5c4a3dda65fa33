"""The paper's MNIST CNN (arXiv:2104.13092 Sec. V.A): inputs, plain reference, FLOPs.

Everything here belongs to the benchmark, not to the program under test:

* ``make_data``  the synthetic MNIST-like population, made in bulk from a
  seed: 10 smooth class prototypes, per-sample shifts and noise, and the
  paper's non-IID split (two single-digit shards per node plus a uniform
  sprinkle). The program receives only these arrays.
* ``init`` / ``logits`` / ``loss`` / ``accuracy``  the architecture written
  out in plain ``jax.numpy``: 2 x (5x5 conv, ReLU, 2x2 max-pool), FC 512
  ReLU, 10 logits, mean cross-entropy, plain SGD.
* ``flops``  the model FLOPs of one DAG-FL iteration, from the shapes.
"""
from __future__ import annotations

import math

import numpy as np

NUM_CLASSES = 10


def program_task(tasks_mod, model: dict):
    """The program's task object at this configuration's widths."""
    return tasks_mod.CNNTask(
        image_size=model["image_size"], channels=tuple(model["channels"]),
        kernel=model["kernel"], fc_units=model["fc_units"],
        num_classes=model["num_classes"], learning_rate=model["learning_rate"])


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


def _prototypes(rng, size: int) -> np.ndarray:
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    protos = np.zeros((NUM_CLASSES, size, size), np.float32)
    for c in range(NUM_CLASSES):
        for _ in range(4):
            fx, fy = rng.uniform(0.5, 3.0, 2)
            px, py = rng.uniform(0, 2 * np.pi, 2)
            protos[c] += (np.cos(2 * np.pi * fx * xx + px)
                          * np.cos(2 * np.pi * fy * yy + py))
        protos[c] /= np.max(np.abs(protos[c]))
    return protos


def _images(rng, protos, labels, noise: float) -> np.ndarray:
    """Shifted, noisy prototypes, min-max normalised over the set: (n, s, s, 1)."""
    n, s = len(labels), protos.shape[1]
    shift = rng.integers(-2, 3, size=(n, 2))
    rows = (np.arange(s)[None, :] - shift[:, :1]) % s          # np.roll per sample
    cols = (np.arange(s)[None, :] - shift[:, 1:]) % s
    imgs = protos[labels][np.arange(n)[:, None, None], rows[:, :, None],
                          cols[:, None, :]]
    imgs = imgs + rng.normal(0, noise, imgs.shape).astype(np.float32)
    imgs = (imgs - imgs.min()) / (imgs.max() - imgs.min() + 1e-9)
    return imgs[..., None].astype(np.float32)


def make_data(cfg: dict, seed_seq: np.random.SeedSequence):
    """(per-node [(train, test)], global validation set) for ``cfg``."""
    d, model = cfg["data"], cfg["model"]
    n_nodes = cfg["dagfl"]["num_nodes"]
    proto_ss, part_ss, val_ss = seed_seq.spawn(3)
    protos = _prototypes(np.random.default_rng(proto_ss), model["image_size"])
    rng = np.random.default_rng(part_ss)
    shards = 2 * n_nodes
    shard_digit = np.repeat(np.arange(NUM_CLASSES), -(-shards // NUM_CLASSES))[:shards]
    rng.shuffle(shard_digit)
    nodes = []
    for i in range(n_nodes):
        labels = np.concatenate([
            np.full(d["shard_size"], shard_digit[2 * i]),
            np.full(d["shard_size"], shard_digit[2 * i + 1]),
            rng.integers(0, NUM_CLASSES, d["uniform_per_node"])])
        x = _images(rng, protos, labels, d["noise"])
        y = labels.astype(np.int32)
        n_test = max(8, int(len(y) * d["test_frac"]))
        perm = rng.permutation(len(y))
        te, tr = perm[:n_test], perm[n_test:]
        nodes.append(({"x": x[tr], "y": y[tr]}, {"x": x[te], "y": y[te]}))
    vrng = np.random.default_rng(val_ss)
    labels = vrng.integers(0, NUM_CLASSES, d["global_val"])
    gval = {"x": _images(vrng, protos, labels, d["noise"]),
            "y": labels.astype(np.int32)}
    return nodes, gval


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def init(key, model: dict):
    """Genesis weights: N(0, 1/fan_in) kernels and zero biases, one key each."""
    import jax
    import jax.numpy as jnp

    c1, c2 = model["channels"]
    k, s = model["kernel"], model["image_size"]
    fan3 = (s // 4) ** 2 * c2
    ks = jax.random.split(key, 4)
    return {
        "conv1": jax.random.normal(ks[0], (k, k, 1, c1)) / math.sqrt(k * k),
        "b1": jnp.zeros((c1,)),
        "conv2": jax.random.normal(ks[1], (k, k, c1, c2)) / math.sqrt(k * k * c1),
        "b2": jnp.zeros((c2,)),
        "fc": jax.random.normal(ks[2], (fan3, model["fc_units"])) / math.sqrt(fan3),
        "bfc": jnp.zeros((model["fc_units"],)),
        "out": jax.random.normal(ks[3], (model["fc_units"], model["num_classes"]))
        / math.sqrt(model["fc_units"]),
        "bout": jnp.zeros((model["num_classes"],)),
    }


def logits(params, batch, precision):
    import jax
    import jax.numpy as jnp

    def block(h, w, b):
        h = jax.lax.conv_general_dilated(
            h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision)
        h = jnp.maximum(h + b, 0)
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = batch["x"].astype(params["conv1"].dtype)
    h = block(x, params["conv1"], params["b1"])
    h = block(h, params["conv2"], params["b2"])
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(jnp.dot(h, params["fc"], precision=precision) + params["bfc"], 0)
    return jnp.dot(h, params["out"], precision=precision) + params["bout"]


def loss(params, batch, precision):
    import jax
    import jax.numpy as jnp

    z = logits(params, batch, precision).astype(jnp.float32)
    ll = jnp.take_along_axis(jax.nn.log_softmax(z), batch["y"][:, None], axis=-1)
    return -jnp.mean(ll)


def accuracy(params, batch, precision):
    import jax.numpy as jnp

    z = logits(params, batch, precision)
    return jnp.mean((jnp.argmax(z, -1) == batch["y"]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------


def forward_macs(model: dict) -> dict:
    """Multiply-accumulates of one example's forward pass, per layer."""
    c1, c2 = model["channels"]
    k, s = model["kernel"], model["image_size"]
    return {
        "conv1": s * s * k * k * 1 * c1,
        "conv2": (s // 2) ** 2 * k * k * c1 * c2,
        "fc": (s // 4) ** 2 * c2 * model["fc_units"],
        "out": model["fc_units"] * model["num_classes"],
    }


def flops(cfg: dict, eval_every: int, iterations: int) -> dict:
    """Model FLOPs of one committed DAG-FL iteration, by part.

    Training counts forward, weight gradients and input gradients (the
    image's own gradient is never formed, so conv1 has none); validation
    evaluates all ``alpha`` candidates plus the freshly trained model on
    ``val_size`` examples; the agent evaluates ``alpha`` tips and its
    aggregate on the global set at each check (``iterations // eval_every``
    of them: the in-loop checks and the final one) plus the genesis
    evaluation, spread over the episode's iterations.
    """
    macs = forward_macs(cfg["model"])
    fwd = 2 * sum(macs.values())
    train_ex = 3 * fwd - 2 * macs["conv1"]
    dg, sim = cfg["dagfl"], cfg["sim"]
    n_params = param_count(cfg["model"])
    checks = iterations // eval_every
    agent_ex = cfg["data"]["global_val"] * ((dg["alpha"] + 1) * checks + 1)
    return {
        "train": dg["beta"] * sim["steps_per_iter"] * sim["minibatch"] * train_ex,
        "validate": (dg["alpha"] + 1) * sim["val_size"] * fwd,
        "aggregate": 2 * dg["k"] * n_params,
        "agent": agent_ex * fwd / iterations,
    }


def param_count(model: dict) -> int:
    c1, c2 = model["channels"]
    k, s = model["kernel"], model["image_size"]
    fan3 = (s // 4) ** 2 * c2
    return (k * k * c1 + c1 + k * k * c1 * c2 + c2 + fan3 * model["fc_units"]
            + model["fc_units"] + model["fc_units"] * model["num_classes"]
            + model["num_classes"])
