"""The paper's Shakespeare character LSTM (arXiv:2104.13092 Sec. V.A).

Inputs, plain reference and FLOPs, as ``tasks/cnn.py`` has them:

* ``make_data``  role-conditioned Markov text over a 90-character alphabet,
  80-character lines, one random role per node (the paper's non-IID
  source); every line of every node is drawn in one vectorised pass.
* ``init`` / ``logits`` / ``loss`` / ``accuracy``  8-dimensional embedding,
  2 x LSTM(256) with gates (i, f, g, o) from one matmul over [x, h] and a
  +1 forget-gate bias, a 90-way output layer; next-character cross-entropy
  and accuracy over the 79 predicted positions; plain SGD.
* ``flops``  the model FLOPs of one DAG-FL iteration, from the shapes.
"""
from __future__ import annotations

import math

import numpy as np


def program_task(tasks_mod, model: dict):
    """The program's task object at this configuration's widths."""
    return tasks_mod.LSTMTask(
        vocab=model["vocab"], embed_dim=model["embed_dim"], hidden=model["hidden"],
        num_layers=model["num_layers"], learning_rate=model["learning_rate"])


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


def _chains(rng, roles: int, vocab: int, bias: float) -> np.ndarray:
    """(roles, V, V) cumulative transition tables, each role favouring 12 chars."""
    base = rng.dirichlet(np.ones(vocab) * 0.3, size=vocab)
    mats = np.empty((roles, vocab, vocab))
    for r in range(roles):
        m = base.copy()
        m[:, rng.choice(vocab, size=12, replace=False)] *= bias
        mats[r] = m / m.sum(axis=1, keepdims=True)
    return np.cumsum(mats, axis=2)


def _lines(rng, cdf: np.ndarray, role: np.ndarray, length: int) -> np.ndarray:
    """One line per entry of ``role``, all lines stepped together."""
    vocab = cdf.shape[1]
    out = np.empty((len(role), length), np.int32)
    c = rng.integers(0, vocab, len(role))
    for t in range(length):
        out[:, t] = c
        u = rng.random(len(role))
        c = np.minimum((cdf[role, c] < u[:, None]).sum(axis=1), vocab - 1)
    return out


def make_data(cfg: dict, seed_seq: np.random.SeedSequence):
    """(per-node [(train, test)], global validation set) for ``cfg``."""
    d, model = cfg["data"], cfg["model"]
    n_nodes = cfg["dagfl"]["num_nodes"]
    chain_ss, part_ss, val_ss = seed_seq.spawn(3)
    cdf = _chains(np.random.default_rng(chain_ss), d["roles"], model["vocab"],
                  d["order_bias"])
    rng = np.random.default_rng(part_ss)
    roles = rng.integers(0, d["roles"], n_nodes)
    per = d["lines_per_node"]
    lines = _lines(rng, cdf, np.repeat(roles, per), d["line_len"])
    nodes = []
    for i in range(n_nodes):
        mine = lines[i * per:(i + 1) * per]
        n_test = max(4, int(per * d["test_frac"]))
        perm = rng.permutation(per)
        nodes.append(({"tokens": mine[perm[n_test:]]}, {"tokens": mine[perm[:n_test]]}))
    vrng = np.random.default_rng(val_ss)
    vroles = np.repeat(np.arange(d["global_val_roles"]), d["global_val_lines"])
    return nodes, {"tokens": _lines(vrng, cdf, vroles, d["line_len"])}


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def init(key, model: dict):
    """Genesis weights: N(0, 0.01) embedding, N(0, 1/fan_in) matrices, zero biases."""
    import jax
    import jax.numpy as jnp

    v, e, h, layers = (model["vocab"], model["embed_dim"], model["hidden"],
                       model["num_layers"])
    ks = jax.random.split(key, 2 + layers)
    params = {
        "embed": jax.random.normal(ks[0], (v, e)) * 0.1,
        "out": jax.random.normal(ks[1], (h, v)) / math.sqrt(h),
        "bout": jnp.zeros((v,)),
    }
    fan_in = e
    for layer in range(layers):
        fan = fan_in + h
        params[f"lstm{layer}"] = {
            "w": jax.random.normal(ks[2 + layer], (fan, 4 * h)) / math.sqrt(fan),
            "b": jnp.zeros((4 * h,)),
        }
        fan_in = h
    return params


def logits(params, batch, precision):
    """(B, T, V) next-character logits for ``batch["tokens"]`` (B, T)."""
    import jax
    import jax.numpy as jnp

    xs = jnp.moveaxis(params["embed"][batch["tokens"]], 1, 0)       # (T, B, E)
    layer = 0
    while f"lstm{layer}" in params:
        p = params[f"lstm{layer}"]
        hidden = p["b"].shape[0] // 4
        zero = jnp.zeros((xs.shape[1], hidden), xs.dtype)

        def step(carry, x, p=p):
            h, c = carry
            z = jnp.dot(jnp.concatenate([x, h], axis=-1), p["w"],
                        precision=precision) + p["b"]
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        _, xs = jax.lax.scan(step, (zero, zero), xs)
        layer += 1
    hs = jnp.moveaxis(xs, 0, 1)
    return jnp.dot(hs, params["out"], precision=precision) + params["bout"]


def loss(params, batch, precision):
    import jax
    import jax.numpy as jnp

    tokens = batch["tokens"]
    z = logits(params, batch, precision)[:, :-1].astype(jnp.float32)
    ll = jnp.take_along_axis(jax.nn.log_softmax(z), tokens[:, 1:, None], axis=-1)
    return -jnp.mean(ll)


def accuracy(params, batch, precision):
    import jax.numpy as jnp

    tokens = batch["tokens"]
    z = logits(params, batch, precision)[:, :-1]
    return jnp.mean((jnp.argmax(z, -1) == tokens[:, 1:]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------


def step_macs(model: dict) -> dict:
    """Multiply-accumulates of one character position's forward pass."""
    e, h, layers = model["embed_dim"], model["hidden"], model["num_layers"]
    macs = {"lstm0": (e + h) * 4 * h}
    for layer in range(1, layers):
        macs[f"lstm{layer}"] = 2 * h * 4 * h
    macs["out"] = h * model["vocab"]
    return macs


def flops(cfg: dict, eval_every: int, iterations: int) -> dict:
    """Model FLOPs of one committed DAG-FL iteration, by part.

    A line runs all ``line_len`` positions through the stack (the logits
    of the last one are dropped after the fact). Training counts forward,
    weight gradients and input gradients (the embedding is trained, so
    every layer's input gradient is formed); validation, aggregation and
    the agent are counted as in ``tasks/cnn.py``.
    """
    d, dg, sim, model = cfg["data"], cfg["dagfl"], cfg["sim"], cfg["model"]
    fwd_line = 2 * sum(step_macs(model).values()) * d["line_len"]
    checks = iterations // eval_every
    gval = d["global_val_roles"] * d["global_val_lines"]
    agent_lines = gval * ((dg["alpha"] + 1) * checks + 1)
    return {
        "train": dg["beta"] * sim["steps_per_iter"] * sim["minibatch"] * 3 * fwd_line,
        "validate": (dg["alpha"] + 1) * sim["val_size"] * fwd_line,
        "aggregate": 2 * dg["k"] * param_count(model),
        "agent": agent_lines * fwd_line / iterations,
    }


def param_count(model: dict) -> int:
    v, e, h, layers = (model["vocab"], model["embed_dim"], model["hidden"],
                       model["num_layers"])
    n = v * e + h * v + v
    fan_in = e
    for _ in range(layers):
        n += (fan_in + h) * 4 * h + 4 * h
        fan_in = h
    return n
