"""Federated LoRA fine-tuning of DeepSeek-V2-Lite: inputs, plain reference, FLOPs.

Everything here belongs to the benchmark, not to the program under test:

* ``make_data``  seeded non-IID text over the vocabulary slice: 10 topics,
  each a Zipf unigram over its own ordering of the slice plus a seeded
  successor rule (each token followed by its topic's successor with
  probability ``rule_prob``, else by a unigram draw), so that next-token
  accuracy can rise; every node holds two topics, the paper's two shards.
  The program receives only these arrays.
* ``init`` / ``logits`` / ``loss`` / ``accuracy``  the model written out in
  plain ``jax.numpy`` at float32 and the given precision, sharing no code
  with the program: MLA with YaRN rope and softmax scale, the dense first
  layer, softmax gating over all ``router_experts`` with the top
  ``num_experts_per_tok`` kept unnormalised, this chip's experts (held
  ones only, each over every token, masked by its gate) beside the shared
  experts, rank-``r`` adapters on the four attention projections, the head
  over the slice, next-token cross-entropy and top-1 accuracy. Adapters
  arrive in the program's layout: ``{"prefix": [layer 0], "layers":
  stacked MoE layers}``, each ``{"attn": {name: {"a", "b"}}}``.
* ``frozen``  the reference's own copy of the frozen base, drawn from
  ``base_seed`` where the reference computes, so only after the window:
  one key per leaf, ``fold_in(PRNGKey(base_seed), crc32(path))`` over the
  program's tree paths (``base_leaves``), norm scales one, the embedding
  N(0, 1), every other weight N(0, 1/fan_in).
* ``flops``, ``param_count``, and the counts of one call of the experts'
  grouped product (``expert_call_flops``, ``expert_call_bytes``) that
  ``metrics/moe_experts_roofline.py`` reads.

Rope acts on the two halves of the rope dimensions (rotate-half). DeepSeek's
modelling code first de-interleaves them, which with seeded weights is a
fixed permutation of the projection's rope columns.
"""
from __future__ import annotations

import json
import math
import zlib

import numpy as np


def _arch(model: dict) -> dict:
    return model["arch"]


_TASKS = {}


def program_task(tasks_mod, model: dict):
    """The program's task object: the configuration's share of the model.

    One object per configuration in a process: the task hashes by identity,
    so every run that shares it (``calibrate.py`` makes one per seed) shares
    one trace of the program's stages and one frozen base."""
    key = json.dumps(model, sort_keys=True)
    if key not in _TASKS:
        _TASKS[key] = _make_task(tasks_mod, model)
    return _TASKS[key]


def _make_task(tasks_mod, model: dict):
    from repro.configs.base import ModelConfig, YarnScaling
    from repro.models.lora import LoRAConfig

    a, ys = _arch(model), _arch(model)["rope_scaling"]
    cfg = ModelConfig(
        name="deepseek-v2-lite-share", family="moe", attention="mla",
        num_layers=a["num_hidden_layers"], d_model=a["hidden_size"],
        num_heads=a["num_attention_heads"], num_kv_heads=a["num_key_value_heads"],
        head_dim=a["qk_nope_head_dim"], v_head_dim=a["v_head_dim"],
        rope_head_dim=a["qk_rope_head_dim"], kv_lora_rank=a["kv_lora_rank"],
        q_lora_rank=a["q_lora_rank"] or 0, rope_theta=float(a["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(ys["factor"]),
            original_max_position_embeddings=ys["original_max_position_embeddings"],
            beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
            mscale=float(ys["mscale"]), mscale_all_dim=float(ys["mscale_all_dim"])),
        d_ff=a["intermediate_size"], moe_d_ff=a["moe_intermediate_size"],
        first_dense_layers=a["first_k_dense_replace"], vocab_size=a["vocab_size"],
        num_experts=model["router_experts"], experts_held=a["n_routed_experts"],
        expert_offset=model["expert_offset"], num_shared_experts=a["n_shared_experts"],
        experts_per_token=a["num_experts_per_tok"], norm_topk_prob=a["norm_topk_prob"],
        moe_impl="ragged", rms_norm_eps=float(a["rms_norm_eps"]), act="swiglu",
        dtype="float32")
    lo = model["lora"]
    return tasks_mod.LoRALMTask(
        cfg, base_seed=model["base_seed"], learning_rate=model["learning_rate"],
        lora=LoRAConfig(rank=lo["rank"], alpha=float(lo["alpha"])))


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


def _check_arch(cfg: dict) -> None:
    """The model's copy of the architecture matches the file's top level."""
    bad = [k for k, v in _arch(cfg["model"]).items() if cfg.get(k) != v]
    if bad:
        raise ValueError(f"model.arch disagrees with the configuration on {bad}")


def _topics(rng, d: dict, vocab: int):
    """Per topic: unigram CDF over the slice and a successor table."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64) ** -float(d["zipf_s"])
    cdfs, succ = [], []
    for _ in range(d["topics"]):
        p = np.empty(vocab)
        p[rng.permutation(vocab)] = ranks / ranks.sum()
        cdfs.append(np.cumsum(p))
        succ.append(rng.permutation(vocab))
    return np.stack(cdfs), np.stack(succ).astype(np.int32)


def _text(rng, cdfs, succ, topic: np.ndarray, length: int, rule_prob: float):
    """One sequence per entry of ``topic``, all stepped together."""
    n, vocab = len(topic), cdfs.shape[1]
    u = rng.random((n, length))
    draw = np.empty((n, length), np.int32)
    for t in np.unique(topic):
        rows = topic == t
        draw[rows] = np.minimum(np.searchsorted(cdfs[t], u[rows]), vocab - 1)
    follow = rng.random((n, length)) < rule_prob
    out = np.empty((n, length), np.int32)
    out[:, 0] = draw[:, 0]
    for i in range(1, length):
        out[:, i] = np.where(follow[:, i], succ[topic, out[:, i - 1]], draw[:, i])
    return out


def make_data(cfg: dict, seed_seq: np.random.SeedSequence):
    """(per-node [(train, test)], global validation set) for ``cfg``; also
    fixes the model the reference computes with (``use``)."""
    _check_arch(cfg)
    use(cfg["model"])
    d, vocab = cfg["data"], _arch(cfg["model"])["vocab_size"]
    n_nodes = cfg["dagfl"]["num_nodes"]
    topic_ss, part_ss, val_ss = seed_seq.spawn(3)
    cdfs, succ = _topics(np.random.default_rng(topic_ss), d, vocab)
    rng = np.random.default_rng(part_ss)
    shards = 2 * n_nodes
    shard_topic = np.repeat(np.arange(d["topics"]), -(-shards // d["topics"]))[:shards]
    rng.shuffle(shard_topic)
    per = d["seqs_per_node"]
    topic = np.concatenate([np.repeat(shard_topic[2 * i:2 * i + 2], per // 2)
                            for i in range(n_nodes)])
    text = _text(rng, cdfs, succ, topic, d["seq_len"], d["rule_prob"])
    nodes = []
    n_test = max(2, int(per * d["test_frac"]))
    for i in range(n_nodes):
        mine = text[i * per:(i + 1) * per]
        perm = rng.permutation(per)
        nodes.append(({"tokens": mine[perm[n_test:]]}, {"tokens": mine[perm[:n_test]]}))
    vrng = np.random.default_rng(val_ss)
    vtopic = np.arange(d["global_val"]) % d["topics"]
    return nodes, {"tokens": _text(vrng, cdfs, succ, vtopic, d["seq_len"], d["rule_prob"])}


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------

LORA_TARGETS = ("wq", "wkv_a", "wkv_b", "wo")


def _dims(model: dict) -> dict:
    a = _arch(model)
    return dict(d=a["hidden_size"], h=a["num_attention_heads"], pn=a["qk_nope_head_dim"],
                pr=a["qk_rope_head_dim"], pv=a["v_head_dim"], r=a["kv_lora_rank"],
                ff=a["intermediate_size"], mf=a["moe_intermediate_size"],
                held=a["n_routed_experts"], shared=a["n_shared_experts"],
                L=a["num_hidden_layers"], L0=a["first_k_dense_replace"], V=a["vocab_size"])


def _attn_leaves(d, h, pn, pr, pv, r):
    return {"attn/kv_norm/scale": (r,), "attn/wkv_a": (d, r + pr),
            "attn/wkv_b": (r, h * (pn + pv)), "attn/wo": (h * pv, d),
            "attn/wq": (d, h * (pn + pr)), "ln1/scale": (d,), "ln2/scale": (d,)}


def base_leaves(model: dict) -> dict:
    """Path -> shape of every leaf of the program's base tree."""
    m = _dims(model)
    d, n = m["d"], m["L"] - m["L0"]
    attn = _attn_leaves(d, m["h"], m["pn"], m["pr"], m["pv"], m["r"])
    out = {"embed": (m["V"], d), "final_norm/scale": (d,), "lm_head": (d, m["V"])}
    for i in range(m["L0"]):
        out.update({f"prefix/{i}/{k}": v for k, v in attn.items()})
        out.update({f"prefix/{i}/mlp/wi": (d, m["ff"]), f"prefix/{i}/mlp/wg": (d, m["ff"]),
                    f"prefix/{i}/mlp/wo": (m["ff"], d)})
    out.update({f"layers/{k}": (n,) + v for k, v in attn.items()})
    sf = m["shared"] * m["mf"]
    out.update({"layers/moe/router": (n, d, model["router_experts"]),
                "layers/moe/wi": (n, m["held"], d, m["mf"]),
                "layers/moe/wg": (n, m["held"], d, m["mf"]),
                "layers/moe/wo": (n, m["held"], m["mf"], d),
                "layers/moe/shared/wi": (n, d, sf), "layers/moe/shared/wg": (n, d, sf),
                "layers/moe/shared/wo": (n, sf, d)})
    return out


def _key(jax, key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def frozen(model: dict, tokens=None) -> dict:
    """The reference's copy of the frozen base, path -> array.

    Drawn afresh wherever the reference computes (inside each of the
    replay's jitted programs): a copy held on the device and closed over
    would be baked into every program as gigabytes of constants. For the
    same reason the key is made to depend on ``tokens`` (adding a zero the
    compiler cannot prove), or the compiler folds the draws themselves."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(model["base_seed"])
    if tokens is not None:
        key = key + jnp.minimum(jnp.min(tokens), 0).astype(jnp.uint32)
    out = {}
    for path, shape in base_leaves(model).items():
        if path.endswith("scale"):
            out[path] = jnp.ones(shape, jnp.float32)
            continue
        w = jax.random.normal(_key(jax, key, path), shape, jnp.float32)
        out[path] = w if path == "embed" else w * (1.0 / math.sqrt(shape[-2]))
    return out


_MODEL = {}


def use(model: dict) -> None:
    """Fix the model that ``logits``, ``loss`` and ``accuracy`` compute with
    (the harness's reference interface passes them no configuration)."""
    _MODEL["model"] = model


def init(key, model: dict):
    """Genesis adapters: ``a`` N(0, 1/fan_in) from its leaf's key, ``b``
    zero, for the four projections of every layer, in the program's layout.
    Also fixes the model the reference computes with (``use``)."""
    import jax
    import jax.numpy as jnp

    use(model)
    rank = model["lora"]["rank"]
    leaves = base_leaves(model)

    def pair(prefix):
        out = {}
        for name in LORA_TARGETS:
            shape = leaves[f"{prefix}/attn/{name}"]
            lead, (fan_in, fan_out) = shape[:-2], shape[-2:]
            a = jax.random.normal(_key(jax, key, f"{prefix}/attn/{name}/a"),
                                  lead + (fan_in, rank), jnp.float32) / math.sqrt(fan_in)
            out[name] = {"a": a, "b": jnp.zeros(lead + (rank, fan_out), jnp.float32)}
        return {"attn": out}

    m = _dims(model)
    return {"prefix": [pair(f"prefix/{i}") for i in range(m["L0"])],
            "layers": pair("layers")}


def _rms(jnp, jax, x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, ys: dict) -> np.ndarray:
    """YaRN (arXiv:2309.00071) frequencies as DeepSeek-V2's rotary embedding
    sets them, in float64."""
    def corr(rot):
        return (dim * math.log(ys["original_max_position_embeddings"] / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extra_share = 1.0 - ramp
    return (1.0 / (ys["factor"] * base)) * ramp + (1.0 / base) * extra_share


def _rope(jnp, x, inv_freq, m):
    """x (B, S, ..., dim): rotate-half rope at positions 0..S-1."""
    s = x.shape[1]
    ang = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None, :]
    shape = (1, s) + (1,) * (x.ndim - 3) + (ang.shape[-1],)
    cos = jnp.asarray((np.cos(ang) * m).reshape(shape), jnp.float32)
    sin = jnp.asarray((np.sin(ang) * m).reshape(shape), jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _proj(jnp, x, w, ad, scale, precision):
    y = jnp.dot(x, w, precision=precision)
    a = jnp.dot(jnp.dot(x, ad["a"], precision=precision), ad["b"], precision=precision)
    return y + a * scale


def _mla(jax, jnp, model, W, ad, x, precision):
    m, a = _dims(model), _arch(model)
    ys = a["rope_scaling"]
    scale = model["lora"]["alpha"] / model["lora"]["rank"]
    b, s, _ = x.shape
    h, pn, pr, pv, r = m["h"], m["pn"], m["pr"], m["pv"], m["r"]
    inv = yarn_inv_freq(pr, float(a["rope_theta"]), ys)
    mrope = _mscale(ys["factor"], ys["mscale"]) / _mscale(ys["factor"], ys["mscale_all_dim"])
    q = _proj(jnp, x, W["attn/wq"], ad["wq"], scale, precision).reshape(b, s, h, pn + pr)
    q_nope, q_rope = q[..., :pn], _rope(jnp, q[..., pn:], inv, mrope)
    kv_a = _proj(jnp, x, W["attn/wkv_a"], ad["wkv_a"], scale, precision)
    c = _rms(jnp, jax, kv_a[..., :r], W["attn/kv_norm/scale"], a["rms_norm_eps"])
    k_rope = _rope(jnp, kv_a[..., r:], inv, mrope)                       # (b, s, pr)
    kv = _proj(jnp, c, W["attn/wkv_b"], ad["wkv_b"], scale, precision).reshape(
        b, s, h, pn + pv)
    k_nope, v = kv[..., :pn], kv[..., pn:]
    sm = (pn + pr) ** -0.5 * _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    scores = (jnp.einsum("bqhp,bkhp->bhqk", q_nope, k_nope, precision=precision)
              + jnp.einsum("bqhp,bkp->bhqk", q_rope, k_rope, precision=precision)) * sm
    causal = np.tril(np.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhv->bqhv", probs, v, precision=precision).reshape(b, s, h * pv)
    return _proj(jnp, out, W["attn/wo"], ad["wo"], scale, precision)


def _ffn(jax, jnp, x, wi, wg, wo, precision):
    hid = jax.nn.silu(jnp.dot(x, wg, precision=precision)) * jnp.dot(x, wi,
                                                                     precision=precision)
    return jnp.dot(hid, wo, precision=precision)


def gates(jax, jnp, model, x, router, precision):
    """(T, E) routing weight of each token on each expert: softmax over all
    experts, the top ``num_experts_per_tok`` kept (unnormalised unless
    ``norm_topk_prob``), zero elsewhere."""
    a = _arch(model)
    probs = jax.nn.softmax(jnp.dot(x, router, precision=precision), axis=-1)
    top, idx = jax.lax.top_k(probs, a["num_experts_per_tok"])
    if a["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
    return jnp.sum(onehot * top[..., None], axis=-2)


def _moe(jax, jnp, model, W, x, precision):
    """This chip's share of a MoE layer: its held experts, each over every
    token weighted by its gate (zero where not routed), plus the shared
    experts."""
    m = _dims(model)
    t = x.reshape(-1, m["d"])
    g = gates(jax, jnp, model, t, W["moe/router"], precision)
    y = _ffn(jax, jnp, t, W["moe/shared/wi"], W["moe/shared/wg"], W["moe/shared/wo"],
             precision)
    off = model["expert_offset"]

    def expert(y, e):
        wi, wg, wo, ge = e
        return y + ge[:, None] * _ffn(jax, jnp, t, wi, wg, wo, precision), None

    y, _ = jax.lax.scan(expert, y, (W["moe/wi"], W["moe/wg"], W["moe/wo"],
                                    g[:, off:off + m["held"]].T))
    return y.reshape(x.shape)


def _layer(jax, jnp, model, W, ad, x, precision):
    eps = _arch(model)["rms_norm_eps"]
    x = x + _mla(jax, jnp, model, W, ad, _rms(jnp, jax, x, W["ln1/scale"], eps), precision)
    h = _rms(jnp, jax, x, W["ln2/scale"], eps)
    if "mlp/wi" in W:
        return x + _ffn(jax, jnp, h, W["mlp/wi"], W["mlp/wg"], W["mlp/wo"], precision)
    return x + _moe(jax, jnp, model, W, h, precision)


def logits(adapters, batch, precision):
    """The dense layers one by one, then the stacked MoE layers in a scan."""
    import jax
    import jax.numpy as jnp

    model = _MODEL["model"]
    m, eps = _dims(model), _arch(model)["rms_norm_eps"]
    F = frozen(model, batch["tokens"])
    x = F["embed"][batch["tokens"]]
    for i in range(m["L0"]):
        W = {k[len(f"prefix/{i}/"):]: v for k, v in F.items() if k.startswith(f"prefix/{i}/")}
        x = _layer(jax, jnp, model, W, adapters["prefix"][i]["attn"], x, precision)
    stacked = {k[len("layers/"):]: v for k, v in F.items() if k.startswith("layers/")}

    def body(x, layer):
        W, ad = layer
        return _layer(jax, jnp, model, W, ad, x, precision), None

    x, _ = jax.lax.scan(body, x, (stacked, adapters["layers"]["attn"]))
    x = _rms(jnp, jax, x, F["final_norm/scale"], eps)
    return jnp.dot(x, F["lm_head"], precision=precision)


def loss(adapters, batch, precision):
    import jax
    import jax.numpy as jnp

    z = logits(adapters, batch, precision)[:, :-1]
    y = batch["tokens"][:, 1:]
    ll = jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1), y[..., None], axis=-1)
    return -jnp.mean(ll)


def accuracy(adapters, batch, precision):
    import jax.numpy as jnp

    z = logits(adapters, batch, precision)[:, :-1]
    return jnp.mean((jnp.argmax(z, -1) == batch["tokens"][:, 1:]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# model FLOPs and the experts' grouped product
# ---------------------------------------------------------------------------


def _held_pairs_per_token(model: dict) -> float:
    """Routed (token, expert) pairs per token that land on this chip's
    experts, reckoned at uniform routing: k * held / router experts."""
    a = _arch(model)
    return a["num_experts_per_tok"] * a["n_routed_experts"] / model["router_experts"]


def forward_macs(model: dict, seq_len: int) -> dict:
    """Multiply-accumulates of one token's forward pass, by part (attention
    scores and values over the full ``seq_len`` keys)."""
    m, rank = _dims(model), model["lora"]["rank"]
    d, h = m["d"], m["h"]
    proj = (d * h * (m["pn"] + m["pr"]) + d * (m["r"] + m["pr"])
            + m["r"] * h * (m["pn"] + m["pv"]) + h * m["pv"] * d)
    lora = rank * (d + h * (m["pn"] + m["pr"]) + d + m["r"] + m["pr"]
                   + m["r"] + h * (m["pn"] + m["pv"]) + h * m["pv"] + d)
    n_moe = m["L"] - m["L0"]
    expert = 3 * d * m["mf"]
    return {
        "attn": m["L"] * (proj + seq_len * h * (m["pn"] + m["pr"] + m["pv"])),
        "lora": m["L"] * lora,
        "mlp": m["L0"] * 3 * d * m["ff"],
        "router": n_moe * d * model["router_experts"],
        "experts": n_moe * _held_pairs_per_token(model) * expert,
        "shared": n_moe * m["shared"] * expert,
        "head": d * m["V"],
    }


def flops(cfg: dict, eval_every: int, iterations: int) -> dict:
    """Model FLOPs of one committed DAG-FL iteration, by part.

    Training backpropagates into the adapters through the frozen base: the
    forward, the input gradients (one forward's worth) and the adapters'
    weight gradients. Validation evaluates the ``alpha`` candidates and the
    trained adapters on ``val_size`` sequences; the agent's checks (alpha
    tips and the aggregate on the global set, ``iterations // eval_every``
    of them plus the genesis evaluation) are spread over the iterations.
    """
    seq = cfg["data"]["seq_len"]
    macs = forward_macs(cfg["model"], seq)
    fwd = 2 * sum(macs.values())
    train_tok = 2 * fwd + 2 * macs["lora"]
    dg, sim = cfg["dagfl"], cfg["sim"]
    checks = iterations // eval_every
    agent_seq = cfg["data"]["global_val"] * ((dg["alpha"] + 1) * checks + 1)
    return {
        "train": dg["beta"] * sim["steps_per_iter"] * sim["minibatch"] * seq * train_tok,
        "validate": (dg["alpha"] + 1) * sim["val_size"] * seq * fwd,
        "aggregate": 2 * dg["k"] * param_count(cfg["model"]),
        "agent": agent_seq * seq * fwd / iterations,
    }


def param_count(model: dict) -> int:
    """Adapter parameters: the DAG-FL payload."""
    shapes = base_leaves(model)
    per = sum(sum(shapes[f"layers/attn/{name}"][-2:]) for name in LORA_TARGETS)
    return _dims(model)["L"] * model["lora"]["rank"] * per


def base_param_count(model: dict) -> int:
    return sum(math.prod(s) for s in base_leaves(model).values())


def expert_pairs(rows: int, model: dict) -> float:
    """Held pairs of one grouped-product call whose operand holds ``rows``
    (token, expert) pairs, every pair of its tokens: tokens * k * held / E."""
    a = _arch(model)
    return rows / a["num_experts_per_tok"] * _held_pairs_per_token(model)


def expert_call_flops(rows: int, model: dict) -> float:
    """One projection over the held pairs: 2 * pairs * hidden * expert width
    (forward, or the input gradient in the backward pass)."""
    a = _arch(model)
    return 2.0 * expert_pairs(rows, model) * a["hidden_size"] * a["moe_intermediate_size"]


def expert_call_bytes(rows: int, model: dict) -> float:
    """The held experts' weights of the projection, read once, plus the
    pairs' activations read and written (float32)."""
    a = _arch(model)
    d, f = a["hidden_size"], a["moe_intermediate_size"]
    return 4.0 * (a["n_routed_experts"] * d * f + expert_pairs(rows, model) * (d + f))
