"""Plain reference of one DAG-FL episode, replayed against the program's answers.

The timed path's answers are the transactions it committed: for each
iteration the union ledger holds its publisher, publish time, approved
parents, accuracy and model, and the program's replicas and bank-transport
state at the episode's end say what every node held. This module replays
the same episode from its inputs alone (the episode seed, the nodes'
batches, the overlay) and checks each answer as it is due:

* the schedule: Poisson starts, the node drawn for each, and the Eq. (5)-(7)
  iteration delay (Table I constants) that sets its completion time;
* the transport, simulated step for step in plain ``jax.numpy``: per-link
  anti-entropy deliveries at the link's cadence, the row merge (the larger
  ``(publish_time, publisher)`` identity wins, approver sets union,
  counters max), and the priced chunk transfer of the model bank (budget
  accrued since the link's last service, content-addressed dedup, striping
  over holders, rollover, drain completions), with each node's view gated
  on the payloads it holds;
* Algorithm 2 at every iteration's start, on the reference's own view of
  the node: tip selection (Gumbel top-``alpha`` of the fresh unapproved
  rows), validation of each candidate's model, the top-``k`` choice,
  ``k``-way averaging and the local SGD steps, and the trained model's
  validation accuracy;
* the stage-4 commit (approver credit, contribution counters, the row at
  the global sequence's slot, the bank's presence bits) and, at the end,
  every replica, the transport state and the union against the program's;
* the external agent's checks on the union (Algorithm 1).

Each iteration's candidate models are the program's own published models
(answers checked at their own iteration), and each commit is the program's
own transaction, so a wrong answer is counted once where it is produced and
does not send the replay down another trajectory.

The task (model, loss, accuracy, genesis weights) comes from the
configuration's ``tasks/<task>.py``. Nothing here imports the program.

``check`` returns the numbers compared, each with its limit (from the
configuration's ``correct_limits``): counts of ledger entries, parent
choices, accuracies and genesis weights that differ from the reference
(exact, limit 0), and ``update_gap``, the worst relative gap between the
norms of the program's and the reference's local update, leaf by leaf.
"""
from __future__ import annotations

import heapq
import time
from typing import Any, Dict, List, NamedTuple

import numpy as np

HIGHEST = "highest"


# ---------------------------------------------------------------------------
# the schedule (Sec. V.A arrivals, Table-I delays)
# ---------------------------------------------------------------------------


class Schedule(NamedTuple):
    t0: np.ndarray        # (n,) f64 start times
    node: np.ndarray      # (n,) node drawn at each start
    t1: np.ndarray        # (n,) f64 completion times
    seq: np.ndarray       # (n,) global sequence number of each commit (genesis is 0)


def schedule(dagfl: dict, iterations: int, seed: int) -> Schedule:
    rng = np.random.default_rng(seed)
    lo, hi = dagfl["cpu_freq_range"]
    freqs = np.random.default_rng(seed).uniform(lo, hi, dagfl["num_nodes"])
    train_cycles = dagfl["train_density"] * dagfl["minibatch_size_bits"] * dagfl["beta"]
    val_cycles = dagfl["validate_density"] * dagfl["valset_size_bits"] * dagfl["alpha"]
    tx = dagfl["tx_size_bits"] / dagfl["bandwidth"]
    t0 = np.cumsum(rng.exponential(1.0 / dagfl["arrival_rate"], iterations))
    node = np.empty(iterations, np.int64)
    t1 = np.empty(iterations)
    for i in range(iterations):
        node[i] = rng.integers(0, dagfl["num_nodes"])
        f = freqs[node[i]]
        t1[i] = t0[i] + (val_cycles / f + train_cycles / f + tx)
    order = sorted(range(iterations), key=lambda i: (t1[i], i))
    seq = np.empty(iterations, np.int64)
    seq[order] = np.arange(1, iterations + 1)
    return Schedule(t0, node, t1, seq)


# ---------------------------------------------------------------------------
# the ledger and the transport, for R replicas at once
# ---------------------------------------------------------------------------


class Ledger(NamedTuple):
    publisher: Any       # (R, cap) i32, -1 empty
    publish_time: Any    # (R, cap) f32
    approvals: Any       # (R, cap, k) i32
    approvers: Any       # (R, cap, M) bool, M = nodes + the agent
    accuracy: Any        # (R, cap) f32
    auth_tag: Any        # (R, cap) f32
    model_slot: Any      # (R, cap) i32
    count: Any           # (R,) i32
    published: Any       # (R, M) i32
    contrib0: Any        # (R, M) i32
    contrib1: Any        # (R, M) i32


ROW_FIELDS = ("publisher", "publish_time", "approvals", "accuracy", "auth_tag",
              "model_slot")
MAX_FIELDS = ("count", "published", "contrib0", "contrib1")


class Transport(NamedTuple):
    have: Any            # (R, S, C) bool physical chunk presence
    credit: Any          # (R, R) f32 rolled-over budget, receiver i <- sender j
    sent: Any            # (R, R) f32 bytes delivered per link
    last: Any            # (R, R) f32 last service instant per link
    deliver_t: Any       # (R, R) f32 next anti-entropy delivery per link
    drain_t: Any         # (R, R) f32 next chunk completion per link
    drain_v: Any         # (R, R) bool a chunk completion is armed
    key: Any             # PRNG key split once per delivery instant


def genesis_ledger(jnp, r: int, cap: int, k: int, m: int, acc0, tag0) -> Ledger:
    pub = jnp.full((cap,), -1, jnp.int32).at[0].set(m - 1)
    one = Ledger(
        publisher=pub,
        publish_time=jnp.zeros((cap,), jnp.float32),
        approvals=jnp.full((cap, k), -1, jnp.int32),
        approvers=jnp.zeros((cap, m), bool),
        accuracy=jnp.zeros((cap,), jnp.float32).at[0].set(acc0),
        auth_tag=jnp.zeros((cap,), jnp.float32).at[0].set(tag0),
        model_slot=jnp.full((cap,), -1, jnp.int32).at[0].set(0),
        count=jnp.ones((), jnp.int32),
        published=jnp.zeros((m,), jnp.int32).at[m - 1].set(1),
        contrib0=jnp.zeros((m,), jnp.int32),
        contrib1=jnp.zeros((m,), jnp.int32),
    )
    return Ledger(*(jnp.broadcast_to(x, (r,) + x.shape) for x in one))


def merge(jnp, led: Ledger, live) -> Ledger:
    """Receiver ``i`` folds in every sender ``j`` with ``live[i, j]``."""
    r = led.publisher.shape[0]
    cand = live | jnp.eye(r, dtype=bool)                             # (i, j)
    occ = led.publisher >= 0                                          # (j, row)
    ok = cand[:, :, None] & occ[None]                                 # (i, j, row)
    tmax = jnp.max(jnp.where(ok, led.publish_time[None], -jnp.inf), axis=1)
    at_t = ok & (led.publish_time[None] == tmax[:, None])
    pmax = jnp.max(jnp.where(at_t, led.publisher[None], -1), axis=1)  # (i, row)
    same = at_t & (led.publisher[None] == pmax[:, None])              # (i, j, row)
    won = pmax >= 0
    src = jnp.where(won, jnp.argmax(same, axis=1), jnp.arange(r)[:, None])
    rows = jnp.arange(led.publisher.shape[1])[None]
    out = {f: getattr(led, f)[src, rows] for f in ROW_FIELDS}
    out["approvers"] = jnp.einsum(
        "ijr,jrn->irn", same.astype(jnp.float32),
        led.approvers.astype(jnp.float32), precision=HIGHEST) > 0
    for f in MAX_FIELDS:
        x = getattr(led, f)
        c = cand.reshape(cand.shape + (1,) * (x.ndim - 1))
        out[f] = jnp.max(jnp.where(c, x[None], 0), axis=1)
    return Ledger(**out)


def available(jnp, have, cid):
    """(R, S, C) a node holds chunk (s, c) or one of equal content at offset c."""
    eq = jnp.all(cid[:, None] == cid[None, :], axis=-1)              # (p, s, C)
    return have | (jnp.einsum("ipc,psc->isc", have.astype(jnp.float32),
                              eq.astype(jnp.float32), precision=HIGHEST) > 0)


def referenced(jnp, led: Ledger, slots: int):
    """(R, S) slots referenced by an occupied row of each replica."""
    hit = (led.model_slot[:, :, None] == jnp.arange(slots)[None, None])
    return jnp.any(hit & (led.publisher[:, :, None] >= 0), axis=1)


def chunk_step(jnp, led: Ledger, tp: Transport, sat, svc, accrued, chunk_bytes):
    """Priced chunk movement on the serviced links; returns (tp, pending)."""
    r, s, c = sat.shape
    need = (referenced(jnp, led, s)[:, :, None] & ~sat).reshape(r, s * c)
    holds = sat.reshape(r, s * c)
    budget = tp.credit + jnp.where(svc, accrued, 0.0)
    afford = jnp.clip(jnp.floor(budget / chunk_bytes), 0,
                      np.iinfo(np.int32).max).astype(jnp.int32)
    can = svc[:, :, None] & need[:, None, :] & holds[None]           # (i, j, m)
    rank_holder = jnp.cumsum(can.astype(jnp.int32), axis=1) - 1
    holders = jnp.sum(can.astype(jnp.int32), axis=1)                  # (i, m)
    m_idx = jnp.arange(s * c, dtype=jnp.int32)[None]
    pick = jnp.where(holders > 0, m_idx % jnp.maximum(holders, 1), -1)
    assigned = can & (rank_holder == pick[:, None, :])
    order = jnp.cumsum(assigned.astype(jnp.int32), axis=2) - 1
    taken = assigned & (order < afford[:, :, None])
    spent = jnp.sum(taken.astype(jnp.int32), axis=2).astype(jnp.float32) * chunk_bytes
    pending = jnp.any(assigned & ~taken, axis=2)
    credit = jnp.where(pending, budget - spent, jnp.where(svc, 0.0, tp.credit))
    have = tp.have | jnp.any(taken, axis=1).reshape(r, s, c)
    return tp._replace(have=have, credit=credit, sent=tp.sent + spent), pending


class Wire(NamedTuple):
    """The overlay as the transport sees it (receiver i, sender j)."""

    adj: Any             # (R, R) bool
    interval: Any        # (R, R) f32 delivery cadence, inf off-link
    drop: Any            # (R, R) f32 loss probability
    bw_bytes: Any        # (R, R) f32 bytes per second
    chunk_bytes: Any     # () f32 transfer granule


def advance(jax, jnp, led: Ledger, tp: Transport, cid, wire: Wire, horizon,
            bank: bool, limit: int, fire_cap: int):
    """Every delivery and chunk completion at or before ``horizon``, in time
    order; simultaneous events form one batch (one merge round)."""

    def due(tp):
        nxt = jnp.min(jnp.where(wire.adj, tp.deliver_t, jnp.inf))
        if bank:
            nxt = jnp.minimum(nxt, jnp.min(jnp.where(tp.drain_v, tp.drain_t, jnp.inf)))
        return nxt

    def cond(carry):
        _, tp, _, done = carry
        return (due(tp) <= horizon) & (done < limit)

    def body(carry):
        led, tp, fires, done = carry
        t = due(tp)
        deliver = wire.adj & (tp.deliver_t == t)
        drain = (tp.drain_v & (tp.drain_t == t)) if bank else jnp.zeros_like(wire.adj)

        def with_round(op):
            led, tp, fires = op
            key, sub = jax.random.split(tp.key)
            live = deliver & (jax.random.uniform(sub, wire.adj.shape) >= wire.drop)
            led = merge(jnp, led, live)
            fires = fires + deliver.astype(jnp.int32)
            skip = (jnp.floor((horizon - tp.deliver_t) / wire.interval) + 1.0) * wire.interval
            step = jnp.where(fires >= fire_cap, skip, wire.interval)
            dt = jnp.where(deliver, tp.deliver_t + step, tp.deliver_t)
            return led, tp._replace(deliver_t=dt, key=key), fires, live

        def no_round(op):
            led, tp, fires = op
            return led, tp, fires, jnp.zeros_like(wire.adj)

        led, tp, fires, live = jax.lax.cond(jnp.any(deliver), with_round, no_round,
                                            (led, tp, fires))
        if bank:
            svc = live | drain
            accrued = jnp.where(svc, (t - tp.last) * wire.bw_bytes, 0.0)
            sat = available(jnp, tp.have, cid)
            tp, pending = chunk_step(jnp, led, tp, sat, svc, accrued, wire.chunk_bytes)
            rate = jnp.maximum(wire.bw_bytes, 1e-9)
            t_next = jnp.nextafter(t, jnp.float32(jnp.inf))
            e_next = jnp.maximum(t + (wire.chunk_bytes - tp.credit) / rate, t_next)
            e_retry = jnp.maximum(t + wire.chunk_bytes / rate, t_next)
            drain_t = jnp.where(svc, jnp.where(pending, e_next, jnp.inf), tp.drain_t)
            tp = tp._replace(last=jnp.where(deliver | drain, t, tp.last),
                             drain_v=jnp.where(svc, pending, tp.drain_v),
                             drain_t=jnp.where(drain & ~svc, e_retry, drain_t))
        return led, tp, fires, done + 1

    fires = jnp.zeros(wire.adj.shape, jnp.int32)
    led, tp, _, _ = jax.lax.while_loop(cond, body, (led, tp, fires, jnp.int32(0)))
    return led, tp


def commit(jnp, led: Ledger, tp: Transport, cid, cid_new, n, seq, t1, parents, acc, tag,
           bank: bool = True):
    """Stage 4 at node ``n``: the row at slot ``seq % cap`` of its replica,
    approver credit and contribution counters, and (with the bank gossiped)
    the presence bits of the slot's chunks."""
    cap = led.publisher.shape[1]
    row = seq % cap
    appr = led.approvers[n]
    c0, c1 = led.contrib0[n], led.contrib1[n]
    for p in range(parents.shape[0]):
        tx = parents[p]
        ok = tx >= 0
        idx = jnp.maximum(tx, 0)
        before = jnp.sum(appr[idx].astype(jnp.int32))
        newly = ok & ~appr[idx, n]
        appr = appr.at[idx, n].set(appr[idx, n] | ok)
        owner = led.publisher[n, idx]
        c0 = c0.at[jnp.maximum(owner, 0)].add(
            (newly & (before == 0) & (owner >= 0)).astype(jnp.int32))
        c1 = c1.at[jnp.maximum(owner, 0)].add(
            (newly & (before == 1) & (owner >= 0)).astype(jnp.int32))
    appr = appr.at[row].set(False)
    led = led._replace(
        publisher=led.publisher.at[n, row].set(n),
        publish_time=led.publish_time.at[n, row].set(t1),
        approvals=led.approvals.at[n, row].set(parents),
        approvers=led.approvers.at[n].set(appr),
        accuracy=led.accuracy.at[n, row].set(acc),
        auth_tag=led.auth_tag.at[n, row].set(tag),
        model_slot=led.model_slot.at[n, row].set(row),
        count=led.count.at[n].set(jnp.maximum(led.count[n], seq + 1)),
        published=led.published.at[n, n].add(1),
        contrib0=led.contrib0.at[n].set(c0),
        contrib1=led.contrib1.at[n].set(c1),
    )
    if not bank:
        return led, tp, cid
    have = tp.have.at[:, row].set(False).at[n, row].set(True)
    return led, tp._replace(have=have), cid.at[row].set(cid_new[row])


def view(jnp, led: Ledger, have, cid, n) -> Ledger:
    """Node ``n``'s usable view: rows whose payload it does not hold are empty."""
    one = Ledger(*(x[n] for x in led))
    sat = available(jnp, have[n][None], cid)[0]                      # (S, C)
    got = jnp.all(sat[jnp.maximum(one.model_slot, 0)], axis=-1)
    ok = (one.publisher < 0) | got
    return one._replace(publisher=jnp.where(ok, one.publisher, -1),
                        model_slot=jnp.where(ok, one.model_slot, -1))


def content_ids(jnp, bank_leaves: List[Any], chunks: int):
    """(S, C, 2) u32 content fingerprints of each slot's ``chunks`` equal byte
    ranges (leaves flattened in order, zero-padded), for dedup by content."""
    import jax

    s = bank_leaves[0].shape[0]
    flat = jnp.concatenate([x.reshape(s, -1).astype(jnp.float32) for x in bank_leaves],
                           axis=1)
    per = -(-flat.shape[1] // chunks)
    flat = jnp.pad(flat, ((0, 0), (0, per * chunks - flat.shape[1])))
    bits = jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(s, chunks, per)
    pos = jnp.arange(per, dtype=jnp.uint32)
    outs = []
    for a, b in ((0x9E3779B1, 0x85EBCA77), (0xC2B2AE3D, 0x27D4EB2F)):
        w = pos * jnp.uint32(a) + jnp.uint32(b)
        x = bits * (w | jnp.uint32(1))
        x = x ^ (x >> jnp.uint32(15))
        outs.append(jnp.sum(x, axis=-1, dtype=jnp.uint32))
    return jnp.stack(outs, axis=-1)


# ---------------------------------------------------------------------------
# Algorithm 2 and the agent: one jitted step per start, commit and check
# ---------------------------------------------------------------------------


def _norm(jnp, x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


class Steps:
    """The replay's three jitted programs for one cell.

    ``start``   advance to the start instant, the node's view, tip selection,
                validation of the candidates' models, the average of the
                program's chosen parents (or the fallback model), the local
                SGD steps, the update norms of the program's and the
                reference's models, and the program's model's accuracy;
    ``commit``  advance to the completion instant and commit;
    ``agent``   the union of the replicas and one Algorithm-1 check.
    """

    def __init__(self, cell, jax):
        import jax.numpy as jnp

        task, model, dg, tr = (cell.task, cell.config["model"], cell.config["dagfl"],
                               cell.traffic)
        lr, beta, alpha, k, tau = (model["learning_rate"], dg["beta"], dg["alpha"],
                                   dg["k"], dg["tau_max"])
        bank_on = bool(tr["bank_gossip"])
        limit, fire_cap = int(tr["max_events_per_advance"]), int(tr["max_ticks_per_advance"])

        def adv(led, tp, cid, wire, horizon):
            return advance(jax, jnp, led, tp, cid, wire, horizon, bank_on, limit, fire_cap)

        def select(led, key, now):
            fresh = (now - led.publish_time) <= tau
            tips = (led.publisher >= 0) & (led.approvers.sum(-1) == 0) & fresh
            u = jax.random.uniform(key, tips.shape, minval=1e-9, maxval=1.0)
            score = jnp.where(tips, -jnp.log(-jnp.log(u)), -jnp.inf)
            top, idx = jax.lax.top_k(score, alpha)
            return jnp.where(jnp.isfinite(top), idx, -1)

        def slot_model(bank, slot):
            return jax.tree_util.tree_map(lambda b: b[slot], bank)

        def validate(bank, slots, batch):
            accs = jax.vmap(lambda s: task.accuracy(slot_model(bank, jnp.maximum(s, 0)),
                                                    batch, HIGHEST))(slots)
            return jnp.where(slots >= 0, accs, -jnp.inf)

        def average(bank, slots):
            w = (slots >= 0).astype(jnp.float32)
            w = w / jnp.maximum(jnp.sum(w), 1.0)
            return jax.tree_util.tree_map(
                lambda b: jnp.tensordot(w, b[jnp.maximum(slots, 0)], axes=1,
                                        precision=HIGHEST), bank)

        def train(params, batch):
            def sgd(p, mb):
                g = jax.grad(task.loss)(p, mb, HIGHEST)
                return jax.tree_util.tree_map(lambda a, d: a - lr * d, p, g), None

            def epoch(_, p):
                return jax.lax.scan(sgd, p, batch)[0]

            return jax.lax.fori_loop(0, beta, epoch, params)

        def start(led, tp, cid, wire, bank, t0, n, key, batch, val, chosen, row):
            led, tp = adv(led, tp, cid, wire, t0)
            v = (view(jnp, led, tp.have, cid, n) if bank_on
                 else Ledger(*(x[n] for x in led)))
            cand = select(v, jax.random.split(key)[0], t0)
            slots = jnp.where(cand >= 0, v.model_slot[jnp.maximum(cand, 0)], -1)
            accs = validate(bank, slots, val)
            last = jnp.maximum(v.model_slot[(v.count - 1) % v.publisher.shape[0]], 0)
            fallback = jnp.full_like(chosen, -1).at[0].set(last)
            agg = average(bank, jnp.where(jnp.any(chosen >= 0), chosen, fallback))
            ref_new = train(agg, batch)
            prog = slot_model(bank, row)
            norms = [jnp.stack([_norm(jnp, a - b) for a, b in zip(
                jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(agg))])
                for new in (prog, ref_new)]
            acc = task.accuracy(prog, val, HIGHEST)
            return led, tp, (cand, accs, norms[0], norms[1], acc)

        def commit_at(led, tp, cid, wire, cid_new, t1, n, seq, parents, acc, tag):
            led, tp = adv(led, tp, cid, wire, t1)
            return commit(jnp, led, tp, cid, cid_new, n, seq, t1, parents, acc, tag, bank_on)

        def union(led):
            everyone = jnp.ones((led.publisher.shape[0],) * 2, bool)
            return Ledger(*(x[0] for x in merge(jnp, led, everyone)))

        def agent(led, bank, key, now, gval):
            u = union(led)
            slots = select(u, key, now)
            accs = validate(bank, slots, gval)
            top, pos = jax.lax.top_k(accs, k)
            chosen = jnp.where(jnp.isfinite(top), slots[pos], -1)
            return jnp.any(chosen >= 0), task.accuracy(average(bank, chosen), gval,
                                                       HIGHEST)

        self.start = jax.jit(start)
        self.commit = jax.jit(commit_at)
        self.agent = jax.jit(agent)
        self.union = jax.jit(union)


def _as_ledger(jnp, dags) -> Ledger:
    """The program's stacked ``DagState`` as a ``Ledger`` (fields by name)."""
    return Ledger(
        publisher=dags.publisher, publish_time=dags.publish_time,
        approvals=dags.approvals, approvers=dags.approvers, accuracy=dags.accuracy,
        auth_tag=dags.auth_tag, model_slot=dags.model_slot, count=dags.count,
        published=dags.published_per_node, contrib0=dags.contributing_m0,
        contrib1=dags.contributing_m1)


def _mismatches(a, b, fields) -> Dict[str, int]:
    out = {}
    for f in fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        bad = x.size if x.shape != y.shape else int(np.sum(x != y))
        if bad:
            out[f] = bad
    return out


EXACT = ("publisher", "publish_time", "approvals", "approvers", "model_slot",
         "count", "published", "contrib0", "contrib1")


class _Clock:
    """Seconds spent per phase of the replay (first calls compile)."""

    def __init__(self):
        self.secs: Dict[str, float] = {}
        self.t = time.perf_counter()

    def lap(self, name: str):
        now = time.perf_counter()
        self.secs[name] = self.secs.get(name, 0.0) + now - self.t
        self.t = now


def replay(cell, dep, overlay, seed_e: int, res, jax, log=None) -> Dict[str, Any]:
    """The readings of one episode: each number the check compares."""
    import jax.numpy as jnp

    clock = _Clock()
    cfg, tr = cell.config, cell.traffic
    dg, sim = cfg["dagfl"], cfg["sim"]
    n_it, nodes_n, cap, k = cell.iterations, dg["num_nodes"], dg["capacity"], dg["k"]
    if n_it + 1 > cap:
        raise ValueError("the replay needs every commit of the episode in the ledger")
    chunks = int(tr["chunks_per_slot"])
    sch = schedule(dg, n_it, seed_e)
    nodes = dep.nodes(seed_e)
    steps = Steps(cell, jax)

    union = _as_ledger(jnp, res.extras["dag"])
    reps = res.extras["replicas"]
    bank = reps.bank
    leaves = jax.tree_util.tree_leaves(bank)
    u_appr = np.asarray(union.approvals)
    u_acc = np.asarray(union.accuracy)
    u_tag = np.asarray(union.auth_tag)

    # genesis: the reference's own weights from the episode seed
    ref0 = cell.task.init(jax.random.PRNGKey(seed_e), cfg["model"])
    prog0 = jax.tree_util.tree_map(lambda b: b[0], bank)
    genesis_mismatch = sum(
        int(np.sum(np.asarray(a) != np.asarray(b)))
        for a, b in zip(jax.tree_util.tree_leaves(prog0), jax.tree_util.tree_leaves(ref0)))
    gval = {kk: jnp.asarray(v) for kk, v in dep.gval.items()}
    acc0 = cell.task.accuracy(ref0, gval, HIGHEST)

    # the overlay and the transport's initial state
    adj = np.asarray(overlay.adjacency, bool)
    lat = np.where(np.isfinite(overlay.latency), overlay.latency, 0.0)
    interval = np.where(adj, np.where(lat > 0, lat, float(tr["sync_period_s"])),
                        np.inf).astype(np.float32)
    slot_bytes = float(sum(x.dtype.itemsize * x[0].size for x in leaves))
    wire = Wire(adj=jnp.asarray(adj), interval=jnp.asarray(interval),
                drop=jnp.asarray(overlay.drop, jnp.float32),
                bw_bytes=jnp.asarray(np.asarray(overlay.bandwidth) / 8.0, jnp.float32),
                chunk_bytes=jnp.float32(max(slot_bytes / chunks, 1e-9)))
    if tr["bank_gossip"]:
        cid_final = content_ids(jnp, leaves, chunks)
        zero = content_ids(jnp, [jnp.zeros((1,) + x.shape[1:], x.dtype) for x in leaves],
                           chunks)[0]
        cid = jnp.where((jnp.arange(cap) == 0)[:, None, None], cid_final, zero[None])
    else:
        cid_final = cid = jnp.zeros((cap, chunks, 2), jnp.uint32)
    led = genesis_ledger(jnp, nodes_n, cap, k, nodes_n + 1, u_acc[0], u_tag[0])
    full = (nodes_n, nodes_n)
    tp = Transport(
        have=jnp.ones((nodes_n, cap, chunks), bool), credit=jnp.zeros(full, jnp.float32),
        sent=jnp.zeros(full, jnp.float32), last=jnp.zeros(full, jnp.float32),
        deliver_t=jnp.asarray(interval), drain_t=jnp.full(full, jnp.inf, jnp.float32),
        drain_v=jnp.zeros(full, bool), key=jax.random.PRNGKey(seed_e))
    clock.lap("set-up")

    starts, checks = [], []
    done = 0

    def do_commit(j):
        nonlocal led, tp, cid, done
        row = int(sch.seq[j] % cap)
        led, tp, cid = steps.commit(
            led, tp, cid, wire, cid_final, jnp.float32(sch.t1[j]), int(sch.node[j]),
            int(sch.seq[j]), jnp.asarray(u_appr[row]), jnp.float32(u_acc[row]),
            jnp.float32(u_tag[row]))
        done += 1

    def do_agent(t1):
        checks.append((done, float(t1), steps.agent(
            led, bank, jax.random.PRNGKey(done), jnp.float32(float(t1) + 1e-3), gval)))

    heap: List = []
    for i in range(n_it):
        while heap and heap[0][0] <= sch.t0[i]:
            t1, j = heapq.heappop(heap)
            do_commit(j)
            if done % cell.eval_every == 0:
                do_agent(t1)
        n = int(sch.node[i])
        batch = nodes[n].epoch(sim["steps_per_iter"], sim["minibatch"])
        val = nodes[n].val_batch(sim["val_size"])
        row = int(sch.seq[i] % cap)
        led, tp, out = steps.start(
            led, tp, cid, wire, bank, jnp.float32(sch.t0[i]), n,
            jax.random.PRNGKey(seed_e * 100003 + i),
            {kk: jnp.asarray(v) for kk, v in batch.items()},
            {kk: jnp.asarray(v) for kk, v in val.items()},
            jnp.asarray(u_appr[row]), row)
        starts.append((row, out))
        heapq.heappush(heap, (sch.t1[i], i))
        if i == 0:
            jax.block_until_ready(out)
            clock.lap("first start (compiles)")
    t1 = None
    while heap:
        t1, j = heapq.heappop(heap)
        do_commit(j)
    do_agent(t1)
    jax.block_until_ready((led, tp))
    clock.lap("the other starts and commits")

    # the answers, iteration by iteration
    choice_mismatch, accuracy_mismatch, update_gap = 0, 0, 0.0
    accuracy_mismatch += int(np.float32(u_acc[0]) != np.float32(acc0))
    for row, out in starts:
        cand, accs, dp, dr, acc = (np.asarray(x) for x in out)
        # top-k of the validated candidates, ties to the earlier candidate
        order = sorted(range(len(cand)), key=lambda c: (-accs[c], c))[:k]
        want = [int(cand[c]) if np.isfinite(accs[c]) else -1 for c in order]
        choice_mismatch += int(list(u_appr[row]) != want)
        accuracy_mismatch += int(np.float32(u_acc[row]) != np.float32(acc))
        dp, dr = dp.astype(np.float64), dr.astype(np.float64)
        floor = np.median(dr)
        keep = dr >= 1e-3 * floor
        gaps = np.abs(dp - dr)[keep] / np.maximum(dr[keep], floor)
        update_gap = max(update_gap, float(gaps.max()))

    # the agent's curve (best accuracy so far at each check)
    curve, best = [], 0.0
    for d, t1, (ok, acc) in checks:
        if bool(ok):
            best = max(best, float(acc))
        curve.append((d, t1, best))
    prog_curve = list(zip(np.asarray(res.iters).tolist(), np.asarray(res.times).tolist(),
                          np.asarray(res.accs).tolist()))
    mism = _mismatches(led, _as_ledger(jnp, reps.dags), EXACT)
    mism.update({"union." + kk: vv for kk, vv in
                 _mismatches(steps.union(led), union, EXACT).items()})
    if tr["bank_gossip"]:
        bs = reps.bank_state
        for f in ("have", "credit", "sent"):
            bad = int(np.sum(np.asarray(getattr(bs, f)) != np.asarray(getattr(tp, f))))
            if bad:
                mism["bank." + f] = bad
    if len(curve) != len(prog_curve):
        mism["agent.checks"] = abs(len(curve) - len(prog_curve))
    for (d0, t0_, a0), (d1, t1_, a1) in zip(prog_curve, curve):
        if d0 != d1 or t0_ != t1_:
            mism["agent.schedule"] = mism.get("agent.schedule", 0) + 1
        accuracy_mismatch += int(np.float32(a0) != np.float32(a1))
    clock.lap("comparison")
    if log:
        log("replay phases: " + ", ".join(f"{k} {v:.1f} s" for k, v in clock.secs.items()))
    return {
        "ledger_mismatch": int(sum(mism.values())),
        "ledger_mismatch_fields": mism,
        "choice_mismatch": choice_mismatch,
        "accuracy_mismatch": accuracy_mismatch,
        "genesis_mismatch": genesis_mismatch,
        "update_gap": update_gap,
    }


def compare(cell, readings: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Each compared number of ``readings`` beside its limit."""
    limits = cell.config["correct_limits"]
    return {name: {"value": readings[name], "limit": limits[name]}
            for name in limits}


def is_correct(compared: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def check(cell, dep, overlay, seed_e: int, res, jax, log=None) -> Dict[str, Dict[str, float]]:
    """Each compared number of the replayed episode beside its limit."""
    return compare(cell, replay(cell, dep, overlay, seed_e, res, jax, log))
