"""Wall-clock spans, the device->host read funnel and the prepare/commit
named scopes of the DAG-FL gossip driver.

One tiny ``run_dagfl_gossip`` episode (events engine) runs under
``jax.profiler``; the host spans are read back from the ``.xplane.pb`` the
profiler writes, the way a device profile would show them.
"""
import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Controller
from repro.fl import systems
from repro.fl.experiments import default_dagfl_config, make_cnn_setup
from repro.fl.systems import SimConfig, run_dagfl_gossip
from repro.net import topology as topo
from repro.net.gossip import GossipConfig

N, ITERS, EVAL_EVERY = 6, 12, 4

# span -> the spans that may enclose it directly (None: top level)
PARENTS = {
    "repro.fl.start": {None},
    "repro.fl.commit": {None},
    "repro.fl.check": {None},
    "repro.fl.inputs": {"repro.fl.start"},
    "repro.fl.prepare": {"repro.fl.start"},
    "repro.net.read": {"repro.fl.start", "repro.fl.commit"},
    "repro.net.advance_events": {"repro.fl.start", "repro.fl.commit"},
    "repro.net.wait.advance": {"repro.fl.start", "repro.fl.commit"},
    "repro.net.wait.approvals": {"repro.fl.commit"},
    "repro.net.wait.check": {"repro.fl.check"},
    "repro.net.wait.observe": {"repro.fl.check"},
    "repro.net.wait.snapshot": {"repro.fl.commit"},
}


def _episode():
    task, nodes, gval, _ = make_cnn_setup(num_nodes=N, seed=0)
    return run_dagfl_gossip(
        task, nodes, default_dagfl_config(num_nodes=N),
        SimConfig(iterations=ITERS, eval_every=EVAL_EVERY, seed=0), gval,
        topology=topo.full(N, link_latency=0.5),
        gossip=GossipConfig(sync_period=1.0, seed=0), engine="events",
    )


def _host_spans(path):
    """(name, start_ns, end_ns, stats) of every ``repro.*`` event on the
    host's Python thread."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for e in line.events:
                if e.name.startswith("repro."):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns), dict(e.stats)))
    return out


def _parents(spans):
    """The innermost span enclosing each span (None at top level)."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1], -spans[i][2]))
    stack, parent = [], {}
    for i in order:
        _, s, e, _ = spans[i]
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        parent[i] = spans[stack[-1]][0] if stack else None
        stack.append(i)
    return parent


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    untraced = _episode()           # compiles every program outside the trace
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        res = _episode()
    (path,) = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    return untraced, res, _host_spans(path)


def test_every_span_appears_with_its_parent(traced):
    _, _, spans = traced
    names = collections.Counter(name for name, *_ in spans)
    assert set(PARENTS) <= set(names), set(PARENTS) - set(names)
    parent = _parents(spans)
    for i, (name, *_rest) in enumerate(spans):
        if name in PARENTS:
            assert parent[i] in PARENTS[name], (name, parent[i])


def test_each_iteration_has_one_start_and_one_commit(traced):
    _, res, spans = traced
    committed = int(np.sum(res.extras["published"][:-1]))
    assert committed == ITERS
    starts = collections.Counter(st["iteration"] for n, _, _, st in spans
                                 if n == "repro.fl.start")
    commits = collections.Counter(st["iteration"] for n, _, _, st in spans
                                  if n == "repro.fl.commit")
    assert starts == commits == collections.Counter(range(ITERS))
    first = {st["iteration"]: s for n, s, _, st in spans if n == "repro.fl.start"}
    for n, s, _, st in spans:
        if n == "repro.fl.commit":
            assert s > first[st["iteration"]]


def test_host_syncs_match_the_loop_and_the_wait_spans(traced):
    _, res, spans = traced
    ex = res.extras
    waits = collections.Counter(n[len("repro.net.wait."):] for n, *_ in spans
                                if n.startswith("repro.net.wait."))
    assert ex["host_syncs"] == sum(ex["sync_counts"].values()) == sum(waits.values())
    assert ex["sync_counts"] == dict(waits)
    # one read per event advance (a start and a commit each advance), one
    # per commit (its approvals), per agent check one or two reads by the
    # agent plus the divergence read, and the mid-run counter snapshot
    checks = ITERS // EVAL_EVERY
    assert ex["sync_counts"]["advance"] == ex["dispatch_counts"]["advance_events"] == 2 * ITERS
    assert ex["sync_counts"]["approvals"] == ITERS
    assert ex["sync_counts"]["observe"] == checks
    assert checks <= ex["sync_counts"]["check"] <= 2 * checks
    assert ex["sync_counts"]["snapshot"] == 1
    assert set(ex["sync_counts"]) == {"advance", "approvals", "observe", "check", "snapshot"}


def test_read_spans_match_read_calls(traced):
    """One ``repro.net.read`` span per compiled replica read, two per
    committed iteration (the start's view, the commit's replica)."""
    _, res, spans = traced
    reads = sum(1 for n, *_ in spans if n == "repro.net.read")
    assert reads == res.extras["read_calls"] == 2 * ITERS


def test_profiler_leaves_the_trajectory_unchanged(traced):
    untraced, res, _ = traced
    np.testing.assert_array_equal(untraced.accs, res.accs)
    assert untraced.extras["host_syncs"] == res.extras["host_syncs"]
    for a, b in zip(jax.tree_util.tree_leaves(untraced.extras["replicas"]),
                    jax.tree_util.tree_leaves(res.extras["replicas"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prepare_and_commit_carry_their_named_scopes():
    task, nodes, gval, _ = make_cnn_setup(num_nodes=N, seed=0)
    dcfg = default_dagfl_config(num_nodes=N)
    sim = SimConfig()
    prep, _, _ = systems._stage_jits(dcfg, task, False)
    state = Controller(dcfg, task.eval_fn).genesis(
        task.init(jax.random.PRNGKey(0)), systems._jb(gval))
    args = (state.dag, state.bank, jnp.float32(0.0), jax.random.PRNGKey(1),
            systems._jb(nodes[0].epoch(sim.steps_per_iter, sim.minibatch)),
            systems._jb(nodes[0].val_batch(sim.val_size)),
            jnp.zeros((N + 1,), jnp.float32))
    # the jitted prepare takes the task's frozen state (none here) first
    text = prep.func.lower(*prep.args, *args).as_text(debug_info=True)
    for scope in ("select", "validate", "aggregate", "train"):
        assert f"dagfl/{scope}/" in text, scope
    prepared = jax.eval_shape(prep, *args)
    commit = jax.jit(systems._gossip_commit).lower(
        state.dag, state.bank, jnp.int32(0), jnp.float32(1.0), prepared, jnp.int32(1))
    assert "dagfl/commit/" in commit.as_text(debug_info=True)
