"""Device time of the transport programs per committed iteration, in ms.

The event engine's advance loop and converge (``net/events.py``,
``net/gossip.py``), the bank transport's commit accounting and the
availability-gated view read (``net/bank.py``), and the union fold and
replica reads the driver makes (``net/replica.py``), by their jit names.
"""

PROGRAMS = ("advance", "converge", "commit_chunks", "gate_view", "merge_all",
            "missing_chunks", "missing_vs_union", "replicas_synced")


def read(ctx):
    secs = sum(v for k, v in ctx["trace"]["programs"].items() if k in PROGRAMS)
    if secs <= 0 or ctx["committed"] <= 0:
        return None
    return 1e3 * secs / ctx["committed"]
