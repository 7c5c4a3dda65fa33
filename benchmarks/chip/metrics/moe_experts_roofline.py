"""Share of the held experts' grouped products' roofline they reach, in %.

The least time the chip could take over the calls of the traced window,
divided by their summed device time. The calls are the ``ragged_dot``
products of ``models/moe.py`` (one per expert projection, forward and the
input gradient of training), which XLA names ``ragged-dot-<mode>.<n>``
(the ``ragged-dot-metadata`` op that sizes their groups is left out). Per
call the need is the larger of bytes over the HBM bandwidth and operations
over the peak (``peaks.json``): the held experts' weights of the projection
read once plus the pairs' activations, and 2 * pairs * hidden * expert
width, with the pairs reckoned as tokens * k * held / E from the call's
row count (``tasks/<task>.py``: ``expert_call_bytes``,
``expert_call_flops``).
"""
import re

_SHAPE = re.compile(r"= f32\[(\d+),\d+\]")


def expert_calls(ctx):
    """[(rows, seconds, calls)] of the grouped products in the trace."""
    out = []
    for (_, op), (secs, calls) in ctx["trace"]["ops_by_program"].items():
        if not op.startswith("ragged-dot") or op.startswith("ragged-dot-metadata"):
            continue
        m = _SHAPE.search(op)
        if m is None:
            return None          # a call whose shape this reader does not know
        out.append((int(m.group(1)), secs, calls))
    return out


def read(ctx):
    calls = expert_calls(ctx)
    if not calls:
        return None
    task, model, peaks = ctx["cell"].task, ctx["cell"].config["model"], ctx["peaks"]
    spent = sum(secs for _, secs, _ in calls)
    need = sum(n * max(task.expert_call_bytes(rows, model) / peaks["hbm_bytes_per_s"],
                       task.expert_call_flops(rows, model) / peaks["flops_per_s"])
               for rows, _, n in calls)
    if spent <= 0:
        return None
    return 100.0 * need / spent
