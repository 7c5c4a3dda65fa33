"""Model FLOPs of the committed iterations per second, as a share of the peak, in %.

The FLOPs of one iteration come from the task's shapes (``tasks/<task>.py``
``flops``: local training forward and backward, validation of the ``alpha``
tips and of the trained model, aggregation, and the agent's checks spread
over the episode), times the iterations committed in the window's episodes
that ran without the profiler (those after the traced one), over their
host-clock length and the chip's peak (``peaks.json``).
"""


def read(ctx):
    if ctx["untraced_committed"] <= 0 or ctx["untraced_wall_s"] <= 0:
        return None
    per_iter = sum(ctx["flops"].values())
    rate = per_iter * ctx["untraced_committed"] / ctx["untraced_wall_s"]
    return 100.0 * rate / ctx["peaks"]["flops_per_s"]
