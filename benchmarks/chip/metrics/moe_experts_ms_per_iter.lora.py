"""Device time of the held experts' grouped products per committed iteration, in ms.

The ``ragged-dot`` products that ``moe_experts_roofline`` reads (forward and
backward, in every program of the traced episode: the prepares and the
agent's checks), summed and divided by the iterations committed in it.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from moe_experts_roofline import expert_calls  # noqa: E402


def read(ctx):
    calls = expert_calls(ctx)
    if not calls or ctx["committed"] <= 0:
        return None
    return 1e3 * sum(secs for _, secs, _ in calls) / ctx["committed"]
