"""Device time of Algorithm 2's prepare programs per committed iteration, in ms.

Stages 1-3 (tip selection, validation, ``bank_average`` aggregation, local
training) run as the jitted ``prepare`` of ``fl/systems.py:_stage_jits``;
the trace names each execution after it.
"""

PROGRAMS = ("prepare",)


def read(ctx):
    secs = sum(v for k, v in ctx["trace"]["programs"].items() if k in PROGRAMS)
    if secs <= 0 or ctx["committed"] <= 0:
        return None
    return 1e3 * secs / ctx["committed"]
