"""Share of the traced window in which no operation ran on the device, in %.

1 minus the union of the device-op intervals over the window
(``trace_reduce.reduce``), averaged over the chips used.
"""


def read(ctx):
    red = ctx["trace"]
    if red["busy_s"] <= 0 or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
