"""Transport dispatches per committed iteration, in calls/iter.

``extras["device_calls"]`` of the traced episode: the count of
``net/gossip.py:GossipNetwork._dispatch``, the one funnel every transport
dispatch goes through, divided by the iterations committed in it.
"""


def read(ctx):
    if ctx["committed"] <= 0:
        return None
    return ctx["device_calls"] / ctx["committed"]
