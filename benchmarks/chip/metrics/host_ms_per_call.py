"""Host time per facade call: each traced ``lasana.simulate`` span less
the device-busy time inside it (stimulus transfer, dispatch, record fetch
and build), averaged over the window's calls, in milliseconds."""


def read(ctx):
    calls = ctx["trace"]["calls"]
    if not calls:
        return None
    return sum(span - busy for span, busy in calls) / len(calls) * 1e3
