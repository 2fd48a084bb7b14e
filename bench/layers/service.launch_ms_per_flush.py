"""Host time to launch one service flush (ms): the mean over the window's
``service.flush.launch`` spans, in which the Filter API routes the
flush's keys and dispatches its device call, without waiting for it."""


def read(ctx):
    launches = [e["dur"] for e in ctx.spans
                if e["name"] == "service.flush.launch"]
    if not launches:
        return None
    return 1e3 * sum(launches) / len(launches)
