"""Host time of one service flush (ms): the mean over the window's
``service.flush`` spans of the span minus its ``service.flush.sync``
child, the part in which the host pads, routes, launches and takes
results while the device waits or idles."""


def read(ctx):
    flushes = {e["span"]: e for e in ctx.spans if e["name"] == "service.flush"}
    if not flushes:
        return None
    sync = {}
    for e in ctx.spans:
        if e["name"] == "service.flush.sync" and e["parent"] in flushes:
            sync[e["parent"]] = sync.get(e["parent"], 0.0) + e["dur"]
    own = [f["dur"] - sync.get(i, 0.0) for i, f in flushes.items()]
    return 1e3 * sum(own) / len(own)
