"""Mean queue wait of a served request (ms): over the window's
``service.flush`` spans, the summed wait of the requests each took, from
enqueue to the flush's start on the service clock (``wait_s``), over the
requests they took (``take``). None where the program's flush spans
carry no wait."""


def read(ctx):
    flushes = [e for e in ctx.spans
               if e["name"] == "service.flush" and "wait_s" in e]
    taken = sum(e["take"] for e in flushes)
    if not taken:
        return None
    return 1e3 * sum(e["wait_s"] for e in flushes) / taken
