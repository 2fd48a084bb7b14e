"""Host time of the admission refresh per service flush (ms): the
window's ``admission.refresh`` spans, which copy the bank to the host
and recount its fill every ``health_every`` flushes, summed over the
number of ``service.flush`` spans. None where the program spans no
refresh."""


def read(ctx):
    refresh = [e["dur"] for e in ctx.spans
               if e["name"] == "admission.refresh"]
    flushes = sum(e["name"] == "service.flush" for e in ctx.spans)
    if not refresh or not flushes:
        return None
    return 1e3 * sum(refresh) / flushes
