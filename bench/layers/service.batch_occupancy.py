"""Share of the service's padded flush slots that carried a request over
the window (%): the counters ``service.flushed_ops`` over
``service.flushes`` times the flush shape."""


def read(ctx):
    c = ctx.counters
    flushes = c.get("flushes", 0)
    max_batch = ctx.window.host.get("max_batch")
    if not flushes or not max_batch:
        return None
    return 100.0 * c["flushed_ops"] / (flushes * max_batch)
