"""How late the load generator submitted each get: 99th percentile,
nearest rank, of submit time minus due time, over the window's gets (ms).
A late generator delays gets that the server never saw yet."""
from bench.lib.stats import nearest_rank


def read(ctx):
    lag = ctx.window.host.get("lag_s")
    if lag is None or not len(lag):
        return None
    return nearest_rank(lag, 99.0) * 1e3
