"""The build side of a TPC-H join on ``orders``: the order keys of the
orders that pass the query's filters, in table order (dbgen's sparse
keys, high word zero).

The configuration gives ``orders`` (the table's rows: 1.5 M a scale
factor), ``qualify_p`` (the share of orders that pass, drawn per order)
and ``build_keys`` (how many of the qualifying orders are kept). The
batches split those keys in table order, so ``batch * count`` must be
``build_keys``."""
from bench.lib import keys as K


def batches(run, role, spec, batch, count):
    cfg = run.cell.config
    n_build = int(cfg["build_keys"])
    if n_build != batch * count:
        raise ValueError(f"{count} batches of {batch} keys do not make the "
                         f"{n_build} build keys")
    build = K.tpch_build(run.seed, n_build, int(cfg["orders"]),
                         float(cfg["qualify_p"]))
    return [build[i * batch:(i + 1) * batch] for i in range(count)]
