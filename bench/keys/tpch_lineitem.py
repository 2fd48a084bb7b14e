"""The probe side of a TPC-H join on ``lineitem``: each batch is a run of
lineitem rows in table order, their ``l_orderkey`` (dbgen's sparse keys,
high word zero). Each order has a uniform number of rows in the
configuration's ``rows_per_order``; a batch starts at an order drawn
from the seed inside the configuration's ``orders``."""
from bench.lib import keys as K


def batches(run, role, spec, batch, count):
    cfg = run.cell.config
    return [K.tpch_lineitem(run.seed, j, batch, int(cfg["orders"]),
                            tuple(cfg["rows_per_order"]))
            for j in range(count)]
