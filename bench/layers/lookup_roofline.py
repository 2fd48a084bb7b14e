"""Share of the HBM roofline (%): the least time the bytes the window's
operations need (bench/lib/work.py) take at the chips' peak bandwidth,
over the device busy time of the whole operation (every kind of device
operation, mean over the chips used)."""
from bench.lib.layer_readers import roofline


def read(ctx):
    return roofline(ctx, "lookup")
