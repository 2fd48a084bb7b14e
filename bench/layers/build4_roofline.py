"""Share of the HBM roofline of the sharded build (%): the least time the
bytes the window's adds need (bench/lib/work.py, distinct blocks counted
over the whole filter) take at the peak bandwidth of all the chips used,
over the device busy time of the whole operation (every kind of device
operation, mean over the chips)."""
from bench.lib.layer_readers import roofline


def read(ctx):
    return roofline(ctx, "build")
