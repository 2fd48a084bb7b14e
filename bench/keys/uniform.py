"""Uniform 64-bit keys (hashes of records or paragraphs), one stream per
role, so that the build side and the probe side of a cell never share a
key by construction."""
from bench.lib import keys as K


def batches(run, role, spec, batch, count):
    stream = f"uniform.{role}"
    return [K.uniform_keys(run.seed, stream, i, batch) for i in range(count)]
