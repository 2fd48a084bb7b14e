"""Quickstart: build, fill and query a TPU-native Bloom filter.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import api
from repro.core.hashing import random_u64x2
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    print(f"registered engines: {api.backends()}")

    # Size for 100k items at 16 bits/key; sectorized layout, 256-bit blocks.
    # backend="auto" is a ranked registry query (jnp off-TPU, Pallas on TPU).
    f = api.filter_for_n_items(100_000, bits_per_key=16,
                               variant="sbf", block_bits=256)
    print(f"created {f.spec} ({f.nbytes/1024:.0f} KiB) on engine {f.backend!r}")

    keys = random_u64x2(100_000, seed=42)
    f = f.add(keys)                               # immutable: returns a new Filter
    hits = np.asarray(f.contains(keys))           # bulk lookup
    print(f"inserted 100k keys; all found: {hits.all()}")

    # probes come from the reserved keyspace — structurally disjoint from inserts
    print(f"measured FPR {f.measure_fpr():.2e}  (theory {f.fpr_theory(100_000):.2e})")
    print(f"fill {f.fill_fraction():.3f}, approx_count {f.approx_count():,.0f}")

    # the same interface runs the Pallas TPU kernels (interpret mode off-TPU):
    fk = api.make_filter("sbf", m_bits=f.spec.m_bits, k=f.spec.k,
                         block_bits=256,
                         backend="pallas-vmem").add(keys[:1000])
    print("pallas-vmem engine:", bool(np.asarray(fk.contains(keys[:1000])).all()))

    # filters are OR-mergeable across engines (here pallas-built -> jnp-built)
    merged = api.union(f, fk)
    print(f"union fill {merged.fill_fraction():.3f} on engine {merged.backend!r}")


if __name__ == "__main__":
    main()
