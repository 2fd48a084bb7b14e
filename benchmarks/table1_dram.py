"""Paper Table 1 analogue: bulk contains/add throughput, DRAM-resident filter.

Filter = 64 MiB (beyond LLC on this host = the paper's "exceeds L2" regime).
Sweeps block size B over the same words-per-block range as the paper
(s = B/S in {2,4,8,16,32}; our S=32 so B in {64..1024} bits) for the
vectorized execution engine, and reports GElem/s + fraction of the GUPS
speed-of-light (paper's headline metric).

The Θ layout dimension of Table 1 is swept structurally on the Pallas
kernels by benchmarks/layout_grid.py (interpret mode — schedule structure,
not wall-clock).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import Csv, keys_u64x2, time_fn
from repro.core import variants as V

M_BITS = 1 << 29          # 64 MiB filter
N_KEYS = 1 << 19
K = 16                    # paper keeps k=16


def run(csv: Csv, m_bits: int = M_BITS, tag: str = "dram", sol_gups=None):
    keys = keys_u64x2(N_KEYS, seed=1)
    for B in (64, 128, 256, 512, 1024):
        spec = V.FilterSpec("sbf", m_bits, K, block_bits=B)
        filt = V.add_scatter(spec, V.init(spec), keys[: 1 << 14])
        contains = jax.jit(lambda f, k, spec=spec: V.contains(spec, f, k))
        add = jax.jit(lambda f, k, spec=spec: V.add_scatter(spec, f, k))
        t_c = time_fn(contains, filt, keys)
        t_a = time_fn(add, filt, keys)
        g_c = N_KEYS / t_c / 1e9
        g_a = N_KEYS / t_a / 1e9
        frac_c = f" frac_sol={g_c / sol_gups['read']:.2f}" if sol_gups else ""
        frac_a = f" frac_sol={g_a / sol_gups['write']:.2f}" if sol_gups else ""
        csv.add(f"table1_{tag}/contains_B{B}", t_c * 1e6,
                f"GElem/s={g_c:.4f}{frac_c}")
        csv.add(f"table1_{tag}/add_B{B}", t_a * 1e6,
                f"GElem/s={g_a:.4f}{frac_a}")


if __name__ == "__main__":
    c = Csv()
    c.header()
    run(c)
