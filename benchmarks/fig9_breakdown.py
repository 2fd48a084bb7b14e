"""Paper Figure 9 analogue: incremental optimization breakdown.

Cumulative steps, DRAM-resident filter (64 MiB, beyond LLC — the regime
where the paper's Fig. 9 gains are largest for the layout step):

    contains:
      1. cbf            classical filter, k scattered word reads per key
      2. sbf_unopt      blocked layout, per-key sequential probe loop,
                        k independent full-hash evaluations
      3. +multhash      one base hash + salt multiplies (paper §4.2)
      4. +vectorized    bulk lockstep engine (hash phase + gathered word
                        tests — the Θ vectorization analogue, §4.1/§4.3)
    add:
      5. cbf_add        k scattered RMWs per key (sequential, exact)
      6. sbf_add        one block RMW per key
      7. +partitioned   block-sorted insertion order (the ownership/
                        radix-partition locality win, §ours — on one core
                        the parallel-segment speedup shows as locality)

Speedups are vs the CBF baseline of the same operation.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import Csv, keys_u64x2, time_fn
from repro.core import hashing as H
from repro.core import variants as V

M_BITS = 1 << 29          # 64 MiB — DRAM-resident
N_KEYS = 1 << 17
N_ADD = 1 << 14           # sequential adds are slower; keep the bench quick
B = 256
K = 8


def _khash_masks(spec, keys):
    """Pattern generation with k independent xxh32 evaluations."""
    s = spec.s
    cols = [jnp.zeros((keys.shape[0],), jnp.uint32) for _ in range(s)]
    for i in range(spec.k):
        hi = H.xxh32_u64x2(keys, np.uint32(0xABCD0000 + i))
        cols[i % s] = cols[i % s] | (jnp.uint32(1) << (hi & jnp.uint32(31)))
    return jnp.stack(cols, axis=1)


def _contains_loop(spec, filt, keys, masks):
    """Per-key sequential probe (the unvectorized execution model)."""
    h2 = H.block_hash(keys)
    starts = (H.block_index(h2, spec.n_blocks) * jnp.uint32(spec.s)
              ).astype(jnp.int32)

    def body(i, acc):
        w = jax.lax.dynamic_slice(filt, (starts[i],), (spec.s,))
        m = masks[i]
        ok = jnp.all((w & m) == m)
        return acc.at[i].set(ok)

    return jax.lax.fori_loop(0, keys.shape[0], body,
                             jnp.zeros((keys.shape[0],), jnp.bool_))


def run(csv: Csv):
    keys = keys_u64x2(N_KEYS, seed=2)
    add_keys = keys_u64x2(N_ADD, seed=7)
    cbf = V.FilterSpec("cbf", M_BITS, K)
    sbf = V.FilterSpec("sbf", M_BITS, K, block_bits=B)
    filt_c = V.add_scatter(cbf, V.init(cbf), keys)
    filt_s = V.add_scatter(sbf, V.init(sbf), keys)

    # ---- contains chain ------------------------------------------------------
    t1 = time_fn(jax.jit(lambda f, k: V.contains(cbf, f, k)), filt_c, keys)

    def unopt(f, k, spec=sbf):
        return _contains_loop(spec, f, k, _khash_masks(spec, k))
    t2 = time_fn(jax.jit(unopt), filt_s, keys, warmup=1, reps=3)

    def multhash_loop(f, k, spec=sbf):
        h1 = H.xxh32_u64x2(k, H.SEED_PATTERN)
        return _contains_loop(spec, f, k, V.block_patterns(spec, h1))
    t3 = time_fn(jax.jit(multhash_loop), filt_s, keys, warmup=1, reps=3)

    t4 = time_fn(jax.jit(lambda f, k: V.contains(sbf, f, k)), filt_s, keys)
    # beyond-paper (§Perf B1): one row gather per key instead of s word gathers
    t4b = time_fn(jax.jit(lambda f, k: V.contains_rows(sbf, f, k)),
                  filt_s, keys)

    for name, t in [("1_cbf", t1), ("2_sbf_unopt", t2),
                    ("3_plus_multhash", t3), ("4_plus_vectorized", t4),
                    ("5_plus_rowgather", t4b)]:
        csv.add(f"fig9/contains/{name}", t * 1e6,
                f"GElem/s={N_KEYS/t/1e9:.4f} speedup_vs_cbf={t1/t:.2f}x")

    # ---- add chain -------------------------------------------------------------
    t5 = time_fn(jax.jit(lambda f, k: V.add_loop(cbf, f, k)),
                 V.init(cbf), add_keys, warmup=1, reps=3)
    t6 = time_fn(jax.jit(lambda f, k: V.add_loop(sbf, f, k)),
                 V.init(sbf), add_keys, warmup=1, reps=3)
    # block-sorted insertion order = partition locality
    h2 = H.block_hash(add_keys)
    order = jnp.argsort(H.block_index(h2, sbf.n_blocks))
    sorted_keys = add_keys[order]
    t7 = time_fn(jax.jit(lambda f, k: V.add_loop(sbf, f, k)),
                 V.init(sbf), sorted_keys, warmup=1, reps=3)
    # beyond-paper (§Perf B2): segmented-OR scan + single row gather/scatter
    t8 = time_fn(jax.jit(lambda f, k: V.add_rows(sbf, f, k)),
                 V.init(sbf), add_keys, warmup=1, reps=3)
    for name, t in [("6_cbf_add", t5), ("7_sbf_add", t6),
                    ("8_plus_partitioned", t7), ("9_plus_segscan_rows", t8)]:
        csv.add(f"fig9/add/{name}", t * 1e6,
                f"GElem/s={N_ADD/t/1e9:.4f} speedup_vs_cbf={t5/t:.2f}x",
                n_ops=N_ADD)

    # ---- probe-strategy column (kernel schedule, interpret mode) -----------
    # The Pallas kernels on a small VMEM-resident spec: per-key Θ loop
    # vs the whole-tile gather engine. Interpret-mode wall time tracks the
    # number of scheduled ops, so the ratio is the schedule-count win the
    # vectorized path must show (acceptance: gather wins or ties).
    from repro.core import tuning
    from repro.kernels import ops as kops
    from repro.kernels.sbf import default_layout
    sbf_v = V.FilterSpec("sbf", 1 << 17, K, block_bits=B)   # VMEM-resident
    pkeys = keys_u64x2(1 << 10, seed=11)
    filt_v = V.add_scatter(sbf_v, V.init(sbf_v), pkeys)
    for op in ("contains", "add"):
        lay = default_layout(sbf_v, op)
        times = {}
        for probe in ("loop", "gather"):
            if op == "contains":
                fn = lambda f, k, p=probe: kops.bloom_contains(
                    sbf_v, f, k, probe=p)
                t = time_fn(fn, filt_v, pkeys, warmup=1, reps=3)
            else:
                fn = lambda f, k, p=probe: kops.bloom_add(
                    sbf_v, f, k, probe=p)
                t = time_fn(fn, V.init(sbf_v), pkeys, warmup=1, reps=3)
            times[probe] = t
            steps = tuning.probe_schedule_steps(sbf_v, lay, op, 256, probe)
            csv.add(f"fig9/probe/{op}/{probe}", t * 1e6,
                    f"sched_steps={steps:.0f}", n_ops=pkeys.shape[0])
        csv.add(f"fig9/probe/{op}/winner", 0,
                f"best={'gather' if times['gather'] <= times['loop'] else 'loop'} "
                f"gather_speedup={times['loop']/times['gather']:.2f}x")


if __name__ == "__main__":
    c = Csv()
    c.header()
    run(c)
