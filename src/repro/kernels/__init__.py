"""Pallas TPU kernels for the performance hot-spots of the paper.

sbf.py — blocked-variant kernels (BBF/RBBF/SBF/CSBF share the skeleton;
          the variant-specific pattern generation is trace-time dispatched):
          Θ layouts, VMEM-/HBM-resident regimes, partitioned add.
cbf.py — classical-filter baseline kernels.
ops.py — jit'd dispatch (regime + layout selection, padding).
ref.py — pure-jnp oracles; every kernel is verified bit-exactly against them.
"""
from repro.kernels.sbf import Layout, default_layout
from repro.kernels import ops, ref
