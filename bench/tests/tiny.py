"""Cells of the benchmark cut to sizes the CPU runs in interpret mode."""
from __future__ import annotations

import copy
import io

import jax

from bench.lib import catalog

# Per configuration: the sizes replaced in a tiny copy of each cell.
TINY_CONFIG = {
    "tpch_q3_prefilter": {"m_bits": 1 << 16, "build_keys": 2048,
                          "orders": 30000, "backend": "pallas-hbm"},
    "ycsb_c_sst_filters": {"m_bits": 1 << 14, "members": 4,
                           "record_count": 4096, "backend": "pallas-vmem",
                           "service": {"max_batch": 256}},
}
TINY_TRAFFIC = {
    "lineitem_probe": {"morsel": 512, "probe_batch": 1024, "pool": 2},
    "join_build_morsels": {"batch": 512},
    "ycsb_c_open_loop": {"arrivals": {"process": "poisson",
                                      "rate_per_s": 200}},
}


def tiny_cell(name: str) -> catalog.Cell:
    cell = copy.deepcopy(catalog.cell(name))
    cell.config.update(copy.deepcopy(TINY_CONFIG[cell.config_name]))
    cell.traffic.update(copy.deepcopy(TINY_TRAFFIC[cell.traffic_name]))
    return cell


def run_tiny(name: str, seed: int = 1, seconds: float = 0.5,
             trace: bool = False, devices=None, program=None):
    """Run a tiny copy of cell ``name`` on the CPU, past the harness's look
    for a chip. Returns (result object, standard output, standard error)."""
    import time
    from bench.lib.harness import run_cell
    cell = tiny_cell(name)
    devices = devices or jax.devices()[:cell.chips]
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(cell, seed, seconds, trace, devices,
                      time.perf_counter(), out=out, err=err, program=program)
    return result, out.getvalue(), err.getvalue()
