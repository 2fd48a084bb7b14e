"""First-principles per-bulk-op resource counts for every filter engine.

For one bulk call of ``n_keys`` keys the model counts, per configuration
(spec x op x regime x layout x tile x bank; cuckoo and quotient also x
their ``coop`` schedule). The Bloom and counting families run one
schedule per regime (``repro.kernels.ops``), and that is what is costed:

* ``bytes_hbm`` — traffic that must cross the slow tier: the key stream
  in, the result stream out, the one-time filter stream-in (VMEM regime)
  or the per-row block DMAs (HBM regime; the add reads and writes each
  unique row of a tile once);
* ``bytes_res`` — fast-tier traffic: every filter word the probe schedule
  touches while the table is resident (the cuckoo and quotient early
  exits touch an *expected* fraction);
* ``flops``    — u32 ALU work: hashing (one fused double-hash), pattern
  generation, compares/RMWs;
* ``launches`` — dispatched programs (all engines launch ONE pallas_call
  per bulk op — that is the point of the design);
* ``vops``     — schedule vector-ops: whole-tile ops issued across all
  grid steps. Off-TPU each costs a Python-dispatch quantum
  (``Calibration.step_us``), which is why interpret-mode ratios track
  schedule *structure*; on TPU the same term models issue overhead.

``predict_us`` converts counts to expected wall time (roofline max of the
three resource terms + launch + schedule overhead); ``ceiling_us`` drops
the schedule term — the *practical speed of light*: the time the op could
not beat on this host even with a perfect schedule.

The expectation constants (alternate-bucket fraction, cluster-scan
fraction) describe a mixed ~50% member workload — they steer the cuckoo
and quotient ``coop`` choice (:func:`choose_coop`), and the drift monitor
(``repro.telemetry.drift``) alerts only outside a loose factor. None of
the constants is calibrated on the chip.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

from repro.core.variants import FilterSpec
from repro.kernels.sbf import HBM_PROBE_DEPTH
from repro.perfmodel.calibrate import Calibration, get_calibration

WORD = 4                       # u32 filter word, bytes
KEY_BYTES = 8                  # u64 key as 2x u32 lanes
OUT_BYTES = 1                  # bool membership result

# Hash flops per key: two 8-byte xxh32 streams from one fused mix that
# shares the seed-independent lane*PRIME3 products
# (``hashing.xxh32_u64x2_pair``).
HASH_FLOPS = 20.0

# Pattern generation + test flops per touched word (index arith, bit
# select, mask OR / compare).
PATTERN_FLOPS_PER_WORD = 3.0

# Expected fraction of cuckoo lookups that must probe the alternate bucket
# (primary-bucket hit rate at ~50% member mix and moderate load).
CUCKOO_ALT_FRACTION = 0.6
# Expected fraction of the quotient run-scan a home-slot ballot avoids.
QUOTIENT_SCAN_FRACTION = 0.7
# Quotient contains reads the resident table several times per tile
# (metadata cumsums + two gathers + remainder compare).
QUOTIENT_SCAN_PASSES = 6.0
# Vector-op equivalents to issue one row DMA (descriptor build + wait
# bookkeeping); the HBM probe's ring overlaps d-1 of every d issues.
DMA_ISSUE_VOPS = 2.0


@dataclasses.dataclass(frozen=True)
class OpCost:
    """Machine-independent resource counts for ONE bulk op call."""

    bytes_hbm: float
    bytes_res: float
    flops: float
    launches: float
    vops: float

    def scaled(self, f: float) -> "OpCost":
        return OpCost(self.bytes_hbm * f, self.bytes_res * f,
                      self.flops * f, self.launches, self.vops * f)


def _unique_fraction(n_rows: int, tile: int) -> float:
    """E[#unique rows]/tile for ``tile`` uniform draws over ``n_rows`` —
    the DMA dedup factor of the row-sorted HBM update."""
    if n_rows <= 0 or tile <= 0:
        return 1.0
    exp_unique = n_rows * (1.0 - (1.0 - 1.0 / n_rows) ** tile)
    return min(exp_unique / tile, 1.0)


def _layout_trips(spec: FilterSpec, layout, tile: int) -> float:
    """Loop-probe schedule steps per tile for a Θ layout: one row load and
    one compare or store per key, Θ keys per trip."""
    theta = 1 if layout is None else layout.theta
    return (tile / max(theta, 1)) * 2.0


def op_cost(spec: FilterSpec, op: str, regime: str = "vmem", *,
            layout=None, coop: str = "none", tile: int = 256,
            n_keys: Optional[int] = None, bank: int = 1) -> OpCost:
    """Resource counts for one bulk ``op`` ("contains"|"add"|"remove") of
    ``n_keys`` keys (default: one tile) under the given configuration.

    Covers every engine family: blocked bit filters (row = s words) and
    counting filters (row = 4s counter words, contains collapses 4
    counter words per logical word), each by its regime's one schedule;
    cuckoo (two bucket gathers, ``coop`` skips the alternate) and quotient
    (whole-table run scan per tile, ``coop`` predicates it on the
    home-slot ballot).
    """
    n = int(n_keys) if n_keys else tile
    n_tiles = max(math.ceil(n / tile), 1)
    hash_f = HASH_FLOPS * n
    lg_tile = max(math.log2(max(tile, 2)), 1.0)
    lg_bank = math.log2(max(bank, 1))

    # Key stream in + result stream out cross the slow tier for every op.
    io_hbm = n * KEY_BYTES + (n * OUT_BYTES if op == "contains" else 0.0)

    if spec.is_fingerprint:
        row_words = spec.s                     # one bucket = s words
        load = bank * spec.n_words * WORD      # resident table stream-in
        if op == "contains":
            buckets = 1.0 + (CUCKOO_ALT_FRACTION if coop == "subtile"
                             else 1.0)
            touched = n * buckets * row_words
            vops = n_tiles * (10.0 + (4.0 if coop == "subtile" else 0.0))
            flops = hash_f + touched * PATTERN_FLOPS_PER_WORD
        else:                                  # sorted bounded-kick RMW
            touched = 4.0 * n * row_words
            vops = n_tiles * (2.0 * lg_tile + 24.0)
            flops = hash_f + touched * 2.0 * PATTERN_FLOPS_PER_WORD
        return OpCost(io_hbm + load, touched * WORD, flops, 1.0, vops)

    if spec.is_quotient:
        load = bank * spec.n_words * WORD
        if op == "contains":
            frac = QUOTIENT_SCAN_FRACTION if coop == "subtile" else 1.0
            touched = (n_tiles * spec.n_words * QUOTIENT_SCAN_PASSES * frac)
            vops = n_tiles * (16.0 + (4.0 if coop == "subtile" else 0.0))
            flops = hash_f + touched * PATTERN_FLOPS_PER_WORD
        else:                                  # decode + sort + rebuild
            touched = n_tiles * spec.n_words * 10.0
            vops = n_tiles * (2.0 * lg_tile + 40.0)
            flops = hash_f + touched * 2.0 * PATTERN_FLOPS_PER_WORD
        return OpCost(io_hbm + load, touched * WORD, flops, 1.0, vops)

    # Blocked / classical bit filters and counting filters. A probe row is
    # s words (bit filters) or 4s counter words (counting); a counting
    # *contains* additionally collapses 4 counter words per logical word.
    counting = spec.is_counting
    row_words = spec.counter_row_words if counting else spec.s
    storage = bank * spec.storage_words * WORD

    if regime == "hbm":
        # Per-row DMA streaming; the filter never becomes resident.
        if op == "contains":
            # one row DMA a key through the ring of HBM_PROBE_DEPTH slots
            depth = min(HBM_PROBE_DEPTH, tile)
            vops = (n_tiles * 6.0 + n * 3.0 + n * DMA_ISSUE_VOPS / depth
                    + n_tiles * 0.01 * depth * row_words)
            touched = n * row_words * (1.5 if counting else 1.0)
            flops = hash_f + touched * PATTERN_FLOPS_PER_WORD
            return OpCost(io_hbm + n * row_words * WORD,
                          touched * WORD, flops, 1.0, vops)
        # adds/updates RMW each unique row of a row-sorted tile once:
        # read + write per unique row.
        uniq = _unique_fraction(spec.n_blocks, tile)
        rows = n * uniq
        vops = (n_tiles * (2.0 * lg_tile + 10.0) + n * 2.0
                + rows * DMA_ISSUE_VOPS)
        touched = n * row_words
        flops = hash_f + touched * 2.0 * PATTERN_FLOPS_PER_WORD
        return OpCost(io_hbm + 2.0 * rows * row_words * WORD,
                      touched * WORD, flops, 1.0, vops)

    # VMEM regime: stream the filter in once, walk the keys Θ a trip
    # against the resident rows (a bank's loads stride the whole bank).
    trips = n_tiles * _layout_trips(spec, layout, tile) * (1.0 + 0.05 * lg_bank)
    if op == "contains":
        collapse = 4.0 if counting else 1.0    # counter words per word
        touched = n * spec.s * collapse
        flops = hash_f + touched * PATTERN_FLOPS_PER_WORD
        return OpCost(io_hbm + storage, touched * WORD, flops, 1.0, trips)

    # add / remove (RMW: read + write every touched word)
    touched = 2.0 * n * row_words
    flops = hash_f + touched * PATTERN_FLOPS_PER_WORD
    return OpCost(io_hbm + storage, touched * WORD, flops, 1.0, 2.0 * trips)


# ---------------------------------------------------------------------------
# Counts -> time
# ---------------------------------------------------------------------------

def _roofline_us(cost: OpCost, calib: Calibration) -> float:
    t_hbm = cost.bytes_hbm / (calib.bw_hbm_gbs * 1e3)      # bytes/GBps -> us
    t_res = cost.bytes_res / (calib.bw_res_gbs * 1e3)
    t_alu = cost.flops / (calib.gops * 1e3)
    return max(t_hbm, t_res, t_alu) + cost.launches * calib.launch_us


def ceiling_us(cost: OpCost, calib: Optional[Calibration] = None) -> float:
    """The practical speed of light: the roofline max of the three
    resource terms plus launch overhead — no schedule term. A perfect
    schedule on this host cannot beat this."""
    return _roofline_us(cost, calib or get_calibration())


def predict_us(cost: OpCost, calib: Optional[Calibration] = None) -> float:
    """Expected wall time: the ceiling plus the schedule vector-op cost
    (dominant in interpret mode, issue overhead on TPU)."""
    calib = calib or get_calibration()
    return _roofline_us(cost, calib) + cost.vops * calib.step_us


def ceiling_mops(spec: FilterSpec, op: str, regime: str = "vmem", *,
                 n_keys: int = 1 << 16, calib: Optional[Calibration] = None,
                 **cfg) -> float:
    """Model-predicted throughput ceiling (Mops/s = keys/us) for a bulk op
    at ``n_keys``."""
    c = op_cost(spec, op, regime, n_keys=n_keys, **cfg)
    return n_keys / ceiling_us(c, calib)


@functools.lru_cache(maxsize=512)
def choose_coop(spec: FilterSpec, op: str = "contains",
                regime: str = "vmem", tile: int = 256) -> str:
    """The cuckoo or quotient ``coop`` schedule with the lowest predicted
    cost — their ``"auto"`` resolution. A tie keeps ``"none"``: the early
    exit must win to displace the plain scan. lru-cached: all-static
    arguments, callable at trace time."""
    assert spec.is_fingerprint or spec.is_quotient, spec
    calib = get_calibration()
    return min(("none", "subtile"),
               key=lambda coop: predict_us(
                   op_cost(spec, op, regime, coop=coop, tile=tile), calib))
