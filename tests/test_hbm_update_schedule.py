"""The HBM row update's DMA schedule under the interpreter's race detector.

``sbf.update_rows_hbm`` starts every row read of a tile before it applies
a key, starts each row's write when its run ends, and waits for the
tile's writes once, at the end of the tile. Run with
``dma_execution_mode="on_wait"`` (a DMA moves nothing until it is waited
for) and ``detect_races=True``, a missing wait leaves a row unread or
unwritten and a read of a row before the previous tile's write of it has
landed is a race: either shows here on the CPU. Each case compares every
word with the reference.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from jax._src.pallas.mosaic.interpret import interpret_pallas_call as _interp
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing as H
from repro.core import variants as V
from repro.kernels import countingbf as cnt_k
from repro.kernels import ref
from repro.kernels import sbf as sbf_k

TILE = 256
RACES = pltpu.InterpretParams(dma_execution_mode="on_wait", detect_races=True)
BITS = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)        # 16 rows
WIDE = V.FilterSpec("sbf", 1 << 20, 8, block_bits=256)        # 256 rows


def _keys(n, seed):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def _rows(spec, keys):
    starts, _ = sbf_k.fingerprints(spec, keys)
    return np.asarray(starts) // sbf_k.row_lanes(spec.n_words)


def _sorted_rows(spec, keys):
    return np.sort(_rows(spec, keys))


def _straddle():
    """512 keys on 16 rows: a row run crosses the edge of the two tiles."""
    keys = _keys(512, seed=41)
    rows = _sorted_rows(BITS, keys)
    assert rows[TILE - 1] == rows[TILE]
    return BITS, V.init(BITS), keys, None


def _one_run():
    """Every key of the tile on one row (not row 0)."""
    cand = _keys(8192, seed=42)
    keys = cand[_rows(BITS, cand) == 5][:TILE]
    assert keys.shape[0] == TILE
    return BITS, V.init(BITS), keys, None


def _distinct_rows():
    """A tile of 256 distinct rows: one run a key."""
    cand = _keys(8192, seed=43)
    _, first = np.unique(_rows(WIDE, cand), return_index=True)
    keys = cand[np.sort(first)]
    assert keys.shape[0] == TILE
    assert len(set(_rows(WIDE, keys))) == TILE
    return WIDE, V.init(WIDE), keys, None


def _padded():
    """An exchange's slots: 200 carried among 512, so the second tile is
    padding; the output aliases a filter that already holds keys."""
    keys = _keys(512, seed=44)
    valid = np.zeros(512, bool)
    valid[np.random.default_rng(44).choice(512, 200, replace=False)] = True
    filt = ref.bloom_add_ref(BITS, V.init(BITS), _keys(300, seed=45))
    return BITS, filt, keys, jnp.asarray(valid)


CASES = {"straddle": _straddle, "one_run": _one_run,
         "distinct_rows": _distinct_rows, "padded": _padded}


@pytest.mark.parametrize("case", list(CASES) + ["counting_repeats"])
def test_update_rows_hbm_schedule_has_no_race(case):
    if case == "counting_repeats":
        spec = V.FilterSpec("countingbf", 1 << 16, 8, block_bits=256)
        base = _keys(256, seed=46)
        keys = jnp.concatenate([base, base[:128], base[:64], base[:64]])
        got = cnt_k.update_hbm(spec, V.init(spec), keys,
                               jnp.ones((512,), jnp.bool_), "add",
                               interpret=RACES, tile=TILE)
        want = V.counting_add(spec, V.init(spec), keys)
    else:
        spec, filt, keys, valid = CASES[case]()
        starts, masks = sbf_k.fingerprints(spec, keys)
        got = sbf_k.update_rows_hbm(filt, starts, masks, tile=TILE,
                                    interpret=RACES, valid=valid)
        carried = keys if valid is None else keys[np.asarray(valid)]
        want = ref.bloom_add_ref(spec, filt, carried)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not _interp.races.races_found
