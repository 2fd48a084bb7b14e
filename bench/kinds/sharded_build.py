"""Bulk construction of a filter sharded over the run's chips: the window
repeats a build, as ``bulk_build`` does. Each build starts from a fresh
empty filter and adds the configuration's ``build_keys`` in batches of
the mix's ``batch``, one ``add`` per call, each call waited for. Each
batch is laid out over the chips before the window, a ``batch / chips``
slice on each, as a worker per chip would hold its keys, so a call moves
no key from one device to another before the program's own exchange.

The window lasts ``run.seconds`` and at least ``MIN_CALLS`` calls. An
engine whose add costs more when a batch puts three keys in one block
(the XLA ``or_rows`` segment update takes one scatter round more, about
1.7 s against 1.05-1.25 s a call on a v5e) meets such a batch about
every second call at this size; a window of the 7 or 8 calls that 10 s
holds then reads how many of its batches were such, not the engine.

The check compares each shard of the filter on its own device, piece by
piece, with the plain reference: a piece is the largest block range the
reference builds at once, and the keys of each piece are gathered to the
piece's device by the check's own exchange, independent of the program's.
"""
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.lib import drive
from bench.lib import reference as R
from bench.lib import work

AXIS = "chips"
# Words of filter the reference builds at a time. Its bit planes take
# about 250 bytes a word on a v5e (2.07 GB of temporaries at 2^23 words,
# compiled for the chip), which leaves room beside two 4 GiB shards.
PIECE_WORDS = 1 << 23
COUNTER_PREFIX = "sharded."
# Fewest calls a window makes, whatever its seconds: 40 x 2^20 keys, under
# a sixth of a build at the cell's sizes. Binds only where a call takes
# over seconds / MIN_CALLS (0.25 s at 10 s).
MIN_CALLS = 40


def _mesh(run):
    return Mesh(np.array(run.devices), (AXIS,))


def _make_filter(run, mesh):
    from repro import api
    return api.make_filter(**drive.filter_kwargs(run), mesh=mesh, axis=AXIS)


def _counters(f) -> dict:
    """The engine's counters where the program keeps them, else {}."""
    reg = getattr(f.engine, "registry", None)
    return {} if reg is None else reg.snapshot(prefix=COUNTER_PREFIX)


def setup(run):
    cfg, tr = run.cell.config, run.cell.traffic
    batch = int(tr["batch"])
    mesh = _mesh(run)
    with TraceAnnotation("bench.make_keys"):
        made = drive.key_batches(run, "build", batch,
                                 int(cfg["build_keys"]) // batch)
        distinct = [drive.distinct_blocks(b, cfg) for b in made]
        on_chips = NamedSharding(mesh, P(AXIS))
        pool = [jax.device_put(b, on_chips) for b in made]
        jax.block_until_ready(pool)
        del made
    # an add into a fresh filter and an add into an add's result are two
    # programs: warm both, on a filter the window does not keep
    warm = _make_filter(run, mesh)
    run.note("engine", warm.backend)
    warm = warm.add(pool[0])
    warm = warm.add(pool[1 % len(pool)])
    jax.block_until_ready(warm.words)
    del warm
    return {"f": _make_filter(run, mesh), "mesh": mesh, "pool": pool,
            "distinct": distinct}


def window(run, st):
    # popped, not read: set-up's empty filter would otherwise stay held
    # through the window, 4 GiB more on every chip
    f, pool = st.pop("f"), st["pool"]
    block_bits = int(run.cell.config["block_bits"])
    per_build = len(pool)
    last_full, call_s, n_bytes, calls = None, [], 0, 0
    before = _counters(f)
    with TraceAnnotation("bench.window"):
        t0 = t = time.perf_counter()
        while True:
            j = calls % per_build
            with TraceAnnotation("bench.add_call"):
                f = f.add(pool[j])
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(f.words)
            n_bytes += work.add_bytes(pool[j].shape[0], st["distinct"][j],
                                      block_bits)
            calls += 1
            if calls % per_build == 0:
                last_full = f
                with TraceAnnotation("bench.new_filter"):
                    f = _make_filter(run, st["mesh"])
            t, t_prev = time.perf_counter(), t
            call_s.append(t - t_prev)
            if t - t0 >= run.seconds and calls >= MIN_CALLS:
                break
        t1 = t
    after = _counters(f)
    run.note("call_s_each", call_s)
    n = calls * pool[0].shape[0]
    st.update(f=f, in_progress=calls % per_build, last_full=last_full)
    host = {"calls": calls, "call_s": call_s,
            "builds_completed": calls // per_build}
    if after:
        host["counters"] = {name: v - before.get(name, 0)
                            for name, v in after.items()}
    return drive.Window(t0=t0, t1=t1, kind="build", attempted=n, failed=0,
                        end_to_end={"build_keys_per_s": n / (t1 - t0)},
                        keys=n, bytes_needed=n_bytes, host=host)


# -- the check ------------------------------------------------------------------

def _geometry(cfg: dict, n_dev: int):
    """(pieces per shard, words per piece, blocks per piece)."""
    m_bits, _, block_bits = drive.spec(cfg)
    shard_words = m_bits // R.WORD_BITS // n_dev
    piece_words = min(shard_words, PIECE_WORDS)
    return (shard_words // piece_words, piece_words,
            piece_words // (block_bits // R.WORD_BITS))


@partial(jax.jit, static_argnames=("mesh", "m_bits", "k", "block_bits",
                                   "blocks_per_piece", "n_pieces"))
def _by_piece(pool, n_batches, *, mesh, m_bits, k, block_bits,
              blocks_per_piece, n_pieces):
    """Each device's keys of the pool's first ``n_batches`` batches, sorted
    by the piece of the filter that holds their block (the others last),
    as (hi, lo) columns, with each piece's offset into them."""
    def body(keys):                             # (count, batch / n_dev, 2)
        batch_of = jnp.arange(keys.shape[0] * keys.shape[1]) // keys.shape[1]
        flat = keys.reshape(-1, 2)
        blk, _ = R.key_bits(flat, m_bits, k, block_bits)
        piece = jnp.where(batch_of < n_batches, blk // blocks_per_piece,
                          n_pieces)
        order = jnp.argsort(piece)
        offsets = jnp.searchsorted(piece[order], jnp.arange(n_pieces + 1))
        # one column at a time: a (n, 2) array pads its 2 lanes to 128
        hi, lo = flat[:, 0], flat[:, 1]
        return hi[order][None], lo[order][None], offsets[None].astype(
            jnp.int32)

    return jax.shard_map(body, mesh=mesh, in_specs=P(None, AXIS),
                         out_specs=P(AXIS), check_vma=False)(pool)


@partial(jax.jit, static_argnames=("mesh", "m_bits", "k", "block_bits",
                                   "piece_words", "pieces_per_shard",
                                   "room"))
def _compare_piece(words, hi, lo, offsets, j, *, mesh, m_bits, k, block_bits,
                   piece_words, pieces_per_shard, room):
    """Piece ``j`` of every shard against the reference: each device sends
    each owner the keys of that owner's piece ``j`` (at most ``room`` of
    them), and the owner builds the piece from them. Returns each device's
    (words differing, keys missing)."""
    s = block_bits // R.WORD_BITS
    n_blocks = piece_words // s

    def body(w, hi, lo, offsets):
        n_dev = jax.lax.axis_size(AXIS)
        pieces = jnp.arange(n_dev) * pieces_per_shard + j
        first = offsets[0][pieces]
        count = offsets[0][pieces + 1] - first

        def cut(col):                          # (n_dev, room)
            at = first[:, None] + jnp.arange(room)[None, :]
            return jnp.take(col[0], at, mode="clip")

        send = jnp.stack([cut(hi), cut(lo)], axis=-1)  # (n_dev, room, 2)
        recv = jax.lax.all_to_all(send, AXIS, 0, 0, tiled=False)
        n_recv = jax.lax.all_to_all(count, AXIS, 0, 0, tiled=True)
        valid = (jnp.arange(room)[None, :] < n_recv[:, None]).reshape(-1)
        keys = recv.reshape(-1, 2)
        me = jax.lax.axis_index(AXIS) * pieces_per_shard + j
        have = jax.lax.dynamic_slice(w, (j * piece_words,), (piece_words,))
        want = R.build_range(keys, m_bits, k, block_bits, me * n_blocks,
                             n_blocks, valid=valid)
        differ = jnp.sum((have != want).astype(jnp.int32))
        missing = R.count_missing(have, keys, m_bits, k, block_bits,
                                  me * n_blocks, n_blocks, valid)
        return differ[None], missing[None]

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
                         out_specs=(P(AXIS), P(AXIS)),
                         check_vma=False)(words, hi, lo, offsets)


def _compare(words, stacked, n_batches, cfg, mesh):
    """(words differing, keys missing) of the whole sharded filter
    ``words`` against the reference built from the first ``n_batches``
    batches of the pool."""
    m_bits, k, block_bits = drive.spec(cfg)
    n_dev = mesh.shape[AXIS]
    pieces_per_shard, piece_words, blocks_per_piece = _geometry(cfg, n_dev)
    geo = dict(mesh=mesh, m_bits=m_bits, k=k, block_bits=block_bits)
    hi, lo, offsets = _by_piece(stacked, n_batches,
                                blocks_per_piece=blocks_per_piece,
                                n_pieces=n_dev * pieces_per_shard, **geo)
    per_piece = np.diff(np.asarray(offsets), axis=1)
    room = max(1024, -(-int(per_piece.max()) // 1024) * 1024)
    differ = missing = 0
    for j in range(pieces_per_shard):
        d, m = _compare_piece(words, hi, lo, offsets, j,
                              piece_words=piece_words,
                              pieces_per_shard=pieces_per_shard, room=room,
                              **geo)
        differ += int(np.asarray(d).sum())
        missing += int(np.asarray(m).sum())
    return differ, missing


def check(run, st, win):
    cfg, mesh = run.cell.config, st["mesh"]
    words = st.pop("f").words
    last_full = st.pop("last_full")
    full_words = None if last_full is None else last_full.words
    del last_full
    pool = st.pop("pool")
    stacked = jax.jit(jnp.stack, out_shardings=NamedSharding(
        mesh, P(None, AXIS)))(pool)
    del pool
    # the build in progress when the window closed: the pool's first
    # in_progress batches
    differ, missing = _compare(words, stacked, st["in_progress"], cfg, mesh)
    del words
    full_diff = 0
    if full_words is not None:
        full_diff, _ = _compare(full_words, stacked, stacked.shape[0], cfg,
                                mesh)
    return {"keys_missing": (missing, 0), "words_differing": (differ, 0),
            "built_words_differing": (full_diff, 0)}
