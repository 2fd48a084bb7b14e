"""Bulk construction: the window repeats a build. Each build starts from
a fresh empty filter and adds the configuration's ``build_keys`` in
batches of the mix's ``batch``, one ``add`` per call, each call waited
for. The keys come from the key source that the ``keys`` entry of the
configuration or the mix names for the role "build"."""
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench.lib import drive
from bench.lib import reference as R
from bench.lib import work


def setup(run):
    cfg, tr = run.cell.config, run.cell.traffic
    batch = int(tr["batch"])
    with TraceAnnotation("bench.make_keys"):
        pool = drive.key_batches(run, "build", batch,
                                 int(cfg["build_keys"]) // batch)
        distinct = [drive.distinct_blocks(p, cfg) for p in pool]
    f = drive.make_filter(run)
    run.note("engine", f.backend)
    # an add into a fresh filter and an add into an add's result are two
    # programs: warm both
    jax.block_until_ready(f.add(pool[0]).add(pool[1 % len(pool)]).words)
    return {"f": f, "pool": pool, "distinct": distinct}


def window(run, st):
    f, pool = st["f"], st["pool"]
    block_bits = int(run.cell.config["block_bits"])
    per_build = len(pool)
    last_full, call_s, n_bytes, calls = None, [], 0, 0
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
                    f = drive.make_filter(run)
            t, t_prev = time.perf_counter(), t
            call_s.append(t - t_prev)
            if t - t0 >= run.seconds:
                break
        t1 = t
    n = calls * pool[0].shape[0]
    st.update(f=f, in_progress=calls % per_build, last_full=last_full)
    return drive.Window(t0=t0, t1=t1, kind="build", attempted=n, failed=0,
                        end_to_end={"build_keys_per_s": n / (t1 - t0)},
                        keys=n, bytes_needed=n_bytes,
                        host={"calls": calls, "call_s": call_s,
                              "builds_completed": calls // per_build})


def check(run, st, win):
    m_bits, k, block_bits = drive.spec(run.cell.config)
    n_blocks = m_bits // block_bits
    pool = st["pool"]
    words = st.pop("f").words
    last_full = st.pop("last_full")
    full_words = None if last_full is None else last_full.words
    del last_full
    # the build in progress when the window closed: the pool's first
    # in_progress batches, compared at the pool's whole shape
    all_keys = jnp.concatenate(pool)
    n_in = st["in_progress"] * pool[0].shape[0]
    in_f = jnp.arange(all_keys.shape[0]) < n_in
    missing = int(R.jit_count_missing(words, all_keys, m_bits, k, block_bits,
                                      0, n_blocks, in_f))
    want = R.jit_build_range(all_keys, m_bits, k, block_bits, 0, n_blocks,
                             None, in_f)
    differ = int(drive.count_differing(words, want))
    del want
    # the last build the window completed
    full_diff = 0
    if full_words is not None:
        want = R.jit_build_range(all_keys, m_bits, k, block_bits, 0,
                                 n_blocks)
        full_diff = int(drive.count_differing(full_words, want))
    return {"keys_missing": (missing, 0), "words_differing": (differ, 0),
            "built_words_differing": (full_diff, 0)}
