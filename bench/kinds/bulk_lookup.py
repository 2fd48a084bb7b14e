"""Bulk lookup: a filter built in set-up from the build keys, then
probed. The window cycles through a pool of probe batches, one
``contains`` per call, each call waited for.

The mix gives ``morsel`` (keys per add of the build), ``probe_batch``
(keys per lookup call) and ``pool`` (probe batches made in set-up). The
configuration's ``build_keys`` are added in morsels; the build and
probe keys come from the key sources that the ``keys`` entries of the
configuration or the mix name for the roles "build" and "probe"."""
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench.lib import drive
from bench.lib import keys as K
from bench.lib import reference as R
from bench.lib import work


def setup(run):
    cfg, tr = run.cell.config, run.cell.traffic
    morsel = int(tr["morsel"])
    with TraceAnnotation("bench.make_keys"):
        morsels = drive.key_batches(run, "build", morsel,
                                    int(cfg["build_keys"]) // morsel)
    f = drive.make_filter(run)
    run.note("engine", f.backend)
    with TraceAnnotation("bench.prefill"):
        for m in morsels:
            f = f.add(m)
        jax.block_until_ready(f.words)
    with TraceAnnotation("bench.make_keys"):
        pool = drive.key_batches(run, "probe", int(tr["probe_batch"]),
                                 int(tr["pool"]))
        distinct = [drive.distinct_blocks(p, cfg) for p in pool]
    jax.block_until_ready(f.contains(pool[0]))
    return {"f": f, "morsels": morsels, "pool": pool, "distinct": distinct}


def window(run, st):
    f, pool = st["f"], st["pool"]
    block_bits = int(run.cell.config["block_bits"])
    answers, call_s, n_bytes = [], [], 0
    with TraceAnnotation("bench.window"):
        t0 = t = time.perf_counter()
        while True:
            j = len(answers) % len(pool)
            with TraceAnnotation("bench.lookup_call"):
                out = f.contains(pool[j])
            with TraceAnnotation("bench.wait"):
                out.block_until_ready()
            answers.append(out)
            n_bytes += work.lookup_bytes(pool[j].shape[0], st["distinct"][j],
                                         block_bits)
            t, t_prev = time.perf_counter(), t
            call_s.append(t - t_prev)
            if t - t0 >= run.seconds:
                break
        t1 = t
    n = len(answers) * pool[0].shape[0]
    st["answers"] = answers
    return drive.Window(t0=t0, t1=t1, kind="lookup", attempted=n, failed=0,
                        end_to_end={"lookup_keys_per_s": n / (t1 - t0)},
                        keys=n, bytes_needed=n_bytes,
                        host={"calls": len(answers), "call_s": call_s})


def check(run, st, win):
    m_bits, k, block_bits = drive.spec(run.cell.config)
    f, pool = st.pop("f"), st["pool"]
    absent = K.uniform_keys(run.seed, "never_inserted", 0, pool[0].shape[0])
    absent = absent.at[:, 0].set(absent[:, 0] | 1)   # no TPC-H key
    run.note("fpr", float(drive.count_true(f.contains(absent)))
             / absent.shape[0])
    words = f.words
    del f
    ref = R.jit_build_range(jnp.concatenate(st["morsels"]), m_bits, k,
                            block_bits, 0, m_bits // block_bits)
    words_diff = int(drive.count_differing(words, ref))
    differ = 0
    for j, probe in enumerate(pool):
        want = R.jit_contains(ref, probe, m_bits, k, block_bits)
        if j == 0:
            run.note("positive_share", float(drive.count_true(want))
                     / probe.shape[0])
        for out in st["answers"][j::len(pool)]:
            differ += int(drive.count_differing(out, want))
    return {"answers_differing": (differ, 0),
            "filter_words_differing": (words_diff, 0)}
