"""Served lookups: a bank of member filters behind the program's
``FilterService``, loaded in set-up through the bulk routed add.

The configuration gives the bank (``members`` filters of its layout),
``record_count`` records from the key source its ``keys`` entry names
for the role "records", and the service's settings (``service``). Member
``b`` holds the records whose key's high word falls in the b-th of
``members`` equal ranges (range-partitioned files).

The mix gives the arrivals (``arrivals``: a process of
``bench/arrivals/`` and its parameters), the record each get asks for
(``records``: a chooser of ``bench/choosers/`` and its parameters) and
``lookups_per_get``: each get is that many routed lookups, the member
that holds its record and members drawn uniformly from the others.

Gets arrive open-loop. A get's latency runs from when it was due to when
its last answer is taken from the service; a refused or unanswered get
counts as slower than every answered one.
"""
import math
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench.lib import catalog
from bench.lib import drive
from bench.lib import keys as K
from bench.lib import reference as R
from bench.lib.stats import nearest_rank

# Seconds past the window's close that a served request may still answer.
ANSWER_GRACE_S = 60.0
# A host phase of the serving loop longer than this is listed as a stall.
STALL_S = 0.02


def _service_config(run):
    from repro.service import ServiceConfig
    from repro.telemetry import TelemetryConfig
    kw = {"max_batch": int(run.cell.config["service"]["max_batch"])}
    if run.trace:
        # the ring must hold every span of the window
        kw["telemetry"] = TelemetryConfig(max_spans=1 << 21)
    return ServiceConfig(**kw)


def holders(records, members: int):
    """The member holding each record: its key's high word, cut into
    ``members`` equal ranges."""
    hi16 = (records[:, 0] >> 16).astype(jnp.uint32)
    return ((hi16 * jnp.uint32(members)) >> 16).astype(jnp.int32)


def requests(run, records_host, holder_host):
    """The window's gets, on the host: (due times, keys (n, per_get, 2),
    members asked (n, per_get))."""
    cfg, tr = run.cell.config, run.cell.traffic
    members = int(cfg["members"])
    rng = K.host_rng(run.seed, "service.gets")
    arrivals = tr["arrivals"]
    due = catalog.plugin("arrivals", arrivals["process"]).due(
        rng, arrivals, run.seconds)
    chooser = tr["records"]
    rec = catalog.plugin("choosers", chooser["chooser"]).draw(
        rng, due.shape[0], records_host.shape[0], chooser)
    per_get = int(tr["lookups_per_get"])
    own = holder_host[rec][:, None]
    others = (own + 1 + rng.integers(0, members - 1,
                                     (due.shape[0], per_get - 1))) % members
    asked = np.concatenate([own, others], axis=1)
    keys = np.repeat(records_host[rec][:, None, :], per_get, axis=1)
    return due, keys, asked


def setup(run):
    from repro import api
    from repro.service import FilterService
    cfg = run.cell.config
    members = int(cfg["members"])
    with TraceAnnotation("bench.make_keys"):
        records = drive.key_batches(run, "records", int(cfg["record_count"]),
                                    1)[0]
        holder = holders(records, members)
    bank = api.make_filter_bank(members, **drive.filter_kwargs(run))
    run.note("engine", bank.backend)
    with TraceAnnotation("bench.prefill"):
        bank = bank.add(records, tenants=holder)
        jax.block_until_ready(bank.words)
    svc = FilterService(bank, _service_config(run))
    rec_host, hold_host = np.asarray(records), np.asarray(holder)
    due, get_keys, asked = requests(run, rec_host, hold_host)

    # warm every shape a flush of the window uses: a full batch, a
    # deadline flush of a few, and the admission refresh that runs every
    # ``health_every`` flushes
    mb = svc.cfg.max_batch
    for _ in range(svc.cfg.admission.health_every + 1):
        svc.submit_many("contains", rec_host[:mb], hold_host[:mb])
    per_get = asked.shape[1]
    svc.submit_many("contains", rec_host[:per_get], hold_host[:per_get])
    time.sleep(2 * (svc.cfg.flush_deadline or 0))
    svc.pump()
    svc.drain()
    svc.take_results()
    return {"svc": svc, "records": records, "holder": holder, "due": due,
            "keys": get_keys, "asked": asked}


def window(run, st):
    svc, due = st["svc"], st["due"]
    get_keys, asked = st["keys"], st["asked"]
    n, per_get = asked.shape
    reg = svc.telemetry.registry
    before = {c: reg.counter(f"service.{c}").value
              for c in ("flushes", "flushed_ops", "padded_slots")}
    seq_of = {}                               # seq -> flat lookup index
    answer = np.full(n * per_get, -1, np.int8)
    left = np.full(n, per_get, np.int64)
    done = np.full(n, np.nan)
    submitted = np.full(n, np.nan)
    refused = np.zeros(n, bool)
    nxt = 0
    open_gets = 0
    flush_after = svc.cfg.flush_deadline
    stalls = []                               # (phase, start s, seconds)

    def timed(phase, t_phase, t0):
        dt = time.perf_counter() - t_phase
        if dt > STALL_S:
            stalls.append((phase, round(t_phase - t0, 6), round(dt, 6)))

    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        give_up = t0 + run.seconds + ANSWER_GRACE_S
        while True:
            el = time.perf_counter() - t0
            if nxt < n and due[nxt] <= el:
                t_phase = time.perf_counter()
                with TraceAnnotation("bench.submit"):
                    while nxt < n and due[nxt] <= el:
                        seqs = svc.submit_many("contains", get_keys[nxt],
                                               asked[nxt])
                        submitted[nxt] = time.perf_counter() - t0
                        if (seqs < 0).any():
                            refused[nxt] = True
                        else:
                            base = nxt * per_get
                            for j, q in enumerate(seqs.tolist()):
                                seq_of[q] = base + j
                            open_gets += 1
                        nxt += 1
                        el = time.perf_counter() - t0
                timed("submit", t_phase, t0)
            if svc.pending_total:
                t_phase = time.perf_counter()
                with TraceAnnotation("bench.pump"):
                    svc.pump()
                timed("pump", t_phase, t0)
            if svc.results:
                t_phase = time.perf_counter()
                with TraceAnnotation("bench.take_results"):
                    res = svc.take_results()
                    t_take = time.perf_counter() - t0
                    for q, v in res.items():
                        idx = seq_of.pop(q, None)
                        if idx is None:
                            continue
                        answer[idx] = int(v)
                        g = idx // per_get
                        left[g] -= 1
                        if left[g] == 0:
                            done[g] = t_take
                            open_gets -= 1
                timed("take_results", t_phase, t0)
            if nxt >= n and open_gets == 0:
                break
            if time.perf_counter() > give_up:
                break
            # sleep until the next arrival or the next flush deadline
            wake = due[nxt] if nxt < n else math.inf
            if svc.pending_total and flush_after is not None:
                oldest = min(p.oldest() for p in svc.pending.values()
                             if p.count)
                wake = min(wake, oldest - t0 + flush_after)
            wait = wake - (time.perf_counter() - t0)
            if wait > 2e-4:
                with TraceAnnotation("bench.idle"):
                    time.sleep(min(wait - 1e-4, 1e-3))
        t1 = time.perf_counter()
    after = {c: reg.counter(f"service.{c}").value for c in before}
    answered = ~np.isnan(done)
    unanswered = int((~answered & ~refused).sum())
    end = t1 - t0
    lat = np.where(answered, done - due, end - due + 1.0)
    st.update(answer=answer)
    lag = submitted - due
    return drive.Window(
        t0=t0, t1=t1, kind="service", attempted=int(n),
        failed=int((~answered).sum()),
        end_to_end={"get_p50_ms": nearest_rank(lat, 50.0) * 1e3},
        keys=int(n * per_get),
        host={"lag_s": lag[~np.isnan(lag)], "latency_s": lat,
              "refused": int(refused.sum()), "unanswered": unanswered,
              "counters": {c: after[c] - before[c] for c in before},
              "max_batch": int(svc.cfg.max_batch),
              "get_p99_ms": nearest_rank(lat, 99.0) * 1e3,
              "stalls": stalls,
              "spans": [e for e in svc.telemetry.tracer.events
                        if e["t0"] >= t0]})


def check(run, st, win):
    cfg = run.cell.config
    m_bits, k, block_bits = drive.spec(cfg)
    members = int(cfg["members"])
    bank_words = st.pop("svc").filt.words.reshape(-1)
    ref = R.jit_build_range(st["records"], m_bits, k, block_bits, 0,
                            members * (m_bits // block_bits), st["holder"])
    words_diff = int(drive.count_differing(bank_words, ref))
    want = np.asarray(R.jit_contains(
        ref, jnp.asarray(st["keys"].reshape(-1, 2)), m_bits, k, block_bits,
        jnp.asarray(st["asked"].reshape(-1))))
    got = st["answer"]
    taken = got >= 0
    differ = int((got[taken] != want[taken]).sum())
    run.note("lookups_answered", int(taken.sum()))
    run.note("lookups_positive_share", float(want.mean()))
    return {"answers_differing": (differ, 0),
            "gets_unanswered": (int(win.host["unanswered"]), 0),
            "bank_words_differing": (words_diff, 0)}
