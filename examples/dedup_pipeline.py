"""Distributed training-data dedup with Bloom filters.

Runs the data pipeline with the paper's technique at three deployment shapes:
  1. single-host DedupFilter (bulk ops, insert-only);
  2. streaming dedup with eviction: a WindowedFilter generation ring drops
     duplicates within a sliding window and retires old signatures in O(1),
     so an unbounded stream never saturates the filter;
  3. 8-device replicated engine with butterfly OR merges (spawn with
     XLA_FLAGS=--xla_force_host_platform_device_count=8 to see >1 device).

All shapes are the same ``repro.api``/``repro.window`` surface — the
deployment is just a constructor choice.

    PYTHONPATH=src python examples/dedup_pipeline.py
"""
import itertools

import numpy as np
import jax
from jax.sharding import Mesh

from repro import api
from repro.data import dedup as D
from repro.data import pipeline as DP
from repro.compile_cache import enable_compile_cache


def single_host():
    cfg = DP.CorpusConfig(n_docs=5000, dup_fraction=0.3, seed=0)
    dd = D.DedupFilter(expected_docs=1 << 14, bits_per_key=16)
    kept = list(dd.filter_stream(DP.synthetic_corpus(cfg)))
    print(f"[single-host] {dd.stats.seen} docs -> kept {len(kept)} "
          f"(dropped {dd.stats.dropped}, drop_rate {dd.stats.drop_rate:.1%}) "
          f"filter fill {dd.filt.fill_fraction():.3f} "
          f"engine {dd.filt.backend!r}")
    rows = list(DP.batches(iter(kept), batch_size=8, seq_len=256))
    print(f"[single-host] packed into {len(rows)} batches of (8, 256)")


def streaming_with_eviction():
    """Unbounded stream: window dedup keeps memory/FPR stationary and lets
    a duplicate through again once its first occurrence has expired."""
    sd = D.StreamingDedupFilter(window_docs=2048, generations=4,
                                batch_docs=128)
    # loop a small corpus 3x: an insert-only filter would drop every repeat
    # forever; the window re-admits docs once they fall out of it
    cfg = DP.CorpusConfig(n_docs=3000, dup_fraction=0.2, seed=2)
    stream = itertools.chain(*(DP.synthetic_corpus(cfg) for _ in range(3)))
    kept = sum(1 for _ in sd.filter_stream(stream))
    print(f"[streaming] {sd.stats.seen} docs -> kept {kept} "
          f"(dropped {sd.stats.dropped}, {sd.stats.advances} ring advances) "
          f"window fill {sd.window.fill_fraction():.3f} "
          f"per-gen fill {np.round(sd.window.generation_fill(), 3)}")


def streaming_with_fingerprint_eviction():
    """Same sliding-window dedup, eviction engine swapped: a cuckoo
    fingerprint filter deletes each retired signature individually
    (Filter.remove) instead of rotating age-class generations — one table
    at ~8.4 bits per live key instead of G ring generations, and the
    insert-failure counter doubles as a capacity alarm."""
    sd = D.StreamingDedupFilter(window_docs=2048, generations=4,
                                batch_docs=128, engine="cuckoo",
                                bits_per_key=8)
    cfg = DP.CorpusConfig(n_docs=3000, dup_fraction=0.2, seed=2)
    stream = itertools.chain(*(DP.synthetic_corpus(cfg) for _ in range(3)))
    kept = sum(1 for _ in sd.filter_stream(stream))
    print(f"[cuckoo-evict] {sd.stats.seen} docs -> kept {kept} "
          f"(dropped {sd.stats.dropped}, {sd.stats.advances} evictions) "
          f"load factor {sd.filt.load_factor():.3f} "
          f"insert failures {int(sd.filt.insert_failures)}")


def per_tenant_cuckoo_bank():
    """Per-tenant dedup on a bank of fingerprint filters: tenant-routed
    contains/add plus per-tenant deletion (GDPR-style forget) that the
    bit-filter bank cannot do."""
    td = D.TenantDedupFilter(n_tenants=8, expected_docs_per_tenant=1 << 10,
                             batch_docs=64, engine="cuckoo")
    cfg = DP.CorpusConfig(n_docs=1200, dup_fraction=0.3, seed=4)
    pairs = [(doc, i % 8) for i, doc in enumerate(DP.synthetic_corpus(cfg))]
    kept = sum(1 for _ in td.filter_stream(iter(pairs)))
    # forget tenant 3 entirely: remove its history from the bank.
    # Deduplicate first — only the first occurrence of each signature was
    # inserted, and cuckoo removes must only target inserted keys
    t3 = [D.doc_signature(d) for (d, t) in pairs if t == 3]
    sigs3 = np.unique(np.stack(t3), axis=0)
    who3 = np.full(len(sigs3), 3)
    td.filt = td.filt.remove(sigs3, tenants=who3)
    again = np.asarray(td.filt.contains(sigs3, tenants=who3))
    print(f"[tenant-cuckoo] kept {kept}/{td.stats.seen} "
          f"(drop_rate {td.stats.drop_rate:.1%}); after forgetting "
          f"tenant 3: {again.mean():.1%} of its sigs still visible")


def multi_host_replicated():
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n_dev), ("data",))
    f = api.make_filter("sbf", m_bits=1 << 20, k=8, block_bits=256,
                        backend="replicated", mesh=mesh)

    # each "host" deduplicates its own shard; the uniform Filter protocol
    # takes one flat key batch and splits it across devices itself
    per_dev = []
    for shard in range(n_dev):
        cfg = DP.CorpusConfig(n_docs=2000, dup_fraction=0.2, seed=1)
        docs = list(DP.synthetic_corpus(cfg, shard=shard % 2, num_shards=2))
        sigs = np.stack([D.doc_signature(d) for d in docs[:512]])
        per_dev.append(sigs)
    keys = np.concatenate(per_dev)                      # (n_dev*512, 2) flat
    f = f.add(keys)
    # contains tests against the butterfly-OR of all replicas, so every
    # device's adds are visible — no explicit sync step in the new API
    hits = np.asarray(f.contains(np.roll(keys, 512, axis=0)))
    print(f"[replicated x{n_dev}] cross-shard hit rate {hits.mean():.1%} "
          f"(shards overlap by construction); "
          f"approx {f.approx_count():,.0f} unique signatures")


if __name__ == "__main__":
    enable_compile_cache()
    single_host()
    streaming_with_eviction()
    streaming_with_fingerprint_eviction()
    per_tenant_cuckoo_bank()
    multi_host_replicated()
