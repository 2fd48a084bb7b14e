"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Batched request serving through the Engine (prefill + decode with caches),
optionally guarded by the Bloom n-gram repetition filter.

    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --requests 8 --new-tokens 24 --guard
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.configs import get_config, smoke_config
from repro.models.model import build_model
from repro.serving.engine import Engine, Request
from repro.serving.ngram_guard import NGramGuard
from repro.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mistral-nemo-12b")
    ap.add_argument("--smoke", dest="smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--guard", action="store_true",
                    help="enable the Bloom n-gram repetition guard")
    ap.add_argument("--guard-decay-every", type=int, default=None,
                    help="time-decayed guard: counting filter + one decay "
                         "per N observed steps (long-running serve loops)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.is_encdec:
        raise SystemExit("enc-dec serving needs --src features; use the "
                         "examples for seamless")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"[serve] {args.arch} ({model.param_count()/1e6:.1f}M params)")

    guard = (NGramGuard(batch=args.batch, n=3, top_k=64,
                        decay_every=args.guard_decay_every)
             if args.guard or args.guard_decay_every else None)
    engine = Engine(model, params, batch=args.batch, max_len=args.max_len,
                    guard=guard)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(2, cfg.vocab,
                                       args.prompt_len).astype(np.int32),
                    max_new_tokens=args.new_tokens)
            for _ in range(args.requests)]
    t0 = time.time()
    outs = engine.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    print(f"[serve] {len(reqs)} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok/dt:.1f} tok/s)")
    if guard:
        print(f"[serve] guard: {guard.stats.observed} n-grams recorded, "
              f"{guard.stats.penalized} candidates penalized, "
              f"{guard.stats.decays} decays "
              f"(engine {guard.filt.backend!r})")
        health = {k: v for k, v in engine.stats().items()
                  if k not in ("guard.observed", "guard.penalized",
                               "guard.decays")}
        print(f"[serve] guard health: " + ", ".join(
            f"{k.removeprefix('guard.')}={v:.4g}"
            for k, v in health.items()))
    print(f"[serve] sample: {outs[0][:12]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
