"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs the real framework loop — synthetic corpus -> Bloom dedup -> packing ->
fault-tolerant driver (checkpoint/restart, straggler watch) -> AdamW — on
whatever devices exist. Full-size configs belong on a pod; ``--smoke``
(default) runs the family-preserving reduced config so the driver is
exercisable anywhere (CI, laptop).

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-3b --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b --no-smoke \
        --mesh 16x16       # on a real pod
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, smoke_config
from repro.configs.base import TrainConfig
from repro.data import dedup as D
from repro.data import pipeline as DP
from repro.launch.mesh import data_axis_names, make_mesh
from repro.models.dist import DistContext
from repro.models.model import build_model
from repro.runtime.fault_tolerance import DriverConfig, TrainingDriver
from repro.training.train_step import make_train_step, train_state_init
from repro.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mistral-nemo-12b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoke", dest="smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--mesh", default=None,
                    help="AxB data x model mesh over available devices")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    model = build_model(cfg)
    print(f"[train] {args.arch} ({model.param_count()/1e6:.1f}M params, "
          f"smoke={args.smoke})")

    dist = None
    if args.mesh:
        a, b = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((a, b), ("data", "model"))
        dist = DistContext(mesh=mesh, data_axes=("data",))
        print(f"[train] mesh {dict(mesh.shape)}")

    # data: synthetic corpus -> bloom dedup -> packed batches
    corpus = DP.CorpusConfig(n_docs=5000, vocab=cfg.vocab, dup_fraction=0.2)
    dd = D.DedupFilter(expected_docs=1 << 14)
    packed = list(DP.batches(dd.filter_stream(DP.synthetic_corpus(corpus)),
                             batch_size=args.batch, seq_len=args.seq))
    print(f"[train] dedup dropped {dd.stats.dropped}/{dd.stats.seen} docs; "
          f"{len(packed)} batches")

    def batch_fn(step):
        b = {"tokens": jnp.asarray(packed[step % len(packed)])}
        if cfg.is_encdec:
            b["src"] = jnp.zeros((args.batch, args.seq, cfg.d_model),
                                 jnp.float32)
        if cfg.frontend == "vision":
            b["prefix"] = jnp.zeros((args.batch, cfg.prefix_len, cfg.d_model),
                                    jnp.float32)
        return b

    tc = TrainConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps,
                     param_dtype=args.param_dtype,
                     compute_dtype="float32" if args.smoke else "bfloat16")
    if args.grad_compression == "int8_ef":
        tc = TrainConfig(**{**tc.__dict__})
    state = train_state_init(model, jax.random.PRNGKey(0), tc)
    if args.grad_compression == "int8_ef":
        from repro.training import compression as C
        state["ef"] = C.ef_init(state["params"])
    step_fn = jax.jit(make_train_step(model, tc, dist=dist, accum=args.accum,
                                      grad_compression=args.grad_compression))
    drv = TrainingDriver(
        step_fn, state, batch_fn,
        DriverConfig(ckpt_dir=args.ckpt_dir
                     or tempfile.mkdtemp(prefix="repro_train_"),
                     ckpt_every=args.ckpt_every))
    t0 = time.time()
    drv.run(args.steps)
    dt = time.time() - t0
    losses = [m["loss"] for m in drv.metrics_log]
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({args.steps*args.batch*args.seq/dt:,.0f} tok/s); "
          f"loss {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
