"""GPipe-style pipeline parallelism over a mesh axis (designed for "pod").

Why PP across pods: inter-pod links (DCN) are an order of magnitude slower
than intra-pod ICI, so the multi-pod mesh wants the *least chatty* axis
across pods. A pipeline boundary moves one (microbatch, seq, d_model)
activation per tick — far less than DP's full gradient all-reduce —
making PP-over-pods the bandwidth-optimal layout for >1 pod (DESIGN.md §6).

Mechanics (inside shard_map over the stage axis):

    tick t in [0, M + S - 1):                     # M microbatches, S stages
        x_in   = ppermute(y_prev, shift +1)       # activations flow down
        x_mine = select(stage == 0, microbatch[t], x_in)
        y      = stage_fn(stage_params, x_mine)   # every stage computes
        outputs collected from the last stage at ticks [S-1, S-1+M)

The schedule is the classic GPipe fill/drain: bubble fraction (S-1)/(M+S-1).
``pipeline_apply`` is generic over stage_fn so tests drive it with toy
stages and the LM integration hands it one layer-group per stage.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params_stacked, microbatches,
                   mesh: Mesh, stage_axis: str = "pod",
                   extra_specs=None):
    """Run a GPipe pipeline.

    stage_fn(stage_params, x) -> y               (one stage's compute)
    params_stacked: pytree with leading dim = n_stages (sharded on stage_axis)
    microbatches:  (M, mb, ...) input activations (replicated across stages)
    Returns (M, mb, ...) outputs from the final stage (replicated).
    """
    S = mesh.shape[stage_axis]
    M = microbatches.shape[0]
    T = M + S - 1

    def body(params_local, mb):
        # inside shard_map: params_local has leading dim 1 (this stage)
        p = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        x0 = jnp.zeros_like(mb[0])
        outs = jnp.zeros_like(mb)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            y_prev, outs = carry
            x_in = jax.lax.ppermute(y_prev, stage_axis, fwd_perm)
            # stage 0 ingests microbatch t (while t < M), others take x_in
            mb_t = mb[jnp.minimum(t, M - 1)]
            x = jnp.where(sid == 0, jnp.where(t < M, mb_t, x_in), x_in)
            y = stage_fn(p, x)
            # last stage emits microbatch t-(S-1) at tick t
            emit_idx = t - (S - 1)
            do_emit = (sid == S - 1) & (emit_idx >= 0)
            outs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(emit_idx, 0), 0),
                lambda o: o, outs)
            return (y, outs), None

        (_, outs), _ = jax.lax.scan(tick, (x0, outs), jnp.arange(T))
        # replicate final-stage outputs to every stage (replicated out_spec)
        outs = jax.lax.all_gather(outs, stage_axis, axis=0)[S - 1]
        return outs

    in_specs = (jax.tree.map(lambda _: P(stage_axis), params_stacked),
                P())
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=in_specs,
                       out_specs=P(),
                       check_vma=False)
    return fn(params_stacked, microbatches)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
