"""Decoder-only LM, generic over per-layer block types; scanned layer groups.

Layer organization (compile-time-friendly for 80-layer models):

    [head: n_dense_head unrolled layers]        (e.g. DeepSeek's dense layer 0)
    [groups: n_groups x block_pattern, lax.scan over stacked params]
    [tail: remainder layers, unrolled]          (e.g. recurrentgemma's 26 % 3)

``lax.scan`` over layer groups keeps the HLO size O(1) in depth — essential
for dry-run compiles of the 40-80 layer configs — and composes with
``jax.checkpoint`` (remat per group) for training memory.

Block types: "attn" (global causal GQA), "local_attn" (sliding window),
"rglru" (RecurrentGemma recurrent block), "rwkv" (RWKV6 time+channel mix).
Any attention block can carry a dense MLP or a MoE FFN (expert-parallel
under shard_map when a DistContext is provided).

Three execution modes share the same block code:
    train   — full sequence, no cache;
    prefill — full sequence, returns per-layer caches;
    decode  — one token against caches (KV / ring / recurrent state).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import recurrent as R
from repro.models import layers as L
from repro.models.dist import DistContext
from repro.models.moe import moe_apply, moe_init
from jax.sharding import PartitionSpec as P

ZERO_AUX = {"load_balance": 0.0, "router_z": 0.0}


def _aux_zeros():
    return {k: jnp.zeros((), jnp.float32) for k in ZERO_AUX}


def _aux_add(a, b):
    return {k: a[k] + b[k] for k in a}


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def block_init(key, cfg: ArchConfig, btype: str, use_moe: bool,
               dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    norm_init, _ = L.make_norm(cfg.norm)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p: Dict[str, Any] = {"norm1": norm_init(d, dtype)}
    if btype in ("attn", "local_attn", "attn_cross", "enc_attn"):
        p["attn"] = A.attn_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads, hd,
                                qkv_bias=cfg.qkv_bias, dtype=dtype)
        if btype == "attn_cross":
            p["norm_x"] = norm_init(d, dtype)
            p["cross"] = A.cross_attn_init(ks[2], d, cfg.n_heads,
                                           cfg.n_kv_heads, hd, dtype=dtype)
        p["norm2"] = norm_init(d, dtype)
        if use_moe:
            p["moe"] = moe_init(ks[1], d, cfg.moe, cfg.mlp, dtype=dtype)
        else:
            p["mlp"] = L.mlp_init(ks[1], d, cfg.d_ff, cfg.mlp, dtype=dtype)
    elif btype == "rglru":
        p["rec"] = R.rglru_block_init(ks[0], d, cfg.rnn_width or d,
                                      cfg.conv_width, dtype=dtype)
        p["norm2"] = norm_init(d, dtype)
        p["mlp"] = L.mlp_init(ks[1], d, cfg.d_ff, cfg.mlp, dtype=dtype)
    elif btype == "rwkv":
        p["tmix"] = R.rwkv_time_mix_init(ks[0], d, cfg.rnn_heads, dtype=dtype)
        p["norm2"] = norm_init(d, dtype)
        p["cmix"] = R.rwkv_channel_mix_init(ks[1], d, cfg.d_ff, dtype=dtype)
    else:
        raise ValueError(btype)
    return p


# ---------------------------------------------------------------------------
# Cache init (shapes only — also used for dry-run ShapeDtypeStructs)
# ---------------------------------------------------------------------------

def block_cache_init(cfg: ArchConfig, btype: str, batch: int, max_len: int,
                     enc_len: int = 0, dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    kv = cfg.n_kv_heads
    if btype == "attn" or btype == "attn_cross":
        c = {"k": jnp.zeros((batch, max_len, kv, hd), dtype),
             "v": jnp.zeros((batch, max_len, kv, hd), dtype)}
        if btype == "attn_cross":
            c["ck"] = jnp.zeros((batch, enc_len, kv, hd), dtype)
            c["cv"] = jnp.zeros((batch, enc_len, kv, hd), dtype)
        return c
    if btype == "local_attn":
        w = cfg.window
        return {"k": jnp.zeros((batch, w, kv, hd), dtype),
                "v": jnp.zeros((batch, w, kv, hd), dtype),
                "rpos": jnp.full((w,), -1, jnp.int32)}
    if btype == "rglru":
        rw = cfg.rnn_width or cfg.d_model
        return {"h": jnp.zeros((batch, rw), jnp.float32),
                "conv": jnp.zeros((batch, cfg.conv_width - 1, rw), jnp.float32)}
    if btype == "rwkv":
        hd_r = cfg.d_model // cfg.rnn_heads
        return {"wkv": jnp.zeros((batch, cfg.rnn_heads, hd_r, hd_r), jnp.float32),
                "shift_tm": jnp.zeros((batch, cfg.d_model), jnp.float32),
                "shift_cm": jnp.zeros((batch, cfg.d_model), jnp.float32)}
    raise ValueError(btype)


# ---------------------------------------------------------------------------
# Block apply — shared by train / prefill / decode
# ---------------------------------------------------------------------------

def _ffn(p, x, cfg: ArchConfig, dist: Optional[DistContext]):
    """Dense MLP or expert-parallel MoE; returns (out, aux)."""
    if "moe" not in p:
        return L.mlp_apply(p["mlp"], x, cfg.mlp), _aux_zeros()
    if dist is None:
        out, aux = moe_apply(p["moe"], x, cfg.moe, cfg.mlp, ep_axis=None)
        return out, {k: aux[k].astype(jnp.float32) for k in aux}

    mA, dA = dist.model_axis, dist.batch_spec
    moe_p = p["moe"]
    moe_specs = {}
    for k, v in moe_p.items():
        if k in ("w_gate", "w_up", "w_down"):
            moe_specs[k] = P(mA, None, None)           # experts over model (EP)
        elif k == "shared":
            moe_specs[k] = {"w_gate": P(None, mA), "w_up": P(None, mA),
                            "w_down": P(mA, None)}     # Megatron-sharded
            moe_specs[k] = {kk: moe_specs[k].get(kk, P(None, None))
                            for kk in v}
        else:
            moe_specs[k] = P(*([None] * v.ndim))
    in_specs = (moe_specs, P(dA, None, None))

    def body(mp, xs):
        out, aux = moe_apply(mp, xs, cfg.moe, cfg.mlp, ep_axis=mA)
        aux = {k: jax.lax.pmean(aux[k], tuple(dist.data_axes)) for k in aux}
        return out, aux

    fn = jax.shard_map(body, mesh=dist.mesh, in_specs=in_specs,
                       out_specs=(P(dA, None, None),
                                  {k: P() for k in ZERO_AUX}),
                       check_vma=False)
    out, aux = fn(moe_p, x)
    return out, aux


def _constrain_attn(q, k, v, cfg: ArchConfig, dist: Optional[DistContext]):
    """Pin the GQA attention layout so XLA never shards the QK contraction.

    heads % tp == 0 -> Q head-sharded; KV head-sharded if kv % tp == 0 else
    replicated (standard GQA-TP with kv < tp).
    heads % tp != 0 (e.g. llama4's 40 on a 16-way axis) -> sequence-shard Q
    and replicate KV: attention runs fully local per sequence slice.
    """
    if dist is None or not dist.attn_shard:
        return q, k, v
    tp = dist.mesh.shape[dist.model_axis]
    b, mA = dist.batch_spec, dist.model_axis
    if cfg.n_heads % tp == 0:
        # XLA's propagation already handles divisible heads well; forcing
        # KV replication here was measured WORSE (+8% collective on
        # qwen2/internlm2 — §Perf iteration T1-refuted). Leave it alone.
        return q, k, v
    # pathological case (e.g. llama4: 40 heads on a 16-way axis): without
    # constraints XLA shards the QK contraction and all-reduces fp32 logits
    # inside the attention scan (44s collective term). Sequence-shard Q and
    # replicate KV: attention is then fully local per sequence slice.
    q = dist.constrain(q, P(b, mA, None, None))
    k = dist.constrain(k, P(b, None, None, None))
    v = dist.constrain(v, P(b, None, None, None))
    return q, k, v


def block_apply(p, x, btype: str, cfg: ArchConfig, *,
                cos_sin=None, mode: str = "train",
                dist: Optional[DistContext] = None,
                cache=None, pos=None, enc_out=None,
                attn_schedule: str = "scan",
                q_offset=0, max_len: Optional[int] = None):
    """Apply one block. Returns (x, new_cache, aux)."""
    _, norm = L.make_norm(cfg.norm)
    hd = cfg.resolved_head_dim
    aux = _aux_zeros()
    new_cache = cache
    enc_kv = None

    if btype in ("attn", "local_attn", "attn_cross", "enc_attn"):
        h = norm(p["norm1"], x)
        q, k, v = A.qkv_project(p["attn"], h, cfg.n_heads, cfg.n_kv_heads, hd)
        cos, sin = cos_sin
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        if mode != "decode":
            q, k, v = _constrain_attn(q, k, v, cfg, dist)
        if mode == "decode":
            if btype == "local_attn":
                slot = pos % cfg.window
                ck = jax.lax.dynamic_update_index_in_dim(cache["k"], k[:, 0], slot, 1)
                cv = jax.lax.dynamic_update_index_in_dim(cache["v"], v[:, 0], slot, 1)
                rpos = jax.lax.dynamic_update_index_in_dim(
                    cache["rpos"], jnp.asarray(pos, jnp.int32), slot, 0)
                att = A.sdpa_decode_ring(q, ck, cv, rpos, pos, cfg.window)
                new_cache = {"k": ck, "v": cv, "rpos": rpos}
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), pos, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), pos, 1)
                att = A.sdpa_decode(q, ck, cv, pos + 1)
                new_cache = dict(cache, k=ck, v=cv)
        elif btype == "local_attn":
            att = A.sdpa_local(q, k, v, window=cfg.window, q_offset=q_offset)
        else:
            att = A.sdpa(q, k, v, causal=(btype != "enc_attn"),
                         q_offset=q_offset, schedule=attn_schedule)
        B, S = x.shape[0], x.shape[1]
        att = att.reshape(B, S, cfg.n_heads * hd) @ p["attn"]["wo"].astype(x.dtype)
        x = x + att
        if btype == "attn_cross":
            hx = norm(p["norm_x"], x)
            if mode == "decode":
                ek, ev = cache["ck"], cache["cv"]
            else:
                ek, ev = A.project_enc_kv(p["cross"], enc_out,
                                          cfg.n_kv_heads, hd)
            enc_kv = (ek, ev)
            x = x + A.cross_attend(p["cross"], hx, ek, ev, cfg.n_heads,
                                   cfg.n_kv_heads, hd)
        h2 = norm(p["norm2"], x)
        f, aux = _ffn(p, h2, cfg, dist)
        x = x + f
        if mode == "prefill":
            new_cache = _harvest_attn_cache(cfg, btype, k, v, enc_kv,
                                            max_len=max_len)

    elif btype == "rglru":
        h = norm(p["norm1"], x)
        if mode == "decode":
            out, hs, buf = R.rglru_block_step(p["rec"], h[:, 0],
                                              cache["h"], cache["conv"])
            x = x + out[:, None]
            new_cache = {"h": hs, "conv": buf}
        elif mode == "prefill":
            out, (hs, buf) = R.rglru_block_apply(p["rec"], h, return_state=True)
            x = x + out
            new_cache = {"h": hs, "conv": buf}
        else:
            x = x + R.rglru_block_apply(p["rec"], h)
        h2 = norm(p["norm2"], x)
        f, aux = _ffn(p, h2, cfg, dist)
        x = x + f

    elif btype == "rwkv":
        h = norm(p["norm1"], x)
        if mode == "decode":
            out, (wkv, sh) = R.rwkv_time_mix_step(
                p["tmix"], h[:, 0], (cache["wkv"], cache["shift_tm"]), cfg.rnn_heads)
            x = x + out[:, None]
            h2 = norm(p["norm2"], x)
            cout, sh_c = R.rwkv_channel_mix_step(p["cmix"], h2[:, 0],
                                                 cache["shift_cm"])
            x = x + cout[:, None]
            new_cache = {"wkv": wkv, "shift_tm": sh.astype(jnp.float32),
                         "shift_cm": sh_c.astype(jnp.float32)}
        elif mode == "prefill":
            out, (wkv, sh) = R.rwkv_time_mix_apply(
                p["tmix"], h, cfg.rnn_heads, state=None, return_state=True)
            x = x + out
            h2 = norm(p["norm2"], x)
            cout, sh_c = R.rwkv_channel_mix_apply(p["cmix"], h2,
                                                  return_state=True)
            x = x + cout
            new_cache = {"wkv": wkv, "shift_tm": sh.astype(jnp.float32),
                         "shift_cm": sh_c.astype(jnp.float32)}
        else:
            x = x + R.rwkv_time_mix_apply(p["tmix"], h, cfg.rnn_heads)
            h2 = norm(p["norm2"], x)
            x = x + R.rwkv_channel_mix_apply(p["cmix"], h2)
    else:
        raise ValueError(btype)

    if dist is not None:
        x = dist.activations(x)
    return x, new_cache, aux


def _harvest_attn_cache(cfg, btype, k, v, enc_kv, max_len=None):
    """Build the decode cache from prefill-computed K/V (post-RoPE).

    Global-attention caches are padded out to ``max_len`` so subsequent
    decode steps can extend them in place."""
    B, S = k.shape[0], k.shape[1]
    if btype == "local_attn":
        w = cfg.window
        # ring slot j holds the latest position p < S with p % w == j
        j = jnp.arange(w)
        last = S - 1 - ((S - 1 - j) % w)
        filled = (j < S) if S < w else jnp.ones((w,), bool)
        idx = jnp.clip(last, 0, S - 1)
        rk = jnp.take(k, idx, axis=1)
        rv = jnp.take(v, idx, axis=1)
        rpos = jnp.where(filled, last, -1).astype(jnp.int32)
        zero = jnp.zeros_like(rk)
        rk = jnp.where(filled[None, :, None, None], rk, zero)
        rv = jnp.where(filled[None, :, None, None], rv, zero)
        return {"k": rk, "v": rv, "rpos": rpos}
    if max_len is not None and max_len > S:
        pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    c = {"k": k, "v": v}
    if btype == "attn_cross":
        c["ck"], c["cv"] = enc_kv
    return c


# ---------------------------------------------------------------------------
# Parameter init for the whole LM
# ---------------------------------------------------------------------------

def _layer_plan(cfg: ArchConfig):
    """(head_types, pattern, n_groups, tail_types)."""
    types = list(cfg.layer_types())
    head = types[: cfg.n_dense_head]
    rest = types[cfg.n_dense_head:]
    p = len(cfg.block_pattern)
    n_groups = len(rest) // p
    tail = rest[n_groups * p:]
    return head, list(cfg.block_pattern), n_groups, tail


def lm_init(key, cfg: ArchConfig, dtype=jnp.float32):
    head, pattern, n_groups, tail = _layer_plan(cfg)
    norm_init, _ = L.make_norm(cfg.norm)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": L.embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.lm_head_init(keys[1], cfg.d_model, cfg.padded_vocab, dtype)
    moe_on = cfg.moe is not None
    params["head"] = [
        block_init(k, cfg, t, use_moe=False, dtype=dtype)
        for k, t in zip(jax.random.split(keys[2], max(len(head), 1)), head)]
    if n_groups > 0:
        gkeys = jax.random.split(keys[3], n_groups)
        params["groups"] = {
            str(i): jax.vmap(
                lambda kk, i=i: block_init(jax.random.fold_in(kk, i), cfg,
                                           pattern[i], use_moe=moe_on,
                                           dtype=dtype))(gkeys)
            for i in range(len(pattern))}
    else:
        params["groups"] = {}
    params["tail"] = [
        block_init(k, cfg, t, use_moe=moe_on, dtype=dtype)
        for k, t in zip(jax.random.split(keys[4], max(len(tail), 1)), tail)]
    return params


def lm_cache_init(cfg: ArchConfig, batch: int, max_len: int, enc_len: int = 0,
                  dtype=jnp.bfloat16):
    head, pattern, n_groups, tail = _layer_plan(cfg)
    mk = lambda t: block_cache_init(cfg, t, batch, max_len, enc_len, dtype)
    cache = {"head": [mk(t) for t in head]}
    cache["groups"] = {
        str(i): jax.tree.map(lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape),
                             mk(pattern[i]))
        for i in range(len(pattern))} if n_groups else {}
    cache["tail"] = [mk(t) for t in tail]
    return cache


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg, tokens, prefix, compute_dtype):
    x = L.embed_lookup(params["embed"], tokens, compute_dtype)
    if cfg.tie_embeddings:
        x = x * float(np.sqrt(cfg.d_model))   # weak scalar: keeps bf16
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(compute_dtype), x], axis=1)
    return x


def _rope_for(cfg, positions):
    return L.rope_table(positions, cfg.resolved_head_dim, cfg.rope_theta)


def lm_forward(params, cfg: ArchConfig, tokens, *, prefix=None,
               dist: Optional[DistContext] = None,
               compute_dtype=jnp.bfloat16, remat: str = "block",
               attn_schedule: str = "scan", mode: str = "train",
               cache=None, pos=None, max_len: Optional[int] = None):
    """Modes: train -> (logits, aux); prefill -> (logits, aux, cache);
    decode -> (logits, cache): tokens (B, 1), pos = current length."""
    head, pattern, n_groups, tail = _layer_plan(cfg)
    x = _embed_inputs(params, cfg, tokens, prefix, compute_dtype)
    B, S = x.shape[0], x.shape[1]
    if mode == "decode":
        positions = jnp.full((B, 1), pos, jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cos_sin = _rope_for(cfg, positions)
    if dist is not None:
        x = dist.activations(x)

    aux_tot = _aux_zeros()
    kw = dict(cfg=cfg, cos_sin=cos_sin, mode=mode, dist=dist,
              attn_schedule=attn_schedule, pos=pos, max_len=max_len)

    new_cache = {"head": [], "groups": {}, "tail": []} if mode != "train" else None

    for i, t in enumerate(head):
        c = cache["head"][i] if cache is not None else None
        x, nc, aux = block_apply(params["head"][i], x, t, cache=c, **kw)
        aux_tot = _aux_add(aux_tot, aux)
        if new_cache is not None:
            new_cache["head"].append(nc)

    if n_groups > 0:
        def group_body(carry, xs):
            x, aux_acc = carry
            ncs = {}
            for gi, t in enumerate(pattern):
                c = xs["cache"][str(gi)] if "cache" in xs else None
                x, nc, aux = block_apply(xs["params"][str(gi)], x, t,
                                         cache=c, **kw)
                aux_acc = _aux_add(aux_acc, aux)
                if mode != "train":
                    ncs[str(gi)] = nc
            return (x, aux_acc), (ncs if mode != "train" else 0)

        body = group_body
        if remat == "block" and mode == "train":
            body = jax.checkpoint(group_body)
        xs = {"params": params["groups"]}
        if cache is not None:
            xs["cache"] = cache["groups"]
        (x, aux_tot), ys = jax.lax.scan(body, (x, aux_tot), xs)
        if mode != "train":
            new_cache["groups"] = ys

    for i, t in enumerate(tail):
        c = cache["tail"][i] if cache is not None else None
        x, nc, aux = block_apply(params["tail"][i], x, t, cache=c, **kw)
        aux_tot = _aux_add(aux_tot, aux)
        if new_cache is not None:
            new_cache["tail"].append(nc)

    _, norm = L.make_norm(cfg.norm)
    x = norm(params["final_norm"], x)
    logits = L.logits_from(params.get("lm_head"), x, params["embed"])
    if dist is not None:
        logits = dist.constrain(logits, P(dist.batch_spec, None, dist.model_axis))

    if mode == "train":
        return logits, aux_tot
    if mode == "prefill":
        return logits, aux_tot, new_cache
    return logits, new_cache
