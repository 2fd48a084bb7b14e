"""Sharded, atomic, async-capable checkpointing with reshard-on-restore.

Layout (one directory per step):

    <dir>/step_000123/
        manifest.json      {key: {file, shape, dtype}}, step, extra metadata
        000000.npy ...     one .npy per pytree leaf
        DONE               commit marker (atomicity: written last)

* **Atomic**: writers fill ``step_X.tmp`` then rename; readers only trust
  directories containing DONE. A crash mid-save never corrupts the latest
  good checkpoint (exercised by runtime.fault_tolerance tests).
* **Async**: ``save(..., sync=False)`` snapshots device arrays to host
  memory, then writes on a background thread — the train loop keeps going
  (the standard hide-the-checkpoint-latency trick).
* **Resharding**: ``restore(..., shardings=...)`` device_puts each leaf with
  the *target* sharding, so a job can restart on a different mesh shape
  (elastic scaling) or device count. On a multi-host pod each process would
  write its addressable shards; the manifest format already carries
  per-leaf metadata to support that split.
* **Filters**: a :class:`repro.api.Filter` is a registered pytree (its word
  array is the only leaf), so it checkpoints inline with the rest of the
  train state. ``save_filter``/``restore_filter`` additionally store the
  *engine-independent* canonical state, so a filter written by one engine
  (e.g. ``sharded`` on a pod) restores into another (``jnp`` on one host).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, Optional

import ml_dtypes
import numpy as np
import jax

# numpy can't serialize bf16 etc. natively; store bit patterns + logical dtype
_EXTENDED = {"bfloat16": (ml_dtypes.bfloat16, np.uint16),
             "float8_e4m3fn": (ml_dtypes.float8_e4m3fn, np.uint8),
             "float8_e5m2": (ml_dtypes.float8_e5m2, np.uint8)}


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]
    return keys, [l for _, l in flat], treedef


def _holds_filter(tree) -> bool:
    from repro.api import Filter
    return any(isinstance(x, Filter) for x in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, Filter)))


def save(ckpt_dir: str, step: int, state: Any, *, sync: bool = True,
         keep: int = 3, extra: Optional[Dict] = None):
    """Write ``state`` (any pytree of arrays) atomically under ckpt_dir."""
    keys, leaves, _ = _flatten(state)
    if _holds_filter(state):
        from repro.core.hashing import HASH_LAYOUT
        extra = {**(extra or {}), "filter_hash_layout": HASH_LAYOUT}
    # snapshot to host BEFORE going async — device buffers may be donated
    host_leaves = [np.asarray(l) for l in leaves]

    def _write():
        final = os.path.join(ckpt_dir, f"step_{step:08d}")
        tmp = final + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        manifest = {"step": step, "extra": extra or {}, "leaves": {}}
        for i, (k, arr) in enumerate(zip(keys, host_leaves)):
            fname = f"{i:06d}.npy"
            logical = str(arr.dtype)
            if logical in _EXTENDED:
                arr = arr.view(_EXTENDED[logical][1])
            np.save(os.path.join(tmp, fname), arr)
            manifest["leaves"][k] = {"file": fname,
                                     "shape": list(arr.shape),
                                     "dtype": logical}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        with open(os.path.join(tmp, "DONE"), "w") as f:
            f.write("ok")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        _gc(ckpt_dir, keep)

    if sync:
        _write()
        return None
    t = threading.Thread(target=_write, daemon=True)
    t.start()
    return t


def _gc(ckpt_dir: str, keep: int):
    steps = sorted(_list_steps(ckpt_dir))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)


def _list_steps(ckpt_dir: str):
    out = []
    if not os.path.isdir(ckpt_dir):
        return out
    for d in os.listdir(ckpt_dir):
        full = os.path.join(ckpt_dir, d)
        if (d.startswith("step_") and not d.endswith(".tmp")
                and os.path.exists(os.path.join(full, "DONE"))):
            out.append(int(d[5:]))
    return out


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = _list_steps(ckpt_dir)
    return max(steps) if steps else None


def save_filter(ckpt_dir: str, step: int, filt, *, sync: bool = True,
                keep: int = 3, extra: Optional[Dict] = None):
    """Checkpoint a ``repro.api.Filter`` in engine-independent form.

    The dense word array is the only array leaf (banks keep their leading
    bank dims on it); spec + engine name + bank shape + ring geometry
    travel in the manifest's ``extra`` metadata, so ``restore_filter`` can
    rebuild on any engine (filter migration across deployment shapes).
    ``extra`` adds caller metadata (JSON-able) to the manifest — the
    service subsystem records its replay cursor there, read back via
    :func:`manifest_extra`."""
    state = filt.to_state()
    extra = dict(extra or {})
    extra.update({"filter_spec": state["spec"],
                  "filter_backend": state["backend"],
                  "filter_hash_layout": state["hash_layout"]})
    if "bank_shape" in state:
        extra["filter_bank_shape"] = state["bank_shape"]
    if "options" in state:
        extra["filter_options"] = state["options"]
    leaves = {"filter_words": state["words"]}
    if "engine_state" in state:
        # stateful engines (cuckoo): the insert-failure counter is real
        # operational state and rides along as a second leaf
        leaves["filter_state"] = state["engine_state"]
    return save(ckpt_dir, step, leaves, sync=sync, keep=keep, extra=extra)


def manifest_extra(ckpt_dir: str, step: Optional[int] = None) -> Dict:
    """The ``extra`` metadata of a checkpoint's manifest (latest step by
    default) — caller metadata stored by ``save``/``save_filter``."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        return json.load(f)["extra"]


def restore_filter(ckpt_dir: str, *, step: Optional[int] = None,
                   backend: Optional[str] = None, options=None):
    """Load a filter written by ``save_filter``; returns (step, Filter).

    ``backend``/``options`` re-home the state onto a different engine than
    the one that wrote it (default: the writer's engine)."""
    from repro.api import BackendOptions, Filter
    from repro.api.filter import check_hash_layout
    from repro.core.variants import FilterSpec

    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    extra = manifest["extra"]
    check_hash_layout(extra.get("filter_hash_layout"))
    spec_d = extra["filter_spec"]
    spec = FilterSpec(**spec_d)
    words = np.load(os.path.join(d, manifest["leaves"]["filter_words"]["file"]))
    state = {"words": words, "spec": spec_d,
             "backend": extra["filter_backend"],
             "hash_layout": extra["filter_hash_layout"]}
    if "filter_state" in manifest["leaves"]:
        state["engine_state"] = np.load(
            os.path.join(d, manifest["leaves"]["filter_state"]["file"]))
    if "filter_bank_shape" in extra:
        state["bank_shape"] = extra["filter_bank_shape"]
    if "filter_options" in extra:
        state["options"] = extra["filter_options"]
    filt = Filter.from_state(state, backend=backend,
                             options=options or BackendOptions())
    assert filt.spec == spec
    return step, filt


def restore(ckpt_dir: str, template: Any, *, step: Optional[int] = None,
            shardings: Any = None):
    """Load into the structure of ``template``; returns (step, state).

    ``shardings``: optional pytree (matching template) of Sharding objects —
    leaves are device_put with them (reshard-on-restore / elastic restart).
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    if _holds_filter(template):
        from repro.api.filter import check_hash_layout
        check_hash_layout(manifest["extra"].get("filter_hash_layout"))
    keys, leaves, treedef = _flatten(template)
    shard_leaves = (jax.tree.leaves(shardings,
                                    is_leaf=lambda x: hasattr(x, "device_set"))
                    if shardings is not None else [None] * len(leaves))
    out = []
    for k, tmpl, shd in zip(keys, leaves, shard_leaves):
        meta = manifest["leaves"].get(k)
        if meta is None:
            raise KeyError(f"checkpoint missing leaf {k!r}")
        arr = np.load(os.path.join(d, meta["file"]))
        if meta["dtype"] in _EXTENDED:
            arr = arr.view(_EXTENDED[meta["dtype"]][0])
        assert list(arr.shape) == list(tmpl.shape), (k, arr.shape, tmpl.shape)
        out.append(jax.device_put(arr, shd) if shd is not None
                   else jax.device_put(arr))
    return step, jax.tree_util.tree_unflatten(treedef, out)
