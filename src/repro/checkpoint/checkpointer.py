"""Atomic sharded checkpointing with auto-resume.

Layout:  <dir>/step_<N>/  holding one ``.npy`` per pytree leaf plus a
``manifest.json`` (tree structure, shapes, dtypes, data-pipeline cursor).
Writes go to ``step_<N>.tmp`` and are renamed only after fsync — a crashed
write never corrupts the latest checkpoint (the fault-tolerance contract:
kill -9 at any moment leaves a loadable directory).

Restore places leaves directly onto the target mesh via ``jax.device_put``
with the caller's shardings — this is also the *elastic resharding* path: the
on-disk format is mesh-agnostic (full logical arrays), so a checkpoint written
on a (16, 16) mesh restores onto (2, 16, 16) or a single CPU device unchanged.
For multi-TB deployments each host would write only its address-able shards
(`jax.experimental.multihost_utils`); the manifest format is unchanged.
"""

from __future__ import annotations

import json
import os
import shutil

import jax
import numpy as np


def _flatten_with_names(tree):
    leaves, treedef = jax.tree.flatten(tree)
    paths = [
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        for path, _ in jax.tree.flatten_with_path(tree)[0]
    ]
    return leaves, paths, treedef


def save(directory: str, step: int, tree, *, extra: dict | None = None) -> str:
    """Atomically persist ``tree`` (+ json-serializable ``extra``)."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)

    leaves, paths, _ = _flatten_with_names(tree)
    manifest = {"step": step, "leaves": [], "extra": extra or {}}
    for i, (leaf, path) in enumerate(zip(leaves, paths)):
        arr = np.asarray(jax.device_get(leaf))
        fname = f"leaf_{i:05d}.npy"
        np.save(os.path.join(tmp, fname), arr)
        manifest["leaves"].append(
            {"path": path, "file": fname, "shape": list(arr.shape), "dtype": str(arr.dtype)}
        )
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and not d.endswith(".tmp")
    ]
    return max(steps) if steps else None


def restore(directory: str, step: int, like, *, shardings=None):
    """Load a checkpoint into the structure of ``like``.

    ``shardings``: optional pytree of NamedShardings parallel to ``like`` —
    leaves are device_put straight onto the (possibly different) target mesh.
    Returns (tree, extra).
    """
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)

    leaves_like, _, treedef = _flatten_with_names(like)
    if len(manifest["leaves"]) != len(leaves_like):
        raise ValueError(
            f"checkpoint has {len(manifest['leaves'])} leaves, "
            f"expected {len(leaves_like)}"
        )
    shard_leaves = (
        jax.tree.flatten(shardings)[0] if shardings is not None else [None] * len(leaves_like)
    )
    out = []
    for rec, like_leaf, shd in zip(manifest["leaves"], leaves_like, shard_leaves):
        arr = np.load(os.path.join(path, rec["file"]))
        want = tuple(getattr(like_leaf, "shape", arr.shape))
        if tuple(arr.shape) != want:
            raise ValueError(f"leaf {rec['path']}: shape {arr.shape} != {want}")
        if shd is not None:
            out.append(jax.device_put(arr, shd))
        else:
            out.append(jax.device_put(arr))
    return jax.tree.unflatten(treedef, out), manifest.get("extra", {})


def prune(directory: str, keep: int = 3) -> None:
    """Keep only the newest ``keep`` checkpoints (bounded disk)."""
    if not os.path.isdir(directory):
        return
    steps = sorted(
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and not d.endswith(".tmp")
    )
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(directory, f"step_{s:08d}"), ignore_errors=True)
