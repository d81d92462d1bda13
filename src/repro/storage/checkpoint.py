"""Distributed checkpointing on the DDS storage path.

Division of labor follows the paper's partial-offload policy (§3):

  * **Saves** are complex, durable, and batched — they take the HOST path
    (DDS front-end library -> DMA rings -> DPU file service).  Saves can be
    asynchronous (write-behind thread), so the train loop never blocks on
    storage: the paper's non-blocking WriteFile + notification groups.

  * **Restores** are simple cold reads — exactly what DDS offloads.  Byte
    ranges of checkpoint files are read back, optionally *resharded onto a
    different mesh* (elastic restart after losing nodes): each host reads
    only the contiguous ranges its new shards need.

Atomic commit: leaf files are written first, the JSON manifest is written
LAST and fsync'd; a checkpoint without a manifest is invisible.  This gives
crash consistency without rename support in the segment FS.
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

import jax

from repro.core.dds_server import DDSStorageServer, ServerConfig


def _leaf_paths(tree: Any) -> list[tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((name or "leaf", leaf))
    return out


@dataclass
class CheckpointInfo:
    step: int
    nbytes: int
    wall_s: float
    leaves: int


class CheckpointManager:
    """Save/restore pytrees to a DDS storage server."""

    MANIFEST_PREFIX = "manifest-"

    def __init__(self, server: DDSStorageServer, keep: int = 3):
        self.server = server
        self.keep = keep
        self._history: list[CheckpointInfo] = []
        self._async_thread: threading.Thread | None = None
        self._async_err: list[BaseException] = []
        self._lock = threading.Lock()

    @classmethod
    def sized_for(cls, tree: Any, keep: int = 3) -> "CheckpointManager":
        """A manager on a new server whose device holds ``keep + 1``
        checkpoints of trees shaped like ``tree`` (arrays or
        ShapeDtypeStructs): a save lands before the oldest is collected."""
        cfg = ServerConfig()
        seg = cfg.segment_size
        per_ckpt = seg                                 # the manifest
        for leaf in jax.tree_util.tree_leaves(tree):
            nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            per_ckpt += -(-nbytes // seg) * seg        # files own whole segments
        reserved = (2 + cfg.journal_segments) * seg    # metadata + journal + spare
        cfg = ServerConfig(device_capacity=(keep + 1) * per_ckpt + reserved)
        return cls(DDSStorageServer(cfg), keep=keep)

    # -- save -------------------------------------------------------------------------
    def save(self, step: int, tree: Any) -> CheckpointInfo:
        t0 = time.perf_counter()
        fe = self.server.frontend
        leaves = _leaf_paths(tree)
        manifest: dict[str, Any] = {"step": step, "leaves": {}}
        total = 0
        for name, leaf in leaves:
            arr = np.asarray(jax.device_get(leaf))
            raw = arr.tobytes()
            fid = fe.create_file(f"ckpt-{step}/{name}")
            fe.write_sync(fid, 0, raw)
            manifest["leaves"][name] = {
                "file_id": fid, "shape": list(arr.shape),
                "dtype": str(arr.dtype), "nbytes": len(raw),
            }
            total += len(raw)
        # Commit point: manifest written last + metadata fsync.
        mid = fe.create_file(f"{self.MANIFEST_PREFIX}{step}")
        fe.write_sync(mid, 0, json.dumps(manifest).encode())
        fe.fsync()
        self.server.run_until_idle()
        info = CheckpointInfo(step, total, time.perf_counter() - t0, len(leaves))
        with self._lock:
            self._history.append(info)
        self._gc()
        return info

    def save_async(self, step: int, tree: Any) -> None:
        """Write-behind save; call ``wait_async`` before depending on it."""
        self.wait_async()
        host_tree = jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)),
                                           tree)

        def work():
            try:
                self.save(step, host_tree)
            except BaseException as e:  # surfaced by wait_async
                self._async_err.append(e)

        self._async_thread = threading.Thread(target=work, daemon=True,
                                              name=f"ckpt-save-{step}")
        self._async_thread.start()

    def wait_async(self) -> None:
        if self._async_thread is not None:
            self._async_thread.join()
            self._async_thread = None
        if self._async_err:
            raise self._async_err.pop()

    # -- discovery ------------------------------------------------------------------------
    def _manifests(self) -> dict[int, int]:
        """step -> manifest file id, scanning the root directory."""
        out = {}
        for fid, meta in self.server.fs.files.items():
            if meta.name.startswith(self.MANIFEST_PREFIX):
                try:
                    out[int(meta.name[len(self.MANIFEST_PREFIX):])] = fid
                except ValueError:
                    pass
        return out

    def latest_step(self) -> int | None:
        steps = self._manifests()
        return max(steps) if steps else None

    def _read_manifest(self, step: int) -> dict:
        mid = self._manifests().get(step)
        if mid is None:
            raise FileNotFoundError(f"no committed checkpoint for step {step}")
        size = self.server.fs.file_size(mid)
        raw = self.server.frontend.read_sync(mid, 0, size)
        return json.loads(raw.decode())

    # -- restore -----------------------------------------------------------------------------
    def restore(self, step: int, template: Any | None = None) -> Any:
        """Full restore.  With ``template``, returns a matching pytree."""
        manifest = self._read_manifest(step)
        arrays: dict[str, np.ndarray] = {}
        for name, m in manifest["leaves"].items():
            raw = self.server.frontend.read_sync(m["file_id"], 0, m["nbytes"])
            arrays[name] = np.frombuffer(raw, dtype=m["dtype"]).reshape(m["shape"])
        if template is None:
            return arrays
        out_leaves = []
        for name, _ in _leaf_paths(template):
            if name not in arrays:
                raise KeyError(f"checkpoint missing leaf {name}")
            out_leaves.append(arrays[name])
        treedef = jax.tree_util.tree_structure(template)
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    def restore_shard(self, step: int, name: str,
                      start_row: int, end_row: int) -> np.ndarray:
        """Elastic restore: read ONLY the byte range of rows [start, end).

        Row-sharding over axis 0 (FSDP) makes each shard a contiguous byte
        range — the cold, simple read the DPU offload path is built for.
        A new mesh shape just changes the (start,end) each host requests.
        """
        manifest = self._read_manifest(step)
        m = manifest["leaves"][name]
        shape, dtype = m["shape"], np.dtype(m["dtype"])
        if not shape:
            raise ValueError("cannot row-shard a scalar leaf")
        row_bytes = int(np.prod(shape[1:], dtype=np.int64)) * dtype.itemsize
        off = start_row * row_bytes
        n = (end_row - start_row) * row_bytes
        raw = self.server.frontend.read_sync(m["file_id"], off, n)
        return np.frombuffer(raw, dtype=dtype).reshape([end_row - start_row]
                                                       + shape[1:])

    def restore_elastic(self, step: int, template: Any,
                        shard_index: int, num_shards: int) -> Any:
        """Restore this host's row-shards for a num_shards-way layout."""
        out_leaves = []
        for name, leaf in _leaf_paths(template):
            shape = np.shape(leaf)
            if not shape or shape[0] % num_shards != 0:
                out_leaves.append(np.asarray(self.restore(step)[name]))
                continue
            rows = shape[0] // num_shards
            out_leaves.append(self.restore_shard(
                step, name, shard_index * rows, (shard_index + 1) * rows))
        treedef = jax.tree_util.tree_structure(template)
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    # -- retention -----------------------------------------------------------------------------
    def _gc(self) -> None:
        steps = sorted(self._manifests())
        fe = self.server.frontend
        while len(steps) > self.keep:
            victim = steps.pop(0)
            manifest = self._read_manifest(victim)
            mid = self._manifests()[victim]
            for m in manifest["leaves"].values():
                fe.delete_file(m["file_id"])
            fe.delete_file(mid)
        self.server.run_until_idle()
