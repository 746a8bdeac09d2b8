"""Sharded checkpoint store: save/restore train state for
checkpoint/restart and *elastic* restart (restore onto a different mesh).

Layout: ``<dir>/step_<N>/manifest.json`` + one ``<leaf>.npy.zst`` per
pytree leaf (zstd-compressed).  Per-leaf files bound writer memory and
let a restore reshard leaf-by-leaf onto a new mesh — the moral equivalent
of an OCDBT/array-store layout at container scale.  ``AsyncCheckpointer``
snapshots device arrays to host, then writes on a background thread so
the train loop never blocks on disk.

Crash consistency: the manifest carries a crc32 + byte count per leaf,
every file (and the step directory) is fsynced before the atomic rename,
and readers verify.  A step torn by a crash mid-write — truncated leaf,
half-written manifest, bytes that never hit the platter — is *skipped
with a warning* by ``latest_step()``/``restore()``, which fall back to
the newest intact step instead of raising out of the very retry path
checkpoints exist to serve.  ``verify_step`` is the explicit probe.
"""
from __future__ import annotations

import json
import os
import queue
import re
import threading
import warnings
import zlib
from typing import Any, Dict, Optional

import jax
import numpy as np
import zstandard


def _fault_injector():
    # lazy lookup, not an import: repro.core.resilience pulls in the
    # session facade (which imports this module back), and a store that
    # never runs under chaos shouldn't pay for it.  If nobody imported
    # the faults module, nobody armed an injector.
    import sys

    mod = sys.modules.get("repro.core.resilience.faults")
    return mod.active() if mod is not None else None


PyTree = Any


def _decompress(codec: str, buf: bytes) -> bytes:
    if codec != "zstd":
        raise ValueError(f"unknown checkpoint codec {codec!r}")
    return zstandard.ZstdDecompressor().decompress(buf)


_SEP = "__"


def _flatten(tree: PyTree) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_path_str(p) for p in path)
        flat[key] = leaf
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


class CheckpointCorrupt(RuntimeError):
    """A checkpoint step failed verification (torn write / bit rot)."""


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    # directory fsync makes the rename itself durable; best-effort on
    # filesystems that refuse O_RDONLY dir fds
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _tear(path: str, manifest: dict, at_byte: int, leaf: int) -> None:
    """Simulate a crash that left ``path`` torn: truncate one file.

    ``leaf < 0`` tears the manifest itself; otherwise the ``leaf``-th
    leaf file (manifest order) is cut at ``at_byte``.
    """
    if leaf < 0:
        victim = os.path.join(path, "manifest.json")
    else:
        files = [m["file"] for m in manifest["leaves"].values()]
        victim = os.path.join(path, files[leaf % len(files)])
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.truncate(min(max(0, at_byte), max(0, size - 1)))


def save(directory: str, step: int, state: PyTree) -> str:
    """Synchronous save. Returns the checkpoint path.

    Durability order: leaf files + manifest are written and fsynced
    inside ``step_N.tmp``, the tmp dir is fsynced, then the atomic
    rename publishes the step and the parent dir is fsynced.  A crash
    at any point leaves either no ``step_N`` or a fully-synced one —
    and if the platter still lies, the per-leaf crc32s catch it on read.
    """
    path = os.path.join(directory, f"step_{step:08d}")
    tmp = path + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    flat = _flatten(state)
    manifest = {"step": step, "format": 2, "leaves": {}}
    for key, leaf in flat.items():
        arr = np.asarray(jax.device_get(leaf))
        payload = zstandard.ZstdCompressor(level=3).compress(
            arr.tobytes(order="C"))
        fn = re.sub(r"[^\w.\-]", "_", key) + ".npy.zst"
        manifest["leaves"][key] = {
            "file": fn, "shape": list(arr.shape), "dtype": str(arr.dtype),
            "codec": "zstd", "bytes": len(payload),
            "crc32": _zlib_crc32(payload),
        }
        fpath = os.path.join(tmp, fn)
        with open(fpath, "wb") as f:
            f.write(payload)
        _fsync_file(fpath)
    mpath = os.path.join(tmp, "manifest.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    _fsync_file(mpath)
    _fsync_dir(tmp)
    if os.path.exists(path):
        import shutil

        shutil.rmtree(path)
    os.rename(tmp, path)
    _fsync_dir(directory)
    inj = _fault_injector()
    if inj is not None:
        act = inj.fire("checkpoint.save", step=step)
        if act is not None and act["action"] == "tear":
            _tear(path, manifest, int(act.get("at_byte", 0)),
                  int(act.get("leaf", 0)))
    return path


def _zlib_crc32(payload: bytes) -> int:
    return zlib.crc32(payload) & 0xFFFFFFFF


def verify_step(directory: str, step: int) -> bool:
    """True iff ``step`` is structurally intact on disk.

    Checks: readable manifest, every leaf file present, and — for
    format-2 manifests — byte count and crc32 of each leaf's on-disk
    payload.  Pre-format-2 steps get the structural check only.
    """
    path = os.path.join(directory, f"step_{step:08d}")
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        for meta in manifest["leaves"].values():
            fpath = os.path.join(path, meta["file"])
            if "bytes" in meta and os.path.getsize(fpath) != meta["bytes"]:
                return False
            if "crc32" in meta:
                with open(fpath, "rb") as f:
                    if _zlib_crc32(f.read()) != meta["crc32"]:
                        return False
            elif not os.path.exists(fpath):
                return False
    except (OSError, ValueError, KeyError):
        return False
    return True


def _steps_on_disk(directory: str):
    if not os.path.isdir(directory):
        return []
    return sorted(
        (int(m.group(1))
         for m in (re.match(r"step_(\d+)$", d) for d in os.listdir(directory))
         if m),
        reverse=True,
    )


def latest_step(directory: str, *, verify: bool = True) -> Optional[int]:
    """Newest step — by default the newest *intact* step.

    A torn/corrupt step is skipped with a warning rather than returned:
    callers feed this straight into retry resume logic, and resuming
    from a poisoned step would crash the retry it exists to serve.
    """
    for step in _steps_on_disk(directory):
        if not verify or verify_step(directory, step):
            return step
        warnings.warn(
            f"checkpoint step {step} under {directory} is torn/corrupt; "
            f"falling back to an older step", RuntimeWarning, stacklevel=2)
    return None


def _read_step(path: str, manifest: dict, flat_like: Dict[str, Any],
               flat_shard: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for key, meta in manifest["leaves"].items():
        if key not in flat_like:
            continue
        with open(os.path.join(path, meta["file"]), "rb") as f:
            payload = f.read()
        if "crc32" in meta and _zlib_crc32(payload) != meta["crc32"]:
            raise CheckpointCorrupt(
                f"crc mismatch for leaf {key!r} in {path}")
        try:
            buf = _decompress(meta.get("codec", "zstd"), payload)
            arr = np.frombuffer(buf, dtype=np.dtype(meta["dtype"])) \
                .reshape(meta["shape"]).copy()
        except Exception as e:  # noqa: BLE001 - any decode error = torn leaf
            raise CheckpointCorrupt(
                f"torn leaf {key!r} in {path}: {e}") from e
        if key in flat_shard and flat_shard[key] is not None:
            out[key] = jax.device_put(arr, flat_shard[key])
        else:
            out[key] = jax.device_put(arr)
    return out


def restore(directory: str, like: PyTree, *, step: Optional[int] = None,
            shardings: Optional[PyTree] = None) -> PyTree:
    """Restore into the structure of ``like``.  ``shardings`` (same
    structure) re-places each leaf — pass shardings derived from a
    *different* mesh to do an elastic restart.

    With ``step=None`` a torn/corrupt newest step is skipped (with a
    warning) in favour of the newest intact one; an explicitly
    requested step raises :class:`CheckpointCorrupt` instead.
    """
    candidates = [step] if step is not None else _steps_on_disk(directory)
    if not candidates:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    flat_like = _flatten(like)
    flat_shard = _flatten(shardings) if shardings is not None else {}
    last_err: Optional[Exception] = None
    for cand in candidates:
        path = os.path.join(directory, f"step_{cand:08d}")
        try:
            try:
                with open(os.path.join(path, "manifest.json")) as f:
                    manifest = json.load(f)
            except (OSError, ValueError) as e:
                raise CheckpointCorrupt(
                    f"unreadable manifest in {path}: {e}") from e
            out = _read_step(path, manifest, flat_like, flat_shard)
        except CheckpointCorrupt as e:
            if step is not None:
                raise
            warnings.warn(
                f"skipping torn/corrupt checkpoint step {cand}: {e}",
                RuntimeWarning, stacklevel=2)
            last_err = e
            continue
        missing = set(flat_like) - set(out)
        if missing:
            raise KeyError(
                f"checkpoint missing leaves: {sorted(missing)[:5]} ...")
        # unflatten back into `like`'s treedef
        leaves_in_order = []
        for path_, _ in jax.tree_util.tree_flatten_with_path(like)[0]:
            key = _SEP.join(_path_str(p) for p in path_)
            leaves_in_order.append(out[key])
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, leaves_in_order)
    raise CheckpointCorrupt(
        f"every checkpoint step under {directory} is torn/corrupt "
        f"(last error: {last_err})")


class AsyncCheckpointer:
    """Snapshot-on-call, write-in-background checkpointing."""

    def __init__(self, directory: str, *, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def save(self, step: int, state: PyTree) -> None:
        if self._err:
            raise self._err
        host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
        self._q.put((step, host_state))  # blocks only if 2 writes queued

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            step, state = item
            try:
                save(self.directory, step, state)
                self._gc()
            except BaseException as e:  # noqa: BLE001
                self._err = e

    def _gc(self):
        steps = sorted(
            int(m.group(1))
            for m in (re.match(r"step_(\d+)$", d) for d in os.listdir(self.directory))
            if m
        )
        import shutil

        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    def wait(self):
        import time

        while not self._q.empty():
            time.sleep(0.01)
        if self._err:
            raise self._err

    def close(self):
        self._q.put(None)
        self._thread.join(timeout=30)
        if self._err:
            raise self._err
