"""Checkpoint serialization: framed tensors + shard manifest.

Every leaf of the training state becomes one self-describing framed blob
(lossless via core/codecs, or lossy via core/lossy for leaves the policy
allows — optimizer moments by default). A JSON manifest binds the tree
structure to stored bytes and records mesh/topology metadata so a restart
can *reshard elastically*: arrays are restored logically and re-placed under
whatever mesh the resumed job has (the paper's checkpoint/restart-for-
walltime story, plus elasticity).

Layout v2 (packed shards — the default):
    <dir>/step_000123/
        manifest.json        {step, format: 2, leaves: {key: {file, offset,
                              bytes, raw_bytes, lossy, bf16}}, meta}
        shard_000.bin        concatenated framed blobs, offset-addressed
        [shard_NNN.bin ...]  byte-balanced when shard_count > 1
All leaf blobs are packed into few large files bound by the manifest's
offset table, so save cost is IO bandwidth, not per-leaf open/write/fsync
metadata pressure (the small-file scaling failure of parallel-IO folklore),
and restore can readahead each shard sequentially.

Layout v1 (legacy, one file per leaf) is still written by format=1 configs
and always restored: entries without an ``offset`` name a per-leaf
``<key-hash>.bin`` file.

Commit/durability protocol (both layouts):
  1. blobs written and **fsynced** (per shard file / per leaf file),
  2. manifest written to a tmp name, fsynced, renamed into the tmp dir,
  3. the tmp dir is atomically published by ``commit``: any existing final
     dir is first moved *aside* (sibling rename — never deleted while it is
     the only copy), the tmp dir is renamed into place, the parent directory
     is fsynced, and only then is the old copy removed. A checkpoint without
     a manifest is invisible to discovery, so readers never observe partial
     state, and a crash at any point leaves either the old or the new
     checkpoint restorable. ``sweep_stale`` (run on manager init) removes
     crashed tmp dirs and re-publishes a copy stranded mid-commit.
"""
from __future__ import annotations

import hashlib
import json
import mmap
import os
import re
import shutil
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codecs, lossy
from repro.kernels.ref import Compressed

PyTree = Any

CHECKPOINT_FORMAT = 2
_SHARD_FMT = "shard_{:03d}.bin"
_TMP_RE = re.compile(r"^\.tmp_step_\d{9}$")
_OLD_RE = re.compile(r"^\.old_(step_\d{9})$")


class CheckpointCorruptError(RuntimeError):
    """A stored blob does not match its manifest entry (truncation/corruption)."""


def _fname(key: str) -> str:
    return hashlib.sha1(key.encode()).hexdigest()[:16] + ".bin"


def _fsync_dir(path: str) -> None:
    """Durably record directory-entry changes (renames) under ``path``."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass                       # not all filesystems support dir fsync
    finally:
        os.close(fd)


@dataclass
class SaveReport:
    step: int
    raw_bytes: int
    stored_bytes: int
    n_leaves: int
    lossy_leaves: int

    @property
    def ratio(self) -> float:
        if self.raw_bytes == 0:
            return 0.0
        return (self.raw_bytes - self.stored_bytes) / self.raw_bytes


def state_to_host(state: PyTree) -> dict[str, np.ndarray | Compressed]:
    """Device->host hand-off: the part the step serializes on."""
    flat = jax.tree_util.tree_flatten_with_path(
        state, is_leaf=lambda x: isinstance(x, Compressed))[0]
    out: dict[str, Any] = {}
    for path, leaf in flat:
        if leaf is None:
            continue
        key = jax.tree_util.keystr(path)
        if isinstance(leaf, Compressed):
            out[key] = Compressed(np.asarray(leaf.q), np.asarray(leaf.scale),
                                  leaf.n_elements, leaf.shape, leaf.dtype)
            continue
        arr = np.asarray(leaf)
        if arr.dtype == jnp.bfloat16:
            # numpy has no bf16: store the raw 16-bit pattern, remember it
            out[key] = arr.view(np.uint16)
        else:
            out[key] = arr
    return out


def encode_leaf(key: str, arr: np.ndarray | Compressed, *,
                lossless: str, eps: float = 1e-2,
                lossy_policy: Optional[Callable[[str], bool]] = None,
                bf16_keys: Optional[set] = None,
                pool=None) -> tuple[bytes, dict]:
    """Lossless-encode ONE leaf -> (framed blob, manifest entry sans file).

    Pure compute, no I/O. This is the unit the checkpoint pipeline fans out
    across the runtime worker pool (leaf-parallel encode); ``pool``
    additionally fans the chunks of a large leaf out on the shared codec
    executor (GIL-released stdlib codecs).
    """
    if isinstance(arr, Compressed):
        # HYBRID path: the lossy stage already ran on device; only the
        # lossless stage happens here.
        blob, st = lossy.frame_compressed(arr, lossless, pool)
        is_lossy, raw_bytes, is_bf16 = True, st.raw_bytes, False
    else:
        is_lossy = bool(lossy_policy and lossy_policy(key))
        is_bf16 = bool(bf16_keys and key in bf16_keys)
        raw_bytes = int(arr.nbytes)
        if is_lossy:
            # lossy path needs real float values; bf16-as-u16 goes via f32
            a = arr
            if is_bf16:
                a = np.asarray(jnp.asarray(arr.view(np.uint16))
                               .view(jnp.bfloat16).astype(jnp.float32))
            blob, _ = lossy.compress_tensor(a, eps=eps, lossless=lossless,
                                            pool=pool)
        else:
            blob, _ = codecs.encode(arr, lossless, pool=pool)
    return blob, {"bytes": len(blob), "lossy": is_lossy,
                  "raw_bytes": raw_bytes, "bf16": is_bf16}


def encode_blobs(host_state: dict[str, np.ndarray], *,
                 lossless: str, eps: float = 1e-2,
                 lossy_policy: Optional[Callable[[str], bool]] = None,
                 bf16_keys: Optional[set] = None,
                 pool=None) -> dict[str, tuple[bytes, dict]]:
    """Serial leaf walk over ``encode_leaf`` (the pipeline fans leaves out)."""
    return {key: encode_leaf(key, arr, lossless=lossless, eps=eps,
                             lossy_policy=lossy_policy, bf16_keys=bf16_keys,
                             pool=pool)
            for key, arr in host_state.items()}


def write_encoded(directory: str,
                  encoded: dict[str, tuple[bytes, dict]]) -> dict[str, dict]:
    """v1 write stage: one fsynced file per leaf; returns manifest entries."""
    os.makedirs(directory, exist_ok=True)
    entries: dict[str, dict] = {}
    for key, (blob, ent) in encoded.items():
        fn = _fname(key)
        with open(os.path.join(directory, fn), "wb") as f:
            f.write(blob)
            f.flush()
            # a published manifest must never point at unwritten blob bytes
            os.fsync(f.fileno())
        entries[key] = {"file": fn, **ent}
    return entries


def write_encoded_shards(directory: str,
                         encoded: dict[str, tuple[bytes, dict]],
                         shard_count: int = 1) -> dict[str, dict]:
    """v2 write stage: pack every blob into ``shard_count`` fsynced files.

    One open/write/fsync per *shard* — independent of leaf count — with the
    manifest's offset table binding each leaf to (file, offset, bytes).
    Leaves pack sequentially in dict order; when ``shard_count > 1`` the
    stream rolls over at byte-balanced boundaries (``shard_count`` is an
    upper bound: a few large leaves may fill the budget in fewer files).
    """
    os.makedirs(directory, exist_ok=True)
    items = list(encoded.items())
    entries: dict[str, dict] = {}
    if not items:
        return entries
    total = sum(len(blob) for _, (blob, _) in items)
    shard_count = max(1, min(int(shard_count), len(items)))
    target = max(1, -(-total // shard_count))          # ceil(total/shards)
    si, offset, f = 0, 0, None
    try:
        for key, (blob, ent) in items:
            if f is None:
                fn = _SHARD_FMT.format(si)
                f = open(os.path.join(directory, fn), "wb")
                offset = 0
            entries[key] = {"file": fn, "offset": offset, **ent}
            f.write(blob)
            offset += len(blob)
            if offset >= target and si < shard_count - 1:
                f.flush()
                os.fsync(f.fileno())
                f.close()
                f, si = None, si + 1
        if f is not None:
            f.flush()
            os.fsync(f.fileno())
            f.close()
            f = None
    finally:
        if f is not None:
            f.close()
    return entries


def write_blobs(host_state: dict[str, np.ndarray], directory: str, *,
                lossless: str, eps: float = 1e-2,
                lossy_policy: Optional[Callable[[str], bool]] = None,
                bf16_keys: Optional[set] = None,
                shard_count: int = 1) -> dict[str, dict]:
    """Encode + write in one call (the pipeline splits the two stages)."""
    return write_encoded_shards(directory, encode_blobs(
        host_state, lossless=lossless, eps=eps, lossy_policy=lossy_policy,
        bf16_keys=bf16_keys), shard_count)


def write_manifest(directory: str, step: int, entries: dict[str, dict],
                   meta: Optional[dict] = None) -> None:
    fmt = (CHECKPOINT_FORMAT
           if any("offset" in e for e in entries.values()) else 1)
    manifest = {"step": step, "format": fmt, "leaves": entries,
                "meta": meta or {}}
    tmp = os.path.join(directory, "manifest.json.tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(directory, "manifest.json"))
    # durably record the step dir's own entries (shard/blob files + this
    # rename): commit() only fsyncs the *parent*, and without this a power
    # loss after publish could lose the entries inside the published dir
    _fsync_dir(directory)


# serializes commit's aside/publish rename pair against sweep_stale's
# recovery renames: a sweep running inside another manager's aside window
# would otherwise republish the .old_ copy and make the publish rename fail
# with ENOTEMPTY. In-process only — sharing one checkpoint directory across
# processes is out of scope (retention has the same caveat).
_commit_lock = threading.Lock()


def commit(tmp_dir: str, final_dir: str) -> None:
    """Atomic publish that never destroys the only copy of a step.

    Any existing ``final_dir`` is moved aside with a sibling rename (not
    deleted — a crash between a delete and the publish rename would lose
    both copies), the tmp dir is renamed into place, the parent directory's
    entries are fsynced so the publish survives power loss, and only then is
    the displaced copy removed. ``sweep_stale`` re-publishes a copy stranded
    in the aside window by a crash.
    """
    parent = os.path.dirname(os.path.abspath(final_dir))
    old = os.path.join(parent, ".old_" + os.path.basename(final_dir))
    with _commit_lock:
        displaced = False
        if os.path.exists(final_dir):
            if os.path.exists(old):
                shutil.rmtree(old)
            os.replace(final_dir, old)
            displaced = True
        os.replace(tmp_dir, final_dir)
        _fsync_dir(parent)
        if displaced:
            shutil.rmtree(old, ignore_errors=True)


def _latest_mtime(path: str) -> float:
    """Newest mtime of a dir or anything directly inside it.

    The dir's own mtime only moves on entry create/rename — a writer
    streaming into an already-open shard file advances the *file's* mtime,
    so liveness checks must look one level down.
    """
    try:
        newest = os.path.getmtime(path)
        for entry in os.scandir(path):
            try:
                newest = max(newest, entry.stat().st_mtime)
            except OSError:
                pass
    except OSError:
        return 0.0
    return newest


def sweep_stale(directory: str, tmp_grace_s: float = 60.0) -> None:
    """Crash recovery at startup: clear the commit protocol's debris.

    * ``.tmp_step_*`` dirs are unpublished partial saves — remove them,
      *unless* the dir or anything in it was modified within ``tmp_grace_s``
      seconds: a fresh tmp dir may belong to a still-live writer (a
      replacement manager constructed while the previous one's async save
      is mid-sink must not destroy it; it will be swept on a later init
      once it is genuinely stale).
    * ``.old_step_N`` with ``step_N`` present is a displaced copy whose
      replacement committed — remove it.
    * ``.old_step_N`` *without* ``step_N`` means the crash hit between the
      aside rename and the publish rename — move the copy back so the step
      is visible again (serialized against a live in-process ``commit`` by
      the shared lock).
    """
    try:
        names = os.listdir(directory)
    except FileNotFoundError:
        return
    now = time.time()
    with _commit_lock:
        for name in names:
            path = os.path.join(directory, name)
            if _TMP_RE.match(name):
                if now - _latest_mtime(path) >= tmp_grace_s:
                    shutil.rmtree(path, ignore_errors=True)
                continue
            m = _OLD_RE.match(name)
            if m:
                final = os.path.join(directory, m.group(1))
                if os.path.exists(final):
                    shutil.rmtree(path, ignore_errors=True)
                elif os.path.exists(path):
                    os.replace(path, final)
        _fsync_dir(directory)


def read_manifest(directory: str) -> dict:
    with open(os.path.join(directory, "manifest.json")) as f:
        return json.load(f)


def _load_shard(path: str):
    """Readahead one shard: mmap (sequential-advised) or a full read.

    Returns a bytes-like whose slices are the leaf blobs; mmap keeps the
    page cache in charge of the actual readahead while letting every leaf
    slice without a per-leaf syscall.
    """
    with open(path, "rb") as f:
        try:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except (ValueError, OSError):       # empty file / no-mmap fs
            f.seek(0)
            return f.read()
    if hasattr(mm, "madvise") and hasattr(mmap, "MADV_SEQUENTIAL"):
        try:
            mm.madvise(mmap.MADV_SEQUENTIAL)
        except OSError:
            pass
    return mm


def _fetch_blob(directory: str, key: str, ent: dict, shards: dict) -> bytes:
    """One leaf's stored bytes, validated against the manifest entry."""
    want = int(ent["bytes"])
    if "offset" in ent:                      # v2: slice the packed shard
        data = shards[ent["file"]]
        off = int(ent["offset"])
        blob = bytes(data[off:off + want])
    else:                                    # v1: per-leaf blob file
        try:
            with open(os.path.join(directory, ent["file"]), "rb") as f:
                blob = f.read()
        except FileNotFoundError as e:
            raise CheckpointCorruptError(
                f"checkpoint {directory}: leaf {key!r} names missing blob "
                f"file {ent['file']!r}") from e
    if len(blob) != want:
        raise CheckpointCorruptError(
            f"checkpoint {directory}: leaf {key!r} expected {want} stored "
            f"bytes, found {len(blob)} (truncated "
            f"{'shard' if 'offset' in ent else 'blob'} file {ent['file']!r})")
    return blob


def read_state(directory: str, template: PyTree,
               shardings: Optional[PyTree] = None,
               pool=None) -> PyTree:
    """Restore a pytree; re-place under ``shardings`` if given (elastic).

    v2 checkpoints are read with one sequential-readahead mmap per shard
    file and the per-leaf decode fanned out on ``pool`` (the shared codec
    executor); v1 per-leaf-file checkpoints restore through the same loop,
    one open per leaf. Truncated/corrupt stored bytes raise
    ``CheckpointCorruptError``; a template leaf missing from the manifest
    raises ``KeyError`` naming the leaf (tree-shape drift) instead of
    failing deep inside decode.
    """
    manifest = read_manifest(directory)
    entries = manifest["leaves"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    shard_flat = (jax.tree_util.tree_flatten(shardings)[0]
                  if shardings is not None else [None] * len(flat))
    # readahead: map every referenced shard file once, before any decode
    shards = {}
    for fn in sorted({e["file"] for e in entries.values() if "offset" in e}):
        try:
            shards[fn] = _load_shard(os.path.join(directory, fn))
        except FileNotFoundError as e:
            raise CheckpointCorruptError(
                f"checkpoint {directory}: manifest references missing shard "
                f"file {fn!r}") from e
    jobs: list[Optional[tuple]] = []
    for (path, leaf), shd in zip(flat, shard_flat):
        if leaf is None:
            jobs.append(None)
            continue
        key = jax.tree_util.keystr(path)
        ent = entries.get(key)
        if ent is None:
            raise KeyError(
                f"checkpoint {directory} has no entry for template leaf "
                f"{key!r} — the template's tree shape drifted since this "
                f"checkpoint was written ({len(entries)} stored leaves)")
        jobs.append((key, ent, leaf, shd))

    fan_leaves = pool is not None and sum(j is not None for j in jobs) > 1

    def _restore_one(job: Optional[tuple]):
        if job is None:
            return None
        key, ent, leaf, shd = job
        blob = _fetch_blob(directory, key, ent, shards)
        # chunk-level fan-out only when leaves decode serially: nesting both
        # levels on one executor would have leaf jobs block on chunk jobs
        # that cannot be scheduled behind them.
        arr = lossy.decompress_blob(blob, None if fan_leaves else pool)
        arr = jnp.asarray(arr)
        if ent.get("bf16") and not ent["lossy"]:
            arr = arr.view(jnp.bfloat16)
        want_dtype = getattr(leaf, "dtype", arr.dtype)
        want_shape = getattr(leaf, "shape", arr.shape)
        arr = arr.astype(want_dtype).reshape(want_shape)
        if shd is not None:
            arr = jax.device_put(arr, shd)
        return arr

    if fan_leaves:
        leaves = list(pool.map(_restore_one, jobs))
    else:
        leaves = [_restore_one(j) for j in jobs]
    return jax.tree_util.tree_unflatten(treedef, leaves)
