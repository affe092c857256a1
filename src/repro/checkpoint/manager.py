"""CheckpointManager: the checkpoint workload as ONE registered pipeline.

Checkpointing is the paper's motivating I/O problem (QE restart files,
hundreds of GB, written every few steps for walltime/failure reasons). The
manager no longer forks the in-situ engine — it registers a single
declarative pipeline into a ``repro.core.runtime.PipelineRuntime``:

    DeviceStage  (HYBRID only) Pallas spectral-lossy on the moment leaves —
                 ONE fused dispatch for the whole tree; the hand-off then
                 ships int8 coefficients + scales (~4-50x smaller — paper
                 Fig. 8/9, NEKO lossy-on-GPU)
    Handoff      two-phase: the loop only *dispatches* the D2H copies
                 (``handoff/dispatch``); ``state_to_host`` + bf16-key
                 bookkeeping materialize on the consumer side, overlapped
                 with the next steps (JAX arrays are immutable, so the
                 deferred snapshot is exact)
    HostStage    'encode': lossless framing of every leaf — a FanoutStage
                 whose per-leaf items are stolen by idle runtime workers
                 (many-small-leaf trees encode leaf-parallel), each item
                 additionally chunk-parallel on the shared codec pool
    Sink         'write': packed shard files (v2 offset-table layout; one
                 fsynced shard_NNN.bin instead of a file per leaf) ->
                 manifest -> crash-safe directory publish, then
                 lock-guarded retention

SYNC / ASYNC / HYBRID are scheduling policies of the shared runtime
(Fig. 1, paper Figs. 10-12), not manager code paths. A runtime can be
shared with other in-situ tasks (the training loop passes its own), so
checkpoint writes and analytics draw from the same p_i worker pool.

Durability: blobs -> manifest -> atomic directory rename; a reader can
never observe a partial checkpoint. Retention keeps the newest K (guarded
by the manager lock — multiple async workers may finish writes
concurrently). ``restore`` re-places leaves under the *current* mesh's
shardings (elastic restart).
"""
from __future__ import annotations

import os
import re
import shutil
import threading
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax

from repro.checkpoint import serialization as ser
from repro.core import codecs, transport
from repro.core.runtime import (FanoutStage, PipelineRuntime, PipelineTask,
                                Placement, Stage)
from repro.core.telemetry import Telemetry

PyTree = Any

# historical name, same enum as the runtime's Placement
InSituMode = Placement

_STEP_RE = re.compile(r"^step_(\d{9})$")


def default_lossy_policy(key: str) -> bool:
    """Lossy only for optimizer moments (noise-dominated statistics)."""
    return (".mu" in key or ".nu" in key or "'mu'" in key or "'nu'" in key
            or "moment" in key)


@dataclass
class CheckpointConfig:
    directory: str
    mode: Placement = Placement.ASYNC
    every: int = 100
    keep: int = 3
    lossless: str = "zstd"
    lossy_eps: float = 1e-2
    lossy_moments: bool = True
    p_i: int = 2                      # workers for a manager-owned runtime
    staging_capacity: int = 2
    chunk_parallel: bool = True       # fan leaf chunks out on the codec pool
    format: int = ser.CHECKPOINT_FORMAT  # 2: packed shards; 1: file per leaf
    shard_count: int = 1              # v2: number of shard_NNN.bin files
    leaf_parallel: bool = True        # fan encode out per leaf on the pool
    mirror: Optional[str] = None      # transport URL replicating committed
                                      # steps (file:// | tcp:// | memory://)

    def __post_init__(self) -> None:
        if self.every < 1:
            raise ValueError(
                f"CheckpointConfig.every must be >= 1, got {self.every}: "
                "maybe_save gates on step % every (every=0 divides by "
                "zero); use save() directly for one-off checkpoints")
        if self.keep < 0:
            raise ValueError(
                f"CheckpointConfig.keep must be >= 0, got {self.keep}")
        if self.format not in (1, ser.CHECKPOINT_FORMAT):
            raise ValueError(
                f"CheckpointConfig.format must be 1 (per-leaf files) or "
                f"{ser.CHECKPOINT_FORMAT} (packed shards), got {self.format}")
        if self.shard_count < 1:
            raise ValueError(
                f"CheckpointConfig.shard_count must be >= 1, "
                f"got {self.shard_count}")
        if self.lossless not in codecs.available():
            raise ValueError(
                f"CheckpointConfig.lossless={self.lossless!r} is not "
                f"available here (codecs: {codecs.available()}); the "
                "default 'zstd' needs the zstandard package")


class CheckpointManager:
    def __init__(self, cfg: CheckpointConfig,
                 telemetry: Optional[Telemetry] = None,
                 runtime: Optional[PipelineRuntime] = None) -> None:
        self.cfg = cfg
        os.makedirs(cfg.directory, exist_ok=True)
        # crash recovery: drop unpublished tmp dirs from dead saves and
        # re-publish a copy stranded mid-commit (see ser.sweep_stale)
        ser.sweep_stale(cfg.directory)
        self.reports: list[ser.SaveReport] = []
        self.mirror_stats = {"steps": 0, "frames": 0, "failures": 0}
        self._mirror = (transport.connect(cfg.mirror, stream="checkpoint")
                        if cfg.mirror else None)
        self._lock = threading.Lock()
        self._owns_runtime = runtime is None
        if runtime is None:
            self.telemetry = telemetry or Telemetry()
            runtime = PipelineRuntime(
                workers=cfg.p_i, staging_capacity=cfg.staging_capacity,
                telemetry=self.telemetry)
        else:
            if telemetry is not None and telemetry is not runtime.telemetry:
                raise ValueError(
                    "pass either a telemetry or a runtime (whose telemetry "
                    "is used), not two different objects")
            self.telemetry = runtime.telemetry
        self.runtime = runtime
        device_stage = (self._device_lossy
                        if cfg.mode is Placement.HYBRID and cfg.lossy_moments
                        else None)
        encode = (FanoutStage("encode", split=self._encode_split,
                              fn=self._encode_leaf_item,
                              gather=self._encode_gather)
                  if cfg.leaf_parallel
                  else Stage("encode", self._encode_stage))
        self._task = self.runtime.register(PipelineTask(
            name="checkpoint",
            source="ckpt_state",
            placement=cfg.mode,
            every=1,                 # save()/maybe_save gate on cfg.every
            device_stage=device_stage,
            handoff=self._handoff,
            host_stages=(encode,),
            sink=self._write_sink,
        ))

    # -- pipeline stages ------------------------------------------------------

    def _lossy_policy(self) -> Optional[Callable[[str], bool]]:
        return default_lossy_policy if self.cfg.lossy_moments else None

    def _device_lossy(self, step: int, payload: tuple) -> tuple:
        """Device stage (HYBRID): spectral-lossy the moment leaves in-place.
        Counts its fused launches (``checkpoint/compress_launches``)."""
        from repro.kernels import ops as kops
        state, meta = payload
        self.telemetry.count("checkpoint/compress_launches",
                             kops.compress_launches(state,
                                                    default_lossy_policy))
        state = kops.spectral_compress_tree(state, self.cfg.lossy_eps,
                                            default_lossy_policy)
        return state, meta

    def _handoff(self, payload: tuple) -> dict:
        """Device->host transfer + bf16 bookkeeping (numpy has no bf16)."""
        state, meta = payload
        host_state = ser.state_to_host(state)
        bf16_keys = {
            k for (p, l) in jax.tree_util.tree_flatten_with_path(state)[0]
            if l is not None and getattr(l, "dtype", None) == jax.numpy.bfloat16
            for k in [jax.tree_util.keystr(p)]}
        return {"state": host_state, "bf16_keys": bf16_keys,
                "meta": meta or {}}

    def _codec_pool(self):
        return codecs.codec_pool() if self.cfg.chunk_parallel else None

    def _encode_stage(self, step: int, payload: dict) -> dict:
        """Serial host stage (``leaf_parallel=False``): walk every leaf."""
        encoded = ser.encode_blobs(
            payload["state"], lossless=self.cfg.lossless,
            eps=self.cfg.lossy_eps, lossy_policy=self._lossy_policy(),
            bf16_keys=payload["bf16_keys"], pool=self._codec_pool())
        return {"encoded": encoded, "meta": payload["meta"]}

    # leaf-parallel encode: one work item per leaf, stolen by idle runtime
    # workers (FanoutStage), gathered before the sink so the commit protocol
    # (blobs -> manifest -> rename) is unchanged. Chunks of a large leaf
    # still fan out on the codec pool — the two pools are distinct, so leaf
    # items never block on their own chunk jobs.

    def _encode_split(self, step: int, payload: dict) -> list:
        bf16_keys = payload["bf16_keys"]
        return [(key, arr, bf16_keys) for key, arr in payload["state"].items()]

    def _encode_leaf_item(self, step: int, item: tuple) -> tuple:
        key, arr, bf16_keys = item
        blob, ent = ser.encode_leaf(
            key, arr, lossless=self.cfg.lossless, eps=self.cfg.lossy_eps,
            lossy_policy=self._lossy_policy(), bf16_keys=bf16_keys,
            pool=self._codec_pool())
        return key, (blob, ent)

    def _encode_gather(self, step: int, payload: dict, results: list) -> dict:
        return {"encoded": dict(results), "meta": payload["meta"]}

    def _write_sink(self, step: int, payload: dict) -> ser.SaveReport:
        """Sink: atomic write (blobs -> manifest -> rename) + retention."""
        tmp = os.path.join(self.cfg.directory, f".tmp_step_{step:09d}")
        final = os.path.join(self.cfg.directory, f"step_{step:09d}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        if self.cfg.format >= ser.CHECKPOINT_FORMAT:
            entries = ser.write_encoded_shards(tmp, payload["encoded"],
                                               self.cfg.shard_count)
        else:
            entries = ser.write_encoded(tmp, payload["encoded"])
        ser.write_manifest(tmp, step, entries, payload["meta"])
        ser.commit(tmp, final)
        self._mirror_committed(step, final)
        raw = sum(e["raw_bytes"] for e in entries.values())
        stored = sum(e["bytes"] for e in entries.values())
        report = ser.SaveReport(step, raw, stored, len(entries),
                                sum(1 for e in entries.values() if e["lossy"]))
        with self._lock:
            self.reports.append(report)
            # retention under the lock: concurrent async workers would
            # otherwise interleave list_steps()/rmtree
            self._retain_locked()
        return report

    def _mirror_committed(self, step: int, final: str) -> None:
        """Replicate a committed step through the secondary transport, one
        CODEC_FILE frame per file with the manifest last (the consumer's
        materialized copy honors the same publish-manifest-last protocol).

        Mirroring is strictly after the local commit and *best-effort*: a
        dead replica counts a failure in ``mirror_stats`` instead of
        raising — a TransientError here would send the whole sink back
        through the runtime's retry loop and re-commit an
        already-committed checkpoint."""
        if self._mirror is None:
            return
        try:
            n = transport.send_directory(
                self._mirror, step, final,
                prefix=os.path.basename(final), stream="checkpoint")
            with self._lock:
                self.mirror_stats["steps"] += 1
                self.mirror_stats["frames"] += n
        except Exception:  # noqa: BLE001 - replication never blocks saves
            with self._lock:
                self.mirror_stats["failures"] += 1

    def _retain_locked(self) -> None:
        steps = sorted(self.list_steps())
        for s in steps[: -self.cfg.keep] if self.cfg.keep > 0 else []:
            shutil.rmtree(os.path.join(self.cfg.directory, f"step_{s:09d}"),
                          ignore_errors=True)

    # -- write path -----------------------------------------------------------

    def save(self, step: int, state: PyTree, meta: Optional[dict] = None) -> None:
        """Checkpoint one training state via the registered pipeline."""
        self.runtime.submit(step, {"ckpt_state": lambda: (state, meta)})

    def maybe_save(self, step: int, state: PyTree,
                   meta: Optional[dict] = None) -> bool:
        if step % self.cfg.every:
            return False
        self.save(step, state, meta)
        return True

    # -- read path ------------------------------------------------------------

    def list_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.cfg.directory):
            m = _STEP_RE.match(name)
            if m and os.path.exists(os.path.join(self.cfg.directory, name,
                                                 "manifest.json")):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, template: PyTree, step: Optional[int] = None,
                shardings: Optional[PyTree] = None) -> tuple[int, PyTree]:
        """Elastic restore: re-places leaves under the current mesh."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.cfg.directory}")
        d = os.path.join(self.cfg.directory, f"step_{step:09d}")
        with self.telemetry.span("checkpoint/restore", step=step):
            state = ser.read_state(d, template, shardings,
                                   pool=self._codec_pool())
        return step, state

    def read_meta(self, step: Optional[int] = None) -> dict:
        """The meta dict recorded with a checkpoint's manifest (e.g. the
        mesh geometry ``Session.set_checkpoint_meta`` attaches); latest
        step when ``step`` is None."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.cfg.directory}")
        d = os.path.join(self.cfg.directory, f"step_{step:09d}")
        return dict(ser.read_manifest(d).get("meta") or {})

    # -- lifecycle ------------------------------------------------------------

    def finish(self) -> None:
        if self._owns_runtime:
            self.runtime.drain()
        if self._mirror is not None:
            try:
                self._mirror.close()
            except Exception:  # noqa: BLE001 - teardown must not raise
                pass

    def wait_idle(self, timeout: float = 600.0) -> None:
        """Block until queued checkpoints are written (tests/end-of-run)."""
        self.runtime.wait_idle(timeout=timeout)
