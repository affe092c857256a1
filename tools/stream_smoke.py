"""Two-process localhost streaming smoke: trainer publishes, consumer steers.

    PYTHONPATH=src python tools/stream_smoke.py

Process A (child): ``tools/insitu_consumer.py`` listening on a free port,
building a replica snapshot chain, pushing one steering command
(``{"task": "kv_snapshot", "every": 2}``) back up the wire, and printing
the digest of its restored state.

Process B (this process): the serving loop from ``repro.launch.serve``
with ``snapshot_to=tcp://...`` — every chain frame the ``SnapshotStore``
publishes is mirrored over TCP while the loop keeps serving.

Passes when:
  * the consumer's restored snapshot digest is BIT-IDENTICAL to a restore
    from the producer's on-disk chain (same step, same leaves);
  * the producer's session report shows the steering command was applied
    mid-run (``report["steering"]``);
  * neither process crashed and the producer never raised a task error.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.consume import restore_report  # noqa: E402
from repro.launch.serve import default_serve_plan, serve_loop  # noqa: E402
from repro.serving.snapshot import SnapshotStore  # noqa: E402


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main() -> int:
    port = _free_port()
    tmp = tempfile.mkdtemp(prefix="stream_smoke_")
    chain_dir = os.path.join(tmp, "producer_chain")
    steer = json.dumps({"task": "kv_snapshot", "every": 2})

    consumer = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "insitu_consumer.py"),
         "--port", str(port), "--idle-timeout", "5",
         "--start-grace", "240",
         "--steer", steer, "--restore", "kv_pages"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        # the producer (this process) may hold the chip: keep the
        # consumer, which only restores bytes, off it
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    print(f"consumer listening on tcp://127.0.0.1:{port} "
          f"(pid {consumer.pid})")

    plan = default_serve_plan(insitu_mode="sync", snapshot_every=4,
                              base_every=4, snapshot_dir=chain_dir,
                              snapshot_to=f"tcp://127.0.0.1:{port}")
    out = serve_loop("smollm-135m", n_requests=16, max_new=16,
                     insitu_mode="sync", plan=plan)
    rep = out["session_report"]

    try:
        stdout, _ = consumer.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        consumer.kill()
        stdout, _ = consumer.communicate()
        print(stdout)
        print("FAIL: consumer did not exit after the stream drained")
        return 1
    print("--- consumer output ---")
    print(stdout.strip())
    print("-----------------------")
    if consumer.returncode != 0:
        print(f"FAIL: consumer exited {consumer.returncode}")
        return 1

    failures = []

    # 1. bit-identical restore: replica digest == producer's on-disk chain
    local = restore_report({"store": SnapshotStore(chain_dir)}, "kv_pages")
    marker = f"digest {local['digest']}"
    if marker not in stdout:
        failures.append(
            f"consumer restore digest != producer chain digest "
            f"(expected {local['digest'][:16]}..., consumer printed: "
            f"{[l for l in stdout.splitlines() if 'digest' in l]})")
    else:
        print(f"restore parity OK: step {local['step']}, "
              f"digest {local['digest'][:16]}... on both sides")

    # 2. steering applied mid-run on the producer
    steering = rep.get("steering", {})
    commands = steering.get("commands", [])
    applied = [s for s in commands if s.get("applied", {}).get("every") == 2]
    if not applied:
        failures.append(f"steering not applied by the producer: {steering}")
    elif steering.get("steering_rejected", 0):
        failures.append(f"valid steering counted as rejected: {steering}")
    else:
        print(f"steering OK: {applied[0]}")

    # 3. the producer streamed and never raised
    snap = rep["tasks"].get("kv_snapshot", {})
    if rep.get("errors"):
        failures.append(f"producer task errors: {rep['errors']}")
    if snap.get("mirror_frames", 0) < 1:
        failures.append(f"no frames mirrored: {snap}")
    else:
        print(f"streamed {snap.get('mirror_frames')} chain frames, "
              f"{snap.get('mirror_failures', 0)} failures")

    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print("stream smoke passed: two processes, live chain replication, "
          "bit-identical restore, mid-run steering")
    return 0


if __name__ == "__main__":
    sys.exit(main())
