"""Two-process replica-hydration smoke: serve, mirror, hydrate, compare.

    PYTHONPATH=src python tools/hydrate_smoke.py

Process A (child, ``--replica``): a cold replica. It listens on a free
TCP port via :class:`repro.launch.hydrate.ReplicaHydrator`, ingests the
producer's mirrored snapshot chain until a restorable snapshot with
in-flight requests arrives, rebuilds the paged engine from it MID-SERVE
(the producer never pauses), decodes a few steps with zero prefill, and
prints each request's continuation tokens plus a digest.

Process B (this process): the serving loop from ``repro.launch.serve``
with a shared 16-token prefix registered for COW sharing and
``snapshot_to=tcp://...`` pointed at the replica.

Passes when:
  * the replica hydrates from the live chain (>= 1 frame ingested,
    >= 1 registered prefix restored, > 0 in-flight requests);
  * every token the replica decodes equals the token the producer
    decoded at the same position of the same request — greedy decode
    from bit-identical state, so the digests must match exactly;
  * the replica ran no prefill at all after hydration.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ARCH = "smollm-135m"
MARKER = "HYDRATE_RESULT "


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _digest(records: list[dict]) -> str:
    blob = json.dumps(sorted(records, key=lambda r: r["rid"]),
                      sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


# ---------------------------------------------------------------------------
# child: the cold replica
# ---------------------------------------------------------------------------

def replica_main(port: int, seed: int, steps: int) -> int:
    import jax
    import numpy as np

    from repro.configs import base as configs
    from repro.launch.hydrate import ReplicaHydrator
    from repro.models import params as P_lib
    from repro.models import transformer

    cfg = configs.get(ARCH, smoke=True)
    params = P_lib.materialize(jax.random.PRNGKey(seed),
                               transformer.param_spec(cfg))
    hyd = ReplicaHydrator(f"tcp://127.0.0.1:{port}")

    def ready() -> bool:
        # restorable is not enough: wait for a snapshot with work in
        # flight, so the decode comparison below has something to decode
        if not hyd.store.restorable(hyd.stream):
            return False
        _, leaves = hyd.store.restore(hyd.stream)
        meta = json.loads(np.asarray(leaves["['meta']"],
                                     np.uint8).tobytes())
        return any(a is not None for a in meta["active"])

    engine, info = hyd.hydrate(cfg, params, ready=ready,
                               idle_timeout_s=30.0, start_grace_s=240.0)
    live = [a for a in engine.active if a is not None]
    offsets = {r.rid: len(r.out) for r in live}
    prefill_before = engine.prefill_tokens
    for _ in range(steps):
        if any(a is not None for a in engine.active):
            engine.step()
    records = [{"rid": r.rid, "offset": offsets[r.rid],
                "tokens": r.out[offsets[r.rid]:]} for r in live]
    out = {"records": records, "digest": _digest(records),
           "frames_ingested": info["frames_ingested"],
           "prefixes": info["prefixes"], "step": info["step"],
           "prefill_after_hydration": engine.prefill_tokens
                                      - prefill_before}
    print(MARKER + json.dumps(out))
    return 0


# ---------------------------------------------------------------------------
# parent: the producer + the assertions
# ---------------------------------------------------------------------------

def main() -> int:
    # a chip belongs to one process, and the bit-identical comparison needs
    # both processes on one backend: producer and replica run on the CPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    port = _free_port()
    child = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--replica",
         "--port", str(port), "--seed", "0", "--steps", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ))

    lines: list[str] = []
    listening = threading.Event()

    def pump():
        for line in child.stdout:          # type: ignore[union-attr]
            lines.append(line)
            if "listening" in line:
                listening.set()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    if not listening.wait(timeout=240):
        child.kill()
        print("".join(lines))
        print("FAIL: replica never started listening")
        return 1
    print(f"replica listening on tcp://127.0.0.1:{port} (pid {child.pid})")

    from repro.launch.serve import default_serve_plan, serve_loop

    plan = default_serve_plan(insitu_mode="async", snapshot_every=2,
                              base_every=4,
                              snapshot_to=f"tcp://127.0.0.1:{port}")
    out = serve_loop(ARCH, n_requests=8, max_new=16, prefix_len=16,
                     insitu_mode="async", plan=plan)

    try:
        child.wait(timeout=240)
    except subprocess.TimeoutExpired:
        child.kill()
        print("".join(lines))
        print("FAIL: replica did not exit")
        return 1
    t.join(timeout=10)
    stdout = "".join(lines)
    print("--- replica output ---")
    print(stdout.strip())
    print("----------------------")
    if child.returncode != 0:
        print(f"FAIL: replica exited {child.returncode}")
        return 1

    marker = [l for l in stdout.splitlines() if l.startswith(MARKER)]
    if not marker:
        print("FAIL: replica printed no result")
        return 1
    res = json.loads(marker[0][len(MARKER):])

    failures = []
    if res["frames_ingested"] < 1:
        failures.append("replica ingested no frames")
    if res["prefixes"] < 1:
        failures.append("replica restored no registered prefix")
    if not res["records"]:
        failures.append("replica hydrated with no in-flight requests")
    if res["prefill_after_hydration"] != 0:
        failures.append(f"replica ran {res['prefill_after_hydration']} "
                        f"prefill tokens after hydration (want 0)")

    # token-for-token: replica continuation == what the producer decoded
    # at the same positions (greedy decode from bit-identical state)
    by_rid = {r.rid: r.out for r in out["requests"]}
    expected = []
    for rec in res["records"]:
        want = by_rid[rec["rid"]][rec["offset"]:
                                  rec["offset"] + len(rec["tokens"])]
        expected.append({"rid": rec["rid"], "offset": rec["offset"],
                         "tokens": want})
        if not rec["tokens"]:
            failures.append(f"request {rec['rid']}: replica decoded "
                            f"nothing")
        elif rec["tokens"] != want:
            failures.append(f"request {rec['rid']} diverged at offset "
                            f"{rec['offset']}: replica {rec['tokens']} "
                            f"vs producer {want}")
    want_digest = _digest(expected)
    if res["digest"] != want_digest:
        failures.append(f"digest mismatch: replica {res['digest'][:16]}... "
                        f"vs producer {want_digest[:16]}...")
    else:
        print(f"digest OK: {res['digest'][:16]}... on both sides "
              f"({len(res['records'])} in-flight requests, "
              f"snapshot step {res['step']})")

    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print("hydrate smoke passed: cold replica hydrated over TCP mid-serve, "
          "decoded in lockstep with zero prefill")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--replica", action="store_true")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()
    if args.replica:
        sys.exit(replica_main(args.port, args.seed, args.steps))
    sys.exit(main())
