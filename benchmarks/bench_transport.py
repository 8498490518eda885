"""Transport distribution gate: fault-proxy sweep + fetch latency + a real
two-process leader/follower serve over TCP.

Three measurement groups, all system-scope (host wall clock):

  * the FULL fault-proxy scenario sweep (``conformance.transport_faults``,
    >= 20 scenarios incl. the stale-envelope replay the per-case oracle
    skips): every fetch must land on the detected-or-bit-exact invariant.
    ``--check`` fails on any violation and dumps the failing verdicts to
    ``results/transport_failures/`` (uploaded by CI on failure);
  * clean-path fetch latency (p50/p95 over repeated fetches of the real
    trained-artifact envelope through a live ``ProgramServer``) plus the
    retry-counter account under transient faults — the numbers
    ``ServingScheduler.stats()`` surfaces as transport health;
  * a REAL two-process ``launch.serve`` run over ``--transport tcp://``:
    leader lowers + publishes + serves, follower fetches + verifies +
    serves without lowering (asserted from its cache stats), and both
    label streams must be bit-exact with the in-process ``SNNReference``
    labels — the paper's semantics-preservation claim, now across a
    process boundary and a network hop.

Emits ``results/bench/transport.json`` (schema-validated).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks import common as CM
from repro.conformance.fuzz import fuzz_case
from repro.conformance.transport_faults import SCENARIOS, run_suite
from repro.core.lowering import lower
from repro.core.program_io import serialize_program
from repro.core.runtimes import make_runtime
from repro.distributed import transport as tp

FAILURES_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                            "transport_failures")
#: scenarios below this count means the sweep itself regressed
MIN_SCENARIOS = 20


def _fault_sweep_rows(art, failures_out: str) -> tuple[list[dict], list[dict]]:
    """Every fault scenario against the real artifact's envelope; failing
    verdicts are dumped as JSON repros."""
    prog = lower(art)
    blob = serialize_program(prog)
    # the stale-replay scenario needs a VALID envelope for a different
    # artifact — a fuzzed one is cheap and definitely distinct
    stale = serialize_program(lower(fuzz_case(1).artifact, cache=False))
    t0 = time.perf_counter()
    verdicts = run_suite(blob, art, prog.fingerprint, stale_blob=stale,
                         seed=0)
    wall_ms = 1e3 * (time.perf_counter() - t0)
    bad = [v for v in verdicts if not v["ok"]]
    if bad:
        os.makedirs(failures_out, exist_ok=True)
        for v in bad:
            path = os.path.join(failures_out, f"{v['scenario']}.json")
            with open(path, "w") as f:
                json.dump(v, f, indent=1)
        print(f"  {len(bad)} scenario(s) violated detected-or-bit-exact; "
              f"verdicts dumped to {failures_out}", file=sys.stderr)
    rows = [{"config": f"fault:{v['scenario']}",
             "scope": "system (transport fault proxy, host wall clock)",
             "expect": v["expect"], "outcome": v["outcome"],
             "ok": v["ok"], "connections": v["connections"],
             "wall_ms": v["wall_ms"]} for v in verdicts]
    rows.append({"config": "fault-suite",
                 "scope": "system (transport fault proxy, host wall clock)",
                 "scenarios": len(verdicts),
                 "detected": sum(v["outcome"] == "detected"
                                 for v in verdicts),
                 "bitexact": sum(v["outcome"] == "bitexact"
                                 for v in verdicts),
                 "violations": len(bad),
                 "envelope_bytes": len(blob),
                 "wall_ms": wall_ms})
    return rows, verdicts


def _latency_rows(art, iters: int) -> list[dict]:
    """Clean-path fetch latency + the retry account under transient faults,
    read back through the same metrics surface the scheduler reports."""
    blob = serialize_program(lower(art))
    tp.reset_metrics()
    with tp.ProgramServer(blob) as srv:
        for i in range(iters):
            tp.fetch_bytes(srv.host, srv.port, seed=i)
    snap = tp.metrics_snapshot()
    clean = {"config": "tcp-fetch-clean",
             "scope": "system (transport, host wall clock)",
             "fetches": int(snap.get("fetches", 0)),
             "envelope_bytes": len(blob),
             "fetch_ms_p50": float(snap.get("fetch_ms_p50", 0.0)),
             "fetch_ms_p95": float(snap.get("fetch_ms_p95", 0.0)),
             "fetch_ms_mean": float(snap.get("fetch_ms_mean", 0.0)),
             "fetch_retries": int(snap.get("fetch_retries", 0)),
             "fetch_failures": int(snap.get("fetch_failures", 0))}
    # transient faults: first 2 connections corrupted -> exactly 2 retries
    from repro.conformance.transport_faults import run_scenario
    transient = next(s for s in SCENARIOS
                     if s.name == "transient-flip-twice")
    tp.reset_metrics()
    t0 = time.perf_counter()
    verdict = run_scenario(transient, blob=blob, artifact=art,
                           leader_fingerprint=lower(art).fingerprint)
    snap = tp.metrics_snapshot()
    retry = {"config": "tcp-fetch-transient-faults",
             "scope": "system (transport, host wall clock)",
             "outcome": verdict["outcome"],
             "fetch_attempts": int(snap.get("fetch_attempts", 0)),
             "fetch_retries": int(snap.get("fetch_retries", 0)),
             "fetch_failures": int(snap.get("fetch_failures", 0)),
             "wall_ms": 1e3 * (time.perf_counter() - t0)}
    return [clean, retry]


def _free_port() -> int:
    import socket
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _two_process_row(art, requests: int = 32) -> dict:
    """Leader/follower ``launch.serve`` over tcp://, labels compared
    bit-exact against the in-process software reference."""
    port = _free_port()
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    # the children test the transport, not the device — and this parent
    # already touched JAX, so it holds the chip a child would wait on
    env["JAX_PLATFORMS"] = "cpu"
    leader_npy = os.path.join(CM.RESULTS, "transport_leader_labels.npy")
    follower_npy = os.path.join(CM.RESULTS, "transport_follower_labels.npy")
    art_path = os.path.abspath(CM.ART_PATH)

    def cmd(role: str, labels: str, extra: list[str]) -> list[str]:
        return [sys.executable, "-m", "repro.launch.serve",
                "--snn-artifact", art_path,
                "--transport", f"tcp://127.0.0.1:{port}",
                "--role", role, "--requests", str(requests),
                "--max-batch", "8", "--envelope-timeout", "120",
                "--labels-out", labels] + extra

    t0 = time.perf_counter()
    leader = subprocess.Popen(
        cmd("leader", leader_npy, ["--await-fetches", "1"]),
        cwd=root, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    # hold the follower until the leader's endpoint is live — followers
    # retry, but a cold jax import outlasts any sane retry budget
    lead_out: list[str] = []
    deadline = time.monotonic() + 180.0
    for line in leader.stdout:
        lead_out.append(line)
        if "publishing program at" in line or time.monotonic() > deadline:
            break
    follower = subprocess.Popen(cmd("follower", follower_npy, []),
                                cwd=root, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
    fol_rest, _ = follower.communicate(timeout=300)
    lead_rest, _ = leader.communicate(timeout=300)
    wall_ms = 1e3 * (time.perf_counter() - t0)
    lead_txt = "".join(lead_out) + (lead_rest or "")
    fol_txt = fol_rest or ""

    rng = np.random.RandomState(0)              # serve.py's request stream
    images = rng.rand(requests, lower(art).n_in).astype(np.float32)
    ref_labels = np.asarray(make_runtime(art, "reference")
                            .forward(images).labels)
    lead_labels = (np.load(leader_npy) if os.path.exists(leader_npy)
                   else np.array([]))
    fol_labels = (np.load(follower_npy) if os.path.exists(follower_npy)
                  else np.array([]))
    row = {"config": "two-process-serve-tcp",
           "scope": "system (multi-host serving, host wall clock)",
           "requests": requests,
           "wall_ms": wall_ms,
           "leader_rc": leader.returncode,
           "follower_rc": follower.returncode,
           "leader_lowered": "(cache: 1 lowered" in lead_txt,
           "follower_lowered_zero": "(cache: 0 lowered" in fol_txt,
           "leader_labels_bitexact": bool(
               np.array_equal(lead_labels, ref_labels)),
           "follower_labels_bitexact": bool(
               np.array_equal(fol_labels, ref_labels)),
           "leader_follower_match": bool(
               np.array_equal(lead_labels, fol_labels))}
    if leader.returncode or follower.returncode:
        print("---- leader output ----\n" + lead_txt, file=sys.stderr)
        print("---- follower output ----\n" + fol_txt, file=sys.stderr)
    return row


def main(quick: bool = False, check: bool = False,
         failures_out: str = FAILURES_DIR) -> int:
    art, _xte, _yte = CM.get_artifact_and_data(quick=quick)
    rows: list[dict] = []

    print(f"transport fault-proxy sweep ({len(SCENARIOS)} scenarios, "
          f"detected-or-bit-exact):")
    fault_rows, verdicts = _fault_sweep_rows(art, failures_out)
    rows.extend(fault_rows)
    summary = fault_rows[-1]
    print(f"  {summary['scenarios']} scenarios: {summary['detected']} "
          f"detected, {summary['bitexact']} bit-exact, "
          f"{summary['violations']} violations "
          f"({summary['wall_ms']:.0f} ms)")

    iters = 20 if quick else 100
    lat_rows = _latency_rows(art, iters)
    rows.extend(lat_rows)
    clean, retry = lat_rows
    print(f"clean fetch: p50 {clean['fetch_ms_p50']:.2f} ms  p95 "
          f"{clean['fetch_ms_p95']:.2f} ms over {clean['fetches']} fetches "
          f"({clean['envelope_bytes']} B envelope, "
          f"{clean['fetch_retries']} retries)")
    print(f"transient faults: {retry['fetch_attempts']} attempts, "
          f"{retry['fetch_retries']} retries -> {retry['outcome']}")

    tw = _two_process_row(art)
    rows.append(tw)
    print(f"two-process tcp serve: leader rc={tw['leader_rc']} "
          f"follower rc={tw['follower_rc']}, follower lowered 0: "
          f"{tw['follower_lowered_zero']}, labels bit-exact "
          f"(leader/follower/ref): {tw['leader_follower_match']}/"
          f"{tw['leader_labels_bitexact']}/{tw['follower_labels_bitexact']} "
          f"({tw['wall_ms']:.0f} ms)")

    CM.emit("transport", rows)

    if check:
        bad = []
        if summary["scenarios"] < MIN_SCENARIOS:
            bad.append(f"only {summary['scenarios']} fault scenarios ran "
                       f"(floor {MIN_SCENARIOS})")
        for v in verdicts:
            if not v["ok"]:
                bad.append(f"{v['scenario']}: expected {v['expect']}, got "
                           f"{v['outcome']} ({v['detail']})")
        if clean["fetch_retries"] or clean["fetch_failures"]:
            bad.append(f"clean path needed {clean['fetch_retries']} retries "
                       f"/ {clean['fetch_failures']} failures")
        if retry["outcome"] != "bitexact":
            bad.append(f"transient-fault fetch ended {retry['outcome']!r}, "
                       f"not healed by retries")
        if retry["fetch_retries"] < 2:
            bad.append(f"transient scenario recorded "
                       f"{retry['fetch_retries']} retries (expected >= 2)")
        if tw["leader_rc"] or tw["follower_rc"]:
            bad.append(f"two-process serve exited "
                       f"leader={tw['leader_rc']} "
                       f"follower={tw['follower_rc']}")
        if not tw["follower_lowered_zero"]:
            bad.append("follower lowered locally instead of consuming the "
                       "leader's envelope")
        for k in ("leader_labels_bitexact", "follower_labels_bitexact",
                  "leader_follower_match"):
            if not tw[k]:
                bad.append(f"two-process serve: {k} is False — served "
                           f"labels diverged")
        if bad:
            print("CHECK FAILED: " + "; ".join(bad), file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer clean-fetch iterations (the CI config)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on any detected-or-bit-exact violation, "
                         "retry-accounting drift, or two-process label "
                         "divergence")
    ap.add_argument("--failures-out", default=FAILURES_DIR,
                    help="directory for failing scenario verdict dumps")
    a = ap.parse_args()
    sys.exit(main(quick=a.quick, check=a.check,
                  failures_out=a.failures_out))
