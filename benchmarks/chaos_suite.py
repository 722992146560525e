"""CI chaos drill: prove the search stack survives injected faults.

Drives three child runs of the converged fmax suite (fast subset,
``--jobs 2``, disk store + per-design checkpoints):

1. **clean**   — no faults: the identity baseline JSON;
2. **killed**  — seeded worker crashes / hangs / torn store writes, plus
   a SIGKILL of the whole process right after the first design's round-0
   checkpoint commits.  The child MUST die with ``-SIGKILL`` — a clean
   exit means the kill site never fired and the drill is vacuous;
3. **resumed** — the same fault seed without the kill, against the same
   store and checkpoint directories.  It must resume the journal and run
   to completion.

The resumed run's JSON — augmented with a ``chaos`` block recording the
kill and the fault plan — is what ``check_regression.py --tol`` gates
against the clean JSON (``check_chaos``): every per-design row must be
bit-identical to the clean run (faults may only tick counters, never
move the frontier), the pool counters must show retries and rebuilds
actually happened, and the reopened store must have quarantined the
entries torn in the killed run.

The fault plan is pinned (seed and rates below): ``FaultPlan.decide`` is
deterministic per (seed, site, token), so the same points crash/tear on
every CI run and the nonzero-counter gates cannot flake.

CLI:
    python benchmarks/chaos_suite.py --json BENCH_chaos.json \
        [--clean-json BENCH_chaos_clean.json] [--workdir DIR] \
        [--timeout 900] [--keep]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

#: the pinned chaos plan (see repro.search.faults.FaultPlan).  attempts=1
#: keeps every fault transient — retries succeed, nothing is quarantined
#: in the pool, so the frontier-identity gate stays exact.
#: per-future timeout for the FAULT-INJECTED runs only, so injected
#: hangs (hang_s=60) resolve in seconds.  The clean run keeps the stock
#: timeout: a cold ILP solve can legitimately take longer than this, and
#: a spurious timeout on the baseline would be a self-inflicted fault.
POOL_TIMEOUT_S = 10.0

FAULT_PLAN = {"seed": 7, "worker_crash": 0.25, "worker_hang": 0.06,
              "torn_write": 0.30, "hang_s": 60.0, "attempts": 1,
              "pool_timeout_s": POOL_TIMEOUT_S}


def _child_env() -> dict:
    env = os.environ.copy()
    env["PYTHONPATH"] = (str(ROOT / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    return env


def _run_suite(label: str, *, json_path: Path, store: Path, checkpoint: Path,
               fault_plan: dict | None, timeout: float) -> int:
    cmd = [sys.executable, str(ROOT / "benchmarks" / "fmax_suite.py"),
           "--subset", "fast", "--converge", "--jobs", "2",
           "--store", str(store), "--checkpoint", str(checkpoint),
           "--json", str(json_path)]
    if fault_plan is not None:
        cmd += ["--faults", json.dumps(fault_plan)]
    print(f"chaos_suite,RUN,0,{label}: {' '.join(cmd[1:])}", flush=True)
    proc = subprocess.run(cmd, env=_child_env(), cwd=ROOT,
                          timeout=timeout)
    print(f"chaos_suite,EXIT,0,{label} returncode={proc.returncode}",
          flush=True)
    return proc.returncode


def run(json_path: str, clean_json: str | None = None,
        workdir: str | None = None, timeout: float = 900.0,
        keep: bool = False) -> dict:
    out = Path(json_path)
    clean_out = Path(clean_json) if clean_json else (
        out.with_name(out.stem + "_clean" + out.suffix))
    wd = Path(workdir) if workdir else Path(
        tempfile.mkdtemp(prefix="repro-chaos-"))
    wd.mkdir(parents=True, exist_ok=True)
    try:
        rc = _run_suite("clean", json_path=clean_out,
                        store=wd / "clean_store",
                        checkpoint=wd / "clean_ckpt",
                        fault_plan=None, timeout=timeout)
        if rc != 0:
            raise SystemExit(f"chaos_suite: clean run failed (rc={rc})")

        kill_plan = dict(FAULT_PLAN, kill_after_round=0)
        rc_killed = _run_suite("killed", json_path=wd / "killed.json",
                               store=wd / "chaos_store",
                               checkpoint=wd / "chaos_ckpt",
                               fault_plan=kill_plan, timeout=timeout)
        if rc_killed != -signal.SIGKILL:
            raise SystemExit(
                f"chaos_suite: killed run exited rc={rc_killed}, expected "
                f"{-signal.SIGKILL} — the parent_kill site never fired "
                f"and the drill is vacuous")

        rc = _run_suite("resumed", json_path=out,
                        store=wd / "chaos_store",
                        checkpoint=wd / "chaos_ckpt",
                        fault_plan=FAULT_PLAN, timeout=timeout)
        if rc != 0:
            raise SystemExit(
                f"chaos_suite: resumed run failed (rc={rc}) — the search "
                f"did not survive resume under fault injection")
    finally:
        if not keep and workdir is None:
            shutil.rmtree(wd, ignore_errors=True)

    with open(out) as f:
        data = json.load(f)
    resumed = [r["name"] for r in data["rows"]
               if r.get("resumed_rounds", 0) > 0]
    data["chaos"] = {
        "killed_runs": 1,
        "kill_returncode": rc_killed,
        "resumed": bool(resumed),
        "resumed_designs": resumed,
        "fault_plan": FAULT_PLAN,
        "pool_timeout_s": POOL_TIMEOUT_S,
    }
    with open(out, "w") as f:
        json.dump(data, f, indent=2)
    obs = data["sim"]["faults"]["observed"]
    print(f"chaos_suite,KILL,0,returncode={rc_killed} "
          f"resumed={sorted(resumed)}")
    print(f"chaos_suite,OBSERVED,0,retried={obs['retried']} "
          f"timed_out={obs['timed_out']} "
          f"pool_rebuilds={obs['pool_rebuilds']} "
          f"store_quarantined={obs['store_quarantined']}")
    print(f"chaos_suite,JSON,0,wrote {out} (baseline {clean_out})")
    return data


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", dest="json_path", required=True,
                    help="write the resumed run's JSON (+ chaos block) here")
    ap.add_argument("--clean-json", default=None,
                    help="write the clean baseline JSON here "
                         "(default: <json>_clean)")
    ap.add_argument("--workdir", default=None,
                    help="store/checkpoint scratch dir (default: temp dir, "
                         "removed afterwards)")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-child-run timeout in seconds")
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch dir for post-mortem")
    args = ap.parse_args()
    run(args.json_path, clean_json=args.clean_json, workdir=args.workdir,
        timeout=args.timeout, keep=args.keep)


if __name__ == "__main__":
    main()
