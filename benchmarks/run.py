"""Benchmark aggregator: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV and writes benchmarks/results.json.

``--quick`` runs every module at a tiny smoke config (seconds, not minutes) —
the tier-1 suite drives it (tests/test_benchmarks_quick.py) so a refactor
that breaks a benchmark module fails CI instead of rotting silently. Quick
numbers are NOT meaningful measurements; results.json is only written by
full runs.
"""
from __future__ import annotations

import inspect
import json
import sys
import time
import traceback
from pathlib import Path

from repro.launch.compile_cache import use_compile_cache

MODULES = [
    "benchmarks.fig2_cost_wall",
    "benchmarks.table1_system_efficiency",
    "benchmarks.bench_prefetch",
    "benchmarks.bench_affinity",
    "benchmarks.bench_scan_plan",
    "benchmarks.bench_rebatch",
    "benchmarks.bench_feed",
    "benchmarks.bench_multitenant",
    "benchmarks.bench_sharded_store",
    "benchmarks.bench_failover",
    "benchmarks.bench_streaming",
    "benchmarks.bench_chaos",
    "benchmarks.bench_serve",
    "benchmarks.bench_kernels",
    "benchmarks.bench_device_mat",
    "benchmarks.fig4_ne_scaling",
]


def run_module(modname: str, quick: bool = False, telemetry=None):
    """Import + execute one benchmark module, honoring the ``quick`` and
    ``telemetry`` knobs if its ``run`` accepts them."""
    import importlib

    mod = importlib.import_module(modname)
    params = inspect.signature(mod.run).parameters
    kw = {}
    if quick and "quick" in params:
        kw["quick"] = True
    if telemetry is not None and "telemetry" in params:
        kw["telemetry"] = telemetry
    return mod.run(**kw)


def _headline(derived: dict) -> dict:
    """The trajectory-worthy subset of a result's derived dict: throughput
    (rows/s) and tail-latency (p99) figures."""
    return {k: v for k, v in derived.items()
            if "rows_per_s" in k or "p99" in k}


def main() -> None:
    use_compile_cache()
    args = [a for a in sys.argv[1:]]
    quick = "--quick" in args
    if quick:
        args.remove("--quick")
    telemetry = None
    if "--telemetry" in args:
        args.remove("--telemetry")
        from repro.obs import Telemetry

        telemetry = Telemetry()
    only = args[0] if args else None
    all_results = []
    failures = []
    print("name,us_per_call,derived")
    for modname in MODULES:
        if only and only not in modname:
            continue
        t0 = time.time()
        try:
            results = run_module(modname, quick=quick, telemetry=telemetry)
        except Exception as e:
            failures.append(modname)
            print(f"{modname},ERROR,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            continue
        for r in results:
            print(r.csv(), flush=True)
            all_results.append({"name": r.name, "us_per_call": r.us_per_call,
                                "derived": r.derived})
        print(f"# {modname} done in {time.time() - t0:.1f}s", flush=True)

    if telemetry is not None:
        # export the run's metrics/spans/events for `python -m repro.obs.report`
        run_dir = Path(__file__).parent / "telemetry"
        run_dir.mkdir(exist_ok=True)
        telemetry.write_run_dir(run_dir)
        print(f"# telemetry run dir: {run_dir}", flush=True)

    # persist only complete full-mode sweeps: quick numbers are smoke-test
    # noise, and a filtered run would clobber every other module's results
    if not quick and not only:
        out = Path(__file__).parent / "results.json"
        out.write_text(json.dumps(all_results, indent=1, default=str))
        # machine-readable perf trajectory: APPEND one entry per full sweep
        # (bench name -> headline rows/s + p99 figures) so regressions are
        # diffable across commits without parsing CSV logs
        obs = Path(__file__).parent / "BENCH_OBS.json"
        try:
            traj = json.loads(obs.read_text()) if obs.exists() else []
            if not isinstance(traj, list):
                traj = []
        except (ValueError, OSError):
            traj = []
        traj.append({
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime()),
            "results": {r["name"]: {"us_per_call": r["us_per_call"],
                                    **_headline(r["derived"])}
                        for r in all_results},
        })
        obs.write_text(json.dumps(traj, indent=1, default=str))
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
