"""Benchmark aggregator: one module per paper table/figure (DESIGN.md §6).

    PYTHONPATH=src python -m benchmarks.run [--fast] [--out BENCH_ci.json]

``--out`` writes a machine-readable summary (per-suite status, wall time,
and whatever rows the suite returned) — CI uploads it as the benchmark
trajectory artifact.
"""

import argparse
import json
import time


def _jsonable(obj):
    """Best-effort conversion of bench return values for the JSON report.
    allow_nan=False so non-finite floats become strings instead of the
    bare NaN/Infinity tokens that break strict JSON consumers."""
    try:
        json.dumps(obj, allow_nan=False)
        return obj
    except (TypeError, ValueError):
        if isinstance(obj, (list, tuple)):
            return [_jsonable(x) for x in obj]
        if isinstance(obj, dict):
            return {str(k): _jsonable(v) for k, v in obj.items()}
        for conv in ("item", "tolist"):   # numpy scalars/arrays stay numeric
            fn = getattr(obj, conv, None)
            if fn is not None:
                try:
                    return _jsonable(fn())
                except (TypeError, ValueError):
                    pass
        return str(obj)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the RL-training benches (fig8 / §5.7)")
    ap.add_argument("--out", default=None,
                    help="write a JSON summary of every suite here")
    args = ap.parse_args()

    from benchmarks import (bench_autotune, bench_evaluator, bench_fleet,
                            bench_kernel_throughput, bench_microbench,
                            bench_moves, bench_pipeline, bench_resilience,
                            bench_reward_loop, bench_rl_sensitivity,
                            bench_roofline, bench_serve, bench_session,
                            bench_stall_resolution, bench_workload_analysis)

    suites = [
        ("table1_microbench", bench_microbench.run),
        ("fig7_stall_resolution", bench_stall_resolution.run),
        ("autotune", bench_autotune.run),
        ("fig6_kernel_throughput", bench_kernel_throughput.run),
        ("table3_workload", bench_workload_analysis.run),
        ("roofline", bench_roofline.run),
        # reward-loop throughput: in the --fast set so the CI bench smoke
        # job records the fast-path trajectory in BENCH_ci.json
        ("reward_loop", bench_reward_loop.run),
        # fleet sessions: shared-memo optimize_many vs isolated sessions
        ("session_fleet", bench_session.run),
        # scenario × target campaign: per-bucket tuning + resume + dispatch
        ("fleet_campaign", bench_fleet.run),
        # pipeline schedules: gpipe vs 1F1B memory/throughput + overlapped
        # pod reduction (measured rows need the 8-device CI bench env)
        ("pipeline_schedules", bench_pipeline.run),
        # serve engine under Poisson load: p50/p99 latency + tokens/s vs
        # QPS, continuous vs gang admission, plans on/off (CPU smoke cell)
        ("serve_load", bench_serve.run),
        # fault-injected campaigns through ResilientBackend: success rate,
        # retries absorbed, and bit-exactness vs the fault-free run at
        # transient rates {0, 5, 20}%
        ("resilience", bench_resilience.run),
        # strategy evaluator: the search roster raced under one budget +
        # the memo-trained cost model's held-out rank correlation
        ("strategy_evaluator", bench_evaluator.run),
    ]
    if not args.fast:
        suites += [
            ("fig8_rl_sensitivity", bench_rl_sensitivity.run),
            ("sec57_moves", bench_moves.run),
        ]

    report = []
    for name, fn in suites:
        print(f"\n==== {name} ====", flush=True)
        t0 = time.time()
        entry = {"suite": name, "ok": True}
        try:
            entry["rows"] = _jsonable(fn())
        except Exception as e:  # keep the suite running; a bench failure
            print(f"BENCH-FAIL,{name},{type(e).__name__}: {e}")
            entry.update(ok=False, error=f"{type(e).__name__}: {e}")
        entry["seconds"] = round(time.time() - t0, 3)
        report.append(entry)
        print(f"# {name} took {entry['seconds']:.1f}s", flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"fast": args.fast, "suites": report}, f, indent=2,
                      allow_nan=False)
        print(f"\n# wrote {args.out} "
              f"({sum(r['ok'] for r in report)}/{len(report)} suites ok)")
    if not all(r["ok"] for r in report):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
