"""Readings that set a serving cell's correctness limit, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 \
        --seconds 20 [--first-seed N]

For each seed, in one process, a whole run of the cell's driver
(``serve.run``): the engine with that seed's weights, the warm-up, the
lead-in and a window of ``--seconds`` at the cell's own load, then the
check of the sample of finished requests against the float32 reference.
The program's worst gap over the sample is the *lower* reading.  For the
first ``--control`` seeds the control is judged too, by the same check
and limit: the reference with every matmul operand rounded through float8
e4m3 put in the program's place, its own first choices read against the
float32 reference at the same positions; its worst gap is the *upper*
reading, and its ``correct`` has to come out false.  One JSON line per
seed, then a summary line.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    args = ap.parse_args(argv)

    from bench import counts, run
    from bench.drivers import serve
    from bench.drivers.common import Context
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    found = run.find_cell(run.load_benchmark(), args.workload)
    devices = run.devices_for(int(found["cell"]["chips"]))
    lines = []
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        ctx = Context(config=found["config"], mix=found["mix"], seed=seed,
                      seconds=args.seconds, trace=False, devices=devices,
                      t0=time.monotonic(),
                      peaks=counts.peaks_for(devices[0].device_kind))
        rec = serve.run(ctx, controls=("fp8",) if k < args.control else ())
        line = {"seed": seed, "correct": rec["correct"],
                "program": rec["checks"]}
        for prec, verdict in rec["controls"].items():
            line["control_" + prec] = verdict
        lines.append(line)
        print(json.dumps(line), flush=True)
        del rec
        gc.collect()

    def worst(v):
        return v["worst_logit_gap_sigma"]["value"]
    lower = [worst(ln["program"]) for ln in lines]
    upper = [worst(ln["control_fp8"]["checks"]) for ln in lines
             if "control_fp8" in ln]
    print(json.dumps({
        "workload": args.workload, "seeds": len(lines),
        "lower": max(v for v in lower if v is not None) if any(
            v is not None for v in lower) else None,
        "upper": min(upper) if upper and None not in upper else None,
        "program_all_correct": all(ln["correct"] for ln in lines),
        "control_all_not_correct": all(
            not ln["control_fp8"]["correct"] for ln in lines
            if "control_fp8" in ln),
        "seconds": time.monotonic() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
