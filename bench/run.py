"""Run one benchmark cell once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration file,
its traffic mix (``bench/traffic/<traffic>.json``), its driver
(``bench/drivers/<driver>.py``, named by the configuration file) and one
reader per metric (``bench/metrics/<metric>.py``) are found from there.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read partly from a profiler trace of a
stretch of the window.

The run needs a TPU and the chips the cell asks for: without them it exits
with code 1 and prints no result.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
number compared with its limit.  The checks are also the last lines of
standard error.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
METRICS_DIR = os.path.join(ROOT, "bench", "metrics")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    """The cell ``name``: its entry, its configuration file and its mix."""
    from bench import gen
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    return {"cell": cell, "config": config, "mix": gen.load_mix(cell["traffic"])}


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The metrics a run of ``workload`` reports: its end-to-end metrics,
    or with a trace its per-layer metrics."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(METRICS_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(bench: dict, workload: str, trace: bool, record: dict
                 ) -> dict:
    """Each metric's reader on the run's record; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_for(bench, workload, trace):
        v = load_reader(m["name"])(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def devices_for(chips: int):
    import jax
    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform if devices else 'no'} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def result_line(record: dict, metrics: dict, device: dict, trace: bool
                ) -> dict:
    out = {"correct": bool(record["correct"]),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]),
           "metrics": metrics,
           "device": device}
    tr = record.get("trace")
    if trace and tr:
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = record["checks"]
    return out


def run_cell(bench: dict, workload: str, found: dict, seed: int,
             seconds: float, trace: bool, devices, t0: float,
             peaks: dict = None) -> dict:
    """One run of a found cell on ``devices``; returns the result line."""
    from bench import counts
    from bench.drivers.common import Context
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print(f"[bench] device {device} at {time.monotonic() - t0:.1f}s",
          flush=True)
    driver = importlib.import_module(
        f"bench.drivers.{found['config']['driver']}")
    ctx = Context(config=found["config"], mix=found["mix"], seed=seed,
                  seconds=seconds, trace=trace, devices=devices, t0=t0,
                  peaks=peaks or counts.peaks_for(d0.device_kind))
    record = driver.run(ctx)
    device["memory_peak_bytes"] = record.get("memory_peak_bytes")
    tr = record.get("trace")
    if trace:
        if tr:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            print(f"[bench] trace: busy {tr['busy_s']}s of {tr['window_s']}s;"
                  f" idle by host span {tr['idle_by_span']}", flush=True)
            print(f"[bench] trace ops [events, seconds, byte statistics]: "
                  f"{tr['op_stats']}", flush=True)
        else:
            print("[bench] trace: no device operation in the traced window",
                  flush=True)
    metrics = read_metrics(bench, workload, trace, record)
    return result_line(record, metrics, device, trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    found = find_cell(bench, args.workload)

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"[bench] compile cache {cache}", flush=True)
    try:
        devices = devices_for(int(found["cell"]["chips"]))
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 1
    out = run_cell(bench, args.workload, found, args.seed, args.seconds,
                   bool(args.trace), devices, T0)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
