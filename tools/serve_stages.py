"""Where a serving cell's time goes, by the engine's own stages: a
measurement made on the chip beside the benchmark, not part of it.

    python tools/serve_stages.py run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>
    python tools/serve_stages.py hlo --workload <cell> [--stages <file>]
    python tools/serve_stages.py span-cost [--workload <cell>]

``run`` is one run of ``bench/run.py`` with the same arguments and the same
output, followed by one line ``stages {...}``: the engine's stage counters
over the window (``bench.stages``), with and without the traced stretch,
and with ``--trace 1`` the device idle time split by the engine's host
spans (``bench.stages.split_idle``), per device op the bytes of its output
shape, and per op instance its events, seconds and bytes (written to
``chiprun_out/ops-<cell>-<seed>.json``).

``hlo`` compiles the paged step at the cell's geometry, writes its
optimized HLO to ``chiprun_out/step-<cell>.hlo.txt`` and lists its copies by
named scope (``repro.serve.decode``), with their bytes (``copy`` apart from
the asynchronous ``copy-start``/``copy-done`` transfers), and the compiler's
argument, output, temporary and aliased bytes, and the fusions and copies
that re-lay out weights (``weight_relayouts``); given a traced
``run``'s stages file, it joins the traced copies to them, per pass.

``span-cost`` times an inactive ``repro.serve`` span and a clock read; with
``--workload``, also the engine's host time per pass with its spans on and
swapped for no-ops, in alternating blocks of steps.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import statistics
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OUT = os.path.join(ROOT, "chiprun_out")


def _dump(name: str, obj) -> str:
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, name)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))
    return path


# ------------------------------------------------------------------- run

class Capture:
    """Patches the driver and the trace reduction to keep what the
    benchmark drops: the run's record, the program's spans and each device
    op instance."""

    def __init__(self):
        from bench import stages, trace
        from bench.drivers import serve
        self.record = None
        self.ops = {}
        self.n_program_spans = 0
        run, from_file, reduce = serve.run, trace.Events.from_file, \
            trace.reduce

        def run_kept(ctx, *a, **kw):
            self.record = run(ctx, *a, **kw)
            return self.record

        def from_file_kept(path):
            ev = from_file(path)
            ev.program_spans = stages.read_program_spans(path)
            self.ops = _op_instances(path, ev.window())
            return ev

        def reduce_split(ev, *a, **kw):
            out = reduce(ev, *a, **kw)
            if out is not None:
                self.n_program_spans = len(ev.program_spans)
                out.update(stages.split_idle(ev, ev.program_spans) or {})
            return out

        serve.run = run_kept
        trace.Events.from_file = staticmethod(from_file_kept)
        trace.reduce = reduce_split


def _op_instances(path: str, window):
    """Per device op instance name (``copy.12``; with its module where the
    trace names one): events, seconds and output bytes in the window, and
    the event's statistics once."""
    from jax.profiler import ProfileData

    from bench import stages, trace
    lo, hi = window or (-math.inf, math.inf)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                if e <= lo or s >= hi:
                    continue
                stats = {k: v for k, v in ev.stats}
                text = ev.name if " = " in ev.name else next(
                    (str(v) for v in stats.values()
                     if isinstance(v, str) and " = " in v), "")
                name = (text.split(" = ", 1)[0].strip().lstrip("%")
                        if text else ev.name)
                module = next((str(v) for k, v in stats.items()
                               if "module" in k), "")
                key = f"{module}:{name}" if module else name
                rec = out.setdefault(key, {
                    "events": 0, "seconds": 0.0,
                    "bytes": stages.output_bytes(text) if text else None,
                    "stats": {k: str(v)[:200] for k, v in stats.items()}})
                rec["events"] += 1
                rec["seconds"] += (min(e, hi) - max(s, lo)) / 1e9
    return out


def _ms(record, key, den, traced: bool):
    """Per ``den`` of the counter ``key``, in ms, over the traced stretch
    (``traced``) or over the window less it."""
    from bench import stages
    c = record.get("counters_traced")
    if traced:
        if not c or key not in c[0]["engine"]:
            return None
        d = c[1]["engine"][den] - c[0]["engine"][den]
        return ((c[1]["engine"][key] - c[0]["engine"][key]) / d * 1e3
                if d else None)
    return stages._engine_ratio(record, key, den)


def summarize(record, cap: Capture) -> dict:
    from bench import readers
    W = record["window_s"]
    first = [(r["stamps"][0] - r["due"]) * 1e3 for r in record["requests"]
             if not r["failed"] and r["stamps"] and 0 <= r["stamps"][0] <= W
             and readers._untraced(record, r["stamps"][0])]
    out = {
        "pass_ms": readers.pass_ms(record),
        "host_ms_per_pass": _ms(record, "host_s", "passes", False),
        "sync_ms_per_pass": _ms(record, "sync_s", "passes", False),
        "host_ms_per_pass_traced": _ms(record, "host_s", "passes", True),
        "sync_ms_per_pass_traced": _ms(record, "sync_s", "passes", True),
        "queue_wait_ms": _ms(record, "queue_wait_s", "admissions", False),
        "prefill_ms": _ms(record, "prefill_s", "first_tokens", False),
        "due_to_first_token_mean_ms": sum(first) / len(first) if first
        else None,
        "first_tokens_counted": len(first),
        # passes dispatched behind an unread pass of their tick, a share
        "passes_ahead_share": _ms(record, "passes_ahead", "passes", False),
    }
    tr = record.get("trace")
    if tr and "idle_by_program_span" in tr:
        split = tr["idle_by_program_span"]
        c = record["counters_traced"]
        passes = c[1]["engine"]["passes"] - c[0]["engine"]["passes"]
        in_step = sum(v for k, v in split.items()
                      if k.startswith("repro.serve."))
        unlabelled = split.get("bench.engine_step", 0.0)
        out.update({
            "window_s": tr["window_s"],
            "idle_share": 100 * tr["idle_share_max"],
            "idle_engine_share": 100 * tr["idle_in_engine_s"]
            / tr["window_s"],
            "idle_by_program_span": split,
            "engine_step_idle_unlabelled_share": 100 * unlabelled
            / (in_step + unlabelled) if in_step + unlabelled else None,
            "traced_passes": passes,
            "program_spans_per_pass": cap.n_program_spans / passes
            if passes else None,
            "idle_ms_per_pass_by_span": {
                k: v / passes * 1e3 for k, v in split.items()} if passes
            else None,
        })
        by_op = defaultdict(lambda: [0, 0.0, 0])
        for key, r in cap.ops.items():
            op = re.sub(r"\.\d+$", "", key.rsplit(":", 1)[-1])
            by_op[op][0] += r["events"]
            by_op[op][1] += r["seconds"]
            by_op[op][2] += r["events"] * (r["bytes"] or 0)
        top = sorted(by_op.items(), key=lambda kv: -kv[1][1])[:12]
        # per op name: events, seconds, output bytes over its events
        out["op_output_bytes"] = {k: v for k, v in top}
    return out


def cmd_run(argv) -> int:
    from bench import run as bench_run
    cap = Capture()
    args = dict(zip(argv[::2], argv[1::2]))
    rc = bench_run.main(argv)
    if cap.record is None:
        return rc
    out = summarize(cap.record, cap)
    if cap.ops:
        out["ops_file"] = _dump(
            f"ops-{args['--workload']}-{args['--seed']}.json", cap.ops)
    line = json.dumps(out)
    _dump(f"stages-{args['--workload']}-{args['--seed']}-t"
          f"{args.get('--trace', '0')}.json", line)
    print("stages " + line, flush=True)
    return rc


# ------------------------------------------------------------------- HLO

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\}?\s*([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _entry_lines(hlo: str):
    """(name, right-hand side, opcode match) of each instruction of the
    entry computation of an HLO module's text."""
    from bench import stages
    inside = False
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            inside = True
            continue
        if inside and line.startswith("}"):
            return
        m = _INSTR.match(line) if inside else None
        if m:
            name, rhs = m.groups()
            yield name, rhs, _OPCODE.search(
                rhs, stages._close(rhs) if rhs.startswith("(") else 0)


def entry_instructions(hlo: str):
    """(name, opcode, op_name, output bytes, result) of each instruction of
    the entry computation of an HLO module's text; ``result`` is the result
    shape without its layout, ``@S<n>`` where it lives in memory space n."""
    from bench import stages
    out = []
    for name, rhs, op in _entry_lines(hlo):
        end = op.start(1) if op else len(rhs)
        result = re.sub(r"\{[^}]*\}", "", rhs[:end]).strip()
        space = re.search(r"S\((\d+)\)", rhs[:end])
        meta = _OP_NAME.search(rhs)
        out.append((name, op.group(1) if op else "?",
                    meta.group(1) if meta else "",
                    stages.output_bytes(f"{name} = {rhs}"),
                    result + (f"@S{space.group(1)}" if space else "")))
    return out


def scope(op_name: str) -> str:
    """An op_name without the jit prefix and with layer and list indices
    as ``*``: ``jit(step)/layer3/attn/kv_write/scatter`` ->
    ``layer*/attn/kv_write/scatter``, ``caches[3]['k']`` ->
    ``caches[*]['k']``."""
    parts = [p for p in op_name.split("/") if not p.startswith("jit(")]
    text = re.sub(r"layer\d+", "layer*", "/".join(parts))
    return re.sub(r"\[\d+\]", "[*]", text) or "(none)"


def count_copies(instrs) -> dict:
    """Copies among ``entry_instructions``: ``copy`` (a new buffer in
    another layout or place, on the compute units) apart from the
    asynchronous transfers between memory spaces (``copy-start`` and its
    ``copy-done``, whose result is the data moved), and all of them by
    opcode, named scope and result."""
    static = defaultdict(lambda: [0, 0])
    for name, op, op_name, nbytes, result in instrs:
        if op.startswith("copy"):
            k = f"{op} {scope(op_name)} {result}"
            static[k][0] += 1
            static[k][1] += nbytes or 0

    def total(opcode, i):
        return sum(v[i] for k, v in static.items()
                   if k.split(" ", 1)[0] == opcode)
    return {"copies_in_step": total("copy", 0),
            "copy_bytes_in_step": total("copy", 1),
            "async_copies_in_step": total("copy-start", 0),
            "async_copy_bytes_in_step": total("copy-done", 1),
            "copies_by_scope": dict(sorted(static.items(),
                                           key=lambda kv: -kv[1][1]))}


_OPERAND = re.compile(r"%([\w.\-]+)")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
# ops that move or rename data without computing on it
_MOVES = ("bitcast", "get-tuple-element", "tuple", "slice-start",
          "slice-done", "async-start", "async-done", "copy-start",
          "copy-done", "custom-call")


def weight_relayouts(hlo: str) -> dict:
    """Fusions and copies of the entry computation that re-lay out weights:
    one of its operands comes from a ``params`` argument of the step (as
    it is, or through moves: bitcasts, tuple elements, asynchronous slices
    and copies) and its result is, or holds, an array of a weight's shape
    (a ``params`` argument's shape, or a stacked one's less its layer
    axis).  Count and result bytes, in all and by instruction name less its
    number (``slice_bitcast_fusion``, ``copy``)."""
    from bench import stages
    operands = {}
    for name, rhs, op in _entry_lines(hlo):
        if op:
            args = rhs[op.end(1):]
            operands[name] = _OPERAND.findall(args[:stages._close(args)])
    instrs = entry_instructions(hlo)
    derived, shapes = set(), set()
    for name, op, op_name, _, result in instrs:
        if op == "parameter" and op_name.startswith("params"):
            derived.add(name)
            for dtype, dims in _SHAPE.findall(result):
                d = dims.split(",")
                if len(d) >= 2:
                    shapes.add((dtype, tuple(d)))
                if len(d) >= 3:
                    shapes.add((dtype, tuple(d[1:])))
    by_name = defaultdict(lambda: [0, 0])
    for name, op, _, nbytes, result in instrs:
        if not derived.intersection(operands.get(name, ())):
            continue
        if op in _MOVES:
            derived.add(name)
        elif op in ("copy", "fusion") and any(
                (dtype, tuple(dims.split(","))) in shapes
                for dtype, dims in _SHAPE.findall(result)):
            derived.add(name)
            k = by_name[re.sub(r"\.\d+$", "", name)]
            k[0] += 1
            k[1] += nbytes or 0
    return {"count": sum(v[0] for v in by_name.values()),
            "bytes": sum(v[1] for v in by_name.values()),
            "by_name": dict(by_name)}


def step_shapes(found):
    """Abstract arguments of the paged step at the cell's geometry."""
    import functools

    import jax
    import jax.numpy as jnp

    from bench.drivers.common import program_config
    from repro.models import lm
    from repro.serve.decode import init_paged_caches, serving_params
    c = found["config"]
    geo = c["engine"]
    pcfg = program_config(c)
    params = serving_params(jax.eval_shape(
        functools.partial(lm.init_model, pcfg), jax.random.PRNGKey(0)))
    caches = jax.eval_shape(functools.partial(
        init_paged_caches, pcfg, geo["kv_blocks"], geo["block_size"],
        geo["max_batch"]))
    B = geo["max_batch"]
    lane = jax.ShapeDtypeStruct((B,), jnp.int32)
    table = jax.ShapeDtypeStruct(
        (B, -(-geo["max_seq"] // geo["block_size"])), jnp.int32)
    return pcfg, geo, (params, caches, lane, table, lane, lane, lane, lane,
                       lane)


def cmd_hlo(argv) -> int:
    ap = argparse.ArgumentParser(prog="serve_stages.py hlo")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--stages", default=None,
                    help="a traced run's stages file: join its copies")
    args = ap.parse_args(argv)
    from bench import run as bench_run
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve.engine import _paged_step_fns
    use_compile_cache()
    found = bench_run.find_cell(bench_run.load_benchmark(), args.workload)
    pcfg, geo, shapes = step_shapes(found)
    step = _paged_step_fns(pcfg, None, geo["max_seq"])[0]
    t = time.monotonic()
    compiled = step.lower(*shapes).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"[hlo] compiled in {time.monotonic() - t:.1f}s; "
          f"{_dump(f'step-{args.workload}.hlo.txt', text)}", flush=True)
    instrs = entry_instructions(text)
    out = count_copies(instrs)
    out["weight_relayouts_in_step"] = weight_relayouts(text)
    out["memory_bytes"] = {k: getattr(mem, f"{k}_size_in_bytes")
                           for k in ("argument", "output", "temp", "alias")}
    if args.stages:
        with open(args.stages) as f:
            run = json.load(f)
        with open(run["ops_file"]) as f:
            ops = json.load(f)
        meta = {name: (f"{op} {scope(op_name)} {result}", nbytes)
                for name, op, op_name, nbytes, result in instrs}
        joined = defaultdict(lambda: [0, 0.0, 0])
        for key, r in ops.items():
            name = key.rsplit(":", 1)[-1]
            if not name.startswith("copy"):
                continue
            k, nbytes = meta.get(name, (f"{name} (not in the step)", None))
            joined[k][0] += r["events"]
            joined[k][1] += r["seconds"]
            joined[k][2] += r["events"] * (nbytes or r["bytes"] or 0)
        n = run["traced_passes"]
        out["traced_copies_per_pass"] = {
            k: [v[0] / n, v[1] / n * 1e3, v[2] / n / 1e6]
            for k, v in sorted(joined.items(), key=lambda kv: -kv[1][1])}
        out["traced_copies_per_pass_units"] = ["events", "ms", "MB"]
        out["sample_stats"] = next(iter(ops.values()))["stats"] if ops \
            else None
    print("hlo " + json.dumps(out), flush=True)
    return 0


# ------------------------------------------------------------- span cost

def cmd_span_cost(argv) -> int:
    """An inactive span and a clock read, timed alone; with ``--workload``,
    also the engine's host time per pass (``host_s``) with its spans and
    with them swapped for no-ops, in alternating blocks of steps over the
    cell's closed backlog."""
    import contextlib

    from repro.serve import engine as engine_mod
    ap = argparse.ArgumentParser(prog="serve_stages.py span-cost")
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--blocks", type=int, default=16)
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args(argv)
    span = engine_mod.span
    n = 200_000
    t = time.perf_counter()
    for _ in range(n):
        with span("pass"):
            pass
    per_span = (time.perf_counter() - t) / n
    t = time.perf_counter()
    for _ in range(n):
        time.perf_counter()
    per_clock = (time.perf_counter() - t) / n
    out = {"inactive_span_us": per_span * 1e6,
           "perf_counter_us": per_clock * 1e6}
    if args.workload:
        out.update(_spans_on_off(args, engine_mod, span,
                                 lambda name: contextlib.nullcontext()))
    print("span-cost " + json.dumps(out), flush=True)
    return 0


def _spans_on_off(args, engine_mod, real, noop) -> dict:
    import jax

    from bench import counts, gen
    from bench import run as bench_run
    from bench.drivers import serve
    from bench.drivers.common import Context
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    found = bench_run.find_cell(bench_run.load_benchmark(), args.workload)
    dev = jax.devices()[:1]
    ctx = Context(config=found["config"], mix=found["mix"], seed=args.seed,
                  seconds=0.0, trace=False, devices=dev,
                  t0=time.monotonic(),
                  peaks=counts.peaks_for(dev[0].device_kind))
    engine = serve.build(ctx)
    serve.warm_up(engine, ctx)
    specs = iter(gen.generate(ctx.mix, args.seed, 100_000,
                              ctx.config["vocab_size"]))
    backlog = 2 * engine.max_batch
    per = {"on": [], "off": []}
    for block in range(args.blocks):
        which = "on" if block % 2 == 0 else "off"
        engine_mod.span = real if which == "on" else noop
        while engine.scheduler.pending() < backlog:
            s = next(specs)
            engine.submit(s.prompt, s.max_new_tokens, tenant=s.tenant)
        c0 = dict(engine.counters)
        t = time.perf_counter()
        for _ in range(args.steps):
            engine.step()
        wall = time.perf_counter() - t
        passes = engine.counters["passes"] - c0["passes"]
        per[which].append(((engine.counters["host_s"] - c0["host_s"])
                           / passes * 1e3, wall / passes * 1e3))
    engine_mod.span = real
    return {f"{k}_{name}_ms_per_pass": statistics.median(r[i] for r in v)
            for k, v in per.items()
            for i, name in enumerate(("host", "step"))} | {"blocks": per}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cmds = {"run": cmd_run, "hlo": cmd_hlo, "span-cost": cmd_span_cost}
    if not argv or argv[0] not in cmds:
        print(__doc__, file=sys.stderr)
        return 2
    return cmds[argv[0]](argv[1:])


if __name__ == "__main__":
    sys.exit(main())
