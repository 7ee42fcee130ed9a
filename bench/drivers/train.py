"""Training driver: the program's ``Trainer`` on the cell's chips, a window
of whole steps, then the check of the first step against the float32
reference.

Set-up makes the weights from the seed (``bench.weights``), builds
``Trainer`` on a mesh of the cell's devices shaped as the job file says
(``mesh``: axis -> size) with those weights placed in the step's layout
and the AdamW moments at zero, and runs ``steps_before_window`` steps: the
first compiles the step, and every later one runs the same executable.
The window then calls ``trainer.step()`` until ``--seconds`` have passed;
each step blocks on its metrics.  The window holds whole steps only: it
closes when the step running at ``--seconds`` returns.  ``serve_tok_s``
reads each step as a request whose every position trained is a token
delivered when the step returns, so it is the tokens trained over the
time from the first step's call to the last one's return.

The weights are made on the chips in the step's own layout, so no chip
holds the whole model.  Right after the first step the program's step is
read per leaf, against the seed's weights made again: the norm of the
parameters' change and the norm of the first moment.

After the window: the peak device memory is read, the trainer is freed,
and the first step (on the seed's weights and the job's first batch) is
compared with ``bench.reference.train``'s on the same weights and batch:
its loss, its global gradient norm, and per leaf the first moment and the
parameters' change, each judged by its worst leaf.  ``nonfinite_steps``
counts every step whose loss or gradient norm was not finite.
"""

from __future__ import annotations

import functools
import gc
import math
import shutil
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import counts, stages, train_readers
from bench import trace as trace_mod
from bench import weights as W
from bench.drivers import common
from bench.drivers.common import (CompileCounter, Context, GcClock,
                                  HostClock, Tracer, log, memory_peak, span)
from bench.reference import train as reference

TRACE_FROM = 0.88          # the window's last 12% is traced
# Device events that enclose other events (a scanned layer stack runs as a
# ``while`` whose body's ops are events of their own): not compute.
CONTAINERS = frozenset({"while", "conditional", "call"})


def program_config(c: Dict):
    """``common.program_config`` for every published key but
    ``partial_rotary_factor``, which becomes the program's
    ``rope_fraction`` (the share of each head that rotary rotates)."""
    rest = {k: v for k, v in c.items() if k != "partial_rotary_factor"}
    return common.program_config(rest).replace(
        rope_fraction=float(c.get("partial_rotary_factor", 1.0)))


def mesh_for(devices, shape: Dict[str, int]):
    """A mesh of ``devices`` with the job's axes, in order."""
    names = tuple(shape)
    arr = np.asarray(devices).reshape(tuple(int(shape[n]) for n in names))
    return jax.sharding.Mesh(
        arr, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_weights(seed: int, pcfg, mesh):
    """The seed's weights (``bench.weights``), made on the chips in the
    train step's parameter layout."""
    from repro.models import lm
    from repro.train.step import train_state_specs

    shapes = jax.eval_shape(functools.partial(lm.init_model, pcfg),
                            jax.random.PRNGKey(0))
    shardings = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        train_state_specs(pcfg, mesh).params,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    return jax.jit(lambda: W.make_params(seed, shapes),
                   out_shardings=shardings)()


def build(ctx: Context):
    """The trainer, with the seed's weights in the step's layout."""
    from repro.train import TrainConfig, Trainer

    c, job = ctx.config, ctx.mix
    pcfg = program_config(c)
    mesh = mesh_for(ctx.devices, job["mesh"])
    params = make_weights(ctx.seed, pcfg, mesh)
    jax.block_until_ready(params)
    log(f"weights made at {time.monotonic() - ctx.t0:.1f}s")
    tcfg = TrainConfig(steps=int(job["schedule_steps"]),
                       seq_len=int(job["seq_len"]),
                       global_batch=int(job["global_batch"]),
                       lr=float(job["lr"]), warmup=int(job["warmup"]),
                       weight_decay=float(job["weight_decay"]),
                       num_microbatches=int(job["microbatches"]),
                       seed=ctx.seed)
    trainer = Trainer(pcfg, tcfg, mesh=mesh, params=params)
    del params
    jax.block_until_ready(trainer.state)
    return trainer


def optimizer_of(job: Dict) -> Dict:
    """AdamW's first step as the job states it, for the reference: the
    learning rate that the linear warm-up gives step 1 (the peak over the
    warm-up steps; the peak with no warm-up).  The program's ``Trainer``
    has the betas, epsilon and clip as constants; a job that states
    others fails the check."""
    return {"lr": float(job["lr"]) / max(int(job["warmup"]), 1),
            "b1": float(job["adam_b1"]), "b2": float(job["adam_b2"]),
            "eps": float(job["adam_eps"]),
            "weight_decay": float(job["weight_decay"]),
            "clip": float(job["clip_norm"])}


def first_step_change(state, seed: int) -> Dict[str, Dict[str, float]]:
    """Per leaf of the program's tree, after its first step from the
    seed's weights: the norm of the parameters' change (``update_norms``)
    and of the first moment (``moment_norms``), which started at zero.
    The initial weights are made again inside the reduction, a leaf at a
    time, so they never sit whole beside the state; they are rounded to
    the parameters' dtype explicitly, since a cast that is never written
    out may keep excess precision on a TPU."""
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          state.params)

    def f32(a):
        return a.astype(jax.numpy.float32)

    @jax.jit
    def norms(p1, mu):
        p0 = W.make_params(seed, shapes)
        sq = jax.numpy.sum
        return (jax.tree.map(
            lambda a, b: sq((f32(a) - reference.stored(f32(b), a.dtype)) ** 2),
            p1, p0), jax.tree.map(lambda m: sq(f32(m) ** 2), mu))

    upd, mom = norms(state.params, state.opt_state.mu)

    def by_path(tree):
        return {W.path_str(p): math.sqrt(float(v))
                for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}

    return {"update_norms": by_path(upd), "moment_norms": by_path(mom)}


def finite(m: Dict) -> bool:
    return math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"])


def collective_ns(ev: trace_mod.Events) -> Dict[str, List[int]]:
    """Per chip, over the traced window: the collectives' time, and the
    part of it with no other operation running.  Unlike
    ``trace.reduce``'s ``collective_exposed_ns``, an enclosing ``while``
    (the scanned layers) does not count as compute, so a collective inside
    a scanned layer is exposed unless another operation overlaps it."""
    lo, hi = ev.window()
    out = {}
    for name, ops in sorted(ev.devices.items()):
        coll = trace_mod.clip([(s, e) for n, s, e in ops
                               if trace_mod.COLLECTIVE.search(n)], lo, hi)
        comp = trace_mod.clip([(s, e) for n, s, e in ops
                               if not trace_mod.COLLECTIVE.search(n)
                               and n not in CONTAINERS], lo, hi)
        out[name] = [trace_mod.total(trace_mod.union(coll)),
                     trace_mod.total(trace_mod.subtract(coll, comp))]
    return out


class TrainTracer(Tracer):
    """``Tracer`` that also reads from the trace, before it is removed, the
    collectives' time without the enclosing loops (:func:`collective_ns`)
    and the program's own spans (``repro.train.*``): per span its count
    and seconds, and the device idle time under each."""

    def reduce(self) -> Optional[Dict]:
        if self.state != "on":
            return None
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"
        try:
            path = trace_mod.find_xplane(self.dir)
            if path is None:
                print("[bench] the profiler wrote no trace", file=sys.stderr)
                return None
            t = time.monotonic()
            ev = trace_mod.Events.from_file(path)
            out = trace_mod.reduce(ev)
            if out is not None:
                out["collective_ns"] = collective_ns(ev)
                spans = stages.read_program_spans(path)
                seen: Dict[str, List[float]] = {}
                for name, s, e in spans:
                    n = seen.setdefault(name, [0, 0.0])
                    n[0] += 1
                    n[1] += (e - s) / 1e9
                out["program_spans"] = seen
                split = stages.split_idle(ev, spans)
                out["idle_by_program_span"] = (
                    split or {}).get("idle_by_program_span")
            log(f"trace reduced in {time.monotonic() - t:.1f}s")
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def least_step_seconds(c: Dict, job: Dict, peaks: Dict, chips: int) -> float:
    """``bench.counts.train_step``'s least time for one step at the peaks
    of ``chips`` chips together."""
    dm = counts.DenseDims.from_config(c)
    flops, nbytes = counts.train_step(dm, int(job["global_batch"]),
                                      int(job["seq_len"]))
    together = {"bf16_flops_per_s": peaks["bf16_flops_per_s"] * chips,
                "hbm_bytes_per_s": peaks["hbm_bytes_per_s"] * chips}
    return counts.least_seconds(flops, nbytes, together)[0]


def window(trainer, ctx: Context) -> Dict:
    """The measured window; returns the run's record (no result judged).
    Steps are ``(start, end, tokens, finite)``, in seconds from the
    window's start, which is the call of its first step; the window ends
    at the last one's return."""
    W_s = float(ctx.seconds)
    host = HostClock()
    tracer = TrainTracer(ctx.trace, TRACE_FROM * W_s)
    counter = CompileCounter()
    gc_clock = GcClock()
    steps = []
    snaps = {}

    def snapshot():
        return {train_readers.GROUP: dict(trainer.counters)}

    counter.active = gc_clock.active = True
    host.start()
    t_start = time.monotonic()
    setup_s = t_start - ctx.t0
    snaps["start"] = snapshot()
    now = 0.0
    while now < W_s:
        was = tracer.state
        tracer.poll(now)
        if tracer.state != was:
            snaps["trace_" + tracer.state] = snapshot()
        tokens = trainer.counters["tokens"]
        with span("train_step"):
            m = trainer.step()
        t1 = time.monotonic() - t_start
        steps.append((now, t1, trainer.counters["tokens"] - tokens,
                      finite(m)))
        now = t1
    counter.active = gc_clock.active = False
    host.stop()
    snaps["end"] = snapshot()
    trace = tracer.reduce()

    took = sorted(e - s for s, e, _, _ in steps)
    log(f"window {now:.3f}s: {len(steps)} steps; step time median {took[len(took) // 2] * 1e3:.1f} ms, shortest "
        f"{took[0] * 1e3:.1f}, longest {took[-1] * 1e3:.1f}; executables "
        f"built in the window: {counter.count}; garbage collection "
        f"{gc_clock.count} times, {gc_clock.seconds * 1e3:.1f} ms")
    log(f"host in the window: {host}")
    log(f"trainer counters: {dict(trainer.counters)}")
    if trace is not None:
        log(f"program spans [count, seconds]: {trace.get('program_spans')}")
        log(f"idle by program span: {trace.get('idle_by_program_span')}")
        log("collective seconds per chip [all, exposed]: " + str(
            {d: [v / 1e9 for v in c]
             for d, c in trace["collective_ns"].items()}))
    return {
        "kind": "train",
        "setup_s": setup_s,
        "window_s": now,
        "traced": [tracer.start, now] if "trace_on" in snaps else None,
        "steps": steps,
        "requests": [{"due": s, "failed": not ok, "prompt_len": 0,
                      "stamps": [e] * n} for s, e, n, ok in steps],
        "least_step_s": least_step_seconds(ctx.config, ctx.mix, ctx.peaks,
                                           len(ctx.devices)),
        "counters0": snaps["start"],
        "counters1": snaps["end"],
        "counters_traced": ([snaps["trace_on"], snaps["end"]]
                            if "trace_on" in snaps else None),
        "compiles_in_window": counter.count,
        "trace": trace,
    }


def _rel(got: float, want: float) -> float:
    """``|got - want| / want``; where ``want`` is zero, 0 if ``got`` is
    too and 1 otherwise."""
    if want == 0:
        return 0.0 if got == 0 else 1.0
    return abs(got - want) / abs(want)


def worst_leaf(got: Dict[str, float], want: Dict[str, float]) -> float:
    """The widest relative gap over the reference's leaves; a leaf the
    program lacks reads 1."""
    return max(_rel(got.get(k, 0.0), v) for k, v in want.items())


def gaps(program: Dict, ref: Dict) -> Dict[str, float]:
    """Relative gaps of a first step to the reference's: its loss, its
    global gradient norm, and by the worst leaf its first moment and its
    parameters' change.  A step that leaves the state as it was reads 1 in
    both of the last."""
    return {"loss_gap_rel": _rel(program["loss"], ref["loss"]),
            "grad_norm_gap_rel": _rel(program["grad_norm"], ref["grad_norm"]),
            "moment_gap_rel": worst_leaf(program["moment_norms"],
                                         ref["moment_norms"]),
            "update_gap_rel": worst_leaf(program["update_norms"],
                                         ref["update_norms"])}


def checks_for(c: Dict, got: Dict[str, float], nonfinite: int) -> Dict:
    limits = c["limits"]
    out = {k: {"value": v, "limit": float(limits[k])} for k, v in got.items()}
    out["nonfinite_steps"] = {"value": nonfinite,
                              "limit": int(limits["nonfinite_steps"])}
    return out


def passes(checks: Dict) -> bool:
    """Whether every number compared is at or under its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def reference_step(c: Dict, job: Dict, seed: int, batch: Dict,
                   precision: str, devices) -> Dict:
    return reference.loss_and_grad_norms(
        c, seed, batch["tokens"], batch["labels"], precision, devices,
        optim=optimizer_of(job))


def judge(c: Dict, job: Dict, seed: int, first: Dict, batch: Dict,
          nonfinite: int, devices, controls=(), ref: Optional[Dict] = None
          ) -> Dict:
    """The check of the first step (``first``: its metrics and
    :func:`first_step_change`'s norms) against the float32 reference, for
    the program (``"program"``) and for each control in ``controls`` (the
    reference at a lower precision put in the program's place).  The
    reference's own step is under ``"reference"``."""
    t = time.monotonic()
    if ref is None:
        ref = reference_step(c, job, seed, batch, "f32", devices)
    log(f"reference: loss {ref['loss']!r}, grad norm {ref['grad_norm']!r}; "
        f"program's first step: loss {first['loss']!r}, grad norm "
        f"{first['grad_norm']!r}; {time.monotonic() - t:.1f}s")
    got = gaps(first, ref)
    log("worst leaves: moment " + max(
        ref["moment_norms"], key=lambda k: _rel(
            first["moment_norms"].get(k, 0.0), ref["moment_norms"][k]))
        + ", change " + max(ref["update_norms"], key=lambda k: _rel(
            first["update_norms"].get(k, 0.0), ref["update_norms"][k])))
    checks = checks_for(c, got, nonfinite)
    out = {"program": {"checks": checks, "correct": passes(checks)},
           "reference": ref}
    for prec in controls:
        t = time.monotonic()
        ctl = reference_step(c, job, seed, batch, prec, devices)
        bad = 0 if finite(ctl) else 1
        checks = checks_for(c, gaps(ctl, ref), bad)
        out[prec] = {"checks": checks, "correct": passes(checks),
                     "loss": ctl["loss"], "grad_norm": ctl["grad_norm"]}
        log(f"control {prec}: loss {ctl['loss']!r}, grad norm "
            f"{ctl['grad_norm']!r}; {time.monotonic() - t:.1f}s")
    return out


def run(ctx: Context, controls=()) -> Dict:
    """One run: set-up, the window, then the check.  ``controls`` also
    judges those lower precisions of the reference in the program's place
    (``record["controls"]``); the benchmark's own runs judge none."""
    c, job = ctx.config, ctx.mix
    trainer = build(ctx)
    log(f"trainer: {c['num_hidden_layers']} layers, hidden "
        f"{c['hidden_size']}, vocab {c['vocab_size']}, {c['torch_dtype']}; "
        f"mesh {job['mesh']}, global batch {job['global_batch']} x "
        f"{job['seq_len']}; built at {time.monotonic() - ctx.t0:.1f}s")
    change = None
    for _ in range(int(job["steps_before_window"])):
        m = trainer.step()
        log(f"warm-up step {m['step']}: loss {m['loss']!r}, grad norm "
            f"{m['grad_norm']!r}, {m['seconds']:.3f}s")
        if change is None:
            change = first_step_change(trainer.state, ctx.seed)
    rec = window(trainer, ctx)
    rec["memory_peak_bytes"] = memory_peak(ctx.devices)
    # a compiled program's scratch is ``bytes_reserved``, not in the peak
    log("device memory after the window: " + "; ".join(
        f"{d.id}: {d.memory_stats()}" for d in ctx.devices))
    first = dict(trainer.metrics_log[0], **change)
    nonfinite = sum(1 for m in trainer.metrics_log if not finite(m))
    batch = trainer.data.batch_at(0)
    del trainer
    gc.collect()

    verdicts = judge(c, job, ctx.seed, first, batch, nonfinite, ctx.devices,
                     controls)
    rec["checks"] = verdicts["program"]["checks"]
    rec["correct"] = verdicts["program"]["correct"]
    rec["controls"] = {p: verdicts[p] for p in controls}
    rec["reference"] = verdicts["reference"]
    rec["first_step"] = first
    rec["attempted"] = len(rec["steps"])
    rec["failed"] = sum(1 for s in rec["steps"] if not s[3])
    return rec
