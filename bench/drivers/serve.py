"""Serving driver: the paged ``ServeEngine`` under an open or a closed
traffic loop, then the check against the plain reference.

Set-up makes the weights from the seed on the device, builds the engine
with the configuration file's ``engine`` geometry, and warms up on short
requests (and, where the mix repeats prompts, a repeated prompt so that
the copy-on-write page copy runs).  The window then drives
``engine.submit`` and ``engine.step`` from one thread.  Every output token
is stamped when the ``step()`` that produced it returns, which is when a
streaming server could send it; the engine's own time stamps are not used.

After the window: the peak device memory is read, the engine is freed,
and a sample of the requests finished in the window, drawn from the seed
with the longest among them, is checked token by token against
``bench.reference.dense``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import counts, gen
from bench import weights as W
from bench.drivers.common import (CompileCounter, Context, GcClock,
                                  HostClock, Tracer, log, memory_peak,
                                  program_config, span)
from bench.reference import dense
from repro.serve.pool import PoolError

CHECK_MIN_TOKENS = 320      # served tokens the check covers at least
CHECK_MAX_REQUESTS = 12     # sequences the reference runs side by side
HEAD_ROWS = 256             # reference output-head rows per call
TRACE_FROM = 0.88          # the window's last 12% is traced


@dataclasses.dataclass
class Rec:
    """What the benchmark saw of one request (times relative to the
    window's start)."""
    spec: gen.RequestSpec
    due: float
    submit: Optional[float] = None
    admitted: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    output: List[int] = dataclasses.field(default_factory=list)
    failed: str = ""


class Probe:
    """Reads the engine's progress from outside after each ``step()``:
    per request, the tokens delivered and the positions its lane
    processed.  Uses the engine's live slot list (``_active``, each slot's
    ``request``, ``pos``, ``write_start``, ``num_generated``) and its
    ``finished`` list; nothing else of the engine's inner state."""

    def __init__(self, engine):
        self.engine = engine
        self.n_finished = len(engine.finished)
        self.pos: Dict[int, int] = {}

    def observe(self, recs: Dict[int, Rec], now: float):
        """Stamp new tokens at ``now``; returns the per-pass lanes of the
        step just run as a list of [(live context, logits used), ...]."""
        spans = []   # (request id, first position, end position, prompt len)
        for s in self.engine._active:
            rid = s.request.id
            start = self.pos.get(rid, max(0, s.write_start - 1))
            spans.append((rid, start, s.pos, len(s.request.prompt)))
            self.pos[rid] = s.pos
            self._stamp(recs.get(rid), s.num_generated, now)
        for req in self.engine.finished[self.n_finished:]:
            rid = req.id
            end = len(req.prompt) + len(req.output) - 1
            start = self.pos.pop(rid, 0)   # 0: admitted and done in one step
            spans.append((rid, start, end, len(req.prompt)))
            rec = recs.get(rid)
            self._stamp(rec, len(req.output), now)
            if rec is not None:
                rec.finished = now
                rec.output = list(req.output)
        self.n_finished = len(self.engine.finished)
        for rid, *_ in spans:
            rec = recs.get(rid)
            if rec is not None and rec.admitted is None:
                rec.admitted = now
        n_pass = max((e - s for _, s, e, _ in spans), default=0)
        return [[(s + k + 1, s + k >= p - 1) for _, s, e, p in spans
                 if s + k < e] for k in range(n_pass)]

    @staticmethod
    def _stamp(rec: Optional[Rec], n: int, now: float) -> None:
        if rec is not None and n > len(rec.stamps):
            rec.stamps.extend([now] * (n - len(rec.stamps)))


def build(ctx: Context):
    """The engine, with the seed's weights on the device."""
    from repro.models import lm
    from repro.serve import ServeEngine, Tenant

    c = ctx.config
    pcfg = program_config(c)
    shapes = jax.eval_shape(functools.partial(lm.init_model, pcfg),
                            jax.random.PRNGKey(0))
    params = W.make_params(ctx.seed, shapes)
    jax.block_until_ready(params)
    log(f"weights made at {time.monotonic() - ctx.t0:.1f}s")
    geo = c["engine"]
    engine = ServeEngine.from_config(
        pcfg, params=params, max_batch=geo["max_batch"],
        max_seq=geo["max_seq"], block_size=geo["block_size"],
        kv_blocks=geo["kv_blocks"], prefill_chunk=geo["prefill_chunk"],
        tenants=[Tenant(t["name"]) for t in ctx.mix["tenants"]], paged=True)
    jax.block_until_ready((engine.params, engine.caches))
    return engine


def warm_up(engine, ctx: Context) -> None:
    """Run every shape the window's traffic uses, on short requests: the
    step and the slot reset with all lanes busy, and, for a mix that
    repeats prompts, the copy-on-write copy of one and of two pages in a
    pass (a prompt of one block and one token, asked three times)."""
    geo = ctx.config["engine"]
    rng = gen.rng_for(ctx.seed, "warmup")
    tenants = [t["name"] for t in ctx.mix["tenants"]]
    prompts = [rng.integers(0, ctx.config["vocab_size"],
                            geo["block_size"] + 1).tolist()
               for _ in range(geo["max_batch"])]
    for i, p in enumerate(prompts):
        engine.submit(p, 2, tenant=tenants[i % len(tenants)])
    engine.run()
    if ctx.mix.get("repeats_per_block"):
        first = engine.submit(prompts[0], 4, tenant=tenants[0])
        while first.first_token_time is None:
            engine.step()
        for i in (1, 2):
            engine.submit(prompts[0], 4, tenant=tenants[i % len(tenants)])
        engine.run()
        log(f"warm-up copy-on-write forks: {engine.counters['cow_forks']}")


def _snapshot(engine) -> Dict:
    return {"engine": dict(engine.counters), "pool": engine.pool.stats()}


def window(engine, ctx: Context, dm: counts.DenseDims) -> Dict:
    """The measured window; returns the run's record (no result judged).

    A mix starts ``lead_in_s`` seconds before the window (a closed mix its
    backlog, an open mix its arrivals), so that the window opens on lanes
    at every stage of their requests and on the pages of live requests,
    as the traffic leaves them, rather than on an empty engine.  Times in
    the record are seconds from the window's start; a request of the
    lead-in has a due time below 0."""
    mix = ctx.mix
    W_s = float(ctx.seconds)
    lead_in = float(mix.get("lead_in_s", 0.0))
    n = gen.requests_for_window(mix, lead_in + W_s)
    specs = gen.generate(mix, ctx.seed, n, ctx.config["vocab_size"])
    closed = mix["kind"] == "closed"
    backlog = int(mix.get("backlog", 0))
    host = HostClock()
    tracer = Tracer(ctx.trace, TRACE_FROM * W_s)
    probe = Probe(engine)
    counter = CompileCounter()
    gc_clock = GcClock()

    recs: Dict[int, Rec] = {}
    order: List[Rec] = []
    steps = []     # (start, end, passes run, passes seen, least seconds)
    snaps: Dict[str, Dict] = {}
    binds = {"flops": 0, "bytes": 0}
    nxt = 0

    def submit(spec: gen.RequestSpec, due: float, now: float) -> None:
        rec = Rec(spec=spec, due=due, submit=now)
        order.append(rec)
        try:
            req = engine.submit(spec.prompt, spec.max_new_tokens,
                                tenant=spec.tenant)
        except (ValueError, PoolError) as e:
            rec.failed = repr(e)
            return
        recs[req.id] = rec

    t_start = time.monotonic() + lead_in
    now = -lead_in
    while now < W_s:
        if now >= 0 and "start" not in snaps:
            snaps["start"] = _snapshot(engine)
            counter.active = gc_clock.active = True
            host.start()
            setup_s = time.monotonic() - ctx.t0
        was = tracer.state
        tracer.poll(now)
        if tracer.state != was:
            snaps["trace_" + tracer.state] = _snapshot(engine)
        with span("submit"):
            if closed:
                while nxt < len(specs) and (
                        engine.scheduler.pending() < backlog):
                    submit(specs[nxt], now, now)
                    nxt += 1
            else:
                while nxt < len(specs) and specs[nxt].due - lead_in <= now:
                    submit(specs[nxt], specs[nxt].due - lead_in, now)
                    nxt += 1
        if engine.active or engine.scheduler.pending():
            p0 = engine.counters["passes"]
            with span("engine_step"):
                engine.step()
            t1 = time.monotonic() - t_start
            with span("stamp"):
                lanes = probe.observe(recs, t1)
                least = 0.0
                for lane_set in lanes:
                    f, b = counts.decode_pass(dm, lane_set)
                    sec, which = counts.least_seconds(f, b, ctx.peaks)
                    least += sec
                    if now >= 0:
                        binds[which] += 1
            if now >= 0:
                steps.append((now, t1, engine.counters["passes"] - p0,
                              len(lanes), least))
        else:
            due = specs[nxt].due - lead_in if nxt < len(specs) else W_s
            with span("idle_wait"):
                time.sleep(max(0.0, min(due, W_s) - now))
        now = time.monotonic() - t_start
    counter.active = gc_clock.active = False
    host.stop()
    t_end = time.monotonic() - t_start
    snaps["end"] = _snapshot(engine)
    trace = tracer.reduce()

    due = [r for r in order if 0 <= r.due < W_s]
    c0, c1 = snaps["start"], snaps["end"]
    log(f"window {t_end:.3f}s: {len(due)} requests due, "
        f"{sum(1 for r in due if r.stamps)} first-served, "
        f"{sum(1 for r in due if r.finished is not None)} finished,"
        f" {len(steps)} steps, {c1['engine']['passes'] - c0['engine']['passes']}"
        f" passes; executables built in the window: {counter.count}")
    log(f"host in the window: {host}")
    passes_seen = sum(s[3] for s in steps)
    passes_done = sum(s[2] for s in steps)
    if passes_seen != passes_done:
        log(f"pass reconstruction saw {passes_seen} of {passes_done} passes")
    if steps:
        # host pauses: a step (submit and engine.step()) or a stretch between
        # steps far longer than the usual step
        took = sorted(s[1] - s[0] for s in steps)
        med = took[len(took) // 2]
        between = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
        slow = [round(s[0], 1) for s in steps if s[1] - s[0] > 2 * med]
        log(f"step time: median {med * 1e3:.1f} ms, longest "
            f"{took[-1] * 1e3:.1f} ms, {len(slow)} over twice the median (at "
            f"{slow[:8]} s); longest stretch between steps "
            f"{max(between, default=0.0) * 1e3:.1f} ms; garbage collection "
            f"{gc_clock.count} times ({gc_clock.full} full), "
            f"{gc_clock.seconds * 1e3:.1f} ms, longest "
            f"{gc_clock.longest * 1e3:.1f} ms")
        if "trace_on" in snaps:
            log(f"host time per pass: {_per_pass(steps, 0, tracer.start)} "
                f"ms before the traced stretch, "
                f"{_per_pass(steps, tracer.start, t_end)} ms in it")
    log(f"roofline terms: least time {sum(s[4] for s in steps):.4f}s over "
        f"the window's passes, bound by bytes in {binds['bytes']} and by "
        f"operations in {binds['flops']} passes")
    return {
        "kind": "serve",
        "setup_s": setup_s,
        "window_s": W_s,
        "traced": [tracer.start, t_end] if "trace_on" in snaps else None,
        "requests": [{"due": r.due, "submit": r.submit,
                      "admitted": r.admitted, "stamps": r.stamps,
                      "finished": r.finished, "failed": r.failed,
                      "prompt_len": len(r.spec.prompt)}
                     for r in order],
        "steps": steps,
        "counters0": c0,
        "counters1": c1,
        "counters_traced": ([snaps["trace_on"], snaps["end"]]
                            if "trace_on" in snaps else None),
        "compiles_in_window": counter.count,
        "trace": trace,
        "_finished": [(r.spec, r.output) for r in order
                      if r.finished is not None and 0 <= r.finished <= W_s],
    }


def _per_pass(steps, lo: float, hi: float) -> Optional[float]:
    """Host milliseconds per pass of the steps between ``lo`` and ``hi``."""
    inside = [s for s in steps if lo <= s[0] and s[1] <= hi]
    passes = sum(s[2] for s in inside)
    if not passes:
        return None
    return round(sum(s[1] - s[0] for s in inside) / passes * 1e3, 2)


def check_sample(finished, seed: int) -> List:
    """The requests the check covers: the longest finished one, one that
    repeats an earlier prompt where there is one, then others drawn from
    the seed until ``CHECK_MIN_TOKENS`` served tokens."""
    if not finished:
        return []
    rng = gen.rng_for(seed, "check")
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0].prompt) + len(finished[i][1]))
    picked = [longest]
    repeats = [i for i, (s, _) in enumerate(finished)
               if s.repeat_of >= 0 and i != longest]
    if repeats:
        picked.append(repeats[int(rng.integers(0, len(repeats)))])
    for i in rng.permutation(len(finished)):
        if len(picked) >= CHECK_MAX_REQUESTS or sum(
                len(finished[j][1]) for j in picked) >= CHECK_MIN_TOKENS:
            break
        if int(i) not in picked:
            picked.append(int(i))
    return [finished[i] for i in picked]


def reference_gaps(cfg: Dict, seed: int, sample, max_seq: int,
                   precisions=("f32",)):
    """Per precision, the gaps of the sample's served tokens (f32) or of
    the control's own first choices (any other precision), in standard
    deviations of the float32 reference's row."""
    seqs = [list(s.prompt) + list(out) for s, out in sample]
    rows = [list(range(len(s.prompt) - 1, len(s.prompt) - 1 + len(out)))
            for s, out in sample]
    served = np.concatenate([np.asarray(out, np.int64) for _, out in sample])
    logits = dense.served_logits(cfg, seed, seqs, rows, precisions,
                                 n_seqs=CHECK_MAX_REQUESTS, length=max_seq,
                                 head_rows=HEAD_ROWS)
    ref = logits[0]
    out = {"f32": dense.logit_gaps(ref, served)}
    for prec, lg in zip(precisions[1:], logits[1:]):
        out[prec] = dense.logit_gaps(ref, lg.argmax(axis=1))
    return out, len(sample), len(served)


def passes(checks: Dict) -> bool:
    """Whether every number compared is at or under its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def judge(c: Dict, seed: int, finished, controls=()) -> Dict:
    """The check of a run's finished requests: the sample through the
    float32 reference.  For the program (``"program"``) and for each
    control in ``controls`` (the reference at a lower precision put in the
    program's place, its own first choices at the sample's positions): the
    numbers compared with their limits, and whether all pass."""
    sample = check_sample(finished, seed)
    limit = float(c["limits"]["worst_logit_gap_sigma"])
    truncated = sum(1 for s, out in sample if len(out) != s.max_new_tokens)
    t = time.monotonic()
    gaps, n_req, n_tok = {}, 0, 0
    if sample:
        gaps, n_req, n_tok = reference_gaps(c, seed, sample,
                                            c["engine"]["max_seq"],
                                            ("f32",) + tuple(controls))
    log(f"reference check: {n_req} requests, {n_tok} served tokens, "
        f"{truncated} cut short; {time.monotonic() - t:.1f}s")
    out = {}
    for name, prec in [("program", "f32")] + [(p, p) for p in controls]:
        worst = float(gaps[prec].max()) if prec in gaps else None
        checks = {"worst_logit_gap_sigma": {"value": worst, "limit": limit},
                  "short_outputs": {"value": truncated, "limit": 0}}
        out[name] = {"checks": checks, "correct": passes(checks)}
    return out


def run(ctx: Context, controls=()) -> Dict:
    """One run: set-up, the window, then the check.  ``controls`` also
    judges those lower precisions of the reference in the program's place
    (``record["controls"]``); the benchmark's own runs judge none."""
    c = ctx.config
    dm = counts.DenseDims.from_config(c)
    engine = build(ctx)
    log(f"engine: {c['num_hidden_layers']} layers, hidden {c['hidden_size']},"
        f" vocab {c['vocab_size']}, {c['torch_dtype']}; geometry "
        f"{c['engine']}; built at {time.monotonic() - ctx.t0:.1f}s")
    warm_up(engine, ctx)
    log(f"warmed up at {time.monotonic() - ctx.t0:.1f}s")
    rec = window(engine, ctx, dm)
    rec["memory_peak_bytes"] = memory_peak(ctx.devices)
    finished = rec.pop("_finished")
    del engine
    gc.collect()

    verdicts = judge(c, ctx.seed, finished, controls)
    rec["checks"] = verdicts["program"]["checks"]
    rec["correct"] = verdicts["program"]["correct"]
    rec["controls"] = {p: verdicts[p] for p in controls}
    W = rec["window_s"]
    due = [r for r in rec["requests"] if 0 <= r["due"] < W]
    rec["attempted"] = len(due)
    rec["failed"] = sum(1 for r in due if r["failed"])
    return rec
