"""Serve engine: continuous batching over a paged KV cache.

:class:`ServeEngine` is the serving path.  Its KV cache is block-major
pages (:func:`~repro.serve.decode.init_paged_caches`) that a
:class:`~repro.serve.pool.KVBlockPool` hands out to requests through
block tables, with prefix sharing and copy-on-write for attention
families; recurrent state keeps ``max_batch`` per-slot rows plus one
scratch row.  A :class:`~repro.serve.scheduler.FairScheduler` decides
which tenant's request gets the next free slot.  Every engine step:

1. **evict** — finished slots release their pool blocks and stamp
   latency/TTFT on their :class:`~repro.serve.scheduler.Request`;
2. **admit** — the fair scheduler fills freed slots (chunked prefill:
   new prompts are teacher-forced through the same decode step, so
   admission needs no separate prefill kernel and a long prompt never
   blocks the running decodes; a prompt whose prefix pages are resident
   maps them and skips their prefill);
3. **advance** — every live slot that the pool can grow moves one
   position through ONE fixed-shape jitted decode pass (lanes reach
   their pages through the block table at per-row positions; idle
   lanes pad onto the scratch row, so the step compiles exactly once);
4. **preempt** — if nothing could advance (pool exhausted), the
   youngest request's pages are copied to the host and its blocks freed;
   it goes back to the head of its tenant queue and later resumes from
   the same position by re-uploading them.

Each stage runs inside a host span ``repro.serve.<stage>`` (:func:`span`;
``step`` holds ``evict``, ``admit``, ``pass`` and ``preempt``; ``pass``
holds ``pool``, ``cow_copy``, ``assemble``, ``dispatch``, ``sync`` and
``apply``; ``layout`` runs once, at construction), which a profiler trace
puts on the device planes' clock.  The stage clocks in ``counters``
(``queue_wait_s``, ``prefill_s``, ``sync_s``, ``host_s``, ``layout_s``) run
whether or not a trace does.

Kernel schedules come from the cache index via
:func:`repro.sched.lowering.schedule_plan` (re-exported here) —
nearest-bucket pure lookups at construction time, **zero**
autotune/``Machine.run`` on the serve path.

The module-level :func:`generate` stays as the one-shot, jit-able
static-batch convenience wrapper (one ``lax.scan`` over the same
``decode_step``); ``ServeEngine.generate`` is its engine-backed
equivalent.  Under greedy decoding the two are bit-exact per request
for batch-independent (non-MoE-capacity) configs — see
``tests/test_serve_paged.py``.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist import sharding as shd
from repro.sched.cache import ScheduleCache
from repro.sched.lowering import schedule_plan  # noqa: F401  (serve-facing API)
from repro.serve.batching import SlotState, assemble_paged
from repro.serve.decode import (Pages, decode_step, init_caches,
                                init_paged_caches, paged_cache_kinds,
                                serving_params)
from repro.serve.pool import KVBlockPool, PoolCapacityError, PoolError  # noqa: F401
from repro.serve.scheduler import (DEFAULT_TENANT, FairScheduler, Request,
                                   Tenant)

# One compiled (step, reset) pair per (config, mesh, max_seq):
# engines in a sweep share tracing/compilation instead of re-jitting per
# instance.
_STEP_FNS: Dict = {}


def span(name: str):
    """A host span ``repro.serve.<name>`` in the profiler's trace, on the
    device planes' clock; costs about a microsecond when no trace runs."""
    return jax.profiler.TraceAnnotation("repro.serve." + name)


def _cfg_key(cfg: ModelConfig) -> str:
    return json.dumps(dataclasses.asdict(cfg), sort_keys=True, default=str)


def _paged_step_fns(cfg: ModelConfig, mesh, max_seq: int):
    """(step, reset) for the engine's caches.  ``"paged"`` cache entries
    pass through whole (lanes address them via the block table);
    ``"slot"`` entries (recurrent state) are gathered by slot and
    scattered back, with idle lanes (gathered from the scratch row)
    routed to an out-of-range row and dropped — one masked scatter, no
    duplicate scratch-row writes to race under the donated buffers.

    ``step`` donates the caches (argument 1): the pages are written in
    place, and the caller must rebind its cache list to the returned one,
    as :meth:`ServeEngine._pass` does."""
    key = (_cfg_key(cfg), None if mesh is None else id(mesh), int(max_seq))
    if key not in _STEP_FNS:
        kinds = paged_cache_kinds(cfg)

        def step(params, caches, idx, table, tok, pos, wstart):
            write_mask = pos >= wstart
            rows = [jax.tree.map(lambda a: a[idx], c) if kind == "slot"
                    else c for c, kind in zip(caches, kinds)]
            logits, new = decode_step(params, rows, tok[:, None], pos, cfg,
                                      pages=Pages(table, write_mask, max_seq),
                                      mesh=mesh)
            out = []
            for c, n, kind in zip(caches, new, kinds):
                if kind == "slot":
                    scratch = jax.tree.leaves(c)[0].shape[0] - 1
                    sidx = jnp.where(idx == scratch, scratch + 1, idx)
                    out.append(jax.tree.map(
                        lambda a, r: a.at[sidx].set(r.astype(a.dtype),
                                                    mode="drop"), c, n))
                else:
                    out.append(n)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), out

        slots = [i for i, kind in enumerate(kinds) if kind == "slot"]
        zero_rows = jax.jit(
            lambda rows, idx: jax.tree.map(lambda a: a.at[idx].set(0), rows),
            donate_argnums=(0,))

        def reset(caches, idx):
            # Only recurrent slot rows need zeroing on admission (a
            # previous occupant's SSM/conv state would otherwise leak
            # into the new request); page contents are never read
            # unmasked before being written, so the pages stay outside
            # the jit: an identity output would copy the whole pool.
            if not slots:
                return caches
            rows = iter(zero_rows([caches[i] for i in slots], idx))
            return [next(rows) if kind == "slot" else c
                    for c, kind in zip(caches, kinds)]

        _STEP_FNS[key] = (jax.jit(step, donate_argnums=(1,)), reset)
    return _STEP_FNS[key]


@dataclasses.dataclass
class _Spill:
    """Host-side copy of a preempted request's KV pages (+ recurrent slot
    rows) — the payload of copy-free preemption.  Travels on
    ``Request.spill`` through the scheduler queue; re-admission allocates
    ``n_blocks`` fresh blocks and uploads ``data`` into them, so decoding
    resumes at ``pos`` bit-exactly with zero recompute."""
    tokens: List[int]
    pos: int
    prompt_len: int
    target_len: int
    n_blocks: int
    data: List


class ServeEngine:
    """Continuous-batching multi-tenant serving over ``decode_step``.

    Construct through :meth:`from_config` — the single supported path::

        engine = ServeEngine.from_config(cfg, schedule_cache=cache,
                                         max_batch=8, max_seq=256)
        req = engine.submit(prompt_tokens, max_new_tokens=64, tenant="a")
        engine.run()            # or engine.step() per tick under a loadgen
        req.output              # generated tokens; req.ttft / req.latency

    ``admission="gang"`` degrades the engine to static batching (admit
    only into an idle engine, wait for the whole gang to finish) — the
    baseline ``bench_serve.py`` compares continuous batching against.

    Preemption spills the victim's pages to the host and resumes it by
    re-uploading them (:class:`_Spill`), so no position runs twice.
    Prefix sharing is on exactly for the attention families (``dense``,
    ``moe``), whose page contents are a pure function of the token
    prefix; recurrent state is per request.  ``paged`` accepts only
    True: the dense per-slot mode was removed.

    The engine takes ownership of ``params``: it lays them out once in the
    serving form (:func:`~repro.serve.decode.serving_params`, after placing
    them on ``mesh`` if one is given), deleting each stacked layer array as
    its per-layer arrays are made.  A caller that uses its weights after
    building an engine passes a copy.
    """

    def __init__(self, cfg: ModelConfig, *, params: Optional[Dict] = None,
                 max_batch: int = 8, max_seq: int = 128,
                 block_size: int = 16, kv_blocks: Optional[int] = None,
                 tenants: Optional[Sequence[Tenant]] = None,
                 starvation_bound: int = 8, prefill_chunk: int = 4,
                 admission: str = "continuous",
                 paged: bool = True,
                 debug_invariants: bool = False,
                 schedule_cache: Optional[Union[ScheduleCache, str]] = None,
                 on_missing: str = "baseline",
                 mesh=None, rng_seed: int = 0):
        if cfg.family == "encdec":
            raise ValueError("ServeEngine serves decoder-only families; "
                             "use examples/serve_decode.py for enc-dec")
        if admission not in ("continuous", "gang"):
            raise ValueError(f"admission must be 'continuous' or 'gang', "
                             f"got {admission!r}")
        if max_batch < 1 or prefill_chunk < 0:
            raise ValueError("need max_batch >= 1 and prefill_chunk >= 0")
        if not paged:
            raise ValueError("the dense per-slot engine mode was removed: "
                             "ServeEngine always serves from paged KV "
                             "caches (drop paged=False)")
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq)
        self.scratch_slot = self.max_batch          # extra padded cache row
        self.admission = admission
        self.prefill_chunk = int(prefill_chunk)
        self.mesh = mesh
        self.debug_invariants = bool(debug_invariants)
        # Prefix sharing needs the cache content at a position to be a pure
        # function of the token prefix — true for attention/MLA pages,
        # false for recurrent state (ssm/hybrid carry per-request rows).
        self._shares_prefix = cfg.family in ("dense", "moe")

        self.pool = KVBlockPool(self.max_batch, self.max_seq,
                                block_size=block_size, num_blocks=kv_blocks)
        self.scheduler = FairScheduler(tenants,
                                       starvation_bound=starvation_bound)

        if params is None:
            from repro.models import lm
            params = lm.init_model(cfg, jax.random.PRNGKey(rng_seed))
        if mesh is not None:
            from repro.models import lm
            params = jax.device_put(
                params, shd.param_shardings(lm.model_spec(cfg), mesh))
        t = time.perf_counter()
        with span("layout"):
            self.params = serving_params(params, consume=True)
        layout_s = time.perf_counter() - t
        self._kinds = paged_cache_kinds(cfg)
        self.caches = init_paged_caches(cfg, self.pool.num_blocks,
                                        self.pool.block_size, self.max_batch)
        if mesh is not None:
            self.caches = jax.device_put(
                self.caches, shd.kv_pool_shardings(cfg, self.caches, mesh,
                                                   self._kinds))
        self._step_fn, self._reset_fn = _paged_step_fns(cfg, mesh,
                                                        self.max_seq)

        if isinstance(schedule_cache, str):
            schedule_cache = ScheduleCache(schedule_cache)
        self.schedule_cache = schedule_cache
        if schedule_cache is not None:
            # Lazy import: launch.specs imports repro.serve at module load.
            from repro.launch.specs import kernel_fleet
            # on_missing="baseline" (default): kernels with missing/corrupt
            # cached schedules degrade to the -O3 baseline (None plan
            # entries, counted below); "raise" refuses to start degraded
            self.plan = schedule_plan(kernel_fleet(cfg), cache=schedule_cache,
                                      on_missing=on_missing)
        else:
            self.plan = {}

        self._active: List[SlotState] = []
        self.finished: List[Request] = []
        # Stage clocks (seconds): ``queue_wait_s`` sums submit (or requeue)
        # to admission over ``admissions``; ``prefill_s`` sums admission to
        # first token over ``first_tokens``; ``sync_s`` is the host blocked
        # on the device for a pass's tokens, ``host_s`` the rest of step();
        # ``layout_s`` is the weights' layout at construction.
        self.counters = {"passes": 0, "lane_tokens": 0,
                         "admissions": 0, "stalls": 0, "preemptions": 0,
                         "max_active": 0,
                         "prefix_hits": 0, "cow_forks": 0,
                         "preempt_spills": 0, "resume_uploads": 0,
                         "schedule_fallbacks": sum(
                             1 for art in self.plan.values() if art is None),
                         "queue_wait_s": 0.0, "prefill_s": 0.0,
                         "first_tokens": 0, "sync_s": 0.0, "host_s": 0.0,
                         "layout_s": layout_s}

    @classmethod
    def from_config(cls, cfg: ModelConfig, **kwargs) -> "ServeEngine":
        """The one constructor path (see class docstring for the knobs)."""
        return cls(cfg, **kwargs)

    # -- request intake ------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               tenant: str = DEFAULT_TENANT) -> Request:
        """Queue a request.  Raises :class:`PoolCapacityError` immediately
        when the prompt can never be served (``len(prompt) >= max_seq``
        leaves no cache position for even one generated token — the old
        silent out-of-range cache write, now a typed admission error)."""
        prompt = [int(t) for t in prompt]
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not self.pool.fits(len(prompt)):
            raise PoolCapacityError(
                f"prompt of {len(prompt)} tokens can never be admitted: "
                f"max_seq={self.max_seq} needs len(prompt) < max_seq so the "
                f"first generated token has a cache position")
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      tenant=tenant)
        budget = next((t.token_budget for t in self.scheduler.tenants
                       if t.name == tenant), None)
        if budget is not None and req.cost > budget:
            raise ValueError(
                f"request cost {req.cost} exceeds tenant {tenant!r} token "
                f"budget {budget}; it could never be admitted")
        return self.scheduler.submit(req)

    # -- the serve loop ------------------------------------------------------

    def step(self) -> int:
        """One engine tick: evict, admit, advance, preempt-on-stall.
        Returns the number of slot advances made.

        Prefill is folded into the decode passes (chunked admission): a
        tick runs one full-width pass, plus up to ``prefill_chunk`` more
        while any slot is still teacher-forcing its prompt — every pass
        advances *all* eligible slots, so prompt catch-up never drops
        lane occupancy and never stalls the running decodes."""
        t0 = time.perf_counter()
        sync0 = self.counters["sync_s"]
        with span("step"):
            with span("evict"):
                self._evict()
            with span("admit"):
                self._admit()
            self.counters["max_active"] = max(self.counters["max_active"],
                                              len(self._active))
            for s in self._active:
                s.stalled = False
            advanced = 0
            for _ in range(1 + self.prefill_chunk):
                with span("pass"):
                    n = self._pass()
                advanced += n
                if n == 0 or not any(s.in_prefill and not s.done
                                     and not s.stalled for s in self._active):
                    break
            with span("evict"):
                self._evict()
            if advanced == 0 and self._active:
                with span("preempt"):
                    self._preempt_youngest()
            if self.debug_invariants:
                self.pool.check()
        self.counters["host_s"] += (time.perf_counter() - t0
                                    - (self.counters["sync_s"] - sync0))
        return advanced

    def run(self, max_steps: int = 1_000_000) -> List[Request]:
        """Drain every queued/active request; returns finished requests
        in completion order."""
        while self._active or self.scheduler.pending():
            if max_steps <= 0:
                raise RuntimeError(
                    f"serve loop did not drain: {len(self._active)} active, "
                    f"{self.scheduler.pending()} pending")
            self.step()
            max_steps -= 1
        return list(self.finished)

    def generate(self, prompt: Sequence[int], max_new_tokens: int,
                 tenant: str = DEFAULT_TENANT) -> List[int]:
        """One-shot convenience over the engine: submit, drain, return
        ``prompt + generated`` (the engine-side equivalent of the
        module-level static-batch :func:`generate`)."""
        req = self.submit(prompt, max_new_tokens, tenant)
        self.run()
        return list(req.prompt) + list(req.output)

    # -- internals -----------------------------------------------------------

    def _admissible(self, req: Request) -> bool:
        if req.spill is not None:
            # Re-granting just the spilled pages is not enough: the request
            # must also be able to grow into its next write position, or a
            # resume under pressure re-creates the stall that spilled it
            # (resume → everyone blocked → preempt youngest → resume …).
            return self.pool.can_resume(
                self.pool.blocks_for(req.spill.pos + 1))
        if self._shares_prefix:
            return self.pool.can_admit_shared(req.prompt)
        return self.pool.can_admit(len(req.prompt))

    def _admit(self) -> None:
        if self.admission == "gang" and self._active:
            return           # static batching: wait for the gang to finish
        fresh: List[int] = []
        resumed = []
        while len(self._active) < self.max_batch:
            req = self.scheduler.admit_next(predicate=self._admissible)
            if req is None:
                break
            if req.spill is not None:
                table = self.pool.alloc_resume(req.id, req.spill.n_blocks)
                self._active.append(SlotState.resume(
                    table.slot, req, tokens=req.spill.tokens,
                    pos=req.spill.pos, prompt_len=req.spill.prompt_len,
                    target_len=req.spill.target_len))
                resumed.append((table, req.spill))
                req.spill = None
            elif self._shares_prefix:
                table = self.pool.alloc_shared(req.id, req.prompt)
                if table.shared_tokens:
                    self.counters["prefix_hits"] += 1
                self._active.append(SlotState.admit(
                    table.slot, req, shared_tokens=table.shared_tokens))
            else:
                table = self.pool.alloc(req.id, len(req.prompt))
                self._active.append(SlotState.admit(table.slot, req))
            fresh.append(table.slot)
            req.admit_time = time.monotonic()
            queued = (req.submit_time if req.queued_time is None
                      else req.queued_time)
            self.counters["queue_wait_s"] += req.admit_time - queued
            self.counters["admissions"] += 1
        if fresh:
            idx = np.full((self.max_batch,), self.scratch_slot, np.int32)
            idx[:len(fresh)] = fresh
            self.caches = self._reset_fn(self.caches, jnp.asarray(idx))
        for table, spill in resumed:
            self._upload_spill(table, spill)
            self.counters["resume_uploads"] += 1

    def _pass(self) -> int:
        cand: List[SlotState] = []
        forks: List = []
        with span("pool"):
            for s in self._active:
                if s.done or s.stalled:
                    continue
                write = s.pos >= s.write_start
                if self.pool.can_advance(s.request.id, s.pos, write=write):
                    pair = self.pool.advance(s.request.id, s.pos, write=write)
                    if pair is not None:
                        forks.append(pair)
                    cand.append(s)
                else:
                    s.stalled = True
                    self.counters["stalls"] += 1
        if forks:
            with span("cow_copy"):
                self._copy_blocks(forks)
        with span("assemble"):
            asm = assemble_paged(
                cand, self.max_batch, self.scratch_slot,
                self.pool.blocks_per_slot,
                lambda s: self.pool.table(s.request.id).blocks)
        if asm is None:
            return 0
        *lanes, stepped = asm     # idx, table, tok, pos, wstart
        with span("dispatch"):
            nxt, self.caches = self._step_fn(
                self.params, self.caches, *(jnp.asarray(a) for a in lanes))
        t = time.perf_counter()
        with span("sync"):
            nxt = np.asarray(nxt)
        self.counters["sync_s"] += time.perf_counter() - t
        with span("apply"):
            now = time.monotonic()
            for lane, s in enumerate(stepped):
                appended = s.apply(int(nxt[lane]), self.max_seq)
                if appended and s.request.first_token_time is None:
                    s.request.first_token_time = now
                    self.counters["prefill_s"] += now - s.request.admit_time
                    self.counters["first_tokens"] += 1
                if self._shares_prefix:
                    self.pool.commit(s.request.id, s.tokens, s.pos,
                                     prompt_len=s.prompt_len)
        self.counters["passes"] += 1
        self.counters["lane_tokens"] += len(stepped)
        return len(stepped)

    def _copy_blocks(self, forks: List) -> None:
        """Apply copy-on-write forks: device-copy each ``src`` page onto
        its ``dst`` before this pass writes into it."""
        src = jnp.asarray([a for a, _ in forks])
        dst = jnp.asarray([b for _, b in forks])
        self.caches = [
            jax.tree.map(lambda a: a.at[dst].set(a[src]), c)
            if kind == "paged" else c
            for c, kind in zip(self.caches, self._kinds)]
        self.counters["cow_forks"] += len(forks)

    def _spill(self, victim: SlotState) -> "_Spill":
        """Copy the victim's pages (and recurrent slot rows) to host
        memory so preemption frees its device blocks without losing the
        computed KV — resume is a remap + upload, not a recompute."""
        t = self.pool.table(victim.request.id)
        ids = jnp.asarray(t.blocks)
        data = []
        for c, kind in zip(self.caches, self._kinds):
            if kind == "paged":
                data.append(jax.tree.map(lambda a: np.asarray(a[ids]), c))
            else:
                data.append(jax.tree.map(
                    lambda a: np.asarray(a[victim.slot]), c))
        return _Spill(tokens=list(victim.tokens), pos=victim.pos,
                      prompt_len=victim.prompt_len,
                      target_len=victim.target_len,
                      n_blocks=t.num_blocks, data=data)

    def _upload_spill(self, table, spill: "_Spill") -> None:
        ids = jnp.asarray(table.blocks)
        self.caches = [
            jax.tree.map(lambda a, h: a.at[ids].set(jnp.asarray(h, a.dtype)),
                         c, d)
            if kind == "paged" else
            jax.tree.map(lambda a, h: a.at[table.slot].set(
                jnp.asarray(h, a.dtype)), c, d)
            for c, d, kind in zip(self.caches, spill.data, self._kinds)]

    def _evict(self) -> None:
        done = [s for s in self._active if s.done]
        if not done:
            return
        now = time.monotonic()
        for s in done:
            req = s.request
            req.output = list(s.generated)
            req.finish_time = now
            self.scheduler.release(req, served_tokens=s.num_generated)
            self.pool.free(req.id)
            self._active.remove(s)
            self.finished.append(req)

    def _preempt_youngest(self) -> None:
        victim = max(self._active,
                     key=lambda s: (s.request.submit_time, s.request.id))
        req = victim.request
        if self.pool.blocks_for(victim.pos + 1) > self.pool.num_blocks:
            # It could never advance even owning the whole pool: finish
            # it truncated rather than starve the queue.
            self._active.remove(victim)
            self.pool.free(req.id)
            generated = list(victim.generated)
            req.truncated = True
            req.output = generated
            req.finish_time = time.monotonic()
            self.scheduler.release(req, served_tokens=len(generated))
            self.finished.append(req)
            return
        # Copy-free preemption: spill the pages block-by-block, free the
        # device blocks, resume later by remap + upload — no teacher-forced
        # recompute of the prefill.
        req.spill = self._spill(victim)
        self._active.remove(victim)
        self.pool.free(req.id)
        req.preemptions += 1
        self.scheduler.release(req, served_tokens=0)
        self.scheduler.requeue_front(req)
        self.counters["preemptions"] += 1
        self.counters["preempt_spills"] += 1

    # -- reporting -----------------------------------------------------------

    @property
    def active(self) -> int:
        return len(self._active)

    def kv_bytes_allocated(self) -> int:
        """Device bytes backing the KV cache pytree: the pages scale with
        ``kv_blocks × block_size``, recurrent rows with ``max_batch + 1``."""
        return int(sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                       for leaf in jax.tree.leaves(self.caches)))

    def peak_kv_bytes(self) -> int:
        """High-water KV footprint actually addressed: the page bytes
        scaled by the pool's high-water block count, plus the recurrent
        slot rows."""
        paged_bytes = slot_bytes = 0
        for c, kind in zip(self.caches, self._kinds):
            n = sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                    for leaf in jax.tree.leaves(c))
            if kind == "paged":
                paged_bytes += n
            else:
                slot_bytes += n
        frac = self.pool.high_water_blocks / max(1, self.pool.num_blocks)
        return int(paged_bytes * frac + slot_bytes)

    def stats(self) -> Dict[str, object]:
        c = dict(self.counters)
        c["lane_utilization"] = (
            c["lane_tokens"] / (c["passes"] * self.max_batch)
            if c["passes"] else 0.0)
        c["kv_bytes_allocated"] = self.kv_bytes_allocated()
        c["peak_kv_bytes"] = self.peak_kv_bytes()
        return {"engine": c, "pool": self.pool.stats(),
                "tenants": self.scheduler.fairness_table()}

    def plan_summary(self) -> List[str]:
        """``kernel@bucket [target]: state`` lines for the resolved plan."""
        lines = []
        for key, art in sorted(self.plan.items(), key=str):
            name, bucket = key if isinstance(key, tuple) else (key, "default")
            label = name if bucket == "default" else f"{name}@{bucket}"
            if art is not None:
                target = art.target or "-"
                lines.append(f"{label} [{target}]: {art.speedup:.3f}x "
                             f"({art.optimized_cycles:.0f} cycles)")
            else:
                lines.append(f"{label}: not optimized (-O3 baseline)")
        return lines


def generate(params: Dict, cfg: ModelConfig, prompt: jax.Array,
             max_new_tokens: int, max_seq: Optional[int] = None,
             mesh=None) -> jax.Array:
    """One-shot static-batch convenience: (B, P) int32 prompt ->
    (B, P + max_new_tokens) greedy tokens in a single jit-able
    ``lax.scan`` over :func:`repro.serve.decode.decode_step`.

    This is the documented convenience wrapper for "run these B prompts
    to completion, nothing else going on" — benchmark cells and tests.
    Anything resembling a service (requests arriving over time, mixed
    lengths, tenants) belongs on :class:`ServeEngine`, which drives the
    *same* decode step per-row and matches this function token-for-token
    under greedy decoding (pass the engine's ``max_seq`` here so cache
    geometry — and hence float summation order — is identical).

    With ``mesh`` given, params and caches are placed by the dist-layer
    rules before the token loop, so the scanned decode step runs sharded
    (head-sharded KV for GQA, sequence-sharded for MQA/long-context).
    The weights are split per layer (:func:`serving_params`) before the
    loop too, into new arrays: the caller's stay as they were."""
    B, P = prompt.shape
    total = P + max_new_tokens
    max_seq = max_seq or total
    caches = init_caches(cfg, B, max_seq)
    if mesh is not None:
        from repro.models import encdec, lm
        model = encdec if cfg.family == "encdec" else lm
        params = jax.device_put(
            params, shd.param_shardings(model.model_spec(cfg), mesh))
        caches = jax.device_put(
            caches, shd.decode_cache_shardings(cfg, caches, mesh))
        prompt = jax.device_put(
            prompt, jax.sharding.NamedSharding(
                mesh, shd.batch_spec(mesh, B)))
    params = serving_params(params)
    tokens0 = jnp.concatenate(
        [prompt, jnp.zeros((B, max_new_tokens), jnp.int32)], axis=1)

    def body(carry, pos):
        tokens, caches = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, pos, 1, axis=1)
        logits, caches = decode_step(params, caches, tok, pos, cfg, mesh=mesh)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # teacher-force inside the prompt, write greedy tokens after it
        write_pos = pos + 1
        keep = write_pos < P
        cur = jax.lax.dynamic_slice_in_dim(tokens, jnp.minimum(write_pos,
                                                               total - 1),
                                           1, axis=1)[:, 0]
        val = jnp.where(keep, cur, nxt)
        tokens = jax.lax.dynamic_update_slice_in_dim(
            tokens, val[:, None], jnp.minimum(write_pos, total - 1), axis=1)
        return (tokens, caches), None

    (tokens, _), _ = jax.lax.scan(body, (tokens0, caches),
                                  jnp.arange(total - 1))
    return tokens
