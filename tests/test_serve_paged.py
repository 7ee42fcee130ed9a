"""Paged KV serving: physical block-pool pages behind per-request block
tables.  Covers bit-exactness of the engine vs the static ``generate()``
reference (dense/windowed/SSM/hybrid/MLA families), copy-free spill
preemption-resume, hash-based prefix sharing with copy-on-write and the
admission capacity it buys, the block-geometry edge cases (block_size=1,
max_seq not a multiple of the block size, prompts ending exactly on a
block boundary), pool invariants under churn, and the zero-measurement
guarantee on the serve hot path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serve import (KVBlockPool, ServeEngine, Tenant, TrafficConfig,
                         generate, poisson_trace)

MAX_SEQ = 48


def _model(arch):
    cfg = get_config(arch, reduced=True)
    return cfg, lm.init_model(cfg, jax.random.PRNGKey(0))


def _own(params):
    """A copy of the weights for an engine, which consumes what it is
    given (the tests reuse their weights for the reference)."""
    return jax.tree.map(jnp.copy, params)


def _refs(params, cfg, prompts, n, max_seq=MAX_SEQ):
    """Batched static-path reference (equal lengths -> one compile)."""
    out = generate(params, cfg, jnp.asarray(prompts, jnp.int32), n,
                   max_seq=max_seq)
    return [row.tolist() for row in np.asarray(out)]


def _ref_one(params, cfg, prompt, n, max_seq=MAX_SEQ):
    out = generate(params, cfg, np.asarray(prompt, np.int32)[None], n,
                   max_seq=max_seq)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# engine == generate(), across cache families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefill_chunk", [2, 4])
@pytest.mark.parametrize("arch", ["qwen1.5-4b", "gemma3-1b", "mamba2-1.3b",
                                  "zamba2-2.7b"])
def test_paged_bitexact_vs_sequential_generate(arch, prefill_chunk):
    """The block-table indirection must not change a single logit:
    per-request streams under continuous batching (staggered arrivals,
    mixed lengths, slot churn) match the one-request-at-a-time static
    path.  Covers absolute caches (qwen), ring-buffer windows re-expressed
    as trailing page windows (gemma), slot-major recurrent state reset
    when a freed slot is reused (mamba), and both riding together in the
    hybrid's shared attention block (zamba); ``prefill_chunk`` 4 is the
    serving cells' setting."""
    cfg, params = _model(arch)
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=3,
                                     max_seq=MAX_SEQ, block_size=8,
                                     prefill_chunk=prefill_chunk,
                                     debug_invariants=True)
    rng = np.random.default_rng(0)
    jobs = []
    for _ in range(4):
        plen, n = int(rng.integers(3, 14)), int(rng.integers(2, 10))
        jobs.append((rng.integers(0, cfg.vocab, plen,
                                  dtype=np.int32).tolist(), n))
    reqs = [engine.submit(p, n) for p, n in jobs[:2]]
    for _ in range(3):
        engine.step()
    reqs += [engine.submit(p, n) for p, n in jobs[2:]]
    engine.run()
    for req, (prompt, n) in zip(reqs, jobs):
        assert req.output == _ref_one(params, cfg, prompt, n), \
            f"request {req.id} diverged from sequential generate()"
        assert len(req.output) == n and not req.truncated
        assert req.ttft is not None and req.latency >= req.ttft
    assert engine.pool.stats()["free_blocks"] == engine.pool.num_blocks
    engine.pool.check()


def test_paged_mla_decode_bitexact():
    """The MLA paged path (latent c_kv + shared k_rope pages) matches the
    slot-cache MLA decode stream."""
    cfg, params = _model("deepseek-v2-lite-16b")
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=2,
                                     max_seq=32, block_size=8,
                                     debug_invariants=True)
    jobs = [([1, 2, 3, 4, 5], 6), ([9, 8, 7], 5)]
    reqs = [engine.submit(p, n) for p, n in jobs]
    engine.run()
    for req, (prompt, n) in zip(reqs, jobs):
        assert req.output == _ref_one(params, cfg, prompt, n, max_seq=32)


# ---------------------------------------------------------------------------
# copy-free preemption: spill to host, resume by remap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen1.5-4b", "mamba2-1.3b"])
def test_spill_preemption_resumes_bitexact_without_recompute(arch):
    """With the pool oversubscribed, stalled victims are spilled —
    their pages copied to host and blocks freed — and later resumed by
    re-uploading into fresh blocks.  Streams stay bit-exact and no
    position runs twice: the lanes stepped are exactly the positions the
    requests needed (prompt + generated - 1 each; nothing is shared)."""
    cfg, params = _model(arch)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, 12).tolist() for _ in range(3)]
    refs = _refs(params, cfg, prompts, 20)
    engine = ServeEngine.from_config(cfg, params=params, max_batch=4,
                                     max_seq=MAX_SEQ, block_size=8,
                                     kv_blocks=6, debug_invariants=True)
    reqs = [engine.submit(p, 20) for p in prompts]
    engine.run(max_steps=5000)
    assert engine.counters["preempt_spills"] > 0, "pool never pressured"
    assert engine.counters["resume_uploads"] > 0
    for req, ref in zip(reqs, refs):
        assert list(req.prompt) + list(req.output) == ref
        assert not req.truncated
    assert engine.counters["prefix_hits"] == 0
    assert engine.counters["lane_tokens"] == sum(len(p) + 20 - 1
                                                 for p in prompts), \
        "spill resume must not recompute"


def test_donated_pages_serve_as_the_slot_engine_through_cow_and_spill():
    """The paged step donates its pages and writes them in place: the
    engine's previous page arrays are consumed by each pass, and with
    copy-on-write forks, spills and resumes in between (eager updates of
    the rebound pages) every stream equals ``generate()``'s from slot
    caches at the same ``max_seq``."""
    cfg, params = _model("qwen1.5-4b")
    prompt = list(range(1, 11))              # 2 full blocks + a partial
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=2,
                                     max_seq=24, block_size=4,
                                     prefill_chunk=1, kv_blocks=7,
                                     debug_invariants=True)
    step, donated = engine._step_fn, []

    def checked(params, caches, *lanes):
        out = step(params, caches, *lanes)
        donated.append(all(x.is_deleted() for x in jax.tree.leaves(caches)))
        return out

    engine._step_fn = checked
    a = engine.submit(prompt, 12)
    while a.first_token_time is None:
        engine.step()
    b = engine.submit(prompt, 12)
    engine.run()
    assert donated and all(donated), "the paged step must donate its pages"
    assert engine.counters["cow_forks"] > 0
    assert engine.counters["preempt_spills"] > 0
    assert engine.counters["resume_uploads"] > 0
    ref = _ref_one(params, cfg, prompt, 12, max_seq=24)
    assert a.output == ref and b.output == ref
    engine.pool.check()


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------

def test_prefix_sharing_cow_under_concurrent_decode():
    """A resident request's prompt blocks (including the partial tail
    block, registered under the whole-prompt key) are shared by later
    identical/prefix-matching admissions; the sharer's first private
    write copy-on-write-forks the shared partial block, and all three
    concurrent streams stay bit-exact."""
    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=3,
                                     max_seq=MAX_SEQ, block_size=8,
                                     debug_invariants=True)
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab, 10).tolist()     # 1 full + partial
    tail = rng.integers(0, cfg.vocab, 3).tolist()
    a = engine.submit(base, 8)
    for _ in range(6):                    # A prefills + starts decoding
        engine.step()
    assert a.first_token_time is not None
    b = engine.submit(base, 8)            # identical prompt: shares 10
    c = engine.submit(base[:8] + tail, 8)  # shares the full block only
    engine.run()

    assert engine.counters["prefix_hits"] == 2
    stats = engine.pool.stats()
    assert stats["shared_tokens_reused"] == 10 + 8
    assert engine.counters["cow_forks"] >= 1, \
        "B's first private write must fork the shared partial block"
    for req, prompt in ((a, base), (b, base), (c, base[:8] + tail)):
        assert req.output == _ref_one(params, cfg, prompt, 8), \
            f"request {req.id} diverged under prefix sharing"
    assert engine.pool.stats()["free_blocks"] == engine.pool.num_blocks
    engine.pool.check()


def test_prefix_sharing_admits_a_burst_on_a_tight_block_budget():
    """The admission capacity prefix sharing buys: after one resident
    request has registered a 24-token system prompt, a burst of 12
    requests behind it (block size 8, 8 blocks, 8 slots) keeps at least 5
    of them resident at once — the concurrency the engine reached here
    when it still had a dense per-slot mode to compare with, where dense
    slots held 2 — and later admissions map the prefix pages."""
    cfg, params = _model("qwen1.5-4b")
    burst = poisson_trace(TrafficConfig(
        qps=1000.0, n_requests=12, n_tenants=1, prompt_len=(2, 4),
        output_len=(4, 8), vocab=cfg.vocab, seed=11, prefix_tokens=24,
        prefix_groups=1), ["t0"])
    engine = ServeEngine.from_config(cfg, params=params, max_batch=8,
                                     max_seq=MAX_SEQ, block_size=8,
                                     kv_blocks=8, tenants=[Tenant("t0")],
                                     debug_invariants=True)
    warm = engine.submit(burst[0].prompt[:25], 8, tenant="t0")
    while warm.first_token_time is None:
        engine.step()
    reqs = [engine.submit(a.prompt, a.max_new_tokens, tenant="t0")
            for a in burst]
    engine.run(max_steps=20_000)
    assert all(r.done for r in reqs)
    assert engine.counters["max_active"] >= 5
    assert engine.counters["prefix_hits"] > 0


def test_shared_prefix_trace_generator_is_seeded_and_layered():
    """loadgen: ``prefix_tokens`` draws from a separate rng stream, so
    the base trace (arrivals, lengths, suffixes) replays token-for-token
    identically with the knob on or off, and the Zipf group choice
    concentrates reuse on the hottest prefix."""
    base = poisson_trace(TrafficConfig(seed=7, n_requests=16))
    pref = poisson_trace(TrafficConfig(seed=7, n_requests=16,
                                       prefix_tokens=16, prefix_groups=4))
    assert [a.at for a in base] == [a.at for a in pref]
    assert all(p.prompt[16:] == b.prompt
               and p.max_new_tokens == b.max_new_tokens
               for b, p in zip(base, pref))
    heads = [tuple(a.prompt[:16]) for a in pref]
    assert len(set(heads)) <= 4
    hottest = max(set(heads), key=heads.count)
    assert heads.count(hottest) >= len(heads) / 4    # Zipf skew
    again = poisson_trace(TrafficConfig(seed=7, n_requests=16,
                                        prefix_tokens=16, prefix_groups=4))
    assert [a.prompt for a in again] == [a.prompt for a in pref]


# ---------------------------------------------------------------------------
# block-geometry edges
# ---------------------------------------------------------------------------

def test_block_size_one():
    """One token per page: every advance grows the table by one block."""
    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=2,
                                     max_seq=24, block_size=1,
                                     debug_invariants=True)
    jobs = [([3, 1, 4, 1, 5], 6), ([2, 7], 5)]
    reqs = [engine.submit(p, n) for p, n in jobs]
    engine.run()
    for req, (prompt, n) in zip(reqs, jobs):
        assert req.output == _ref_one(params, cfg, prompt, n, max_seq=24)
    assert engine.pool.stats()["free_blocks"] == engine.pool.num_blocks


def test_max_seq_not_multiple_of_block_size():
    """max_seq=42 over 8-token blocks: the last block is only partially
    addressable; truncation still lands exactly at max_seq."""
    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=2,
                                     max_seq=42, block_size=8,
                                     debug_invariants=True)
    req = engine.submit([5, 4, 3, 2, 1, 0], 60)        # must truncate
    engine.run()
    assert req.truncated
    # every cache position 0..41 is written; the final emitted token
    # rides without a cache slot, so the stream is max_seq + 1 long
    assert len(req.prompt) + len(req.output) == 43
    ref = _ref_one(params, cfg, [5, 4, 3, 2, 1, 0], 60, max_seq=42)
    assert req.output == ref[:len(req.output)]


def test_prompt_exactly_fills_last_block():
    """A 16-token prompt at block_size=8 ends on a block boundary: the
    first generated token's write opens a fresh block, and when the
    whole prompt is full blocks the partial-tail registration is a
    no-op (everything shareable is already keyed)."""
    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=2,
                                     max_seq=MAX_SEQ, block_size=8,
                                     debug_invariants=True)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, 16).tolist()
    a = engine.submit(prompt, 6)
    for _ in range(8):
        engine.step()
    assert a.first_token_time is not None
    b = engine.submit(prompt, 6)          # shares both full prompt blocks
    engine.run()
    assert engine.counters["prefix_hits"] == 1
    assert engine.pool.stats()["shared_tokens_reused"] == 16
    for req in (a, b):
        assert req.output == _ref_one(params, cfg, prompt, 6)


# ---------------------------------------------------------------------------
# pool-level invariants under churn
# ---------------------------------------------------------------------------

def test_paged_pool_invariants_under_shared_churn():
    """Seeded alloc_shared/advance/commit/free churn with overlapping
    prompts: refcounts, registry keys, and block accounting hold after
    every operation (``check()`` raises on any violation)."""
    rng = np.random.default_rng(13)
    pool = KVBlockPool(num_blocks=24, block_size=4, max_seq=32,
                       num_slots=6)
    prompts = {}
    live = {}
    next_id = 0
    for _ in range(300):
        op = rng.choice(["admit", "advance", "free"])
        if op == "admit" and len(live) < 6:
            plen = int(rng.integers(2, 12))
            if rng.random() < 0.5 and prompts:
                donor = prompts[int(rng.choice(list(prompts)))]
                prompt = (donor + [int(x) for x in
                                   rng.integers(0, 50, 2)])[:plen] \
                    if plen > len(donor) else donor[:plen]
            else:
                prompt = [int(x) for x in rng.integers(0, 50, plen)]
            if pool.can_admit_shared(prompt):
                t = pool.alloc_shared(next_id, prompt)
                live[next_id] = [len(prompt), prompt]
                prompts[next_id] = prompt
                next_id += 1
        elif op == "advance" and live:
            rid = int(rng.choice(list(live)))
            pos, prompt = live[rid]
            if pos < 32 and pool.can_advance(rid, pos, write=True):
                pool.advance(rid, pos, write=True)
                tokens = prompt + [int(x) for x in
                                   rng.integers(0, 50, pos + 1)]
                pool.commit(rid, tokens[:pos + 1], pos,
                            prompt_len=len(prompt))
                live[rid][0] = pos + 1
        elif op == "free" and live:
            rid = int(rng.choice(list(live)))
            pool.free(rid)
            del live[rid], prompts[rid]
        pool.check()
    for rid in list(live):
        pool.free(rid)
    pool.check()
    assert pool.stats()["free_blocks"] == pool.num_blocks


# ---------------------------------------------------------------------------
# zero-measurement serve hot path
# ---------------------------------------------------------------------------

def test_paged_serve_hot_path_zero_measurements(tmp_path, stall_db,
                                                monkeypatch):
    """Acceptance criterion: an engine constructed with a schedule cache
    resolves its whole kernel plan and serves traffic, a shared prompt
    prefix included, with zero ``Machine.run``/``Machine.time``/autotune
    calls — schedules reach the serve path as pure index lookups."""
    import sys

    from repro.core import Machine
    from repro.sched import OptimizationSession, make_budgeted_strategy
    from repro.sched.cache import ScheduleCache
    from repro.sched.session import OptimizeRequest

    session = OptimizationSession(
        strategy=make_budgeted_strategy("greedy", timesteps=64,
                                        episode_length=8),
        cache_dir=str(tmp_path / "cache"), stall_db=stall_db,
        verify_seeds=2)
    session.optimize(OptimizeRequest(kernel="rmsnorm"))

    calls = {"run": 0, "time": 0, "autotune": 0}
    real_run, real_time = Machine.run, Machine.time
    autotune_mod = sys.modules["repro.sched.autotune"]

    def counting(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(Machine, "run", counting("run", real_run))
    monkeypatch.setattr(Machine, "time", counting("time", real_time))
    monkeypatch.setattr(autotune_mod, "autotune",
                        counting("autotune", autotune_mod.autotune))

    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(
        cfg, params=params, max_batch=2, max_seq=32, block_size=8,
        debug_invariants=True,
        schedule_cache=ScheduleCache(str(tmp_path / "cache")))
    # the tuned kernel resolved in every bucket; untuned fleet members
    # explicitly serve the -O3 baseline (None), never re-measured
    rms = {k: v for k, v in engine.plan.items()
           if (k[0] if isinstance(k, tuple) else k) == "rmsnorm"}
    assert rms and all(a is not None for a in rms.values())
    assert any(a is None for a in engine.plan.values())
    a = engine.submit([1, 2, 3, 4], 4)
    while a.first_token_time is None:
        engine.step()
    engine.submit([1, 2, 3, 4], 4)       # maps the resident prompt page
    engine.submit([5, 6], 3)
    engine.run()
    assert calls == {"run": 0, "time": 0, "autotune": 0}
    assert engine.counters["prefix_hits"] == 1


# ---------------------------------------------------------------------------
# the engine's own spans and named scopes
# ---------------------------------------------------------------------------

STAGES = {"step": None, "evict": "step", "admit": "step", "pass": "step",
          "preempt": "step", "pool": "pass", "cow_copy": "pass",
          "assemble": "pass", "dispatch": "pass", "sync": "pass",
          "apply": "pass"}


def _program_spans(path):
    from jax.profiler import ProfileData
    return [(ev.name[len("repro.serve."):], int(ev.start_ns), int(ev.end_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.serve.")]


def test_profiled_step_writes_every_stage_span_nested(tmp_path):
    """Under the profiler, ``step()`` writes a ``repro.serve.<stage>`` span
    for each stage, each inside its parent: a second request on a live
    prompt forks its partial page (``cow_copy``), and 7 blocks for two
    requests growing to 6 each make the engine preempt."""
    import glob

    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=params, max_batch=2,
                                     max_seq=24, block_size=4, kv_blocks=7,
                                     prefill_chunk=1)
    prompt = list(range(1, 11))              # 2 full blocks + a partial
    engine.generate(prompt, 2)               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    a = engine.submit(prompt, 12)
    while a.first_token_time is None:
        engine.step()
    engine.submit(prompt, 12)
    engine.run()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _program_spans(path)
    assert engine.counters["cow_forks"] and engine.counters["preemptions"]
    assert {n for n, _, _ in spans} == set(STAGES)
    for name, s, e in spans:
        parent = STAGES[name]
        if parent is not None:
            assert any(n == parent and ps <= s and e <= pe
                       for n, ps, pe in spans), f"{name} outside {parent}"


def test_engine_lays_out_its_weights_once_under_a_span(tmp_path):
    """Building the engine lays out the weights it is given per layer,
    inside one ``repro.serve.layout`` span, timed in ``layout_s``; the
    stacked arrays passed in are consumed, and no step lays them out
    again."""
    import glob

    cfg, params = _model("qwen1.5-4b")
    given = _own(params)
    jax.profiler.start_trace(str(tmp_path))
    engine = ServeEngine.from_config(cfg, params=given, max_batch=2,
                                     max_seq=MAX_SEQ, block_size=8)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert [n for n, _, _ in _program_spans(path)] == ["layout"]
    assert engine.counters["layout_s"] > 0
    assert all(a.is_deleted() for a in jax.tree.leaves(given["layers"]))
    assert len(engine.params["layers"]) == cfg.n_layers
    engine.submit([1, 2, 3], 4)
    engine.run()
    assert engine.counters["layout_s"] == engine.stats()["engine"][
        "layout_s"]
    assert engine.finished[0].output == _ref_one(params, cfg, [1, 2, 3], 4)


def test_paged_step_hlo_carries_named_scopes():
    """The compiled paged step keeps the decode step's named scopes in its
    ``op_name`` metadata, per layer and for the head."""
    from repro.serve.engine import _paged_step_fns

    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=params, max_batch=2,
                                     max_seq=MAX_SEQ, block_size=8)
    step = _paged_step_fns(cfg, None, MAX_SEQ)[0]
    lane = jnp.zeros((2,), jnp.int32)
    table = jnp.zeros((2, engine.pool.blocks_per_slot), jnp.int32)
    text = step.lower(engine.params, engine.caches, lane, table, lane, lane,
                      lane).compile().as_text()
    for scope in ("layer0/attn/kv_write", "layer0/attn/attend",
                  "layer1/mlp", "/head/"):
        assert scope in text, scope
