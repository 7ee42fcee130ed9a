"""Continuous-batching serve engine: per-request bit-exactness vs the
static ``generate()`` path under preemption and gang admission, stage
counters, KV block-pool invariants under seeded churn, weighted-fair
scheduling with starvation/budget guards, the cache-capacity admission
boundary, and the load generator."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serve import (FairScheduler, KVBlockPool, PoolCapacityError,
                         PoolError, Request, ServeEngine, Tenant,
                         TrafficConfig, generate, run_load)
from repro.serve.decode import decode_step, init_caches, serving_params

MAX_SEQ = 48


def _model(arch):
    cfg = get_config(arch, reduced=True)
    return cfg, lm.init_model(cfg, jax.random.PRNGKey(0))


def _own(params):
    """A copy of the weights for an engine, which consumes what it is
    given (the tests reuse their weights for the reference)."""
    return jax.tree.map(jnp.copy, params)


def _ref_generation(params, cfg, prompt, n):
    """One-request-at-a-time reference: the static scanned generate()
    at the engine's cache geometry (same max_seq -> same summation
    order), returning just the generated suffix."""
    out = generate(params, cfg, np.asarray(prompt, np.int32)[None], n,
                   max_seq=MAX_SEQ)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# continuous-batching equivalence
# ---------------------------------------------------------------------------

def test_preempted_requests_resume_bitexact():
    """Spill preemption under ``prefill_chunk`` 4: with the block pool
    oversubscribed, stalled requests have their pages spilled to the host
    and are requeued, and still finish bit-exact vs the sequential
    reference (a truncated one as a prefix of it)."""
    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=2,
                                     max_seq=MAX_SEQ, block_size=8,
                                     kv_blocks=7, prefill_chunk=4,
                                     debug_invariants=True)
    rng = np.random.default_rng(2)
    jobs = [(rng.integers(0, cfg.vocab, 10, dtype=np.int32).tolist(), 30)
            for _ in range(3)]
    reqs = [engine.submit(p, n) for p, n in jobs]
    engine.run()
    assert engine.counters["preemptions"] > 0, "pool never pressured"
    assert (engine.counters["preempt_spills"]
            == engine.counters["preemptions"])
    for req, (prompt, n) in zip(reqs, jobs):
        ref = _ref_generation(params, cfg, prompt, n)
        if req.truncated:
            assert req.output == ref[:len(req.output)]
        else:
            assert req.output == ref
    engine.pool.check()


def test_stage_counters_and_admission_stamps():
    """The engine's stage clocks after ``run()``: one first token per
    request, waits and prefills never negative, host and sync time inside
    the wall time of the ``step()`` calls, and a request queued behind a
    full batch admitted after it was submitted.  A preempted request is
    timed from its requeue (``queued_time``), not from its submit."""
    import time

    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=params, max_batch=2,
                                     max_seq=MAX_SEQ, block_size=8,
                                     kv_blocks=7, prefill_chunk=4)
    rng = np.random.default_rng(2)
    reqs = [engine.submit(rng.integers(0, cfg.vocab, 10).tolist(), 30)
            for _ in range(3)]
    wall = 0.0
    while engine.active or engine.scheduler.pending():
        t = time.perf_counter()
        engine.step()
        wall += time.perf_counter() - t
    c = engine.counters
    assert c["first_tokens"] == len(reqs)
    assert c["queue_wait_s"] >= 0 and c["prefill_s"] >= 0
    assert c["host_s"] > 0 and c["sync_s"] > 0
    assert c["host_s"] + c["sync_s"] <= wall
    assert "engine_steps" not in c and "truncations" not in c
    assert reqs[2].admit_time > reqs[2].submit_time   # behind the batch
    preempted = [r for r in reqs if r.preemptions]
    assert preempted, "pool never pressured"
    for r in reqs:
        if r.preemptions:      # admit_time is the latest admission
            assert r.admit_time >= r.queued_time > r.submit_time
        else:
            assert r.first_token_time >= r.admit_time >= r.submit_time


def test_gang_admission_is_static_batching():
    """admission='gang' (the bench baseline) only admits into an idle
    engine and still produces the exact sequential streams."""
    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=_own(params), max_batch=2,
                                     max_seq=MAX_SEQ, admission="gang")
    jobs = [([1, 2, 3], 5), ([4, 5, 6, 7], 4), ([8, 9], 6)]
    reqs = [engine.submit(p, n) for p, n in jobs]
    saw_full_gang = False
    while engine.active or engine.scheduler.pending():
        engine.step()
        assert engine.active <= 2
        if engine.active == 2 and engine.scheduler.pending():
            saw_full_gang = True
            assert engine.counters["admissions"] == 2  # 3rd waits for gang
    assert saw_full_gang
    for req, (prompt, n) in zip(reqs, jobs):
        assert req.output == _ref_generation(params, cfg, prompt, n)


# ---------------------------------------------------------------------------
# the serving form of the weights
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-v2-lite-16b",
                                  "zamba2-2.7b"])
def test_serving_params_split_each_stacked_group_per_layer(arch):
    """Dense, moe with MLA and a leading dense layer, and hybrid: each
    stacked group becomes a list with one tree per layer whose leaves are
    ``a[i]`` of the stacked leaves; other entries pass through.  With
    ``consume`` the stacked leaves are deleted, and only they."""
    cfg, params = _model(arch)
    groups = [k for k in ("layers", "dense_layers") if k in params]
    assert groups == (["layers", "dense_layers"]
                      if arch.startswith("deepseek") else ["layers"])
    got = serving_params(_own(params), consume=False)
    assert set(got) == set(params)
    for key in params:
        if key not in groups:
            assert jax.tree.all(jax.tree.map(
                lambda a, b: bool((a == b).all()), got[key], params[key]))
            continue
        stacked = jax.tree.leaves(params[key])
        assert isinstance(got[key], list)
        assert len(got[key]) == stacked[0].shape[0]
        for i, layer in enumerate(got[key]):
            assert (jax.tree.structure(layer)
                    == jax.tree.structure(jax.tree.map(lambda a: a[0],
                                                       params[key])))
            for a, b in zip(stacked, jax.tree.leaves(layer)):
                np.testing.assert_array_equal(np.asarray(a[i]),
                                              np.asarray(b))
    own = _own(params)
    serving_params(own, consume=True)
    for key in own:
        assert all(a.is_deleted() == (key in groups)
                   for a in jax.tree.leaves(own[key])), key


# ---------------------------------------------------------------------------
# ragged per-row positions in decode_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-v2-lite-16b"])
def test_vector_pos_decode_step_matches_scalar(arch):
    """A (B,) pos vector with equal entries is bit-identical to the
    scalar-pos path (attention and MLA latent caches)."""
    cfg, params = _model(arch)
    B, steps = 3, 5
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (steps, B, 1), dtype=np.int32)
    params = serving_params(params)
    c_s = init_caches(cfg, B, MAX_SEQ)
    c_v = init_caches(cfg, B, MAX_SEQ)
    for pos in range(steps):
        t = jnp.asarray(toks[pos])
        log_s, c_s = decode_step(params, c_s, t, pos, cfg)
        log_v, c_v = decode_step(params, c_v, t,
                                 jnp.full((B,), pos, jnp.int32), cfg)
        np.testing.assert_array_equal(np.asarray(log_s), np.asarray(log_v))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# capacity boundary
# ---------------------------------------------------------------------------

def test_prompt_at_cache_capacity_raises_pool_capacity_error():
    """A prompt of exactly ``max_seq`` tokens must raise a typed
    PoolCapacityError at admission (pool and engine), not silently write
    out of cache range; ``max_seq - 1`` still admits and generates."""
    pool = KVBlockPool(num_slots=2, max_seq=16, block_size=4)
    assert not pool.fits(16) and not pool.fits(17) and pool.fits(15)
    with pytest.raises(PoolCapacityError):
        pool.alloc("r1", 16)
    pool.check()

    cfg, params = _model("qwen1.5-4b")
    engine = ServeEngine.from_config(cfg, params=params, max_batch=2,
                                     max_seq=16)
    with pytest.raises(PoolCapacityError):
        engine.submit(list(range(16)), 4)
    req = engine.submit(list(range(15)), 4)      # boundary-1: admissible
    engine.run()
    # positions 14 and 15 each emit one token, then a clean truncation —
    # never a clamped out-of-range cache write
    assert len(req.output) == 2 and req.truncated
    engine.pool.check()


# ---------------------------------------------------------------------------
# pool invariants
# ---------------------------------------------------------------------------

def test_pool_alloc_free_invariants_under_seeded_churn():
    pool = KVBlockPool(num_slots=4, max_seq=64, block_size=8, num_blocks=20)
    rng = np.random.default_rng(42)
    live = {}
    for i in range(400):
        if live and (rng.random() < 0.4 or not pool.free_slot_count):
            rid = rng.choice(list(live))
            pool.free(rid)
            del live[rid]
        else:
            rid, plen = f"r{i}", int(rng.integers(1, 64))
            if pool.can_admit(plen):
                t = pool.alloc(rid, plen)
                live[rid] = t
                assert t.tokens >= plen
        if live and rng.random() < 0.5:
            rid = rng.choice(list(live))
            pos = int(rng.integers(0, 64))
            if pool.can_advance(rid, pos):
                assert pool.advance(rid, pos) is None     # nothing shared
                assert pool.table(rid).tokens >= pos + 1
        pool.check()                      # conservation + no double-grant
    for rid in list(live):
        pool.free(rid)
    pool.check()
    s = pool.stats()
    assert s["free_blocks"] == pool.num_blocks      # no leak after churn
    assert s["free_slots"] == pool.num_slots
    assert s["allocs"] == s["frees"]


def test_pool_double_free_and_protocol_errors():
    pool = KVBlockPool(num_slots=2, max_seq=32, block_size=8)
    pool.alloc("a", 10)
    with pytest.raises(PoolError):
        pool.alloc("a", 4)               # duplicate allocation
    pool.free("a")
    with pytest.raises(PoolError):
        pool.free("a")                   # double free
    with pytest.raises(PoolError):
        pool.ensure("ghost", 8)          # unknown request
    pool.check()


def test_pool_oversubscription_runs_out_of_blocks_not_slots():
    pool = KVBlockPool(num_slots=4, max_seq=32, block_size=8, num_blocks=5)
    pool.alloc("a", 24)                  # 3 blocks
    pool.alloc("b", 16)                  # 2 blocks -> 0 free
    assert pool.free_slot_count == 2 and not pool.can_admit(1)
    with pytest.raises(PoolCapacityError):
        pool.alloc("c", 8)
    assert pool.can_advance("a", 23)     # inside its 3 blocks
    assert not pool.can_advance("a", 24)  # needs a 4th
    with pytest.raises(PoolCapacityError):
        pool.advance("a", 31)
    pool.free("b")
    assert pool.can_advance("a", 31)
    pool.advance("a", 31)
    assert pool.table("a").tokens == 32
    pool.check()


# ---------------------------------------------------------------------------
# fair scheduler
# ---------------------------------------------------------------------------

def _drain(sched, n):
    order = []
    for _ in range(n):
        req = sched.admit_next()
        if req is None:
            break
        req.finish_time = req.submit_time + 1.0
        sched.release(req, served_tokens=req.cost)
        order.append(req.tenant)
    return order


def test_wfq_admission_tracks_weight_ratio():
    """Tenants with 3:1 weights and equal-cost backlogs get admissions in
    a 3:1 ratio over any busy window (stride scheduling)."""
    sched = FairScheduler([Tenant("a", weight=3.0), Tenant("b", weight=1.0)],
                          starvation_bound=1000)
    for i in range(40):
        sched.submit(Request(prompt=[0] * 8, max_new_tokens=8, tenant="a"))
        sched.submit(Request(prompt=[0] * 8, max_new_tokens=8, tenant="b"))
    order = _drain(sched, 24)
    assert order.count("a") == 18 and order.count("b") == 6


def test_starvation_bound_caps_low_weight_wait():
    """Weights bound *rates*, not *waits*: at a 1000:1 effective weight
    ratio pure WFQ would serve the light tenant once and then pass it
    over for ~1000 rounds.  The starvation bound instead caps every
    inter-admission gap at ``bound`` passed-over rounds."""
    bound = 4
    sched = FairScheduler([Tenant("heavy", weight=1.0),
                           Tenant("light", weight=0.001)],
                          starvation_bound=bound)
    for _ in range(30):
        sched.submit(Request(prompt=[0] * 8, max_new_tokens=8,
                             tenant="heavy"))
    for _ in range(5):
        sched.submit(Request(prompt=[0] * 8, max_new_tokens=8,
                             tenant="light"))
    order = _drain(sched, 20)
    light_pos = [i for i, t in enumerate(order) if t == "light"]
    assert len(light_pos) >= 3, f"light tenant starved: {order}"
    gaps = [b - a for a, b in zip(light_pos, light_pos[1:])]
    assert all(g <= bound + 1 for g in gaps), \
        f"light tenant waited beyond the bound: {order} (gaps {gaps})"


def test_token_budget_caps_in_flight_tokens():
    sched = FairScheduler([Tenant("a", weight=1.0, token_budget=20)])
    reqs = [sched.submit(Request(prompt=[0] * 6, max_new_tokens=4,
                                 tenant="a")) for _ in range(3)]
    assert sched.admit_next() is reqs[0]          # 10 in flight
    assert sched.admit_next() is reqs[1]          # 20 in flight: at budget
    assert sched.admit_next() is None             # over budget -> throttled
    sched.release(reqs[0], served_tokens=4)
    assert sched.admit_next() is reqs[2]          # budget freed
    table = {r["tenant"]: r for r in sched.fairness_table()}
    assert table["a"]["in_flight_tokens"] == 20


def test_preemption_requeue_is_not_double_charged():
    """A preempted request re-admits without advancing its tenant's
    virtual time again (its footprint was charged at first admission)."""
    sched = FairScheduler([Tenant("a", weight=1.0)])
    req = sched.submit(Request(prompt=[0] * 8, max_new_tokens=8, tenant="a"))
    assert sched.admit_next() is req
    v1 = sched.fairness_table()[0]["vtime"]
    sched.release(req)                            # preemption
    sched.requeue_front(req)
    assert sched.admit_next() is req
    assert sched.fairness_table()[0]["vtime"] == v1


# ---------------------------------------------------------------------------
# load generator
# ---------------------------------------------------------------------------

def test_load_generator_replays_seeded_trace_and_reports():
    cfg, params = _model("qwen1.5-4b")
    traffic = TrafficConfig(qps=200.0, n_requests=6, n_tenants=2,
                            prompt_len=(2, 6), output_len=(2, 5),
                            vocab=cfg.vocab, seed=3)
    engine = ServeEngine.from_config(
        cfg, params=params, max_batch=2, max_seq=24,
        tenants=[Tenant("t0", weight=2.0), Tenant("t1", weight=1.0)])
    report = run_load(engine, traffic, pace=False)
    assert report["completed"] == 6 and report["truncated"] == 0
    assert report["tokens"] > 0 and report["tokens_per_s"] > 0
    for k in ("latency_p50_s", "latency_p99_s", "ttft_p50_s"):
        assert np.isfinite(report[k]) and report[k] >= 0
    served = {r["tenant"]: r["served_tokens"]
              for r in report["stats"]["tenants"]}
    assert sum(served.values()) == report["tokens"]
    assert report["stats"]["engine"]["lane_utilization"] > 0
