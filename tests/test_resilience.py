"""Fault-tolerant measurement & campaign layer: seeded fault injection
(transients / hangs / crash fingerprints / outliers), ResilientBackend
retry + robust timing + circuit breaking, supervised resumable campaigns
with the persistent failure ledger, the previously-untested
probabilistic-verify failure path, cache/memo quarantine, and graceful
serve degradation (``on_missing``)."""

import json
import os
import pickle
import warnings

import pytest

from repro.core import (FaultSpec, FaultyMachine, HardFault, Machine,
                        MeasureError, schedule_fingerprint)
from repro.core.faults import MeasureTimeout
from repro.launch.optimize import campaign_requests, parse_scenarios
from repro.sched import (FailureLedger, FastTimingBackend,
                         OptimizationSession, OptimizeFailure,
                         OptimizeRequest, ResilientBackend, RetryPolicy,
                         baseline, lower, make_backend,
                         make_budgeted_strategy, resolve_schedule)
from repro.sched.backends import (MemoVersionError, SharedMeasureMemo,
                                  warm_start_memo)
from repro.sched.cache import CacheVersionError, ScheduleCache
from repro.sched.resilience import MeasureExhausted, cell_key
from repro.sched.scenario import build_spec, get_target
from repro.sched.session import SearchOutcome
from repro.core.isa import program_text


def _scheduled(kernel_programs, name="rmsnorm"):
    return kernel_programs[name]


def _faulty_factory(**spec_kw):
    spec = FaultSpec(**spec_kw)
    return lambda: FaultyMachine(spec)


# ---------------------------------------------------------------------------
# fault injection (core/faults.py)
# ---------------------------------------------------------------------------

def test_fault_injection_deterministic_and_fingerprint_invariance(
        kernel_programs):
    prog = _scheduled(kernel_programs)

    def trace(seed):
        m = FaultyMachine(FaultSpec(seed=seed, transient_rate=0.3,
                                    outlier_rate=0.2, outlier_scale=5.0))
        out = []
        for _ in range(30):
            try:
                out.append(round(m.time(prog), 3))
            except MeasureError:
                out.append("X")
        return out, dict(m.fault_counters)

    t1, c1 = trace(7)
    t2, c2 = trace(7)
    assert t1 == t2 and c1 == c2            # same seed -> same fault replay
    t3, _ = trace(8)
    assert t1 != t3                          # different seed -> different
    assert c1["transients"] > 0 and c1["outliers"] > 0

    # no faults firing -> byte-identical to the wrapped machine
    clean = FaultyMachine(FaultSpec(seed=0))
    assert clean.time(prog) == Machine().time(prog)
    assert clean.run(prog).cycles == Machine().run(prog).cycles

    # the fingerprint is permutation-invariant (identifies the *cell*,
    # not the ordering the game is mutating) and schedule-hint-blind
    fp = schedule_fingerprint(prog)
    assert schedule_fingerprint(list(reversed(prog))) == fp
    other = _scheduled(kernel_programs, "softmax")
    assert schedule_fingerprint(other) != fp

    crash = FaultyMachine(FaultSpec(seed=0, crash_fingerprints={fp}))
    with pytest.raises(HardFault):
        crash.time(prog)
    with pytest.raises(HardFault):
        crash.time(list(reversed(prog)))     # every permutation crashes
    assert crash.time(other) == Machine().time(other)  # siblings untouched


# ---------------------------------------------------------------------------
# ResilientBackend: retry, timeout, robust statistics, breaker
# ---------------------------------------------------------------------------

def test_resilient_retries_transients_to_exact_value(kernel_programs):
    prog = _scheduled(kernel_programs)
    rb = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=1, transient_rate=0.5)),
        policy=RetryPolicy(max_retries=10))
    for _ in range(5):
        assert rb.time(prog) == Machine().time(prog)   # retried, bit-exact
    s = rb.stats()
    assert s["transients"] > 0 and s["retries"] == s["transients"]
    assert s["measures"] == 5 and not rb.circuit_open

    # zero retry budget -> exhaustion is a loud typed failure
    dead = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=1, transient_rate=1.0)),
        policy=RetryPolicy(max_retries=3, breaker_threshold=99))
    with pytest.raises(MeasureExhausted):
        dead.time(prog)
    assert dead.stats()["exhausted"] == 1


def test_resilient_timeout_detects_hangs(kernel_programs):
    prog = _scheduled(kernel_programs)
    rb = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=0, hang_rate=1.0,
                                          hang_s=0.03)),
        policy=RetryPolicy(max_retries=2, timeout_s=0.005,
                           breaker_threshold=99))
    with pytest.raises(MeasureExhausted) as ei:
        rb.time(prog)
    assert isinstance(ei.value.__cause__, MeasureTimeout)
    assert rb.stats()["timeouts"] == 3       # every attempt blew the deadline

    # a generous deadline lets the (slow) measurement through
    ok = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=0, hang_rate=1.0,
                                          hang_s=0.001)),
        policy=RetryPolicy(timeout_s=5.0))
    assert ok.time(prog) == Machine().time(prog)


def test_resilient_outlier_rejection_and_adaptive_k(kernel_programs):
    prog = _scheduled(kernel_programs)
    rb = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=2, outlier_rate=0.4,
                                          outlier_scale=100.0)),
        policy=RetryPolicy(samples=3, max_samples=16))
    vals = [rb.time(prog) for _ in range(6)]
    clean = Machine().time(prog)
    assert vals == [clean] * 6     # median + MAD rejection kills the spikes
    s = rb.stats()
    assert s["outliers_rejected"] > 0
    assert s["sample_escalations"] > 0       # high variance widened k


def test_circuit_breaker_degrades_to_scoreboard(kernel_programs):
    prog = _scheduled(kernel_programs)
    fp = schedule_fingerprint(prog)
    rb = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=0, crash_fingerprints={fp})),
        policy=RetryPolicy(max_retries=1, breaker_threshold=3))
    for _ in range(2):
        with pytest.raises(HardFault):
            rb.time(prog)
        assert not rb.circuit_open           # below the threshold
    # third consecutive hard failure trips the breaker; the call itself is
    # already served by the deterministic scoreboard fallback
    assert rb.time(prog) == Machine().time(prog)
    assert rb.circuit_open
    assert rb.time(prog) == Machine().time(prog)     # degraded steady state
    s = rb.stats()
    assert s["breaker_trips"] == 1 and s["open_breakers"] == 1
    assert s["degraded"] >= 2
    assert "OPEN" in rb.summary()

    # machines the backend hands out degrade too (the game / verify path),
    # with real dataflow results from the fallback oracle
    m = rb.new_machine()
    assert m.run(prog).cycles == Machine().run(prog).cycles

    # a success before the threshold resets the consecutive count: one
    # crashing cell does not degrade an otherwise healthy target
    healthy = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=0, crash_fingerprints={fp})),
        policy=RetryPolicy(max_retries=1, breaker_threshold=3))
    other = _scheduled(kernel_programs, "softmax")
    for _ in range(5):
        with pytest.raises(HardFault):
            healthy.time(prog)
        assert healthy.time(other) == Machine().time(other)
    assert not healthy.circuit_open


def test_resilient_passthrough_and_for_target_isolation(kernel_programs):
    prog = _scheduled(kernel_programs)
    rb = make_backend("resilient")           # registered, over fast timing
    assert rb.name == "resilient[fast]"
    # deterministic inner -> machines/memo pass straight through (the
    # memoized fast path stays enabled and bit-exact)
    assert type(rb.new_machine()) is Machine
    assert rb.memo_view(prog, "k") is not None
    assert rb.time(prog) == Machine().time(prog)

    # per-target breakers: wedging one target leaves its sibling closed
    faulty = ResilientBackend(
        FastTimingBackend(_faulty_factory(seed=1, transient_rate=1.0)),
        policy=RetryPolicy(max_retries=0, breaker_threshold=1))
    sibling = faulty.for_target(Machine)
    # threshold 1: the very first exhaustion trips the breaker and the
    # call itself is already served by the degraded fallback
    assert faulty.time(prog) == Machine().time(prog)
    assert faulty.circuit_open and not sibling.circuit_open
    assert sibling.time(prog) == Machine().time(prog)
    agg = faulty.stats()                      # summary aggregates the family
    assert agg["targets"] == 2 and agg["open_breakers"] == 1


# ---------------------------------------------------------------------------
# optimize_many supervision (threaded partial results + verify failures)
# ---------------------------------------------------------------------------

class _MangleStrategy:
    """Returns a schedule with one true-dependent pair swapped — the
    masking-bug shape probabilistic testing (§4.1) exists to catch."""

    name = "mangle"

    def search(self, program, *, stall_db, backend, owner="", verbose=False):
        bad = [ins.copy() for ins in program]
        for i in range(len(bad) - 1):
            a, b = bad[i], bad[i + 1]
            if a.defs and b.uses and set(a.defs) & set(b.uses):
                bad[i], bad[i + 1] = b, a
                break
        cycles = backend.time(bad, owner)
        return SearchOutcome(best_program=bad, best_cycles=cycles,
                             baseline_cycles=cycles, stats=[])


def test_verify_failure_refuses_to_cache(tmp_path, stall_db):
    session = OptimizationSession(strategy=_MangleStrategy(),
                                  cache_dir=str(tmp_path / "cache"),
                                  stall_db=stall_db, verify_seeds=2)
    with pytest.raises(RuntimeError, match="probabilistic testing FAILED"):
        session.optimize(OptimizeRequest(kernel="rmsnorm",
                                         config={"br": 8, "cols": 2048}))
    # the mangled schedule must NOT have been cached
    assert session.cache.lookup_best("rmsnorm") is None


def test_optimize_many_collects_partial_results(tmp_path, stall_db):
    tiny = make_budgeted_strategy("random", timesteps=16, episode_length=8)
    session = OptimizationSession(strategy=tiny,
                                  cache_dir=str(tmp_path / "cache"),
                                  stall_db=stall_db, verify_seeds=2)
    cfg = {"br": 8, "cols": 2048}
    reqs = [OptimizeRequest(kernel="rmsnorm", config=cfg),
            OptimizeRequest(kernel="rmsnorm", config=cfg,
                            strategy=_MangleStrategy(), force=True),
            OptimizeRequest(kernel="softmax", config={"br": 8, "cols": 4096})]

    # threaded collect: the failing sibling is captured, the healthy ones
    # complete and return (the old pool.map discarded them all)
    outcomes = session.optimize_many(reqs, max_workers=3, on_error="collect")
    assert [o.ok for o in outcomes] == [True, False, True]
    failure = outcomes[1]
    assert isinstance(failure, OptimizeFailure)
    assert failure.error_type == "RuntimeError"
    assert "probabilistic testing FAILED" in failure.error
    assert outcomes[0].artifact is not None and outcomes[2].artifact is not None

    # legacy contract: on_error="raise" still propagates the first error
    with pytest.raises(RuntimeError, match="probabilistic testing FAILED"):
        session.optimize_many([reqs[1]], max_workers=2, on_error="raise")
    with pytest.raises(ValueError, match="on_error"):
        session.optimize_many(reqs, on_error="ignore")


# ---------------------------------------------------------------------------
# the acceptance campaign: 20% transients + one always-crashing cell,
# scenarios × targets, bit-exact healthy cells, resumable ledger
# ---------------------------------------------------------------------------

def _campaign_session(cache_dir, stall_db, backend):
    return OptimizationSession(
        backend=backend,
        strategy=make_budgeted_strategy("random", timesteps=16,
                                        episode_length=8),
        cache_dir=str(cache_dir), stall_db=stall_db, verify_seeds=2)


def test_supervised_campaign_with_faults_matches_fault_free_run(
        tmp_path, stall_db):
    scens = parse_scenarios("4x512,8x4096")
    targets = [get_target("tpu-tsass-v1"), get_target("tpu-tsass-v2")]
    units = [(n, s) for n in ("rmsnorm", "softmax") for s in scens]
    reqs = campaign_requests(units, targets)
    assert len(reqs) == 8                     # 2 kernels × 2 scens × 2 tgts

    # the always-crashing cell: softmax @ scens[1] @ tpu-tsass-v1.  Pin
    # the schedules unique to that workload point (configs clamped to the
    # same spec at both points share a fingerprint — pinning those would
    # crash the sibling scenario too), so some autotune measurement in
    # that cell — and only that cell — hard-faults, every pass
    from repro.kernels import get_kernel
    kd = get_kernel("softmax")

    def fps_at(scen):
        return {schedule_fingerprint(baseline.schedule(lower(
            build_spec(kd.make_spec, cfg, scen)))) for cfg in kd.configs}

    crash_fps = fps_at(scens[1]) - fps_at(scens[0])
    assert crash_fps                          # the scenarios do differ
    crash_cell = cell_key("softmax", scens[1], targets[0])

    # fault-free reference campaign (its own cache dir)
    ref = _campaign_session(tmp_path / "ref", stall_db, FastTimingBackend())
    ref_results = ref.optimize_many(reqs)
    assert all(r.ok for r in ref_results)

    # faulty campaign: v1 measures through 20% transients + the crash
    # pins; v2 siblings (via for_target) stay clean
    faulty = ResilientBackend(
        FastTimingBackend(_faulty_factory(
            seed=5, transient_rate=0.2, crash_fingerprints=crash_fps)),
        policy=RetryPolicy(max_retries=8))
    session = _campaign_session(tmp_path / "run", stall_db, faulty)
    ledger = FailureLedger(str(tmp_path / "run" / "campaign_state.json"))

    results = session.optimize_many(reqs, ledger=ledger, max_retries=1)
    by_cell = {session._cell_key(r): out
               for r, out in zip(reqs, results)}

    # exactly the crashing cell failed, with its attempt recorded
    fails = {c: o for c, o in by_cell.items() if not o.ok}
    assert set(fails) == {crash_cell}
    assert fails[crash_cell].error_type == "HardFault"
    assert fails[crash_cell].attempts == 1
    assert set(ledger.failed_cells()) == {crash_cell}
    assert ledger.attempts(crash_cell) == 1
    assert not faulty.circuit_open            # healthy successes reset it

    # every healthy cell is bit-exact vs the fault-free campaign
    # (schedule text AND measured cycles — the memo-backed values agree)
    ref_by_cell = {ref._cell_key(r): out
                   for r, out in zip(reqs, ref_results)}
    for cell, out in by_cell.items():
        if cell == crash_cell:
            continue
        want = ref_by_cell[cell]
        assert program_text(out.artifact.program) == \
            program_text(want.artifact.program), cell
        assert out.artifact.optimized_cycles == \
            want.artifact.optimized_cycles, cell
        assert out.artifact.baseline_cycles == \
            want.artifact.baseline_cycles, cell
        assert not out.degraded

    # resume pass: healthy cells are pure cache hits, ONLY the crashing
    # cell re-runs its search — and fails again (attempts -> 2)
    resume = session.optimize_many(reqs, ledger=ledger, max_retries=1)
    by_cell2 = {session._cell_key(r): o for r, o in zip(reqs, resume)}
    for cell, o in by_cell2.items():
        if cell == crash_cell:
            assert not o.ok and o.attempts == 2 and not o.skipped
        else:
            assert o.ok and o.from_cache
    assert ledger.attempts(crash_cell) == 2

    # third pass: the retry budget (max_retries=1 -> 2 total attempts) is
    # spent; the cell is skipped without re-running, attempts unchanged
    third = session.optimize_many(reqs, ledger=ledger, max_retries=1)
    crash_out = {session._cell_key(r): o
                 for r, o in zip(reqs, third)}[crash_cell]
    assert crash_out.skipped and crash_out.attempts == 2
    assert ledger.attempts(crash_cell) == 2

    # the ledger is persistent: a fresh process sees the same state
    reread = FailureLedger(str(tmp_path / "run" / "campaign_state.json"))
    assert reread.attempts(crash_cell) == 2
    assert "HardFault" in reread.failed_cells()[crash_cell]["error"]


def test_failure_ledger_quarantines_corrupt_state(tmp_path):
    path = str(tmp_path / "campaign_state.json")
    led = FailureLedger(path)
    led.record_failure("k@default@t", RuntimeError("boom"), backoff=0.25)
    assert FailureLedger(path).failed_cells()["k@default@t"]["attempts"] == 1
    with open(path, "w") as f:
        f.write("{ not json")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fresh = FailureLedger(path)
    assert len(fresh) == 0
    assert os.path.exists(path + ".quarantine")
    assert any("quarantine" in str(x.message) for x in w)
    with open(path + ".quarantine") as f:      # the bad payload survives
        assert f.read() == "{ not json"
    os.replace(path + ".quarantine", path)
    with pytest.raises(RuntimeError, match="corrupt campaign ledger"):
        FailureLedger(path, strict=True)


# ---------------------------------------------------------------------------
# memo warm-start quarantine (satellite)
# ---------------------------------------------------------------------------

def test_memo_warm_start_quarantines_corrupt_payload(tmp_path):
    path = str(tmp_path / "measure_memo.pkl")
    memo = SharedMeasureMemo()
    memo.view([], owner="k")[b"key"] = 42.0
    memo.save(path)
    fresh = SharedMeasureMemo()
    assert warm_start_memo(fresh, path) == 1          # healthy roundtrip

    with open(path, "wb") as f:
        f.write(b"\x80\x04 truncated garbage")
    target = SharedMeasureMemo()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert warm_start_memo(target, path) == 0
    assert len(target) == 0
    assert os.path.exists(path + ".quarantine")
    assert any("quarantine" in str(x.message) for x in w)
    assert warm_start_memo(target, path) == 0         # file gone: empty start

    # strict mode keeps the loud pre-campaign failure
    with open(path, "wb") as f:
        pickle.dump({"format": "something-else"}, f)
    with pytest.raises(MemoVersionError):
        warm_start_memo(SharedMeasureMemo(), path, strict=True)
    assert os.path.exists(path)                       # strict never renames


# ---------------------------------------------------------------------------
# cache quarantine + serve degradation (on_missing)
# ---------------------------------------------------------------------------

def _optimized_cache(tmp_path, stall_db, sub="cache"):
    session = OptimizationSession(
        strategy=make_budgeted_strategy("random", timesteps=16,
                                        episode_length=8),
        cache_dir=str(tmp_path / sub), stall_db=stall_db, verify_seeds=2)
    session.optimize(OptimizeRequest(kernel="rmsnorm"))
    return str(tmp_path / sub)


def test_resolve_schedule_quarantines_corrupt_cache(tmp_path, stall_db):
    cache_dir = _optimized_cache(tmp_path, stall_db)
    kdir = os.path.join(cache_dir, "tpu-tsass-v1", "rmsnorm")
    idx = os.path.join(kdir, "index.json")

    # corrupt index, intact sidecar: the index is quarantined with a
    # warning and the artifact still resolves through the v1 fallback
    with open(idx, "w") as f:
        f.write("not json at all")
    cache = ScheduleCache(cache_dir)
    with pytest.raises(CacheVersionError):
        cache.lookup_best("rmsnorm")          # direct lookups stay loud
    with pytest.warns(UserWarning, match="quarantined"):
        art = resolve_schedule(ScheduleCache(cache_dir), "rmsnorm",
                               on_missing="baseline")
    assert art is not None and art.kernel == "rmsnorm"
    assert os.path.exists(idx + ".quarantine") and not os.path.exists(idx)

    # now also corrupt the sidecar: quarantined (taking its .tsass twin),
    # nothing loadable remains -> -O3 baseline fallback, counted
    sidecars = [f for f in os.listdir(kdir) if f.endswith(".json")]
    assert sidecars
    for f in sidecars:
        with open(os.path.join(kdir, f), "w") as fh:
            fh.write('{"version": 999}')
    cache = ScheduleCache(cache_dir)
    with pytest.warns(UserWarning, match="quarantined"):
        art = resolve_schedule(cache, "rmsnorm", on_missing="baseline")
    assert art is None
    assert cache.fallbacks == 1 and cache.stats()["quarantined"] >= 2
    left = os.listdir(kdir)
    assert all(f.endswith(".quarantine") for f in left) and left

    # strict mode: missing -> FileNotFoundError; corrupt -> loud raise
    with pytest.raises(FileNotFoundError, match="on_missing"):
        resolve_schedule(ScheduleCache(cache_dir), "rmsnorm",
                         on_missing="raise")
    corrupt2 = _optimized_cache(tmp_path, stall_db, sub="cache2")
    idx2 = os.path.join(corrupt2, "tpu-tsass-v1", "rmsnorm", "index.json")
    with open(idx2, "w") as f:
        f.write("garbage")
    with pytest.raises(CacheVersionError):
        resolve_schedule(ScheduleCache(corrupt2), "rmsnorm",
                         on_missing="raise")
    assert os.path.exists(idx2)               # strict mode never renames
    with pytest.raises(ValueError, match="on_missing"):
        resolve_schedule(ScheduleCache(corrupt2), "rmsnorm",
                         on_missing="explode")


def test_serve_engine_on_missing_baseline_vs_strict(tmp_path, monkeypatch):
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import ServeEngine

    cfg = get_config("gemma3-1b", reduced=True)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    empty_cache = str(tmp_path / "empty_cache")
    os.makedirs(empty_cache, exist_ok=True)

    calls = {"run": 0, "time": 0}
    real_run, real_time = Machine.run, Machine.time
    monkeypatch.setattr(Machine, "run",
                        lambda *a, **k: calls.__setitem__("run", 1) or
                        real_run(*a, **k))
    monkeypatch.setattr(Machine, "time",
                        lambda *a, **k: calls.__setitem__("time", 1) or
                        real_time(*a, **k))

    # baseline mode: every kernel serves the -O3 baseline, counted, and
    # serving never touches a Machine
    engine = ServeEngine.from_config(cfg, params=jax.tree.map(jax.numpy.copy,
                                                              params),
                                     max_batch=2, max_seq=32,
                                     schedule_cache=empty_cache,
                                     on_missing="baseline")
    assert engine.plan and all(a is None for a in engine.plan.values())
    assert engine.counters["schedule_fallbacks"] == len(engine.plan) > 0
    req = engine.submit([3, 5, 7], max_new_tokens=4)
    engine.run()
    assert len(req.output) == 4
    assert calls == {"run": 0, "time": 0}     # zero Machine work at serve

    # strict mode refuses to start degraded
    with pytest.raises(FileNotFoundError, match="on_missing"):
        ServeEngine.from_config(cfg, params=params, max_batch=2, max_seq=32,
                                schedule_cache=empty_cache,
                                on_missing="raise")
