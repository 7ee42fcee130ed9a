"""Serve-engine load benchmark: latency/throughput vs offered QPS.

Replays the same seeded Poisson trace (mixed prompt/output lengths, two
weighted tenants) against the continuous-batching ``ServeEngine`` and
against the same engine degraded to static gang batching, at each offered
QPS — with and without an RL-optimized schedule plan resolved from a
freshly tuned cache (nearest-bucket index lookups; the plan axis records
the fleet's mean kernel speedup and the modeled tokens/s it implies,
since the simulated machine is not in the CPU serve loop).

Reported per row: delivered tokens/s, p50/p99 end-to-end latency, p50
TTFT, stall/preemption counts.  The suite asserts the continuous-batching
acceptance criterion: at the saturating QPS point, continuous admission
beats gang admission on delivered tokens/s.  In the CI ``--fast`` smoke
set, so the numbers land in ``BENCH_ci.json`` every run.

The ``serve_paged`` cell drains a seeded shared-prefix burst (loadgen
``prefix_tokens``) through the engine at a *fixed* ``kv_blocks`` budget,
recording delivered tokens/s, peak KV bytes, and the concurrency
high-water mark (``tests/test_serve_paged.py`` holds the engine to that
high-water mark).  Pool invariants (``KVBlockPool.check``) run every
tick of this cell.
"""

import tempfile
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.configs import get_config
from repro.core import build_stall_table
from repro.models import lm
from repro.sched import OptimizationSession, make_budgeted_strategy
from repro.sched.session import OptimizeRequest
from repro.serve import (ServeEngine, Tenant, TrafficConfig, poisson_trace,
                         run_load)

ARCH = "qwen1.5-4b"
QPS_SWEEP = (4.0, 256.0)         # trickle vs saturating offered load
N_REQUESTS = 24
MAX_BATCH = 4
MAX_SEQ = 48
PLAN_KERNELS = ("rmsnorm", "softmax")


def _build_plan_cache(timesteps: int) -> str:
    """Tune a small kernel fleet into a throwaway cache dir (greedy
    budgeted strategy — the bench measures serving, not search)."""
    cache_dir = tempfile.mkdtemp(prefix="bench_serve_cache_")
    session = OptimizationSession(
        stall_db=build_stall_table(), cache_dir=cache_dir,
        strategy=make_budgeted_strategy("greedy", timesteps=timesteps,
                                        episode_length=8))
    session.optimize_many([OptimizeRequest(kernel=k, force=True)
                           for k in PLAN_KERNELS], max_workers=2)
    return cache_dir


def _mean_plan_speedup(engine) -> float:
    arts = [a for a in engine.plan.values() if a is not None]
    if not arts:
        return 1.0
    return sum(a.speedup for a in arts) / len(arts)


# Capacity cell: a shared-system-prompt burst on a tight fixed block
# budget.  Requests share the 3 prefix blocks and add ~1 private block
# each.
PAGED_PREFIX = 24                # 3 full blocks at block_size=8
PAGED_KV_BLOCKS = 8
PAGED_BURST = 12


def _own(params):
    """A copy for one engine: an engine consumes the weights it is given."""
    return jax.tree.map(jnp.copy, params)


def _paged_capacity_cell(cfg, params):
    traffic = TrafficConfig(
        qps=1000.0, n_requests=PAGED_BURST, n_tenants=1,
        prompt_len=(2, 4), output_len=(4, 8), vocab=cfg.vocab, seed=11,
        prefix_tokens=PAGED_PREFIX, prefix_groups=1)
    burst = poisson_trace(traffic, ["t0"])
    warm_prompt = burst[0].prompt[:PAGED_PREFIX + 1]

    engine = ServeEngine.from_config(
        cfg, params=_own(params), max_batch=8, max_seq=MAX_SEQ,
        block_size=8, kv_blocks=PAGED_KV_BLOCKS, tenants=[Tenant("t0")],
        debug_invariants=True)
    # Warm the prefix cache the way a real deployment does: one resident
    # request whose prefill registers the system prompt, then the burst
    # admits against it.
    warm = engine.submit(warm_prompt, 8, tenant="t0")
    for _ in range(200):
        if warm.first_token_time is not None:
            break
        engine.step()
    assert warm.first_token_time is not None, "warm-up never prefilled"
    reqs = [engine.submit(a.prompt, a.max_new_tokens, tenant="t0")
            for a in burst]
    t0 = time.monotonic()
    engine.run(max_steps=20_000)
    wall = time.monotonic() - t0
    assert all(r.done for r in reqs)
    eng = engine.stats()["engine"]
    toks = sum(len(r.output) for r in reqs)
    print(f"# paged capacity: {eng['max_active']} concurrent seqs at "
          f"{PAGED_KV_BLOCKS} blocks")
    return [("serve_paged", ARCH, PAGED_KV_BLOCKS, PAGED_BURST, PAGED_PREFIX,
             round(toks / wall, 2), toks, eng["max_active"],
             eng["peak_kv_bytes"], eng["kv_bytes_allocated"],
             eng["passes"], eng["stalls"], eng["preemptions"],
             eng["prefix_hits"], eng["cow_forks"], eng["preempt_spills"])]


def run(timesteps: int = 48):
    cfg = get_config(ARCH, reduced=True)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    tenants = lambda: [Tenant("t0", weight=3.0), Tenant("t1", weight=1.0)]
    plan_cache = _build_plan_cache(timesteps)

    rows = []
    sat = {}      # (admission, plans) -> tokens/s at the saturating QPS
    for qps in QPS_SWEEP:
        # Wide output-length mix: the gang baseline holds every lane until
        # its longest member finishes, which is the waste continuous
        # admission exists to reclaim.
        traffic = TrafficConfig(qps=qps, n_requests=N_REQUESTS, n_tenants=2,
                                prompt_len=(2, 16), output_len=(2, 24),
                                vocab=cfg.vocab, seed=7)
        for admission in ("continuous", "gang"):
            for plans in (False, True):
                engine = ServeEngine.from_config(
                    cfg, params=_own(params), max_batch=MAX_BATCH,
                    max_seq=MAX_SEQ,
                    block_size=8, tenants=tenants(), admission=admission,
                    schedule_cache=plan_cache if plans else None)
                report = run_load(engine, traffic)
                speedup = _mean_plan_speedup(engine) if plans else 1.0
                eng = report["stats"]["engine"]
                if qps == max(QPS_SWEEP):
                    sat[(admission, plans)] = report["tokens_per_s"]
                rows.append((
                    "serve_load", ARCH, qps, admission,
                    "plan" if plans else "baseline", report["n_requests"],
                    round(report["tokens_per_s"], 2),
                    round(report["latency_p50_s"] * 1e3, 2),
                    round(report["latency_p99_s"] * 1e3, 2),
                    round(report["ttft_p50_s"] * 1e3, 2),
                    round(speedup, 4),
                    round(report["tokens_per_s"] * speedup, 2),
                    eng["stalls"], eng["preemptions"],
                    round(eng["lane_utilization"], 3)))

    # Acceptance: continuous batching beats static gang batching on
    # delivered tokens/s once the offered load saturates the engine.
    for plans in (False, True):
        cont, gang = sat[("continuous", plans)], sat[("gang", plans)]
        print(f"# saturation ({'plan' if plans else 'baseline'}): "
              f"continuous {cont:.1f} tok/s vs gang {gang:.1f} tok/s "
              f"({cont / gang:.2f}x)")
        assert cont > gang, (
            f"continuous batching did not beat static batching at "
            f"saturation: {cont:.1f} vs {gang:.1f} tok/s (plans={plans})")

    emit(rows, header=("bench", "arch", "qps", "admission", "plans",
                       "n_requests", "tokens_per_s", "latency_p50_ms",
                       "latency_p99_ms", "ttft_p50_ms", "plan_speedup",
                       "modeled_tokens_per_s", "stalls", "preemptions",
                       "lane_utilization"))

    paged_rows = _paged_capacity_cell(cfg, params)
    emit(paged_rows, header=("bench", "arch", "kv_blocks", "n_requests",
                             "prefix_tokens", "tokens_per_s", "tokens",
                             "max_active", "peak_kv_bytes",
                             "kv_bytes_allocated", "passes", "stalls",
                             "preemptions", "prefix_hits", "cow_forks",
                             "preempt_spills"))
    return rows + paged_rows
