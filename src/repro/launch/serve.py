"""Serving launcher: ``python -m repro.launch.serve --arch gemma3-1b``.

The launcher is built around :class:`repro.serve.engine.ServeEngine`:
it spins up the continuous-batching engine, replays a seeded
Poisson-arrival trace at ``--qps`` across ``--tenants`` weighted
tenants, and reports p50/p99 latency, TTFT, tokens/s, the paged KV
pool's sharing and spills, the per-tenant fairness table, and the
resolved ``kernel@bucket [target]`` schedule plan (pure cache-index
lookups — no autotune at serve time).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import for_config


def _print_plan(engine) -> None:
    if not engine.plan:
        return
    print("[serve] resolved schedule plan:")
    for line in engine.plan_summary():
        print(f"[serve]   {line}")


def _print_fairness(engine) -> None:
    rows = engine.scheduler.fairness_table()
    cols = ["tenant", "weight", "token_budget", "admitted", "served_tokens",
            "in_flight_tokens", "queued", "vtime"]
    widths = {c: max(len(c), *(len(str(r[c])) for r in rows)) for c in cols}
    print("[serve] tenant fairness:")
    print("[serve]   " + "  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("[serve]   " + "  ".join(str(r[c]).ljust(widths[c])
                                       for c in cols))


def _serve(args, cfg) -> None:
    from repro.serve import ServeEngine, Tenant, TrafficConfig, run_load

    weights = ([float(w) for w in args.tenant_weights.split(",")]
               if args.tenant_weights else [1.0] * args.tenants)
    if len(weights) != args.tenants:
        raise SystemExit(f"--tenant-weights needs {args.tenants} values")
    tenants = [Tenant(f"t{i}", weight=w) for i, w in enumerate(weights)]

    model = for_config(cfg)
    params = model.init_model(cfg, jax.random.PRNGKey(0))
    max_seq = args.max_seq or (args.prompt_len + args.new_tokens + 8)
    engine = ServeEngine.from_config(
        cfg, params=params, max_batch=args.max_batch, max_seq=max_seq,
        block_size=args.block_size, kv_blocks=args.kv_blocks,
        tenants=tenants, schedule_cache=args.schedule_cache,
        debug_invariants=args.debug_invariants,
        on_missing="raise" if args.strict_schedules else "baseline")
    _print_plan(engine)
    if engine.counters.get("schedule_fallbacks"):
        print(f"[serve] WARNING: {engine.counters['schedule_fallbacks']} "
              f"kernel(s) serving the -O3 baseline (no cached schedule); "
              f"use --strict-schedules to refuse degraded serving")

    traffic = TrafficConfig(
        qps=args.qps, n_requests=args.requests, n_tenants=args.tenants,
        prompt_len=(max(2, args.prompt_len // 2), args.prompt_len),
        output_len=(max(1, args.new_tokens // 2), args.new_tokens),
        vocab=cfg.vocab, seed=0,
        prefix_tokens=args.prefix_tokens, prefix_groups=args.prefix_groups)
    print(f"[serve] {args.arch}: {args.requests} requests @ {args.qps} qps, "
          f"{args.tenants} tenants, max_batch={args.max_batch}, "
          f"max_seq={max_seq}, kv_blocks={engine.pool.num_blocks}"
          + (f", shared prefix {args.prefix_tokens} tokens x "
             f"{args.prefix_groups} groups" if args.prefix_tokens else ""))
    report = run_load(engine, traffic)
    print(f"[serve] tokens/s {report['tokens_per_s']:.1f}  "
          f"p50 {report['latency_p50_s'] * 1e3:.1f}ms  "
          f"p99 {report['latency_p99_s'] * 1e3:.1f}ms  "
          f"ttft p50 {report['ttft_p50_s'] * 1e3:.1f}ms  "
          f"completed {report['completed']}/{report['n_requests']} "
          f"(truncated {report['truncated']})")
    eng = report["stats"]["engine"]
    print(f"[serve] engine: {eng['passes']} passes, lane utilization "
          f"{eng['lane_utilization']:.2f}, {eng['stalls']} stalls, "
          f"{eng['preemptions']} preemptions")

    def per(key: str, n: int) -> float:      # ms of counter ``key`` each
        return eng[key] / n * 1e3 if n else float("nan")

    print(f"[serve] stages: queue wait "
          f"{per('queue_wait_s', eng['admissions']):.2f} ms, prefill "
          f"{per('prefill_s', eng['first_tokens']):.2f} ms a request; host "
          f"{per('host_s', eng['passes']):.2f} ms, sync "
          f"{per('sync_s', eng['passes']):.2f} ms a pass")
    pool = report["stats"]["pool"]
    print(f"[serve] paged kv: max_active {eng['max_active']}, "
          f"prefix hits {eng['prefix_hits']} "
          f"({pool['shared_tokens_reused']} tokens reused), "
          f"cow forks {eng['cow_forks']}, "
          f"spills {eng['preempt_spills']}, "
          f"high water {pool['high_water_blocks']}/"
          f"{engine.pool.num_blocks} blocks, "
          f"peak kv {engine.peak_kv_bytes() / 1e6:.1f} MB")
    _print_fairness(engine)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=list(ARCHS))
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--schedule-cache", default=None, metavar="DIR",
                    help="resolve the arch's RL-optimized kernel schedules "
                         "from this cache (index lookup only, no autotune)")
    ap.add_argument("--strict-schedules", action="store_true",
                    help="refuse to serve kernels without a cached schedule "
                         "(on_missing='raise'); default degrades them to "
                         "the -O3 baseline with a warning")
    ap.add_argument("--qps", type=float, default=8.0,
                    help="offered Poisson arrival rate (requests/s)")
    ap.add_argument("--tenants", type=int, default=2,
                    help="number of weighted-fair tenants")
    ap.add_argument("--tenant-weights", default=None, metavar="W1,W2,...",
                    help="per-tenant WFQ weights (default: all 1.0)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="engine slots (concurrent requests per pass)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="KV pool blocks; below slots*blocks_per_slot "
                         "oversubscribes the pool (stall/preempt pressure)")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="cache positions per slot")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV pool block granularity (tokens)")
    ap.add_argument("--requests", type=int, default=32,
                    help="trace length for the load generator")
    ap.add_argument("--prefix-tokens", type=int, default=0,
                    help="prepend a shared system prompt of this many tokens "
                         "to every request (Zipf-distributed over "
                         "--prefix-groups distinct prefixes)")
    ap.add_argument("--prefix-groups", type=int, default=4,
                    help="distinct shared prefixes for --prefix-tokens")
    ap.add_argument("--debug-invariants", action="store_true",
                    help="run KVBlockPool.check() every engine tick")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="longest prompt in the trace (shortest is half)")
    ap.add_argument("--new-tokens", type=int, default=16,
                    help="longest answer in the trace (shortest is half)")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=not args.full)
    if cfg.family == "encdec":
        raise SystemExit("use examples/serve_decode.py for the enc-dec arch")
    _serve(args, cfg)


if __name__ == "__main__":
    main()
