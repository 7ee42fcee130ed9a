#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, through its entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the distributed train step

One chip, three phases:

* kernels: every built-in Pallas kernel once, compiled for the chip
  (``interpret=False``), on inputs of several blocks per grid axis,
  against its oracle in ``repro.kernels.ref``;
* serve: qwen1.5-4b at its published widths (40 layers, d_model 2560,
  vocab 151936, bf16, random weights from a fixed seed) behind
  ``ServeEngine.from_config`` (paged KV cache), driven by
  ``serve.loadgen.run_load`` with two tenants and shared prompt prefixes,
  then one request repeated so that prefix sharing and the copy-on-write
  page copy run; every run of that prompt must yield the same tokens, and
  finished requests are checked token by token against the model's full
  forward pass;
* optimize: ``OptimizationSession().optimize`` on one kernel with a few
  hundred PPO timesteps, so the policy network runs on the chip.

Four chips: stablelm-3b at published widths (24 of its 32 layers) trains
3 steps through ``Trainer`` with the GSPMD step on a (data=2, model=2)
mesh and 3 steps with the shard_map pipeline step on a (pipe=2, data=1,
model=2) mesh, from the same seed on the same batches; the losses must
agree and all four chips must hold state.

Every phase runs in this one process (a chip belongs to one process).
Lines before the last are smoke facts, not speeds.  The last line is one
JSON object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
The run fails -- ``ok`` false, exit code 1 -- when JAX finds no TPU, when
the device count is not ``--chips``, and when any phase raises or any
check misses.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0

# serve phase: qwen1.5-4b, one chip.  Parameters (7.1 GB) plus the page
# pool held about three times by the undonated step (3 x 1.7 GB) fit the
# chip's 16 GB at max_seq 512 with a block for every slot position.
SERVE_ARCH = "qwen1.5-4b"
MAX_BATCH, MAX_SEQ, BLOCK = 8, 512, 16
N_REQUESTS, N_TENANTS, QPS = 12, 2, 2.0
PREFIX_TOKENS, PREFIX_GROUPS = 120, 2     # 7.5 blocks of shared prompt
SUFFIX_TOKENS = (8, 136)                  # prompts of 128..256 tokens
NEW_TOKENS = 32
# Forward check: at every generated position, forward's logit for the
# engine's token must be within LOGIT_TOL_SIGMA standard deviations (of
# that row's logits) of forward's largest logit.  The engine (one token
# per pass through a paged bf16 cache) and forward (the whole sequence at
# once) round differently in bf16 at each of 40 layers, and a deep
# random-weight network amplifies that: near-ties flip.  Sound runs gave
# up to 0.54 sigma on a v5e.  In a 40-layer, d_model 256 rehearsal on the
# CPU (sound: 0.31) lanes reading each other's block tables gave 5.95 and
# block ids off by one 3.40, but a skipped copy-on-write page copy only
# 0.76: the replay check below catches that one.
LOGIT_TOL_SIGMA = 1.0

# optimize phase
OPT_KERNEL, PPO_TIMESTEPS = "rmsnorm", 512

# train phase: stablelm-3b, four chips.  Depth is cut from 32 to 24
# layers: compiled for a described v5e 2x2, the pipeline step needs per
# chip 7.6 GB of state plus 12.0 GB of temporaries at 32 layers and
# 6.0 + 9.2 GB at 24 (the GSPMD step 5.1 + 1.5 GB), against 16 GiB.
TRAIN_ARCH, TRAIN_LAYERS = "stablelm-3b", 24
TRAIN_STEPS, SEQ_LEN, GLOBAL_BATCH = 3, 256, 8
# Relative-gap bounds between the two runs' losses, at step 1 and at any
# step.  The two steps partition one bf16 computation differently
# (all-reduce vs pipeline stages and tensor-parallel psums, whole batch vs
# 4 microbatches), so sums round in different orders.  At step 1 both see
# the same parameters and batch and only that rounding separates them;
# Adam's normalized updates then amplify it.  On a v5e 2x2 the sound gaps
# were 2.4e-4 at step 1 and 3.3e-3 at worst; a batch whose second half
# repeats its first moved the step-1 loss by 7.6e-3.  Gradients left
# unsummed over `model` or over `pipe` stayed inside both bounds: at this
# random init three steps of Adam barely move the loss.  The step-1 grad
# norm is printed, not bounded: it was 4.9e-2 apart in the sound run and
# 6.7e-2 with the `model` sum missing.
STEP1_LOSS_RTOL, LOSS_RTOL = 1e-3, 1e-2
# no chip may hold more than this share of the bytes in use on all four
# (an even layout gives each about a quarter)
MAX_DEVICE_SHARE = 0.5


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernel_phase(case_list) -> None:
    """Each case once on the device against its oracle at "highest"
    matmul precision; fails on the first miss."""
    import jax
    from repro.kernels import cases

    key = jax.random.PRNGKey(SEED)
    for case in case_list:
        args = case.make(key)
        got = jax.jit(case.run)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(case.ref)(*args)
        err = cases.relative_error(got, want)
        log(f"kernel {case.name}: inputs {[tuple(a.shape) for a in args]}, "
            f"max error {err:.3e} of max |ref| (bound {cases.REL_TOL})")
        if not err <= cases.REL_TOL:
            raise AssertionError(f"kernel {case.name}: relative error {err} "
                                 f"> {cases.REL_TOL}")


def logit_gaps(fwd, params, req):
    """Per generated token: (forward's top logit - forward's logit for the
    engine's token) / std of that row's logits."""
    import jax.numpy as jnp
    import numpy as np

    seq = list(req.prompt) + list(req.output)
    tokens = np.zeros((1, MAX_SEQ), np.int32)
    tokens[0, :len(seq)] = seq
    p, n = len(req.prompt), len(req.output)
    # position t's logits predict token t + 1; padding after the sequence
    # cannot reach earlier positions under the causal mask
    rows = fwd(params, jnp.asarray(tokens))[0, p - 1:p - 1 + n]
    rows = np.asarray(rows.astype(jnp.float32))
    chosen = rows[np.arange(n), np.asarray(req.output)]
    return (rows.max(axis=1) - chosen) / rows.std(axis=1)


def serve_phase(cfg, n_requests: int = N_REQUESTS) -> None:
    import jax
    import numpy as np
    from repro.models import lm
    from repro.serve import ServeEngine, Tenant, TrafficConfig, run_load

    t0 = time.monotonic()
    params = lm.init_model(cfg, jax.random.PRNGKey(SEED))
    engine = ServeEngine.from_config(
        cfg, params=params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
        block_size=BLOCK, kv_blocks=MAX_BATCH * MAX_SEQ // BLOCK,
        tenants=[Tenant(f"t{i}") for i in range(N_TENANTS)])
    jax.block_until_ready((engine.params, engine.caches))
    log(f"serve {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab}, {cfg.dtype}; set-up {time.monotonic() - t0:.1f}s")

    # warm-up request: compiles the step before the paced trace starts
    t0 = time.monotonic()
    engine.generate([1] * (BLOCK + 1), 2, tenant="t0")
    log(f"serve compile + warm-up {time.monotonic() - t0:.1f}s")

    traffic = TrafficConfig(
        qps=QPS, n_requests=n_requests, n_tenants=N_TENANTS,
        prompt_len=SUFFIX_TOKENS, output_len=(NEW_TOKENS, NEW_TOKENS),
        vocab=cfg.vocab, seed=SEED, prefix_tokens=PREFIX_TOKENS,
        prefix_groups=PREFIX_GROUPS)
    report = run_load(engine, traffic)
    eng = report["stats"]["engine"]
    log(f"serve load: completed {report['completed']}/{report['n_requests']}"
        f" (truncated {report['truncated']}), tokens {report['tokens']}, "
        f"prefix hits {eng['prefix_hits']}, passes {eng['passes']}, "
        f"preemptions {eng['preemptions']}")
    if report["completed"] != n_requests or report["truncated"]:
        raise AssertionError(f"not every request finished: {report}")
    loaded = list(engine.finished[1:])

    # The same prompt again while its first copy decodes: the second maps
    # the first's pages, including the partial last prompt block, and its
    # first write there forks that block (copy-on-write).
    prompt = list(loaded[0].prompt)
    if len(prompt) % BLOCK == 0:
        prompt = prompt[:-1]          # the partial last block is the shared
    first = engine.submit(prompt, NEW_TOKENS, tenant="t0")
    while first.first_token_time is None:
        engine.step()
    second = engine.submit(prompt, NEW_TOKENS, tenant="t1")
    engine.run()
    eng = engine.stats()["engine"]
    log(f"serve shared prompt of {len(prompt)} tokens: prefix hits "
        f"{eng['prefix_hits']}, copy-on-write forks {eng['cow_forks']}")
    if not (first.done and second.done and len(second.output) == NEW_TOKENS):
        raise AssertionError("the repeated request did not finish")
    if eng["cow_forks"] < 1 or eng["prefix_hits"] < 1:
        raise AssertionError(f"prefix sharing / copy-on-write did not run: "
                             f"{eng}")
    # Greedy decoding is deterministic, and a lane's arithmetic depends on
    # neither its lane nor its neighbours, so every run of one prompt
    # yields the same tokens.  The second copy read pages the first wrote,
    # and the first wrote on into the block it forked: a skipped or
    # misdirected page copy shows here, where the forward check's
    # tolerance may not see it.
    runs = [first, second] + [r for r in loaded if list(r.prompt) == prompt]
    if any(r.output != first.output for r in runs):
        raise AssertionError(f"{len(runs)} runs of one prompt disagree: "
                             f"{[r.output for r in runs]}")
    log(f"serve replay: {len(runs)} runs of one prompt, identical tokens")

    # The engine consumed its weights in laying them out per layer: free
    # it, and make the seed's stacked weights again for the forward pass.
    del engine, params
    params = lm.init_model(cfg, jax.random.PRNGKey(SEED))
    fwd = jax.jit(functools.partial(lm.forward, cfg=cfg))
    worst = 0.0
    for req in (loaded[0], loaded[-1], first, second):
        gaps = logit_gaps(fwd, params, req)
        worst = max(worst, float(gaps.max()))
        log(f"serve request {req.id}: {len(req.output)} tokens, logit gap "
            f"worst {gaps.max():.4f} mean {gaps.mean():.4f} sigma, exact "
            f"argmax at {int(np.sum(gaps == 0))}/{len(gaps)}")
    log(f"serve forward check: worst logit gap {worst:.4f} sigma "
        f"(bound {LOGIT_TOL_SIGMA})")
    if not worst <= LOGIT_TOL_SIGMA:
        raise AssertionError(f"engine tokens disagree with forward: gap "
                             f"{worst} > {LOGIT_TOL_SIGMA} sigma")


def optimizer_phase(cache_dir: str) -> None:
    import jax
    from repro.core.ppo import PPOConfig
    from repro.kernels import get_kernel
    from repro.sched import OptimizationSession, OptimizeRequest

    t0 = time.monotonic()
    ppo = PPOConfig(total_timesteps=PPO_TIMESTEPS, num_envs=8, num_steps=32,
                    episode_length=16, seed=SEED)
    res = OptimizationSession(cache_dir=cache_dir).optimize(OptimizeRequest(
        kernel=OPT_KERNEL, config=get_kernel(OPT_KERNEL).configs[0],
        ppo=ppo, force=True))
    art = res.artifact
    policy = {d.platform for leaf in jax.tree.leaves(res.game.params)
              for d in leaf.devices()}
    log(f"optimize {OPT_KERNEL}: {PPO_TIMESTEPS} PPO timesteps, "
        f"{len(res.stats)} updates, policy on {sorted(policy)}, "
        f"{art.baseline_cycles:.0f} -> {art.optimized_cycles:.0f} simulated "
        f"cycles; {time.monotonic() - t0:.1f}s")
    if not res.stats or art.optimized_cycles > art.baseline_cycles:
        raise AssertionError(f"optimizer phase produced no valid schedule: "
                             f"{len(res.stats)} updates, {art}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def train_metrics(cfg, mesh):
    """``Trainer(cfg, ..., mesh=mesh).run()``'s (loss, grad norm) per step:
    the GSPMD step on a mesh without ``pipe``, the shard_map pipeline step
    with it.  Fails when one chip holds most of the bytes in use, which
    would show the state placed whole on one chip."""
    import jax
    from repro.train import TrainConfig, Trainer

    t0 = time.monotonic()
    trainer = Trainer(cfg, TrainConfig(
        steps=TRAIN_STEPS, seq_len=SEQ_LEN, global_batch=GLOBAL_BATCH,
        lr=3e-4, warmup=0, seed=SEED), mesh=mesh)
    steps = [(m["loss"], m["grad_norm"]) for m in trainer.run()]
    jax.block_until_ready(trainer.state)
    used = [d.memory_stats()["bytes_in_use"] for d in mesh.devices.flat]
    name = "pipeline" if trainer.use_pipeline else "gspmd"
    log(f"train {name} on {dict(mesh.shape)}: (loss, grad norm) per step "
        f"{steps}; {time.monotonic() - t0:.1f}s with compile; bytes in use "
        f"per device {used}")
    if max(used) > MAX_DEVICE_SHARE * sum(used):
        raise AssertionError(f"train {name}: one device holds "
                             f"{max(used)} of {sum(used)} bytes")
    del trainer
    gc.collect()
    return steps


def metric_gaps(ref, got):
    """Relative gaps between two runs' per-step (loss, grad norm): (step-1
    loss, step-1 grad norm, worst loss over all steps)."""
    def rel(a, b):
        return abs(a - b) / abs(a)
    return (rel(ref[0][0], got[0][0]), rel(ref[0][1], got[0][1]),
            max(rel(a[0], b[0]) for a, b in zip(ref, got)))


def train_phase(cfg) -> None:
    from repro.launch.mesh import make_host_mesh

    log(f"train {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"batch {GLOBAL_BATCH} x {SEQ_LEN} tokens, {TRAIN_STEPS} steps")
    gspmd = train_metrics(cfg, make_host_mesh(model=2))
    pipe = train_metrics(cfg, make_host_mesh(model=2, pipe=2))
    step1, gnorm, worst = metric_gaps(gspmd, pipe)
    log(f"train agreement: relative gaps step-1 loss {step1:.3e} (bound "
        f"{STEP1_LOSS_RTOL}), worst loss {worst:.3e} (bound {LOSS_RTOL}), "
        f"step-1 grad norm {gnorm:.3e}")
    if not (step1 <= STEP1_LOSS_RTOL and worst <= LOSS_RTOL):
        raise AssertionError(f"GSPMD {gspmd} vs pipeline {pipe}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve, kernels and optimizer; 4: the GSPMD vs "
                         "pipeline train-step comparison only")
    args = ap.parse_args(argv)

    device = None
    try:
        from repro.launch.compile_cache import use_compile_cache
        cache = use_compile_cache()
        import jax

        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        log(f"device {device}; compile cache {cache}")
        if device["platform"] != "tpu":
            raise RuntimeError(f"no TPU: JAX found {device}")
        if device["count"] != args.chips:
            raise RuntimeError(f"--chips {args.chips} needs {args.chips} "
                               f"devices, JAX found {device['count']}")

        from repro.configs import get_config
        if args.chips == 4:
            train_phase(get_config(TRAIN_ARCH).replace(n_layers=TRAIN_LAYERS))
        else:
            from repro.kernels.cases import kernel_cases
            kernel_phase(kernel_cases())
            serve_phase(get_config(SERVE_ARCH))
            gc.collect()
            optimizer_phase(os.path.join(ROOT, ".repro_cache"))
            peak = devices[0].memory_stats().get("peak_bytes_in_use")
            log(f"peak device bytes {peak}")
    except Exception:
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
