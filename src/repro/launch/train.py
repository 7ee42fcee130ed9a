"""Training launcher: ``python -m repro.launch.train --arch stablelm-3b``.

On this CPU container it trains the reduced config end-to-end (the ~100M /
few-hundred-step driver lives in examples/train_lm.py); on a real cluster
the same entrypoint takes --full --mesh to pjit over the production mesh.

``--pipe S`` (or ``pipeline_stages`` on the config) builds a host mesh
with a ``pipe`` axis and switches the Trainer onto the shard_map pipeline
step (``--pipe-schedule gpipe|1f1b`` picks the micro-op timetable);
``--model M`` composes a tensor-parallel ``model`` axis into the pipeline
stages; ``--pods P`` adds a ``pod`` axis whose gradient reduction — when
the shard_map step is active, i.e. ``--pipe >= 2`` — runs compressed
(bf16 + error feedback) unless ``--no-compress-pod-grads``.  With
``--pods`` alone the jit/GSPMD path still data-parallelizes over ``pod``,
in plain fp32.
Multi-device CPU smoke needs
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` exported before
launch.
"""

from __future__ import annotations

import argparse
import warnings

from repro.configs import ARCHS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.train import TrainConfig, Trainer


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b", choices=list(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation chunks (jit step)")
    ap.add_argument("--pipe", type=int, default=0,
                    help="pipeline stages (0 = cfg.pipeline_stages; > 1 "
                         "builds a `pipe` mesh axis + shard_map step)")
    ap.add_argument("--pipe-microbatches", type=int, default=0,
                    help="pipeline microbatches "
                         "(0 = cfg.pipeline_microbatches)")
    ap.add_argument("--pipe-schedule", default=None,
                    help="pipeline micro-op schedule: gpipe | 1f1b "
                         "(default cfg.pipeline_schedule)")
    ap.add_argument("--model", type=int, default=1,
                    help="tensor-parallel `model` axis size (> 1 composes "
                         "TP into the pipeline stages)")
    ap.add_argument("--pods", type=int, default=1,
                    help="pod axis size (> 1 = multi-pod gradient reduction)")
    ap.add_argument("--no-compress-pod-grads", action="store_true",
                    help="plain fp32 psum over `pod` instead of bf16+EF")
    ap.add_argument("--full", action="store_true",
                    help="full (not reduced) config — cluster use")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=not args.full)
    pipe = args.pipe or cfg.pipeline_stages
    overrides = {}
    if pipe:
        overrides["pipeline_stages"] = pipe
    if args.pipe_microbatches:
        overrides["pipeline_microbatches"] = args.pipe_microbatches
    if args.pipe_schedule:
        overrides["pipeline_schedule"] = args.pipe_schedule
    if args.no_compress_pod_grads:
        overrides["compress_pod_grads"] = False
    if overrides:
        cfg = cfg.replace(**overrides)

    # validate the schedule name eagerly — a typo should die here with the
    # valid choices, not deep inside step construction
    from repro.dist.pipeline import SCHEDULES
    if cfg.pipeline_schedule not in SCHEDULES:
        ap.error(f"--pipe-schedule {cfg.pipeline_schedule!r} is not a valid "
                 f"pipeline schedule; choose from {sorted(SCHEDULES)}")

    mesh = None
    if pipe > 1 or args.pods > 1 or args.model > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.model, pipe=max(pipe, 1),
                              pods=args.pods)
        note = ""
        if args.pods > 1:
            # the compressed reduction lives in the shard_map pipeline
            # step, which the Trainer only selects for pipe >= 2 — say so
            # instead of claiming compression the jit path won't do
            if args.no_compress_pod_grads:
                pod_grads = "fp32 psum"
            elif pipe > 1:
                pod_grads = "bf16+EF compressed"
            else:
                pod_grads = ("fp32 psum — compressed reduction needs the "
                             "shard_map step, pass --pipe >= 2")
            note = f" (pod grads: {pod_grads})"
        print(f"[train] mesh: {dict(mesh.shape)}{note}")
        if pipe > 1:
            # surface the gcd clamp the Trainer will apply instead of
            # letting a non-dividing --pipe-microbatches remap silently
            from repro.train.loop import pipeline_microbatch_clamp
            n_micro, local_b = pipeline_microbatch_clamp(
                cfg.pipeline_microbatches, args.global_batch, mesh)
            if n_micro != cfg.pipeline_microbatches:
                warnings.warn(
                    f"--pipe-microbatches {cfg.pipeline_microbatches} does "
                    f"not divide the per-shard batch {local_b}; the Trainer "
                    f"will clamp it to {n_micro}", stacklevel=1)

    tcfg = TrainConfig(steps=args.steps, seq_len=args.seq_len,
                       global_batch=args.global_batch, lr=args.lr,
                       ckpt_dir=args.ckpt_dir,
                       num_microbatches=args.microbatches)
    trainer = Trainer(cfg, tcfg, mesh=mesh)

    def on_straggler(step, dt):
        print(f"[train] straggler watermark: step {step} took {dt:.2f}s")

    trainer.straggler_hook = on_straggler
    log = trainer.run()
    for row in log[:: max(1, len(log) // 10)]:
        print(f"[train] step={row['step']:5d} loss={row['loss']:.4f} "
              f"gnorm={row['grad_norm']:.3f} {row['seconds']*1e3:.0f}ms")
    print(f"[train] final loss: {log[-1]['loss']:.4f} "
          f"(start {log[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
