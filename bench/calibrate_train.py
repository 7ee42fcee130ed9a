"""Readings that set a training cell's correctness limits, on the chip.

    python3 bench/calibrate_train.py --workload <cell> --seeds 6 \
        --control 3 --fault 1 --seconds 9 [--first-seed N]

For each seed, in one process, a whole run of the training driver
(``train.run``): the trainer with that seed's weights, the steps before
the window, a window of ``--seconds``, then the check of the first step
against the float32 reference.  The program's gaps are the *lower*
readings.  For the first ``--control`` seeds the control is judged too,
by the same check and limits: the reference with every matmul operand
rounded through float8 e4m3 (forward and backward) put in the program's
place; its gaps are the *upper* readings, and its ``correct`` has to come
out false.  With ``--fault 1``, on the first seed, a planted fault is read
too: a step whose gradients are not summed over ``data`` (each data shard
steps with its own rows' gradient, as shard 0 holds it), which has to
fail.  A step that leaves the state unchanged reads 1 in
``moment_gap_rel`` and ``update_gap_rel`` by their definition.  One JSON
line per seed, then a summary line.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

GAPS = ("loss_gap_rel", "grad_norm_gap_rel", "moment_gap_rel",
        "update_gap_rel")


def plant_unsummed(n_data: int) -> None:
    """Make every later ``make_train_step`` differentiate only the rows of
    data shard 0 (over ``n_data`` shards), divided by ``n_data``: the
    gradient that shard holds when nothing sums it over ``data``.  The
    loss it reports is still the whole batch's."""
    import jax
    from repro.models import lm
    from repro.train import step as step_mod

    def loss_fn(params, batch, cfg, mesh=None):
        logits = lm.forward(params, batch["tokens"], cfg, mesh=mesh)
        whole = step_mod.cross_entropy(logits, batch["labels"])
        rows = batch["labels"].shape[0] // n_data
        local = step_mod.cross_entropy(logits[:rows],
                                       batch["labels"][:rows]) / n_data
        loss = jax.lax.stop_gradient(whole - local) + local
        return loss, {"loss": loss}

    step_mod.loss_fn = loss_fn


def read_fault(ctx, ref: dict) -> dict:
    """The planted unsummed step's first step, judged against the sound
    run's reference ``ref`` (the same seed, weights and batch)."""
    from bench.drivers import train
    from repro.train import step as step_mod
    sound = step_mod.loss_fn
    plant_unsummed(int(ctx.mix["mesh"]["data"]))
    try:
        trainer = train.build(ctx)
        m = trainer.step()
        first = dict(m, **train.first_step_change(trainer.state, ctx.seed))
        batch = trainer.data.batch_at(0)
        del trainer
        gc.collect()
    finally:
        step_mod.loss_fn = sound
    v = train.judge(ctx.config, ctx.mix, ctx.seed, first, batch,
                    0 if train.finite(m) else 1, ctx.devices, ref=ref)
    return v["program"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=9.0)
    ap.add_argument("--first-seed", type=int, default=4_100_000_003)
    args = ap.parse_args(argv)

    from bench import counts, readers, run
    from bench.drivers import train
    from bench.drivers.common import Context
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    found = run.find_cell(run.load_benchmark(), args.workload)
    devices = run.devices_for(int(found["cell"]["chips"]))
    lines = []
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        ctx = Context(config=found["config"], mix=found["mix"], seed=seed,
                      seconds=args.seconds, trace=False, devices=devices,
                      t0=time.monotonic(),
                      peaks=counts.peaks_for(devices[0].device_kind))
        controls = ("fp8",) if k < args.control else ()
        rec = train.run(ctx, controls=controls)
        line = {"seed": seed, "correct": rec["correct"],
                "program": {n: c["value"] for n, c in rec["checks"].items()},
                "serve_tok_s": readers.serve_tokens_per_s(rec),
                "steps": rec["attempted"],
                "setup_s": rec["setup_s"],
                "memory_peak_bytes": rec["memory_peak_bytes"]}
        for p, v in rec["controls"].items():
            line[p] = {"correct": v["correct"],
                       **{n: c["value"] for n, c in v["checks"].items()}}
        if k == 0 and args.fault:
            v = read_fault(ctx, rec["reference"])
            line["unsummed"] = {"correct": v["correct"],
                                **{n: c["value"]
                                   for n, c in v["checks"].items()}}
        lines.append(line)
        print(json.dumps(line), flush=True)
    summary = {"seeds": len(lines),
               "all_correct": all(x["correct"] for x in lines),
               "controls_failed": sum(1 for x in lines
                                      if "fp8" in x and not x["fp8"]["correct"]),
               "controls": sum(1 for x in lines if "fp8" in x),
               "serve_tok_s": [x["serve_tok_s"] for x in lines]}
    for name in GAPS:
        summary[name] = {
            "program_max": max(x["program"][name] for x in lines),
            "fp8_min": min((x["fp8"][name] for x in lines if "fp8" in x),
                           default=None),
            "unsummed": next((x["unsummed"][name] for x in lines
                              if "unsummed" in x), None)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
