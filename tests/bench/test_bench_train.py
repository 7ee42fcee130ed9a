"""The training driver, its plain reference and the check that decides
``correct``, on the CPU at a tiny size (no chip; the runner's look for a
TPU is skipped by building the run's context here)."""

import functools
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from bench import counts, gen, readers, stages, train_readers  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.drivers import train  # noqa: E402
from bench.drivers.common import Context  # noqa: E402
from bench.reference import train as reference  # noqa: E402

SEED = 2 ** 33 + 4321       # more than 32 bits, as the driver's seeds are
CONFIG = "stablelm-3b-4e1t.train-4chip"


def tiny_config(**over):
    with open(os.path.join(ROOT, "bench", "configs", f"{CONFIG}.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
             torch_dtype="float32")
    c.update(over)
    return c


def tiny_job(**over):
    job = gen.load_mix("train-4k")
    job.update(seq_len=64, global_batch=4, mesh={"data": 1, "model": 1},
               steps_before_window=2)
    job.update(over)
    return job


def context(cfg, job, seconds=1.5, devices=None):
    return Context(config=cfg, mix=job, seed=SEED, seconds=seconds,
                   trace=False, devices=devices or jax.devices()[:1],
                   t0=time.monotonic(), peaks=counts.peaks_for("TPU v5 lite"))


def test_program_config_has_the_published_equations():
    with open(os.path.join(ROOT, "bench", "configs", f"{CONFIG}.json")) as f:
        c = json.load(f)
    pc = train.program_config(c)
    assert (pc.n_layers, pc.d_model, pc.n_heads, pc.head_dim, pc.d_ff,
            pc.vocab) == (32, 2560, 32, 80, 6912, 50304)
    assert (pc.norm, pc.tie_embeddings, pc.qkv_bias, pc.rope_dims,
            pc.rope_theta, pc.dtype) == ("layernorm", False, False, 20,
                                         10000, "bfloat16")
    dm = reference.dims(c)
    assert (dm.rot, dm.layernorm, dm.tied, dm.bias) == (20, True, False,
                                                        False)


def test_program_loss_and_every_leaf_gradient_match_the_reference():
    """stablelm-3b's reduced configuration (LayerNorm, partial rotary,
    untied head) in float32 on the benchmark's weights: the loss within
    1e-5 and each leaf's gradient norm within 1e-4, relative."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.train.step import loss_fn
    c = tiny_config()
    pc = train.program_config(c)
    red = get_config("stablelm-3b", reduced=True)
    assert pc.replace(attn_chunk=red.attn_chunk, remat=red.remat) == red
    shapes = jax.eval_shape(functools.partial(lm.init_model, pc),
                            jax.random.PRNGKey(0))
    params = W.make_params(SEED, shapes)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, c["vocab_size"], (2, 64)).astype(np.int32)
    labels = rng.integers(0, c["vocab_size"], (2, 64)).astype(np.int32)
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"tokens": tokens, "labels": labels}, pc)
    ref = reference.loss_and_grad_norms(c, SEED, tokens, labels)
    np.testing.assert_allclose(float(loss), ref["loss"], rtol=1e-5)
    got = {W.path_str(p): float(np.linalg.norm(np.asarray(g)))
           for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    assert sorted(got) == sorted(ref["leaf_norms"])
    for k, v in ref["leaf_norms"].items():
        np.testing.assert_allclose(got[k], v, rtol=1e-4, err_msg=k)
    np.testing.assert_allclose(
        np.sqrt(sum(v * v for v in got.values())), ref["grad_norm"],
        rtol=1e-4)


@pytest.fixture(scope="module")
def sound_run():
    """Four layers and 128 positions, deep and long enough for the fp8
    control's rounding to show through the cell's own limits."""
    cfg, job = tiny_config(num_hidden_layers=4), tiny_job(seq_len=128)
    return cfg, train.run(context(cfg, job), controls=("fp8",))


def test_sound_run_is_correct(sound_run):
    cfg, rec = sound_run
    assert rec["correct"], rec["checks"]
    assert set(rec["checks"]) == {"loss_gap_rel", "grad_norm_gap_rel",
                                  "moment_gap_rel", "update_gap_rel",
                                  "nonfinite_steps"}
    assert rec["checks"]["nonfinite_steps"]["value"] == 0
    assert rec["attempted"] >= 1 and rec["failed"] == 0
    assert rec["compiles_in_window"] == 0
    for read in (readers.serve_tokens_per_s,
                 train_readers.host_ms_per_step, train_readers.step_mfu):
        assert read(rec) > 0
    # a CPU run reads no trace
    assert train_readers.collective_share(rec) is None


def test_fp8_control_is_not_correct(sound_run):
    """The reference with every matmul operand rounded through float8 e4m3
    (forward and backward), put in the program's place, fails a limit."""
    cfg, rec = sound_run
    ctl = rec["controls"]["fp8"]
    assert not ctl["correct"], ctl["checks"]
    assert (ctl["checks"]["loss_gap_rel"]["value"]
            > rec["checks"]["loss_gap_rel"]["value"])


_UNSUMMED = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src"),
                os.path.join({root!r}, "tests", "bench")]
import jax
import test_bench_train as t
from bench import calibrate_train
from bench.drivers import train
if {fault}:
    calibrate_train.plant_unsummed(2)
job = t.tiny_job(mesh={{"data": 2, "model": 1}})
rec = train.run(t.context(t.tiny_config(), job, seconds=0.5,
                          devices=jax.devices()[:2]))
print("CHECKS " + json.dumps(rec["checks"]))
"""


@pytest.mark.parametrize("fault", [False, True])
def test_gradients_not_summed_over_data_fail_the_check(subproc, fault):
    """On a 2-device host mesh (data=2): the sound step passes; a step
    whose gradients are not summed over ``data`` (the calibrator's planted
    fault: data shard 0's own gradient) fails ``grad_norm_gap_rel`` and
    ``moment_gap_rel`` (its loss, the whole batch's, still passes)."""
    out = subproc(_UNSUMMED.format(root=ROOT, fault=fault), n_devices=2)
    checks = json.loads(out.split("CHECKS ", 1)[1].splitlines()[0])
    assert checks["loss_gap_rel"]["value"] <= checks["loss_gap_rel"]["limit"]
    for name in ("grad_norm_gap_rel", "moment_gap_rel"):
        gap = checks[name]
        assert (gap["value"] > gap["limit"]) == fault, (name, gap)


def _unchanged_state(monkeypatch):
    """The step computes its loss and gradients but returns the state it
    was given."""
    import repro.train.loop as loop
    sound = loop.make_train_step

    def make(*a, **k):
        step = sound(*a, **k)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        return unchanged

    monkeypatch.setattr(loop, "make_train_step", make)


def _optimizer(monkeypatch, **over):
    """The trainer's AdamW built with ``over`` in place of its options."""
    import repro.train.loop as loop
    sound = loop.adamw_fn
    monkeypatch.setattr(loop, "adamw_fn",
                        lambda *a, **k: sound(*a, **dict(k, **over)))


FAULTS = {
    "state_unchanged": (_unchanged_state,
                        {"moment_gap_rel": 1.0, "update_gap_rel": 1.0}),
    "not_clipped": (functools.partial(_optimizer, max_grad_norm=None),
                    {"moment_gap_rel": None}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_optimizer_fault_fails_the_check(monkeypatch, fault):
    """At the peak learning rate (one warm-up step), in float32: a step
    that leaves the state as it was or skips the clip computes the right
    loss and gradients, and fails a check of its first moment or its
    parameters' change; the sound step passes them."""
    plant, failing = FAULTS[fault]
    cfg, job = tiny_config(), tiny_job(warmup=1)
    sound = train.run(context(cfg, job, seconds=0.3))
    assert sound["correct"], sound["checks"]
    # the clip acts on the first step
    assert sound["reference"]["grad_norm"] > job["clip_norm"]
    plant(monkeypatch)
    rec = train.run(context(cfg, job, seconds=0.3))
    checks = rec["checks"]
    for name in ("loss_gap_rel", "grad_norm_gap_rel"):
        assert checks[name]["value"] <= checks[name]["limit"], name
    for name, value in failing.items():
        assert checks[name]["value"] > checks[name]["limit"], (name, checks)
        if value is not None:
            assert checks[name]["value"] == value
    assert not rec["correct"]


def test_serve_tok_s_counts_whole_steps_only():
    """The window closes at the return of the step running at
    ``--seconds``, so ``serve_tok_s`` is the tokens of whole steps over the
    first call to the last return; a step that was not finite counts
    none."""

    class Steps:
        counters = {"tokens": 0, "steps": 0}

        def step(self):
            time.sleep(0.04)
            n = self.counters["steps"] + 1
            self.counters = {"tokens": 100 * n, "steps": n}
            return {"loss": float("nan") if n == 2 else 1.0,
                    "grad_norm": 1.0}

    rec = train.window(Steps(), context(tiny_config(), tiny_job(),
                                        seconds=0.1))
    steps = rec["steps"]
    assert len(steps) >= 3 and steps[-2][1] < 0.1 <= steps[-1][1]
    assert rec["window_s"] == steps[-1][1]
    assert [s[2] for s in steps] == [100] * len(steps)
    assert [s[3] for s in steps] == [k != 1 for k in range(len(steps))]
    assert readers.serve_tokens_per_s(rec) == pytest.approx(
        100 * (len(steps) - 1) / steps[-1][1])


def test_step_mfu_leaves_out_the_traced_stretch():
    rec = {"window_s": 10.0, "traced": [6.0, 10.0], "least_step_s": 0.5,
           "steps": [(0.0, 2.0, 1, True), (2.0, 4.0, 1, True),
                     (4.0, 6.0, 1, True), (6.0, 9.0, 1, True)]}
    assert train_readers.step_mfu(rec) == pytest.approx(25.0)


def test_collective_time_inside_a_loop_is_exposed():
    """A ``while`` encloses a layer's ops: a collective under it counts as
    exposed unless another op overlaps it, which ``trace.reduce`` (the
    loop read as compute) would hide."""
    from bench import trace
    dev = [("while", 0, 100), ("fusion", 0, 40), ("all-reduce", 30, 70),
           ("all-gather", 80, 90), ("fusion", 85, 100)]
    ev = trace.Events({"/device:TPU:0": dev,
                       "/device:TPU:1": [("while", 0, 100),
                                         ("all-reduce", 0, 20)]},
                      [("bench.trace_window", 0, 100)])
    got = train.collective_ns(ev)
    assert got == {"/device:TPU:0": [50, 35], "/device:TPU:1": [20, 20]}
    assert trace.reduce(ev)["devices"]["/device:TPU:0"][
        "collective_exposed_ns"] == 0
    rec = {"trace": {"window_s": 100e-9, "collective_ns": got}}
    assert train_readers.collective_share(rec) == pytest.approx(35.0)


def test_trainer_spans_and_counters(tmp_path):
    """Each step runs inside ``repro.train.step`` with its four stages in
    it; ``host_s + sync_s`` is the steps' time and ``data_s`` lies in
    ``host_s``."""
    from repro.configs import get_config
    from repro.train import TrainConfig, Trainer
    tr = Trainer(get_config("stablelm-3b", reduced=True),
                 TrainConfig(steps=10, seq_len=32, global_batch=2, warmup=1))
    tr.step()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        tr.step()
    jax.profiler.stop_trace()
    names = [n for n, _, _ in stages.read_program_spans(
        _xplane(str(tmp_path)))]
    for stage in ("step", "data", "put", "dispatch", "sync"):
        assert names.count("repro.train." + stage) == 3, names
    c = tr.counters
    assert (c["steps"], c["tokens"]) == (4, 4 * 2 * 32)
    total = sum(m["seconds"] for m in tr.metrics_log)
    assert c["host_s"] + c["sync_s"] == pytest.approx(total, abs=1e-9)
    assert 0 < c["data_s"] <= c["host_s"]


def _xplane(root):
    from bench import trace
    path = trace.find_xplane(root)
    assert path is not None
    return path
