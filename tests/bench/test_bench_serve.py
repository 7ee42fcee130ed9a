"""The serving driver, the plain reference and the check that decides
``correct``, on the CPU at a tiny size (no chip; the runner's look for a
TPU is skipped by building the run's context here)."""

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

from bench import counts, gen  # noqa: E402
from bench.drivers import serve  # noqa: E402
from bench.drivers.common import Context, program_config  # noqa: E402
from bench.reference import dense  # noqa: E402

SEED = 2 ** 33 + 12345      # more than 32 bits, as the driver's seeds are


def tiny_config(name="qwen1.5-4b.serve-1chip", **over):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=4, vocab_size=256)
    c["engine"] = dict(max_batch=4, max_seq=512, block_size=16,
                       kv_blocks=128, prefill_chunk=4)
    c.update(over)
    return c


def fast_mix(mix):
    """The tiny model serves many times faster than the chip's: offer the
    open mix more requests, so that a short window finishes enough."""
    if mix["kind"] == "open":
        mix["rate_per_s"] = 3.0
    return mix


def context(cfg, mix_name, seconds=4.0, **mix_over):
    mix = fast_mix(gen.load_mix(mix_name))
    mix.update(mix_over)
    return Context(config=cfg, mix=mix, seed=SEED,
                   seconds=seconds, trace=False, devices=jax.devices()[:1],
                   t0=time.monotonic(), peaks=counts.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("over", [
    {},                                                    # Qwen2
    {"tie_word_embeddings": True, "attention_bias": False},
    {"attention_bias": False, "layer_norm_eps": 1e-5},     # StableLM's norm
])
def test_reference_matches_program_forward(over):
    """The reference's logits equal the program's full forward pass at
    float32, on the benchmark's own weights."""
    import functools
    from repro.models import lm
    from bench import weights as W

    cfg = tiny_config(torch_dtype="float32", **over)
    if "layer_norm_eps" in over:
        del cfg["rms_norm_eps"]
    pcfg = program_config(cfg).replace(remat=False)
    shapes = jax.eval_shape(functools.partial(lm.init_model, pcfg),
                            jax.random.PRNGKey(0))
    params = W.make_params(SEED, shapes)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 256, n).tolist() for n in (37, 20)]
    rows = [list(range(len(s))) for s in seqs]
    (ref,) = dense.served_logits(cfg, SEED, seqs, rows, head_rows=16)
    want = []
    with jax.default_matmul_precision("highest"):
        for s in seqs:
            want.append(np.asarray(lm.forward(params, np.asarray([s]),
                                              pcfg))[0])
    np.testing.assert_allclose(ref, np.concatenate(want), rtol=2e-4,
                               atol=2e-4)


def test_rope_partial_rotary_leaves_the_tail():
    cfg = tiny_config(partial_rotary_factor=0.25)
    dm = dense.Dims(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 4, dm.hd))
    y = dense._rope(dm, x, np.arange(5)[None])
    np.testing.assert_array_equal(np.asarray(y[..., dm.rot:]),
                                  np.asarray(x[..., dm.rot:]))
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-6)


@pytest.mark.parametrize("mix", ["chat-prefix", "batch-unshared"])
def test_sound_run_is_correct(mix):
    rec = serve.run(context(tiny_config(), mix))
    assert rec["correct"], rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert rec["attempted"] > 0 and rec["failed"] == 0
    if mix == "chat-prefix":
        pool = rec["counters1"]["pool"]
        assert pool["shared_tokens_reused"] > rec["counters0"]["pool"][
            "shared_tokens_reused"]


def test_open_mix_lead_in_stays_out_of_the_window():
    """An open mix with ``lead_in_s`` sends its own arrivals before the
    window: they are served, and none counts among the window's attempted
    requests or in its first-token tail."""
    from bench import readers
    rec = serve.run(context(tiny_config(), "chat-prefix", lead_in_s=2.0))
    assert rec["correct"], rec["checks"]
    dues = [r["due"] for r in rec["requests"]]
    assert min(dues) < 0 <= max(dues)
    assert rec["attempted"] == sum(1 for d in dues
                                   if 0 <= d < rec["window_s"])
    assert any(r["stamps"] for r in rec["requests"] if r["due"] < 0)
    in_window = dict(rec, requests=[r for r in rec["requests"]
                                    if r["due"] >= 0])
    assert readers.ttft_ms(rec, 75) == readers.ttft_ms(in_window, 75)


def run_line(mix_name, seconds=4.0):
    """A whole run through the runner, on the CPU at the tiny size: the
    look for a chip is skipped, everything after it runs."""
    from bench import run
    bench = run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["traffic"] == mix_name)
    found = run.find_cell(bench, cell["name"])
    found["config"] = tiny_config()
    fast_mix(found["mix"])
    return run.run_cell(bench, cell["name"], found, SEED, seconds, False,
                        jax.devices()[:1], time.monotonic(),
                        peaks=counts.peaks_for("TPU v5 lite"))


def test_sound_run_through_the_runner():
    out = run_line("chat-prefix")
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert {"ttft_p50_ms", "ttft_p75_ms", "itl_p95_ms",
            "setup_s"} <= set(out["metrics"])
    assert out["device"]["count"] == 1


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    """A wrong token from the step (lane 0, every 5th pass) fails the
    check of a whole run."""
    from repro.serve import engine as eng_mod
    real = eng_mod._paged_step_fns
    calls = {"n": 0}

    def broken(*a, **kw):
        step, reset = real(*a, **kw)

        def bad_step(*args):
            tok, caches = step(*args)
            calls["n"] += 1
            if calls["n"] % 5 == 0:
                tok = tok.at[0].set((tok[0] + 1) % 256)
            return tok, caches
        return bad_step, reset

    monkeypatch.setattr(eng_mod, "_paged_step_fns", broken)
    out = run_line("chat-prefix")
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["worst_logit_gap_sigma"]["value"] > out["checks"][
        "worst_logit_gap_sigma"]["limit"]


def test_fp8_control_reads_above_the_sound_run():
    """The control (the reference with every matmul operand in float8, its
    own first choices in the program's place) goes through the same check
    and limit as the program and comes out not correct, at a worst gap
    more than three times the sound program's, at this size as on the
    chip.  Four layers and a vocabulary of 4096: float8's error grows
    with depth, and at two layers of 256 tokens the control's worst gap
    swings across the limit from one sample to the next."""
    cfg = tiny_config(num_hidden_layers=4, vocab_size=4096)
    rec = serve.run(context(cfg, "chat-prefix"), controls=("fp8",))
    assert rec["correct"] is True, rec["checks"]
    control = rec["controls"]["fp8"]
    assert control["correct"] is False, control["checks"]
    worst = {k: v["worst_logit_gap_sigma"]["value"] for k, v in
             (("program", rec["checks"]), ("fp8", control["checks"]))}
    assert worst["fp8"] > control["checks"]["worst_logit_gap_sigma"]["limit"]
    assert worst["fp8"] > 3 * worst["program"], worst
