"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + finite values, plus decode-path checks.
This is deliverable (f)'s smoke-test requirement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.data.pipeline import make_data
from repro.models import for_config
from repro.optim import adamw, constant_schedule
from repro.serve import decode_step, init_caches, serving_params
from repro.train.step import TrainState, make_train_step

SEQ, BATCH = 32, 2


@pytest.fixture(scope="module")
def trained():
    """arch -> (cfg, params) cache shared across tests in this module."""
    out = {}
    for arch in ARCHS:
        cfg = get_config(arch, reduced=True)
        model = for_config(cfg)
        out[arch] = (cfg, model.init_model(cfg, jax.random.PRNGKey(0)))
    return out


@pytest.mark.parametrize("arch", list(ARCHS))
def test_train_step_smoke(arch, trained):
    cfg, params = trained[arch]
    batch = make_data(cfg, SEQ, BATCH).batch_at(0)
    opt = adamw(constant_schedule(1e-3))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    step = jax.jit(make_train_step(cfg, opt))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(state.step) == 1


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get_config(a).family != "encdec"])
def test_forward_shapes(arch, trained):
    cfg, params = trained[arch]
    from repro.models import lm
    tokens = jnp.zeros((BATCH, SEQ), jnp.int32)
    logits = jax.jit(lambda p, t: lm.forward(p, t, cfg))(params, tokens)
    assert logits.shape == (BATCH, SEQ, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", list(ARCHS))
def test_decode_step_smoke(arch, trained):
    cfg, params = trained[arch]
    caches = init_caches(cfg, BATCH, SEQ)
    token = jnp.zeros((BATCH, 1), jnp.int32)
    fn = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg))
    params = serving_params(params)
    logits, caches = fn(params, caches, token, 0)
    assert logits.shape == (BATCH, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    logits2, _ = fn(params, caches, token, 1)
    assert not np.allclose(np.asarray(logits), np.asarray(logits2))


@pytest.mark.parametrize("arch", ["gemma3-1b", "mamba2-1.3b"])
def test_decode_consistent_with_forward(arch, trained):
    """Greedy decode over a teacher-forced prompt must reproduce the
    forward logits at every position."""
    cfg, params = trained[arch]
    from repro.models import lm
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, 12)), jnp.int32)
    full = lm.forward(params, tokens, cfg)
    caches = init_caches(cfg, 1, 12)
    per_layer = serving_params(params)
    for pos in range(8):
        logits, caches = decode_step(per_layer, caches,
                                     tokens[:, pos:pos + 1],
                                     pos, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, pos]), atol=2e-3,
                                   err_msg=f"{arch} pos={pos}")


def test_loss_decreases_stablelm():
    cfg = get_config("stablelm-3b", reduced=True)
    from repro.train import TrainConfig, Trainer
    tcfg = TrainConfig(steps=25, seq_len=32, global_batch=4, lr=5e-3,
                       warmup=2, ckpt_dir=None)
    log = Trainer(cfg, tcfg).run()
    assert log[-1]["loss"] < log[0]["loss"]


def test_generate_shapes():
    cfg = get_config("qwen1.5-4b", reduced=True)
    from repro.models import lm
    from repro.serve import generate
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((2, 4), jnp.int32)
    out = jax.jit(lambda p, t: generate(p, cfg, t, 6))(params, prompt)
    assert out.shape == (2, 10)


def test_window_schedule_gemma_pattern():
    from repro.models.lm import window_schedule
    from repro.nn.attention import NO_WINDOW
    cfg = get_config("gemma3-1b")
    ws = window_schedule(cfg)
    assert len(ws) == 26
    assert (ws == NO_WINDOW).sum() == 4            # layers 5, 11, 17, 23
    assert ws[5] == NO_WINDOW and ws[0] == 512
    # 5 local : 1 global within each full period
    assert list(ws[:6]).count(512) == 5


def test_param_count_estimates():
    """n_params() tracks the actual initialized parameter count."""
    from repro.utils.tree import param_count
    for arch in ["stablelm-3b", "gemma3-1b", "mamba2-1.3b"]:
        cfg = get_config(arch, reduced=True)
        model = for_config(cfg)
        params = model.init_model(cfg, jax.random.PRNGKey(0))
        actual = param_count(params)
        est = cfg.n_params()
        assert 0.4 < est / actual < 2.5, (arch, est, actual)
