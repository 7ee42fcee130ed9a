"""Optimizer/schedule math vs hand-rolled numpy references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adam, adamw, constant_schedule, cosine_schedule,
                         linear_schedule, linear_warmup_cosine)
from repro.optim.adamw import apply_updates


def test_adamw_matches_numpy_reference():
    lr, b1, b2, eps, wd = 1e-2, 0.9, 0.95, 1e-8, 0.1
    opt = adamw(constant_schedule(lr), b1=b1, b2=b2, eps=eps, weight_decay=wd)
    w = jnp.asarray(np.random.default_rng(0).standard_normal((4, 3)),
                    jnp.float32)
    params = {"w": w}
    state = opt.init(params)
    m = np.zeros((4, 3)); v = np.zeros((4, 3))
    wn = np.asarray(w)
    for t in range(1, 6):
        g = np.full((4, 3), 0.5, np.float32) * t
        updates, state = opt.update({"w": jnp.asarray(g)}, state, params)
        params = apply_updates(params, updates)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
        wn = wn - lr * (mh / (np.sqrt(vh) + eps) + wd * wn)
        np.testing.assert_allclose(np.asarray(params["w"]), wn, atol=1e-5)


def test_weight_decay_skips_1d_params():
    opt = adamw(constant_schedule(1e-2), weight_decay=1.0)
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = opt.init(params)
    zero = jax.tree.map(jnp.zeros_like, params)
    updates, _ = opt.update(zero, state, params)
    assert float(jnp.abs(updates["w"]).sum()) > 0    # decayed
    assert float(jnp.abs(updates["b"]).sum()) == 0   # not decayed


def test_grad_clipping():
    opt = adam(constant_schedule(1.0), max_grad_norm=1e-6)
    params = {"w": jnp.ones((8,))}
    state = opt.init(params)
    huge = {"w": jnp.full((8,), 1e9)}
    updates, _ = opt.update(huge, state, params)
    assert np.isfinite(np.asarray(updates["w"])).all()


def test_schedules():
    s = linear_schedule(1.0, 100)
    assert float(s(jnp.asarray(0))) == pytest.approx(1.0)
    assert float(s(jnp.asarray(100))) == pytest.approx(0.0)
    c = cosine_schedule(1.0, 100, min_frac=0.1)
    assert float(c(jnp.asarray(0))) == pytest.approx(1.0)
    assert float(c(jnp.asarray(100))) == pytest.approx(0.1)
    w = linear_warmup_cosine(1.0, 10, 100)
    assert float(w(jnp.asarray(5))) == pytest.approx(0.5)
    assert float(w(jnp.asarray(10))) <= 1.0


def test_bf16_params_keep_f32_moments():
    opt = adamw(constant_schedule(1e-3))
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    state = opt.init(params)
    assert state.mu["w"].dtype == jnp.float32
    updates, _ = opt.update({"w": jnp.ones((4, 4), jnp.bfloat16)},
                            state, params)
    assert updates["w"].dtype == jnp.bfloat16


@pytest.mark.parametrize("arch", ["stablelm-3b", "mamba2-1.3b"])
def test_trainer_decays_matrices_only_stacked_or_not(arch):
    """The Trainer's mask: a stacked per-layer leaf decays when each
    layer's slice is a matrix, so the layers' norm scales and biases
    (stacked to 2-D) take no weight decay, as the final norm's do not."""
    import functools
    from repro.configs import get_config
    from repro.models import lm
    from repro.train.step import decay_mask

    def path_str(path):
        return "/".join(str(k.key) for k in path)

    cfg = get_config(arch, reduced=True)
    shapes = jax.eval_shape(functools.partial(lm.init_model, cfg),
                            jax.random.PRNGKey(0))
    mask = decay_mask(cfg)(shapes)
    got = {path_str(p): m for p, m in
           jax.tree_util.tree_flatten_with_path(mask)[0]}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = path_str(path)
        per_layer = leaf.ndim - name.startswith("layers/")
        assert got[name] == (per_layer >= 2), name
    assert not got["layers/ln1/scale"] and got["embed/table"]
