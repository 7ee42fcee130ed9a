"""Single-device unit tests for the dist-layer sharding rules.

Everything here runs on AbstractMesh (no device allocation), so each
``cache_sharding`` branch and the ``spec_for_axes`` divisibility fallback
are covered without the 8-device subprocess harness of test_dist.py.
"""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd


def _mesh(shape, names):
    return jax.sharding.AbstractMesh(tuple(shape), tuple(names))


# ---------------------------------------------------------------------------
# spec_for_axes
# ---------------------------------------------------------------------------

def test_spec_for_axes_divisibility_fallback_per_dim():
    mesh = _mesh((2, 4), ("data", "model"))
    # both dims divisible: embed -> data (FSDP), mlp -> model (TP)
    assert shd.spec_for_axes(("embed", "mlp"), mesh, (64, 32)) \
        == P("data", "model")
    # mlp not divisible by model=4 -> only that dim falls back
    assert shd.spec_for_axes(("embed", "mlp"), mesh, (64, 30)) \
        == P("data", None)
    # embed not divisible by data=2 -> only that dim falls back
    assert shd.spec_for_axes(("embed", "mlp"), mesh, (63, 32)) \
        == P(None, "model")


def test_spec_for_axes_missing_mesh_axis_replicates():
    mesh = _mesh((4,), ("model",))
    assert shd.spec_for_axes(("embed", "mlp"), mesh, (64, 32)) \
        == P(None, "model")


def test_spec_for_axes_never_reuses_a_mesh_axis():
    mesh = _mesh((4,), ("model",))
    # vocab and mlp both prefer model; only the first dim gets it
    assert shd.spec_for_axes(("vocab", "mlp"), mesh, (64, 64)) \
        == P("model", None)


def test_spec_for_axes_unknown_and_scan_axes_replicate():
    mesh = _mesh((2, 4), ("data", "model"))
    assert shd.spec_for_axes(("layers", "embed", "mlp"), mesh, (8, 64, 32)) \
        == P(None, "data", "model")
    assert shd.spec_for_axes((None, "nonesuch"), mesh, (8, 8)) == P(None, None)


# ---------------------------------------------------------------------------
# dp helpers / batch_spec
# ---------------------------------------------------------------------------

def test_dp_axes_and_sizes():
    assert shd.dp_axes(_mesh((2, 4), ("data", "model"))) == "data"
    assert shd.dp_axes(_mesh((2, 2, 4), ("pod", "data", "model"))) \
        == ("pod", "data")
    assert shd.dp_axes(_mesh((4,), ("model",))) is None
    assert shd.dp_size(_mesh((2, 2, 4), ("pod", "data", "model"))) == 4
    assert shd.model_size(_mesh((2, 2, 4), ("pod", "data", "model"))) == 4
    assert shd.model_size(_mesh((4,), ("pipe",))) == 1


def test_batch_spec_divisibility_fallback():
    mesh = _mesh((4, 2), ("data", "model"))
    assert shd.batch_spec(mesh, 8) == P("data", None)
    assert shd.batch_spec(mesh, 6) == P(None, None)        # 6 % 4 != 0
    assert shd.batch_spec(mesh, 8, ndim=3) == P("data", None, None)
    assert shd.batch_spec(_mesh((4,), ("pipe",)), 8) == P(None, None)


# ---------------------------------------------------------------------------
# cache_sharding — one test per branch
# ---------------------------------------------------------------------------

def test_cache_sharding_head_branch():
    mesh = _mesh((2, 4), ("data", "model"))
    assert shd.cache_sharding(mesh, 8, 1024, 8) \
        == P("data", None, "model", None)


def test_cache_sharding_mqa_sequence_branch():
    mesh = _mesh((2, 4), ("data", "model"))
    assert shd.cache_sharding(mesh, 8, 1024, 1) \
        == P("data", "model", None, None)
    # kv=2 not divisible by model=4 -> same sequence-sharded branch
    assert shd.cache_sharding(mesh, 8, 1024, 2) \
        == P("data", "model", None, None)


def test_cache_sharding_long_context_branch():
    mesh = _mesh((2, 4), ("data", "model"))
    spec = shd.cache_sharding(mesh, 1, 1024, 1)
    assert spec[0] is None and set(spec[1]) == {"data", "model"}


def test_cache_sharding_full_fallback_replicates():
    mesh = _mesh((2, 4), ("data", "model"))
    # nothing divides: odd batch, prime seq, odd kv heads
    assert shd.cache_sharding(mesh, 3, 1021, 3) == P(None, None, None, None)
    # divisible batch but seq/heads indivisible: batch-only sharding
    assert shd.cache_sharding(mesh, 8, 1021, 3) == P("data", None, None, None)


def test_cache_sharding_model_only_mesh():
    mesh = _mesh((4,), ("model",))
    # no dp axes at all -> sequence over model when divisible
    assert shd.cache_sharding(mesh, 8, 1024, 8) \
        == P(None, ("model",), None, None)


# ---------------------------------------------------------------------------
# host mesh shape arithmetic (pipe/pod compose with data/model)
# ---------------------------------------------------------------------------

def test_host_mesh_shape_pipe_composes():
    from repro.launch.mesh import host_mesh_shape
    assert host_mesh_shape(8) == ((8, 1), ("data", "model"))
    assert host_mesh_shape(8, model=2) == ((4, 2), ("data", "model"))
    # pipe no longer replaces data/model — it composes
    assert host_mesh_shape(8, pipe=4) \
        == ((4, 2, 1), ("pipe", "data", "model"))
    assert host_mesh_shape(8, model=2, pipe=2) \
        == ((2, 2, 2), ("pipe", "data", "model"))
    assert host_mesh_shape(8, pipe=2, pods=2) \
        == ((2, 2, 2, 1), ("pod", "pipe", "data", "model"))
    assert host_mesh_shape(16, model=2, pipe=2, pods=2) \
        == ((2, 2, 2, 2), ("pod", "pipe", "data", "model"))


def test_host_mesh_shape_rejects_indivisible():
    import pytest
    from repro.launch.mesh import host_mesh_shape
    with pytest.raises(ValueError):
        host_mesh_shape(8, pipe=3)


def test_production_mesh_pipe_carves_data():
    import pytest
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(ValueError):
        make_production_mesh(pipe=3)   # must divide the 16-way data axis


# ---------------------------------------------------------------------------
# shard_map pipeline-step specs
# ---------------------------------------------------------------------------

def test_sharded_param_specs_split_layers_over_pipe():
    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("stablelm-3b", reduced=True)
    spec_tree = lm.model_spec(cfg)
    specs = shd.sharded_param_specs(spec_tree)
    assert jax.tree.structure(specs) == jax.tree.structure(
        spec_tree, is_leaf=lambda x: hasattr(x, "axes"))
    flat = jax.tree.leaves(specs["layers"])
    assert flat and all(s == P("pipe") for s in flat)
    assert all(s == P() for s in jax.tree.leaves(specs["embed"]))
    ef = shd.sharded_ef_specs(spec_tree)
    assert all(s == P("pod", "pipe") for s in jax.tree.leaves(ef["layers"]))
    assert all(s == P("pod") for s in jax.tree.leaves(ef["embed"]))


def test_pipe_size_helper():
    assert shd.pipe_size(_mesh((4,), ("pipe",))) == 4
    assert shd.pipe_size(_mesh((2, 4), ("data", "model"))) == 1


def test_make_sharded_train_step_validates_eagerly():
    import pytest
    from repro.configs import get_config
    from repro.optim import adamw as adamw_fn, constant_schedule
    from repro.train.step import make_sharded_train_step
    opt = adamw_fn(constant_schedule(1e-3))
    cfg = get_config("stablelm-3b", reduced=True)
    # no pipe axis
    with pytest.raises(ValueError, match="pipe"):
        make_sharded_train_step(cfg, opt, _mesh((2, 4), ("data", "model")))
    # tensor parallelism composes for dense configs with divisible dims...
    assert make_sharded_train_step(
        cfg, opt, _mesh((2, 2, 2), ("pipe", "data", "model"))) is not None
    # ...but TP dims that do not divide the model axis are rejected
    with pytest.raises(ValueError, match="divisible by model"):
        make_sharded_train_step(
            cfg.replace(d_ff=cfg.d_ff + 1), opt,
            _mesh((2, 2, 2), ("pipe", "data", "model")))
    # and non-dense families have no explicit-TP stage path
    with pytest.raises(ValueError, match="dense family"):
        make_sharded_train_step(
            get_config("mamba2-1.3b", reduced=True), opt,
            _mesh((2, 2, 2), ("pipe", "data", "model")))
    # unknown schedule names fail eagerly with the valid choices
    with pytest.raises(ValueError, match="gpipe"):
        make_sharded_train_step(
            cfg, opt, _mesh((2, 2, 1), ("pipe", "data", "model")),
            schedule="interleaved")
    # layer stack must split evenly across stages (reduced has 2 layers)
    with pytest.raises(ValueError, match="divisible"):
        make_sharded_train_step(
            cfg.replace(n_layers=2), opt,
            _mesh((4, 2, 1), ("pipe", "data", "model")))
    # non-uniform families are rejected
    with pytest.raises(ValueError, match="family"):
        make_sharded_train_step(
            get_config("zamba2-2.7b", reduced=True), opt,
            _mesh((2, 2, 1), ("pipe", "data", "model")))


# ---------------------------------------------------------------------------
# decode_cache_shardings leaf classification (shapes only, via eval_shape)
# ---------------------------------------------------------------------------

def test_decode_cache_shardings_covers_all_families():
    from repro.configs import get_config
    from repro.serve.decode import init_caches
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch in ("stablelm-3b", "mamba2-1.3b", "deepseek-v2-lite-16b"):
        cfg = get_config(arch, reduced=True)
        caches = jax.eval_shape(lambda: init_caches(cfg, 2, 64))
        sh = shd.decode_cache_shardings(cfg, caches, mesh)
        assert jax.tree.structure(sh) == jax.tree.structure(caches)


# ---------------------------------------------------------------------------
# kv_pool_shardings: paged K/V pages (num_blocks, block_size, KH * D)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,kv_heads,model,head_ax", [
    ("qwen1.5-4b", 4, 2, "model"),       # 2 whole heads per chunk
    ("qwen1.5-4b", 4, 4, "model"),
    ("qwen1.5-4b", 3, 2, None),          # heads do not divide: replicate
    ("gemma3-1b", 1, 2, None),           # MQA
    ("zamba2-2.7b", 4, 2, "model"),      # hybrid: shared-attention pages
])
def test_kv_pool_shardings_merged_page_heads(arch, kv_heads, model, head_ax):
    from repro.configs import get_config
    from repro.serve.decode import init_paged_caches, paged_cache_kinds
    cfg = get_config(arch, reduced=True).replace(n_kv_heads=kv_heads)
    caches = jax.eval_shape(lambda: init_paged_caches(cfg, 12, 4, 2))
    kinds = paged_cache_kinds(cfg)
    sh = shd.kv_pool_shardings(cfg, caches, _mesh((2, model),
                                                  ("data", "model")), kinds)
    assert jax.tree.structure(sh) == jax.tree.structure(caches)
    pages = [s for c, kind in zip(sh, kinds) if kind == "paged"
             for s in jax.tree.leaves(c)]
    assert pages
    assert all(s.spec == P(None, None, head_ax) for s in pages)


def test_kv_pool_shardings_mla_latent_pages_replicate():
    from repro.configs import get_config
    from repro.serve.decode import init_paged_caches, paged_cache_kinds
    cfg = get_config("deepseek-v2-lite-16b", reduced=True)
    caches = jax.eval_shape(lambda: init_paged_caches(cfg, 12, 4, 2))
    sh = shd.kv_pool_shardings(cfg, caches, _mesh((1, 2), ("data", "model")),
                               paged_cache_kinds(cfg))
    assert all(s.spec == P(None, None, None) for s in jax.tree.leaves(sh))
