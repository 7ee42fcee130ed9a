"""Compiles for a described TPU v5e, with no chip attached.

Every built-in Pallas kernel at its check-case shapes, and the serving
engine's paged decode step at qwen1.5-4b's published widths (depth cut to
2 layers), go through the TPU compiler installed with JAX: what it
refuses here (an unaligned block, a primitive Mosaic cannot lower, a
program too large for the chip) would fail on the chip.

The topology is described inside a module fixture and never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers each import every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import cases
from repro.models import lm
from repro.serve.decode import init_paged_caches, serving_params
from repro.serve.engine import _paged_step_fns

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but can never be read back without the chip: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("name", cases.BUILTIN)
def test_kernel_compiles_for_v5e(one_chip, name):
    case = cases.kernel_case(name)
    args = _on(one_chip, jax.eval_shape(case.make, jax.random.PRNGKey(0)))
    compiled = jax.jit(case.run).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} did not lower to a Mosaic kernel"


def _compile_paged_step(sharding, cfg, max_batch, max_seq, block, blocks):
    """The engine's paged step compiled for ``sharding``'s device, with the
    weights in the serving form, and its abstract caches."""
    step, _ = _paged_step_fns(cfg, None, max_seq)
    params = serving_params(_on(sharding, jax.eval_shape(
        lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))))
    caches = _on(sharding, jax.eval_shape(
        lambda: init_paged_caches(cfg, blocks, block, max_batch)))
    lane = jax.ShapeDtypeStruct((max_batch,), jnp.int32, sharding=sharding)
    table = jax.ShapeDtypeStruct((max_batch, -(-max_seq // block)),
                                 jnp.int32, sharding=sharding)
    compiled = step.lower(params, caches, lane, table, lane, lane,
                          lane).compile()
    return compiled, caches


def test_paged_decode_step_compiles_at_qwen_widths(one_chip):
    cfg = get_config("qwen1.5-4b").replace(n_layers=2)
    max_batch, max_seq, block = 8, 512, 16
    compiled, _ = _compile_paged_step(one_chip, cfg, max_batch, max_seq,
                                      block, max_batch * max_seq // block)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES


_COPY = re.compile(r"^\s*%?\S+ = \w+\[([\d,]*)\]\S* copy\(", re.M)


def test_paged_decode_step_updates_pages_in_place(one_chip):
    """At the benchmark cell's geometry (10 lanes, 320 blocks of 16) the
    step neither re-lays out a page array (no ``copy`` whose result has a
    page array's shape, whole or flattened) nor writes a new one: the
    cache outputs alias the donated inputs."""
    cfg = get_config("qwen1.5-4b").replace(n_layers=2)
    max_batch, max_seq, block, blocks = 10, 512, 16, 320
    compiled, caches = _compile_paged_step(one_chip, cfg, max_batch,
                                           max_seq, block, blocks)
    kh, d = cfg.n_kv_heads, cfg.head_dim
    page_forms = {(blocks, block, kh * d), (blocks * block, kh * d),
                  (blocks, block, kh, d), (blocks * block, kh, d)}
    text = compiled.as_text()
    copies = [tuple(int(n) for n in dims.split(",") if n)
              for dims in _COPY.findall(text)]
    assert copies, "no copy found: the pattern no longer reads the HLO"
    assert not [c for c in copies if c in page_forms], copies
    page_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(caches))
    assert compiled.memory_analysis().alias_size_in_bytes >= page_bytes


def test_paged_decode_step_weight_relayouts_do_not_grow_with_depth(
        one_chip):
    """At the benchmark cell's geometry the step slices no stacked weight
    (no ``slice_bitcast_fusion``) and re-lays out the same few weight
    matrices (layer 0's, prefetched) at 4 layers and at 8: slicing stacked
    weights inside the step gave 2 slice fusions and 8 copies at 4 layers,
    one copy per attention matrix a layer.  ``tools/serve_stages.py``'s
    count of weight relayouts reads the same from the compiled text."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_stages

    counts = []
    for n_layers in (4, 8):
        cfg = get_config("qwen1.5-4b").replace(n_layers=n_layers)
        compiled, _ = _compile_paged_step(one_chip, cfg, 10, 512, 16, 320)
        text = compiled.as_text()
        assert "slice_bitcast_fusion" not in text
        weights = {a.shape for a in jax.tree.leaves(jax.eval_shape(
            lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))["layers"])}
        weights = {s[1:] for s in weights if len(s) >= 3}
        copies = [tuple(int(n) for n in dims.split(",") if n)
                  for dims in _COPY.findall(text)]
        counts.append(len([c for c in copies if c in weights]))
        assert serve_stages.weight_relayouts(text)["count"] == counts[-1]
    assert counts[0] == counts[1] <= 2, counts


def test_train_step_at_4k_fits_a_v5e_2x2(topo):
    """stablelm-3b's GSPMD train step, 2 of its 32 layers at published
    widths, global batch 4 x 4096 on (data=2, model=2): the attention
    backward recomputes each 512-wide KV chunk and each data shard runs
    its own rows, so the step's peak is 3.09 GB a chip; keeping every
    chunk's float32 logits and probabilities for the backward
    (2048-wide chunks, every row on every chip) asked 15.71 GB."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as shd
    from repro.optim import adamw, linear_warmup_cosine
    from repro.train.step import (TrainState, make_train_step,
                                  train_state_specs)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("stablelm-3b").replace(n_layers=2)
    opt = adamw(linear_warmup_cosine(3e-4, 10, 100), weight_decay=0.1,
                max_grad_norm=1.0)

    def init():
        p = lm.init_model(cfg, jax.random.PRNGKey(0))
        return TrainState(p, opt.init(p), jnp.zeros((), jnp.int32))

    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        jax.eval_shape(init), train_state_specs(cfg, mesh),
        is_leaf=lambda x: isinstance(x, P))
    rows = NamedSharding(mesh, shd.batch_spec(mesh, 4, 2))
    batch = {k: jax.ShapeDtypeStruct((4, 4096), jnp.int32, sharding=rows)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(cfg, opt, mesh=mesh), donate_argnums=0)
    mem = step.lower(state, batch).compile().memory_analysis()
    assert mem.peak_memory_in_bytes < 8 * 1024 ** 3, mem
