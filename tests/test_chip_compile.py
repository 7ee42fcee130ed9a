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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import cases
from repro.models import lm
from repro.serve.decode import init_paged_caches
from repro.serve.engine import _paged_step_fns

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


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


def test_paged_decode_step_compiles_at_qwen_widths(one_chip):
    cfg = get_config("qwen1.5-4b").replace(n_layers=2)
    max_batch, max_seq, block = 8, 512, 16
    step, _ = _paged_step_fns(cfg, None, max_seq)
    params = _on(one_chip, jax.eval_shape(
        lambda: lm.init_model(cfg, jax.random.PRNGKey(0))))
    caches = _on(one_chip, jax.eval_shape(
        lambda: init_paged_caches(cfg, max_batch * max_seq // block, block,
                                  max_batch)))
    lane = jax.ShapeDtypeStruct((max_batch,), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((max_batch, max_seq // block), jnp.int32,
                                 sharding=one_chip)
    compiled = step.lower(params, caches, lane, table, lane, lane,
                          lane).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
