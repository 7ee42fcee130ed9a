"""Check cases for the registered Pallas kernels.

One case per built-in kernel: inputs that span ``TILES`` blocks on every
grid axis of the kernel's first ``CONFIGS`` entry, and the registry's
Pallas call (at that config's block sizes) and oracle.  ``chip_smoke.py``
runs every case on the chip; the tests compile them for a described TPU
and run them in interpret mode.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import get_kernel

TILES = 3      # blocks per grid axis: every index_map sees a non-zero step
# relative_error bound against the oracle run at "highest" matmul
# precision: bf16 inputs/outputs round at 2^-8 relative and an MXU pass may
# round f32 operands to bf16 too, so a correct kernel lands well under
# 1e-2; a wrong block index or tiling gives errors of order 1.
REL_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    make: Callable[[jax.Array], Tuple[jax.Array, ...]]  # key -> inputs
    run: Callable[..., jax.Array]                       # the Pallas call
    ref: Callable[..., jax.Array]                       # its oracle


def _normal(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _mats(*shapes, dtype=jnp.bfloat16):
    """Inputs of the given shapes; matrices after the first are scaled by
    1/sqrt(fan_in) so outputs stay O(1)."""
    def make(key):
        ks = jax.random.split(key, len(shapes))
        return tuple(_normal(k, s, dtype, 1.0 if i == 0 else s[-2] ** -0.5)
                     for i, (k, s) in enumerate(zip(ks, shapes)))
    return make


def _rmsnorm_inputs(rows: int, cols: int):
    def make(key):
        k1, k2 = jax.random.split(key)
        return (_normal(k1, (rows, cols), jnp.bfloat16),
                _normal(k2, (cols,), jnp.bfloat16))
    return make


def _ssd_inputs(bh: int, s: int, p: int, n: int):
    def make(key):
        ks = jax.random.split(key, 4)
        return (_normal(ks[0], (bh, s, p), jnp.float32),
                -jnp.abs(_normal(ks[1], (bh, s), jnp.float32, 0.2)),
                _normal(ks[2], (bh, s, n), jnp.float32, n ** -0.5),
                _normal(ks[3], (bh, s, n), jnp.float32, n ** -0.5))
    return make


def _gemm(c, t):
    return _mats((t * c["bm"], t * c["bk"]), (t * c["bk"], t * c["bn"]))


def _attention(c, t):
    shape = (1, t, t * max(c["bq"], c["bk"]), c["d"])  # B*H = t rows
    return _mats(shape, shape, shape)


# kernel -> (inputs builder (config, tiles) -> key -> inputs, the config
# keys its Pallas call takes as block sizes)
_INPUTS: Dict[str, Tuple[Callable, Tuple[str, ...]]] = {
    "matmul_leakyrelu": (_gemm, ("bm", "bn", "bk")),
    "fused_ff": (lambda c, t: _mats((t * c["bm"], t * c["bk"]),
                                    (t * c["bk"], t * c["bn"]),
                                    (t * c["bk"], t * c["bn"])),
                 ("bm", "bn", "bk")),
    "bmm": (lambda c, t: _mats((t, t * c["bm"], t * c["bk"]),
                               (t, t * c["bk"], t * c["bn"])),
            ("bm", "bn", "bk")),
    "flash_attention": (_attention, ("bq", "bk")),
    "softmax": (lambda c, t: _mats((t * c["br"], c["cols"])), ("br",)),
    "rmsnorm": (lambda c, t: _rmsnorm_inputs(t * c["br"], c["cols"]),
                ("br",)),
    "ssd": (lambda c, t: _ssd_inputs(t, t * c["chunk"], c["p"], c["n"]),
            ("chunk",)),
}
BUILTIN = tuple(_INPUTS)


def kernel_case(name: str, interpret: bool = False) -> KernelCase:
    """The check case of built-in kernel ``name`` at its first config."""
    kdef = get_kernel(name)
    cfg = kdef.configs[0]
    inputs, block_keys = _INPUTS[name]
    run = functools.partial(kdef.pallas_fn, interpret=interpret,
                            **{k: cfg[k] for k in block_keys})
    return KernelCase(name, inputs(cfg, TILES), run, kdef.ref_fn)


def kernel_cases(interpret: bool = False) -> List[KernelCase]:
    """One case per built-in kernel, in :data:`BUILTIN` order."""
    return [kernel_case(name, interpret) for name in BUILTIN]


def relative_error(got, want) -> float:
    """max |got - want| over max |want|: scale-free, so one bound serves
    every kernel and dtype."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))
