"""Dispatching wrappers: one call site per op, selectable implementation.

``impl="ref"`` is the pure-jnp oracle (XLA path — used by the model stack,
the dry-run and CPU training); ``impl="pallas"`` is the TPU kernel
(``interpret=True`` executes the kernel body on CPU for validation).
Model code calls these, so flipping a config flag swaps the backend per op.
"""

from __future__ import annotations

from typing import Literal

from repro.kernels import (bmm as _bmm_mod, flash_attention as _fa_mod,
                           fused_ff as _ff_mod,
                           matmul_leakyrelu as _mm_mod, ref,
                           rmsnorm as _rms_mod, softmax as _sm_mod,
                           ssd as _ssd_mod)

Impl = Literal["ref", "pallas", "pallas_interpret"]


def _interp(impl: Impl) -> bool:
    return impl == "pallas_interpret"


def matmul_leakyrelu(a, b, *, impl: Impl = "ref", **kw):
    if impl == "ref":
        return ref.matmul_leakyrelu(a, b, kw.get("negative_slope", 0.01))
    return _mm_mod.matmul_leakyrelu(a, b, interpret=_interp(impl), **kw)


def bmm(a, b, *, impl: Impl = "ref", **kw):
    if impl == "ref":
        return ref.bmm(a, b)
    return _bmm_mod.bmm(a, b, interpret=_interp(impl), **kw)


def fused_ff(x, w_gate, w_up, *, impl: Impl = "ref", **kw):
    if impl == "ref":
        return ref.fused_ff(x, w_gate, w_up)
    return _ff_mod.fused_ff(x, w_gate, w_up, interpret=_interp(impl), **kw)


def softmax(x, *, impl: Impl = "ref", **kw):
    if impl == "ref":
        return ref.softmax(x)
    return _sm_mod.softmax(x, interpret=_interp(impl), **kw)


def rmsnorm(x, gamma, *, impl: Impl = "ref", **kw):
    if impl == "ref":
        return ref.rmsnorm(x, gamma, kw.get("eps", 1e-6))
    return _rms_mod.rmsnorm(x, gamma, interpret=_interp(impl), **kw)


def flash_attention(q, k, v, *, impl: Impl = "ref", causal=True, **kw):
    if impl == "ref":
        return ref.flash_attention(q, k, v, causal=causal,
                                   scale=kw.get("scale"))
    return _fa_mod.flash_attention(q, k, v, causal=causal,
                                   interpret=_interp(impl), **kw)


def ssd(x, a, b, c, *, impl: Impl = "ref", **kw):
    """Flat-head layout: x (BH, S, P); a (BH, S); b, c (BH, S, N)."""
    if impl == "ref":
        y = ref.ssd_chunk(x[:, :, None, :], a[:, :, None],
                          b[:, :, None, :], c[:, :, None, :])
        return y[:, :, 0, :]
    return _ssd_mod.ssd(x, a, b, c, interpret=_interp(impl), **kw)
