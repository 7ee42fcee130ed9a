"""Pallas TPU kernels for the paper's evaluated kernel set (Table 2) plus
the SSD chunk kernel for the assigned SSM architectures.

Each module ships: the ``pl.pallas_call`` kernel with explicit BlockSpec
VMEM tiling, a ``make_spec``/``CONFIGS`` pair for the schedule optimizer
(autotune space, §3.1), and a pure-jnp oracle in :mod:`repro.kernels.ref`.

``KERNELS`` is the registry the optimization session resolves kernel names
through; :func:`register_kernel` adds new entries (the built-in set below,
tests registering fixtures, downstream code registering its own kernels):

    from repro.kernels import register_kernel
    from repro.sched import KernelDef

    register_kernel(KernelDef("my_kernel", make_spec, CONFIGS))
    OptimizationSession().optimize(OptimizeRequest(kernel="my_kernel"))
"""

from typing import Dict

from repro.kernels import ref
from repro.sched.session import KernelDef

KERNELS: Dict[str, KernelDef] = {}


def register_kernel(kdef: KernelDef) -> KernelDef:
    """Register ``kdef`` under its name (last registration wins, so tests
    can shadow and restore entries).  Returns the definition, so it can be
    used as a decorator over ``KernelDef``-returning builders' results."""
    if not isinstance(kdef, KernelDef):
        raise TypeError(f"register_kernel expects a KernelDef, got {kdef!r}")
    KERNELS[kdef.name] = kdef
    return kdef


def unregister_kernel(name: str) -> None:
    """Remove a registry entry (test cleanup)."""
    KERNELS.pop(name, None)


def get_kernel(name: str) -> KernelDef:
    try:
        return KERNELS[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered kernels: "
                       f"{sorted(KERNELS)}") from None


def _register_builtins():
    from repro.kernels import (bmm, flash_attention, fused_ff,
                               matmul_leakyrelu, rmsnorm, softmax, ssd)
    for kdef in (
        KernelDef("matmul_leakyrelu", matmul_leakyrelu.make_spec,
                  matmul_leakyrelu.CONFIGS, matmul_leakyrelu.matmul_leakyrelu,
                  ref.matmul_leakyrelu),
        KernelDef("fused_ff", fused_ff.make_spec, fused_ff.CONFIGS,
                  fused_ff.fused_ff, ref.fused_ff),
        KernelDef("bmm", bmm.make_spec, bmm.CONFIGS, bmm.bmm, ref.bmm),
        KernelDef("flash_attention", flash_attention.make_spec,
                  flash_attention.CONFIGS, flash_attention.flash_attention,
                  ref.flash_attention),
        KernelDef("softmax", softmax.make_spec, softmax.CONFIGS,
                  softmax.softmax, ref.softmax),
        KernelDef("rmsnorm", rmsnorm.make_spec, rmsnorm.CONFIGS,
                  rmsnorm.rmsnorm, ref.rmsnorm),
        KernelDef("ssd", ssd.make_spec, ssd.CONFIGS, ssd.ssd, ref.ssd),
    ):
        register_kernel(kdef)


_register_builtins()

__all__ = ["KERNELS", "KernelDef", "get_kernel", "register_kernel",
           "unregister_kernel", "ref"]
