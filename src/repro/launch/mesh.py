"""Production mesh construction (DESIGN.md §5).

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run pins the device count *before* any jax
initialization).

Axis layout (outermost → innermost): ``pod`` (multi-pod replica groups),
``pipe`` (pipeline stages, carved out of the data-parallel dimension),
``data`` (within-pod DP / FSDP), ``model`` (tensor/expert parallelism).
``pod``/``pipe`` only appear when their size is > 1, so meshes built
without them keep the original two- or three-axis shape.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax


def _auto(axes: Tuple[str, ...]):
    """``Auto`` axis types: the partitioner propagates shardings, as the
    sharding rules and ``with_sharding_constraint`` calls expect (JAX's
    ``make_mesh`` otherwise builds ``Explicit`` axes)."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False, pipe: int = 1):
    """single-pod: (data=16, model=16) = 256 chips;
    multi-pod:  (pod=2, data=16, model=16) = 512 chips.

    ``pipe > 1`` carves the pipeline axis out of the 16-way data dimension
    (same chip count): e.g. ``pipe=4`` -> (pipe=4, data=4, model=16).
    """
    if 16 % pipe:
        raise ValueError(f"pipe={pipe} must divide the 16-way data axis")
    shape: Tuple[int, ...] = ()
    axes: Tuple[str, ...] = ()
    if multi_pod:
        shape, axes = (2,), ("pod",)
    if pipe > 1:
        shape, axes = shape + (pipe,), axes + ("pipe",)
    shape += (16 // pipe, 16)
    axes += ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def host_mesh_shape(n_devices: int, *, model: int = 1,
                    data: Optional[int] = None, pipe: Optional[int] = None,
                    pods: Optional[int] = None):
    """Pure shape arithmetic behind :func:`make_host_mesh` (unit-testable
    without devices).  Returns ``(shape, axis_names)``.

    ``pipe``/``pods`` compose with ``data``/``model`` instead of replacing
    them: the data dimension defaults to whatever devices remain after the
    other axes take their share.
    """
    pipe = pipe or 1
    pods = pods or 1
    if data is None:
        denom = pods * pipe * model
        if n_devices % denom:
            raise ValueError(
                f"{n_devices} devices not divisible by pods*pipe*model="
                f"{denom}")
        data = n_devices // denom
    shape: Tuple[int, ...] = ()
    axes: Tuple[str, ...] = ()
    if pods > 1:
        shape, axes = shape + (pods,), axes + ("pod",)
    if pipe > 1:
        shape, axes = shape + (pipe,), axes + ("pipe",)
    shape += (data, model)
    axes += ("data", "model")
    return shape, axes


def make_host_mesh(model: int = 1, data: Optional[int] = None,
                   pipe: Optional[int] = None, pods: Optional[int] = None):
    """Small meshes over whatever devices exist (tests / CPU smoke).

    ``make_host_mesh(pipe=4)`` on 8 devices builds
    ``(pipe=4, data=2, model=1)`` — the pipe axis composes with the others
    rather than silently dropping them.
    """
    shape, axes = host_mesh_shape(len(jax.devices()), model=model, data=data,
                                  pipe=pipe, pods=pods)
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))
