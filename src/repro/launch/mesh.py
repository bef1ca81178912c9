"""Mesh construction.  Functions, not module constants — importing this
module never touches jax device state.

Every mesh the program builds comes from :func:`make_mesh`, which gives all
axes the ``Auto`` type: the sharding rules (``repro.dist.sharding``) and the
activation constraints (``models.common.shard``) are written for GSPMD's
automatic propagation, and ``jax.make_mesh`` now defaults to ``Explicit``
axes, which ``with_sharding_constraint`` refuses.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh", "make_serve_mesh"]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types over ``devices`` (default:
    all local devices)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Production mesh: 16x16 (one 256-chip pod) or 2x16x16 (two pods).

    The ``pod`` axis is pure data-parallel; ``data`` carries DP+FSDP and
    ``model`` carries TP/EP (see repro.dist.sharding).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over the first ``data * model`` local devices (tests / examples)."""
    return make_mesh((data, model), ("data", "model"), jax.devices()[: data * model])


def make_serve_mesh(spec: str):
    """Build a serving mesh from a ``"dp,tp"`` CLI spec (e.g. ``"2,4"`` =
    data-parallel 2 x tensor-parallel 4 — the layout
    ``launch/serve.py --mesh`` and the sharded-serve tests use).  ``"1,1"``
    is the degenerate single-device mesh; the serve stack treats it exactly
    like no mesh at all (DESIGN.md §8).  Raises with an actionable message
    when the spec asks for more devices than exist (on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    try:
        data, model = (int(p) for p in spec.split(","))
    except ValueError as e:
        raise ValueError(f"--mesh expects 'dp,tp' (e.g. '2,4'), got {spec!r}") from e
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data}, model={model}")
    have = len(jax.devices())
    if data * model > have:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices but only {have} "
            f"exist; on CPU, force virtual devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data * model}"
        )
    return make_local_mesh(data, model)
