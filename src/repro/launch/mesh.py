"""Production meshes. Functions, not module constants: importing this module
never touches jax device state (the dry-run sets XLA_FLAGS before any init).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with all axes Auto-typed."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 v5e pod (256 chips); multi_pod adds the 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever this host has (1 CPU device here): for smoke tests/examples."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def make_sweep_mesh(n_shards: int, axis: str) -> Mesh:
    """1-D mesh over the first ``n_shards`` local devices for the sharded
    sweep runner (:mod:`repro.api.sweep`): ``axis`` is ``"cells"`` or
    ``"workers"``.  ``n_shards`` must not exceed the local device count
    (callers size it via :func:`repro.api.sweep.resolve_shard`, which picks
    the largest power of two that fits)."""
    return make_mesh((n_shards,), (axis,))


def device_summary() -> dict:
    """This host's accelerator inventory as a plain dict -- surfaced by the
    experiment service's ``GET /stats`` endpoint and stamped into bench
    provenance, so serve-side numbers always say what hardware (and how many
    sweep shards) produced them."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform if devs else "none",
        "device_count": len(devs),
        "sweep_shards": _pow2_floor(len(devs)),
    }


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_divisor(mesh: Mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out
