"""Mesh construction + axis bookkeeping (the single bootstrapping point).

Every launcher used to re-derive mesh shapes and axis contexts by hand; all
of that lives here now and is consumed through :class:`repro.api.Session`.
``build_mesh``/``mesh_and_axes`` are FUNCTIONS (importing this module never
touches jax device state).  Single pod: 16x16 = 256 chips (TPU v5e);
multi-pod: 2x16x16 = 512 — the leading ``pod`` axis extends data parallelism
(FL client cohorts double).
"""

from __future__ import annotations

import os
import pathlib

import jax

from repro.dist.collectives import AxisCtx

#: The persistent compilation cache's home when the environment names none:
#: a fixed directory in the checkout (git-ignored).  The path is part of
#: the cache key, so it must not move between runs.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is changed
    (jax reads it itself); otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)

_AXES_FOR_RANK = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}


def parse_mesh(spec: str) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """``"DATAxMODEL"`` / ``"PODxDATAxMODEL"`` -> (shape, axis names)."""
    shape = tuple(int(x) for x in str(spec).lower().split("x"))
    if len(shape) not in _AXES_FOR_RANK:
        raise ValueError(f"mesh spec {spec!r} must have 1-3 'x'-separated dims")
    return shape, _AXES_FOR_RANK[len(shape)]


def build_mesh(spec: str):
    """Mesh from a ``"2x16x16"``-style string (axis names inferred by rank)."""
    shape, axes = parse_mesh(spec)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_and_axes(spec: str):
    """The one-call bootstrap: (mesh, AxisCtx) from a mesh-spec string."""
    mesh = build_mesh(spec)
    return mesh, axis_ctx_for(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    return build_mesh("2x16x16" if multi_pod else "16x16")


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for CPU smoke tests (collectives become no-ops at size 1)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def axis_ctx_for(mesh) -> AxisCtx:
    names = tuple(mesh.axis_names)
    if "pod" in names:
        batch = ("pod", "data")
    else:
        batch = ("data",)
    model = "model" if "model" in names else None
    return AxisCtx(batch_axes=batch, model_axis=model, fsdp_axes=batch)


def mesh_axis_size(mesh, name: str | None) -> int:
    if name is None:
        return 1
    d = dict(zip(mesh.axis_names, mesh.devices.shape))
    return d.get(name, 1)


def tp_size(mesh, axes: AxisCtx) -> int:
    """Model-parallel (tensor-parallel) world size."""
    return mesh_axis_size(mesh, axes.model_axis)


def fsdp_size(mesh, axes: AxisCtx) -> int:
    """Product of the FSDP axes' sizes."""
    n = 1
    for a in axes.fsdp_axes:
        n *= mesh_axis_size(mesh, a)
    return n


def batch_size(mesh, axes: AxisCtx) -> int:
    """Product of the batch (data-parallel / FL-client) axes' sizes."""
    n = 1
    for a in axes.batch_axes:
        n *= mesh_axis_size(mesh, a)
    return n
