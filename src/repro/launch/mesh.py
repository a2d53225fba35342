"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and smoke tests/benches must keep seeing 1 device.

Mesh shapes (per the brief):
  single-pod : (16, 16)       axes (data, model)        = 256 chips
  multi-pod  : (2, 16, 16)    axes (pod, data, model)   = 512 chips

With pipeline parallelism (``pipe`` stages), the pipe axis splits the data
axis — total chip count is unchanged, the DP width shrinks by ``pipe``:
  single-pod : (pipe, 16/?, 16)      axes (pipe, data, model)
  multi-pod  : (2, pipe, ?, 16)      axes (pod, pipe, data, model)
Pod stays outermost (cross-pod links are the scarce resource); pipe sits
between pod and data so each pipeline stage owns a contiguous DP group.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_host_mesh",
    "make_pod_mesh",
    "dp_axes",
    "tp_axis",
    "pipe_size",
]


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    JAX 0.9 makes mesh axes ``Explicit`` by default, which puts sharding in
    the array types. The models and the partial-auto ``shard_map`` in
    dist/collectives.py leave the ``model`` axis to GSPMD's propagation, so
    every mesh here is built Auto.
    """
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False, pipe: int = 0):
    if pipe and pipe > 1:
        data = (16 * 16) // (pipe * 16)
        if data < 1 or (pipe * data * 16) != 256:
            raise ValueError(f"pipe={pipe} does not divide the 256-chip pod")
        if multi_pod:
            return _mesh((2, pipe, data, 16), ("pod", "pipe", "data", "model"))
        return _mesh((pipe, data, 16), ("pipe", "data", "model"))
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0, pipe: int = 0,
                   devices=None):
    """Small mesh over however many (fake or real) devices exist — tests.

    ``devices``: explicit device subset (elastic training builds one
    pod-local mesh per pod over disjoint subsets; ``None`` = all devices).
    """
    if pipe:
        if pod:
            return _mesh((pod, pipe, data, model),
                         ("pod", "pipe", "data", "model"), devices)
        return _mesh((pipe, data, model), ("pipe", "data", "model"), devices)
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"), devices)
    return _mesh((data, model), ("data", "model"), devices)


def make_pod_mesh(n_pods: int, devices=None):
    """1-D ``pod`` mesh carrying ONLY the rare compressed outer syncs.

    One device per pod (each pod's lead device); inner DP/TP traffic never
    crosses it. This is the axis the DiLoCo outer optimizer all-reduces
    the EDGC-compressed outer deltas over.
    """
    return _mesh((n_pods,), ("pod",), devices)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel (manual / EDGC-compressed) axes of a mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_axis(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None


def pipe_size(mesh) -> int:
    """Size of the pipeline axis (1 when the mesh has no ``pipe`` axis)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pipe", 1)
