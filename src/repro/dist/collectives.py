"""Data-parallel gradient-sync collectives (the manual pod/data axes).

EDGC's contribution lives here: the DP gradient all-reduce that the
compressor intercepts. The train step runs its body in a shard_map MANUAL
region over the ("pod", "data") axes, and the primitives below are what it
calls inside that region:

  * ``make_dp_pmean(axes)`` / ``make_dp_psum(axes)`` — mean/sum over the
    manual DP axes, identity when there are none (single worker). These are
    the ``psum_mean`` hooks handed to ``repro.core.compressor.sync_grads``:
    compressed leaves pmean their rank-r PowerSGD factors, everything else
    pmeans in full.
  * ``dp_sync_grads`` — the one-call entry point: compress -> pmean ->
    decompress with error feedback under a CompressionPlan.
  * ``shard_map_dp`` — ``jax.shard_map`` with the DP axes manual and the
    rest (``model``) left to GSPMD.
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax

from repro.core.compressor import CompressionPlan, sync_grads

__all__ = [
    "dp_sync_grads",
    "dp_world_size",
    "make_dp_pmean",
    "make_dp_psum",
    "shard_map_dp",
]


def dp_world_size(mesh) -> int:
    """Number of data-parallel workers = product of the (pod, data) sizes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return math.prod(sizes.get(a, 1) for a in ("pod", "data"))


def make_dp_pmean(axes) -> Callable[[Any], Any]:
    """Mean over the manual DP axes; identity for an empty axis set.

    Works on a single array or a whole pytree (gradient trees, metrics).
    Must be called inside the shard_map region that binds ``axes``.
    """
    axes_t = tuple(axes)
    if not axes_t:
        return lambda x: x
    return lambda tree: jax.tree_util.tree_map(
        lambda a: jax.lax.pmean(a, axes_t), tree
    )


def make_dp_psum(axes) -> Callable[[Any], Any]:
    """Sum over the manual DP axes; identity for an empty axis set."""
    axes_t = tuple(axes)
    if not axes_t:
        return lambda x: x
    return lambda tree: jax.tree_util.tree_map(
        lambda a: jax.lax.psum(a, axes_t), tree
    )


def dp_sync_grads(grads: Any, comp_state: dict, plan: CompressionPlan,
                  axes, use_kernels: bool = False,
                  bucketed: bool | None = None) -> tuple[Any, dict]:
    """Compression-aware DP gradient sync over the manual ``axes``.

    Compressed leaves move rank-r factors through the pmean (with error
    feedback); the rest move in full. ``bucketed`` picks the executor
    (None = infer from the state format): the per-leaf loop, or the
    shape-grouped stacked + flat-bucket schedule from core/bucketing.py
    that collapses O(num_leaves) collectives to O(groups + buckets).
    Returns (synced grads, new state).
    """
    return sync_grads(grads, comp_state, plan, make_dp_pmean(axes),
                      use_kernels=use_kernels, bucketed=bucketed)


def shard_map_dp(f, mesh, in_specs, out_specs, manual_axes,
                 check: bool = False):
    """shard_map with ``manual_axes`` manual and every other axis AUTO.

    The 'model' axis stays AUTO so GSPMD applies the TP rules from
    dist/sharding.py inside the body, while the (pod, data) gradient sync
    is explicit (EDGC's compressed pmeans).
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual_axes),
                         check_vma=check)
