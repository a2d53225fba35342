"""Run a Pallas kernel inside a region whose mesh axes are still Auto.

The train step calls kernels inside a ``shard_map`` that leaves the
``model`` axis to GSPMD, and XLA cannot partition a Mosaic call.
``manual_over_auto`` makes the axes still Auto at the call site manual for
the kernel alone: every operand replicated, or split as the caller's specs
say.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P


def auto_axes() -> dict[str, int]:
    """Size of each mesh axis still Auto where the caller is traced."""
    mesh = jax.sharding.get_abstract_mesh()
    return {a: n for a, n, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types) if t == AxisType.Auto}


def partition_seen() -> bool:
    """Whether the caller sees how its operands are partitioned: it is
    traced under a mesh, or there is one device. A jit over several devices
    without a mesh in context leaves the partition to GSPMD, unseen."""
    return (not jax.sharding.get_abstract_mesh().empty
            or jax.device_count() == 1)


def manual_over_auto(fn, in_specs=P(), out_specs=P()):
    """``fn`` as it is where no axis is Auto; else run on each device of the
    Auto axes with its operands split by ``in_specs`` (default: whole)."""
    auto = auto_axes()
    if not auto:
        return fn
    return jax.shard_map(fn, mesh=jax.sharding.get_abstract_mesh(),
                         in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(auto), check_vma=False)
