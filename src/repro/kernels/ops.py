"""Public jit'd wrappers for the Pallas kernels, with shape-aware fallbacks.

Callers use these; the wrappers pick interpret mode off the backend (CPU ->
interpret=True so the identical kernel bodies execute in Python), route
shapes the kernels can't tile (non-divisible, too large for a VMEM panel)
to the ref.py oracles, and handle dtype promotion.

The train step calls them inside a ``shard_map`` that leaves the ``model``
axis to GSPMD, and XLA cannot partition a Mosaic call. ``_run``
makes the axes still Auto at the call site manual for the kernel alone,
with every operand replicated (``spmd.manual_over_auto``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_walk import walk_trips

from . import entropy_hist as _hist
from . import flash_attention_bwd as _flash
from . import lowrank as _lr
from . import pack as _pack
from . import ref
from .spmd import auto_axes, manual_over_auto, partition_seen

F32 = jnp.float32


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _run(kernel, *args, **static):
    """Call a Pallas kernel: interpreted on CPU, compiled elsewhere.

    Inside a region with Auto mesh axes the kernel runs whole on each
    device of those axes, its operands replicated.
    """
    return manual_over_auto(partial(kernel, interpret=_interpret(),
                                    **static))(*args)


def _tileable(m: int, n: int) -> bool:
    return m % 128 == 0 and n % 128 == 0


@partial(jax.jit, static_argnames=())
def lowrank_p(grad, err, q):
    m, n = grad.shape
    if not _tileable(m, n):
        return ref.ef_lowrank_p(grad, err, q)
    return _run(_lr.ef_lowrank_p, grad, err, q)


@partial(jax.jit, static_argnames=())
def lowrank_q(grad, err, p_hat):
    m, n = grad.shape
    if not _tileable(m, n):
        return ref.ef_lowrank_q(grad, err, p_hat)
    return _run(_lr.ef_lowrank_q, grad, err, p_hat)


@partial(jax.jit, static_argnames=())
def decompress_residual(p_hat, q, grad, err):
    m, n = grad.shape
    if not _tileable(m, n):
        return ref.decompress_residual(p_hat, q, grad, err)
    return _run(_lr.decompress_residual, p_hat, q, grad, err)


@partial(jax.jit, static_argnames=())
def orthonormalize(p):
    """Gram-Schmidt panel kernel under ~4 MB VMEM, else jnp QR."""
    m, r = p.shape
    if m * r * 4 > (4 << 20) or m % 8 != 0:
        return jnp.linalg.qr(p.astype(F32))[0]
    return _run(_lr.gram_schmidt_panel, p)


# ------------------------------------------------ batched (E, m, n) stacks
# Wrappers for the bucketed executor's shape groups; same routing rules as
# the 2-D wrappers (interpret on CPU, ref/jnp fallback for untileable shapes)
# applied per stack.

@partial(jax.jit, static_argnames=())
def lowrank_p3(grad, err, q):
    _, m, n = grad.shape
    if not _tileable(m, n):
        return jax.vmap(ref.ef_lowrank_p)(grad, err, q)
    return _run(_lr.ef_lowrank_p_batched, grad, err, q)


@partial(jax.jit, static_argnames=())
def lowrank_q3(grad, err, p_hat):
    _, m, n = grad.shape
    if not _tileable(m, n):
        return jax.vmap(ref.ef_lowrank_q)(grad, err, p_hat)
    return _run(_lr.ef_lowrank_q_batched, grad, err, p_hat)


@partial(jax.jit, static_argnames=())
def decompress_residual3(p_hat, q, grad, err):
    _, m, n = grad.shape
    if not _tileable(m, n):
        return jax.vmap(ref.decompress_residual)(p_hat, q, grad, err)
    return _run(_lr.decompress_residual_batched, p_hat, q, grad, err)


@partial(jax.jit, static_argnames=())
def orthonormalize3(p):
    """Per-slice Gram-Schmidt panels under ~4 MB VMEM each, else jnp QR."""
    _, m, r = p.shape
    if m * r * 4 > (4 << 20) or m % 8 != 0:
        return jax.vmap(lambda x: jnp.linalg.qr(x.astype(F32))[0])(p)
    return _run(_lr.gram_schmidt_panel_batched, p)


# ------------------------------------------------ causal flash attention
# The model's training attention (models/layers.py::attn_apply). Interpreted,
# the kernels are far slower than XLA's blockwise attention, so the route
# takes them only on a compiled backend.

# q and k tile, cut to T where T is shorter: at T = 1024, Dh = 64 on v5e one
# whole-sequence tile beats 512 (fewer grid steps) and 256 or 128 by far
# (PERF.md §6).
FLASH_BLOCK = 1024


def _flash_backend() -> bool:
    return not _interpret()


def flash_attention_fits(B: int, T: int, kv_heads: int, head_dim: int, *,
                         causal: bool, window: int) -> bool:
    """Whether causal self-attention of B sequences of T tokens takes the
    flash kernels: on a compiled backend, without a window, at a shape they
    tile, where the partition is seen (``spmd.partition_seen``) and the
    mesh axes still Auto split the batch and the heads evenly
    (``flash_attention_bwd.partitions``)."""
    block = min(FLASH_BLOCK, T)
    return (_flash_backend() and causal and window == 0 and T % block == 0
            and block % 128 == 0 and head_dim % 16 == 0 and partition_seen()
            and _flash.partitions(B, kv_heads, auto_axes()))


def flash_attention_calls(jaxpr) -> int:
    """Flash attention calls a traced step makes per run: launches of the
    dK/dV kernel, one in each call's backward, every trip of an enclosing
    scan counted (``jaxpr_walk.walk_trips``)."""
    return sum(trips for eqn, trips in walk_trips(jaxpr)
               if eqn.primitive.name == "pallas_call"
               and eqn.params["jaxpr"].debug_info.func_name
               == _flash.DKV_KERNEL)


def flash_attention(q, k, v):
    """Causal GQA self-attention (B, T, H, Dh) through the Pallas flash
    forward and backward kernels (a custom VJP that saves O and the LSE)."""
    block = min(FLASH_BLOCK, q.shape[1])
    return _flash.flash_attention_train(q, k, v, True, block, block,
                                        _interpret())


# legacy alias used by core.powersgd's use_kernels path
def lowrank_matmul(m_mat, q):
    """M @ Q with the P-kernel (EF already folded into m_mat by the caller)."""
    zeros = jnp.zeros_like(m_mat)
    mm, nn = m_mat.shape
    if not _tileable(mm, nn):
        return m_mat.astype(F32) @ q.astype(F32)
    return _run(_lr.ef_lowrank_p, m_mat, zeros, q)


# ------------------------------------------------ wire-format bit packing
# b-bit code <-> uint32 word packing for core/wire.py. Small payloads (under
# one 512-word panel) route to the ref oracle — the padding would dominate —
# larger ones run the Pallas kernels (interpret on CPU, as above).

_PACK_BW = 512


@partial(jax.jit, static_argnames=("bits",))
def pack_bits(codes, bits: int):
    """Flat unsigned codes (n,) -> uint32 words (ceil(n / (32 // bits)),)."""
    epw = 32 // bits
    n = codes.shape[0]
    nwords = -(-n // epw)
    if nwords < _PACK_BW:
        return ref.pack_bits(codes, bits)
    nw_p = -(-nwords // _PACK_BW) * _PACK_BW
    c = jnp.pad(codes.astype(jnp.uint32), (0, nw_p * epw - n))
    slots = c.reshape(nw_p, epw).T            # row j = bit-slot j of each word
    words = _run(_pack.pack_words, slots, bits=bits, bw=_PACK_BW)
    return words[:nwords]


@partial(jax.jit, static_argnames=("bits", "n"))
def unpack_bits(words, bits: int, n: int):
    """Inverse of pack_bits: uint32 words -> first n int32 codes."""
    epw = 32 // bits
    nwords = words.shape[0]
    if nwords < _PACK_BW:
        return ref.unpack_bits(words, bits, n)
    nw_p = -(-nwords // _PACK_BW) * _PACK_BW
    w = jnp.pad(words, (0, nw_p - nwords))
    slots = _run(_pack.unpack_words, w, bits=bits, bw=_PACK_BW)
    return slots.T.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("num_bins", "range_sigmas"))
def sampled_entropy_hist(x, num_bins: int = 256, range_sigmas: float = 8.0):
    """Histogram differential entropy via the Pallas binning kernel."""
    eps = 1e-12
    x = x.astype(F32).reshape(-1)
    mu = jnp.mean(x)
    sigma = jnp.std(x) + eps
    lo = mu - range_sigmas * sigma
    width = (2.0 * range_sigmas * sigma) / num_bins
    counts = _run(_hist.hist_counts, x, lo, 1.0 / width, num_bins=num_bins)
    p = counts / x.shape[0]
    plogp = jnp.where(p > 0, p * jnp.log(p + eps), 0.0)
    return -jnp.sum(plogp) + jnp.log(width + eps)
