"""Pallas TPU kernels for the PowerSGD compression hot-spots.

The compression pipeline touches the full (m, n) gradient three times per
step: P = (G + E) @ Q, Q = (G + E)^T @ P_hat, and the decompress+residual
G_hat = P_hat Q^T / E' = (G + E) - G_hat. Uncompressed these are four HBM
sweeps of the gradient (EF add, two factor matmuls, residual); the kernels
fuse the EF add into each consumer so every sweep reads G and E exactly once
— the arithmetic intensity of the factor matmuls is ~r FLOPs/byte, so they
are HBM-bound and the fusion is worth exactly one sweep (~25%).

Tiling: (bm, bn) VMEM tiles of the gradient, MXU-aligned (multiples of 128
on the contracting dims); the thin factor (n x r or m x r panel, r <= 256)
stays resident across the accumulation grid axis. fp32 accumulation.

Every entry point takes ``interpret`` with no default: ``True`` on CPU (how
tests validate them against ref.py), ``False`` to compile for the TPU with
the same BlockSpecs. ops.py picks it from the backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

F32 = jnp.float32


def _tile(dim: int, pref: int) -> int:
    """Largest MXU-friendly tile <= pref that divides dim (fallback: dim)."""
    for t in (pref, pref // 2, pref // 4, 256, 128):
        if t and t <= dim and dim % t == 0:
            return t
    return dim


# --------------------------------------------------------------- P = (G+E)@Q
def _p_kernel(g_ref, e_ref, q_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    m_blk = g_ref[...].astype(F32) + e_ref[...].astype(F32)   # fused EF add
    o_ref[...] += jnp.dot(m_blk, q_ref[...].astype(F32),
                          preferred_element_type=F32)


def ef_lowrank_p(grad, err, q, *, bm: int = 256, bn: int = 512,
                 interpret: bool):
    """P = (grad + err) @ q.  grad/err (m, n), q (n, r) -> (m, r) fp32."""
    m, n = grad.shape
    r = q.shape[1]
    bm, bn = _tile(m, bm), _tile(n, bn)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _p_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, r), F32),
        interpret=interpret,
    )(grad, err, q)


# ------------------------------------------------------------ Q = (G+E)^T@P
def _q_kernel(g_ref, e_ref, p_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    m_blk = g_ref[...].astype(F32) + e_ref[...].astype(F32)
    o_ref[...] += jnp.dot(m_blk.T, p_ref[...].astype(F32),
                          preferred_element_type=F32)


def ef_lowrank_q(grad, err, p_hat, *, bm: int = 512, bn: int = 256,
                 interpret: bool):
    """Q = (grad + err)^T @ p_hat.  grad/err (m, n), p_hat (m, r) -> (n, r)."""
    m, n = grad.shape
    r = p_hat.shape[1]
    bm, bn = _tile(m, bm), _tile(n, bn)
    grid = (n // bn, m // bm)   # accumulate over m
    return pl.pallas_call(
        _q_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((bm, r), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, r), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r), F32),
        interpret=interpret,
    )(grad, err, p_hat)


# --------------------------------------- G_hat = P Q^T ; E' = (G+E) - G_hat
def _dec_kernel(p_ref, q_ref, g_ref, e_ref, ghat_ref, newerr_ref):
    g_hat = jnp.dot(p_ref[...].astype(F32), q_ref[...].astype(F32).T,
                    preferred_element_type=F32)
    ghat_ref[...] = g_hat.astype(ghat_ref.dtype)
    m_blk = g_ref[...].astype(F32) + e_ref[...].astype(F32)
    newerr_ref[...] = (m_blk - g_hat).astype(newerr_ref.dtype)


def decompress_residual(p_hat, q, grad, err, *, bm: int = 256, bn: int = 512,
                        interpret: bool):
    """(g_hat, new_err) both (m, n), one pass, no accumulation grid axis."""
    m, n = grad.shape
    r = q.shape[1]
    bm, bn = _tile(m, bm), _tile(n, bn)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _dec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), grad.dtype),
            jax.ShapeDtypeStruct((m, n), grad.dtype),
        ],
        interpret=interpret,
    )(p_hat, q, grad, err)


# ------------------------------------------------------- Gram-Schmidt panel
def _gs_kernel(p_ref, o_ref, *, r: int, eps: float):
    """Single-block Gram-Schmidt; the (m, r) panel lives in VMEM.

    r is static and small (<= 256); each column step is a few masked VPU
    passes over the resident panel.
    """
    o_ref[...] = _gram_schmidt(p_ref[...].astype(F32), r, eps)


def _gram_schmidt(p, r: int, eps: float):
    """Gram-Schmidt on an (m, r) value, column by column, on the whole panel.

    Mosaic lowers neither a scatter (``p.at[:, i].set``) nor a matrix-vector
    product against an unaligned column slice, so every step is a masked
    full-panel VPU pass: extract column i, project out the columns before
    it (twice: one classical pass loses orthogonality as cond(p)^2 * eps),
    normalize, and write it back with a column-mask select. The column
    loop is a ``fori_loop``; unrolled, its compile time grows with m * r^2.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)

    def column(i, p):
        at_i = col == i
        v = jnp.sum(jnp.where(at_i, p, 0.0), axis=1, keepdims=True)  # (m, 1)
        for _ in range(2):   # projecting twice keeps ill-conditioned panels
            coef = jnp.sum(jnp.where(col < i, p, 0.0) * v, axis=0,
                           keepdims=True)                # (1, r), zero >= i
            v = v - jnp.sum(p * coef, axis=1, keepdims=True)
        v = v / (jnp.sqrt(jnp.sum(v * v)) + eps)
        return jnp.where(at_i, v, p)

    return jax.lax.fori_loop(0, r, column, p)


def gram_schmidt_panel(p, *, eps: float = 1e-8, interpret: bool):
    """Orthonormalize an (m, r) panel in one VMEM-resident kernel call.

    VMEM budget: m * r * 4 bytes (<= ~4 MB for m=16384, r=64). ops.py falls
    back to jnp QR above that.
    """
    m, r = p.shape
    return pl.pallas_call(
        functools.partial(_gs_kernel, r=r, eps=eps),
        grid=(1,),
        in_specs=[pl.BlockSpec((m, r), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((m, r), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, r), F32),
        interpret=interpret,
    )(p)


# -------------------------------------------------- batched (E, m, n) stacks
# Entry points for the bucketed sync executor (core/bucketing.py): a shape
# group stacks E same-shaped gradients, and the grid grows a leading E axis
# so one kernel launch sweeps the whole stack. Block shapes keep a leading 1
# on the stack axis; the VMEM working set per program is identical to the
# 2-D kernels'.

def _p3_kernel(g_ref, e_ref, q_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    m_blk = g_ref[0].astype(F32) + e_ref[0].astype(F32)   # fused EF add
    o_ref[0] += jnp.dot(m_blk, q_ref[0].astype(F32),
                        preferred_element_type=F32)


def ef_lowrank_p_batched(grad, err, q, *, bm: int = 256, bn: int = 512,
                         interpret: bool):
    """P[e] = (grad[e] + err[e]) @ q[e].  (E, m, n) x (E, n, r) -> (E, m, r)."""
    num_e, m, n = grad.shape
    r = q.shape[-1]
    bm, bn = _tile(m, bm), _tile(n, bn)
    grid = (num_e, m // bm, n // bn)    # accumulate over j (fastest axis)
    return pl.pallas_call(
        _p3_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, bn, r), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, r), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((num_e, m, r), F32),
        interpret=interpret,
    )(grad, err, q)


def _q3_kernel(g_ref, e_ref, p_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    m_blk = g_ref[0].astype(F32) + e_ref[0].astype(F32)
    o_ref[0] += jnp.dot(m_blk.T, p_ref[0].astype(F32),
                        preferred_element_type=F32)


def ef_lowrank_q_batched(grad, err, p_hat, *, bm: int = 512, bn: int = 256,
                         interpret: bool):
    """Q[e] = (grad[e] + err[e])^T @ p_hat[e].  -> (E, n, r)."""
    num_e, m, n = grad.shape
    r = p_hat.shape[-1]
    bm, bn = _tile(m, bm), _tile(n, bn)
    grid = (num_e, n // bn, m // bm)    # accumulate over m (fastest axis)
    return pl.pallas_call(
        _q3_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, bm, bn), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, bm, r), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, r), lambda b, j, i: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((num_e, n, r), F32),
        interpret=interpret,
    )(grad, err, p_hat)


def _dec3_kernel(p_ref, q_ref, g_ref, e_ref, ghat_ref, newerr_ref):
    g_hat = jnp.dot(p_ref[0].astype(F32), q_ref[0].astype(F32).T,
                    preferred_element_type=F32)
    ghat_ref[0] = g_hat.astype(ghat_ref.dtype)
    m_blk = g_ref[0].astype(F32) + e_ref[0].astype(F32)
    newerr_ref[0] = (m_blk - g_hat).astype(newerr_ref.dtype)


def decompress_residual_batched(p_hat, q, grad, err, *, bm: int = 256,
                                bn: int = 512, interpret: bool):
    """(g_hat, new_err) both (E, m, n); one pass, no accumulation axis."""
    num_e, m, n = grad.shape
    r = q.shape[-1]
    bm, bn = _tile(m, bm), _tile(n, bn)
    grid = (num_e, m // bm, n // bn)
    return pl.pallas_call(
        _dec3_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, r), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bn, r), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_e, m, n), grad.dtype),
            jax.ShapeDtypeStruct((num_e, m, n), grad.dtype),
        ],
        interpret=interpret,
    )(p_hat, q, grad, err)


def _gs3_kernel(p_ref, o_ref, *, r: int, eps: float):
    o_ref[0] = _gram_schmidt(p_ref[0].astype(F32), r, eps)


def gram_schmidt_panel_batched(p, *, eps: float = 1e-8,
                               interpret: bool):
    """Per-slice Gram-Schmidt over an (E, m, r) stack; grid over E, one
    VMEM-resident (m, r) panel per program (same budget as the 2-D panel)."""
    num_e, m, r = p.shape
    return pl.pallas_call(
        functools.partial(_gs3_kernel, r=r, eps=eps),
        grid=(num_e,),
        in_specs=[pl.BlockSpec((1, m, r), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, m, r), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_e, m, r), F32),
        interpret=interpret,
    )(p)
