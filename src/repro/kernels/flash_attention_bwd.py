"""Pallas flash attention for training: forward with LSE, and its backward.

Standard recompute-form backward (no materialized scores in HBM):

  D  = rowsum(dO ∘ O)                      (per query row)
  P  = exp(S - L)     with L = m + log(l)  (recomputed per tile)
  dV = Σ_q  Pᵀ dO
  dP = dO Vᵀ
  dS = P ∘ (dP - D)
  dQ = Σ_k  dS K · scale
  dK = Σ_q  dSᵀ Q · scale

Two kernels with transposed grids (the classic split):
  * dq kernel : grid (BH, n_q, n_k) — dQ tile accumulates across k tiles;
  * dkv kernel: grid (BH, n_k, n_q) — dK/dV tiles accumulate across q tiles.
    It works on Sᵀ = K Qᵀ, so L and D broadcast as rows and no tile is
    transposed.

Matrix products take their operands in the input dtype and accumulate in
float32; P and dS are cast to that dtype before their products, as the
model's matmuls do. The running max, sum, L, D and the accumulators stay
float32. Under causal masking a tile above the diagonal is skipped, and its
index map re-uses the block already resident, so it costs no DMA; only the
tiles the diagonal crosses are masked.

``flash_attention_train`` wires fwd+bwd through jax.custom_vjp; the fwd
saves (O, LSE) — the standard memory footprint (1 extra row per query).
Where mesh axes are still Auto at the call (the ``model`` axis of the
train step), the forward and the backward each run manual over them
(``spmd.manual_over_auto``), heads split over ``model`` as Megatron's
column-parallel wq/wk/wv leave them and the batch over the other Auto axes
(``partition``), so no device gathers another's heads. Oracle: jax.grad of
ref.flash_reference (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .spmd import auto_axes, manual_over_auto

F32 = jnp.float32
NEG_INF = -1e30
NT = (((1,), (1,)), ((), ()))          # a @ bᵀ
PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, NT, preferred_element_type=F32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


def _causal_tiles(body, causal: bool, q0, k0, bq: int, bk: int):
    """Run ``body(masked)`` on a (q tile, k tile) pair: skip it above the
    diagonal, mask it where the diagonal crosses it."""
    if not causal:
        body(False)
        return
    below = k0 + bk - 1 <= q0           # every key precedes every query
    crosses = (k0 <= q0 + bq - 1) & jnp.logical_not(below)
    pl.when(below)(lambda: body(False))
    pl.when(crosses)(lambda: body(True))


def _last_k(i, bq: int, bk: int):
    """Last k tile that q tile ``i`` sees under causal masking."""
    return (i * bq + bq - 1) // bk


def _first_q(i, bq: int, bk: int):
    """First q tile that sees k tile ``i`` under causal masking."""
    return (i * bk) // bq


def _kv_index(rep: int, causal: bool, bq: int, bk: int):
    """K/V block of grid point (q head h, q tile i, k tile j): the kv head
    h // rep (GQA), and past the diagonal the last k tile q tile i sees."""
    def index(h, i, j):
        return (h // rep, jnp.minimum(j, _last_k(i, bq, bk)) if causal else j,
                0)
    return index


# ----------------------------------------------------------- fwd (with LSE)
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, bq: int, bk: int):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(masked):
        s = _dot_nt(q_ref[0], k_ref[0]) * scale
        if masked:
            q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0]
        acc_ref[...] = acc_ref[...] * alpha + _dot(p.astype(v.dtype), v)
        m_ref[...] = m_new

    _causal_tiles(body, causal, qb * bq, kb * bk, bq, bk)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # (bq, 1) column -> (1, bq) row, through an aligned transpose
        lse = jnp.broadcast_to(m_ref[...] + jnp.log(l), (bq, 128))
        lse_ref[...] = lse.T[:1]


# ----------------------------------------------------------------- dq kernel
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale: float, causal: bool, bq: int, bk: int):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        k = k_ref[0]
        s = _dot_nt(q_ref[0], k) * scale
        if masked:
            q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = _dot_nt(do_ref[0], v_ref[0])
        ds = p * (dp - delta_ref[0][:, None])
        acc_ref[...] += _dot(ds.astype(k.dtype), k)

    _causal_tiles(body, causal, qb * bq, kb * bk, bq, bk)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------- dkv kernel
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale: float, causal: bool, bq: int, bk: int):
    kb, qb = pl.program_id(1), pl.program_id(2)   # inner axis: q tiles

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        q, do = q_ref[0], do_ref[0]
        st = _dot_nt(k_ref[0], q) * scale                     # (bk, bq)
        if masked:
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            st = jnp.where(q_pos >= k_pos, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[...])
        dv_acc[...] += _dot(pt.astype(do.dtype), do)
        dpt = _dot_nt(v_ref[0], do)
        dst = pt * (dpt - delta_ref[...])
        dk_acc[...] += _dot(dst.astype(q.dtype), q)

    _causal_tiles(body, causal, qb * bq, kb * bk, bq, bk)

    @pl.when(qb == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


DKV_KERNEL = _dkv_kernel.__name__


# ------------------------------------------------------------------ plumbing
def _heads_first(x):
    """(B, T, H, Dh) -> (B*H, T, Dh)."""
    B, T, H, Dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, Dh)


def _heads_last(x, B: int):
    """(B*H, T, Dh) -> (B, T, H, Dh)."""
    BH, T, Dh = x.shape
    return x.reshape(B, BH // B, T, Dh).transpose(0, 2, 1, 3)


def _row_spec(bq: int, index):
    """A (1, bq) block of a per-query f32 row array (BH, 1, T)."""
    return pl.BlockSpec((None, 1, bq), lambda h, i, j: (h, 0, index(i, j)))


def _fwd_with_stats(q, k, v, *, causal, bq, bk, interpret):
    B, Tq, H, Dh = q.shape
    _, Tk, Hkv, _ = k.shape
    rep = H // Hkv
    scale = 1.0 / math.sqrt(Dh)
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)

    kv_index = _kv_index(rep, causal, bq, bk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, Dh), kv_index),
            pl.BlockSpec((1, bk, Dh), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dh), lambda h, i, j: (h, i, 0)),
            _row_spec(bq, lambda i, j: i),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, Dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Tq), F32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, Dh), F32), pltpu.VMEM((bq, 1), F32),
                        pltpu.VMEM((bq, 1), F32)],
        compiler_params=PARALLEL,
        interpret=interpret,
    )(qf, kf, vf)
    return _heads_last(o, B), lse.reshape(B, H, Tq)


def _bwd(q, k, v, o, lse, do, *, causal, bq, bk, interpret):
    B, Tq, H, Dh = q.shape
    _, Tk, Hkv, _ = k.shape
    rep = H // Hkv
    scale = 1.0 / math.sqrt(Dh)
    BH = B * H
    lse = lse.reshape(BH, 1, Tq)
    qf, kf, vf, dof = map(_heads_first, (q, k, v, do))
    delta = jnp.sum(dof.astype(F32) * _heads_first(o).astype(F32),
                    axis=-1)[:, None, :]                      # (BH, 1, Tq)

    kv_index = _kv_index(rep, causal, bq, bk)
    q_index = lambda h, i, j: (h, i, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(BH, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), q_index),
            pl.BlockSpec((1, bk, Dh), kv_index),
            pl.BlockSpec((1, bk, Dh), kv_index),
            pl.BlockSpec((1, bq, Dh), q_index),
            _row_spec(bq, lambda i, j: i),
            _row_spec(bq, lambda i, j: i),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), q_index),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, Dh), F32)],
        compiler_params=PARALLEL,
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # dK/dV accumulate over q tiles PER Q-HEAD; GQA sums the groups after.
    def q_block(i, j):
        return jnp.maximum(j, _first_q(i, bq, bk)) if causal else j

    kv_q_index = lambda h, i, j: (h // rep, i, 0)
    qo_index = lambda h, i, j: (h, q_block(i, j), 0)
    dkv_dtype = k.dtype if rep == 1 else F32
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(BH, Tk // bk, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), qo_index),
            pl.BlockSpec((1, bk, Dh), kv_q_index),
            pl.BlockSpec((1, bk, Dh), kv_q_index),
            pl.BlockSpec((1, bq, Dh), qo_index),
            _row_spec(bq, q_block),
            _row_spec(bq, q_block),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, Dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, Dh), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, Dh), dkv_dtype),
            jax.ShapeDtypeStruct((BH, Tk, Dh), dkv_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, Dh), F32), pltpu.VMEM((bk, Dh), F32)],
        compiler_params=PARALLEL,
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # GQA: sum the rep query heads sharing each kv head
    dk = _heads_last(dk, B).reshape(B, Tk, Hkv, rep, Dh).sum(axis=3)
    dv = _heads_last(dv, B).reshape(B, Tk, Hkv, rep, Dh).sum(axis=3)
    return _heads_last(dq, B), dk.astype(k.dtype), dv.astype(v.dtype)


# ------------------------------------------------------------- partition
def partition(auto: dict[str, int]):
    """Specs of the (B, T, H, Dh) operands and the (B, H, T) LSE over the
    Auto mesh axes ``auto``: heads over ``model``, batch over the rest."""
    heads = "model" if "model" in auto else None
    batch = tuple(a for a in auto if a != "model") or None
    return P(batch, None, heads, None), P(batch, heads, None)


def partitions(batch: int, kv_heads: int, auto: dict[str, int]) -> bool:
    """Whether ``partition(auto)`` splits ``batch`` and ``kv_heads`` evenly."""
    return (kv_heads % auto.get("model", 1) == 0
            and batch % math.prod(n for a, n in auto.items()
                                  if a != "model") == 0)


# ------------------------------------------------------------- custom_vjp op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_train(q, k, v, causal: bool = True, bq: int = 256,
                          bk: int = 256, interpret: bool = True):
    return _vjp_fwd(q, k, v, causal, bq, bk, interpret)[0]


def _vjp_fwd(q, k, v, causal, bq, bk, interpret):
    x, rows = partition(auto_axes())
    fwd = functools.partial(_fwd_with_stats, causal=causal,
                            bq=min(bq, q.shape[1]), bk=min(bk, k.shape[1]),
                            interpret=interpret)
    o, lse = manual_over_auto(fwd, (x, x, x), (x, rows))(q, k, v)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, bq, bk, interpret, res, do):
    x, rows = partition(auto_axes())
    bwd = functools.partial(_bwd, causal=causal, bq=min(bq, res[0].shape[1]),
                            bk=min(bk, res[1].shape[1]), interpret=interpret)
    return manual_over_auto(bwd, (x, x, x, x, rows, x), (x, x, x))(*res, do)


flash_attention_train.defvjp(_vjp_fwd, _vjp_bwd)
