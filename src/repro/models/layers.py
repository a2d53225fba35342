"""Shared neural-net layers (pure functional JAX).

Conventions used across the zoo:

  * Params are nested dicts of jnp arrays; weight matrices are stored 2-D
    ``(in, out)`` (so the compressor's matricize is the identity) and
    homogeneous layer stacks carry a leading layer dim (scanned).
  * All matmuls accumulate in float32 (``preferred_element_type``) so bf16
    params are safe on the MXU target.
  * Attention is grouped-query (GQA) with optional qk-norm, qkv-bias,
    sliding window, RoPE or learned/sinusoidal positions; the prefill path
    is blockwise (online softmax) so 32k-token prefill never materializes a
    full (T x T) score matrix. Causal self-attention that the Pallas flash
    kernels tile runs through them on a compiled backend (``attn_apply``).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops

Params = dict[str, Any]
F32 = jnp.float32


# --------------------------------------------------------------------------- init
def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), F32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), F32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------- norms
def rms_norm(x, weight, eps: float = 1e-5):
    x32 = x.astype(F32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * weight.astype(F32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    x32 = x.astype(F32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(F32) + bias.astype(F32)).astype(x.dtype)


# --------------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float = 1e4):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (..., T, H, Dh); positions: (..., T) int32."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                          # (Dh/2,)
    ang = positions[..., :, None].astype(F32) * inv      # (..., T, Dh/2)
    cos = jnp.cos(ang)[..., :, None, :]                  # (..., T, 1, Dh/2)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(T: int, d: int, dtype=F32):
    pos = jnp.arange(T, dtype=F32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=F32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((T, d), F32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)


# --------------------------------------------------------------------------- mlp
def mlp_init(key, d_model: int, d_ff: int, dtype, gated: bool = True, bias: bool = False):
    ks = jax.random.split(key, 3)
    p: Params = {
        "up": dense_init(ks[0], d_model, d_ff, dtype),
        "down": dense_init(ks[1], d_ff, d_model, dtype),
    }
    if gated:
        p["gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    if bias:
        p["up_bias"] = jnp.zeros((d_ff,), dtype)
        p["down_bias"] = jnp.zeros((d_model,), dtype)
    return p


def mlp_apply(p: Params, x, act: str = "silu"):
    up = jnp.einsum("...d,df->...f", x, p["up"], preferred_element_type=F32)
    if "up_bias" in p:
        up = up + p["up_bias"].astype(F32)
    if "gate" in p:
        gate = jnp.einsum("...d,df->...f", x, p["gate"], preferred_element_type=F32)
        h = jax.nn.silu(gate) * up if act == "silu" else jax.nn.gelu(gate) * up
    else:
        h = jax.nn.gelu(up) if act == "gelu" else jax.nn.silu(up)
    h = h.astype(x.dtype)
    out = jnp.einsum("...f,fd->...d", h, p["down"], preferred_element_type=F32)
    if "down_bias" in p:
        out = out + p["down_bias"].astype(F32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- attention
def attn_init(
    key,
    d_model: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    dtype,
    qkv_bias: bool = False,
    qk_norm: bool = False,
):
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["q_bias"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["k_bias"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
        p["v_bias"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
    if qk_norm:
        p["q_norm_scale"] = jnp.ones((head_dim,), dtype)
        p["k_norm_scale"] = jnp.ones((head_dim,), dtype)
    return p


def _project_qkv(p, x, num_heads, num_kv_heads, head_dim, positions,
                 rope_theta, use_rope, norm_eps):
    B, T, _ = x.shape
    q = jnp.einsum("btd,de->bte", x, p["wq"], preferred_element_type=F32)
    k = jnp.einsum("btd,de->bte", x, p["wk"], preferred_element_type=F32)
    v = jnp.einsum("btd,de->bte", x, p["wv"], preferred_element_type=F32)
    if "q_bias" in p:
        q = q + p["q_bias"].astype(F32)
        k = k + p["k_bias"].astype(F32)
        v = v + p["v_bias"].astype(F32)
    q = q.reshape(B, T, num_heads, head_dim)
    k = k.reshape(B, T, num_kv_heads, head_dim)
    v = v.reshape(B, T, num_kv_heads, head_dim).astype(x.dtype)
    if "q_norm_scale" in p:
        q = rms_norm(q, p["q_norm_scale"], norm_eps)
        k = rms_norm(k, p["k_norm_scale"], norm_eps)
    if use_rope:
        q = apply_rope(q.astype(x.dtype), positions, rope_theta)
        k = apply_rope(k.astype(x.dtype), positions, rope_theta)
    return q.astype(x.dtype), k.astype(x.dtype), v


def blockwise_attention(
    q, k, v, *, causal: bool, q_offset: int | jax.Array = 0,
    window: int = 0, block_q: int = 512,
):
    """Memory-efficient attention: scan over query blocks, online softmax.

    q: (B, Tq, H, Dh); k, v: (B, Tk, Hkv, Dh) with H a multiple of Hkv (GQA).
    ``q_offset`` is the absolute position of q[0] (prefill: 0; decode: cache
    length). ``window`` > 0 masks keys older than ``window`` (sliding-window
    attention). Never materializes more than (block_q x Tk) scores.
    """
    B, Tq, H, Dh = q.shape
    _, Tk, Hkv, _ = k.shape
    rep = H // Hkv
    scale = 1.0 / math.sqrt(Dh)

    # pad Tq to a multiple of block_q
    pad = (-Tq) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = q.shape[1] // block_q
    qb = q.reshape(B, nblk, block_q, H, Dh).transpose(1, 0, 2, 3, 4)

    k_pos = jnp.arange(Tk)

    def one_block(carry, inp):
        qi, blk_idx = inp
        q_pos = q_offset + blk_idx * block_q + jnp.arange(block_q)
        # scores: (B, H, block_q, Tk)
        qh = qi.reshape(B, block_q, Hkv, rep, Dh)
        # converts ride inside the dots (preferred_element_type) — casting
        # the operands would materialize f32 copies of K/V (see attn_decode)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qh, k,
                       preferred_element_type=F32) * scale
        mask = jnp.ones((block_q, Tk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        # softmax weights downcast to the value dtype: a mixed f32xbf16 dot
        # makes XLA materialize (and under GSPMD, gather) an f32 copy of V
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v, preferred_element_type=F32)
        return carry, o.reshape(B, block_q, H, Dh).astype(v.dtype)

    _, outs = jax.lax.scan(one_block, None, (qb, jnp.arange(nblk)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nblk * block_q, H, Dh)
    return out[:, :Tq]


def attn_apply(
    p: Params, x, *, num_heads: int, num_kv_heads: int, head_dim: int,
    causal: bool = True, positions=None, rope_theta: float = 1e4,
    use_rope: bool = True, window: int = 0, norm_eps: float = 1e-5,
    block_q: int = 512,
):
    """Full-sequence (training / prefill) GQA attention.

    Causal self-attention without a window goes through the Pallas flash
    kernels where ``ops.flash_attention_fits`` (a compiled backend, a shape
    they tile, heads and batch split evenly over the Auto mesh axes);
    everything else through ``blockwise_attention``.
    """
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim,
                           positions, rope_theta, use_rope, norm_eps)
    if ops.flash_attention_fits(B, T, num_kv_heads, head_dim, causal=causal,
                                window=window):
        o = ops.flash_attention(q, k, v)
    else:
        o = blockwise_attention(q, k, v, causal=causal, window=window,
                                block_q=block_q)
    o = o.reshape(B, T, num_heads * head_dim)
    out = jnp.einsum("bte,ed->btd", o, p["wo"], preferred_element_type=F32)
    return out.astype(x.dtype)


def attn_decode(
    p: Params, x, cache_k, cache_v, cache_len, *, num_heads: int,
    num_kv_heads: int, head_dim: int, rope_theta: float = 1e4,
    use_rope: bool = True, window: int = 0, norm_eps: float = 1e-5,
):
    """Single-token decode against a KV cache.

    x: (B, 1, d); cache_k/v: (B, C, Hkv, Dh) where C = max context (full
    cache) or C = window (ring buffer); cache_len: scalar int32 = tokens
    already in the cache (absolute position of the new token).
    Returns (out (B,1,d), new_k, new_v).
    """
    B, _, _ = x.shape
    C = cache_k.shape[1]
    positions = jnp.broadcast_to(cache_len[None], (B, 1)) if jnp.ndim(cache_len) == 0 \
        else cache_len[:, None]
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim,
                           positions, rope_theta, use_rope, norm_eps)
    slot = cache_len % C if window > 0 else cache_len
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))

    rep = num_heads // num_kv_heads
    qh = q.reshape(B, 1, num_kv_heads, rep, head_dim)
    # NOTE: do NOT .astype(F32) the cache operand — that materializes (and
    # under GSPMD, gathers) a full-width copy of the cache; the convert is
    # free inside the MXU op via preferred_element_type.
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qh, cache_k,
                   preferred_element_type=F32)
    s = s / math.sqrt(head_dim)
    k_idx = jnp.arange(C)
    if window > 0:
        # ring buffer: valid slots are the last min(cache_len+1, C) writes
        age = (slot - k_idx) % C
        valid = age <= jnp.minimum(cache_len, C - 1)
    else:
        valid = k_idx <= cache_len
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    # same downcast rationale as blockwise_attention (avoids f32 V-cache copy)
    pattn = jax.nn.softmax(s, axis=-1).astype(cache_v.dtype)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", pattn, cache_v,
                   preferred_element_type=F32)
    o = o.reshape(B, 1, num_heads * head_dim).astype(x.dtype)
    out = jnp.einsum("bte,ed->btd", o, p["wo"], preferred_element_type=F32)
    return out.astype(x.dtype), cache_k, cache_v


def cross_attn_apply(p: Params, x, enc_k, enc_v, *, num_heads: int,
                     num_kv_heads: int, head_dim: int):
    """Cross-attention with precomputed encoder K/V (whisper decoder)."""
    B, T, _ = x.shape
    q = jnp.einsum("btd,de->bte", x, p["wq"], preferred_element_type=F32)
    q = q.reshape(B, T, num_heads, head_dim).astype(x.dtype)
    o = blockwise_attention(q, enc_k, enc_v, causal=False, block_q=min(512, max(T, 8)))
    o = o.reshape(B, T, num_heads * head_dim)
    out = jnp.einsum("bte,ed->btd", o, p["wo"], preferred_element_type=F32)
    return out.astype(x.dtype)


def cross_kv(p: Params, enc_out, *, num_kv_heads: int, head_dim: int):
    B, S, _ = enc_out.shape
    k = jnp.einsum("bsd,de->bse", enc_out, p["wk"], preferred_element_type=F32)
    v = jnp.einsum("bsd,de->bse", enc_out, p["wv"], preferred_element_type=F32)
    return (k.reshape(B, S, num_kv_heads, head_dim).astype(enc_out.dtype),
            v.reshape(B, S, num_kv_heads, head_dim).astype(enc_out.dtype))


# --------------------------------------------------------------------------- head
def lm_logits(x, embed_or_head, tie: bool):
    """Final projection to vocab; tied uses the embedding transposed."""
    w = embed_or_head
    if tie:
        return jnp.einsum("btd,vd->btv", x, w, preferred_element_type=F32)
    return jnp.einsum("btd,dv->btv", x, w, preferred_element_type=F32)


def cross_entropy(logits, labels, mask=None):
    """Mean next-token CE in nats; logits (B,T,V) fp32, labels (B,T) int32."""
    logits = logits.astype(F32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
