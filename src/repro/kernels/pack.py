"""Pallas kernels: bit-pack/unpack int codes for the entropy-coded wire.

The wire codec (core/wire.py) quantizes sync payloads to b-bit unsigned
codes (b in {4, 8}); these kernels pack 32//b codes into each uint32 word
and back. The pack -> unpack round trip is bit-exact, which is what lets
the coded sync path keep PR 6's chunked-vs-monolithic equality at the
coded-payload level.

Layout: the ops wrapper reshapes the flat code vector to (epw, nwords) --
row j holds bit-slot j of every word -- so the kernel only does contiguous
row slices (no in-kernel reshapes or strided loads). Grid is over word
blocks; each program ORs epw shifted rows into its (1, bw) word block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

U32 = jnp.uint32


def _pack_kernel(c_ref, w_ref, *, bits: int):
    epw = 32 // bits
    word = c_ref[0:1, :].astype(U32)
    for j in range(1, epw):
        word = word | (c_ref[j:j + 1, :].astype(U32) << U32(j * bits))
    w_ref[...] = word


def _unpack_kernel(w_ref, c_ref, *, bits: int):
    epw = 32 // bits
    mask = U32((1 << bits) - 1)
    w = w_ref[...]                                   # (1, bw) uint32
    rows = [((w >> U32(j * bits)) & mask).astype(jnp.int32)
            for j in range(epw)]
    c_ref[...] = jnp.concatenate(rows, axis=0)       # (epw, bw)


def pack_words(slots: jax.Array, *, bits: int, bw: int = 512,
               interpret: bool) -> jax.Array:
    """Pack slot-major codes (epw, nwords) -> uint32 words (nwords,).

    nwords must be a multiple of bw (the ops wrapper pads).
    """
    epw, nwords = slots.shape
    assert epw == 32 // bits and nwords % bw == 0
    words = pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits),
        grid=(nwords // bw,),
        in_specs=[pl.BlockSpec((epw, bw), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, bw), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nwords), U32),
        interpret=interpret,
    )(slots)
    return words[0]


def unpack_words(words: jax.Array, *, bits: int, bw: int = 512,
                 interpret: bool) -> jax.Array:
    """Unpack uint32 words (nwords,) -> slot-major int32 codes (epw, nwords)."""
    epw = 32 // bits
    nwords = words.shape[0]
    assert nwords % bw == 0
    return pl.pallas_call(
        functools.partial(_unpack_kernel, bits=bits),
        grid=(nwords // bw,),
        in_specs=[pl.BlockSpec((1, bw), lambda i: (0, i))],
        out_specs=pl.BlockSpec((epw, bw), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((epw, nwords), jnp.int32),
        interpret=interpret,
    )(words.reshape(1, -1))
