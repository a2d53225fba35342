"""Operations and HBM bytes of one call of each flash-attention kernel the
train step runs (``repro/kernels/flash_attention_bwd.py``), at given shapes.

The kernels work on ``(B*H, T, Dh)`` operands over a grid of (head, tile,
tile), the last axis fastest; a (q tile, k tile) pair wholly above the
causal diagonal is skipped, and the others are computed in full (the ones
the diagonal crosses are masked, not shortened). Per computed pair of a
``bq`` × ``bk`` tile, in matrix products (2 operations per multiply-add):

* ``_fwd_kernel`` (forward, and remat's recompute): S = Q Kᵀ and P V,
  4 bq bk Dh;
* ``_dq_kernel``: S = Q Kᵀ, dP = dO Vᵀ and dS K, 6 bq bk Dh;
* ``_dkv_kernel``: Sᵀ = K Qᵀ, Pᵀ dO, dPᵀ = V dOᵀ and dSᵀ Q, 8 bq bk Dh.

Bytes are what the Pallas pipeline copies between HBM and VMEM: an
operand's block each time its block index differs from the previous grid
step's (a skipped pair's index map repeats the resident block), an
output's block each time it is left. The block specs are the kernels'
own, written out here.
"""
from __future__ import annotations

import itertools

F32_BYTES = 4
MATMULS = {"_fwd_kernel": 2, "_dq_kernel": 3, "_dkv_kernel": 4}


def _copied(grid, blocks) -> int:
    """Bytes moved over ``grid`` by ``blocks``: ``(index_map, bytes)`` of
    every input and output."""
    last = [None] * len(blocks)
    total = 0
    for point in itertools.product(*map(range, grid)):
        for k, (index, nbytes) in enumerate(blocks):
            at = index(*point)
            if at != last[k]:
                total += nbytes
                last[k] = at
    return total


def _computed(grid, q_tile, k_tile, bq: int, bk: int, causal: bool) -> int:
    """Grid points whose (q tile, k tile) pair, by the grid's own indices,
    is not skipped."""
    return sum(grid[0] for i, j in itertools.product(range(grid[1]),
                                                     range(grid[2]))
               if not causal or k_tile(i, j) * bk <= q_tile(i, j) * bq + bq - 1)


def flash_attention_cost(batch: int, heads: int, kv_heads: int, seq: int,
                         head_dim: int, tile: int, causal: bool,
                         itemsize: int) -> dict[str, dict[str, int]]:
    """Kernel name -> {``flops``, ``bytes``} of one call, for self-attention
    over ``seq`` tokens in ``tile`` × ``tile`` blocks, operands of
    ``itemsize`` bytes."""
    bq = bk = min(tile, seq)
    n = seq // bq
    bh, rep = batch * heads, heads // kv_heads
    last_k = lambda i: (i * bq + bq - 1) // bk
    first_q = lambda i: (i * bk) // bq
    kv_tile = lambda i, j: min(j, last_k(i)) if causal else j
    q_tile = lambda i, j: max(j, first_q(i)) if causal else j
    tile_bytes = bq * head_dim * itemsize
    row_bytes = bq * F32_BYTES
    dkv_bytes = tile_bytes if rep == 1 else bq * head_dim * F32_BYTES
    grid = (bh, n, n)
    q_block = lambda h, i, j: (h, i)
    kv_block = lambda h, i, j: (h // rep, kv_tile(i, j))
    by_q = [(q_block, tile_bytes), (kv_block, tile_bytes),
            (kv_block, tile_bytes)]
    blocks = {
        # q, k, v; o, lse
        "_fwd_kernel": by_q + [(q_block, tile_bytes), (q_block, row_bytes)],
        # q, k, v, dO, lse, delta; dq
        "_dq_kernel": by_q + [(q_block, tile_bytes), (q_block, row_bytes),
                              (q_block, row_bytes), (q_block, tile_bytes)],
    }
    qo = lambda h, i, j: (h, q_tile(i, j))
    kv_own = lambda h, i, j: (h // rep, i)
    out = lambda h, i, j: (h, i)
    # q, k, v, dO, lse, delta; dk, dv (grid: head, k tile, q tile)
    blocks["_dkv_kernel"] = [
        (qo, tile_bytes), (kv_own, tile_bytes), (kv_own, tile_bytes),
        (qo, tile_bytes), (qo, row_bytes), (qo, row_bytes),
        (out, dkv_bytes), (out, dkv_bytes)]
    fwd_pairs = _computed(grid, lambda i, j: i, lambda i, j: j, bq, bk,
                          causal)
    pairs = {"_fwd_kernel": fwd_pairs, "_dq_kernel": fwd_pairs,
             "_dkv_kernel": _computed(grid, lambda i, j: j, lambda i, j: i,
                                      bq, bk, causal)}
    return {k: {"flops": 2 * MATMULS[k] * bq * bk * head_dim * pairs[k],
                "bytes": _copied(grid, blocks[k])}
            for k in MATMULS}
