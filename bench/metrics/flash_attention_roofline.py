"""The flash-attention kernels' share of their roofline: for each of
``_fwd_kernel`` (forward and remat recompute), ``_dq_kernel`` and
``_dkv_kernel``, its executions in the window times max(FLOPs / bf16 peak,
bytes / HBM bandwidth) of one call (``flash_count.py`` at the cell's
shapes, ``peaks.json``), summed, over the kernels' measured device self
time, in percent (device trace, kernels named by ``xplane.reduce_scopes``).

Shapes on one chip: the cell's batch and sequence, the model's heads,
kv heads and head size in its parameter dtype, causal, in the program's
tile (``repro.kernels.ops.FLASH_BLOCK``, or the sequence where shorter).
A cell on more chips splits the batch or heads and needs a reader of its
own. Nothing where no flash kernel ran."""
import flash_count
import flops


def read(ctx):
    r = ctx.reduced
    if r is None or ctx.chips != 1:
        return None
    ran = {k: r["ops"][k] for k in flash_count.MATMULS if k in r["ops"]}
    if not ran:
        return None
    import jax.numpy as jnp
    from repro.kernels.ops import FLASH_BLOCK
    model, wl = ctx.model, ctx.workload
    heads = model["num_heads"]
    cost = flash_count.flash_attention_cost(
        batch=wl["batch"], heads=heads,
        kv_heads=model.get("num_kv_heads", heads), seq=wl["seq_len"],
        head_dim=model.get("head_dim") or model["d_model"] // heads,
        tile=FLASH_BLOCK, causal=True,
        itemsize=jnp.dtype(model.get("dtype", "float32")).itemsize)
    peak = flops.peaks(ctx.device_kind)
    bound_s = sum(row["calls"] * max(
        cost[k]["flops"] / peak["bf16_flops_per_s"],
        cost[k]["bytes"] / peak["hbm_bytes_per_s"]) for k, row in ran.items())
    took_s = sum(row["ns"] for row in ran.values()) / 1e9
    return 100.0 * bound_s / took_s
