"""§Perf hillclimbing harness: re-lower one (arch x shape) with overrides.

Each named experiment is one hypothesis->change->measure cycle from
EXPERIMENTS.md §Perf: it perturbs exactly one knob (rank, plan policy, MoE
group size, cache sharding, dtype path), re-lowers on the production mesh
and prints the three roofline terms next to the recorded baseline.

  PYTHONPATH=src python -m benchmarks.perf_iter --exp qwen3_rank_sweep
"""
# Must precede any jax import (same contract as launch/dryrun.py).
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"   # fake host devices, never the chip

import argparse
import dataclasses
import json

import jax

from repro.core.comm_model import TPU_V5E
from repro.launch.dryrun import lower_one
from repro.launch.mesh import make_production_mesh

HW = TPU_V5E


def terms(rec: dict) -> dict:
    return {
        "compute_s": rec["flops_per_chip"] / HW.peak_flops,
        "memory_s": rec["bytes_per_chip"] / HW.hbm_bw,
        "collective_s": rec["collective_total"] / HW.ici_bw,
    }


def show(tag: str, rec: dict) -> None:
    t = terms(rec)
    dom = max(t, key=t.get)
    print(f"{tag::<56} comp={t['compute_s']:.3e} mem={t['memory_s']:.3e} "
          f"coll={t['collective_s']:.3e}  dom={dom}", flush=True)


def _patched_config(arch: str, variant: str, **overrides):
    """Temporarily override the arch's FULL config (restored after)."""
    import repro.configs as C
    mod = C.arch_module(arch)
    orig = getattr(mod, "FULL" if variant == "full" else "LONG_CONTEXT")
    patched = dataclasses.replace(orig, **overrides)
    return mod, orig, patched


def run_with_overrides(arch: str, shape: str, mesh, *, policy="edgc",
                       rank=64, cfg_overrides=None, tag=""):
    import repro.configs as C
    mod = C.arch_module(arch)
    saved_full, saved_long = mod.FULL, mod.LONG_CONTEXT
    try:
        if cfg_overrides:
            mod.FULL = dataclasses.replace(mod.FULL, **cfg_overrides)
            if mod.LONG_CONTEXT is not None:
                mod.LONG_CONTEXT = dataclasses.replace(
                    mod.LONG_CONTEXT, **cfg_overrides)
        rec = lower_one(arch, shape, mesh, policy=policy, rank=rank)
        show(tag or f"{arch} x {shape} [{policy} r={rank}] {cfg_overrides}", rec)
        return rec
    finally:
        mod.FULL, mod.LONG_CONTEXT = saved_full, saved_long


# ------------------------------------------------------------ experiments
def exp_qwen3_rank_sweep(mesh):
    """H1: EDGC's collective term vs compression rank (paper technique).

    Hypothesis: DP-sync collective bytes scale ~ (m+n)r/(mn) for compressed
    leaves; the uncompressed-policy row is the Megatron baseline.
    """
    out = {}
    out["none"] = run_with_overrides("qwen3-32b", "train_4k", mesh,
                                     policy="none", tag="H1 policy=none")
    for r in (256, 64, 16):
        out[f"r{r}"] = run_with_overrides(
            "qwen3-32b", "train_4k", mesh, policy="edgc", rank=r,
            tag=f"H1 edgc rank={r}")
    return out


def exp_kimi_moe_group(mesh):
    """H2: MoE dispatch traffic vs GShard group size.

    Hypothesis: dispatch tensor bytes ~ tokens*E*C with C = S*k/E*cf, so
    bytes ~ tokens*S*k*cf — halving S halves the dominant memory term.
    """
    out = {}
    for S in (1024, 512, 256):
        out[f"S{S}"] = run_with_overrides(
            "kimi-k2-1t-a32b", "train_4k", mesh,
            cfg_overrides={"moe_group": S}, tag=f"H2 moe_group={S}")
    return out


def exp_kimi_capacity(mesh):
    """H2b (iter 2): the dominant MoE traffic is the (G,E,C,d) expert
    activations ~ tokens*k*cf*d — S-invariant (iter-1 refuted the dispatch
    hypothesis). Levers: capacity factor (C = S*k/E*cf at S=1024 is NOT
    pinned by the C>=k floor) and remat (recompute trades bytes for flops).
    """
    out = {}
    for cf in (1.25, 1.0):
        out[f"cf{cf}"] = run_with_overrides(
            "kimi-k2-1t-a32b", "train_4k", mesh,
            cfg_overrides={"capacity_factor": cf, "moe_group": 1024},
            tag=f"H2b S=1024 capacity_factor={cf}")
    out["noremat"] = run_with_overrides(
        "kimi-k2-1t-a32b", "train_4k", mesh,
        cfg_overrides={"moe_group": 1024, "remat": False},
        tag="H2b S=1024 cf=1.25 remat=False")
    return out


def exp_decode_cache(mesh):
    """H3: decode collective term — bf16 cache einsum + sharding variants.

    The baseline decode materialized an f32 copy of the KV cache and
    all-gathered it (models/layers.py now keeps the convert inside the dot);
    measure the delta on the worst decode rows.
    """
    out = {}
    for arch in ("qwen2-0.5b", "qwen3-32b", "llama3-405b"):
        out[arch] = run_with_overrides(arch, "decode_32k", mesh,
                                       tag=f"H3 {arch} decode_32k (fixed einsum)")
    return out


def exp_qwen3_multipod_dcn(mesh):
    """H1b: the paper's bandwidth-constrained regime = the cross-pod links.

    Hypothesis: on a uniform single pod, DP grad sync is <1% of collective
    bytes (TP activations dominate). Across pods, the DP sync IS the
    cross-pod traffic; with DCN ~8x slower per chip than ICI, EDGC's rank-r
    compression removes ~(1 - (m+n)r/mn) of the DCN bottleneck — the
    46%-comm-time-class win the paper reports on slow Ethernet.
    """
    from repro.launch.mesh import make_production_mesh
    mesh2 = make_production_mesh(multi_pod=True)
    DCN_BW = 50e9 / 8  # assumed per-chip cross-pod bandwidth (document!)
    out = {}
    for tag, (policy, rank) in {"none": ("none", 64), "edgc16": ("edgc", 16),
                                "edgc64": ("edgc", 64)}.items():
        rec = lower_one("qwen3-32b", "train_4k", mesh2, policy=policy, rank=rank)
        cross = rec.get("collective_cross_total", 0)
        intra = rec["collective_total"] - cross
        print(f"H1b {tag:8s} intra={intra/2**30:.1f}GiB/chip "
              f"cross-pod={cross/2**30:.3f}GiB/chip "
              f"t_ici={intra/50e9:.2f}s t_dcn={cross/DCN_BW:.2f}s", flush=True)
        out[tag] = rec
    return out


EXPERIMENTS = {
    "qwen3_multipod_dcn": exp_qwen3_multipod_dcn,
    "qwen3_rank_sweep": exp_qwen3_rank_sweep,
    "kimi_moe_group": exp_kimi_moe_group,
    "kimi_capacity": exp_kimi_capacity,
    "decode_cache": exp_decode_cache,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", required=True, choices=sorted(EXPERIMENTS))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    recs = EXPERIMENTS[args.exp](mesh)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({k: v for k, v in recs.items()}, f, indent=1, default=str)


if __name__ == "__main__":
    main()
