"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

Proves the distribution config is coherent without hardware: 512 fake host
devices stand in for 2 pods x 256 v5e chips; every combination must
``.lower().compile()``, and the compiled artifacts yield the roofline terms
(cost_analysis = per-device FLOPs/bytes; collective bytes parsed from the
partitioned HLO).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                   # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod       # 2-pod mesh
  ... --out results.json
"""
# The fake-device flag MUST precede any jax import (device count locks at
# first init). Do NOT move these lines or set this flag anywhere global.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"   # fake host devices, never the chip

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_config, sharding_mode
from repro.core import classify_leaves, make_plan
from repro.core.compressor import NO_COMPRESSION
from repro.dist.sharding import batch_pspec, cache_pspecs, param_shardings
from repro.launch.mesh import dp_axes, make_pod_mesh, make_production_mesh
from repro.models.model import ModelConfig, build_model
from repro.optim import adam
from repro.train.step import (
    TrainStepConfig, make_train_step, replicate_comp_state, state_shardings,
)

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2}

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")


# ------------------------------------------------------------ HLO parsing
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes of all array shapes in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-op-kind result bytes of every collective in a partitioned module."""
    out = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w,\[\]{}\s]*?)\s*"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start)?\(", ls)
        if m:
            out[m.group(2)] += _shape_bytes(m.group(1))
    return out


# ------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, shape_name: str) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for the batch of one input shape."""
    spec = INPUT_SHAPES[shape_name]
    B, T = spec["global_batch"], spec["seq_len"]
    kind = spec["kind"]
    tok = jax.ShapeDtypeStruct
    if kind in ("train", "prefill"):
        batch = {"tokens": tok((B, T), jnp.int32)}
        if kind == "train":
            batch["labels"] = tok((B, T), jnp.int32)
        if cfg.family == "whisper":
            batch["frames"] = tok((B, cfg.audio_frames, cfg.d_model), cfg.jdtype)
        if cfg.family == "vlm":
            batch["patches"] = tok((B, cfg.num_patches, cfg.d_model), cfg.jdtype)
        return batch
    # decode: ONE new token against a seq_len-deep cache
    return {"tokens": tok((B,), jnp.int32)}


def _eval_shape_tree(fn, *args):
    return jax.eval_shape(fn, *args)


# ------------------------------------------------------------- one combo
def lower_one(arch: str, shape_name: str, mesh, policy: str = "edgc",
              rank: int = 64, verbose: bool = True,
              opt_dtype: str = "float32", stash: str = "replay",
              stash_every: int = 2, overlap: bool = False,
              chunk_bytes: int = 0, outer_k: int = 0,
              outer_rank: int = 32, inject: bool = False) -> dict:
    """Lower+compile one (arch, shape, mesh); return the roofline record."""
    spec = INPUT_SHAPES[shape_name]
    kind = spec["kind"]
    B, T = spec["global_batch"], spec["seq_len"]
    mode = sharding_mode(arch)
    variant = "long" if shape_name == "long_500k" else "full"
    cfg = get_config(arch, variant)
    if cfg is None:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "long_500k inapplicable (see DESIGN §5)"}
    pipe = "pipe" in mesh.axis_names
    if pipe and kind != "train":
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "pipeline mesh applies to train shapes only"}
    if pipe:
        # The stage adapter's own reason string is surfaced verbatim (a
        # family without an adapter, a layer/stage mismatch, ...) instead
        # of a bare traceback. Memory-bound 'auto' archs lower dp_tp-style
        # here: the pipe axis splits the params S ways, standing in for
        # the FSDP sharding the flat auto path would use.
        from repro.launch.mesh import pipe_size
        from repro.pipeline.partition import pipeline_supported
        cfg = dataclasses.replace(cfg, num_stages=pipe_size(mesh))
        reason = pipeline_supported(cfg, pipe_size(mesh))
        if reason is not None:
            return {"arch": arch, "shape": shape_name, "skipped": True,
                    "reason": f"pipeline: {reason}"}
    model = build_model(cfg)
    t0 = time.time()

    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pshard = param_shardings(params_shapes, mesh, fsdp=(mode == "auto"))

    if kind == "train" and pipe:
        rec = _lower_train_pipelined(arch, cfg, model, mesh, params_shapes,
                                     shape_name, policy, rank, opt_dtype,
                                     stash=stash, stash_every=stash_every,
                                     overlap=overlap,
                                     chunk_bytes=chunk_bytes)
    elif kind == "train":
        rec = _lower_train(arch, cfg, model, mesh, mode, params_shapes,
                           pshard, shape_name, policy, rank, opt_dtype,
                           inject=inject)
    elif kind == "prefill":
        rec = _lower_prefill(cfg, model, mesh, params_shapes, pshard, shape_name)
    else:
        rec = _lower_decode(cfg, model, mesh, params_shapes, pshard, shape_name)
    if outer_k and kind == "train":
        if "pod" in mesh.axis_names:
            rec["outer_sync"] = _lower_outer_sync(cfg, mesh, params_shapes,
                                                  outer_rank)
            rec["outer_sync"]["outer_k"] = outer_k
        else:
            rec["outer_sync"] = {"skipped": True,
                                 "reason": "outer loop needs --multi-pod"}
    rec.update({"arch": arch, "shape": shape_name, "mode": mode,
                "mesh": "x".join(map(str, mesh.devices.shape)),
                "compile_s": round(time.time() - t0, 1)})
    return rec


def _record(compiled, hlo_text: str, pod_size: int = 0) -> dict:
    from repro.launch.hlo_cost import analyze_hlo, xla_cost_analysis
    ca = xla_cost_analysis(compiled)
    ma = compiled.memory_analysis()
    # loop-scaled walker: cost_analysis counts while bodies ONCE, which
    # undercounts layer-scanned models by their trip counts (hlo_cost.py)
    walked = analyze_hlo(hlo_text, pod_size=pod_size)
    coll = {k: int(v) for k, v in walked["collective_bytes"].items()}
    cross = {k: int(v) for k, v in walked.get("collective_bytes_cross", {}).items()}
    return {
        "flops_per_chip": float(walked["flops"]),
        "bytes_per_chip": float(walked["bytes"]),
        "collective_bytes_per_chip": coll,
        "collective_total": int(sum(coll.values())),
        "collective_cross_pod": cross,
        "collective_cross_total": int(sum(cross.values())),
        "xla_cost_analysis": {
            "flops_unscaled": float(ca.get("flops", 0.0)),
            "bytes_unscaled": float(ca.get("bytes accessed", 0.0)),
        },
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
        },
    }


def _lower_outer_sync(cfg, mesh, params_shapes, rank):
    """Lower+compile the DiLoCo outer sync on the pod-lead sub-mesh.

    The outer all-reduce runs on ONE lead device per pod over the cross-pod
    links — exactly the topology ``make_pod_mesh`` gives the elastic
    trainer. Deltas ship fp32 (parameter scale); the record carries the
    compressed-vs-raw outer wire bytes the EDGC plan buys per round.
    """
    from repro.core.compressor import init_compressor_state, plan_wire_bytes
    from repro.core.entropy import GDSConfig
    from repro.optim.outer import make_outer_sync_step

    n_pods = mesh.devices.shape[list(mesh.axis_names).index("pod")]
    leads = mesh.devices.reshape(n_pods, -1)[:, 0]
    omesh = make_pod_mesh(n_pods, devices=list(leads))

    leaves = classify_leaves(params_shapes, cfg.num_layers, 1, min_dim=128)
    plan = make_plan("fixed", leaves, fixed_rank=rank, num_stages=1)
    delta_shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                       sharding=NamedSharding(omesh, P())),
        params_shapes)
    comp_shapes = jax.eval_shape(lambda: replicate_comp_state(
        init_compressor_state(delta_shapes, plan, jax.random.PRNGKey(2)),
        n_pods))
    comp_shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=NamedSharding(omesh,
                                                              P("pod"))),
        comp_shapes)
    step = make_outer_sync_step(omesh, plan, GDSConfig())
    with omesh:
        compiled = step.lower(delta_shapes, comp_shapes).compile()
    # On the lead mesh every device IS a pod: pod_size=1 marks every
    # collective byte as crossing the pod boundary.
    rec = _record(compiled, compiled.as_text(), pod_size=1)
    compressed, full = plan_wire_bytes(leaves, plan, 4)
    rec.update({"n_pods": int(n_pods), "outer_rank": int(rank),
                "compressed_leaves": len(plan.ranks),
                "wire_bytes_compressed": int(compressed),
                "wire_bytes_full": int(full)})
    return rec


def _lower_train(arch, cfg, model, mesh, mode, params_shapes, pshard,
                 shape_name, policy, rank, opt_dtype="float32",
                 inject=False):
    spec = INPUT_SHAPES[shape_name]
    B = spec["global_batch"]
    axes = dp_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = int(np.prod([sizes.get(a, 1) for a in axes])) or 1

    if mode == "auto":
        plan = NO_COMPRESSION
    else:
        leaves = classify_leaves(params_shapes, cfg.num_layers, cfg.num_stages,
                                 min_dim=128)
        plan = make_plan(policy if policy != "edgc" else "edgc", leaves,
                         stage_ranks=[rank] * cfg.num_stages,
                         fixed_rank=rank, num_stages=cfg.num_stages)

    acfg = adam.AdamConfig(opt_dtype=opt_dtype)

    # Same executor-eligibility rule the Trainer applies, so the reported
    # collective counts model what production actually lowers (not the
    # per-leaf parity oracle).
    from repro.core.bucketing import bucketing_supported
    bucketed = mode == "dp_tp" and bucketing_supported(mesh)

    def init_state():
        params = model.init(jax.random.PRNGKey(0))
        ost = adam.init(params, acfg)
        from repro.core.bucketing import layout_for_tree
        from repro.core.compressor import init_compressor_state
        layout = layout_for_tree(params, plan) if bucketed else None
        comp = init_compressor_state(params, plan, jax.random.PRNGKey(1),
                                     layout=layout)
        comp = replicate_comp_state(comp, world if mode == "dp_tp" else 1)
        return {"params": params, "opt_m": ost.m, "opt_v": ost.v,
                "opt_step": ost.step, "comp": comp}

    state_shapes = jax.eval_shape(init_state)
    sshard = state_shardings(state_shapes, model, mesh, fsdp=(mode == "auto"))
    if mode == "auto":
        # params/opt sharded FSDP+TP; comp empty
        sshard["params"] = pshard
        sshard["opt_m"] = pshard
        sshard["opt_v"] = pshard

    batch = input_specs(cfg, shape_name)
    if inject:
        # the fault-injection channel rides in the batch (constant batch
        # structure keeps one compiled variant; see train/faults.py)
        batch["_inject"] = jax.ShapeDtypeStruct((B,), jnp.float32)
    bshard = {k: NamedSharding(mesh, batch_pspec(v.ndim, mesh, B))
              for k, v in batch.items()}

    scfg = TrainStepConfig(mode=mode if mode == "dp_tp" else "auto",
                           policy_plan=plan, measure_entropy=(mode == "dp_tp"),
                           bucketed=bucketed or None,
                           remat=cfg.remat, adam=acfg,
                           guard_nonfinite=inject)
    step = make_train_step(model, mesh, scfg)
    jstep = jax.jit(step, in_shardings=(sshard, bshard),
                    out_shardings=(sshard, NamedSharding(mesh, P())),
                    donate_argnums=0)
    with mesh:
        lowered = jstep.lower(state_shapes, batch)
        compiled = lowered.compile()
    pod = 256 if "pod" in mesh.axis_names else 0
    rec = _record(compiled, compiled.as_text(), pod_size=pod)
    rec["policy"] = policy if plan.ranks else "none"
    rec["compressed_leaves"] = len(plan.ranks)
    rec["guarded"] = bool(inject)
    return rec


def _lower_train_pipelined(arch, cfg, model, mesh, params_shapes, shape_name,
                           policy, rank, opt_dtype="float32",
                           stash="replay", stash_every=2, overlap=False,
                           chunk_bytes=0):
    """Lower+compile the pipelined train step (pipe mesh): stage-partitioned
    state, 1F1B schedule, per-stage DP sync — what a pipelined pod runs.
    ``stash`` picks the executor's activation-stashing policy; the record
    carries the per-stage ``peak_activation_bytes`` ledger for it.
    ``overlap`` lowers the schedule-interleaved sync executor and records
    the overlap planner's launch/residual/feasibility summary."""
    from repro.launch.mesh import pipe_size
    from repro.pipeline import partition as ppart
    from repro.pipeline import sync as psync
    from repro.pipeline.config import PipelineConfig
    from repro.pipeline.schedule import (
        boundary_nbytes, peak_activation_bytes, pipeline_state_shardings,
        plan_overlap,
    )

    spec = INPUT_SHAPES[shape_name]
    B = spec["global_batch"]
    S = pipe_size(mesh)
    axes = dp_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = int(np.prod([sizes.get(a, 1) for a in axes])) or 1

    leaves = classify_leaves(params_shapes, cfg.num_layers, S, min_dim=128)
    plan = make_plan(policy, leaves, stage_ranks=[rank] * S,
                     fixed_rank=rank, num_stages=S)
    part = ppart.make_partition(model, S)
    stage_shapes = jax.eval_shape(
        lambda p: part.partition_params(p)[0], params_shapes)
    splans = psync.make_stage_plans(
        plan, S, psync.stage_local_leaves(stage_shapes),
        chunk_bytes=chunk_bytes,
        local_path=part.local_leaf_path)
    acfg = adam.AdamConfig(opt_dtype=opt_dtype)

    def init_state():
        params = model.init(jax.random.PRNGKey(0))
        sp, sh = part.partition_params(params)
        ost = adam.init({"stage": sp, "shared": sh}, acfg)
        comp = psync.init_pipeline_comp_state(params, plan,
                                              jax.random.PRNGKey(1), splans)
        comp = psync.replicate_pipeline_comp_state(comp, world)
        return {"stage_params": sp, "shared_params": sh,
                "opt_m": ost.m, "opt_v": ost.v, "opt_step": ost.step,
                "comp": comp}

    state_shapes = jax.eval_shape(init_state)
    sshard = pipeline_state_shardings(state_shapes, model, mesh)

    batch = input_specs(cfg, shape_name)
    bshard = {k: NamedSharding(mesh, batch_pspec(v.ndim, mesh, B))
              for k, v in batch.items()}

    scfg = TrainStepConfig(mode="dp_tp", policy_plan=plan,
                           measure_entropy=True, remat=cfg.remat,
                           pipeline=PipelineConfig(
                               num_stages=S, schedule="1f1b",
                               stash_policy=stash, stash_every=stash_every,
                               overlap_sync=overlap,
                               chunk_bytes=chunk_bytes),
                           adam=acfg)
    step = make_train_step(model, mesh, scfg)
    jstep = jax.jit(step, in_shardings=(sshard, bshard),
                    out_shardings=(sshard, NamedSharding(mesh, P())),
                    donate_argnums=0)
    with mesh:
        lowered = jstep.lower(state_shapes, batch)
        compiled = lowered.compile()
    pod = 256 if "pod" in mesh.axis_names else 0
    rec = _record(compiled, compiled.as_text(), pod_size=pod)
    rec["policy"] = policy if plan.ranks else "none"
    rec["compressed_leaves"] = len(plan.ranks)
    # Per-stage (compressed, full) DP-sync bytes — the Algorithm-2 wire
    # ledger, reported per family so `--pipe` runs show where the bytes go.
    rec["pipeline"] = {"num_stages": S, "schedule": "1f1b",
                       "family": cfg.family,
                       "distinct_plans": len(splans.distinct),
                       "stage_bytes": psync.stage_wire_bytes(leaves, plan, S)}
    # Activation-memory ledger: per-rank microbatch boundary bytes (the
    # local batch is B / dp_world, split M ways) x the stash policy's
    # live ring entries from the tick table.
    M = S  # the executor's default microbatch count
    mb = {k: jax.ShapeDtypeStruct((max(1, v.shape[0] // (world * M)),)
                                  + v.shape[1:], v.dtype)
          for k, v in batch.items()}
    rec["pipeline"]["stash_policy"] = stash
    rec["pipeline"]["peak_activation_bytes"] = peak_activation_bytes(
        "1f1b", S, M, stash, boundary_bytes=boundary_nbytes(part, mb),
        n_units=part.num_units(), stash_every=stash_every)
    if overlap:
        # The overlap planner's summary for this lowering: how many chunks
        # each stage hides in its drain ticks vs runs post-loop, and the
        # Eq. 4 feasibility signal the DAC would consume.
        oplan = plan_overlap("1f1b", S, M, splans)
        rec["pipeline"]["overlap"] = {
            "chunk_bytes": chunk_bytes,
            "in_loop_chunks": [sum(len(ids) for _, ids in oplan.launches[s])
                               for s in range(S)],
            "residual_chunks": [len(oplan.residual[s]) for s in range(S)],
            "feasible": list(oplan.feasible),
        }
    return rec


def _lower_prefill(cfg, model, mesh, params_shapes, pshard, shape_name):
    spec = INPUT_SHAPES[shape_name]
    B = spec["global_batch"]
    batch = input_specs(cfg, shape_name)
    bshard = {k: NamedSharding(mesh, batch_pspec(v.ndim, mesh, B))
              for k, v in batch.items()}
    out_shard = NamedSharding(mesh, batch_pspec(3, mesh, B))

    jfwd = jax.jit(model.forward, in_shardings=(pshard, bshard),
                   out_shardings=out_shard)
    with mesh:
        lowered = jfwd.lower(params_shapes, batch)
        compiled = lowered.compile()
    pod = 256 if "pod" in mesh.axis_names else 0
    return _record(compiled, compiled.as_text(), pod_size=pod)


def _lower_decode(cfg, model, mesh, params_shapes, pshard, shape_name):
    spec = INPUT_SHAPES[shape_name]
    B, T = spec["global_batch"], spec["seq_len"]

    if cfg.family == "whisper":
        from repro.models import encdec
        cache_shapes = jax.eval_shape(lambda: encdec.init_cache(cfg, B, T))
    else:
        cache_shapes = jax.eval_shape(lambda: model.init_cache(B, T))
    cshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        cache_pspecs(cache_shapes, mesh, B))
    tokens = jax.ShapeDtypeStruct((B,), jnp.int32)
    tshard = NamedSharding(mesh, batch_pspec(1, mesh, B))
    logit_shard = NamedSharding(mesh, batch_pspec(2, mesh, B))

    jdec = jax.jit(model.decode_step,
                   in_shardings=(pshard, cshard, tshard),
                   out_shardings=(logit_shard, cshard),
                   donate_argnums=1)
    with mesh:
        lowered = jdec.lower(params_shapes, cache_shapes, tokens)
        compiled = lowered.compile()
    pod = 256 if "pod" in mesh.axis_names else 0
    return _record(compiled, compiled.as_text(), pod_size=pod)


# ------------------------------------------------------------------- main
def record_summary(rec: dict) -> dict:
    """Machine-checkable summary of one lowering record — the structured
    twin of the human OK/SKIP/FAIL line, emitted as a ``dryrun`` event so
    CI can assert on lowerings instead of grepping stdout."""
    out = {"arch": rec.get("arch"), "shape": rec.get("shape")}
    if rec.get("skipped"):
        out["status"] = "skipped"
        out["reason"] = rec.get("reason")
        return out
    if "error" in rec:
        out["status"] = "failed"
        out["error"] = rec["error"]
        return out
    out["status"] = "ok"
    for key in ("flops_per_chip", "bytes_per_chip", "collective_total",
                "compile_s", "policy", "compressed_leaves", "guarded"):
        if key in rec:
            out[key] = rec[key]
    mem = rec.get("memory")
    if mem:
        out["per_chip_bytes"] = int(mem.get("argument_bytes", 0)
                                    + mem.get("temp_bytes", 0))
    pipe = rec.get("pipeline")
    if pipe:
        out["pipeline"] = {
            "num_stages": pipe.get("num_stages"),
            "schedule": pipe.get("schedule"),
            "stash_policy": pipe.get("stash_policy"),
            "stage_bytes": pipe.get("stage_bytes"),
            "peak_activation_bytes": pipe.get("peak_activation_bytes"),
        }
        if "overlap" in pipe:
            out["pipeline"]["overlap"] = pipe["overlap"]
    osync = rec.get("outer_sync")
    if osync and not osync.get("skipped"):
        out["outer_sync"] = {
            "wire_bytes_compressed": osync.get("wire_bytes_compressed"),
            "wire_bytes_full": osync.get("wire_bytes_full"),
            "outer_k": osync.get("outer_k"),
            "outer_rank": osync.get("outer_rank"),
        }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (512-chip) mesh")
    ap.add_argument("--pipe", type=int, default=0,
                    help="pipeline stages: adds a 'pipe' mesh axis and "
                         "lowers the pipelined (1F1B) train step")
    ap.add_argument("--policy", default="edgc")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--stash", default="replay",
                    choices=["replay", "full", "every_k"],
                    help="pipeline activation-stash policy (with --pipe): "
                         "how much of each stage's forward survives to its "
                         "backward tick")
    ap.add_argument("--stash-every", type=int, default=2,
                    help="k for --stash every_k")
    ap.add_argument("--overlap", action="store_true",
                    help="with --pipe: lower the schedule-interleaved "
                         "(overlapped) per-stage sync executor")
    ap.add_argument("--chunk-bytes", type=int, default=0,
                    help="with --overlap: max bytes per sync transfer "
                         "chunk (0 = one chunk per bucket)")
    ap.add_argument("--outer-k", type=int, default=0,
                    help="with --multi-pod: also lower the DiLoCo outer "
                         "sync (EDGC-compressed outer-delta all-reduce on "
                         "the pod-lead mesh); K = inner steps per round")
    ap.add_argument("--outer-rank", type=int, default=32,
                    help="PowerSGD rank for the outer-sync lowering")
    ap.add_argument("--inject", action="store_true",
                    help="lower the fault-guarded train step variant "
                         "(non-finite guard + injection channel)")
    ap.add_argument("--out", default=None, help="write JSON records here")
    ap.add_argument("--metrics-dir", default=None,
                    help="also emit one structured 'dryrun' event per combo "
                         "to DIR/metrics.jsonl (the telemetry sink format)")
    args = ap.parse_args()

    registry = None
    if args.metrics_dir:
        import os

        from repro.obs import JsonlSink, MetricsRegistry
        registry = MetricsRegistry(
            [JsonlSink(os.path.join(args.metrics_dir, "metrics.jsonl"))])

    mesh = make_production_mesh(multi_pod=args.multi_pod, pipe=args.pipe)
    archs = [args.arch] if args.arch else [a for a in ARCHS if a != "gpt2"]
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)

    records = []
    for arch in archs:
        for shape_name in shapes:
            tag = f"{arch} x {shape_name} [{'x'.join(map(str, mesh.devices.shape))}]"
            try:
                rec = lower_one(arch, shape_name, mesh,
                                policy=args.policy, rank=args.rank,
                                stash=args.stash,
                                stash_every=args.stash_every,
                                overlap=args.overlap,
                                chunk_bytes=args.chunk_bytes,
                                outer_k=args.outer_k,
                                outer_rank=args.outer_rank,
                                inject=args.inject)
                if rec.get("skipped"):
                    print(f"SKIP {tag}: {rec['reason']}", flush=True)
                else:
                    mem = rec["memory"]
                    per_chip_gb = (mem["argument_bytes"] + mem["temp_bytes"]) / 2**30
                    extra = ""
                    if "pipeline" in rec:
                        sb = ";".join(str(c) for c, _ in
                                      rec["pipeline"]["stage_bytes"])
                        extra = (f", {rec['pipeline']['family']} "
                                 f"stage-sync [{sb}] B")
                        if "overlap" in rec["pipeline"]:
                            ov = rec["pipeline"]["overlap"]
                            extra += (", overlap in-loop "
                                      f"{ov['in_loop_chunks']} residual "
                                      f"{ov['residual_chunks']}")
                    if rec.get("guarded"):
                        extra += ", guarded"
                    osync = rec.get("outer_sync")
                    if osync and not osync.get("skipped"):
                        extra += (", outer-sync "
                                  f"{osync['wire_bytes_compressed']/2**20:.1f}"
                                  f"/{osync['wire_bytes_full']/2**20:.1f} MiB"
                                  f" (K={osync['outer_k']}, "
                                  f"r={osync['outer_rank']})")
                    print(f"OK   {tag}: {rec['flops_per_chip']:.3e} FLOP/chip, "
                          f"{rec['bytes_per_chip']:.3e} B/chip, "
                          f"coll {rec['collective_total']/2**20:.1f} MiB/chip, "
                          f"mem {per_chip_gb:.2f} GiB/chip, "
                          f"{rec['compile_s']}s{extra}", flush=True)
            except Exception as e:
                rec = {"arch": arch, "shape": shape_name, "error": str(e),
                       "traceback": traceback.format_exc()}
                print(f"FAIL {tag}: {e}", flush=True)
            records.append(rec)
            if registry is not None:
                registry.event("dryrun", **record_summary(rec))
                registry.flush()
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(records, f, indent=1)

    n_ok = sum(1 for r in records if "flops_per_chip" in r)
    n_skip = sum(1 for r in records if r.get("skipped"))
    n_fail = len(records) - n_ok - n_skip
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if registry is not None:
        registry.event("dryrun_summary", ok=n_ok, skipped=n_skip,
                       failed=n_fail)
        registry.close()
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
