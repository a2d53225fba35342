"""Collective-safety audit: static analysis over traced train steps.

Walks the closed jaxprs of representative compiled-step variants — flat,
pipelined, overlap-scheduled, wire-coded, and every pipeline family
adapter — and machine-checks the invariants the EDGC design stands on:

  * **collective parity** — every ``lax.switch``/``cond`` either launches
    identical collective sequences in all branches or branches on a
    predicate provably uniform across the collectives' mesh axes (SPMD
    deadlock freedom; ``repro.analysis.parity``),
  * **psum budgets** — the overlapped executor's switch branches launch
    exactly the collectives the overlap planner declared, and the
    entropy-off variant lowers exactly 3 fewer psums (the ISR gate;
    ``repro.analysis.budget``),
  * **host syncs** — no device->host callback is traced into any step,
    and a short real run keeps the trainer's compile cache
    window-bounded (``repro.analysis.hostcalls``),
  * **source lint** — repo-specific AST rules: duplicate dict keys,
    host calls in jit hot paths, collectives without an explicit axis
    name, unhashable compile-cache keys (``repro.analysis.lint``).

Everything but the trainer run is pure abstract tracing
(``jax.make_jaxpr`` over ShapeDtypeStruct trees — no FLOPs), so zoo
configs audit at production scale on fake host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.audit                  # everything
  PYTHONPATH=src python -m repro.launch.audit --lint-only
  PYTHONPATH=src python -m repro.launch.audit --skip-train     # no real run
  PYTHONPATH=src python -m repro.launch.audit --arch qwen3-moe-235b-a22b \
      --shape train_4k --pipe 4 --overlap                      # zoo config

Exit status is non-zero when any violation survives — CI runs this as a
blocking gate.
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
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import analysis
from repro.core import EDGCConfig, SyncConfig, classify_leaves, make_plan
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.models.model import ModelConfig, build_model
from repro.optim import adam
from repro.pipeline import PipelineConfig
from repro.pipeline import partition as ppart
from repro.pipeline import sync as psync
from repro.pipeline.schedule import overlap_branch_psums, plan_overlap
from repro.train.step import TrainStepConfig, make_train_step, \
    replicate_comp_state

# Tiny-but-representative configs: one per pipeline family adapter, all
# 2-stage (zamba deliberately ragged — 3 layers over 2 stages).  Shapes
# mirror the pipeline test suite's; the audit only traces them.
FAMILY_CFGS = {
    "dense": ModelConfig(name="audit-dense", family="dense", num_layers=4,
                         d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                         vocab_size=512, num_stages=2),
    "moe": ModelConfig(name="audit-moe", family="moe", num_layers=4,
                       d_model=128, num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=512, num_experts=2, experts_per_token=1,
                       capacity_factor=4.0, num_stages=2),
    "xlstm": ModelConfig(name="audit-xlstm", family="xlstm", num_layers=4,
                         d_model=128, num_heads=2, num_kv_heads=2,
                         vocab_size=512, chunk=16, num_stages=2),
    "zamba": ModelConfig(name="audit-zamba", family="zamba", num_layers=3,
                         d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
                         vocab_size=512, ssm_state=16, chunk=16,
                         attn_every=2, num_stages=2),
    "whisper": ModelConfig(name="audit-whisper", family="whisper",
                           num_layers=2, encoder_layers=2, d_model=128,
                           num_heads=4, num_kv_heads=4, d_ff=256,
                           vocab_size=512, audio_frames=16,
                           max_position=512, num_stages=2),
    "vlm": ModelConfig(name="audit-vlm", family="vlm", num_layers=2,
                       d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
                       vocab_size=512, num_patches=4, num_stages=2),
}

LINT_ROOTS = ("src/repro", "tests", "benchmarks", "examples")


def _family_batch(cfg: ModelConfig, B: int = 8, T: int = 16) -> dict:
    """Abstract batch specs for one family (modality stubs included)."""
    tok = jax.ShapeDtypeStruct
    batch = {"tokens": tok((B, T), jnp.int32),
             "labels": tok((B, T), jnp.int32)}
    if cfg.family == "whisper":
        batch["frames"] = tok((B, cfg.audio_frames, cfg.d_model), cfg.jdtype)
    if cfg.family == "vlm":
        batch["patches"] = tok((B, cfg.num_patches, cfg.d_model), cfg.jdtype)
    return batch


def _trace_pipelined(cfg: ModelConfig, mesh, *, overlap: bool,
                     measure_entropy: bool = True, chunk_bytes: int = 1 << 16,
                     sync: SyncConfig | None = None, rank: int = 8):
    """Abstract-trace a pipelined step; return (jaxpr, oplan, splans)."""
    S = cfg.num_stages
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves = classify_leaves(params, cfg.num_layers, S, min_dim=64)
    plan = make_plan("edgc", leaves, stage_ranks=[rank] * S, num_stages=S)
    part = ppart.make_partition(model, S)
    stage_shapes = jax.eval_shape(lambda p: part.partition_params(p)[0],
                                  params)
    sync = sync or SyncConfig()
    splans = psync.make_stage_plans(
        plan, S, psync.stage_local_leaves(stage_shapes),
        bucket_bytes=sync.bucket_bytes, chunk_bytes=chunk_bytes,
        local_path=part.local_leaf_path)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = int(np.prod([sizes.get(a, 1) for a in dp_axes(mesh)])) or 1
    M = S * 2

    def init_state():
        p = model.init(jax.random.PRNGKey(0))
        sp, sh = part.partition_params(p)
        ost = adam.init({"stage": sp, "shared": sh}, adam.AdamConfig())
        comp = psync.init_pipeline_comp_state(p, plan, jax.random.PRNGKey(1),
                                              splans)
        comp = psync.replicate_pipeline_comp_state(comp, world)
        return {"stage_params": sp, "shared_params": sh,
                "opt_m": ost.m, "opt_v": ost.v, "opt_step": ost.step,
                "comp": comp}

    state = jax.eval_shape(init_state)
    scfg = TrainStepConfig(
        mode="dp_tp", policy_plan=plan, measure_entropy=measure_entropy,
        pipeline=PipelineConfig(num_stages=S, schedule="1f1b",
                                num_microbatches=M, overlap_sync=overlap,
                                chunk_bytes=chunk_bytes),
        sync=sync)
    step = make_train_step(model, mesh, scfg)
    traced = jax.make_jaxpr(step)(state, _family_batch(cfg))
    oplan = plan_overlap("1f1b", S, M, splans) if overlap else None
    return traced, oplan, splans


def _trace_flat(cfg: ModelConfig, mesh, *, measure_entropy: bool = True,
                sync: SyncConfig | None = None, rank: int = 8):
    """Abstract-trace the flat (non-pipelined) bucketed step."""
    from repro.core.bucketing import layout_for_tree
    from repro.core.compressor import init_compressor_state

    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves = classify_leaves(params, cfg.num_layers, 1, min_dim=64)
    plan = make_plan("edgc", leaves, stage_ranks=[rank], num_stages=1)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = int(np.prod([sizes.get(a, 1) for a in dp_axes(mesh)])) or 1

    def init_state():
        p = model.init(jax.random.PRNGKey(0))
        ost = adam.init(p, adam.AdamConfig())
        layout = layout_for_tree(p, plan)
        comp = init_compressor_state(p, plan, jax.random.PRNGKey(1),
                                     layout=layout)
        comp = replicate_comp_state(comp, world)
        return {"params": p, "opt_m": ost.m, "opt_v": ost.v,
                "opt_step": ost.step, "comp": comp}

    state = jax.eval_shape(init_state)
    scfg = TrainStepConfig(mode="dp_tp", policy_plan=plan,
                           measure_entropy=measure_entropy,
                           sync=sync or SyncConfig(bucketed=True))
    step = make_train_step(model, mesh, scfg)
    return jax.make_jaxpr(step)(state, _family_batch(cfg))


class Report:
    """Violation accumulator with per-target timing."""

    def __init__(self) -> None:
        self.violations: list[tuple[str, analysis.Violation]] = []
        self.targets: list[dict] = []

    def run(self, name: str, fn) -> None:
        t0 = time.time()
        try:
            found = fn()
        except Exception as e:                       # surface, don't crash
            found = [analysis.Violation(
                rule="audit-error", path=name,
                message=f"{type(e).__name__}: {e}")]
        dt = round(time.time() - t0, 1)
        self.violations.extend((name, v) for v in found)
        self.targets.append({"target": name, "violations": len(found),
                             "seconds": dt})
        status = "ok" if not found else f"{len(found)} VIOLATION(S)"
        print(f"  {name:<44} {status}  ({dt}s)")
        for v in found:
            print(f"    {v}")

    def as_json(self) -> dict:
        return {"targets": self.targets,
                "violations": [{"target": t, "rule": v.rule, "path": v.path,
                                "message": v.message}
                               for t, v in self.violations]}


def _audit_step_family(rep: Report, fam: str, *, sync: SyncConfig | None
                       = None, tag: str = "") -> None:
    """Parity + declared-budget + host-sync audit of one family's
    overlapped pipelined step."""
    cfg = FAMILY_CFGS[fam]
    mesh = make_host_mesh(pipe=cfg.num_stages, data=2, model=1)
    name = f"{fam}{tag}:pipelined-overlapped"
    holder: dict = {}

    def go():
        traced, oplan, splans = _trace_pipelined(cfg, mesh, overlap=True,
                                                 sync=sync)
        holder.update(traced=traced, oplan=oplan, splans=splans)
        return analysis.check_collective_parity(traced)

    rep.run(f"{name}:parity", go)
    if not holder:
        return
    rep.run(f"{name}:psum-budget",
            lambda: analysis.check_overlap_branches(
                holder["traced"], holder["oplan"], holder["splans"]))
    rep.run(f"{name}:host-sync",
            lambda: analysis.check_host_transfers(holder["traced"]))


def _audit_entropy_gates(rep: Report) -> None:
    cfg = FAMILY_CFGS["dense"]
    mesh_p = make_host_mesh(pipe=2, data=2, model=1)
    mesh_f = make_host_mesh(data=2, model=1)

    def gate_pipelined():
        on, _, _ = _trace_pipelined(cfg, mesh_p, overlap=True,
                                    measure_entropy=True)
        off, _, _ = _trace_pipelined(cfg, mesh_p, overlap=True,
                                     measure_entropy=False)
        return analysis.check_entropy_gate(on, off, analysis.ENTROPY_PSUMS,
                                           where="dense:pipelined")

    def gate_flat():
        # the flat step measures entropy on already-synced grads: the off
        # variant must lower ZERO fewer collectives (pure compute gate)
        flat_cfg = dataclasses.replace(cfg, num_stages=1)
        on = _trace_flat(flat_cfg, mesh_f, measure_entropy=True)
        off = _trace_flat(flat_cfg, mesh_f, measure_entropy=False)
        return analysis.check_entropy_gate(on, off, 0, where="dense:flat")

    rep.run("dense:pipelined:entropy-gate", gate_pipelined)
    rep.run("dense:flat:entropy-gate", gate_flat)


def _audit_flat(rep: Report) -> None:
    cfg = dataclasses.replace(FAMILY_CFGS["dense"], num_stages=1)
    mesh = make_host_mesh(data=2, model=1)
    holder: dict = {}

    def go():
        traced = _trace_flat(cfg, mesh)
        holder["traced"] = traced
        return analysis.check_collective_parity(traced)

    rep.run("dense:flat:parity", go)
    if holder:
        rep.run("dense:flat:host-sync",
                lambda: analysis.check_host_transfers(holder["traced"]))


def _audit_trainer_cache(rep: Report) -> None:
    """Short REAL run; prove compiled-step variants stay window-bounded."""
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = dataclasses.replace(FAMILY_CFGS["dense"], num_layers=2, d_model=64,
                              d_ff=128, num_stages=1)

    def go():
        mesh = make_host_mesh(data=2, model=1)
        model = build_model(cfg)
        edgc = EDGCConfig()
        edgc = dataclasses.replace(
            edgc, dac=dataclasses.replace(edgc.dac, window=3))
        tr = Trainer(model, mesh, edgc,
                     TrainerConfig(total_steps=6, log_every=100))
        rng = np.random.default_rng(0)

        def data():
            while True:
                toks = rng.integers(0, cfg.vocab_size,
                                    (8, 16)).astype(np.int32)
                yield {"tokens": toks, "labels": toks}

        tr.run(data())
        return analysis.audit_recompiles(tr)

    rep.run("trainer:recompile-window", go)


def _audit_lint(rep: Report) -> None:
    roots = [r for r in LINT_ROOTS if os.path.isdir(r)]

    def go():
        return [analysis.Violation(rule=f.rule, path=f"{f.file}:{f.line}",
                                   message=f.message)
                for f in analysis.run_lint(roots)]

    rep.run(f"lint:{','.join(roots)}", go)


def _audit_zoo(rep: Report, arch: str, shape: str, pipe: int,
               overlap: bool) -> None:
    """Frontier-scale audit of one zoo config — abstract tracing only, so
    a 235B MoE on a 256-chip mesh walks in seconds."""
    from repro.configs import get_config
    from repro.launch.dryrun import input_specs
    from repro.launch.mesh import make_production_mesh, pipe_size
    from repro.pipeline.partition import pipeline_supported

    cfg = get_config(arch, "full")
    mesh = make_production_mesh(pipe=pipe)
    S = pipe_size(mesh)
    cfg = dataclasses.replace(cfg, num_stages=S)
    reason = pipeline_supported(cfg, S)
    if reason is not None:
        print(f"  zoo:{arch}: skipped ({reason})")
        return
    batch = input_specs(cfg, shape)
    holder: dict = {}

    def go():
        traced, oplan, splans = _trace_zoo(cfg, mesh, batch, overlap)
        holder.update(traced=traced, oplan=oplan, splans=splans)
        return analysis.check_collective_parity(traced)

    def _trace_zoo(cfg, mesh, batch, overlap):
        model = build_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        leaves = classify_leaves(params, cfg.num_layers, S, min_dim=128)
        plan = make_plan("edgc", leaves, stage_ranks=[64] * S, num_stages=S)
        part = ppart.make_partition(model, S)
        stage_shapes = jax.eval_shape(
            lambda p: part.partition_params(p)[0], params)
        splans = psync.make_stage_plans(
            plan, S, psync.stage_local_leaves(stage_shapes),
            chunk_bytes=1 << 22, local_path=part.local_leaf_path)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        world = int(np.prod([sizes.get(a, 1)
                             for a in dp_axes(mesh)])) or 1
        M = S

        def init_state():
            p = model.init(jax.random.PRNGKey(0))
            sp, sh = part.partition_params(p)
            ost = adam.init({"stage": sp, "shared": sh}, adam.AdamConfig())
            comp = psync.init_pipeline_comp_state(
                p, plan, jax.random.PRNGKey(1), splans)
            comp = psync.replicate_pipeline_comp_state(comp, world)
            return {"stage_params": sp, "shared_params": sh,
                    "opt_m": ost.m, "opt_v": ost.v, "opt_step": ost.step,
                    "comp": comp}

        state = jax.eval_shape(init_state)
        scfg = TrainStepConfig(
            mode="dp_tp", policy_plan=plan, measure_entropy=True,
            remat=cfg.remat,
            pipeline=PipelineConfig(num_stages=S, schedule="1f1b",
                                    num_microbatches=M,
                                    overlap_sync=overlap,
                                    chunk_bytes=1 << 22))
        step = make_train_step(model, mesh, scfg)
        traced = jax.make_jaxpr(step)(state, batch)
        oplan = plan_overlap("1f1b", S, M, splans) if overlap else None
        return traced, oplan, splans

    rep.run(f"zoo:{arch}:{shape}:parity", go)
    if not holder:
        return
    rep.run(f"zoo:{arch}:{shape}:host-sync",
            lambda: analysis.check_host_transfers(holder["traced"]))
    if overlap and holder["oplan"] is not None:
        rep.run(f"zoo:{arch}:{shape}:psum-budget",
                lambda: analysis.check_overlap_branches(
                    holder["traced"], holder["oplan"], holder["splans"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Collective-safety audit (parity / budgets / host "
                    "syncs / lint) over traced train-step variants.")
    ap.add_argument("--lint-only", action="store_true")
    ap.add_argument("--skip-lint", action="store_true")
    ap.add_argument("--skip-train", action="store_true",
                    help="skip the short real trainer run (cache audit)")
    ap.add_argument("--families", default=None,
                    help=f"comma list from {sorted(FAMILY_CFGS)} "
                         f"(default: all)")
    ap.add_argument("--arch", default=None,
                    help="audit one zoo config instead of the built-ins")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--pipe", type=int, default=4)
    ap.add_argument("--overlap", action="store_true")
    ap.add_argument("--out", default=None, help="write a JSON report")
    args = ap.parse_args(argv)

    rep = Report()
    print("collective-safety audit")
    if not args.skip_lint:
        _audit_lint(rep)
    if args.lint_only:
        pass
    elif args.arch:
        _audit_zoo(rep, args.arch, args.shape, args.pipe, args.overlap)
    else:
        _audit_flat(rep)
        _audit_entropy_gates(rep)
        fams = (args.families.split(",") if args.families
                else list(FAMILY_CFGS))
        for fam in fams:
            _audit_step_family(rep, fam)
        # the wire-coded executor swaps packed payloads under the same
        # collectives: the switch budgets must survive the codec
        _audit_step_family(rep, "dense", sync=SyncConfig(wire="quant8"),
                           tag="+quant8")
        if not args.skip_train:
            _audit_trainer_cache(rep)

    n = len(rep.violations)
    print(f"{len(rep.targets)} target(s), {n} violation(s)")
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rep.as_json(), fh, indent=2)
    return 1 if n else 0


if __name__ == "__main__":
    sys.exit(main())
