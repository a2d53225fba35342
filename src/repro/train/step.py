"""Train / eval / serve step builders.

Two distribution modes (DESIGN §3, §5):

  * ``dp_tp``  — paper-faithful Megatron semantics. The step body runs in a
    ``shard_map`` MANUAL over the (pod, data) axes — each replica computes
    local grads for its batch shard — while the 'model' axis stays AUTO
    (GSPMD applies the Megatron TP rules from dist/sharding.py). The DP
    gradient sync is explicit: EDGC/PowerSGD factor pmeans for compressed
    leaves, plain pmean for the rest. This is where the paper lives.

  * ``auto``   — pure pjit (no shard_map): params FSDP-sharded over 'data'
    + TP over 'model'; XLA inserts the gradient reduce. Used by the
    memory-bound monster archs where replicated-DP params cannot fit
    (llama3-405b, kimi-k2-1t, qwen3-moe-235b); compression policy must be
    'none' in this mode (the sync is a fused reduce-scatter).

The returned step functions are NOT jitted here — launch/dryrun.py lowers
them with explicit in/out shardings, and the trainer wraps them in its
compile cache keyed by CompressionPlan.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.compressor import CompressionPlan
from repro.core.config import SYNC_FIELDS, alias_property, resolve_embedded
from repro.core import powersgd
from repro.core.powersgd import LowRankState
from repro.core.entropy import GDSConfig, grads_entropy
from repro.core.sync_executor import SyncExecutor
from repro.dist.collectives import make_dp_pmean, shard_map_dp
from repro.dist.sharding import batch_pspec, param_shardings
from repro.launch.mesh import dp_axes
from repro.models.model import Model
from repro.obs import scopes
from repro.optim import adam
from repro.pipeline.config import PIPELINE_FIELDS

__all__ = ["TrainStepConfig", "make_train_step", "make_serve_step",
           "make_prefill_step", "TrainState"]


@dataclasses.dataclass(frozen=True, init=False)
class TrainStepConfig:
    """Step-builder config.

    Execution-surface knobs live in the embedded configs: ``pipeline``
    (``repro.pipeline.PipelineConfig`` — stages, schedule, microbatching,
    stashing, sync overlap) and ``sync`` (``repro.core.SyncConfig`` —
    bucketing and kernels for the DP sync). The old flat fields
    (``num_stages``, ``schedule``, ``bucketed``, ``use_kernels``, ...)
    are still accepted as init kwargs and readable as properties —
    deprecated aliases for ``cfg.pipeline.*`` / ``cfg.sync.*``.
    """

    mode: str = "dp_tp"            # dp_tp | auto
    policy_plan: CompressionPlan = CompressionPlan(ranks=())
    gds: GDSConfig = GDSConfig()
    measure_entropy: bool = True
    remat: bool = True             # activation checkpointing over blocks
    guard_nonfinite: bool = False  # recovery: skip non-finite updates
    # Pipeline parallelism + sync-executor surfaces (resolved in __init__;
    # pipeline.num_stages > 1 routes make_train_step to the pipelined
    # builder — the mesh must carry a matching 'pipe' axis).
    pipeline: object = None        # repro.pipeline.PipelineConfig
    sync: object = None            # repro.core.SyncConfig
    adam: adam.AdamConfig = dataclasses.field(default_factory=adam.AdamConfig)

    def __init__(self, mode: str = "dp_tp",
                 policy_plan: CompressionPlan = CompressionPlan(ranks=()),
                 gds: GDSConfig | None = None, measure_entropy: bool = True,
                 remat: bool = True, guard_nonfinite: bool = False,
                 pipeline=None, sync=None,
                 adam=None, **legacy) -> None:
        pipeline, sync = resolve_embedded(pipeline, sync, legacy,
                                          where="TrainStepConfig")
        if adam is None:
            from repro.optim.adam import AdamConfig
            adam = AdamConfig()
        set_ = lambda k, v: object.__setattr__(self, k, v)
        set_("mode", mode)
        set_("policy_plan", policy_plan)
        set_("gds", gds if gds is not None else GDSConfig())
        set_("measure_entropy", measure_entropy)
        set_("remat", remat)
        set_("guard_nonfinite", guard_nonfinite)
        set_("pipeline", pipeline)
        set_("sync", sync)
        set_("adam", adam)


# Deprecated flat-field aliases (kept for existing call sites/tests); the
# canonical homes are cfg.pipeline.* and cfg.sync.*.
for _name in PIPELINE_FIELDS:
    setattr(TrainStepConfig, _name, alias_property("pipeline", _name))
for _name in SYNC_FIELDS:
    setattr(TrainStepConfig, _name, alias_property("sync", _name))
del _name


class TrainState(dict):
    """params / opt / comp (compressor) / step — a plain dict pytree."""


def _loss_with_remat(model: Model, remat: bool):
    if not remat:
        return model.loss_fn
    return jax.checkpoint(model.loss_fn, static_argnums=())


def make_train_step(model: Model, mesh, cfg: TrainStepConfig):
    """Returns (step_fn, in_shardings, out_shardings) ready for jax.jit.

    step signature: (state, batch) -> (state, metrics)
      state = {params, opt_m, opt_v, opt_step, comp}
      metrics = {loss, grad_norm, lr, entropy}

    ``cfg.num_stages > 1`` routes to the pipeline-parallel builder
    (``repro.pipeline.schedule``): same signature, but the state carries
    the stage-partitioned layout of the model family's ``StageAdapter``
    (``repro.pipeline.adapters``) — stage-stacked stacks zero-padded to
    the widest stage for ragged (hybrid/enc-dec) plans, plus the shared
    (pipe-replicated) remainder.
    """
    if cfg.num_stages > 1 or "pipe" in mesh.axis_names:
        from repro.pipeline.schedule import make_pipeline_train_step
        return make_pipeline_train_step(model, mesh, cfg)
    axes = dp_axes(mesh)
    adam_cfg = cfg.adam

    loss_fn = _loss_with_remat(model, cfg.remat)

    manual = cfg.mode == "dp_tp" and bool(axes)
    sync_exec = SyncExecutor(cfg.sync, mode="flat", plan=cfg.policy_plan)

    def local_step(state, batch):
        params = state["params"]
        # Compressor state (the PowerSGD error-feedback residual) is
        # PER-WORKER: it enters with a leading replica dim sharded over the
        # manual axes (locally size 1) — squeeze it here, restore on exit.
        comp_in = state["comp"]
        if manual:
            comp_in = jax.tree_util.tree_map(lambda a: a[0], comp_in)

        # Fault-injection channel: a (B,)-shaped flag array the trainer
        # adds when a nan_grad fault is scheduled (batch-dim shaped so the
        # uniform manual batch spec shards it like any other batch leaf).
        batch = dict(batch)
        inject = batch.pop("_inject", None)

        def lf(p):
            with jax.named_scope(scopes.FORWARD):
                return loss_fn(p, batch)

        (loss, mets), grads = jax.value_and_grad(lf, has_aux=True)(params)
        if inject is not None:
            bad = jnp.max(inject) > 0
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(bad, jnp.full_like(g, jnp.nan), g), grads)
        pmean = make_dp_pmean(axes) if manual else (lambda x: x)
        loss = pmean(loss)
        with jax.named_scope(scopes.COMPRESS):
            synced, comp = sync_exec.sync(grads, comp_in, pmean)
        if cfg.measure_entropy:
            with jax.named_scope(scopes.ENTROPY):
                entropy = grads_entropy(synced, cfg.gds)
        else:
            entropy = jnp.zeros((), jnp.float32)
        opt_state = adam.AdamState(state["opt_step"], state["opt_m"], state["opt_v"])
        with jax.named_scope(scopes.OPTIMIZER):
            if cfg.guard_nonfinite:
                # Recovery guard: a non-finite loss or synced-grad norm (NaN
                # injection, corrupted compressor payload, divergence) must not
                # reach the optimizer OR the compressor's warm-start/EF state.
                # The whole update is computed and discarded leaf-wise — the
                # host sees metrics['skipped'] == 1 and resets the EF state.
                gnorm = adam.global_norm(synced)
                ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
                new_params, new_opt, opt_mets = adam.update(
                    params, synced, opt_state, adam_cfg, gnorm=gnorm)
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), new, old)
                params = keep(new_params, params)
                opt_state = adam.AdamState(
                    step=keep(new_opt.step, opt_state.step),
                    m=keep(new_opt.m, opt_state.m),
                    v=keep(new_opt.v, opt_state.v))
                comp = keep(comp, comp_in)
                skipped = 1.0 - ok.astype(jnp.float32)
            else:
                params, opt_state, opt_mets = adam.update(
                    params, synced, opt_state, adam_cfg)
                skipped = None
        # EF-residual norm on the per-worker comp state BEFORE the replica
        # dim is restored — one scalar, fetched lazily by the obs flush.
        with jax.named_scope(scopes.COMPRESS):
            ef_norm = jnp.sqrt(pmean(powersgd.ef_norm_sq(comp)))
        if manual:
            comp = jax.tree_util.tree_map(lambda a: a[None], comp)
        new_state = {
            "params": params,
            "opt_m": opt_state.m, "opt_v": opt_state.v, "opt_step": opt_state.step,
            "comp": comp,
        }
        metrics = {"loss": loss, "entropy": entropy, "ef_norm": ef_norm,
                   **opt_mets,
                   **{k: pmean(v) for k, v in mets.items() if k != "loss"}}
        if skipped is not None:
            metrics["skipped"] = skipped
        return new_state, metrics

    if manual:
        state_specs = {
            "params": P(), "opt_m": P(), "opt_v": P(), "opt_step": P(),
            "comp": P(tuple(axes)),   # per-worker EF/Q, replica dim first
        }
        step = shard_map_dp(
            local_step, mesh,
            in_specs=(state_specs, _batch_specs_manual(axes)),
            out_specs=({**state_specs}, P()),
            manual_axes=axes,
        )
    else:
        step = local_step
    return step


def replicate_comp_state(comp, world: int):
    """Give compressor leaves their leading per-worker replica dim.

    The warm-start Q must be IDENTICAL across workers at init (PowerSGD
    requirement), so a broadcast — not independent inits — is correct.
    """
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (world,) + a.shape), comp)


def _batch_specs_manual(axes):
    """Manual in_spec for the batch dict: leading dim sharded over DP axes.

    shard_map accepts a pytree-prefix of specs; a single spec broadcasts to
    every dict entry, and all batch arrays carry the batch dim first.
    """
    return P(tuple(axes))


def state_shardings(state, model: Model, mesh, fsdp: bool = False):
    """NamedShardings for the TrainState pytree.

    params (and their opt m/v mirrors) follow the TP rules. Compressor
    state: the per-worker replica dim leads (manual axes); the EF residual's
    TRAILING dims must mirror its param's TP spec — a replicated EF is
    param-sized per chip AND forces XLA to all-gather the (TP-sharded)
    gradient to add it (observed: +120 GiB/chip of gathers on qwen3-32b,
    EXPERIMENTS §Perf H1). Q factors are rank-thin and stay replicated.
    Stacked (group-keyed) compressor states mix leaves with different TP
    specs in one array, so their trailing dims fall back to replicated via
    the pspec lookup below (group keys are not param paths).
    """
    from repro.dist.sharding import param_pspecs

    pshard = param_shardings(state["params"], mesh, fsdp=fsdp)
    rep = NamedSharding(mesh, P())
    axes = dp_axes(mesh)
    lead = (tuple(axes),) if axes else ()

    pspecs_flat = {
        jax.tree_util.keystr(kp): spec
        for kp, spec in jax.tree_util.tree_flatten_with_path(
            param_pspecs(state["params"], mesh))[0]
    }

    comp_shardings = {}
    for path, st in state["comp"].items():
        if not isinstance(st, LowRankState):
            # Raw-array entries (flat-bucket wire-EF residuals, ef:<path>):
            # bucketed-only, hence TP=1 — replicate the trailing dims.
            comp_shardings[path] = NamedSharding(mesh, P(*lead))
            continue
        pspec = pspecs_flat.get(path, P())
        comp_shardings[path] = type(st)(
            q=NamedSharding(mesh, P(*lead)),
            err=NamedSharding(mesh, P(*lead, *tuple(pspec))),
        )
    return {
        "params": pshard,
        "opt_m": pshard, "opt_v": pshard,
        "opt_step": rep,
        "comp": comp_shardings,
    }


def batch_shardings(batch, mesh, batch_size: int):
    return {
        k: NamedSharding(mesh, batch_pspec(v.ndim, mesh, batch_size))
        for k, v in batch.items()
    }


# ----------------------------------------------------------------- serving
def make_prefill_step(model: Model):
    """Full-sequence forward (inference prefill): (params, batch) -> logits."""
    def prefill(params, batch):
        return model.forward(params, batch)
    return prefill


def make_serve_step(model: Model):
    """One decode step: (params, cache, tokens (B,)) -> (logits, cache)."""
    def serve(params, cache, tokens):
        return model.decode_step(params, cache, tokens)
    return serve
