"""Trainer: the host loop that runs EDGC (or a baseline policy) end to end.

Responsibilities:
  * build model/optimizer/compressor state (+ shardings on a mesh),
  * drive the EDGCController: alpha-gated entropy readings, window
    boundaries, plan changes,
  * maintain the compile cache — one jitted step per CompressionPlan
    (rank changes re-specialize at window boundaries only, paper §IV-C),
  * account exact DP-sync wire bytes per step (feeds Tables III/VI),
  * checkpoint.

Runs identically on 1 CPU device (fidelity experiments) and on a mesh.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (
    EDGCConfig,
    EDGCController,
    classify_leaves,
    init_compressor_state,
    plan_wire_bytes,
    resize_compressor_state,
)
from repro.core import wire
from repro.core.bucketing import bucketing_supported, make_bucket_layout
from repro.core.config import SYNC_FIELDS, SyncConfig, alias_property, \
    resolve_embedded
from repro.models.model import Model
from repro.obs import scopes
from repro.optim import adam
from repro.train import checkpoint as ckpt_mod
from repro.train.step import (
    TrainStepConfig,
    make_train_step,
    replicate_comp_state,
    state_shardings,
)
from repro.launch.mesh import dp_axes, pipe_size
from repro.pipeline.config import PIPELINE_FIELDS


@dataclasses.dataclass(init=False)
class TrainerConfig:
    """Host-loop config.

    The execution knobs live in the embedded configs: ``pipeline``
    (``repro.pipeline.PipelineConfig`` — schedule, microbatching,
    stashing, sync overlap) and ``sync`` (``repro.core.SyncConfig`` —
    bucketing/kernels; ``bucketed=None`` resolves to "bucketed where the
    mesh supports it", matching the old ``bucketed=True`` default — the
    stacked group state cannot mirror per-leaf TP specs, so TP>1 meshes
    drop to the per-leaf executor). The old flat fields (``schedule``,
    ``bucketed``, ``use_kernels``, ...) remain accepted as init kwargs
    and readable/settable as properties, deprecated in favor of
    ``tcfg.pipeline.*`` / ``tcfg.sync.*``.
    """

    total_steps: int = 1000
    log_every: int = 50
    ckpt_every: int = 0             # 0 = no checkpoints
    ckpt_path: str = "ckpt/state"
    min_compress_dim: int = 64
    measure_entropy: bool = True
    remat: bool = False
    recovery: Any = None            # repro.train.faults.RecoveryConfig
    faults: Any = None              # repro.train.faults.FaultPlan (injection)
    pipeline: Any = None            # repro.pipeline.PipelineConfig
    sync: Any = None                # repro.core.SyncConfig
    metrics: Any = None             # repro.obs.MetricsRegistry (or a view)
    metrics_dir: str | None = None  # convenience: JSONL sink at <dir>/metrics.jsonl
    adam: adam.AdamConfig = dataclasses.field(default_factory=adam.AdamConfig)

    def __init__(self, total_steps: int = 1000, log_every: int = 50,
                 ckpt_every: int = 0, ckpt_path: str = "ckpt/state",
                 min_compress_dim: int = 64, measure_entropy: bool = True,
                 remat: bool = False, recovery=None, faults=None,
                 pipeline=None, sync=None, metrics=None, metrics_dir=None,
                 adam=None, **legacy) -> None:
        pipeline, sync = resolve_embedded(pipeline, sync, legacy,
                                          where="TrainerConfig")
        self.total_steps = total_steps
        self.log_every = log_every
        self.ckpt_every = ckpt_every
        self.ckpt_path = ckpt_path
        self.min_compress_dim = min_compress_dim
        self.measure_entropy = measure_entropy
        self.remat = remat
        self.recovery = recovery
        self.faults = faults
        self.pipeline = pipeline
        self.sync = sync
        self.metrics = metrics
        self.metrics_dir = metrics_dir
        if adam is None:
            from repro.optim.adam import AdamConfig
            adam = AdamConfig()
        self.adam = adam


# Deprecated flat-field aliases; TrainerConfig is mutable, so writes pass
# through too (replacing the embedded frozen config).
for _name in PIPELINE_FIELDS:
    setattr(TrainerConfig, _name,
            alias_property("pipeline", _name, settable=True))
for _name in SYNC_FIELDS:
    setattr(TrainerConfig, _name, alias_property("sync", _name,
                                                 settable=True))
del _name


class Trainer:
    def __init__(self, model: Model, mesh, edgc_cfg: EDGCConfig,
                 tcfg: TrainerConfig, seed: int = 0) -> None:
        self.model = model
        self.mesh = mesh
        self.edgc_cfg = edgc_cfg
        self.tcfg = tcfg
        if edgc_cfg.policy == "edgc" and not tcfg.measure_entropy:
            # The DAC window would silently fill with the step's 0.0
            # placeholder entropies and drive ranks off a constant — an
            # unconditionally corrupt control loop, so refuse up front.
            raise ValueError("policy='edgc' requires measure_entropy=True: "
                             "the DAC consumes the GDS entropy readings")

        key = jax.random.PRNGKey(seed)
        params = model.init(key)
        from repro.models.model import param_count
        self.n_params = param_count(params)   # true count (pre-padding)
        self.leaves = classify_leaves(
            params, model.config.num_layers, edgc_cfg.num_stages,
            min_dim=tcfg.min_compress_dim,
        )
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.world = int(np.prod([sizes.get(a, 1) for a in dp_axes(mesh)])) or 1
        self.controller = EDGCController(edgc_cfg, self.leaves, world=self.world)

        # Pipeline-parallel execution: a 'pipe' mesh axis + num_stages > 1
        # routes everything through repro.pipeline (stage-partitioned state,
        # microbatch schedule, per-stage DP sync). Without a pipe axis,
        # num_stages > 1 keeps the legacy "virtual stages" semantics (DAC
        # emits per-stage ranks, the sync runs on the flat DP mesh).
        self.pipelined = "pipe" in mesh.axis_names
        if self.pipelined and pipe_size(mesh) != edgc_cfg.num_stages:
            raise ValueError(
                f"mesh pipe axis size {pipe_size(mesh)} != "
                f"num_stages={edgc_cfg.num_stages}")

        # The ONE canonical config pair every step build sees (the step
        # builder receives these exact objects, not copied fields): the
        # trainer's PipelineConfig pinned to the executed stage count, and
        # its SyncConfig with ``bucketed`` resolved against the mesh.
        pcfg = tcfg.pipeline
        s_exec = edgc_cfg.num_stages if self.pipelined else 1
        if pcfg.num_stages != s_exec:
            pcfg = dataclasses.replace(pcfg, num_stages=s_exec)
        self.pipeline_cfg = pcfg
        if self.pipelined:
            # pipelined sync is always the per-stage bucketed executor;
            # the flag is only meaningful on the flat path
            self.sync_cfg = (tcfg.sync if tcfg.sync.bucketed is None
                             else dataclasses.replace(tcfg.sync,
                                                      bucketed=None))
        else:
            self._bucketed = ((tcfg.sync.bucketed is not False)
                              and bucketing_supported(mesh))
            self.sync_cfg = dataclasses.replace(tcfg.sync,
                                                bucketed=self._bucketed)

        # ----- wire coding (PR 9) ----------------------------------------
        # The lossless-training wire format rides on the bucketed executor
        # (per-member quantize+pack happens inside the flat-bucket sync);
        # the per-leaf TP fallback has no coded path.
        if self.sync_cfg.wire != "raw" and not self.pipelined \
                and not self._bucketed:
            raise ValueError(
                f"wire={self.sync_cfg.wire!r} requires the bucketed sync "
                "executor (unsupported mesh or SyncConfig.bucketed=False)")
        # entropy mode re-resolves the codec at window boundaries against
        # the first measured entropy (the reference distribution); until a
        # reading exists it falls back to quant8 inside resolve_codec.
        self._wire_ref_entropy: float | None = None
        codec = self.sync_cfg.codec
        if codec is None and self.sync_cfg.wire != "raw":
            codec = wire.resolve_codec(self.sync_cfg.wire)
            self.sync_cfg = dataclasses.replace(self.sync_cfg, codec=codec)
        self._codec = codec

        self._comp_key = jax.random.fold_in(key, 123)
        if self.pipelined:
            self._init_pipelined_state(params, jax.random.fold_in(key, 99),
                                       tcfg.adam)
        else:
            ost = adam.init(params, tcfg.adam)
            # Stacked (group-keyed) compressor state + the bucketed sync
            # executor: O(shape groups + flat buckets) DP collectives
            # instead of O(leaves). TP>1 keeps the per-leaf executor (see
            # TrainerConfig.sync / SyncConfig.bucketed).
            self._layout = (make_bucket_layout(self.leaves,
                                               self.controller.plan,
                                               self.sync_cfg.bucket_bytes)
                            if self._bucketed else None)
            comp = init_compressor_state(params, self.controller.plan,
                                         jax.random.fold_in(key, 99),
                                         layout=self._layout,
                                         wire_ef=self._codec is not None)
            comp = replicate_comp_state(comp, self.world)
            self.state = {"params": params, "opt_m": ost.m, "opt_v": ost.v,
                          "opt_step": ost.step, "comp": comp}
        self._shard_state()

        # Overlapped per-stage sync: hand the DAC the schedule's measured
        # Eq. 4 slack so Algorithm 2 aligns (and feasibility-clamps) ranks
        # against the geometry the overlap planner actually schedules.
        self.overlap_plan = None
        if self.pipelined and self.pipeline_cfg.overlap_sync:
            from repro.pipeline.schedule import plan_overlap
            s_count = self.pipeline_cfg.num_stages
            mb = self.pipeline_cfg.num_microbatches or s_count
            self.overlap_plan = plan_overlap(
                self.pipeline_cfg.schedule, s_count, mb, self._splans)
            t_mb = self.controller.dac.t_micro_back
            self.controller.set_overlap_feedback(
                [t * t_mb for t in self.overlap_plan.slack_seconds])

        self._step_cache: dict[Any, Any] = {}
        self.step_configs: dict[Any, TrainStepConfig] = {}
        self.history: list[dict] = []
        self.bytes_synced = 0           # exact DP wire bytes so far (coded)
        self.bytes_wire_raw = 0         # same payloads priced uncoded
        self.bytes_full = 0             # what no-compression would have moved
        self._last_entropy = 0.0        # most recent alpha-gated reading
        self._last_stage_entropy = None  # per-stage hold (pipelined only)

        # ----- telemetry (repro.obs) --------------------------------------
        # tcfg.metrics wins (shared registry / tagged elastic view); else
        # metrics_dir attaches a JSONL sink; else a bare no-sink registry so
        # the loop never needs a null check.
        from repro.obs import JsonlSink, MetricsRegistry
        if tcfg.metrics is not None:
            self.metrics = tcfg.metrics
        elif tcfg.metrics_dir:
            import os
            self.metrics = MetricsRegistry(
                [JsonlSink(os.path.join(tcfg.metrics_dir, "metrics.jsonl"))])
        else:
            self.metrics = MetricsRegistry()
        pcfg = self.pipeline_cfg
        self.metrics.event(
            "run_meta", step=0,
            model=model.config.name, family=model.config.family,
            policy=edgc_cfg.policy, n_params=int(self.n_params),
            world=self.world, pipelined=self.pipelined,
            num_stages=int(edgc_cfg.num_stages), schedule=pcfg.schedule,
            num_microbatches=int(pcfg.num_microbatches or pcfg.num_stages),
            stash_policy=pcfg.stash_policy, overlap_sync=pcfg.overlap_sync,
            window=int(edgc_cfg.dac.window), log_every=int(tcfg.log_every),
            total_steps=int(tcfg.total_steps))
        if self.overlap_plan is not None:
            op = self.overlap_plan
            n_in = [sum(len(ids) for _, ids in op.launches[s])
                    for s in range(op.num_stages)]
            n_res = [len(op.residual[s]) for s in range(op.num_stages)]
            total = sum(n_in) + sum(n_res)
            self.metrics.event(
                "overlap_plan", step=0,
                in_loop=n_in, residual=n_res,
                slack_seconds=list(op.slack_seconds),
                est_sync_seconds=list(op.est_sync_seconds),
                feasible=list(op.feasible),
                slack_utilization=(sum(n_in) / total if total else 0.0))

        # ----- fault injection + recovery policy (PR 7) -------------------
        from repro.train.faults import FaultPlan, RecoveryState
        self.faults = tcfg.faults if tcfg.faults is not None else FaultPlan()
        self.recovery = (RecoveryState() if tcfg.recovery is not None
                         else None)
        self._guard = bool(tcfg.recovery is not None
                           and tcfg.recovery.guard_nonfinite
                           and not self.pipelined)
        if self.pipelined and (self.faults.has("nan_grad")
                               or self.faults.has("corrupt_payload")):
            raise ValueError("nan_grad/corrupt_payload fault injection "
                             "requires the flat (non-pipelined) trainer: "
                             "the pipelined step has no guard/injection "
                             "channel yet")
        self._ckpt_ring: list[tuple[str, int]] = []  # newest last
        self._tear_next_ckpt = False                 # torn_ckpt fault armed
        self._last_step_ok = True                    # recovered-event edge
        self._ema_seen = 0                           # spike-detector warmup
        # Faults are one-shot (transient): a rollback that replays past a
        # fired event's step must NOT re-inject it, or a deterministic
        # fault would defeat every retry.
        self._fired_faults: set[int] = set()

    def _init_pipelined_state(self, params, comp_key, acfg) -> None:
        from repro.pipeline import partition as ppart
        from repro.pipeline import sync as psync

        S = self.edgc_cfg.num_stages
        reason = ppart.pipeline_supported(self.model.config, S)
        if reason is not None:
            raise ValueError(f"pipeline trainer unsupported: {reason}")
        # The family's stage adapter owns the layout (stacked stage keys,
        # ragged-plan padding, local<->global leaf paths).
        self._part = ppart.make_partition(self.model, S,
                                          remat=self.tcfg.remat)
        stage_p, shared_p = self._part.partition_params(params)
        ost = adam.init({"stage": stage_p, "shared": shared_p}, acfg)
        self._splans = psync.make_stage_plans(
            self.controller.plan, S, psync.stage_local_leaves(stage_p),
            bucket_bytes=self.sync_cfg.bucket_bytes,
            chunk_bytes=self.pipeline_cfg.chunk_bytes,
            local_path=self._part.local_leaf_path)
        comp = psync.init_pipeline_comp_state(
            params, self.controller.plan, comp_key, self._splans,
            wire_ef=self._codec is not None)
        comp = psync.replicate_pipeline_comp_state(comp, self.world)
        self.state = {
            "stage_params": stage_p, "shared_params": shared_p,
            "opt_m": ost.m, "opt_v": ost.v, "opt_step": ost.step,
            "comp": comp,
        }

    # ------------------------------------------------------------------ setup
    def _shard_state(self) -> None:
        if self.pipelined:
            from repro.pipeline.schedule import pipeline_state_shardings
            self._sshard = pipeline_state_shardings(self.state, self.model,
                                                    self.mesh)
        else:
            self._sshard = state_shardings(self.state, self.model, self.mesh)
        self.state = jax.device_put(self.state, self._sshard)

    def _get_step(self, measure_entropy: bool | None = None):
        """Compiled step for the current plan; ``measure_entropy`` picks
        the entropy-on or entropy-off variant (the GDS ISR/alpha gate —
        off-steps must lower no moment work at all, §IV-B)."""
        if measure_entropy is None:
            measure_entropy = self.tcfg.measure_entropy
        plan = self.controller.plan
        # sync_cfg is part of the key: entropy-mode wire coding swaps the
        # codec at window boundaries, which must re-specialize the step.
        key = (plan, measure_entropy, self.sync_cfg)
        if key not in self._step_cache:
            # The step builder sees the trainer's canonical embedded
            # configs BY IDENTITY (no field copying): one source of truth
            # for the pipeline/sync surface across host loop and step.
            scfg = TrainStepConfig(
                mode="dp_tp", policy_plan=plan,
                gds=self.edgc_cfg.gds,
                measure_entropy=measure_entropy,
                remat=self.tcfg.remat,
                guard_nonfinite=self._guard,
                pipeline=self.pipeline_cfg,
                sync=self.sync_cfg,
                adam=self.tcfg.adam,
            )
            self.step_configs[key] = scfg
            raw = make_train_step(self.model, self.mesh, scfg)
            self._step_cache[key] = jax.jit(
                raw,
                in_shardings=(self._sshard, None),
                out_shardings=(self._sshard, NamedSharding(self.mesh, P())),
                donate_argnums=0,
            )
        return self._step_cache[key]

    def step_cache_keys(self) -> tuple:
        """Every ``(plan, measure_entropy, sync_cfg)`` key a compiled step
        variant exists for — the auditor's recompile pass proves the count
        stays window-bounded (plans/codecs only change at DAC windows)."""
        return tuple(self._step_cache)

    def _refresh_codec(self) -> bool:
        """Entropy-mode wire coding: re-pick the bit width from the most
        recent pooled entropy reading (reference = the run's first
        measurement). Returns True when the codec changed, i.e. the byte
        ledger must re-price. Called at window boundaries only, so the
        step re-specialization it triggers rides the existing
        plan-change recompile cadence."""
        if self.sync_cfg.wire != "entropy":
            return False
        hist = self.controller.entropy_history
        if not hist:
            return False
        if self._wire_ref_entropy is None:
            self._wire_ref_entropy = float(hist[0][1])
        new = wire.resolve_codec("entropy",
                                 entropy_nats=self._last_entropy,
                                 ref_nats=self._wire_ref_entropy)
        if new == self._codec:
            return False
        self._codec = new
        self.sync_cfg = dataclasses.replace(self.sync_cfg, codec=new)
        return True

    def _price_plan(self) -> tuple[int, int, int]:
        """(coded, raw-payload, no-compression) bytes per step under the
        current plan. ``coded == raw`` when wire coding is off; ``raw`` is
        the same sync payload priced at its uncoded wire dtype, so
        coded/raw is the measured wire-format reduction."""
        comp, full = plan_wire_bytes(self.leaves, self.controller.plan,
                                     codec=self._codec)
        raw = (plan_wire_bytes(self.leaves, self.controller.plan)[0]
               if self._codec is not None else comp)
        return comp, raw, full

    def _apply_plan_change(self) -> None:
        """Resize/extend compressor state to the new plan (host-side).

        Stacked states migrate between bucket layouts: existing leaves keep
        their warm-start Q (resized) and EF residual; newly-compressed
        leaves get fresh state.
        """
        plan = self.controller.plan
        if self.pipelined:
            from repro.pipeline import sync as psync
            S = self.edgc_cfg.num_stages
            new_splans = psync.make_stage_plans(
                plan, S,
                psync.stage_local_leaves(self.state["stage_params"]),
                bucket_bytes=self.sync_cfg.bucket_bytes,
                chunk_bytes=self.pipeline_cfg.chunk_bytes,
                local_path=self._part.local_leaf_path)
            comp_host = jax.device_get(self.state["comp"])
            fresh = psync.resize_pipeline_comp_state(
                comp_host, self._splans, new_splans, self._comp_key)
            self._splans = new_splans
            comp = psync.replicate_pipeline_comp_state(fresh, self.world)
            self.state = dict(self.state)
            self.state["comp"] = comp
            self._shard_state()
            return
        comp_host = jax.tree_util.tree_map(lambda a: a[0], self.state["comp"])
        if self._bucketed:
            new_layout = make_bucket_layout(self.leaves, plan,
                                            self.sync_cfg.bucket_bytes)
            fresh = resize_compressor_state(
                comp_host, plan, self._comp_key,
                old_layout=self._layout, new_layout=new_layout,
            )
            self._layout = new_layout
        else:
            # per-leaf path: fresh state for new leaves, resize the rest
            params = self.state["params"]
            fresh = init_compressor_state(params, plan, self._comp_key)
            from repro.core.powersgd import resize_rank
            for path in list(fresh.keys()):
                if path in comp_host:
                    fresh[path] = resize_rank(
                        comp_host[path], plan.rank_of(path), self._comp_key)
        comp = replicate_comp_state(fresh, self.world)
        self.state = dict(self.state)
        self.state["comp"] = comp
        self._shard_state()

    # ------------------------------------------------------------------- run
    def run(self, batches: Iterator[dict], num_steps: int | None = None
            ) -> list[dict]:
        """Run ``num_steps`` (default: remaining up to total_steps).

        Can be called repeatedly; the global step counter persists, so
        windows/warm-up continue correctly across calls.

        With ``tcfg.recovery`` set, the loop additionally watches every
        step's outcome: a guarded skip (non-finite update) triggers an EF
        reset, a non-finite or spiking loss rolls back to the newest intact
        checkpoint in the ring (bounded retries + re-arm backoff), and
        repeated anomalies pin the controller to uncompressed sync.
        """
        tcfg, ctrl = self.tcfg, self.controller
        rcfg, rs = tcfg.recovery, self.recovery
        comp_bytes, raw_bytes, full_bytes = self._price_plan()
        stage_b = self.stage_bytes()    # refreshed only at plan changes
        window = self.edgc_cfg.dac.window
        t0 = time.time()
        start = getattr(self, "_global_step", 0)
        end = min(tcfg.total_steps, start + (num_steps if num_steps is not None
                                             else tcfg.total_steps - start))
        inject_nan_faults = self.faults.has("nan_grad")
        # Deferred metric fetch: steps buffer their device metrics here and
        # ONE batched block_until_ready runs at flush boundaries (log_every,
        # window ends, checkpoints, run end) — the step loop itself never
        # forces a device->host sync. The recovery guard is the documented
        # exception: it must read each step's loss to decide skip/rollback.
        pending: list[tuple] = []
        step_idx = start
        while step_idx < end:
            with jax.profiler.StepTraceAnnotation(scopes.STEP,
                                                  step_num=step_idx):
                batch = next(batches)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                fired_now = [(i, ev) for i, ev in enumerate(self.faults.events)
                             if not ev.on_round and ev.at == step_idx
                             and i not in self._fired_faults]
                self._fired_faults.update(i for i, _ in fired_now)
                for _, ev in fired_now:
                    self.metrics.event("fault_injected", step=step_idx,
                                       kind=ev.kind, at=int(ev.at))
                    if ev.kind == "corrupt_payload":
                        self._poison_comp_state()
                    elif ev.kind == "torn_ckpt":
                        self._tear_next_ckpt = True
                if inject_nan_faults:
                    # Constant batch structure (one compiled variant): the flag
                    # array is present on EVERY step once any nan_grad fault is
                    # scheduled, zero except at the scheduled steps.
                    flag = float(any(ev.kind == "nan_grad"
                                     for _, ev in fired_now))
                    bsz = next(iter(batch.values())).shape[0]
                    batch["_inject"] = jnp.full((bsz,), flag, jnp.float32)
                # ISR (alpha) gate: off-iterations dispatch the entropy-off
                # step variant, so the skipped measurements never lower any
                # device work (§IV-B's "fraction of iterations" sampling).
                measure = tcfg.measure_entropy and ctrl.wants_entropy(step_idx)
                step_fn = self._get_step(measure)
                self.state, mets = step_fn(self.state, batch)

                self.bytes_synced += comp_bytes
                self.bytes_wire_raw += raw_bytes
                self.bytes_full += full_bytes

                step_ok = True
                if rs is not None:
                    loss = float(mets["loss"])
                    skipped = float(mets.get("skipped", 0.0)) > 0.5
                    if skipped:
                        # The compiled guard already refused the update; the
                        # compressor warm-start/EF may still hold the garbage
                        # that caused it (corrupted payload), so reset it.
                        rs.skipped_steps += 1
                        rs.anomalies += 1
                        self.metrics.event("guard_skip", step=step_idx,
                                           loss=loss)
                        self._reset_comp_state()
                        rs.ef_resets += 1
                        self.metrics.counter("ef_resets", step=step_idx)
                        self.metrics.event("ef_reset", step=step_idx)
                        step_ok = False
                    elif not np.isfinite(loss):
                        rs.anomalies += 1
                        step_ok = False
                        rolled = self._maybe_rollback()
                        if rolled is not None:
                            self.metrics.event("rollback", step=step_idx,
                                               restored_step=int(rolled))
                            self._maybe_fallback(ctrl)
                            comp_bytes, raw_bytes, full_bytes = self._price_plan()
                            stage_b = self.stage_bytes()
                            step_idx = rolled
                            continue
                    else:
                        armed = (self._ema_seen >= rcfg.spike_warmup
                                 and step_idx >= rs.backoff_until)
                        if (armed and rs.loss_ema is not None and rcfg.rollback
                                and loss > rcfg.spike_factor
                                * max(rs.loss_ema, 1e-8)):
                            rs.anomalies += 1
                            rolled = self._maybe_rollback()
                            if rolled is not None:
                                self.metrics.event("rollback", step=step_idx,
                                                   restored_step=int(rolled),
                                                   spike_loss=loss)
                                self._maybe_fallback(ctrl)
                                comp_bytes, raw_bytes, full_bytes = \
                                    self._price_plan()
                                stage_b = self.stage_bytes()
                                step_idx = rolled
                                continue
                        rs.loss_ema = (loss if rs.loss_ema is None else
                                       rcfg.ema_decay * rs.loss_ema
                                       + (1 - rcfg.ema_decay) * loss)
                        self._ema_seen += 1
                    if self._maybe_fallback(ctrl):
                        comp_bytes, raw_bytes, full_bytes = self._price_plan()
                        stage_b = self.stage_bytes()
                    if step_ok and not self._last_step_ok:
                        self.metrics.event("recovered", step=step_idx)
                    self._last_step_ok = step_ok

                # Buffer this step's device metrics + host-side snapshots; the
                # host reads (on_entropy, history, telemetry) happen in-order at
                # the next flush boundary. Snapshots are taken NOW because the
                # cumulative byte ledgers and rank plan advance under the buffer.
                pending.append((
                    step_idx, measure and step_ok, mets,
                    self.bytes_synced, self.bytes_wire_raw, self.bytes_full,
                    stage_b,
                    ctrl.dac.current_ranks() if not ctrl.in_warmup else [],
                    rs.as_dict() if rs is not None else None,
                    time.time() - t0,
                ))

                at_window = (step_idx + 1) % window == 0
                logged = (step_idx % tcfg.log_every == 0
                          or step_idx == tcfg.total_steps - 1)
                at_ckpt = bool(tcfg.ckpt_every
                               and (step_idx + 1) % tcfg.ckpt_every == 0)
                if at_window or logged or at_ckpt:
                    # Window ends flush BEFORE on_window_end so every gated
                    # entropy reading in the window reaches the DAC; records
                    # therefore snapshot the plan the step actually ran under.
                    self._flush_pending(pending, t0)

                if at_window:
                    with jax.profiler.TraceAnnotation(scopes.WINDOW_END):
                        plan_changed = ctrl.on_window_end(step_idx)
                        if plan_changed:
                            self._apply_plan_change()
                            self.metrics.event(
                                "plan_change", step=step_idx,
                                ranks=ctrl.dac.current_ranks())
                        # entropy-mode wire coding re-picks its bit width here,
                        # on the same cadence as plan changes (one recompile max
                        # per window)
                        if self._refresh_codec():
                            plan_changed = True
                            self.metrics.event(
                                "wire_codec", step=step_idx,
                                bits=int(self._codec.bits),
                                entropy=self._last_entropy)
                        if plan_changed:
                            comp_bytes, raw_bytes, full_bytes = self._price_plan()
                            stage_b = self.stage_bytes()

                if at_ckpt:
                    path = f"{tcfg.ckpt_path}_{step_idx+1}"
                    self.save_checkpoint(path, step=step_idx + 1)
                    self.metrics.event("checkpoint", step=step_idx, path=path)
                    if self._tear_next_ckpt:
                        # torn_ckpt fault: simulate a crash mid-write AFTER the
                        # save completed — the atomic-rename path cannot tear,
                        # so the injector truncates the archive in place.
                        from repro.train.faults import truncate_file
                        truncate_file(path + ".npz")
                        self._tear_next_ckpt = False
                    self._ring_push(path, step_idx + 1)
                step_idx += 1
        self._flush_pending(pending, t0)
        self._global_step = end
        return self.history

    def _flush_pending(self, pending: list[tuple], t0: float) -> None:
        """Drain the deferred-metrics buffer: ONE batched device sync, then
        in-order host processing (controller entropy feed, history records,
        telemetry emission) and a registry flush."""
        with jax.profiler.TraceAnnotation(scopes.FLUSH):
            if pending:
                jax.block_until_ready([m["loss"] for (_, _, m, *_rest) in pending])
            tcfg, ctrl = self.tcfg, self.controller
            for (s_i, meas, m, b_syn, b_raw, b_full, st_b, ranks, rec_rs,
                 wall) in pending:
                if meas:
                    self._last_entropy = float(m["entropy"])
                    if "stage_entropy" in m:
                        self._last_stage_entropy = [
                            float(h) for h in np.asarray(m["stage_entropy"])]
                    ctrl.on_entropy(s_i, self._last_entropy)
                if s_i % tcfg.log_every == 0 or s_i == tcfg.total_steps - 1:
                    rec = {
                        "step": s_i,
                        "loss": float(m["loss"]),
                        # zero-order hold: off-gate steps report the most
                        # recent alpha-gated reading, not the step's 0.0
                        # placeholder (the sampled trajectory stays usable)
                        "entropy": self._last_entropy,
                        "grad_norm": float(m["grad_norm"]),
                        "lr": float(m["lr"]),
                        "bytes_synced": b_syn,
                        "bytes_full": b_full,
                        "stage_bytes": st_b,
                        "ranks": ranks,
                        "wall_s": wall,
                    }
                    if b_raw != b_syn:      # wire coding active
                        rec["bytes_wire_raw"] = b_raw
                    if rec_rs is not None:
                        rec["recovery"] = rec_rs
                    self.history.append(rec)
                    self._emit_step_telemetry(s_i, m, b_syn, b_raw, b_full,
                                              st_b, ranks, wall)
            pending.clear()
            self.metrics.flush()

    def _emit_step_telemetry(self, s_i: int, m: dict, b_syn: int,
                             b_raw: int, b_full: int, st_b, ranks,
                             wall: float) -> None:
        """One logged step's structured records (values already on host)."""
        reg = self.metrics
        reg.scalar("loss", float(m["loss"]), s_i)
        reg.scalar("entropy", self._last_entropy, s_i)
        reg.scalar("grad_norm", float(m["grad_norm"]), s_i)
        reg.scalar("lr", float(m["lr"]), s_i)
        if "ef_norm" in m:
            reg.scalar("ef_norm", float(m["ef_norm"]), s_i)
        reg.scalar("bytes_synced", int(b_syn), s_i)
        reg.scalar("bytes_full", int(b_full), s_i)
        if b_syn:
            reg.scalar("compression_ratio", b_full / b_syn, s_i)
        if self.sync_cfg.wire != "raw":
            # coded vs raw payload bytes: the measured wire-format
            # reduction, orthogonal to the rank-compression ratio above
            reg.scalar("wire_bytes_coded", int(b_syn), s_i)
            reg.scalar("wire_bytes_raw", int(b_raw), s_i)
            if b_raw:
                reg.scalar("wire_reduction", b_syn / b_raw, s_i)
            if self._codec is not None:
                reg.scalar("wire_bits", int(self._codec.bits), s_i)
        reg.scalar("wall_s", wall, s_i)
        reg.series("stage_wire_bytes", [int(c) for c, _ in st_b], s_i)
        reg.series("stage_wire_bytes_full", [int(f) for _, f in st_b], s_i)
        if ranks:
            reg.series("dac_applied_ranks", [int(r) for r in ranks], s_i)
            cqm = self.controller.cqm
            if cqm.anchored:
                reg.series("cqm_error",
                           [float(cqm.error_at(int(r))) for r in ranks], s_i)
        if self._last_stage_entropy is not None:
            # same zero-order hold as the pooled reading: off-gate steps
            # report the most recent measured per-stage vector
            reg.series("stage_entropy", list(self._last_stage_entropy), s_i)

    # ------------------------------------------------------------- recovery
    def _ring_push(self, path: str, step: int) -> None:
        keep = (self.tcfg.recovery.ckpt_ring
                if self.tcfg.recovery is not None else 3)
        self._ckpt_ring.append((path, step))
        del self._ckpt_ring[:-keep]

    def _maybe_rollback(self) -> int | None:
        """Try the ring newest-to-oldest; returns the restored step or None.

        A torn newest checkpoint (CheckpointError) falls through to the
        next older one — the atomic-save + nonce machinery is what makes
        this safe.
        """
        rcfg, rs = self.tcfg.recovery, self.recovery
        if not (rcfg.rollback and rs.rollbacks < rcfg.max_rollbacks):
            return None
        while self._ckpt_ring:
            path, _ = self._ckpt_ring[-1]
            try:
                restored = self.restore_checkpoint(path, load_recovery=False)
            except ckpt_mod.CheckpointError:
                self._ckpt_ring.pop()
                continue
            rs.rollbacks += 1
            rs.backoff_until = restored + rcfg.backoff_steps
            rs.loss_ema = None          # re-warm the spike detector
            self._ema_seen = 0
            return restored
        return None

    def _maybe_fallback(self, ctrl) -> bool:
        """After ``fallback_after`` anomalies, pin to uncompressed sync."""
        rcfg, rs = self.tcfg.recovery, self.recovery
        if rs.fallback or rs.anomalies < rcfg.fallback_after:
            return False
        rs.fallback = True
        if ctrl.force_fallback():
            self._apply_plan_change()
            return True
        return False

    def _reset_comp_state(self) -> None:
        """Fresh compressor state under the current plan (EF reset).

        Wholesale re-init rather than surgical repair: after a corrupted
        payload there is no trustworthy row to keep, and the warm-start Q
        must be identical across workers anyway.
        """
        if self.pipelined:
            raise RuntimeError("EF reset requires the flat trainer")
        fresh = init_compressor_state(self.state["params"],
                                      self.controller.plan, self._comp_key,
                                      layout=self._layout,
                                      wire_ef=self._codec is not None)
        comp = replicate_comp_state(fresh, self.world)
        self.state = dict(self.state)
        self.state["comp"] = comp
        self._shard_state()

    def _poison_comp_state(self) -> None:
        """corrupt_payload fault: NaN-poison the compressor state."""
        from repro.train.faults import poison_lowrank_state
        comp_host = jax.device_get(self.state["comp"])
        self.state = dict(self.state)
        self.state["comp"] = poison_lowrank_state(comp_host)
        self._shard_state()

    # --------------------------------------------------------- checkpointing
    def save_checkpoint(self, path: str, step: int | None = None) -> None:
        """Device tree + the host control plane (controller/DAC/CQM state).

        The ``extra`` dict carries everything the window loop mutates, so a
        resumed run continues mid-window instead of silently restarting
        warm-up (paper §IV-D2: warm-up is a once-per-run phase).
        """
        extra = {
            "step": int(step if step is not None
                        else getattr(self, "_global_step", 0)),
            "bytes_synced": int(self.bytes_synced),
            "bytes_wire_raw": int(self.bytes_wire_raw),
            "bytes_full": int(self.bytes_full),
            "controller": self.controller.state_dict(),
            "metrics": self.metrics.state_dict(),
        }
        if self.recovery is not None:
            extra["recovery"] = self.recovery.as_dict()
        with jax.profiler.TraceAnnotation(scopes.CHECKPOINT):
            ckpt_mod.save(path, self.state, extra=extra)

    def restore_checkpoint(self, path: str, load_recovery: bool = True) -> int:
        """Restore device tree + control plane; returns the global step.

        Order matters: the controller state (and with it the compression
        plan) is restored FIRST, the state template is re-shaped to that
        plan, and only then are the arrays loaded into it.

        ``load_recovery=False`` keeps the live recovery counters (rollback
        must not rewind its own retry budget).
        """
        extra = ckpt_mod.read_extra(path)
        if "controller" in extra:
            self.controller.load_state_dict(extra["controller"])
            self._apply_plan_change()     # reshape comp state to the plan
        if load_recovery and self.recovery is not None and "recovery" in extra:
            from repro.train.faults import RecoveryState
            self.recovery = RecoveryState.from_dict(extra["recovery"])
        from repro.obs.metrics import MetricsRegistry as _Registry
        if (load_recovery and "metrics" in extra
                and isinstance(self.metrics, _Registry)):
            # Telemetry cursor: a resumed run appends to its series instead
            # of restarting at step 0. In-run rollback (load_recovery=False)
            # keeps the LIVE registry — the telemetry already written is
            # real history, not state to rewind. Tagged pod views skip the
            # load too: the fleet owner (ElasticTrainer) restores the shared
            # cursor exactly once.
            self.metrics.load_state_dict(extra["metrics"])
        self.bytes_synced = int(extra.get("bytes_synced", 0))
        self.bytes_wire_raw = int(extra.get("bytes_wire_raw", 0))
        self.bytes_full = int(extra.get("bytes_full", 0))
        self._global_step = int(extra.get("step", 0))
        # re-seed the zero-order hold so post-resume off-gate history
        # records carry the last real reading, not the 0.0 init
        hist = self.controller.entropy_history
        self._last_entropy = float(hist[-1][1]) if hist else 0.0
        # entropy-mode wire coding re-derives its reference (the run's
        # first reading) and current bit width from the restored history
        self._wire_ref_entropy = None
        self._refresh_codec()
        restored, _ = ckpt_mod.restore(path, jax.device_get(self.state))
        self.state = restored
        self._shard_state()
        return self._global_step

    # --------------------------------------------------------------- summary
    def stage_bytes(self) -> list[tuple[int, int]]:
        """Per-stage (compressed, full) DP-sync bytes under the current plan
        — the Algorithm-2 ledger (sums to ``plan_wire_bytes``)."""
        from repro.pipeline.sync import stage_wire_bytes
        return stage_wire_bytes(self.leaves, self.controller.plan,
                                max(1, self.edgc_cfg.num_stages),
                                codec=self._codec)

    def comm_savings(self) -> float:
        """Fraction of DP-sync bytes saved vs no compression (Table III)."""
        if self.bytes_full == 0:
            return 0.0
        return 1.0 - self.bytes_synced / self.bytes_full
