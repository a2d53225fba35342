"""GPipe / 1F1B microbatch schedules over the ``pipe`` mesh axis.

Both schedules run as ONE SPMD program inside a shard_map manual over
(pipe, pod, data): every rank executes the same tick sequence against its
own stage's params, boundary activations move forward via ``ppermute``
(+1 ring) and boundary-activation cotangents move backward via the inverse
``ppermute`` — the compat shim in ``dist/collectives.py`` provides the
shard_map surface. Off-schedule ticks are masked per rank (clipped
microbatch indices, zero cotangents) — SPMD uniformity again.

What a stage computes, what the boundary activation looks like (a pytree:
the enc-dec family ships two channels), and whether a stage contributes an
auxiliary loss (the MoE router balance term) all come from the family's
:class:`~repro.pipeline.adapters.StageAdapter` — this module only owns the
tick tables and the collective choreography.

The backward is a hand-rolled VJP (not ``jax.grad`` of the whole chain):
each backward tick re-derives its stage's forward from SAVED activations
and pulls cotangents through ``jax.vjp``. HOW MUCH is saved is the
``stash_policy`` axis (the executor's memory/compute knob):

  replay   only the stage's boundary input survives the forward tick
           (stage-granular rematerialization, Megatron's standard
           recompute) — the backward's VJP replays the WHOLE stage, with
           the adapter's per-unit remat inside when ``cfg.remat``.
  full     every inter-unit carry is stashed into a second activation
           ring; the backward runs one VJP per unit from its stashed
           input — residual live range is one unit, no remat recompute.
  every_k  stash every ``stash_every``-th unit boundary; segment VJPs
           replay at most k units from the nearest stash (segments run
           un-remat'ed — the stash bounds the residual span instead).

Every policy's VJP re-runs the un-stashed segment forwards exactly once
(one stage-forward total): stashing bounds the residual/recompute SPAN
and removes replay's per-unit remat recompute, it does not change the
replay SUM. ``peak_activation_bytes`` is the byte-accurate ledger of what
each policy keeps live per stage; ``policy_tick_cost`` is the matching
backward-tick cost model the calibrated ``simulate_schedule`` (and with
it the Eq. 4 slack the DAC consumes) runs on. That makes the *schedule*
an explicit tick table rather than whatever AD reversal produces:

  tick grids (F = forward of microbatch j at stage s, B = its backward)

    gpipe :  F at  t = j + s            B at  t = 2M + 2S - 3 - j - s
             all forwards, then all backwards in reverse — M in-flight
             boundary activations per rank.
    1f1b  :  F at  t = j + s            B at  t = j + (2S - 1 - s)
             stage S-1 starts draining one tick after its first forward —
             in-flight activations bounded by min(M, 2S) per rank, the
             1F1B memory bound.

Both schedules leave stage s's LAST backward s ticks before stage 0's —
exactly the per-stage slack Algorithm 2 (Eq. 4) converts into larger
ranks: stage s's DP sync may take ``T_com(r_stage1) + s * T_microBack``
and still finish with stage 0 (the paper's 1-indexed stage i has
``(i-1)`` spare microbatch-backwards; here 0-indexed ``s``).
``simulate_schedule`` generalizes the unit-tick analytics to measured
(t_F, t_B) tick costs — B-cost != F-cost shifts both the bubble fraction
and the Eq. 4 slack the DAC consumes (see benchmarks/pipeline_overlap.py
for the CommModel.fit calibration).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bucketing
from repro.core.comm_model import ring_allreduce_seconds
from repro.core.config import SyncConfig
from repro.core.sync_executor import SyncExecutor
from repro.dist.collectives import make_dp_pmean, shard_map_dp
from repro.dist.sharding import param_pspecs, stage_param_pspecs
from repro.launch.mesh import dp_axes, pipe_size
from repro.models.model import Model
from repro.obs import scopes
from repro.pipeline import sync as psync
from repro.pipeline.partition import make_partition

from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "SCHEDULES",
    "STASH_POLICIES",
    "slot_table",
    "tick_count",
    "ring_slots",
    "bubble_fraction",
    "peak_inflight",
    "sync_slack_ticks",
    "last_backward_tick",
    "sync_ticks",
    "OverlapPlan",
    "plan_overlap",
    "stash_points",
    "stash_segments",
    "tick_spans",
    "peak_activation_bytes",
    "policy_tick_cost",
    "boundary_nbytes",
    "simulate_schedule",
    "make_pipeline_train_step",
    "pipeline_state_shardings",
]

SCHEDULES = ("gpipe", "1f1b")
STASH_POLICIES = ("replay", "full", "every_k")

tmap = jax.tree_util.tree_map


# ------------------------------------------------------------------ analytics
def tick_count(name: str, S: int, M: int) -> int:
    if name == "gpipe":
        return 2 * (M + S - 1)
    if name == "1f1b":
        return M + 2 * S - 1
    raise ValueError(f"unknown schedule {name!r} (want one of {SCHEDULES})")


def ring_slots(name: str, S: int, M: int) -> int:
    """Boundary-activation ring size: the schedule's in-flight bound."""
    return M if name == "gpipe" else min(M, 2 * S)


def _fwd_mb(t: int, s: int) -> int:
    return t - s


def _bwd_mb(name: str, t: int, s: int, S: int, M: int) -> int:
    if name == "gpipe":
        return (2 * M + 2 * S - 3) - t - s
    return t - (2 * S - 1) + s


def first_bwd_tick(name: str, S: int, M: int) -> int:
    return (M + S - 1) if name == "gpipe" else S


def slot_table(name: str, S: int, M: int,
               sync_plan: "OverlapPlan | None" = None) -> list[list[tuple]]:
    """table[s][t] = tuple of ("F"|"B", microbatch) actions at that tick.

    With a ``sync_plan`` (``plan_overlap``), each stage's tick row also
    carries ("S", chunk_id) entries at the ticks where the overlapped
    executor launches that stage's DP-sync chunks — the schedule-
    interleaved tick table, SYNC ticks included.
    """
    n = tick_count(name, S, M)
    table: list[list[tuple]] = [[() for _ in range(n)] for _ in range(S)]
    for s in range(S):
        for t in range(n):
            acts = []
            if t < M + S - 1:
                j = _fwd_mb(t, s)
                if 0 <= j < M:
                    acts.append(("F", j))
            if t >= first_bwd_tick(name, S, M):
                j = _bwd_mb(name, t, s, S, M)
                if 0 <= j < M:
                    acts.append(("B", j))
            table[s][t] = tuple(acts)
    if sync_plan is not None:
        for s in range(S):
            for t, chunk_ids in sync_plan.launches[s]:
                table[s][t] = table[s][t] + tuple(
                    ("S", ci) for ci in chunk_ids)
    return table


def bubble_fraction(S: int, M: int) -> float:
    """Idle fraction of the classic unit-slot model, (S-1)/(M+S-1).

    GPipe and (non-interleaved) 1F1B share it — the schedules differ in
    peak activation memory and WHEN sync slack opens, not total idle time.
    """
    return (S - 1) / (M + S - 1)


def peak_inflight(name: str, S: int, M: int) -> list[int]:
    """Max simultaneously-saved boundary activations per stage (from the
    tick table: +1 at each F, -1 at each B)."""
    table = slot_table(name, S, M)
    peaks = []
    for s in range(S):
        live = peak = 0
        for acts in table[s]:
            for kind, _ in acts:
                if kind not in ("F", "B"):   # "S" sync entries hold no ring slot
                    continue
                live += 1 if kind == "F" else -1
                peak = max(peak, live)
        peaks.append(peak)
    return peaks


def sync_slack_ticks(name: str, S: int, M: int) -> list[int]:
    """Ticks between stage s's last backward and stage 0's (Alg 2 slack)."""
    last_b = last_backward_tick(name, S, M)
    return [last_b[0] - last_b[s] for s in range(S)]


def last_backward_tick(name: str, S: int, M: int) -> list[int]:
    """Tick of stage s's LAST microbatch backward — after it, the stage's
    gradient accumulator is final (off-schedule VJPs add exact zeros), so
    its DP sync may launch on the very next tick."""
    table = slot_table(name, S, M)
    return [max(t for t, acts in enumerate(table[s])
                if any(k == "B" for k, _ in acts)) for s in range(S)]


def sync_ticks(name: str, S: int, M: int) -> list[tuple[int, ...]]:
    """Per-stage ticks eligible to carry SYNC work: strictly after the
    stage's last backward, within the schedule's tick table. 1F1B drains
    back-to-front, so stage s gets the trailing ``sync_slack_ticks[s]``
    ticks (stage 0 gets none — its sync runs post-loop, as before)."""
    last_b = last_backward_tick(name, S, M)
    n = tick_count(name, S, M)
    return [tuple(range(last_b[s] + 1, n)) for s in range(S)]


@dataclasses.dataclass(frozen=True)
class OverlapPlan:
    """Schedule-interleaved sync plan emitted by ``plan_overlap``.

    ``launches[s]`` is a tuple of ``(tick, chunk_ids)`` pairs: at global
    ``tick`` the overlapped executor launches those ``sync_chunks`` of
    stage s's bucket layout (psums for a stacked-PowerSGD shape group, or
    one flat-bucket member run). ``residual[s]`` holds the chunk ids that
    did not fit the stage's drain window and run post-loop (stage 0's
    whole schedule is residual — zero slack). ``feasible[s]`` is the
    Eq. 4 signal the DAC consumes: does stage s's estimated sync time fit
    ``est_sync_seconds[0] + slack_seconds[s]``?
    """

    schedule: str
    num_stages: int
    num_microbatches: int
    launches: tuple          # per stage: ((tick, (chunk_id, ...)), ...)
    residual: tuple          # per stage: (chunk_id, ...)
    slack_seconds: tuple     # per stage, from simulate_schedule
    est_sync_seconds: tuple  # per stage, CommModel estimate (or tick units)
    feasible: tuple          # per stage: bool

    def launch_ticks(self, s: int) -> tuple[int, ...]:
        return tuple(t for t, _ in self.launches[s])


def plan_overlap(name: str, S: int, M: int, splans, *,
                 t_f: float = 1.0, t_b: float = 1.0,
                 comm=None, codec=None) -> OverlapPlan:
    """Plan which sync chunks launch at which drain ticks (the planner).

    Greedy per stage: walk the stage's eligible drain ticks front-to-back
    and pack chunks into each tick until the tick's time budget (``t_b``,
    one backward's worth of compute to hide under) is spent; whatever is
    left spills to the post-loop residual. Chunk times come from the
    fitted ``CommModel`` when given (``ring_allreduce_seconds`` of the
    chunk's wire bytes over the model's ICI bandwidth); without one each
    chunk counts a full tick (the unit model — one chunk per drain tick).

    The feasibility signal compares each stage's total estimated sync
    time against stage 0's plus the stage's measured slack — exactly the
    Eq. 4 budget ``DAC._feasible_clamp`` enforces on ranks.
    """
    sim = simulate_schedule(name, S, M, t_f, t_b)
    slack = sim["slack_seconds"]
    ticks = sync_ticks(name, S, M)
    launches, residual, est = [], [], []
    for s in range(S):
        d = splans.d_of_stage[s]
        chunks = bucketing.sync_chunks(splans.layouts[d])
        if comm is not None:
            # wire_bytes: itemsize-aware raw sizes, or the entropy-coded
            # payload when the sync runs under a codec — transfer placement
            # should plan for the bytes that actually move.
            times = [ring_allreduce_seconds(c.wire_bytes(codec=codec),
                                            comm.world,
                                            comm.hw.ici_bw) for c in chunks]
        else:
            times = [t_b] * len(chunks)
        est.append(sum(times))
        per_tick: list[list[int]] = [[] for _ in ticks[s]]
        rest: list[int] = []
        ti, used = 0, 0.0
        for ci, ct in enumerate(times):
            if ti >= len(per_tick):
                rest.append(ci)
                continue
            per_tick[ti].append(ci)
            used += ct
            if used >= t_b - 1e-12:
                ti, used = ti + 1, 0.0
        launches.append(tuple((ticks[s][i], tuple(ids))
                              for i, ids in enumerate(per_tick) if ids))
        residual.append(tuple(rest))
    return OverlapPlan(
        schedule=name, num_stages=S, num_microbatches=M,
        launches=tuple(launches), residual=tuple(residual),
        slack_seconds=tuple(float(t) for t in slack),  # lint: allow(host-call-in-hot-path) host-side planner, never traced
        est_sync_seconds=tuple(est),
        feasible=tuple(est[s] <= est[0] + slack[s] + 1e-9
                       for s in range(S)),
    )


def overlap_branch_psums(oplan: "OverlapPlan", splans
                         ) -> tuple[tuple[tuple[int, tuple[int, ...]], ...],
                                    tuple[int, ...]]:
    """Declared per-switch psum budgets of the overlapped executor.

    The traced step contains one ``lax.switch`` over ``axis_index('pipe')``
    per launch tick (each branch = one stage's chunk launches for that
    tick) plus one residual switch after the flush.  This derives, from
    the SAME plan the executor consumes, the psum count each branch must
    launch: ``SyncChunk.num_collectives`` summed over the tick's chunk
    ids.  Returns ``(in_loop, residual)`` where ``in_loop`` is
    ``((tick, (count_stage0, ..., count_stageS-1)), ...)`` in tick order —
    the ground truth the auditor's psum-budget pass diffs traced switches
    against (a dropped psum in one branch is deadlock-free but silently
    leaves a chunk unsynced; the diff catches it).
    """
    chunks_by_d = tuple(bucketing.sync_chunks(l) for l in splans.layouts)

    def n_of(s: int, ids) -> int:
        d = splans.d_of_stage[s]
        return sum(chunks_by_d[d][ci].num_collectives for ci in ids)

    launch_at: dict[int, dict[int, tuple[int, ...]]] = {}
    for s in range(oplan.num_stages):
        for t, ids in oplan.launches[s]:
            launch_at.setdefault(t, {})[s] = ids
    in_loop = tuple(
        (t, tuple(n_of(s, launch_at[t].get(s, ()))
                  for s in range(oplan.num_stages)))
        for t in sorted(launch_at))
    residual = tuple(n_of(s, oplan.residual[s])
                     for s in range(oplan.num_stages))
    return in_loop, residual


def stash_points(policy: str, n_units: int, stash_every: int = 2
                 ) -> tuple[int, ...]:
    """Interior unit boundaries the forward tick stashes (static).

    ``replay`` stashes nothing (the backward re-derives the stage from its
    boundary input); ``full`` stashes every inter-unit carry; ``every_k``
    stashes multiples of ``stash_every`` strictly inside ``(0, n_units)``.
    """
    if policy == "replay":
        return ()
    if policy == "full":
        return tuple(range(1, n_units))
    if policy == "every_k":
        return tuple(range(max(1, stash_every), n_units,
                           max(1, stash_every)))
    raise ValueError(
        f"unknown stash policy {policy!r} (want one of {STASH_POLICIES})")


def stash_segments(policy: str, n_units: int, stash_every: int = 2
                   ) -> tuple[tuple[int, int], ...]:
    """Consecutive unit spans between stash points — what the backward
    replays per VJP. ``replay`` degenerates to one whole-stage span."""
    bounds = (0,) + stash_points(policy, n_units, stash_every) + (n_units,)
    return tuple(zip(bounds[:-1], bounds[1:]))


def peak_activation_bytes(name: str, S: int, M: int, policy: str, *,
                          boundary_bytes: int, n_units: int,
                          stash_every: int = 2) -> list[int]:
    """Per-stage peak bytes of the saved-activation rings — the ledger.

    Tick-table derived: each F tick saves one boundary-ring entry plus
    ``len(stash_points)`` stash-ring entries for its microbatch and the
    matching B tick frees them, so the peak live entry count per stage is
    exactly ``peak_inflight``. Every entry is one boundary-spec'd pytree
    (``boundary_bytes``; the stashed inter-unit carry IS the boundary for
    every current family — see ``StageAdapter.stash_spec``), hence
    ``full >= every_k >= replay`` per stage, always.
    """
    n_stash = len(stash_points(policy, n_units, stash_every))
    per_mb = boundary_bytes * (1 + n_stash)
    return [p * per_mb for p in peak_inflight(name, S, M)]


def policy_tick_cost(t_f: float, t_b: float, policy: str,
                     remat: bool = False) -> float:
    """Backward-tick cost model per stash policy (feeds the calibrated
    ``simulate_schedule`` and the Eq. 4 slack the DAC consumes).

    Every policy's hand-rolled VJP re-runs the un-stashed segment
    forwards once — one stage-forward (``t_f``) on top of the pure
    backward ``t_b`` — because stashing bounds the recompute SPAN, not
    the replay SUM. ``replay`` with per-unit remat inside the stage pays
    that forward a second time (the scan bodies recompute under
    ``jax.checkpoint``); the stashed policies run their segments
    un-remat'ed, so they never do.
    """
    if policy not in STASH_POLICIES:
        raise ValueError(
            f"unknown stash policy {policy!r} (want one of {STASH_POLICIES})")
    replay_cost = t_f * (2.0 if (policy == "replay" and remat) else 1.0)
    return t_b + replay_cost


def boundary_nbytes(part, mb: dict) -> int:
    """Bytes of one boundary-activation pytree for one microbatch.

    ``mb`` maps batch keys to per-microbatch ShapeDtypeStructs (or
    arrays); ``part`` is the family's stage adapter.
    """
    import math
    spec = part.boundary_spec(mb)
    return sum(math.prod(l.shape) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(spec))


def tick_spans(name: str, S: int, M: int,
               t_f: float = 1.0, t_b: float = 1.0) -> list[dict]:
    """Per-action spans of the dependency-driven event simulation.

    One dict per tick-table F/B entry::

        {"stage": s, "tick": t, "kind": "F"|"B", "mb": j,
         "start": seconds, "end": seconds}

    This is the timing engine ``simulate_schedule`` aggregates over:
    each F(s, j) waits for F(s-1, j) and the rank's previous op; each
    B(s, j) waits for B(s+1, j) (or its own F on the last stage).
    """
    table = slot_table(name, S, M)
    end_f: dict[tuple[int, int], float] = {}
    end_b: dict[tuple[int, int], float] = {}
    free = [0.0] * S
    spans: list[dict] = []
    for t in range(tick_count(name, S, M)):
        for s in range(S):
            for kind, j in table[s][t]:
                if kind == "F":
                    dep = end_f.get((s - 1, j), 0.0) if s > 0 else 0.0
                    start = max(free[s], dep)
                    end_f[(s, j)] = free[s] = start + t_f
                else:
                    dep = (end_b.get((s + 1, j), 0.0) if s < S - 1
                           else end_f[(s, j)])
                    dep = max(dep, end_f[(s, j)])
                    start = max(free[s], dep)
                    end_b[(s, j)] = free[s] = start + t_b
                spans.append({"stage": s, "tick": t, "kind": kind,
                              "mb": j, "start": start, "end": free[s]})
    return spans


def simulate_schedule(name: str, S: int, M: int,
                      t_f: float = 1.0, t_b: float = 1.0,
                      splans=None, comm=None) -> dict:
    """Dependency-driven timing of a schedule with measured tick costs.

    The unit-tick analytics above assume B-cost == F-cost; real backwards
    run ~2x the forward (plus the stage-replay recompute here), which
    changes both the bubble fraction and the per-stage Eq. 4 slack.
    ``t_b`` is per STASH POLICY: pass ``policy_tick_cost(t_f, t_b_pure,
    policy, remat)`` so the slack the DAC consumes reflects what the
    backward tick actually replays under that policy. This
    replays the slot table as an event simulation: each F(s, j) waits for
    F(s-1, j) and the rank's previous op; each B(s, j) waits for B(s+1, j)
    (or its own F on the last stage). Returns::

        {"makespan": seconds, "bubble_fraction": scalar,
         "slack_seconds": [per stage]}       # Eq. 4 slack in seconds

    The bubble is one number: every stage is busy for exactly
    M * (t_f + t_b) seconds of the same makespan. With t_f == t_b == 1
    it matches ``bubble_fraction`` and the slack equals
    ``sync_slack_ticks`` (the calibration degenerates to the unit model).

    With ``splans`` (per-stage bucket layouts from ``make_stage_plans``)
    the simulation is also the OVERLAP PLANNER: the returned dict gains
    ``out["overlap"]``, the :class:`OverlapPlan` from ``plan_overlap``
    driven by this run's measured (t_f, t_b) — which tick each stage's
    sync chunks launch at, what spills to the residual, and the per-stage
    Eq. 4 feasibility signal (chunk times from the fitted ``comm`` model
    when given).
    """
    spans = tick_spans(name, S, M, t_f, t_b)
    makespan = max(sp["end"] for sp in spans)
    busy = M * (t_f + t_b)
    last_b = [max(sp["end"] for sp in spans
                  if sp["stage"] == s and sp["kind"] == "B")
              for s in range(S)]
    out = {
        "makespan": makespan,
        "bubble_fraction": 1.0 - busy / makespan,
        "slack_seconds": [last_b[0] - last_b[s] for s in range(S)],
    }
    if splans is not None:
        out["overlap"] = plan_overlap(name, S, M, splans,
                                      t_f=t_f, t_b=t_b, comm=comm)
    return out


# ------------------------------------------------------------- step builder
def make_pipeline_train_step(model: Model, mesh, cfg):
    """Pipelined train step: (state, batch) -> (state, metrics).

    ``cfg`` is a ``repro.train.step.TrainStepConfig`` with
    ``num_stages > 1``; the mesh must carry a ``pipe`` axis of that size.
    State layout (see the family's ``StageAdapter`` /
    ``init_pipeline_comp_state``):

      stage_params  stage-stacked stacks, leaves (S, Lmax, ...) over 'pipe'
      shared_params embeddings/head/norms/shared blocks, replicated
      opt_m/opt_v   {"stage": ..., "shared": ...} mirrors of the above
      opt_step      scalar
      comp          per-distinct-plan stacked compressor state,
                    leaves (S, dp_world, ...) over ('pipe', dp axes)
    """
    S = cfg.num_stages
    M = cfg.num_microbatches or S
    name = cfg.schedule
    if name not in SCHEDULES:
        raise ValueError(f"unknown schedule {name!r} (want one of {SCHEDULES})")
    if cfg.measure_entropy and cfg.gds.estimator != "gaussian":
        # The pipelined entropy is reassembled from psum'd sufficient
        # statistics, which only the Gaussian (Lemma 2) estimator admits —
        # refuse loudly rather than silently diverge from the flat step.
        raise ValueError(
            f"pipelined step supports the gaussian entropy estimator only, "
            f"got {cfg.gds.estimator!r}")
    if pipe_size(mesh) != S:
        raise ValueError(f"mesh pipe axis has size {pipe_size(mesh)}, "
                         f"step wants num_stages={S}")
    stash = getattr(cfg, "stash_policy", "replay")
    if stash not in STASH_POLICIES:
        raise ValueError(f"unknown stash policy {stash!r} "
                         f"(want one of {STASH_POLICIES})")
    axes_dp = dp_axes(mesh)
    manual = ("pipe",) + tuple(axes_dp)
    # Stashed policies bound the backward's residual span by the segment
    # width, so per-unit remat inside the stage would only re-add the
    # recompute the stash exists to remove. Replay keeps per-unit remat
    # when the step or, as on the flat path, the model config asks for it:
    # without it a stage's VJP holds every unit's residuals at once.
    part = make_partition(
        model, S,
        remat=(cfg.remat or model.config.remat) and stash == "replay")
    segs = stash_segments(stash, part.num_units(),
                          getattr(cfg, "stash_every", 2))
    n_stash = len(segs) - 1
    adam_cfg = cfg.adam

    sync_cfg = getattr(cfg, "sync", None) or SyncConfig(
        use_kernels=getattr(cfg, "use_kernels", False))
    overlap = bool(getattr(cfg, "overlap_sync", False))

    # Static stage-plan schedule from the flat plan + the local leaf shapes.
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stage_shapes = jax.eval_shape(
        lambda p: part.partition_params(p)[0], params_shapes)
    splans = psync.make_stage_plans(
        cfg.policy_plan, S, psync.stage_local_leaves(stage_shapes),
        bucket_bytes=sync_cfg.bucket_bytes,
        chunk_bytes=int(getattr(cfg, "chunk_bytes", 0) or 0),
        local_path=part.local_leaf_path)
    sync_exec = SyncExecutor(
        sync_cfg, mode="per-stage-overlapped" if overlap else "per-stage",
        splans=splans)
    if overlap:
        # The planner: which drain tick launches which sync chunks. The
        # tick table is static, so the launch plan specializes the traced
        # loop at build time — SYNC ticks become real per-rank branches
        # (one lax.switch on the pipe index per launching tick) instead of
        # every rank running every distinct schedule where-masked.
        oplan = plan_overlap(name, S, M, splans)
        chunks_by_d = tuple(bucketing.sync_chunks(l) for l in splans.layouts)
        launch_at: dict[int, dict[int, tuple[int, ...]]] = {}
        for s_ in range(S):
            for t_, ids_ in oplan.launches[s_]:
                launch_at.setdefault(t_, {})[s_] = ids_
    else:
        oplan, launch_at = None, {}

    R = ring_slots(name, S, M)
    n_ticks = tick_count(name, S, M)
    fbt = first_bwd_tick(name, S, M)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]
    inv_M = 1.0 / M

    def local_step(state, batch):
        from repro.optim import adam

        s_idx = lax.axis_index("pipe")
        is_first = s_idx == 0
        is_last = s_idx == S - 1
        squeeze = lambda t: tmap(lambda a: a[0], t)
        stage_p = squeeze(state["stage_params"])
        shared_p = state["shared_params"]
        comp = tmap(lambda a: a[0, 0], state["comp"])

        def to_mb(a):
            if a.shape[0] % M:
                raise ValueError(f"local batch {a.shape[0]} not divisible by "
                                 f"num_microbatches={M}")
            return a.reshape((M, a.shape[0] // M) + a.shape[1:])

        mb = {k: to_mb(v) for k, v in batch.items()}
        take_mb = lambda j: {k: jnp.take(v, j, axis=0) for k, v in mb.items()}
        bspec = part.boundary_spec(
            {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
             for k, v in mb.items()})
        zeros_bnd = lambda: tmap(lambda s: jnp.zeros(s.shape, s.dtype), bspec)

        def seg_fwd(sp, sh, xin, mbj, i):
            # One stash segment's compute, SPMD-uniform across ranks: the
            # first segment owns embed (+ the is_first boundary select),
            # the last owns the head CE (masked by is_last), and every
            # segment contributes its own aux loss (MoE router balance) —
            # the pipe psum of loss_acc totals both. The masked paths get
            # zero cotangents in the backward, so their params see zero
            # gradient without explicit bookkeeping.
            lo, hi = segs[i]
            if i == 0:
                x0 = part.embed(sh, mbj)
                xin = tmap(lambda a, b: jnp.where(is_first, a, b), x0, xin)
            y, aux = part.blocks_segment(sp, sh, xin, s_idx, lo, hi)
            contrib = aux
            if i == len(segs) - 1:
                head = part.head_loss(sh, y, mbj)
                contrib = contrib + jnp.where(is_last, head, 0.0)
            return y, contrib

        def rank_fwd(sp, sh, mbj, x_recv):
            # Full forward chain; with stash_policy="replay" (one segment)
            # this is byte-identical to the pre-stash executor. The
            # interior segment inputs are what the stash ring saves.
            y = x_recv
            local_loss = jnp.zeros((), jnp.float32)
            interior = []
            for i in range(len(segs)):
                if i:
                    interior.append(y)
                y, contrib = seg_fwd(sp, sh, y, mbj, i)
                local_loss = local_loss + contrib
            return y, local_loss, interior

        fwd_recv = zeros_bnd()
        bwd_recv = zeros_bnd()
        ring = tmap(lambda s: jnp.zeros((R,) + s.shape, s.dtype), bspec)
        sspec = part.stash_spec(
            {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
             for k, v in mb.items()})
        stash_ring = (tmap(lambda s: jnp.zeros((R, n_stash) + s.shape,
                                               s.dtype), sspec)
                      if n_stash else None)
        loss_acc = jnp.zeros((), jnp.float32)
        f32z = lambda t: tmap(lambda a: jnp.zeros(a.shape, jnp.float32), t)
        gacc_s = f32z(stage_p)
        gacc_sh = f32z(shared_p)

        pmean_dp = make_dp_pmean(axes_dp)
        kps, stage_def = jax.tree_util.tree_flatten_with_path(stage_p)
        spaths = tuple(jax.tree_util.keystr(kp) for kp, _ in kps)
        pdt = {p: l.dtype for p, (_, l) in zip(spaths, kps)}
        sync_carry = None
        if overlap:
            # In-loop sync carry: synced stage leaves (wire dtype, zeros
            # until their chunk runs) + the compressor state. Every
            # lax.switch branch returns this exact pytree structure.
            sync_carry = (
                {p: jnp.zeros(l.shape, l.dtype)
                 for p, (_, l) in zip(spaths, kps)},
                comp,
            )

        def launch_sync(t, carry, gacc):
            """Launch tick t's planned chunks: one lax.switch on the pipe
            index. All DP peers of a stage share the index, hence the
            branch, so the chunk psums stay collective-consistent inside
            the stage's DP group while other stages run real F/B work.
            A stage's gacc is final here — its last backward already
            retired (plan invariant; off-schedule VJPs add exact zeros)."""
            here = launch_at[t]
            gvals = jax.tree_util.tree_leaves(gacc)
            g_by_path = {p: g.astype(pdt[p]) for p, g in zip(spaths, gvals)}

            def mk(s):
                ids = here.get(s, ())
                if not ids:
                    return lambda c: c
                d = splans.d_of_stage[s]
                need = sorted({p for ci in ids
                               for p in chunks_by_d[d][ci].member_paths})

                def run(c, ids=ids, d=d, need=need):
                    parts, comp_c = c
                    gb = {p: g_by_path[p] for p in need}
                    upd, comp_c = sync_exec.run_chunks(
                        d, ids, gb, comp_c, pmean_dp)
                    parts = {p: upd.get(p, parts[p]) for p in spaths}
                    return parts, comp_c

                return run

            with jax.named_scope(scopes.COMPRESS):
                return lax.switch(s_idx, [mk(s) for s in range(S)], carry)

        for t in range(n_ticks):
            if t < M + S - 1:
                off = t - s_idx
                valid_f = (off >= 0) & (off < M)
                jf = jnp.clip(off, 0, M - 1)
                with jax.named_scope(scopes.FORWARD):
                    y, loss_mb, interior = rank_fwd(stage_p, shared_p,
                                                    take_mb(jf), fwd_recv)
                loss_acc = loss_acc + jnp.where(valid_f, loss_mb, 0.0)
                upd = lambda r, v: jnp.where(
                    valid_f,
                    lax.dynamic_update_index_in_dim(r, v, jf % R, 0), r)
                ring = tmap(upd, ring, fwd_recv)
                if n_stash:
                    stash_ring = tmap(
                        upd, stash_ring,
                        tmap(lambda *xs: jnp.stack(xs), *interior))
                fwd_recv = tmap(lambda a: lax.ppermute(a, "pipe", fwd_perm), y)
            if t >= fbt:
                # same arithmetic the slot_table analytics use (on traced s)
                offb = _bwd_mb(name, t, s_idx, S, M)
                valid_b = (offb >= 0) & (offb < M)
                jb = jnp.clip(offb, 0, M - 1)
                mbj = take_mb(jb)
                x_saved = tmap(lambda r: jnp.take(r, jb % R, axis=0), ring)
                stash_saved = (tmap(lambda r: jnp.take(r, jb % R, axis=0),
                                    stash_ring) if n_stash else None)

                # vjp is linear in the cotangents: masking them masks the
                # whole backward (param grads AND the outgoing boundary
                # cotangent) — off-schedule ranks contribute exact zeros.
                # seg_fwd internally masks the head by is_last, so the
                # uniform inv_M loss cotangent is correct on every rank
                # (it also pulls the per-stage aux-loss gradients).
                # Segments chain back to front: each VJP re-runs only its
                # own span's forward from the stashed input (replay's
                # single segment re-runs the whole stage) and hands its
                # input cotangent to the upstream segment.
                ct_carry = tmap(
                    lambda a: jnp.where(valid_b & ~is_last, a,
                                        jnp.zeros_like(a)), bwd_recv)
                ct_loss = jnp.where(valid_b, inv_M, 0.0)
                add32 = lambda a, g: a + g.astype(jnp.float32)
                with jax.named_scope(scopes.BACKWARD):
                    for i in range(len(segs) - 1, -1, -1):
                        xin = (x_saved if i == 0 else
                               tmap(lambda a, i=i: a[i - 1], stash_saved))

                        def seg(sp, sh, xr, mbj=mbj, i=i):
                            return seg_fwd(sp, sh, xr, mbj, i)

                        _, vjp = jax.vjp(seg, stage_p, shared_p, xin)
                        gs, gsh, ct_carry = vjp((ct_carry, ct_loss))
                        gacc_s = tmap(add32, gacc_s, gs)
                        gacc_sh = tmap(add32, gacc_sh, gsh)
                bwd_recv = tmap(lambda a: lax.ppermute(a, "pipe", bwd_perm),
                                ct_carry)
            if overlap and t in launch_at:
                sync_carry = launch_sync(t, sync_carry, gacc_s)

        psum_pipe = lambda x: lax.psum(x, "pipe")
        loss = pmean_dp(psum_pipe(loss_acc) * inv_M)

        cast_like = lambda g, p: g.astype(p.dtype)
        gacc_s = tmap(cast_like, gacc_s, stage_p)
        # Shared-param grads: boundary ranks (and, for Zamba's shared attn
        # block, every rank) computed partial contributions; the pipe psum
        # gives every rank the total.
        gacc_sh = tmap(lambda g, p: psum_pipe(g).astype(p.dtype),
                       gacc_sh, shared_p)

        with jax.named_scope(scopes.COMPRESS):
            if overlap:
                # Residual chunks (whatever the drain window couldn't hide —
                # all of stage 0's, whose slack is zero) run post-loop in the
                # same per-stage switch; then the synced leaves reassemble in
                # flatten order and the shared leaves finish exactly as the
                # monolithic path does.
                g_by_path = dict(zip(spaths, jax.tree_util.tree_leaves(gacc_s)))

                def fin(s):
                    ids = oplan.residual[s]
                    d = splans.d_of_stage[s]
                    need = sorted({p for ci in ids
                                   for p in chunks_by_d[d][ci].member_paths})

                    def run(c, ids=ids, d=d, need=need):
                        parts, comp_c = c
                        if ids:
                            gb = {p: g_by_path[p] for p in need}
                            upd, comp_c = sync_exec.run_chunks(
                                d, ids, gb, comp_c, pmean_dp)
                            parts = {p: upd.get(p, parts[p]) for p in spaths}
                        return parts, comp_c

                    return run

                parts_f, comp2 = lax.switch(
                    s_idx, [fin(s) for s in range(S)], sync_carry)
                synced_s = jax.tree_util.tree_unflatten(
                    stage_def, [parts_f[p] for p in spaths])
                synced_sh = sync_exec.sync_shared(gacc_sh, pmean_dp)
            else:
                synced_s, synced_sh, comp2 = sync_exec.sync(
                    gacc_s, comp, pmean_dp, shared_grads=gacc_sh,
                    my_stage=s_idx)

        if cfg.measure_entropy:
            with jax.named_scope(scopes.ENTROPY):
                from repro.core.entropy import entropy_from_moments, sample_moments
                # Ragged stage plans zero-pad each rank's stacks to the widest
                # stage; pooling the PADDED leaves would count the exact-zero
                # pad slots in n and bias sigma (and the Lemma-2 entropy) low.
                # Each top-level key of the stage tree is one adapter stack —
                # its live-unit mask drops pad samples so the pipelined pooled
                # moments match the flat step's exactly.
                z = jnp.zeros((), jnp.float32)
                n1 = a1 = a2 = z
                for key in sorted(synced_s):
                    kn, k1, k2 = sample_moments(
                        synced_s[key], cfg.gds,
                        lead_mask=part.stage_flags(key, s_idx))
                    n1, a1, a2 = n1 + kn, a1 + k1, a2 + k2
                n2, c1, c2 = sample_moments(synced_sh, cfg.gds)
                w = jnp.where(is_first, 1.0, 0.0)  # count shared leaves once
                # Each rank scatters its pooled moments into its stage's slot
                # and the (S,)-vectors psum over pipe: the SAME three Lemma-2
                # collectives as the scalar pooling (the ISR-gate invariant —
                # the off variant lowers exactly 3 fewer psums), but the slots
                # now also yield the per-stage entropy series for free. Slot
                # sums recover the pooled moments exactly: every other rank
                # contributes zeros to a slot.
                scatter = lambda v: jnp.zeros((S,), jnp.float32).at[s_idx].set(v)
                n_vec = psum_pipe(scatter(n1 + w * n2))
                s1_vec = psum_pipe(scatter(a1 + w * c1))
                s2_vec = psum_pipe(scatter(a2 + w * c2))
                entropy = entropy_from_moments(n_vec.sum(), s1_vec.sum(),
                                               s2_vec.sum())
                stage_entropy = entropy_from_moments(n_vec, s1_vec, s2_vec)
        else:
            entropy = jnp.zeros((), jnp.float32)
            stage_entropy = jnp.zeros((S,), jnp.float32)

        with jax.named_scope(scopes.OPTIMIZER):
            sumsq = lambda t: sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                                  for l in jax.tree_util.tree_leaves(t))
            gnorm = jnp.sqrt(psum_pipe(sumsq(synced_s)) + sumsq(synced_sh))

            params_local = {"stage": stage_p, "shared": shared_p}
            grads_local = {"stage": synced_s, "shared": synced_sh}
            ost = adam.AdamState(
                step=state["opt_step"],
                m={"stage": squeeze(state["opt_m"]["stage"]),
                   "shared": state["opt_m"]["shared"]},
                v={"stage": squeeze(state["opt_v"]["stage"]),
                   "shared": state["opt_v"]["shared"]},
            )
            new_p, ost, opt_mets = adam.update(params_local, grads_local, ost,
                                               adam_cfg, gnorm=gnorm)

        unsq = lambda t: tmap(lambda a: a[None], t)
        new_state = {
            "stage_params": unsq(new_p["stage"]),
            "shared_params": new_p["shared"],
            "opt_m": {"stage": unsq(ost.m["stage"]), "shared": ost.m["shared"]},
            "opt_v": {"stage": unsq(ost.v["stage"]), "shared": ost.v["shared"]},
            "opt_step": ost.step,
            "comp": tmap(lambda a: a[None, None], comp2),
        }
        from repro.core.powersgd import ef_norm_sq
        with jax.named_scope(scopes.COMPRESS):
            ef_norm = jnp.sqrt(pmean_dp(psum_pipe(ef_norm_sq(comp2))))
        metrics = {"loss": loss, "entropy": entropy,
                   "stage_entropy": stage_entropy, "ef_norm": ef_norm,
                   **opt_mets}
        return new_state, metrics

    dp = tuple(axes_dp)
    sspecs = {
        "stage_params": P("pipe"),
        "shared_params": P(),
        "opt_m": {"stage": P("pipe"), "shared": P()},
        "opt_v": {"stage": P("pipe"), "shared": P()},
        "opt_step": P(),
        "comp": P("pipe", dp),
    }
    step = shard_map_dp(
        local_step, mesh,
        in_specs=(sspecs, P(dp)),
        out_specs=({**sspecs}, P()),
        manual_axes=manual,
    )
    return step


def pipeline_state_shardings(state, model: Model, mesh):
    """NamedShardings for the pipelined TrainState.

    Stage-stacked leaves: 'pipe' on the stage dim + Megatron TP on the
    rest; shared leaves follow the flat TP rules; compressor state leads
    with ('pipe', dp) and keeps its (rank-thin or group-mixed) trailing
    dims replicated, mirroring the flat trainer's bucketed layout choice.
    """
    stage_specs = stage_param_pspecs(state["stage_params"], mesh)
    shared_specs = param_pspecs(state["shared_params"], mesh)
    dp = dp_axes(mesh)
    ns = lambda spec: NamedSharding(mesh, spec)
    comp_shard = tmap(lambda a: ns(P("pipe", tuple(dp))), state["comp"])
    return {
        "stage_params": tmap(ns, stage_specs),
        "shared_params": tmap(ns, shared_specs),
        "opt_m": {"stage": tmap(ns, stage_specs),
                  "shared": tmap(ns, shared_specs)},
        "opt_v": {"stage": tmap(ns, stage_specs),
                  "shared": tmap(ns, shared_specs)},
        "opt_step": ns(P()),
        "comp": comp_shard,
    }
