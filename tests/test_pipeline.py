"""Pipeline-parallel subsystem: partitioning, schedules, per-stage sync,
checkpoint resume of the control plane, and pipelined-vs-flat trainer
parity on one device. The runs on a real multi-device pipe axis are in
test_pipeline_multidev.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.core import (
    EDGCConfig, GDSConfig, classify_leaves, init_compressor_state, make_plan,
    plan_wire_bytes, sync_grads,
)
from repro.core.dac import DACConfig
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models.model import ModelConfig, build_model
from repro.optim.adam import AdamConfig
from repro.pipeline import partition as ppart
from repro.pipeline import schedule as psched
from repro.pipeline import sync as psync
from repro.train.trainer import Trainer, TrainerConfig

TINY = ModelConfig(name="pp", family="dense", num_layers=4, d_model=128,
                   num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
                   num_stages=2)

# One tiny config per non-dense family with a stage adapter. zamba is
# deliberately RAGGED (3 layers, attn_every=2 -> groups [2, 1] -> stage
# layer counts [2, 1]); whisper splits 2 enc + 2 dec layers over 2 stages.
FAMILY_CFGS = {
    "moe": ModelConfig(
        name="pp-moe", family="moe", num_layers=4, d_model=128, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=512, num_experts=2,
        experts_per_token=1, capacity_factor=4.0, num_stages=2),
    "xlstm": ModelConfig(
        name="pp-xlstm", family="xlstm", num_layers=4, d_model=128,
        num_heads=2, num_kv_heads=2, vocab_size=512, chunk=16, num_stages=2),
    "zamba": ModelConfig(
        name="pp-zamba", family="zamba", num_layers=3, d_model=128,
        num_heads=4, num_kv_heads=4, d_ff=256, vocab_size=512, ssm_state=16,
        chunk=16, attn_every=2, num_stages=2),
    "whisper": ModelConfig(
        name="pp-whisper", family="whisper", num_layers=2, encoder_layers=2,
        d_model=128, num_heads=4, num_kv_heads=4, d_ff=256, vocab_size=512,
        audio_frames=16, max_position=512, num_stages=2),
    "vlm": ModelConfig(
        name="pp-vlm", family="vlm", num_layers=2, d_model=128, num_heads=4,
        num_kv_heads=4, d_ff=256, vocab_size=512, num_patches=4,
        num_stages=2),
}


def _family_batch(cfg, B=2, T=16, seed=0):
    from repro.data.pipeline import add_modality_stubs
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    batch = add_modality_stubs(batch, cfg.family,
                               audio_frames=cfg.audio_frames,
                               num_patches=cfg.num_patches,
                               d_model=cfg.d_model, seed=seed)
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _setup(stage_ranks=(4, 16)):
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    leaves = classify_leaves(params, TINY.num_layers, 2, min_dim=64)
    plan = make_plan("edgc", leaves, stage_ranks=list(stage_ranks),
                     num_stages=2)
    return model, params, leaves, plan


# ---------------------------------------------------------------- partition
def test_partition_roundtrip():
    model, params, _, _ = _setup()
    stage_p, shared_p = ppart.partition_params(params, 2)
    for leaf in jax.tree_util.tree_leaves(stage_p):
        assert leaf.shape[0] == 2          # leading stage dim
    assert "embed" in shared_p and "stages" not in shared_p
    merged = ppart.merge_params(stage_p, shared_p, 2)
    ref, out = jax.tree_util.tree_flatten(params), \
        jax.tree_util.tree_flatten(merged)
    assert ref[1] == out[1]
    for a, b in zip(ref[0], out[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partition_unsupported():
    """Satellite: the reason string is the ADAPTER's, not a generic one."""
    cfg = ModelConfig(name="x", family="dense", num_layers=3, num_stages=3)
    assert "num_stages" in ppart.pipeline_supported(cfg, 2)  # stage mismatch
    # unregistered family names the registry
    cfg = ModelConfig(name="x", family="nosuch")
    reason = ppart.pipeline_supported(cfg, 2)
    assert "no stage adapter" in reason and "dense" in reason
    # family-specific constraints come from the family's adapter
    cfg = ModelConfig(name="x", family="xlstm", num_layers=2, num_stages=2)
    assert "pair" in ppart.pipeline_supported(cfg, 2)        # 1 pair, 2 stages
    cfg = ModelConfig(name="x", family="zamba", num_layers=2, attn_every=2,
                      num_stages=2, ssm_state=16)
    assert "group" in ppart.pipeline_supported(cfg, 2)       # 1 group, 2 stages
    assert ppart.pipeline_supported(TINY, 2) is None
    # moe / vlm / whisper now have adapters
    cfg = ModelConfig(name="x", family="moe", num_layers=4, num_stages=2,
                      num_experts=2, experts_per_token=1)
    assert ppart.pipeline_supported(cfg, 2) is None
    cfg = ModelConfig(name="x", family="whisper", num_layers=2,
                      encoder_layers=2, num_stages=2)
    assert ppart.pipeline_supported(cfg, 2) is None


@pytest.mark.parametrize("fam", sorted(FAMILY_CFGS))
def test_family_partition_roundtrip(fam):
    """Satellite: every family's adapter partition/merge is lossless —
    including zero-padded ragged stage plans (zamba) and the enc/dec
    union tree (whisper)."""
    cfg = FAMILY_CFGS[fam]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    part = ppart.make_partition(model, cfg.num_stages)
    stage_p, shared_p = part.partition_params(params)
    for leaf in jax.tree_util.tree_leaves(stage_p):
        assert leaf.shape[0] == cfg.num_stages
    assert "stages" not in shared_p
    merged = part.merge_params(stage_p, shared_p)
    ref, out = jax.tree_util.tree_flatten(params), \
        jax.tree_util.tree_flatten(merged)
    assert ref[1] == out[1], fam
    for a, b in zip(ref[0], out[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fam", sorted(FAMILY_CFGS))
def test_family_stagewise_forward_matches_flat(fam):
    """Chaining the adapter's embed -> per-stage blocks -> head (plus the
    per-stage aux losses) on concrete stage indices reproduces the flat
    model's loss — the forward half of pipeline parity, per family,
    without any mesh."""
    cfg = FAMILY_CFGS[fam]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    part = ppart.make_partition(model, cfg.num_stages)
    stage_p, shared_p = part.partition_params(params)
    batch = _family_batch(cfg)

    bnd = part.embed(shared_p, batch)
    aux_total = jnp.zeros((), jnp.float32)
    for s in range(cfg.num_stages):
        tree_s = jax.tree_util.tree_map(lambda a: a[s], stage_p)
        bnd, aux = part.blocks(tree_s, shared_p, bnd, jnp.int32(s))
        aux_total = aux_total + aux
    loss = part.head_loss(shared_p, bnd, batch) + aux_total

    flat_loss, _ = model.loss_fn(params, batch)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(flat_loss),
                               rtol=2e-5, atol=2e-5)


def test_zamba_ragged_stage_plan_is_padded():
    """The hybrid adapter owns layer->stage assignment: whole attention
    groups per stage, ragged counts zero-padded to the widest stage."""
    cfg = FAMILY_CFGS["zamba"]
    model = build_model(cfg)
    part = ppart.make_partition(model, 2)
    assert part.unit_counts() == {"mamba": [2, 1]}
    params = model.init(jax.random.PRNGKey(0))
    stage_p, _ = part.partition_params(params)
    for leaf in jax.tree_util.tree_leaves(stage_p):
        assert leaf.shape[:2] == (2, 2)     # (S, Lmax) with stage 1 padded
    # padded slice is exactly zero
    pad = jax.tree_util.tree_map(lambda a: a[1, 1:], stage_p)
    assert all(float(jnp.max(jnp.abs(l))) == 0.0
               for l in jax.tree_util.tree_leaves(pad))


def test_local_global_path_mapping():
    _, params, _, plan = _setup()
    for path, _ in plan.ranks:
        s, lp = ppart.local_leaf_path(path)
        assert ppart.global_leaf_path(s, lp) == path
    assert ppart.local_leaf_path("['embed']['tok']") is None


# ---------------------------------------------------------------- schedules
@pytest.mark.parametrize("name", psched.SCHEDULES)
@pytest.mark.parametrize("S,M", [(2, 2), (4, 4), (4, 8), (3, 7)])
def test_schedule_table_dependencies(name, S, M):
    """Every F/B obeys pipeline dataflow; every microbatch runs exactly once."""
    table = psched.slot_table(name, S, M)
    f_tick = {}
    b_tick = {}
    for s in range(S):
        for t, acts in enumerate(table[s]):
            for kind, j in acts:
                (f_tick if kind == "F" else b_tick)[(s, j)] = t
    assert set(f_tick) == {(s, j) for s in range(S) for j in range(M)}
    assert set(b_tick) == set(f_tick)
    for s in range(S):
        for j in range(M):
            if s > 0:       # F needs upstream F one tick earlier
                assert f_tick[(s, j)] > f_tick[(s - 1, j)]
            if s < S - 1:   # B needs downstream B one tick earlier
                assert b_tick[(s, j)] > b_tick[(s + 1, j)]
            assert b_tick[(s, j)] > f_tick[(s, j)]
    # in-flight activations never exceed the ring the executor allocates
    peaks = psched.peak_inflight(name, S, M)
    assert max(peaks) <= psched.ring_slots(name, S, M)


def test_simulate_schedule_degenerates_and_weights():
    """Satellite: the weighted-tick simulator matches the unit analytics at
    t_f == t_b == 1 and scales the Eq. 4 slack by the BACKWARD tick cost."""
    S, M = 4, 16
    for name in psched.SCHEDULES:
        sim = psched.simulate_schedule(name, S, M, 1.0, 1.0)
        assert sim["bubble_fraction"] == pytest.approx(
            psched.bubble_fraction(S, M))
        assert sim["slack_seconds"] == [
            float(s) for s in psched.sync_slack_ticks(name, S, M)]
    # B-cost 2x F-cost: slack (in seconds) is s backward ticks
    sim = psched.simulate_schedule("1f1b", S, M, 1.0, 2.0)
    assert sim["slack_seconds"] == [0.0, 2.0, 4.0, 6.0]
    assert sim["makespan"] == (M + S - 1) * 3.0


def test_stash_points_and_segments():
    """Stash cuts are static interior unit boundaries; the segments tile
    [0, n_units) for every policy."""
    assert psched.stash_points("replay", 6) == ()
    assert psched.stash_points("full", 6) == (1, 2, 3, 4, 5)
    assert psched.stash_points("every_k", 6, 2) == (2, 4)
    assert psched.stash_points("every_k", 7, 3) == (3, 6)
    assert psched.stash_points("full", 1) == ()      # single unit: no cuts
    with pytest.raises(ValueError, match="stash policy"):
        psched.stash_points("nope", 4)
    for pol, n, k in [("replay", 5, 2), ("full", 5, 2), ("every_k", 5, 2),
                      ("every_k", 8, 3)]:
        segs = psched.stash_segments(pol, n, k)
        assert segs[0][0] == 0 and segs[-1][1] == n
        assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))
        assert all(hi > lo for lo, hi in segs)
        assert len(segs) == len(psched.stash_points(pol, n, k)) + 1


@pytest.mark.parametrize("name", psched.SCHEDULES)
@pytest.mark.parametrize("S,M", [(2, 4), (4, 8), (4, 16)])
def test_peak_activation_bytes_matches_tick_oracle(name, S, M):
    """Acceptance: the activation-memory ledger equals an independent
    tick-table walk — at each F the stage saves (1 + n_stash) boundary-
    sized entries for its microbatch, each B frees them — and orders
    full >= every_k >= replay per stage."""
    n_units, bbytes, k = 4, 1000, 2
    table = psched.slot_table(name, S, M)
    by_pol = {}
    for pol in psched.STASH_POLICIES:
        per_entry = bbytes * (1 + len(psched.stash_points(pol, n_units, k)))
        oracle = []
        for s in range(S):
            live = peak = 0
            for acts in table[s]:
                for kind, _ in acts:
                    live += per_entry if kind == "F" else -per_entry
                    peak = max(peak, live)
            oracle.append(peak)
        got = psched.peak_activation_bytes(name, S, M, pol,
                                           boundary_bytes=bbytes,
                                           n_units=n_units, stash_every=k)
        assert got == oracle, (name, pol, got, oracle)
        by_pol[pol] = got
    for s in range(S):
        assert (by_pol["full"][s] >= by_pol["every_k"][s]
                >= by_pol["replay"][s])
    assert max(by_pol["full"]) > max(by_pol["every_k"]) \
        > max(by_pol["replay"])


def test_policy_tick_cost_model():
    """Every policy's VJP replays the un-stashed spans once (+t_f); only
    replay-with-remat pays the per-unit recompute a second time."""
    t_f, t_b = 1.0, 2.5
    assert psched.policy_tick_cost(t_f, t_b, "replay") == t_b + t_f
    assert psched.policy_tick_cost(t_f, t_b, "full") == t_b + t_f
    assert psched.policy_tick_cost(t_f, t_b, "every_k") == t_b + t_f
    assert psched.policy_tick_cost(t_f, t_b, "replay", remat=True) \
        == t_b + 2 * t_f
    # stashed segments run un-remat'ed: remat never changes their cost
    assert psched.policy_tick_cost(t_f, t_b, "full", remat=True) == t_b + t_f
    with pytest.raises(ValueError, match="stash policy"):
        psched.policy_tick_cost(t_f, t_b, "nope")


def test_schedule_analytics():
    S, M = 4, 16
    assert psched.bubble_fraction(S, M) == pytest.approx((S - 1) / (M + S - 1))
    # 1F1B bounds in-flight activations by min(M, 2S); GPipe holds all M
    assert max(psched.peak_inflight("gpipe", S, M)) == M
    assert max(psched.peak_inflight("1f1b", S, M)) <= min(M, 2 * S)
    # both schedules open s ticks of sync slack at stage s (Alg 2 / Eq. 4)
    for name in psched.SCHEDULES:
        assert psched.sync_slack_ticks(name, S, M) == list(range(S))


# -------------------------------------------------------------- stage plans
def test_make_stage_plans_distinct_grouping():
    model, params, leaves, plan = _setup(stage_ranks=(4, 16))
    stage_p, _ = ppart.partition_params(params, 2)
    local = psync.stage_local_leaves(stage_p)
    splans = psync.make_stage_plans(plan, 2, local)
    assert splans.num_stages == 2
    assert len(splans.distinct) == 2           # two distinct ranks
    assert splans.d_of_stage == (0, 1)
    for s, sp in enumerate(splans.stage_plans):
        assert sp.ranks, f"stage {s} must compress"
        for lp, r in sp.ranks:
            assert r == (4, 16)[s]
            assert plan.rank_of(ppart.global_leaf_path(s, lp)) == r
    # uniform plan -> one schedule, zero masked redundancy
    uni = make_plan("fixed", leaves, fixed_rank=8)
    su = psync.make_stage_plans(uni, 2, local)
    assert len(su.distinct) == 1
    assert su.d_of_stage == (0, 0)


def test_stage_wire_bytes_sums_to_plan():
    _, _, leaves, plan = _setup()
    per_stage = psync.stage_wire_bytes(leaves, plan, 2)
    comp, full = plan_wire_bytes(leaves, plan)
    assert sum(c for c, _ in per_stage) == comp
    assert sum(f for _, f in per_stage) == full
    # stage 1 runs rank 16 vs stage 0's rank 4 on identical block shapes:
    # its block bytes are strictly larger (Alg 2: later stages, bigger ranks)
    assert per_stage[1][0] > 0 and per_stage[0][0] > 0


# ---------------------------------------------------- per-stage sync parity
def test_stage_sync_matches_per_leaf_oracle_and_applies_stage_ranks():
    """Acceptance: DAC ranks are applied per stage — wire accounting via a
    psum spy — and the synced grads match the flat per-leaf oracle."""
    model, params, leaves, plan = _setup(stage_ranks=(4, 16))
    stage_p, shared_p = ppart.partition_params(params, 2)
    splans = psync.make_stage_plans(plan, 2,
                                    psync.stage_local_leaves(stage_p))
    comp = psync.init_pipeline_comp_state(params, plan, jax.random.PRNGKey(1),
                                          splans)

    rng = np.random.default_rng(0)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
        params)
    g_stage, g_shared = ppart.partition_params(grads, 2)

    # flat per-leaf oracle on the full tree
    oracle_state = init_compressor_state(params, plan, jax.random.PRNGKey(1))
    oracle, _ = sync_grads(grads, oracle_state, plan, lambda x: x)
    o_stage, o_shared = ppart.partition_params(oracle, 2)

    for s in range(2):
        local_g = jax.tree_util.tree_map(lambda a: a[s], g_stage)
        local_c = jax.tree_util.tree_map(lambda a: a[s], comp)
        spy = analysis.CollectiveSpy()
        synced_s, synced_sh, _ = psync.stage_sync_grads(
            local_g, g_shared, local_c, splans, spy, my_stage=s)

        # per-stage rank application: the schedule covering stage s psums
        # factors whose trailing dim is EXACTLY the DAC rank for stage s
        # (and the other schedule's rank also appears — masked SPMD pass)
        assert (4, 16)[s] in spy.factor_ranks()
        assert spy.factor_ranks() == [4, 16]  # both schedules execute (SPMD)

        # grads parity with the flat oracle, stage leaves + shared leaves
        want = jax.tree_util.tree_map(lambda a: a[s], o_stage)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(synced_s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(o_shared),
                        jax.tree_util.tree_leaves(synced_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_moe_stage_sync_psum_spy_applies_stage_ranks():
    """Satellite: per-stage DAC ranks apply on a MoE tree — expert stacks
    compress through 3-D factor psums whose trailing dim is the stage's
    rank, and the result matches the flat per-leaf oracle."""
    cfg = FAMILY_CFGS["moe"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    leaves = classify_leaves(params, cfg.num_layers, 2, min_dim=64)
    plan = make_plan("edgc", leaves, stage_ranks=[4, 16], num_stages=2)
    # expert stacks must be in the plan (router excluded)
    assert any("experts" in p for p, _ in plan.ranks)
    assert not any("router" in p for p, _ in plan.ranks)

    part = ppart.make_partition(model, 2)
    stage_p, shared_p = part.partition_params(params)
    splans = psync.make_stage_plans(plan, 2,
                                    psync.stage_local_leaves(stage_p),
                                    local_path=part.local_leaf_path)
    comp = psync.init_pipeline_comp_state(params, plan, jax.random.PRNGKey(1),
                                          splans)

    rng = np.random.default_rng(0)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
        params)
    g_stage, g_shared = part.partition_params(grads)

    oracle_state = init_compressor_state(params, plan, jax.random.PRNGKey(1))
    oracle, _ = sync_grads(grads, oracle_state, plan, lambda x: x)
    o_stage, o_shared = part.partition_params(oracle)

    for s in range(2):
        local_g = jax.tree_util.tree_map(lambda a: a[s], g_stage)
        local_c = jax.tree_util.tree_map(lambda a: a[s], comp)
        spy = analysis.CollectiveSpy()
        synced_s, synced_sh, _ = psync.stage_sync_grads(
            local_g, g_shared, local_c, splans, spy, my_stage=s)
        assert (4, 16)[s] in spy.factor_ranks()
        assert spy.factor_ranks() == [4, 16]  # both schedules execute (SPMD)

        want = jax.tree_util.tree_map(lambda a: a[s], o_stage)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(synced_s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(o_shared),
                        jax.tree_util.tree_leaves(synced_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_hybrid_ragged_nonuniform_stage_plans():
    """Acceptance: per-stage BucketLayout grouping on a NON-UNIFORM
    (ragged hybrid) stage plan — distinct per-stage layouts, padded local
    shapes, padded gradient slices stay exactly zero through the sync,
    and live slices match the leaf-level compressor run with the same
    warm-start state."""
    from repro.core import bucketing
    from repro.core.powersgd import compress_leaf

    cfg = FAMILY_CFGS["zamba"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    leaves = classify_leaves(params, cfg.num_layers, 2, min_dim=64)
    plan = make_plan("edgc", leaves, stage_ranks=[4, 16], num_stages=2)
    assert plan.ranks, "zamba mamba stacks must be compressible"
    # the shared attention block must NOT be in the plan (pipe-replicated)
    assert not any("shared" in p for p, _ in plan.ranks)

    part = ppart.make_partition(model, 2)
    stage_p, shared_p = part.partition_params(params)
    splans = psync.make_stage_plans(plan, 2,
                                    psync.stage_local_leaves(stage_p),
                                    local_path=part.local_leaf_path)
    assert len(splans.distinct) == 2           # two distinct rank plans
    r0 = {g.rank for g in splans.layouts[0].groups}
    r1 = {g.rank for g in splans.layouts[1].groups}
    assert r0 == {4} and r1 == {16}
    # local shapes are the PADDED per-rank shapes (Lmax = 2 everywhere)
    for lay in splans.layouts:
        for g in lay.groups:
            for _, shp in g.members:
                assert shp[0] == 2

    comp = psync.init_pipeline_comp_state(params, plan, jax.random.PRNGKey(1),
                                          splans)
    rng = np.random.default_rng(1)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
        params)
    g_stage, g_shared = part.partition_params(grads)

    for s in range(2):
        local_g = jax.tree_util.tree_map(lambda a: a[s], g_stage)
        local_c = jax.tree_util.tree_map(lambda a: a[s], comp)
        synced_s, _, _ = psync.stage_sync_grads(
            local_g, g_shared, local_c, splans, lambda x: x, my_stage=s)
        # leaf-level oracle: same warm-start state, per-leaf compression
        d = splans.d_of_stage[s]
        per_leaf = bucketing.unstack_state(
            {k[len(f"p{d}:"):]: v for k, v in local_c.items()
             if k.startswith(f"p{d}:")},
            splans.layouts[d])
        by_path = {jax.tree_util.keystr(kp): g for kp, g
                   in jax.tree_util.tree_flatten_with_path(local_g)[0]}
        synced_by_path = {jax.tree_util.keystr(kp): g for kp, g
                          in jax.tree_util.tree_flatten_with_path(synced_s)[0]}
        for lp, _rank in splans.stage_plans[s].ranks:
            want, _ = compress_leaf(by_path[lp], per_leaf[lp], lambda x: x)
            np.testing.assert_allclose(np.asarray(synced_by_path[lp]),
                                       np.asarray(want),
                                       rtol=1e-4, atol=1e-5)
        if s == 1:   # stage 1's second (padded) slice: zero in, zero out
            for lp, _rank in splans.stage_plans[s].ranks:
                np.testing.assert_array_equal(
                    np.asarray(by_path[lp][1:]) * 0,
                    np.asarray(synced_by_path[lp][1:]))


def _family_trainer(cfg, mesh, steps, num_micro):
    model = build_model(cfg)
    edgc = EDGCConfig(policy="fixed", fixed_rank=8, num_stages=1,
                      total_iterations=steps,
                      gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=3, adjust_limit=4))
    tcfg = TrainerConfig(total_steps=steps, log_every=1, schedule="1f1b",
                         num_microbatches=num_micro,
                         adam=AdamConfig(lr=1e-3, warmup_steps=2,
                                         total_steps=steps))
    return Trainer(model, mesh, edgc, tcfg, seed=0)


def _family_data(cfg, seed=3):
    base = SyntheticLM(cfg.vocab_size, 32, 4, seed=seed)
    from repro.data.pipeline import add_modality_stubs
    for b in base.batches():
        yield add_modality_stubs(b, cfg.family,
                                 audio_frames=cfg.audio_frames,
                                 num_patches=cfg.num_patches,
                                 d_model=cfg.d_model, seed=seed)


@pytest.mark.parametrize("fam,num_micro", [
    ("moe", 1), ("zamba", 2), ("whisper", 2), ("xlstm", 2), ("vlm", 2),
])
def test_pipelined_trainer_families_pipe1_parity(fam, num_micro):
    """Acceptance: non-dense pipe=1 pipelined training (microbatching,
    boundary rings, manual VJP, per-stage sync) matches the flat trainer's
    loss trajectory. MoE runs M=1: with top-1 routing, the per-microbatch
    router-aux mean differs from the full-batch mean in a way that FLIPS
    discrete expert assignments after one update, so microbatch counts
    must agree for a strict parity statement (the flat trainer has no
    microbatching; see test_pipelined_moe_microbatched_envelope)."""
    cfg = dataclasses.replace(FAMILY_CFGS[fam], num_stages=1)
    steps = 4
    tp = _family_trainer(cfg, make_host_mesh(pipe=1, data=1, model=1),
                         steps, num_micro)
    hp = tp.run(_family_data(cfg))
    tf_ = _family_trainer(cfg, make_host_mesh(data=1, model=1), steps, 0)
    hf = tf_.run(_family_data(cfg))
    lp, lf = [h["loss"] for h in hp], [h["loss"] for h in hf]
    assert max(abs(a - b) for a, b in zip(lp, lf)) < 5e-3, (fam, lp, lf)
    assert tp.bytes_synced == tf_.bytes_synced


def test_pipelined_moe_microbatched_envelope():
    """MoE with real microbatching (M=2) stays finite and inside a loose
    envelope of the flat trainer: per-microbatch router-aux gradients
    legitimately differ from the full-batch ones (exactly as per-DP-shard
    aux does), and top-1 routing makes that a discrete perturbation."""
    cfg = dataclasses.replace(FAMILY_CFGS["moe"], num_stages=1)
    steps = 4
    tp = _family_trainer(cfg, make_host_mesh(pipe=1, data=1, model=1),
                         steps, 2)
    hp = tp.run(_family_data(cfg))
    tf_ = _family_trainer(cfg, make_host_mesh(data=1, model=1), steps, 0)
    hf = tf_.run(_family_data(cfg))
    lp, lf = [h["loss"] for h in hp], [h["loss"] for h in hf]
    assert all(np.isfinite(lp)), lp
    assert max(abs(a - b) for a, b in zip(lp, lf)) < 0.2, (lp, lf)


def test_resize_pipeline_comp_state_across_replan():
    """DAC window re-plan: Q keeps leading columns / EF survives, per stage."""
    model, params, leaves, _ = _setup()
    stage_p, _ = ppart.partition_params(params, 2)
    local = psync.stage_local_leaves(stage_p)
    plan0 = make_plan("edgc", leaves, stage_ranks=[8, 8], num_stages=2)
    plan1 = make_plan("edgc", leaves, stage_ranks=[4, 16], num_stages=2)
    sp0 = psync.make_stage_plans(plan0, 2, local)
    sp1 = psync.make_stage_plans(plan1, 2, local)
    st0 = psync.replicate_pipeline_comp_state(
        psync.init_pipeline_comp_state(params, plan0, jax.random.PRNGKey(2),
                                       sp0), 1)
    st1 = psync.resize_pipeline_comp_state(st0, sp0, sp1,
                                           jax.random.PRNGKey(3))
    from repro.core import bucketing
    for s, r_new in [(0, 4), (1, 16)]:
        d0, d1 = sp0.d_of_stage[s], sp1.d_of_stage[s]
        old = {k[len(f"p{d0}:"):]:
               jax.tree_util.tree_map(lambda a: a[s, 0], v)
               for k, v in st0.items() if k.startswith(f"p{d0}:")}
        new = {k[len(f"p{d1}:"):]:
               jax.tree_util.tree_map(lambda a: a[s], v)
               for k, v in st1.items() if k.startswith(f"p{d1}:")}
        per0 = bucketing.unstack_state(old, sp0.layouts[d0])
        per1 = bucketing.unstack_state(new, sp1.layouts[d1])
        assert set(per0) == set(per1)
        for lp in per1:
            assert per1[lp].q.shape[-1] == r_new
            np.testing.assert_array_equal(np.asarray(per0[lp].err),
                                          np.asarray(per1[lp].err))
            keep = min(8, r_new)
            np.testing.assert_array_equal(
                np.asarray(per0[lp].q[..., :keep]),
                np.asarray(per1[lp].q[..., :keep]))


# ------------------------------------- pipelined entropy vs flat (ragged)
@pytest.mark.parametrize("fam", ["zamba", "whisper"])
def test_pipelined_entropy_matches_flat_ragged(fam):
    """Acceptance/regression: ragged stage plans zero-pad each rank's
    stacks — pooling the PADDED leaves fed exact-zero pad slots into the
    Lemma-2 moments (sigma under-estimated, entropy biased low). With the
    live-unit masks the pipelined pooled entropy equals the flat
    ``grads_entropy`` to 1e-6 (the strided sample positions coincide)."""
    from repro.core.entropy import (
        entropy_from_moments, grads_entropy, sample_moments,
    )
    cfg = FAMILY_CFGS[fam]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    part = ppart.make_partition(model, cfg.num_stages)
    rng = np.random.default_rng(0)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
        params)
    g_stage, g_shared = part.partition_params(grads)
    gds = GDSConfig(alpha=0.5, beta=0.25)

    z = jnp.zeros((), jnp.float32)
    n = s1 = s2 = z
    n_old = o1 = o2 = z
    for s in range(cfg.num_stages):
        local = jax.tree_util.tree_map(lambda a: a[s], g_stage)
        for key in sorted(local):
            kn, k1, k2 = sample_moments(
                local[key], gds,
                lead_mask=part.stage_flags(key, jnp.int32(s)))
            n, s1, s2 = n + kn, s1 + k1, s2 + k2
        kn, k1, k2 = sample_moments(local, gds)     # the old padded pooling
        n_old, o1, o2 = n_old + kn, o1 + k1, o2 + k2
    n2, c1, c2 = sample_moments(g_shared, gds)
    masked = float(entropy_from_moments(n + n2, s1 + c1, s2 + c2))
    padded = float(entropy_from_moments(n_old + n2, o1 + c1, o2 + c2))
    flat = float(grads_entropy(grads, gds))
    assert abs(masked - flat) < 1e-6, (fam, masked, flat)
    # the bias this guards against was real and material
    assert padded < flat - 1e-3, (fam, padded, flat)


# --------------------------------------------- end-to-end (single device)
def _trainer(mesh, policy="fixed", num_stages=1, steps=6, schedule="1f1b",
             num_micro=2, seed=0, stash="replay", num_layers=2):
    cfg = ModelConfig(name="pp1", family="dense", num_layers=num_layers,
                      d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                      vocab_size=512, num_stages=num_stages)
    model = build_model(cfg)
    edgc = EDGCConfig(policy=policy, fixed_rank=8, num_stages=num_stages,
                      total_iterations=steps,
                      gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=3, adjust_limit=4))
    tcfg = TrainerConfig(total_steps=steps, log_every=1, schedule=schedule,
                         num_microbatches=num_micro, stash_policy=stash,
                         adam=AdamConfig(lr=1e-3, warmup_steps=2,
                                         total_steps=steps))
    return Trainer(model, mesh, edgc, tcfg, seed=seed)


@pytest.mark.parametrize("schedule", psched.SCHEDULES)
def test_pipelined_trainer_single_device_parity(schedule):
    """pipe=1 mesh exercises the full pipelined executor (microbatching,
    ring buffer, manual VJP, per-stage sync) without fake devices; the loss
    trajectory must match the flat trainer's."""
    data = lambda: SyntheticLM(512, 32, 4, seed=3).batches()
    tp = _trainer(make_host_mesh(pipe=1, data=1, model=1), schedule=schedule)
    hp = tp.run(data())
    tf_ = _trainer(make_host_mesh(data=1, model=1))
    hf = tf_.run(data())
    lp, lf = [h["loss"] for h in hp], [h["loss"] for h in hf]
    assert max(abs(a - b) for a, b in zip(lp, lf)) < 5e-3, (lp, lf)
    assert tp.bytes_synced == tf_.bytes_synced


@pytest.mark.parametrize("schedule", psched.SCHEDULES)
@pytest.mark.parametrize("stash", ["full", "every_k"])
def test_pipelined_trainer_stash_policies_parity(schedule, stash):
    """Acceptance: the stashed executors (segmented forward + stash ring +
    per-segment backward VJPs) hold the same loss parity replay does, for
    both schedules. 4 layers -> 4 units at pipe=1: full stashes 3 carries,
    every_k=2 one — both exercise a real second ring."""
    data = lambda: SyntheticLM(512, 32, 4, seed=3).batches()
    tp = _trainer(make_host_mesh(pipe=1, data=1, model=1), schedule=schedule,
                  stash=stash, num_layers=4)
    hp = tp.run(data())
    tf_ = _trainer(make_host_mesh(data=1, model=1), num_layers=4)
    hf = tf_.run(data())
    lp, lf = [h["loss"] for h in hp], [h["loss"] for h in hf]
    assert max(abs(a - b) for a, b in zip(lp, lf)) < 5e-3, \
        (schedule, stash, lp, lf)
    assert tp.bytes_synced == tf_.bytes_synced


def test_entropy_off_variant_lowers_no_moment_collectives():
    """Satellite: the GDS ISR (alpha) gate is real — the entropy-off step
    variant traces EXACTLY the three Lemma-2 moment psums fewer (n, s1,
    s2 over the pipe axis) and nothing else; dispatching on
    wants_entropy means off-gate iterations run the cheaper program.
    (Counted in the jaxpr: on a pipe=1 mesh the partitioned HLO elides
    size-1 collectives entirely.)"""
    from repro.train.step import TrainStepConfig, make_train_step
    data = lambda: SyntheticLM(512, 32, 4, seed=3).batches()
    tp = _trainer(make_host_mesh(pipe=1, data=1, model=1), num_layers=4)
    batch = {k: jnp.asarray(v) for k, v in next(data()).items()}
    state = jax.device_get(tp.state)
    traced = {}
    for measure in (True, False):
        scfg = TrainStepConfig(
            mode="dp_tp", policy_plan=tp.controller.plan,
            gds=tp.edgc_cfg.gds, measure_entropy=measure,
            num_stages=1, schedule="1f1b", num_microbatches=2,
            adam=tp.tcfg.adam)
        raw = make_train_step(tp.model, tp.mesh, scfg)
        traced[measure] = jax.make_jaxpr(raw)(state, batch)
    counts = {m: analysis.count_collectives(t, "psum")
              for m, t in traced.items()}
    assert counts[False] < counts[True], counts
    assert analysis.check_entropy_gate(traced[True], traced[False]) == []


def test_trainer_rejects_edgc_without_entropy():
    """Satellite: policy='edgc' with measure_entropy=False used to fill
    the DAC window with the step's 0.0 placeholder entropies — now an
    up-front error."""
    cfg = ModelConfig(name="pp1", family="dense", num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
                      num_stages=1)
    model = build_model(cfg)
    edgc = EDGCConfig(policy="edgc", num_stages=1, total_iterations=8,
                      gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=3))
    tcfg = TrainerConfig(total_steps=8, measure_entropy=False)
    with pytest.raises(ValueError, match="measure_entropy"):
        Trainer(model, make_host_mesh(), edgc, tcfg, seed=0)


def test_alpha_gate_skips_and_holds_history():
    """Satellite: off-gate iterations dispatch the entropy-off variant
    (no on_entropy recording) and history zero-order-holds the last
    measured reading instead of logging a 0.0 placeholder."""
    data = lambda: SyntheticLM(512, 32, 4, seed=3).batches()
    tr = _trainer(make_host_mesh(data=1, model=1), steps=6)
    hist = tr.run(data())
    gds = tr.edgc_cfg.gds
    measured = {s for s in range(6)
                if gds.should_measure(s % tr.edgc_cfg.dac.window)}
    assert {s for s, _ in tr.controller.entropy_history} == measured
    by_step = {h["step"]: h["entropy"] for h in hist}
    ent = dict(tr.controller.entropy_history)
    last = 0.0
    for s in range(6):
        if s in ent:
            last = ent[s]
        assert by_step[s] == pytest.approx(last)
    assert any(v != 0.0 for v in by_step.values())


def test_pipelined_trainer_checkpoint_resume(tmp_path):
    """Satellite: the control plane survives save/restore — a resumed EDGC
    run must not restart warm-up and must keep the DAC plan."""
    steps = 24
    mesh = make_host_mesh()
    cfg = ModelConfig(name="ckpt", family="dense", num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
                      num_stages=2)

    def mk():
        model = build_model(cfg)
        edgc = EDGCConfig(policy="edgc", fixed_rank=16, num_stages=2,
                          total_iterations=steps,
                          gds=GDSConfig(alpha=0.5, beta=0.25),
                          dac=DACConfig(window=4, adjust_limit=4))
        tcfg = TrainerConfig(total_steps=steps, log_every=4,
                             adam=AdamConfig(lr=1e-3, warmup_steps=2,
                                             total_steps=steps))
        return Trainer(model, mesh, edgc, tcfg, seed=0)

    data = SyntheticLM(512, 32, 4, seed=3)
    t1 = mk()
    t1.run(data.batches(), num_steps=16)
    assert not t1.controller.in_warmup
    path = str(tmp_path / "state")
    t1.save_checkpoint(path)

    t2 = mk()
    assert t2.controller.in_warmup
    assert t2.restore_checkpoint(path) == 16
    assert not t2.controller.in_warmup, "resume restarted warm-up"
    assert t2.controller.plan == t1.controller.plan
    assert t2.controller.rank_history == t1.controller.rank_history
    for k in t1.state["comp"]:
        np.testing.assert_array_equal(
            np.asarray(t1.state["comp"][k].q), np.asarray(t2.state["comp"][k].q))
    h = t2.run(data.batches())
    assert h[-1]["step"] == steps - 1


def test_make_plan_rejects_short_stage_ranks():
    _, _, leaves, _ = _setup()
    with pytest.raises(ValueError, match="one rank per pipeline stage"):
        make_plan("edgc", leaves, stage_ranks=[4], num_stages=2)
    with pytest.raises(ValueError, match="one rank per pipeline stage"):
        make_plan("edgc", leaves, stage_ranks=[4, 8, 16], num_stages=2)
    with pytest.raises(ValueError):
        make_plan("edgc", leaves, stage_ranks=None, num_stages=2)
