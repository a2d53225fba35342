"""Pipelined trainer on a real multi-device ``pipe`` axis (fake host devices,
one subprocess each): 1F1B/GPipe loss parity with the single-stage trainer
under all four policies with DAC Algorithm-2 ranks applied per stage, and
the non-dense families on two devices.

Kept apart from test_pipeline.py so that, with tests spread over workers
file by file, these two long subprocesses run beside it, not after it.
"""
import os
import subprocess
import sys
import textwrap

import pytest


# ------------------------------------------- 4-device mesh (fake devices)
_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np

    from repro.core import EDGCConfig, GDSConfig
    from repro.core.dac import DACConfig
    from repro.core.powersgd import compressed_bytes
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import ModelConfig, build_model
    from repro.optim.adam import AdamConfig
    from repro.train.trainer import Trainer, TrainerConfig

    S = 4
    CFG = ModelConfig(name="pp4", family="dense", num_layers=4, d_model=128,
                      num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
                      num_stages=S)

    def trainer(policy, mesh, steps, sched="1f1b"):
        model = build_model(CFG)
        # alpha=1 keeps the ISR gate always-on: one compiled step
        # variant per (policy, plan) instead of two, which keeps this
        # 10-trainer subprocess inside its timeout
        edgc = EDGCConfig(policy=policy, fixed_rank=16, num_stages=S,
                          total_iterations=steps,
                          gds=GDSConfig(alpha=1.0, beta=0.25),
                          dac=DACConfig(window=5, adjust_limit=4))
        tcfg = TrainerConfig(total_steps=steps, log_every=1, schedule=sched,
                             adam=AdamConfig(lr=1e-3, warmup_steps=2,
                                             total_steps=steps))
        return Trainer(model, mesh, edgc, tcfg, seed=0)

    data = lambda: SyntheticLM(512, 32, 8, seed=3).batches()
    mesh_pipe = make_host_mesh(pipe=S, data=1, model=1)
    mesh_flat = make_host_mesh(data=1, model=1)

    # 1F1B loss parity with the single-stage trainer, all four policies;
    # GPipe spot-checked on the compressed baseline.
    runs = [(p, "1f1b", 30 if p == "edgc" else 8)
            for p in ("none", "fixed", "optimus", "edgc")]
    runs.append(("fixed", "gpipe", 8))
    tp_edgc = None
    for policy, sched, steps in runs:
        tp = trainer(policy, mesh_pipe, steps, sched)
        hp = tp.run(data())
        tf = trainer(policy, mesh_flat, steps)
        hf = tf.run(data())
        lp = [h["loss"] for h in hp]; lf = [h["loss"] for h in hf]
        gap = max(abs(a - b) for a, b in zip(lp, lf))
        tol = 5e-3 if policy != "edgc" else 2e-2   # edgc: resize RNG differs
        assert gap < tol, (policy, sched, gap, lp, lf)
        if policy == "edgc":
            tp_edgc = tp
        print(f"{policy}/{sched}: gap {gap:.2e} PARITY_OK")

    # Algorithm 2 applied per stage: the edgc run warmed up, emitted a
    # stage-aligned (non-decreasing) rank vector, and the per-stage wire
    # ledger reflects exactly those ranks.
    tp = tp_edgc
    assert not tp.controller.in_warmup
    ranks = tp.controller.rank_history[-1][1]   # the vector the plan used
    assert len(ranks) == S
    assert all(b >= a for a, b in zip(ranks, ranks[1:])), ranks
    per_stage = tp.stage_bytes()
    plan = tp.controller.plan.as_dict()
    for s in range(S):
        stage_leaves = [l for l in tp.leaves if l.stage == s]
        comp = sum(compressed_bytes(l.shape, plan[l.path]) if l.path in plan
                   else int(np.prod(l.shape)) * 2 for l in stage_leaves)
        assert comp == per_stage[s][0], (s, comp, per_stage)
        for l in stage_leaves:
            if l.path in plan:
                max_r = min(l.shape[-2:]) // 2
                assert plan[l.path] == max(1, min(ranks[s], max_r)), l.path
    print("stage ranks", ranks, "stage bytes", per_stage)
    print("PIPELINE_4DEV_OK")
""")


@pytest.mark.slow
def test_pipeline_4dev_parity_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PIPELINE_4DEV_OK" in proc.stdout, \
        proc.stdout[-2000:] + proc.stderr[-3000:]


# ------------------------- 2-device mesh, non-dense families (fake devices)
_SCRIPT_FAMILIES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    import numpy as np

    from repro.core import EDGCConfig, GDSConfig
    from repro.core.dac import DACConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import ModelConfig, build_model
    from repro.optim.adam import AdamConfig
    from repro.train.trainer import Trainer, TrainerConfig

    ZAMBA = ModelConfig(name="pp2-zamba", family="zamba", num_layers=3,
                        d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
                        vocab_size=512, ssm_state=16, chunk=16, attn_every=2,
                        num_stages=2)        # ragged: stage layers [2, 1]
    MOE = ModelConfig(name="pp2-moe", family="moe", num_layers=4,
                      d_model=128, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=512, num_experts=2, experts_per_token=1,
                      capacity_factor=4.0, num_stages=2)

    def trainer(cfg, mesh, steps, stash="replay"):
        model = build_model(cfg)
        edgc = EDGCConfig(policy="fixed", fixed_rank=8, num_stages=2,
                          total_iterations=steps,
                          gds=GDSConfig(alpha=1.0, beta=0.25),
                          dac=DACConfig(window=5, adjust_limit=4))
        tcfg = TrainerConfig(total_steps=steps, log_every=1, schedule="1f1b",
                             stash_policy=stash,
                             adam=AdamConfig(lr=1e-3, warmup_steps=2,
                                             total_steps=steps))
        return Trainer(model, mesh, edgc, tcfg, seed=0)

    data = lambda cfg: SyntheticLM(cfg.vocab_size, 32, 4, seed=3).batches()
    mesh_pipe = make_host_mesh(pipe=2, data=1, model=1)
    mesh_flat = make_host_mesh(data=1, model=1)

    # RAGGED hybrid: strict 1F1B parity on a real pipe axis (no discrete
    # routing in the family, so the padded executor must match the flat
    # trainer's virtual-stage run to fp tolerance).
    steps = 6
    tp = trainer(ZAMBA, mesh_pipe, steps)
    hp = tp.run(data(ZAMBA))
    tf = trainer(ZAMBA, mesh_flat, steps)
    hf = tf.run(data(ZAMBA))
    lp = [h["loss"] for h in hp]; lf = [h["loss"] for h in hf]
    gap = max(abs(a - b) for a, b in zip(lp, lf))
    assert gap < 5e-3, ("zamba", gap, lp, lf)
    assert tp.bytes_synced == tf.bytes_synced
    print(f"zamba ragged pipe=2: gap {gap:.2e} PARITY_OK")

    # MoE on a real pipe axis: microbatching flips discrete top-1 routing
    # vs the unmicrobatched flat run, so assert a loose envelope + the
    # per-stage wire ledger (which must sum to the flat plan's bytes).
    tp = trainer(MOE, mesh_pipe, steps)
    hp = tp.run(data(MOE))
    tf = trainer(MOE, mesh_flat, steps)
    hf = tf.run(data(MOE))
    lp = [h["loss"] for h in hp]; lf = [h["loss"] for h in hf]
    assert all(np.isfinite(lp)), lp
    gap = max(abs(a - b) for a, b in zip(lp, lf))
    assert gap < 0.25, ("moe", gap, lp, lf)
    per_stage = tp.stage_bytes()
    from repro.core import plan_wire_bytes
    comp, full = plan_wire_bytes(tp.leaves, tp.controller.plan)
    assert sum(c for c, _ in per_stage) == comp
    assert sum(f for _, f in per_stage) == full
    print(f"moe pipe=2: gap {gap:.2e} stage bytes {per_stage}")

    # Selective stashing on a REAL pipe axis with a RAGGED plan: 5 layers,
    # attn_every=2 -> groups [2,2,1] -> stage group slots [2, 1] (Gmax=2),
    # so stash="full" saves one inter-group carry per microbatch and the
    # backward replays single group slots instead of the whole stage.
    ZAMBA5 = ModelConfig(name="pp2-zamba5", family="zamba", num_layers=5,
                         d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
                         vocab_size=512, ssm_state=16, chunk=16,
                         attn_every=2, num_stages=2)
    steps = 4
    tp = trainer(ZAMBA5, mesh_pipe, steps, stash="full")
    hp = tp.run(data(ZAMBA5))
    tf = trainer(ZAMBA5, mesh_flat, steps)
    hf = tf.run(data(ZAMBA5))
    lp = [h["loss"] for h in hp]; lf = [h["loss"] for h in hf]
    gap = max(abs(a - b) for a, b in zip(lp, lf))
    assert gap < 5e-3, ("zamba5-stash-full", gap, lp, lf)
    print(f"zamba ragged pipe=2 stash=full: gap {gap:.2e} PARITY_OK")
    print("PIPELINE_FAMILIES_2DEV_OK")
""")


@pytest.mark.slow
def test_pipeline_families_2dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", _SCRIPT_FAMILIES], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PIPELINE_FAMILIES_2DEV_OK" in proc.stdout, \
        proc.stdout[-2000:] + proc.stderr[-3000:]
