#!/usr/bin/env python3
"""Compile the pipelined GPT-2 2.5B step (26 layers, pipe=2 x data=2, 1F1B,
8 microbatches of 1 x 1024 per replica, PowerSGD rank 338) for a described
TPU v5e 2x2, without a chip, and print each device's bytes.

    JAX_PLATFORMS=cpu python bench/rehearse_pipe.py

The state is built from shapes alone (``jax.eval_shape``) by the steps the
pipelined ``Trainer`` takes, so nothing of full size is allocated here. A
rehearsal for a four-chip cell; it gives memory and code, never a time.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

LAYERS, STAGES, DATA, MICRO, RANK, BATCH, SEQ = 26, 2, 2, 8, 338, 16, 1024


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.configs.gpt2 import GPT2_2_5B
    from repro.core import EDGCConfig, EDGCController, GDSConfig, SyncConfig
    from repro.core import classify_leaves
    from repro.core.dac import DACConfig
    from repro.models.model import build_model
    from repro.optim import adam
    from repro.pipeline import PipelineConfig
    from repro.pipeline import partition as ppart
    from repro.pipeline import sync as psync
    from repro.pipeline.schedule import pipeline_state_shardings
    from repro.train.step import TrainStepConfig, make_train_step

    cfg = dataclasses.replace(GPT2_2_5B, num_layers=LAYERS, num_stages=STAGES)
    model = build_model(cfg)
    pcfg = PipelineConfig(num_stages=STAGES, schedule="1f1b",
                          num_microbatches=MICRO)
    sync = SyncConfig()
    acfg = adam.AdamConfig(lr=3e-4, warmup_steps=0, total_steps=10**6)
    edgc = EDGCConfig(policy="fixed", fixed_rank=RANK, total_iterations=10**6,
                      gds=GDSConfig(alpha=0.1, beta=0.25),
                      dac=DACConfig(window=1000), pipeline=pcfg, sync=sync)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    leaves = classify_leaves(params, cfg.num_layers, STAGES, min_dim=64)
    ctrl = EDGCController(edgc, leaves, world=DATA)
    part = ppart.make_partition(model, STAGES, remat=False)
    stage_p, shared_p = jax.eval_shape(part.partition_params, params)
    ost = jax.eval_shape(lambda t: adam.init(t, acfg),
                         {"stage": stage_p, "shared": shared_p})
    splans = psync.make_stage_plans(
        ctrl.plan, STAGES, psync.stage_local_leaves(stage_p),
        bucket_bytes=sync.bucket_bytes, chunk_bytes=pcfg.chunk_bytes,
        local_path=part.local_leaf_path)
    comp = jax.eval_shape(lambda k: psync.replicate_pipeline_comp_state(
        psync.init_pipeline_comp_state(None, ctrl.plan, k, splans), DATA),
        key)
    state = {"stage_params": stage_p, "shared_params": shared_p,
             "opt_m": ost.m, "opt_v": ost.v, "opt_step": ost.step,
             "comp": comp}

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = np.array(topo.devices).reshape(STAGES, DATA, 1)
    mesh = Mesh(devs, ("pipe", "data", "model"),
                axis_types=(AxisType.Auto,) * 3)
    shard = pipeline_state_shardings(state, model, mesh)
    sds = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shard)
    rep = NamedSharding(mesh, P())
    batch = {k: jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32, sharding=rep)
             for k in ("tokens", "labels")}
    print(f"plan: {len(ctrl.plan.ranks)} compressed leaves at rank {RANK}; "
          f"r_min, r_max at world {DATA}: {ctrl.r_min}, {ctrl.r_max}")
    for measure in (True, False):
        scfg = TrainStepConfig(mode="dp_tp", policy_plan=ctrl.plan,
                               gds=edgc.gds, measure_entropy=measure,
                               remat=False, pipeline=pcfg, sync=sync,
                               adam=acfg)
        step = jax.jit(make_train_step(model, mesh, scfg),
                       in_shardings=(shard, None), out_shardings=(shard, rep),
                       donate_argnums=0)
        t = time.perf_counter()
        compiled = step.lower(sds, batch).compile()
        print(f"entropy={measure}: compiled in {time.perf_counter() - t:.1f}"
              f" s on this host's CPU; per device {compiled.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
