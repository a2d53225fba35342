"""Training driver.

CPU-runnable end to end with reduced configs; the same flags drive the
production mesh on real hardware.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --variant reduced \
      --policy edgc --steps 300 --window 50
  PYTHONPATH=src python -m repro.launch.train --arch gpt2 --variant reduced \
      --policy fixed --rank 32 --steps 200

Pipeline parallelism: ``--pipe S`` adds a ``pipe`` axis of size S to the
mesh (total devices = pipe * data * model), rebuilds the model config with
``num_stages=S``, and routes the Trainer through the pipelined executor
(family permitting — the stage adapter's reason is surfaced otherwise).
``--pipe 1`` exercises the full pipelined path on a single device:

  PYTHONPATH=src python -m repro.launch.train --arch gpt2 --pipe 1 \
      --micro 2 --policy edgc --steps 100

Elastic outer loop: ``--outer-k K`` routes through the DiLoCo-style
ElasticTrainer — ``--pods`` pod-local inner Trainers (one device each; set
XLA_FLAGS=--xla_force_host_platform_device_count=N to simulate pods), K
inner steps per outer round, EDGC-compressed outer-delta all-reduce.
``--inject`` schedules faults; ``--recover`` arms the recovery policies:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.train --arch gpt2 --outer-k 20 \
      --pods 2 --rounds 8 --recover \
      --inject 'nan_grad@30,pod_drop:1@r3,pod_join@r5'
"""
from __future__ import annotations

import argparse
import dataclasses
import json


from repro.configs import ARCHS, get_config
from repro.core import EDGCConfig, GDSConfig
from repro.core.dac import DACConfig
from repro.data.pipeline import SyntheticLM, add_modality_stubs
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.optim.adam import AdamConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2", choices=sorted(ARCHS))
    ap.add_argument("--variant", default="reduced", choices=["full", "reduced"])
    ap.add_argument("--policy", default="edgc",
                    choices=["none", "fixed", "optimus", "edgc"])
    ap.add_argument("--rank", type=int, default=32)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--window", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--stages", type=int, default=0, help="0 = config default")
    ap.add_argument("--pipe", type=int, default=0,
                    help="pipeline stages: adds a 'pipe' mesh axis and runs "
                         "the pipelined (GPipe/1F1B) executor")
    ap.add_argument("--schedule", default="1f1b", choices=["gpipe", "1f1b"])
    ap.add_argument("--micro", type=int, default=0,
                    help="microbatches per step (0 -> num_stages)")
    ap.add_argument("--stash", default="replay",
                    choices=["replay", "full", "every_k"],
                    help="pipeline activation stashing: replay re-derives "
                         "each stage's forward in its backward (memory "
                         "floor); full/every_k stash inter-unit carries "
                         "into a second ring and replay only the un-stashed "
                         "segments")
    ap.add_argument("--stash-every", type=int, default=2,
                    help="k for --stash every_k")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap each stage's DP sync with the pipeline "
                         "drain: sync chunks launch inside the schedule's "
                         "free back-of-drain ticks instead of after the "
                         "loop (pipelined executor only)")
    ap.add_argument("--chunk-bytes", type=int, default=0,
                    help="split flat sync buckets into transfer chunks of "
                         "at most this many bytes for overlap scheduling "
                         "(0 = one chunk per bucket)")
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--wire", default="raw",
                    choices=["raw", "quant8", "quant4", "entropy"],
                    help="lossless-training wire coding of the DP sync "
                         "payloads: scaled int8/int4 quantization + bit "
                         "packing with error feedback; 'entropy' picks the "
                         "bit width per window from the controller's "
                         "entropy reading (quant8 until the first one)")
    # ---- fault injection + recovery -------------------------------------
    ap.add_argument("--inject", default=None,
                    help="comma-separated fault specs kind[:arg]@N (step) "
                         "or kind[:arg]@rN (outer round); kinds: nan_grad, "
                         "corrupt_payload, torn_ckpt, pod_drop, pod_join. "
                         "e.g. 'nan_grad@40,pod_drop:1@r3'")
    ap.add_argument("--recover", action="store_true",
                    help="arm the recovery policies: non-finite step guard "
                         "+ error-feedback reset, loss-spike rollback to "
                         "the checkpoint ring, uncompressed-sync fallback "
                         "after repeated anomalies")
    ap.add_argument("--spike-factor", type=float, default=4.0,
                    help="loss > factor * EMA counts as an anomaly")
    ap.add_argument("--max-rollbacks", type=int, default=3)
    ap.add_argument("--fallback-after", type=int, default=4,
                    help="anomalies before pinning uncompressed sync")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint cadence in steps (rollback needs > 0)")
    ap.add_argument("--ckpt-path", default="ckpt/state")
    # ---- elastic DiLoCo outer loop --------------------------------------
    ap.add_argument("--outer-k", type=int, default=0,
                    help="> 0 routes through the elastic outer loop: K "
                         "inner steps per pod per outer round")
    ap.add_argument("--pods", type=int, default=2,
                    help="initial pod count (needs that many devices)")
    ap.add_argument("--rounds", type=int, default=10,
                    help="outer rounds to run")
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--outer-momentum", type=float, default=0.9)
    ap.add_argument("--outer-policy", default="edgc",
                    choices=["none", "fixed", "edgc"],
                    help="outer-delta compression policy")
    ap.add_argument("--outer-rank", type=int, default=32)
    ap.add_argument("--outer-window", type=int, default=2,
                    help="outer DAC window, counted in ROUNDS")
    # ---- observability (repro.obs) --------------------------------------
    ap.add_argument("--metrics-dir", default=None,
                    help="write structured telemetry (scalars/series/events) "
                         "as JSONL to <dir>/metrics.jsonl; read it back with "
                         "python -m repro.launch.report <dir>")
    ap.add_argument("--trace", default=None,
                    help="emit a Chrome trace-event JSON of the pipeline "
                         "schedule (Perfetto-loadable) to this path, with "
                         "tick durations scaled to the measured mean step "
                         "time (pipelined runs only)")
    ap.add_argument("--profile", default=None, metavar="LOGDIR",
                    help="wrap the run in a jax.profiler trace written to "
                         "LOGDIR (view with TensorBoard/Perfetto)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    from repro.train.faults import RecoveryConfig, parse_inject
    faults = parse_inject(args.inject) if args.inject else None
    recovery = RecoveryConfig(
        spike_factor=args.spike_factor, max_rollbacks=args.max_rollbacks,
        fallback_after=args.fallback_after) if args.recover else None
    if args.outer_k and args.pipe:
        raise SystemExit("--outer-k does not compose with --pipe: the outer "
                         "loop wraps flat pod-local trainers")
    if args.outer_k:
        total_steps = args.outer_k * args.rounds
    else:
        total_steps = args.steps

    cfg = get_config(args.arch, args.variant)
    if args.pipe:
        from repro.pipeline.partition import pipeline_supported
        if args.stages and args.stages != args.pipe:
            raise SystemExit(f"--pipe {args.pipe} conflicts with --stages "
                             f"{args.stages}: the pipe axis size IS the "
                             "stage count")
        num_stages = args.pipe
        cfg = dataclasses.replace(cfg, num_stages=num_stages)
        reason = pipeline_supported(cfg, num_stages)
        if reason is not None:
            raise SystemExit(f"--pipe {args.pipe} unsupported for "
                             f"{cfg.name}: {reason}")
        mesh = make_host_mesh(pipe=args.pipe, data=args.data_mesh,
                              model=args.model_mesh)
    else:
        num_stages = args.stages or cfg.num_stages
        mesh = make_host_mesh(data=args.data_mesh, model=args.model_mesh)
    model = build_model(cfg)

    # The unified config surface: one PipelineConfig + one SyncConfig,
    # shared by the EDGC controller, the Trainer, and (by identity) every
    # step build.
    from repro.core import SyncConfig
    from repro.pipeline import PipelineConfig
    pipe_cfg = PipelineConfig(
        num_stages=num_stages, schedule=args.schedule,
        num_microbatches=args.micro, stash_policy=args.stash,
        stash_every=args.stash_every, overlap_sync=args.overlap,
        chunk_bytes=args.chunk_bytes,
    )
    sync_cfg = SyncConfig(use_kernels=args.use_kernels, wire=args.wire)

    edgc = EDGCConfig(
        policy=args.policy, fixed_rank=args.rank,
        total_iterations=total_steps,
        gds=GDSConfig(alpha=0.5, beta=0.25),
        dac=DACConfig(window=args.window, adjust_limit=4),
        pipeline=pipe_cfg, sync=sync_cfg,
    )
    tcfg = TrainerConfig(
        total_steps=total_steps, log_every=max(1, total_steps // 20),
        ckpt_every=args.ckpt_every, ckpt_path=args.ckpt_path,
        recovery=recovery, faults=faults,
        pipeline=pipe_cfg, sync=sync_cfg,
        metrics_dir=args.metrics_dir,
        adam=AdamConfig(lr=args.lr, warmup_steps=max(10, total_steps // 10),
                        total_steps=total_steps),
    )
    from repro.obs import profiler_session

    def pod_batches(pod: int):
        data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           batch_size=args.batch, seed=args.seed + 1000 * pod)
        for b in data.batches():
            yield add_modality_stubs(b, cfg.family,
                                     audio_frames=cfg.audio_frames,
                                     num_patches=cfg.num_patches,
                                     d_model=cfg.d_model, seed=args.seed)

    if args.outer_k:
        from repro.optim.outer import OuterConfig
        from repro.train.elastic import ElasticTrainer
        ocfg = OuterConfig(outer_k=args.outer_k, lr=args.outer_lr,
                           momentum=args.outer_momentum,
                           policy=args.outer_policy,
                           fixed_rank=args.outer_rank,
                           window=args.outer_window,
                           total_rounds=args.rounds)
        et = ElasticTrainer(model, edgc, tcfg, ocfg, args.pods,
                            pod_batches, seed=args.seed)
        print(f"{cfg.name}: elastic outer loop, {args.pods} pods x "
              f"K={args.outer_k} inner steps, outer policy="
              f"{args.outer_policy}, {args.rounds} rounds"
              + (f", inject={args.inject}" if args.inject else ""))
        with profiler_session(bool(args.profile), args.profile or "profile"):
            hist = et.run_rounds(args.rounds)
        et.metrics.close()
        for h in hist:
            ev = f" {h['membership_events']}" if h["membership_events"] else ""
            losses = "/".join(f"{x:.3f}" for x in h["pod_losses"])
            print(f"round {h['round']:4d} pods {h['n_pods']} "
                  f"loss {losses} H {h['entropy']:+.3f} "
                  f"outer-bytes {h['bytes_synced']}/{h['bytes_full']}{ev}")
        print(f"outer comm savings vs raw fp32: {et.outer.comm_savings():.2%}")
        if et.pods[0].recovery is not None:
            print(f"recovery: {et.pods[0].recovery.as_dict()}")
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"history": hist, "arch": cfg.name,
                           "outer": dataclasses.asdict(ocfg),
                           "comm_savings": et.outer.comm_savings()},
                          f, indent=1)
        return

    trainer = Trainer(model, mesh, edgc, tcfg, seed=args.seed)
    pipe_tag = (f", pipe={args.pipe} ({args.schedule}, stash={args.stash}"
                f"{', overlapped sync' if args.overlap else ''})"
                if args.pipe else "")
    print(f"{cfg.name}: {trainer.n_params/1e6:.1f}M params, "
          f"policy={args.policy}{pipe_tag}, {trainer.controller.describe()}")

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       batch_size=args.batch, seed=args.seed)

    def batches():
        for b in data.batches():
            yield add_modality_stubs(b, cfg.family,
                                     audio_frames=cfg.audio_frames,
                                     num_patches=cfg.num_patches,
                                     d_model=cfg.d_model, seed=args.seed)

    with profiler_session(bool(args.profile), args.profile or "profile"):
        hist = trainer.run(batches())
    for h in hist:
        print(f"step {h['step']:5d} loss {h['loss']:.4f} H {h['entropy']:+.3f} "
              f"ranks {h['ranks']} comm-saved "
              f"{1 - h['bytes_synced']/max(1, h['bytes_full']):.1%}")
    print(f"final comm savings vs no-compression: {trainer.comm_savings():.2%}")
    if args.wire != "raw" and trainer.bytes_wire_raw:
        print(f"wire coding ({args.wire}): {trainer.bytes_synced}/"
              f"{trainer.bytes_wire_raw} coded/raw payload bytes "
              f"({trainer.bytes_synced / trainer.bytes_wire_raw:.2%})")

    if args.trace:
        if not args.pipe:
            raise SystemExit("--trace requires --pipe: the tick tracer "
                             "renders the pipeline schedule")
        from repro.obs import (load_trace, tick_trace_events, validate_trace,
                               write_chrome_trace)
        from repro.pipeline.schedule import simulate_schedule
        S, M = args.pipe, (args.micro or args.pipe)
        sim = simulate_schedule(args.schedule, S, M)
        # Scale the unit-tick spans so the trace's makespan matches the
        # measured mean step wall time (first->last history record).
        if len(hist) >= 2 and hist[-1]["step"] > hist[0]["step"]:
            mean_step_s = ((hist[-1]["wall_s"] - hist[0]["wall_s"])
                           / (hist[-1]["step"] - hist[0]["step"]))
        else:
            mean_step_s = float(sim["makespan"])
        scale = mean_step_s / float(sim["makespan"])
        events = tick_trace_events(
            args.schedule, S, M, t_f=scale, t_b=scale,
            sync_plan=trainer.overlap_plan, stash_policy=args.stash,
            n_units=trainer._part.num_units(), stash_every=args.stash_every,
            time_unit_us=1e6)
        write_chrome_trace(args.trace, events, metadata={
            "arch": cfg.name, "schedule": args.schedule, "S": S, "M": M,
            "mean_step_s": mean_step_s})
        summary = validate_trace(load_trace(args.trace))
        print(f"trace: {args.trace} — {summary['spans']} spans on "
              f"{summary['tracks']} stage tracks, "
              f"{summary['end_us']/1e6:.3f}s span horizon")
    trainer.metrics.close()
    if trainer.recovery is not None:
        print(f"recovery: {trainer.recovery.as_dict()}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": hist, "arch": cfg.name,
                       "policy": args.policy,
                       "comm_savings": trainer.comm_savings()}, f, indent=1)


if __name__ == "__main__":
    main()
