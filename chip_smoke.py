#!/usr/bin/env python3
"""Smoke run of the EDGC trainer on a TPU, at GPT-2 345M's full width.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: pipe=2 x data=2 only

One process drives the chip(s) through the normal entry points: a
``repro.train.trainer.Trainer`` built as ``repro.launch.train`` builds it,
on GPT-2 345M (24 layers, d_model 1024, 16 heads, d_ff 4096, vocab 50257,
bf16, remat) with random weights from a fixed seed and ``SyntheticLM``
data, sequence 1024, global batch 8.

One chip, on a 1x1 host mesh:
  (a) policy=fixed, rank 32: PowerSGD-compressed sync from the first step.
      Every loss finite; mean of the last 5 below the mean of the first 5.
  (b) (a) with the Pallas kernels (``SyncConfig(use_kernels=True)``): the
      compiled step holds ``tpu_custom_call``, its first-step loss matches
      (a)'s within LOSS_RTOL, its losses pass (a)'s checks, and the mean
      of its last 5 is within KERNEL_DRIFT of (a)'s.
  (c) policy=edgc, DAC window 4: reports whether warm-up ended, the applied
      stage ranks and the number of compiled step variants.

Four chips (``--chips 4``): GPT-2 345M in two 1F1B pipeline stages on a
pipe=2 x data=2 mesh, so the per-stage compressed psums and the stage
boundary ``ppermute`` cross chips. With policy=none its first-step loss
must match the flat trainer's on one chip at the same batch and seed;
with policy=fixed, rank 32, every loss is finite and the loss falls; and
every chip must hold at least a quarter of chip 0's bytes.

Times printed here are smoke readings (host clock, one run, the host
blocks on every step), not a benchmark. The script exits non-zero, without
its last ``{"ok": true, ...}`` line, when JAX finds no TPU or any check
fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEQ = 1024
BATCH = 8
RANK = 32
SEED = 0
# Two bf16 ulps, relative: two programs that differ only after the forward
# pass (sync path, microbatch split) must agree on the first loss this far.
LOSS_RTOL = 2.0 ** -7
# The kernel path orthonormalizes by Gram-Schmidt, the XLA path by QR; the
# means of their last 5 of 20 losses must stay this close (nats).
KERNEL_DRIFT = 0.1
KERNEL_MARKER = "tpu_custom_call"


class PhaseFailed(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


class CompileClock:
    """Sums XLA backend compile seconds, as JAX reports them, per phase."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.count = 0

        def listen(event: str, duration: float, **_) -> None:
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.count

    def since(self, mark: tuple[float, int]) -> tuple[float, int]:
        return self.seconds - mark[0], self.count - mark[1]


def make_trainer(cfg, mesh, *, policy: str, steps: int, rank: int = RANK,
                 window: int = 50, use_kernels: bool = False,
                 pipe: int = 0, seed: int = SEED):
    """A Trainer configured the way ``repro.launch.train`` configures one,
    except that entropy is measured on every step (GDS alpha 1, not 0.5):
    each rank plan then compiles one step variant instead of two, which
    keeps the run's compiles inside its time limit."""
    from repro.core import EDGCConfig, GDSConfig, SyncConfig
    from repro.core.dac import DACConfig
    from repro.models.model import build_model
    from repro.optim.adam import AdamConfig
    from repro.pipeline import PipelineConfig
    from repro.train.trainer import Trainer, TrainerConfig

    num_stages = pipe or cfg.num_stages
    pipe_cfg = PipelineConfig(num_stages=num_stages, schedule="1f1b")
    sync_cfg = SyncConfig(use_kernels=use_kernels)
    edgc = EDGCConfig(
        policy=policy, fixed_rank=rank, total_iterations=steps,
        gds=GDSConfig(alpha=1.0, beta=0.25),
        dac=DACConfig(window=window, adjust_limit=4),
        pipeline=pipe_cfg, sync=sync_cfg)
    tcfg = TrainerConfig(
        total_steps=steps, log_every=1, pipeline=pipe_cfg, sync=sync_cfg,
        adam=AdamConfig(lr=1e-3, warmup_steps=max(10, steps // 10),
                        total_steps=steps))
    return Trainer(build_model(cfg), mesh, edgc, tcfg, seed=seed)


def batches(cfg, seq: int, batch: int, seed: int = SEED):
    from repro.data.pipeline import SyntheticLM
    return SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       batch_size=batch, seed=seed).batches()


def run_phase(name: str, trainer, data, steps: int, clock: CompileClock,
              mark: tuple[float, int] | None = None) -> list[float]:
    """Run ``steps`` steps; print compile and per-step seconds and device
    memory; return the per-step losses. Compiles count from ``mark``."""
    mark = mark or clock.mark()
    t0 = time.perf_counter()
    trainer.run(data, 1)                   # compiles the first variant
    t1 = time.perf_counter()
    trainer.run(data, steps - 1)
    t2 = time.perf_counter()
    compile_s, n_compiles = clock.since(mark)
    losses = [h["loss"] for h in trainer.history]
    check(len(losses) == steps, f"{name}: {len(losses)} losses for "
                                f"{steps} steps")
    rest = max(1, steps - 1)
    print(f"[{name}] compile_s={compile_s:.3f} ({n_compiles} XLA compiles) "
          f"first_step_s={t1 - t0:.3f}")
    print(f"[{name}] smoke reading, not a benchmark: wall_s_per_step="
          f"{(t2 - t1) / rest:.4f} over steps 1..{steps - 1} (host blocks "
          f"on every step; re-plan compiles included)")
    print(f"[{name}] losses " + " ".join(f"{x:.4f}" for x in losses))
    print_memory(name, trainer.mesh.devices.flat)
    return losses


def check_learning(name: str, losses: list[float], k: int) -> None:
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")
    first, last = sum(losses[:k]) / k, sum(losses[-k:]) / k
    check(last < first, f"{name}: mean of the last {k} losses {last:.4f} is "
                        f"not below the mean of the first {k} {first:.4f}")
    print(f"[{name}] loss check ok: mean first {k} {first:.4f} -> "
          f"mean last {k} {last:.4f}")


def check_same_loss(name: str, got: float, want: float) -> None:
    check(abs(got - want) <= LOSS_RTOL * abs(want),
          f"{name}: first-step loss {got:.6f} vs {want:.6f} exceeds "
          f"rtol {LOSS_RTOL}")
    print(f"[{name}] first-step loss {got:.6f} vs {want:.6f} "
          f"(|diff| {abs(got - want):.3e}, rtol {LOSS_RTOL:.3e}) ok")


def print_memory(name: str, devices) -> list[int]:
    """Print and return each device's ``bytes_in_use``; the peak printed
    is the process's so far."""
    in_use = []
    for d in devices:
        stats = d.memory_stats() or {}
        in_use.append(int(stats.get("bytes_in_use", 0)))
        print(f"[{name}] device {d.id}: bytes_in_use={in_use[-1]} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 0)}")
    return in_use


def one_chip(cfg, devices, clock: CompileClock, *, seq: int = SEQ,
             batch: int = BATCH, steps: int = 20, edgc_steps: int = 48
             ) -> None:
    """Phases (a)-(c) on a 1x1 host mesh over ``devices[:1]``."""
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(devices=devices[:1])

    tr = make_trainer(cfg, mesh, policy="fixed", steps=steps)
    print(f"[model] {cfg.name}: n_params={tr.n_params} "
          f"compressed_leaves={len(tr.controller.plan.ranks)} rank={RANK}")
    fixed = run_phase("a fixed", tr, batches(cfg, seq, batch), steps, clock)
    check_learning("a fixed", fixed, 5)
    del tr
    gc.collect()

    tr = make_trainer(cfg, mesh, policy="fixed", steps=steps,
                      use_kernels=True)
    mark = clock.mark()
    probe = next(batches(cfg, seq, batch))
    # The step the first run() call dispatches, compiled ahead of it; the
    # run then finds it in the persistent compilation cache.
    text = tr._get_step(True).lower(tr.state, probe).compile().as_text()
    check(KERNEL_MARKER in text,
          f"b kernels: the compiled step holds no {KERNEL_MARKER}")
    print(f"[b kernels] compiled step holds {text.count(KERNEL_MARKER)} "
          f"{KERNEL_MARKER} ops")
    kern = run_phase("b kernels", tr, batches(cfg, seq, batch), steps, clock,
                     mark)
    check_same_loss("b kernels", kern[0], fixed[0])
    check_learning("b kernels", kern, 5)
    drift = abs(sum(kern[-5:]) - sum(fixed[-5:])) / 5
    check(drift <= KERNEL_DRIFT,
          f"b kernels: mean of the last 5 losses is {drift:.4f} from (a)'s, "
          f"over {KERNEL_DRIFT}")
    print(f"[b kernels] last-5 mean {drift:.4f} from (a)'s "
          f"(limit {KERNEL_DRIFT}) ok")
    del tr
    gc.collect()

    tr = make_trainer(cfg, mesh, policy="edgc", steps=edgc_steps, window=4)
    run_phase("c edgc", tr, batches(cfg, seq, batch), edgc_steps, clock)
    ctrl = tr.controller
    check(all(math.isfinite(h["loss"]) for h in tr.history),
          "c edgc: non-finite loss")
    ranks = ctrl.dac.current_ranks() if not ctrl.in_warmup else []
    print(f"[c edgc] warmup_ended={not ctrl.in_warmup} "
          f"applied_stage_ranks={ranks} "
          f"rank_history={ctrl.rank_history} "
          f"step_variants={len(tr.step_cache_keys())}")


def four_chips(cfg, devices, clock: CompileClock, *, seq: int = SEQ,
               batch: int = BATCH, steps: int = 16) -> None:
    """GPT-2 in 2 pipeline stages on pipe=2 x data=2, against the flat
    trainer on one chip."""
    from repro.launch.mesh import make_host_mesh
    from repro.pipeline.partition import pipeline_supported

    cfg = dataclasses.replace(cfg, num_stages=2)
    reason = pipeline_supported(cfg, 2)
    check(reason is None, f"pipeline unsupported: {reason}")

    flat = make_trainer(cfg, make_host_mesh(devices=devices[:1]),
                        policy="none", steps=1)
    ref = run_phase("4chip flat-1chip none", flat, batches(cfg, seq, batch),
                    1, clock)
    del flat
    gc.collect()

    mesh = make_host_mesh(pipe=2, data=2, devices=devices[:4])
    tr = make_trainer(cfg, mesh, policy="none", steps=1, pipe=2)
    got = run_phase("4chip pipe2xdata2 none", tr, batches(cfg, seq, batch),
                    1, clock)
    check_same_loss("4chip pipe2xdata2 none", got[0], ref[0])
    del tr
    gc.collect()

    tr = make_trainer(cfg, mesh, policy="fixed", steps=steps, pipe=2)
    fixed = run_phase("4chip pipe2xdata2 fixed", tr,
                      batches(cfg, seq, batch), steps, clock)
    check_learning("4chip pipe2xdata2 fixed", fixed, 4)
    in_use = print_memory("4chip spread", devices[:4])
    check(in_use[0] > 0, "4chip: device 0 reports no bytes_in_use")
    check(min(in_use) >= in_use[0] / 4,
          f"4chip: state is not spread over the chips: {in_use}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the pipe=2 x data=2 phase and nothing else")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    from repro.configs.gpt2 import GPT2_345M
    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform})",
              file=sys.stderr)
        return 1
    print(f"[device] platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    print(f"[cache] compilation cache dir: {cache_dir}")

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(GPT2_345M, devices, clock)
    else:
        one_chip(GPT2_345M, devices, clock)
    print(f"[total] wall_s={time.perf_counter() - t0:.1f} "
          f"compile_s={clock.seconds:.1f} ({clock.count} XLA compiles)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
