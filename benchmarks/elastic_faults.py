"""Elastic outer-loop fault suite: end-to-end recovery + wire-byte ledger.

Runs a clean elastic baseline (2 pods, DiLoCo outer loop, EDGC-compressed
outer sync) and then one run per fault class — ``nan_grad``,
``corrupt_payload``, ``pod_drop``, ``pod_join`` injected into the same
schedule, plus a flat-trainer ``torn_ckpt`` + rollback class where the
newest ring checkpoint is torn and restore must fall through to an older
intact one. Every faulted run must complete with a final loss within
tolerance of the fault-free run; the JSON records the outer-sync
compressed-vs-raw bytes and the recovery overhead (wall clock + counters)
per fault class.

  PYTHONPATH=src python benchmarks/elastic_faults.py            # full + JSON
  PYTHONPATH=src python benchmarks/elastic_faults.py --smoke    # CI gate

``--smoke`` shrinks the schedule and exits nonzero if any run diverges,
any recovery policy fails to engage, or the outer sync saved no bytes.
Standalone only (not part of benchmarks.run): it must force the fake
device count before jax initializes.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"   # fake host devices, never the chip

import argparse
import json
import tempfile
import time

import numpy as np

from repro.core import EDGCConfig, GDSConfig
from repro.core.dac import DACConfig
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models.model import ModelConfig, build_model
from repro.optim.adam import AdamConfig
from repro.optim.outer import OuterConfig
from repro.train.elastic import ElasticTrainer
from repro.train.faults import RecoveryConfig, parse_inject
from repro.train.trainer import Trainer, TrainerConfig

CFG = ModelConfig(name="bench-el", family="dense", num_layers=2, d_model=128,
                  num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512)

# one injection schedule per elastic fault class (steps hit pod 0's inner
# trainer; @rN events are outer-round membership changes)
ELASTIC_FAULTS = {
    "nan_grad": "nan_grad@7",
    "corrupt_payload": "corrupt_payload@9",
    "pod_drop": "pod_drop:1@r2",
    "pod_join": "pod_drop:1@r1,pod_join@r3",
}


def _elastic_run(rounds, k, inject, workdir, seed=0):
    model = build_model(CFG)
    steps = rounds * k
    edgc = EDGCConfig(policy="fixed", fixed_rank=8, total_iterations=steps,
                      gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=10, adjust_limit=4))
    tcfg = TrainerConfig(total_steps=steps, log_every=steps,
                         ckpt_path=os.path.join(workdir, "st"),
                         faults=parse_inject(inject) if inject else None,
                         recovery=RecoveryConfig(rollback=False),
                         adam=AdamConfig(lr=1e-3, warmup_steps=5,
                                         total_steps=steps))
    ocfg = OuterConfig(outer_k=k, policy="fixed", fixed_rank=8,
                       window=2, total_rounds=rounds)

    # fresh stream per invocation: a fleet rebuild (drop/join) must hand the
    # new pods NEW data, not a replay of batches the model already fit
    calls = [0]

    def batch_fn(pod):
        calls[0] += 1
        return SyntheticLM(CFG.vocab_size, 64, 4,
                           seed=seed + 1000 * calls[0] + pod).batches()

    et = ElasticTrainer(model, edgc, tcfg, ocfg, 2, batch_fn, seed=seed)
    t0 = time.time()
    hist = et.run_rounds(rounds)
    wall = time.time() - t0
    events = [e for h in hist for e in h["membership_events"]]
    return {
        "final_loss": float(min(hist[-1]["pod_losses"])),
        "wall_s": round(wall, 2),
        "pod_counts": [h["n_pods"] for h in hist],
        "membership_events": events,
        "recovery": hist[-1]["recovery"],
        "outer_bytes_synced": int(et.outer.bytes_synced),
        "outer_bytes_full": int(et.outer.bytes_full),
        "outer_comm_savings": round(et.outer.comm_savings(), 4),
    }


def _rollback_run(steps, inject, workdir, seed=0):
    """Flat trainer, guard OFF: NaN lands, rollback walks the ckpt ring
    (whose newest entry the torn_ckpt fault has corrupted)."""
    model = build_model(CFG)
    edgc = EDGCConfig(policy="fixed", fixed_rank=8, total_iterations=steps,
                      gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=10, adjust_limit=4))
    tcfg = TrainerConfig(total_steps=steps, log_every=steps,
                         ckpt_every=10, ckpt_path=os.path.join(workdir, "rb"),
                         faults=parse_inject(inject) if inject else None,
                         recovery=(RecoveryConfig(guard_nonfinite=False)
                                   if inject else None),
                         adam=AdamConfig(lr=1e-3, warmup_steps=10,
                                         total_steps=steps))
    tr = Trainer(model, make_host_mesh(), edgc, tcfg, seed=seed)
    data = SyntheticLM(CFG.vocab_size, 64, 4, seed=seed).batches()
    t0 = time.time()
    hist = tr.run(data)
    wall = time.time() - t0
    return {
        "final_loss": float(hist[-1]["loss"]),
        "wall_s": round(wall, 2),
        "recovery": tr.recovery.as_dict() if tr.recovery else None,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short schedule + hard assertions (CI gate)")
    ap.add_argument("--rounds", type=int, default=0,
                    help="outer rounds (0 = 4 smoke / 10 full)")
    ap.add_argument("--out", default="BENCH_elastic.json")
    ap.add_argument("--tol", type=float, default=0.75,
                    help="max final-loss excess over the fault-free run")
    args = ap.parse_args()

    rounds = args.rounds or (4 if args.smoke else 10)
    k = 5
    flat_steps = rounds * k * 2
    results = {"config": {"rounds": rounds, "outer_k": k, "pods": 2,
                          "arch": CFG.name}, "elastic": {}, "rollback": {}}

    with tempfile.TemporaryDirectory() as workdir:
        print(f"clean elastic baseline: {rounds} rounds x K={k}, 2 pods")
        clean = _elastic_run(rounds, k, None, workdir)
        results["elastic"]["clean"] = clean
        print(f"  loss {clean['final_loss']:.3f}, {clean['wall_s']}s, "
              f"outer bytes {clean['outer_bytes_synced']}/"
              f"{clean['outer_bytes_full']} "
              f"({clean['outer_comm_savings']:.1%} saved)")

        failures = []
        for name, inject in ELASTIC_FAULTS.items():
            r = _elastic_run(rounds, k, inject, workdir)
            r["inject"] = inject
            r["loss_delta_vs_clean"] = round(
                r["final_loss"] - clean["final_loss"], 4)
            r["overhead_s"] = round(r["wall_s"] - clean["wall_s"], 2)
            results["elastic"][name] = r
            print(f"  {name:16s} loss {r['final_loss']:.3f} "
                  f"(d{r['loss_delta_vs_clean']:+.3f}), "
                  f"overhead {r['overhead_s']:+.1f}s, pods {r['pod_counts']}, "
                  f"recovery {r['recovery']}")
            # one-sided: a faulted run must not end WORSE than clean + tol
            # (ending lower is fine — e.g. pod_drop halves the data stream)
            if not np.isfinite(r["final_loss"]) or \
                    r["loss_delta_vs_clean"] > args.tol:
                failures.append(f"{name}: final loss {r['final_loss']} "
                                f"diverged beyond {args.tol} of clean "
                                f"{clean['final_loss']}")

        print(f"flat rollback class: {flat_steps} steps, torn ring entry")
        rb_clean = _rollback_run(flat_steps, None, workdir)
        rb = _rollback_run(flat_steps, "torn_ckpt@11,nan_grad@25", workdir)
        rb["inject"] = "torn_ckpt@11,nan_grad@25"
        rb["loss_delta_vs_clean"] = round(
            rb["final_loss"] - rb_clean["final_loss"], 4)
        rb["overhead_s"] = round(rb["wall_s"] - rb_clean["wall_s"], 2)
        results["rollback"] = {"clean": rb_clean, "torn_ckpt": rb}
        print(f"  torn_ckpt        loss {rb['final_loss']:.3f} "
              f"(d{rb['loss_delta_vs_clean']:+.3f}), "
              f"overhead {rb['overhead_s']:+.1f}s, "
              f"recovery {rb['recovery']}")

        # recovery must have ENGAGED, not merely not-crashed
        el = results["elastic"]
        if el["nan_grad"]["recovery"]["skipped_steps"] < 1:
            failures.append("nan_grad: guard never skipped a step")
        if el["corrupt_payload"]["recovery"]["ef_resets"] < 1:
            failures.append("corrupt_payload: EF was never reset")
        if el["pod_drop"]["pod_counts"][-1] != 1:
            failures.append("pod_drop: pod never dropped")
        if el["pod_join"]["pod_counts"][-1] != 2:
            failures.append("pod_join: pod never joined")
        if rb["recovery"]["rollbacks"] < 1:
            failures.append("torn_ckpt: rollback never engaged")
        if not np.isfinite(rb["final_loss"]) or \
                rb["loss_delta_vs_clean"] > args.tol:
            failures.append(f"torn_ckpt: final loss {rb['final_loss']} "
                            "diverged beyond tolerance")
        if clean["outer_comm_savings"] <= 0:
            failures.append("outer sync saved no bytes")

    results["failures"] = failures
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {args.out}")
    if failures:
        for msg in failures:
            print(f"FAIL {msg}")
        raise SystemExit(1)
    print("elastic fault suite OK: all classes recovered within "
          f"{args.tol} of the fault-free loss")


if __name__ == "__main__":
    main()
