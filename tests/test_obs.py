"""Telemetry subsystem: registry semantics, sink round-trips, trainer
series reconciling with the wire-byte/DAC ledgers, and the fault-event
log."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EDGCConfig, GDSConfig
from repro.core.dac import DACConfig
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models.model import ModelConfig, build_model
from repro.obs import (
    JsonlSink, MemorySink, MetricsRegistry, read_jsonl, write_csv,
)
from repro.optim.adam import AdamConfig
from repro.train.faults import RecoveryConfig, parse_inject
from repro.train.trainer import Trainer, TrainerConfig

TINY = ModelConfig(name="obs", family="dense", num_layers=2, d_model=128,
                   num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
                   num_stages=2)


def _trainer(policy="edgc", steps=22, window=8, log_every=2, metrics=None,
             faults=None, recovery=None, ckpt_every=0, ckpt_path="ckpt/obs",
             seed=0):
    model = build_model(TINY)
    edgc = EDGCConfig(policy=policy, fixed_rank=16,
                      num_stages=TINY.num_stages, total_iterations=steps,
                      gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=window, adjust_limit=4))
    tcfg = TrainerConfig(total_steps=steps, log_every=log_every,
                         metrics=metrics, faults=faults, recovery=recovery,
                         ckpt_every=ckpt_every, ckpt_path=ckpt_path,
                         adam=AdamConfig(lr=1e-3, warmup_steps=10,
                                         total_steps=steps))
    return Trainer(model, make_host_mesh(), edgc, tcfg, seed=seed)


def _data(seed=0):
    return SyntheticLM(vocab_size=TINY.vocab_size, seq_len=64, batch_size=4,
                       seed=seed).batches()


# --------------------------------------------------------------- registry
def test_registry_kinds_tags_and_cursor():
    sink = MemorySink()
    reg = MetricsRegistry([sink])
    reg.scalar("loss", 1.5, step=0)
    reg.series("ranks", [8, 16], step=0)
    reg.counter("resets", step=3)
    reg.counter("resets", step=4)
    reg.event("boom", step=5, kind_detail="nan")
    reg.scalar("loss", 1.25)           # no step -> cursor (5)
    reg.flush()

    assert reg.last_step == 5 and reg.n_emitted == 6
    assert sink.scalars("loss") == [(0, 1.5), (5, 1.25)]
    assert sink.series("ranks") == [(0, [8, 16])]
    assert sink.counters("resets") == [(3, 1), (4, 2)]
    (ev,) = sink.events("boom")
    assert ev["data"]["kind_detail"] == "nan"

    view = reg.with_tags(pod=1)
    view.scalar("loss", 9.0, step=6)
    view.with_tags(shard=2).event("nested", step=6)
    reg.flush()
    tagged = [r for r in sink.records if r.get("pod") == 1]
    assert len(tagged) == 2
    assert tagged[1]["shard"] == 2 and "shard" not in tagged[0]
    assert reg.last_step == 6        # views share the base cursor


def test_flush_defers_device_fetch(monkeypatch):
    """Device values stay device values until flush; flush does exactly one
    batched block_until_ready for everything pending."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    reg = MetricsRegistry([sink := MemorySink()])
    for i in range(4):
        reg.scalar("x", jnp.float32(i) * 2, step=i)
    reg.series("v", jnp.arange(3, dtype=jnp.float32), step=4)
    assert calls == []               # nothing fetched yet
    reg.flush()
    assert len(calls) == 1           # one sync for all five records
    assert sink.scalars("x") == [(0, 0.0), (1, 2.0), (2, 4.0), (3, 6.0)]
    (sv,) = sink.series("v")
    assert sv[1] == [0.0, 1.0, 2.0]
    assert all(isinstance(v, float) for v in sv[1])


def test_jsonl_roundtrip_and_csv(tmp_path):
    path = str(tmp_path / "m" / "metrics.jsonl")
    reg = MetricsRegistry([JsonlSink(path)])
    reg.scalar("loss", 2.0, step=0)
    reg.series("ranks", [4, 8], step=1)
    reg.event("plan_change", step=1, window=1)
    reg.close()

    records = read_jsonl(path)
    assert [r["kind"] for r in records] == ["scalar", "series", "event"]
    assert json.loads(open(path).readline())["value"] == 2.0

    # append mode: a second registry continues the same file
    reg2 = MetricsRegistry([JsonlSink(path)])
    reg2.scalar("loss", 1.0, step=2)
    reg2.close()
    assert len(read_jsonl(path)) == 4

    csv_path = str(tmp_path / "out.csv")
    write_csv(records, csv_path)
    rows = open(csv_path).read().strip().splitlines()
    assert rows[0] == "step,name,kind,value"
    assert rows[1] == "0,loss,scalar,2.0"
    assert rows[2] == "1,ranks,series,4;8"
    assert len(rows) == 3            # events are not tabular -> skipped


def test_state_dict_cursor_roundtrip():
    reg = MetricsRegistry([MemorySink()])
    reg.scalar("loss", 1.0, step=7)
    reg.counter("resets")
    reg.flush()
    sd = reg.state_dict()
    assert sd["step"] == 7 and sd["emitted"] == 2

    sink2 = MemorySink()
    reg2 = MetricsRegistry([sink2])
    reg2.load_state_dict(sd)
    reg2.flush()
    assert reg2.last_step == 7 and reg2.n_emitted >= 2
    (ev,) = sink2.events("telemetry_resume")
    assert ev["step"] == 7
    assert reg2.counter("resets") == 2   # counter totals carried over


# ------------------------------------------------- trainer reconciliation
def test_trainer_series_reconcile_with_ledgers():
    """The acceptance check: JSONL-visible series must equal the trainer's
    own wire-byte ledger and the DAC's applied ranks, exactly."""
    sink = MemorySink()
    tr = _trainer("edgc", steps=22, window=8, log_every=2,
                  metrics=MetricsRegistry([sink]))
    tr.run(_data())

    ledger = tr.stage_bytes()
    step, last_swb = sink.series("stage_wire_bytes")[-1]
    assert last_swb == [int(c) for c, _ in ledger]
    _, last_full = sink.series("stage_wire_bytes_full")[-1]
    assert last_full == [int(f) for _, f in ledger]
    assert step == 21

    assert sink.scalars("bytes_synced")[-1][1] == tr.bytes_synced
    assert sink.scalars("bytes_full")[-1][1] == tr.bytes_full

    ranks = sink.series("dac_applied_ranks")
    assert ranks and ranks[-1][1] == [
        int(r) for r in tr.controller.dac.current_ranks()]

    # history and telemetry describe the same logged steps
    hist_steps = [h["step"] for h in tr.history]
    assert [s for s, _ in sink.scalars("loss")] == hist_steps
    for h, (s, v) in zip(tr.history, sink.scalars("loss")):
        assert h["loss"] == pytest.approx(v)

    names = {e["name"] for e in sink.events()}
    assert {"run_meta", "plan_change"} <= names


# ------------------------------------------------------------- fault log
def test_fault_run_event_log_sequence():
    """nan_grad -> guard skip + EF reset -> recovered, in order, in the
    structured event log."""
    sink = MemorySink()
    tr = _trainer("fixed", steps=24, window=8, log_every=24,
                  metrics=MetricsRegistry([sink]),
                  faults=parse_inject("nan_grad@12"),
                  recovery=RecoveryConfig(rollback=False))
    tr.run(_data())
    assert tr.recovery.skipped_steps == 1 and tr.recovery.ef_resets == 1

    seq = [(e["name"], e["step"]) for e in sink.events()
           if e["name"] in ("fault_injected", "guard_skip", "ef_reset",
                            "recovered")]
    assert [n for n, _ in seq] == ["fault_injected", "guard_skip",
                                   "ef_reset", "recovered"]
    assert seq[0][1] == 12 and seq[1][1] == 12 and seq[2][1] == 12
    assert seq[3][1] == 13
    (fault,) = sink.events("fault_injected")
    assert fault["data"]["kind"] == "nan_grad"
    assert sink.counters("ef_resets")[-1][1] == 1


def test_checkpoint_carries_metrics_cursor(tmp_path):
    sink = MemorySink()
    tr = _trainer("fixed", steps=12, window=6, log_every=4,
                  metrics=MetricsRegistry([sink]), ckpt_every=6,
                  ckpt_path=str(tmp_path / "st"))
    tr.run(_data())
    saved_cursor = tr.metrics.last_step

    sink2 = MemorySink()
    tr2 = _trainer("fixed", steps=12, window=6, log_every=4,
                   metrics=MetricsRegistry([sink2]), ckpt_every=6,
                   ckpt_path=str(tmp_path / "st"))
    step = tr2.restore_checkpoint(str(tmp_path / "st_12"))
    assert step == 12
    tr2.metrics.flush()
    assert tr2.metrics.last_step >= step - 1
    assert tr2.metrics.last_step <= saved_cursor
    (ev,) = sink2.events("telemetry_resume")
    assert ev["data"]["emitted"] > 0   # resumed run appends, not restarts


# ----------------------------------------------------------------- dryrun
def test_dryrun_record_summary():
    saved = os.environ.get("XLA_FLAGS")
    try:
        from repro.launch.dryrun import record_summary
    finally:                    # dryrun import mutates XLA_FLAGS
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved

    ok = record_summary({
        "arch": "a", "shape": "s", "flops_per_chip": 1.0,
        "bytes_per_chip": 2.0, "collective_total": 3, "compile_s": 4.5,
        "policy": "edgc", "compressed_leaves": 7, "guarded": True,
        "memory": {"argument_bytes": 10, "temp_bytes": 5},
        "pipeline": {"num_stages": 2, "schedule": "1f1b",
                     "stash_policy": "replay", "stage_bytes": [[1, 2]],
                     "peak_activation_bytes": 99, "family": "dense",
                     "overlap": {"in_loop_chunks": 3, "residual_chunks": 1}},
        "outer_sync": {"wire_bytes_compressed": 6, "wire_bytes_full": 8,
                       "outer_k": 20, "outer_rank": 32},
    })
    assert ok["status"] == "ok" and ok["per_chip_bytes"] == 15
    assert ok["pipeline"]["overlap"]["in_loop_chunks"] == 3
    assert ok["outer_sync"]["outer_k"] == 20
    assert "traceback" not in json.dumps(ok)

    skip = record_summary({"arch": "a", "shape": "s", "skipped": True,
                           "reason": "too big"})
    assert skip == {"arch": "a", "shape": "s", "status": "skipped",
                    "reason": "too big"}
    fail = record_summary({"arch": "a", "shape": "s", "error": "boom",
                           "traceback": "..."})
    assert fail["status"] == "failed" and fail["error"] == "boom"
    assert "traceback" not in fail


def test_registry_series_handles_numpy_and_scalars():
    sink = MemorySink()
    reg = MetricsRegistry([sink])
    reg.series("v", np.array([1, 2], dtype=np.int64), step=0)
    reg.scalar("s", np.float32(0.5), step=0)
    reg.flush()
    assert sink.series("v") == [(0, [1, 2])]
    assert sink.scalars("s") == [(0, 0.5)]
    assert isinstance(sink.scalars("s")[0][1], float)
