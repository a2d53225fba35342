"""A whole run at a tiny size on the CPU, past the harness's look for a chip,
with the timed path broken underneath: ``correct`` must come out false for
each fault a one-chip training cell can have, and true without one.

The model runs in float32 here, where a sound program meets the reference
to rounding, so the cell's own limits apply unchanged; the learning rate
is raised so that three steps move the tiny model as far as they move the
full one.
"""
import time

import jax
import jax.numpy as jnp
import pytest

import flops
import harness
import repro.train.trainer as trainer_mod

CELL = "gpt2-345m.edgc-r342"


def unchanged(raw):
    """The step returns the state it was given."""
    def step(state, batch):
        _, mets = raw(state, batch)
        return state, mets
    return step


def half_batch(raw):
    """Half of the batch left out; the mean is taken over the rest."""
    def step(state, batch):
        n = batch["tokens"].shape[0] // 2
        return raw(state, {k: v[:n] for k, v in batch.items()})
    return step


def loss_altered(raw):
    """The loss the step reports is altered where it is produced."""
    def step(state, batch):
        new, mets = raw(state, batch)
        return new, dict(mets, loss=mets["loss"] * 1.05)
    return step


def loss_not_finite(raw):
    """Every step reports a non-finite loss."""
    def step(state, batch):
        new, mets = raw(state, batch)
        return new, dict(mets, loss=mets["loss"] * jnp.nan)
    return step


def run(tiny, monkeypatch, fault=None):
    wl, model = tiny(CELL, dtype="float32")
    wl["adam"] = dict(wl["adam"], lr=1e-2)
    if fault is not None:
        real = trainer_mod.make_train_step
        monkeypatch.setattr(trainer_mod, "make_train_step",
                            lambda *a, **k: fault(real(*a, **k)))
    monkeypatch.setattr(flops, "peaks",
                        lambda kind: {"bf16_flops_per_s": 197e12})
    return harness.run_cell(CELL, wl, {"model": model}, 2**31 + 17, 0.1,
                            False, jax.devices(), time.perf_counter())


def test_a_sound_run_is_correct(tiny, monkeypatch):
    res = run(tiny, monkeypatch)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] % harness.PERIOD == 0
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s", "mfu"}


@pytest.mark.parametrize("fault", [unchanged, half_batch, loss_altered])
def test_a_broken_step_is_not_correct(tiny, monkeypatch, fault):
    res = run(tiny, monkeypatch, fault)
    assert not res["correct"], res["check"]


def test_every_step_of_the_window_counts_toward_failed(tiny, monkeypatch):
    res = run(tiny, monkeypatch, loss_not_finite)
    assert not res["correct"]
    assert res["failed"] == res["attempted"] > 1
