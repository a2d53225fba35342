"""The look behind a number the check cannot explain, at a tiny size: a
float32 program meets the reference leaf by leaf."""
import jax

import diagnose


def test_a_float32_program_meets_the_reference_leaf_by_leaf(tiny):
    wl, model = tiny("gpt2-345m.edgc-r342", dtype="float32")
    res = diagnose.compare(model, wl, 2**31 + 21, jax.devices())
    assert len(res["program"]) == 3 == len(res["reference_loss"])
    for row in res["leaves"]:
        assert row["init_differ"] == 0, row
        assert row["change3_gap"] < 1e-2, row
    losses = res["f32_loss"]
    assert abs(losses["program_after_2"] - losses["reference_after_2"]) < 1e-3
