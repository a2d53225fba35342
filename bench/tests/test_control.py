"""The control: the reference in the program's place at float8, one
precision below the configuration's bfloat16, must fail the cell's limits;
the float32 reference against itself must pass them."""
import check
import control
import harness
import reference


def test_the_control_fails_the_limits(tiny):
    wl, model = tiny("gpt2-345m.edgc-r342")
    for seed in (5, 2**31 + 9):
        ups = control.upper_readings(model, wl, seed)
        for kind in ("control", "half_batch", "unchanged"):
            ok, table = check.judge(ups[kind], wl["limits"])
            assert not ok, (kind, table)


def test_the_reference_meets_itself(tiny):
    wl, model = tiny("gpt2-345m.edgc-r342")
    batches = harness.make_ring(model, wl, 3)[:harness.CHECKED_STEPS]
    a = reference.run(model, wl, 3, batches)
    b = reference.run(model, wl, 3, batches)
    ok, table = check.judge(check.readings(a, b), wl["limits"])
    assert ok, table
    assert all(row["value"] == 0 for row in table.values())
