"""The trace reduction: interval arithmetic on hand-built intervals, and the
loader on a small trace recorded on the CPU."""
import xplane


def test_merge_total_clip():
    merged = xplane.merge([(5, 8), (0, 2), (1, 3), (8, 9), (20, 20)])
    assert merged == [(0, 3), (5, 9)]
    assert xplane.total(merged) == 7
    assert xplane.clip(merged, 2, 6) == [(2, 3), (5, 6)]


def test_subtract_and_gaps():
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert xplane.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert xplane.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_exposed_collective_time():
    ops = [("fusion.1", 0, 10), ("all-reduce.2", 8, 14),
           ("all-reduce.3", 20, 25), ("fusion.4", 22, 23),
           ("collective-permute.5", 30, 31)]
    # 10..14 (4) and 20..22, 23..25 (4) are not hidden by compute
    assert xplane.exposed(ops, r"^all-reduce") == 8
    assert xplane.exposed(ops, r"^collective-permute") == 1


def test_self_times_of_nested_ops():
    ev = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 40, 50),
          ("copy.4", 120, 125)]
    assert dict(xplane.self_times(ev)) == {"while.1": 70, "fusion.2": 20,
                                           "fusion.3": 10, "copy.4": 5}


def test_reduce_window_idle_share_and_breakdown():
    tr = xplane.Trace(
        ops={"/device:TPU:0": [("while.1", 0, 40), ("fusion.7", 5, 15),
                               ("fusion.8", 50, 90)],
             "/device:TPU:1": [("fusion.9", 0, 100)]},
        modules={"/device:TPU:0": [("jit_step(11)", 0, 40),
                                   ("jit_step(12)", 50, 90)]},
        host={"python": [("bench.window", 0, 100), ("flush", 38, 52)]})
    r = xplane.reduce_window(tr, 0, 100, "python")
    assert r["busy_ns"] == (80 + 100) / 2          # mean over the devices
    assert r["window_ns"] == 100
    assert r["modules"] == {"jit_step": [40, 40]}
    assert dict(r["device_ops"]) == {"fusion": (10 + 40 + 100) / 2,
                                     "while": 30 / 2}
    assert r["idle_gaps"][0] == ("bench.window", 10)   # 90..100
    assert ("flush", 10) in r["idle_gaps"]             # 40..50


def test_load_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xplane.load(xplane.find_xplane(str(tmp_path)))
    line, lo, hi = xplane.span(tr, "bench.window")
    assert hi > lo and line.startswith("/host:")
    r = xplane.reduce_window(tr, lo, hi, line)
    assert r["busy_ns"] is None          # the CPU has no device plane
    assert r["window_ns"] == hi - lo
