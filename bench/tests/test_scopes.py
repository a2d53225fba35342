"""The harness's own reading of scopes, kernels, host spans and program
counters: on a compiled step it equals ``scopes.py``'s, a new scope gets a
key of its own, Pallas kernels are named from the trace's HLO, the events
carry the program's counters, and the flash kernels' roofline counts."""
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

import flash_count
import flops
import harness
import scopes
import xplane

CELL = "gpt2-345m.edgc-r342"
SCOPE_METRICS = ("forward_ms", "backward_ms", "compress_ms", "entropy_ms",
                 "optimizer_ms", "step_unscoped_ms")
OP_NAME = re.compile(r'op_name="([^"]*)"')
OP_NS = 1000


def with_device_ops(trace, instrs, lo, hi):
    """``trace`` with one device running each instruction of each traced
    program that has an ``op_name`` path, ``OP_NS`` each, in module
    executions laid end to end inside ``[lo, hi)``; and the paths of those
    ops."""
    ops, modules, paths, t = [], [], [], lo + 10
    for pid, table in instrs.items():
        named = [(k, path) for k, (path, _) in table.items() if path]
        if not named:
            continue
        start = t
        for k, path in named:
            ops.append((k, t, t + OP_NS))
            paths.append(path)
            t += OP_NS
        modules.append((f"jit_step({pid})", start, t))
    assert t < hi, "the window is too short for the fake ops"
    plane = "/device:TPU:0"
    return xplane.Trace(ops={plane: ops}, modules={plane: modules},
                        host=trace.host), paths


@pytest.fixture(scope="module")
def traced_window(tmp_path_factory):
    """A tiny cell's trainer run for two steps under the window's span and
    the profiler, its trace loaded, and its programs' instructions."""
    import conftest
    wl, _ = harness.load_cell(CELL)
    wl = dict(wl, batch=4, seq_len=32, ring=4, rank=8)
    model = dict(conftest.TINY_MODEL, dtype="float32")
    ring = harness.make_ring(model, wl, 11)
    tr = harness.build_trainer(model, wl, 11, jax.devices())
    data = harness.feed(ring)
    tr.run(data, 2)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
        tr.run(data, 2)
        jax.block_until_ready(tr.state)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(log_dir)
    return xplane.load(path), xplane.hlo_instructions(path)


def test_harness_readings_equal_the_scopes_summary(traced_window):
    trace, instrs = traced_window
    _, lo, hi = xplane.span(trace, harness.WINDOW_SPAN)
    faked, paths = with_device_ops(trace, instrs, lo, hi)
    reduced = harness.reduce_loaded(faked, instrs)
    steps = 2
    ctx = type("Ctx", (), {"reduced": reduced, "steps": steps})()
    summary = scopes.summary(reduced, steps)
    want: dict = {}
    for path in paths:
        layer = scopes.layer_of(path)
        name = "step_unscoped_ms" if layer == "unscoped" else f"{layer}_ms"
        want[name] = want.get(name, 0) + OP_NS / steps / 1e6
    assert {"forward_ms", "backward_ms", "compress_ms", "entropy_ms",
            "optimizer_ms"} <= set(want)
    for name in SCOPE_METRICS + ("host_step_ms",):
        got = harness.read_metric(name, ctx)
        assert got == summary[name], name
        if name != "host_step_ms":
            assert got == pytest.approx(want.get(name)), name
    assert summary["host_step_ms"] > 0
    assert summary["host_spans"]["edgc.step"] == 2
    total = sum(harness.read_metric(n, ctx) or 0 for n in SCOPE_METRICS)
    assert total == pytest.approx(len(paths) * OP_NS / steps / 1e6)
    assert sum(v["ns"] for v in reduced["ops"].values()) == pytest.approx(
        len(paths) * OP_NS)


def test_a_nested_new_scope_gets_its_own_key():
    def loss(w, x):
        with jax.named_scope("edgc.forward"):
            h = jnp.tanh(x @ w)
            with jax.named_scope("edgc.toy"):
                h = jnp.sin(h @ w)
            return h.sum()

    step = jax.jit(lambda w, x: w - jax.grad(loss)(w, x))
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    paths = OP_NAME.findall(step.lower(w, x).compile().as_text())
    keys = {xplane.scope_of(p) for p in paths}
    assert {"forward", "backward", "toy", "toy.backward"} <= keys
    assert {scopes.layer_of(p) for p in paths
            if "edgc.toy" in p} <= {"forward", "backward"}


@pytest.mark.parametrize("path,key", [
    ("jit(step)/jvp(edgc.forward)/edgc.moe/dot", "moe"),
    ("jit(step)/transpose(jvp(edgc.forward))/edgc.moe/dot", "moe.backward"),
    ("jit(step)/edgc.backward/jvp(edgc.forward)/dot", "backward"),
    ("jit(step)/edgc.backward/edgc.moe/dot", "moe.backward"),
    ("jit(step)/edgc.forwarding/add", "forwarding"),
    ("jit(step)/edgc.compress/qr", "compress"),
    ("jvp_edgc.forward_.1", "unscoped"),
])
def test_scope_of(path, key):
    assert xplane.scope_of(path) == key


def test_events_carry_the_attention_route(tiny, monkeypatch):
    wl, model = tiny(CELL, dtype="float32")
    monkeypatch.setattr(flops, "peaks",
                        lambda kind: {"bf16_flops_per_s": 197e12})
    seen = []
    harness.run_cell(CELL, wl, {"model": model}, 2**31 + 23, 0.1, False,
                     jax.devices(), time.perf_counter(), observe=seen.append)
    events = seen[0].events
    routes = events["attention_route"]
    assert sorted(r["data"]["measure_entropy"] for r in routes) == [False,
                                                                   True]
    assert all(r["data"]["flash"] == 0 for r in routes)   # CPU: blockwise
    assert events["run_meta"][0]["data"]["model"] == "tiny"
    assert len(events["loss"]) >= harness.CHECKED_STEPS


def test_pallas_kernels_are_named_from_the_hlo():
    """The flash kernels compiled for a described v5e carry their names in
    the Mosaic module of their backend config."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels.flash_attention_bwd import flash_attention_train
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        s = jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.bfloat16,
                                 sharding=SingleDeviceSharding(
                                     topo.devices[0]))
        loss = lambda q, k, v: flash_attention_train(
            q, k, v, True, 256, 256, False).astype(jnp.float32).sum()
        exe = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            s, s, s).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    proto = memoryview(exe.runtime_executable().hlo_modules()[0]
                       .as_serialized_hlo_module_proto())
    names = []
    for comp in xplane._repeated(proto, 3):
        for instr in xplane._repeated(comp, 2):
            ins = dict(xplane._fields(instr))
            if bytes(ins.get(28, b"")) == xplane.MOSAIC_TARGET:
                names.append(xplane.mosaic_kernel(bytes(ins[43])))
    assert sorted(names) == ["_dkv_kernel", "_dq_kernel", "_fwd_kernel"]
    assert xplane.mosaic_kernel(b"{}") is None


def test_flash_count_at_the_cell():
    """GPT-2 345M's attention, 16 x 1024 tokens, 16 heads of 64, one 1024²
    tile: no pair skipped; 22 T² Dh per head and layer, forward twice."""
    c = flash_count.flash_attention_cost(16, 16, 16, 1024, 64, 1024, True, 2)
    per_head = {k: v["flops"] / 256 for k, v in c.items()}
    assert per_head == {"_fwd_kernel": 4 * 1024**2 * 64,
                        "_dq_kernel": 6 * 1024**2 * 64,
                        "_dkv_kernel": 8 * 1024**2 * 64}
    step = 24 * (2 * c["_fwd_kernel"]["flops"] + c["_dq_kernel"]["flops"]
                 + c["_dkv_kernel"]["flops"])
    assert step == 22 * 1024**2 * 64 * 256 * 24        # about 9.07 TFLOP
    # q, k, v, o in bf16 and the f32 LSE, each read or written once
    assert c["_fwd_kernel"]["bytes"] == 256 * (4 * 1024 * 64 * 2 + 1024 * 4)


def test_flash_count_skips_the_tiles_above_the_diagonal():
    full = flash_count.flash_attention_cost(1, 2, 1, 1024, 64, 256, False, 2)
    causal = flash_count.flash_attention_cost(1, 2, 1, 1024, 64, 256, True, 2)
    for k in flash_count.MATMULS:
        assert causal[k]["flops"] * 16 == full[k]["flops"] * 10   # 10 of 16
        assert causal[k]["bytes"] < full[k]["bytes"]


def reduced_with(ops):
    return {"ops": ops, "modules": {"jit_step": [500_000_000] * 4}}


def test_the_roofline_reader():
    import conftest
    wl, _ = harness.load_cell(CELL)
    model = dict(conftest.TINY_MODEL, dtype="bfloat16")
    c = flash_count.flash_attention_cost(wl["batch"], 4, 4, wl["seq_len"],
                                         16, 1024, True, 2)
    peak = flops.peaks("TPU v5 lite")
    bound = {k: max(v["flops"] / peak["bf16_flops_per_s"],
                    v["bytes"] / peak["hbm_bytes_per_s"])
             for k, v in c.items()}
    calls = {"_fwd_kernel": 4, "_dq_kernel": 2, "_dkv_kernel": 2}
    ops = {k: {"ns": 4e9 * calls[k] * bound[k], "calls": calls[k]}
           for k in calls}
    ctx = type("Ctx", (), dict(
        reduced=reduced_with(dict(ops, fusion={"ns": 1e9, "calls": 9})),
        chips=1, model=model, workload=wl, device_kind="TPU v5 lite"))()
    assert harness.read_metric("flash_attention_roofline",
                               ctx) == pytest.approx(25.0)
    ctx.reduced = reduced_with({"fusion": {"ns": 1e9, "calls": 9}})
    assert harness.read_metric("flash_attention_roofline", ctx) is None
    ctx.reduced = None
    assert harness.read_metric("flash_attention_roofline", ctx) is None


def test_the_step_mfu_reader():
    ctx = type("Ctx", (), dict(
        reduced=reduced_with({}), chips=1, steps=4, tokens=4 * 16384,
        flops_per_token=2_422_708_224, device_kind="TPU v5 lite"))()
    want = 100 * 2_422_708_224 * 16384 / (0.5 * 197e12)
    assert harness.read_metric("step_mfu", ctx) == pytest.approx(want)
    ctx.reduced = None
    assert harness.read_metric("step_mfu", ctx) is None
