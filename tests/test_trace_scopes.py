"""The train step's layer scopes and the host loop's spans, as a profiler
trace carries them, and the benchmark's reduction of them
(``bench/scopes.py``): the compiled flat and pipelined steps name their
layers, the classifier maps op paths to layers, the per-layer self times
add up, and a traced ``Trainer.run`` holds its ``edgc.*`` spans."""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import scopes as bench_scopes  # noqa: E402
import xplane  # noqa: E402

from repro.core import EDGCConfig, GDSConfig  # noqa: E402
from repro.core.dac import DACConfig  # noqa: E402
from repro.data.pipeline import SyntheticLM  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models.model import ModelConfig, build_model  # noqa: E402
from repro.obs import scopes  # noqa: E402
from repro.optim.adam import AdamConfig  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

OP_NAME = re.compile(r'op_name="([^"]*)"')


def _trainer(mesh, steps=3, log_every=2):
    cfg = ModelConfig(name="scopes", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
                      vocab_size=256, act="gelu_plain", pos="learned",
                      max_position=32, num_stages=1, dtype="float32")
    edgc = EDGCConfig(policy="fixed", fixed_rank=4, num_stages=1,
                      total_iterations=steps,
                      gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=100))
    tcfg = TrainerConfig(total_steps=steps, log_every=log_every,
                         num_microbatches=2,
                         adam=AdamConfig(lr=1e-3, warmup_steps=0,
                                         total_steps=steps))
    return Trainer(build_model(cfg), mesh, edgc, tcfg, seed=0)


def _data():
    return SyntheticLM(256, 32, 4, seed=1).batches()


def _op_paths(tr, measure: bool) -> list[str]:
    """The ``op_name`` paths of the compiled step variant."""
    batch = {k: jnp.asarray(v) for k, v in next(_data()).items()}
    text = tr._get_step(measure).lower(tr.state, batch).compile().as_text()
    return OP_NAME.findall(text)


@pytest.fixture(scope="module")
def flat_paths():
    tr = _trainer(make_host_mesh())
    return {m: _op_paths(tr, m) for m in (True, False)}


@pytest.fixture(scope="module")
def pipe_paths():
    return _op_paths(_trainer(make_host_mesh(pipe=1)), True)


def _layers(paths) -> set[str]:
    return {bench_scopes.layer_of(p) for p in paths}


@pytest.mark.parametrize("measure", [True, False],
                         ids=["entropy-on", "entropy-off"])
def test_flat_step_names_its_layers(flat_paths, measure):
    paths = flat_paths[measure]
    assert {"forward", "backward", "compress", "optimizer"} <= _layers(paths)
    assert any(f"jvp({scopes.FORWARD})" in p for p in paths)
    assert any(f"transpose(jvp({scopes.FORWARD}))" in p for p in paths)
    assert (scopes.ENTROPY in " ".join(paths)) == measure
    assert ("entropy" in _layers(paths)) == measure


def test_pipelined_step_names_forward_and_backward(pipe_paths):
    layers = _layers(pipe_paths)
    assert {"forward", "backward", "compress", "entropy",
            "optimizer"} <= layers
    assert any(scopes.BACKWARD in p for p in pipe_paths)


def test_every_classified_scope_is_in_the_program(flat_paths, pipe_paths):
    """Drift guard: the benchmark writes the scope names out; each one it
    classifies is a scope the compiled programs carry, and the host span
    names agree with the program's."""
    text = " ".join(flat_paths[True] + pipe_paths)
    names = {f"edgc.{layer}" for layer in bench_scopes.LAYERS}
    assert names == set(scopes.DEVICE_SCOPES)
    for name in names:
        assert re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])", text), name
    assert set(bench_scopes.HOST_SPANS) == set(scopes.HOST_SPANS)


@pytest.mark.parametrize("path,layer", [
    ("jit(step)/jvp(edgc.forward)/dot_general", "forward"),
    ("jit(step)/transpose(jvp(edgc.forward))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "backward"),
    ("jit(step)/edgc.optimizer/mul;jit(step)/jvp(edgc.forward)/add",
     "optimizer"),
    ("jit(step)/shard_map/edgc.backward/jvp(seg)/dot_general", "backward"),
    ("jit(step)/edgc.compress/while/body/qr", "compress"),
    ("jit(step)/edgc.entropy/reduce_sum", "entropy"),
    ("jit(step)/edgc.forwarding/add", "unscoped"),
    ("jit(step)/pmean/copy", "unscoped"),
])
def test_layer_of(path, layer):
    assert bench_scopes.layer_of(path) == layer


def test_layer_self_times_add_up_to_the_op_self_time():
    ops = {
        "/device:TPU:0": [
            ("jit(step)/transpose(jvp(edgc.forward))/while", 0, 60),
            ("jit(step)/transpose(jvp(edgc.forward))/dot", 10, 40),
            ("jit(step)/jvp(edgc.forward)/dot", 60, 70),
            ("jit(step)/edgc.compress/qr", 70, 85),
            ("jit(step)/edgc.optimizer/sub", 85, 95),
            ("copy.3", 95, 100)],
        "/device:TPU:1": [("jit(step)/edgc.entropy/reduce", 0, 100)],
    }
    got = bench_scopes.device_layers(ops, 0, 100)
    assert got == {"backward": 30.0, "forward": 5.0, "compress": 7.5,
                   "optimizer": 5.0, "unscoped": 2.5, "entropy": 50.0}
    tr = xplane.Trace(ops=ops, modules={}, host={"python": []})
    whole = sum(t for _, t in xplane.reduce_window(tr, 0, 100, "python")[
        "device_ops"])
    assert sum(got.values()) == whole == 100.0
    assert bench_scopes.unscoped_top(ops, 0, 100) == [("copy", 2.5)]


def test_op_paths_come_from_the_traced_programs_hlo(tmp_path):
    def loss(w, x):
        with jax.named_scope(scopes.FORWARD):
            return jnp.tanh(x @ w).sum()

    step = jax.jit(lambda w, x: w - jax.grad(loss)(w, x))
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    step(w, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(w, x).block_until_ready()
    jax.profiler.stop_trace()
    names = bench_scopes.hlo_op_names(xplane.find_xplane(str(tmp_path)))
    paths = [p for prog in names.values() for p in prog.values()]
    assert {"forward", "backward"} <= _layers(paths)
    # a device's ops take the paths of the program whose execution holds
    # them; the id is the one XLA appends to the module's name
    pid, prog = next((k, v) for k, v in names.items()
                     if any(scopes.FORWARD in p for p in v.values()))
    instr = next(k for k, v in prog.items() if scopes.FORWARD in v)
    ops = [(f"%{instr} = f32[4,16] fusion(...)", 10, 20), ("copy.1", 30, 31),
           (f"%{instr} = f32[4,16] fusion(...)", 50, 60)]
    big = {2**64 - 7: prog}            # a program id of 2**63 or more
    got = bench_scopes.op_paths(ops, [(f"jit_step({pid})", 5, 25),
                                      ("jit_other(-7)", 29, 40),
                                      ("jit_step(-7)", 45, 65)],
                                {**names, **big})
    assert got == [(prog[instr], 10, 20), ("copy.1", 30, 31),
                   (prog[instr], 50, 60)]


def test_host_step_self_time_and_idle_gaps():
    host = {"/host:CPU/python": [
        ("edgc.step", 0, 10), ("PjitFunction(step)", 1, 2),
        ("edgc.step", 10, 30), ("edgc.flush", 12, 20),
        ("edgc.window_end", 20, 25), ("bench.window", 0, 40),
        ("edgc.step", 45, 50)]}
    spans = bench_scopes.host_spans(host, 0, 40)
    assert [n for n, _, _ in spans] == ["edgc.step", "edgc.step",
                                        "edgc.flush", "edgc.window_end"]
    assert bench_scopes.step_self_ns(spans) == [10, 7]
    ops = {"/device:TPU:0": [("a", 0, 13), ("b", 16, 40)]}
    assert bench_scopes.idle_gaps(ops, spans, 0, 40) == [
        ("edgc.flush", 3)]


def test_traced_trainer_run_holds_its_spans(tmp_path):
    tr = _trainer(make_host_mesh(), steps=3, log_every=2)
    jax.profiler.start_trace(str(tmp_path))
    tr.run(_data(), 3)
    jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_xplane(str(tmp_path)))
    spans = bench_scopes.host_spans(trace.host, 0, 1 << 62)
    lines = {line for line, evs in trace.host.items()
             for n, _, _ in evs if n == scopes.STEP}
    assert len(lines) == 1
    steps = [ev for ev in spans if ev[0] == scopes.STEP]
    flushes = [ev for ev in spans if ev[0] == scopes.FLUSH]
    assert len(steps) == 3
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))  # one clock
    last = steps[-1]
    assert any(last[1] <= s and e <= last[2] for _, s, e in flushes)
