"""A new model family comes in by new files only: its configuration, its
plain reference (``reference_<family>.py``), its FLOP count
(``flops_<family>.py``), a metric that reads a scope of its own and one
that reads a program counter, with no file of ``bench/`` edited."""
import hashlib
import json
import shutil
import sys
import types

import jax

import harness
import xplane
from conftest import BENCH

ROOT = BENCH.parent

REFERENCE_TOY = '''
import jax
import jax.numpy as jnp
from reference import leaf_norms

COMPRESSED = ("w",)


def init_params(model, key):
    return {"w": jax.random.normal(key, (model["d_model"],) * 2)}


def run(model, wl, seed, batches, steps=3, precision="f32", rows=4,
        update=True):
    p = init_params(model, jax.random.PRNGKey(seed))
    g = {"w": jnp.ones_like(p["w"])}
    return {"loss": [1.0] * steps, "entropy": None, "grad": leaf_norms(g),
            "change": leaf_norms(g if update else {"w": 0 * g["w"]})}
'''

FLOPS_TOY = '''
def flops_per_token(model, seq_len):
    return 6 * model["d_model"] ** 2
'''

TOY_MS = '''
import xplane


def read(ctx):
    return xplane.scope_ms(ctx.reduced, "toy", ctx.steps)
'''

TOY_ROUTE = '''
def read(ctx):
    found = ctx.events.get("toy_route")
    return float(found[-1]["data"]["experts"]) if found else None
'''


def tree_digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and "tests" not in p.parts}


def add_toy(tmp_path):
    """A copy of the benchmark with the toy family's new files in it."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = tree_digest(bench)
    model = {"name": "toy-1", "family": "toy", "d_model": 8,
             "vocab_size": 16}
    (bench / "configs" / "toy-1.json").write_text(json.dumps(
        {"name": "toy-1", "model": model}))
    (bench / "workloads" / "toy-1.plain.json").write_text(json.dumps(
        {"config": "toy-1", "chips": 1, "batch": 2, "seq_len": 4,
         "limits": {"loss_gap": 0.01}}))
    (bench / "reference_toy.py").write_text(REFERENCE_TOY)
    (bench / "flops_toy.py").write_text(FLOPS_TOY)
    (bench / "metrics" / "toy_ms.py").write_text(TOY_MS)
    (bench / "metrics" / "toy_route.py").write_text(TOY_ROUTE)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-1", "source": "a test",
                            "file": "bench/configs/toy-1.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "toy-1.plain", "config": "toy-1",
                              "traffic": "plain", "chips": 1, "why": "a test"})
    for name, source in (("toy_ms", "device_trace"),
                         ("toy_route", "program_counter")):
        spec["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower", "source": source,
             "layer": "toy", "moves": "tokens_per_s",
             "workloads": ["toy-1.plain"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = tree_digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    return bench, model


def toy_window():
    """A reduced window in which one op runs under ``edgc.toy`` (nested in
    ``edgc.forward``) for 3 ms and one under ``edgc.forward`` for 1 ms."""
    trace = xplane.Trace(
        ops={"/device:TPU:0": [("fusion.1", 0, 1_000_000),
                               ("fusion.2", 1_000_000, 4_000_000)]},
        modules={"/device:TPU:0": [("jit_step(5)", 0, 4_000_000)]},
        host={"python": [("edgc.step", 0, 4_000_000)]})
    instrs = {5: {"fusion.1": ("jit(step)/jvp(edgc.forward)/add", None),
                  "fusion.2": ("jit(step)/jvp(edgc.forward)/edgc.toy/dot",
                               None)}}
    return xplane.reduce_scopes(trace, instrs, 0, 4_000_000)


def test_a_new_family_needs_new_files_only(tmp_path, monkeypatch):
    bench, model = add_toy(tmp_path)
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.syspath_prepend(str(bench))
    for name in ("reference_toy", "flops_toy"):
        monkeypatch.delitem(sys.modules, name, raising=False)
    import flops

    wl, cfg = harness.load_cell("toy-1.plain")
    assert cfg["model"] == model
    ref = harness.reference_for(model)
    assert ref.__file__ == str(bench / "reference_toy.py")
    assert ref.COMPRESSED == ("w",)
    got = ref.run(model, wl, 2**31 + 5, [], 3)
    assert set(got) == {"loss", "entropy", "grad", "change"}
    p0 = ref.init_params(model, jax.random.PRNGKey(7))
    assert set(harness.change_norms(p0, model, 7).values()) == {0.0}
    assert flops.flops_per_token(model, 4) == 6 * 64

    names = [m["name"] for m in harness.cell_metrics("toy-1.plain", True)]
    assert names[-2:] == ["toy_ms", "toy_route"]
    ctx = types.SimpleNamespace(
        reduced=toy_window(), steps=2,
        events={"toy_route": [{"kind": "event", "name": "toy_route",
                               "step": 0, "data": {"experts": 8}}]})
    assert harness.read_metric("toy_ms", ctx) == 1.5
    assert harness.read_metric("toy_route", ctx) == 8.0
    ctx.events = {}
    assert harness.read_metric("toy_route", ctx) is None


def test_dense_resolves_to_the_gpt2_reference():
    import reference
    cfg = json.loads((BENCH / "configs" / "gpt2-345m.json").read_text())
    assert harness.reference_for(cfg["model"]) is reference
    assert harness.reference_for({}) is reference
