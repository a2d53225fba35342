"""FLOPs and peaks, the files ``BENCHMARK.json`` names, and the runner's
refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import flops
import harness
from conftest import BENCH

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def hand_count(layers, d, ff, vocab, seq):
    attn = 4 * d * d                      # q, k, v, o
    mlp = 2 * d * ff                      # up, down
    n = layers * (attn + mlp) + vocab * d  # the tied head counts once
    return 6 * n + 12 * layers * seq * d


@pytest.mark.parametrize("model,want", [
    (json.loads((BENCH / "configs" / "gpt2-345m.json").read_text())["model"],
     2_422_708_224),
    ({"family": "dense", "num_layers": 26, "d_model": 1920, "num_heads": 20,
      "num_kv_heads": 20, "d_ff": 7680, "vocab_size": 50257,
      "act": "gelu_plain"}, 8_093_318_400),
])
def test_flops_per_token(model, want):
    got = flops.flops_per_token(model, 1024)
    assert got == want == hand_count(model["num_layers"], model["d_model"],
                                     model["d_ff"], model["vocab_size"], 1024)


def test_peaks_known_and_unknown():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")


def test_every_entry_resolves_to_its_file():
    for cfg in SPEC["configs"]:
        assert (ROOT / cfg["file"]).is_file()
        assert json.loads((ROOT / cfg["file"]).read_text())["name"] == cfg["name"]
    names = {c["name"] for c in SPEC["configs"]}
    for wl in SPEC["workloads"]:
        w, c = harness.load_cell(wl["name"])
        assert w["config"] == wl["config"] in names
        assert w["chips"] == wl["chips"]
        assert set(w["limits"]) and c["model"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_names_and_units_use_allowed_characters():
    entries = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
        + SPEC["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for wl in SPEC["workloads"]:
        assert NAME.match(wl["config"]) and NAME.match(wl["traffic"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len({e["name"] for e in entries}) == len(entries)


def test_a_dropped_in_cell_is_found_without_a_code_edit(tmp_path,
                                                        monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    wl = json.loads(
        (bench / "workloads" / "gpt2-345m.edgc-r342.json").read_text())
    wl["batch"] = 8
    (bench / "workloads" / "gpt2-345m.edgc-b8.json").write_text(
        json.dumps(wl))
    (bench / "metrics" / "loss_at_end.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    spec = dict(SPEC)
    spec["workloads"] = SPEC["workloads"] + [
        {"name": "gpt2-345m.edgc-b8", "config": "gpt2-345m",
         "traffic": "edgc-b8", "chips": 1, "why": "a test"}]
    spec["per_layer"] = SPEC["per_layer"] + [
        {"name": "loss_at_end", "unit": "nats", "better": "lower",
         "source": "program_counter", "layer": "step", "moves":
         "tokens_per_s", "workloads": ["gpt2-345m.edgc-b8"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    got, cfg = harness.load_cell("gpt2-345m.edgc-b8")
    assert got["batch"] == 8 and cfg["name"] == "gpt2-345m"
    names = [m["name"] for m in harness.cell_metrics("gpt2-345m.edgc-b8",
                                                     True)]
    assert names[-1] == "loss_at_end"
    assert "loss_at_end" not in [m["name"] for m in harness.cell_metrics(
        "gpt2-345m.edgc-r342", True)]
    assert harness.read_metric("loss_at_end", None) == 1.5


def run_bench(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2-345m.edgc-r342",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = run_bench(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = run_bench(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
