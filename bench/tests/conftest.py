"""The benchmark's own tests, run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

TINY_MODEL = {"name": "tiny", "family": "dense", "num_layers": 2,
              "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "d_ff": 256,
              "vocab_size": 256, "norm": "layernorm", "act": "gelu_plain",
              "pos": "learned", "tie_embeddings": True, "max_position": 32,
              "num_stages": 2, "remat": True}


@pytest.fixture
def tiny():
    """A cell's workload cut to a CPU-sized GPT-2 (same mechanisms), and the
    model at a given parameter dtype."""
    import harness

    def make(cell: str, dtype: str = "bfloat16"):
        wl, _ = harness.load_cell(cell)
        wl = dict(wl, batch=4, seq_len=32, ring=4)
        if wl.get("rank"):
            wl["rank"] = 8
        return wl, dict(TINY_MODEL, dtype=dtype)
    return make
