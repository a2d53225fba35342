"""Model operations per token, and the chip's peaks by ``device_kind``.

Model FLOPs per trained token = 6 N + 12 L s d_attn (forward and backward):

* N counts the parameters that enter matrix multiplications: the attention
  projections and MLP of every block, and the LM head once (a tied head is
  the embedding table used as a matrix). The embedding lookup is a gather
  and counts nothing.
* The attention term counts QK^T and PV in full, not halved for causal
  masking, as the step computes every masked block too.
* Recomputation (remat, pipeline replay) does not count.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def dense_flops_per_token(model: dict, seq_len: int) -> int:
    """6 N + 12 L s d_attn for a dense decoder (``model``: ModelConfig keys)."""
    d = model["d_model"]
    heads = model["num_heads"]
    kv_heads = model.get("num_kv_heads", heads)
    hd = model.get("head_dim") or d // heads
    d_ff = model["d_ff"]
    gated = model.get("act", "silu") in ("silu", "gelu")
    attn = d * heads * hd * 2 + d * kv_heads * hd * 2      # q, o, k, v
    mlp = d * d_ff * (3 if gated else 2)
    n_matmul = model["num_layers"] * (attn + mlp) + model["vocab_size"] * d
    return 6 * n_matmul + 12 * model["num_layers"] * seq_len * heads * hd


def flops_per_token(model: dict, seq_len: int) -> int:
    """Dispatch on the model family; a family other than ``dense`` brings
    its own ``flops_<family>.py`` beside this file, with the same function."""
    family = model.get("family", "dense")
    if family == "dense":
        return dense_flops_per_token(model, seq_len)
    mod = importlib.import_module(f"flops_{family}")
    return mod.flops_per_token(model, seq_len)


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]
