"""Compile the Pallas kernels of the train step for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
one chip of a ``v5e:2x2`` topology that is described, not attached, at the
shapes GPT-2 345M compresses (d_model 1024, d_ff 4096, fused QKV 3072) and
rank 32, and the flash attention forward and backward at its attention
(16 x 1024 tokens, 16 heads of 64), on one chip and split over a
data=2 x model=2 mesh of the four. A kernel that only works in interpret
mode fails here. The topology is described inside a fixture, so only the
worker that runs this file loads the TPU library, and the tests skip where
it cannot be described.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import lowrank as lr
from repro.kernels import ops, pack
from repro.kernels.flash_attention_bwd import flash_attention_train
from repro.dist.collectives import shard_map_dp
from repro.models import layers as L

RANK = 32
E = 24                      # one stacked shape group: every layer of 345M
MATRICES = [(1024, 3072), (1024, 1024), (1024, 4096), (4096, 1024)]
PAYLOAD_CODES = 1024 * 3072     # one full-size leaf, coded


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device compile can be written to the persistent cache
    # but never read back without a chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("m,n", MATRICES)
@pytest.mark.parametrize("kernel", ["p", "q", "decompress"])
@pytest.mark.parametrize("batched", [False, True], ids=["2d", "stacked"])
def test_lowrank_kernels_compile_for_v5e(one_chip, kernel, m, n, batched):
    lead = (E,) if batched else ()
    g = (lead + (m, n), BF16)
    if kernel == "p":
        fn = lr.ef_lowrank_p_batched if batched else lr.ef_lowrank_p
        shapes = [g, g, (lead + (n, RANK), F32)]
    elif kernel == "q":
        fn = lr.ef_lowrank_q_batched if batched else lr.ef_lowrank_q
        shapes = [g, g, (lead + (m, RANK), F32)]
    else:
        fn = (lr.decompress_residual_batched if batched
              else lr.decompress_residual)
        shapes = [(lead + (m, RANK), F32), (lead + (n, RANK), F32), g, g]
    text = _compiled_text(functools.partial(fn, interpret=False), one_chip,
                          *shapes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", sorted({m for m, _ in MATRICES}
                                     | {n for _, n in MATRICES}))
@pytest.mark.parametrize("batched", [False, True], ids=["2d", "stacked"])
def test_gram_schmidt_panel_compiles_for_v5e(one_chip, m, batched):
    fn = lr.gram_schmidt_panel_batched if batched else lr.gram_schmidt_panel
    shape = ((E,) if batched else ()) + (m, RANK)
    text = _compiled_text(functools.partial(fn, interpret=False), one_chip,
                          (shape, F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [8, 4])
def test_pack_kernels_compile_for_v5e(one_chip, bits):
    epw, bw = 32 // bits, 512
    nwords = -(-PAYLOAD_CODES // epw // bw) * bw
    packed = _compiled_text(
        functools.partial(pack.pack_words, bits=bits, bw=bw, interpret=False),
        one_chip, ((epw, nwords), jnp.uint32))
    unpacked = _compiled_text(
        functools.partial(pack.unpack_words, bits=bits, bw=bw,
                          interpret=False),
        one_chip, ((nwords,), jnp.uint32))
    assert "tpu_custom_call" in packed
    assert "tpu_custom_call" in unpacked


@pytest.mark.parametrize("kv_heads", [16, 4], ids=["mha", "gqa"])
def test_flash_attention_train_compiles_for_v5e(one_chip, kv_heads):
    B, T, H, Dh = 16, 1024, 16, 64
    block = min(ops.FLASH_BLOCK, T)

    def loss(q, k, v):
        o = flash_attention_train(q, k, v, True, block, block, False)
        return jnp.sum(o.astype(F32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                          ((B, T, H, Dh), BF16), ((B, T, kv_heads, Dh), BF16),
                          ((B, T, kv_heads, Dh), BF16))
    assert text.count("tpu_custom_call") >= 3       # fwd, dq, dkv


def test_flash_attention_keeps_heads_split_under_tp(v5e, monkeypatch):
    """The train step's layout on data=2 x model=2: data manual, model Auto,
    wq/wk/wv column- and wo row-parallel. The flash kernels run on each
    device's own 8 heads of its 8 sequences: the compiled gradient gathers
    nothing, and only reduces (Megatron's all-reduces of x's and wo's
    partial sums)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    B, T, H, Dh = 16, 1024, 16, 64
    d = H * Dh
    cols, rows = P(None, "model"), P("model", None)
    specs = {"wq": cols, "wk": cols, "wv": cols, "wo": rows}
    p = {n: jax.ShapeDtypeStruct((d, d), BF16,
                                 sharding=NamedSharding(mesh, s))
         for n, s in specs.items()}
    x = jax.ShapeDtypeStruct((B, T, d), BF16,
                             sharding=NamedSharding(mesh, P("data")))

    def local(p, x):
        def loss(p):
            o = L.attn_apply(p, x, num_heads=H, num_kv_heads=H, head_dim=Dh)
            return jnp.sum(o.astype(F32))
        return jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, "data"),
                                      jax.grad(loss)(p))

    step = shard_map_dp(local, mesh, in_specs=(P(), P("data")),
                        out_specs=P(), manual_axes=("data",))
    text = jax.jit(step).lower(p, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 3       # fwd, dq, dkv
    assert "bf16[64,1024,64]" in text          # 8 sequences x 8 heads each
    assert "all-gather" not in text
