"""Training attention's route: causal self-attention takes the Pallas flash
kernels on a compiled backend, everything else ``blockwise_attention``.

The backend is stood in for by patching ``ops._flash_backend``; the kernels
then run interpreted here, through the same custom VJP as on the chip. The
route is read from the traced jaxpr (``ops.flash_attention_calls``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.analysis.jaxpr_walk import walk_trips
from repro.configs.gpt2 import GPT2_345M
from repro.core import EDGCConfig, GDSConfig
from repro.core.dac import DACConfig
from repro.data.pipeline import SyntheticLM
from repro.kernels import flash_attention_bwd as fab
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh
from repro.models import layers as L
from repro.models.model import ModelConfig, build_model
from repro.obs import MemorySink, MetricsRegistry
from repro.optim.adam import AdamConfig
from repro.train.trainer import Trainer, TrainerConfig

BF16 = jnp.bfloat16


def _attn(p, x, H, Hkv, Dh, **kw):
    return L.attn_apply(p, x, num_heads=H, num_kv_heads=Hkv, head_dim=Dh,
                        **kw)


def _grad_jaxpr(H, Hkv, Dh, B, T, **kw):
    """The jaxpr of the gradient of one ``attn_apply`` call, traced from
    shapes alone."""
    p = jax.eval_shape(lambda k: L.attn_init(k, H * Dh, H, Hkv, Dh, BF16),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((B, T, H * Dh), BF16)

    def loss(p, x):
        return jnp.sum(_attn(p, x, H, Hkv, Dh, **kw).astype(jnp.float32))

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(p, x)


def _auto_mesh(**sizes):
    return AbstractMesh(tuple(sizes.values()), tuple(sizes),
                        axis_types=(AxisType.Auto,) * len(sizes))


@pytest.mark.parametrize("compiled,T,causal,window,mesh,route", [
    (True, 1024, True, 0, None, "flash"),
    (False, 1024, True, 0, None, "blockwise"),    # CPU: interpreted kernels
    (True, 1024, True, 256, None, "blockwise"),   # sliding window
    (True, 1536, True, 0, None, "blockwise"),     # T not a whole number of blocks
    (True, 1024, False, 0, None, "blockwise"),    # non-causal (encoder)
    (True, 1024, True, 0, {"data": 2, "model": 2}, "flash"),   # TP splits heads
    (True, 1024, True, 0, {"data": 1, "model": 8}, "blockwise"),  # 4 kv heads
    (True, 1024, True, 0, {"data": 4, "model": 1}, "blockwise"),  # batch of 2
], ids=["compiled", "cpu", "window", "ragged_T", "non_causal", "tp2",
        "tp8_uneven_heads", "dp4_uneven_batch"])
def test_attention_route(monkeypatch, compiled, T, causal, window, mesh,
                         route):
    monkeypatch.setattr(ops, "_flash_backend", lambda: compiled)
    H, Hkv, Dh, B = 8, 4, 64, 2
    kw = dict(causal=causal, window=window)
    if mesh is None:
        jaxpr = _grad_jaxpr(H, Hkv, Dh, B, T, **kw)
    else:
        with jax.sharding.use_abstract_mesh(_auto_mesh(**mesh)):
            jaxpr = _grad_jaxpr(H, Hkv, Dh, B, T, **kw)
    assert ops.flash_attention_calls(jaxpr) == int(route == "flash")


def test_flash_kernels_run_on_local_heads_under_tp(monkeypatch):
    """With ``model`` Auto, each device's kernels see only its own heads
    (and its own share of the batch over the other Auto axes), never the
    whole head set gathered to it."""
    monkeypatch.setattr(ops, "_flash_backend", lambda: True)
    H, Hkv, Dh, B, T = 8, 4, 64, 4, 256
    with jax.sharding.use_abstract_mesh(_auto_mesh(data=2, model=2)):
        jaxpr = _grad_jaxpr(H, Hkv, Dh, B, T)
    leading = {eqn.invars[0].aval.shape[0] for eqn, _ in walk_trips(jaxpr)
               if eqn.primitive.name == "pallas_call"}
    assert leading == {(B // 2) * (H // 2)}      # q rows: local batch x heads


@pytest.mark.parametrize("auto,specs", [
    ({}, (P(None, None, None, None), P(None, None, None))),
    ({"model": 2}, (P(None, None, "model", None), P(None, "model", None))),
    ({"pod": 2, "data": 2, "model": 4},
     (P(("pod", "data"), None, "model", None), P(("pod", "data"), "model",
                                                 None))),
], ids=["none", "model", "pod_data_model"])
def test_flash_partition_specs(auto, specs):
    assert fab.partition(auto) == specs


def test_gpt2_345m_attention_is_all_flash(monkeypatch):
    """Every one of GPT-2 345M's 24 remat'd, scanned layers takes the flash
    kernels on a compiled backend at the benchmark's 16 x 1024 batch."""
    monkeypatch.setattr(ops, "_flash_backend", lambda: True)
    model = build_model(GPT2_345M)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((16, 1024), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: model.loss_fn(p, b)[0]))(
        params, batch)
    assert ops.flash_attention_calls(jaxpr) == 24


def _rand(shape, seed):
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x, jnp.float32).astype(BF16)


@pytest.mark.parametrize("Hkv", [4, 2], ids=["mha", "gqa_rep2"])
def test_flash_route_matches_blockwise(monkeypatch, Hkv):
    """``attn_apply``'s flash route (interpreted) against the blockwise route,
    both in bf16: values, and gradients with respect to wq, wk, wv, wo, x and
    the core's q, k, v. Against a float32 run of the blockwise route, the
    flash route is as close as the blockwise one (no precision lowered)."""
    B, T, H, Dh = 2, 256, 4, 64
    d = H * Dh
    p = L.attn_init(jax.random.PRNGKey(3), d, H, Hkv, Dh, BF16)
    x = _rand((B, T, d), 4)
    q, k, v = (_rand((B, T, h, Dh), s) for s, h in ((5, H), (6, Hkv), (7, Hkv)))

    def attn(p, x):
        return _attn(p, x, H, Hkv, Dh)

    def core(q, k, v):
        if ops.flash_attention_fits(B, T, Hkv, Dh, causal=True, window=0):
            return ops.flash_attention(q, k, v)
        return L.blockwise_attention(q, k, v, causal=True)

    def sin_sum(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a).astype(jnp.float32)))

    def run(p, x, q, k, v):
        grad = jax.grad(sin_sum(attn), argnums=(0, 1))
        flash = ops.flash_attention_calls(jax.make_jaxpr(grad)(p, x))
        gp, gx = grad(p, x)
        gq, gk, gv = jax.grad(sin_sum(core), argnums=(0, 1, 2))(q, k, v)
        got = {"out": attn(p, x), "x": gx, "q": gq, "k": gk, "v": gv,
               **{n: gp[n] for n in ("wq", "wk", "wv", "wo")}}
        return {n: np.asarray(a, np.float32) for n, a in got.items()}, flash

    monkeypatch.setattr(ops, "_flash_backend", lambda: False)
    blockwise, calls_b = run(p, x, q, k, v)
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        exact, _ = run(f32(p), f32(x), f32(q), f32(k), f32(v))
    monkeypatch.setattr(ops, "_flash_backend", lambda: True)
    flash, calls_f = run(p, x, q, k, v)
    assert (calls_b, calls_f) == (0, 1)
    norm = np.linalg.norm
    for name, want in exact.items():
        got, base = flash[name], blockwise[name]
        np.testing.assert_allclose(got, base, rtol=0,
                                   atol=2.0 ** -5 * np.abs(want).max(),
                                   err_msg=name)
        assert norm(got - base) <= 2.0 ** -7 * norm(want), name
        assert norm(got - want) <= 1.25 * norm(base - want), name


def test_trainer_emits_attention_route(monkeypatch):
    """One ``attention_route`` event per step variant, counting each
    scanned layer; the flash route trains through the whole step."""
    monkeypatch.setattr(ops, "_flash_backend", lambda: True)
    cfg = ModelConfig(name="route", family="dense", num_layers=3, d_model=128,
                      num_heads=2, num_kv_heads=2, d_ff=256, vocab_size=256,
                      act="gelu_plain", pos="learned", max_position=128,
                      num_stages=1, dtype="float32")
    edgc = EDGCConfig(policy="fixed", fixed_rank=4, num_stages=1,
                      total_iterations=2, gds=GDSConfig(alpha=0.5, beta=0.25),
                      dac=DACConfig(window=100))
    sink = MemorySink()
    tcfg = TrainerConfig(total_steps=2, log_every=1,
                         metrics=MetricsRegistry([sink]),
                         adam=AdamConfig(lr=1e-3, warmup_steps=0,
                                         total_steps=2))
    tr = Trainer(build_model(cfg), make_host_mesh(), edgc, tcfg, seed=0)
    hist = tr.run(SyntheticLM(256, 128, 2, seed=1).batches())
    assert len(hist) == 2 and all(np.isfinite(h["loss"]) for h in hist)
    tr.metrics.flush()
    events = sink.events("attention_route")
    assert len(events) == len(tr.step_cache_keys())
    assert {e["data"]["flash"] for e in events} == {3}
