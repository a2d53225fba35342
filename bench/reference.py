"""Plain float32 reference of one data-parallel worker's training step.

It imports nothing of the program and takes nothing the program made. From
the seed it re-derives the initial weights and PowerSGD's warm-start factors
by the recipe the program documents (``jax.random`` key splits), then trains
in plain ``jax.numpy`` at float32 with ``Precision.HIGHEST``:

* a GPT-2 decoder as the configuration states it: learned positions,
  pre-LayerNorm blocks with causal multi-head attention and a tanh-GeLU MLP
  with biases, no attention biases, final LayerNorm, LM head tied to the
  token embedding, mean next-token cross-entropy;
* the gradient of that loss, summed over blocks of rows so that it fits;
* PowerSGD with error feedback at the stated rank on every block matrix
  (one power iteration from the warm-start Q, orthonormalised by QR);
* the pooled Gaussian entropy of a strided beta-sample of the synced
  gradient (GDS, Lemma 2);
* AdamW with global-norm clipping and a cosine schedule.

Parameters are stored in the configuration's parameter dtype (bfloat16)
between steps, as the configuration states; every operation computes in
float32. ``precision="fp8"`` is the control: the same step with the
operands of every model matrix product rounded to scaled float8 (e4m3
forward, e5m2 for the gradients flowing back), which a correct program at
bfloat16 must not pass for.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LOG_2PI_E = math.log(2.0 * math.pi) + 1.0
COMPRESSED = ("wq", "wk", "wv", "wo", "up", "down")

# ------------------------------------------------------------ matrix products


def _mm32(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _q8(x, dtype, fmax):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / fmax, 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


def _e4m3(x):
    return _q8(x, jnp.float8_e4m3fn, 448.0)


def _e5m2(x):
    return _q8(x, jnp.float8_e5m2, 57344.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(eq, a, b):
    return _mm32(eq, _e4m3(a), _e4m3(b))


def _mm8_fwd(eq, a, b):
    qa, qb = _e4m3(a), _e4m3(b)
    return _mm32(eq, qa, qb), (qa, qb)


def _mm8_bwd(eq, res, g):
    _, vjp = jax.vjp(functools.partial(_mm32, eq), *res)
    return vjp(_e5m2(g))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)

MATMUL = {"f32": _mm32, "fp8": _mm8}

# --------------------------------------------------------------------- model


def stage_sizes(layers: int, stages: int) -> list[int]:
    base, extra = divmod(layers, stages)
    return [base + (1 if i < extra else 0) for i in range(stages)]


def check_supported(model: dict) -> None:
    want = {"family": "dense", "norm": "layernorm", "act": "gelu_plain",
            "pos": "learned", "tie_embeddings": True, "qkv_bias": False,
            "qk_norm": False, "sliding_window": 0}
    bad = {k: model.get(k) for k, v in want.items() if model.get(k, v) != v}
    if bad or model.get("num_kv_heads", model["num_heads"]) != model["num_heads"]:
        raise ValueError(f"the reference covers GPT-2 blocks only, not {bad}")


def init_params(model: dict, key) -> dict:
    """The program's initial weights from ``key`` (its recipe: one split
    per stage plus three, one per block, then per projection), rounded to
    the parameter dtype and held in float32."""
    check_supported(model)
    d, H, f, V = (model["d_model"], model["num_heads"], model["d_ff"],
                  model["vocab_size"])
    hd = model.get("head_dim") or d // H
    S = model["num_stages"]
    normal = lambda k, shape, scale: jax.random.normal(k, shape, F32) * scale

    def block(k):
        k4 = jax.random.split(k, 4)
        ka = jax.random.split(k4[0], 4)
        km = jax.random.split(k4[1], 3)
        return {
            "attn": {"wq": normal(ka[0], (d, H * hd), 1 / math.sqrt(d)),
                     "wk": normal(ka[1], (d, H * hd), 1 / math.sqrt(d)),
                     "wv": normal(ka[2], (d, H * hd), 1 / math.sqrt(d)),
                     "wo": normal(ka[3], (H * hd, d), 1 / math.sqrt(H * hd))},
            "attn_norm_scale": jnp.ones((d,), F32),
            "attn_norm_bias": jnp.zeros((d,), F32),
            "mlp": {"up": normal(km[0], (d, f), 1 / math.sqrt(d)),
                    "down": normal(km[1], (f, d), 1 / math.sqrt(f)),
                    "up_bias": jnp.zeros((f,), F32),
                    "down_bias": jnp.zeros((d,), F32)},
            "mlp_norm_scale": jnp.ones((d,), F32),
            "mlp_norm_bias": jnp.zeros((d,), F32),
        }

    ks = jax.random.split(key, S + 3)
    params = {
        "embed": {"tok": normal(ks[0], (V, d), 0.02)},
        "stages": [{"blocks": jax.vmap(block)(jax.random.split(ks[1 + s], n))}
                   for s, n in enumerate(stage_sizes(model["num_layers"], S))],
        "final_norm_scale": jnp.ones((d,), F32),
        "final_norm_bias": jnp.zeros((d,), F32),
        "pos_embed": normal(ks[-2], (model["max_position"], d), 0.01),
    }
    return round_params(params, model)


def round_params(params, model):
    """Round to the parameter dtype, held in float32. ``reduce_precision``
    and not a round trip through the dtype: XLA may fold a pair of converts
    away inside a fused program (excess precision), keeping float32."""
    fi = jnp.finfo(jnp.dtype(model.get("dtype", "float32")))
    if fi.bits == 32:
        return params
    return jax.tree_util.tree_map(
        lambda p: jax.lax.reduce_precision(p, exponent_bits=fi.nexp,
                                           mantissa_bits=fi.nmant), params)


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(x, p, model, mm):
    B, T, d = x.shape
    H = model["num_heads"]
    hd = model.get("head_dim") or d // H
    eps = model.get("norm_eps", 1e-5)
    h = _layer_norm(x, p["attn_norm_scale"], p["attn_norm_bias"], eps)
    a = p["attn"]
    q = mm("btd,de->bte", h, a["wq"]).reshape(B, T, H, hd)
    k = mm("btd,de->bte", h, a["wk"]).reshape(B, T, H, hd)
    v = mm("btd,de->bte", h, a["wv"]).reshape(B, T, H, hd)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + mm("bte,ed->btd", o.reshape(B, T, H * hd), a["wo"])
    h = _layer_norm(x, p["mlp_norm_scale"], p["mlp_norm_bias"], eps)
    m = p["mlp"]
    u = _gelu_tanh(mm("btd,df->btf", h, m["up"]) + m["up_bias"])
    return x + mm("btf,fd->btd", u, m["down"]) + m["down_bias"]


def loss_fn(params, tokens, labels, model, mm):
    """Mean next-token cross-entropy of a block of rows, in nats."""
    T = tokens.shape[1]
    x = params["embed"]["tok"][tokens] + params["pos_embed"][:T]
    body = jax.checkpoint(lambda h, p: (_block(h, p, model, mm), None))
    for stage in params["stages"]:
        x, _ = jax.lax.scan(body, x, stage["blocks"])
    x = _layer_norm(x, params["final_norm_scale"], params["final_norm_bias"],
                    model.get("norm_eps", 1e-5))
    logits = mm("btd,vd->btv", x, params["embed"]["tok"])
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)


def loss_and_grads(params, tokens, labels, model, mm, rows):
    """Loss and gradient of the batch mean, summed over blocks of ``rows``."""
    B, T = tokens.shape
    n = B // rows
    xs = (tokens.reshape(n, rows, T), labels.reshape(n, rows, T))
    vg = jax.value_and_grad(loss_fn)

    def body(acc, x):
        loss, g = vg(params, x[0], x[1], model, mm)
        return (acc[0] + loss,
                jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), F32), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, xs)
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)

# ------------------------------------------------------------------ training


def path_of(kp) -> str:
    return jax.tree_util.keystr(kp)


def compressed_paths(params) -> list[str]:
    """Every block matrix, in the order the tree flattens."""
    return [path_of(kp) for kp, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]
            if path_of(kp).split("'")[-2] in COMPRESSED]


def init_q(params, rank: int, key) -> dict:
    """Warm-start Q per compressed leaf, the program's recipe: leaf ``i`` of
    the compressed ones in flatten order draws ``normal(fold_in(key, i))``."""
    by_path = {path_of(kp): p for kp, p in
               jax.tree_util.tree_flatten_with_path(params)[0]}
    out = {}
    for i, path in enumerate(compressed_paths(params)):
        shape = by_path[path].shape
        out[path] = jax.random.normal(jax.random.fold_in(key, i),
                                      shape[:-2] + (shape[-1], rank), F32)
    return out


def powersgd(g, err, q):
    """One power iteration with error feedback on a (L, m, n) stack."""
    m = g + err
    p = jnp.einsum("lmn,lnr->lmr", m, q, precision=HIGHEST)
    p, _ = jnp.linalg.qr(p)
    q_new = jnp.einsum("lmn,lmr->lnr", m, p, precision=HIGHEST)
    g_hat = jnp.einsum("lmr,lnr->lmn", p, q_new, precision=HIGHEST)
    return g_hat, m - g_hat, q_new


def entropy(tree, beta: float):
    """Gaussian entropy of the pooled strided beta-sample of every leaf with
    more than 16 entries (GDS)."""
    n, s1, s2 = 0, 0.0, 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        if leaf.size <= 16:
            continue
        k = max(1, int(leaf.size * beta))
        stride = max(1, leaf.size // k)
        s = leaf.reshape(-1)[:stride * k:stride]
        n += s.shape[0]
        s1 = s1 + jnp.sum(s)
        s2 = s2 + jnp.sum(jnp.square(s))
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    return jnp.log(jnp.sqrt(var) + 1e-12) + 0.5 * LOG_2PI_E


def lr_at(adam: dict, step):
    step = step.astype(F32)
    lr, warm = adam["lr"], adam["warmup_steps"]
    prog = jnp.clip((step - warm) / max(1, adam["total_steps"] - warm), 0, 1)
    frac = adam.get("min_lr_frac", 0.1)
    cos = lr * (frac + (1 - frac) * 0.5 * (1 + jnp.cos(math.pi * prog)))
    return jnp.where(step < warm, lr * step / max(1, warm), cos)


def adamw(params, grads, m, v, step, adam: dict, model: dict):
    b1, b2 = adam.get("betas", (0.9, 0.95))
    eps, wd = adam.get("eps", 1e-8), adam.get("weight_decay", 0.1)
    clip = adam.get("grad_clip", 1.0)
    step = step + 1
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-12))
    lr = lr_at(adam, step)
    c1 = 1.0 - b1 ** step.astype(F32)
    c2 = 1.0 - b2 ** step.astype(F32)

    def leaf(p, g, m_, v_):
        g = g * scale
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        upd = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        if p.ndim >= 2:         # the configuration decays every >=2-D leaf
            upd = upd + wd * p
        return p - lr * upd, m_, v_

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    is_t = lambda x: isinstance(x, tuple)
    pick = lambda i: jax.tree_util.tree_map(lambda t: t[i], out, is_leaf=is_t)
    return round_params(pick(0), model), pick(1), pick(2), step


def make_step(model: dict, wl: dict, precision: str = "f32", rows: int = 4,
              update: bool = True):
    """The reference's training step: ``(state, tokens, labels) -> (state,
    loss, entropy)``; ``state`` holds params, Adam's m and v, the step, and
    per compressed leaf PowerSGD's Q and error-feedback residual. With
    ``update`` false the step returns the state it was given (a fault for
    the control's readings)."""
    mm = MATMUL[precision]
    rank = wl.get("rank")
    beta = wl["gds"]["beta"]

    def step(state, tokens, labels):
        params = state["params"]
        loss, grads = loss_and_grads(params, tokens, labels, model, mm,
                                     min(rows, tokens.shape[0]))
        q, err = dict(state["q"]), dict(state["err"])
        if rank:
            flat, tdef = jax.tree_util.tree_flatten_with_path(grads)
            synced = []
            for kp, g in flat:
                path = path_of(kp)
                if path in q:
                    g, err[path], q[path] = powersgd(g, err[path], q[path])
                synced.append(g)
            grads = jax.tree_util.tree_unflatten(tdef, synced)
        h = entropy(grads, beta)
        if not update:
            return state, loss, h
        p, m, v, n = adamw(params, grads, state["m"], state["v"],
                           state["step"], wl["adam"], model)
        return {"params": p, "m": m, "v": v, "step": n, "q": q,
                "err": err}, loss, h

    return jax.jit(step, donate_argnums=0)


def init_state(model: dict, wl: dict, seed: int):
    """The reference's state at step 0, re-derived from the seed."""
    key = jax.random.PRNGKey(seed)
    params = init_params(model, key)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    q, err = {}, {}
    if wl.get("rank"):
        q = init_q(params, wl["rank"], jax.random.fold_in(key, 99))
        by_path = {path_of(kp): p for kp, p in
                   jax.tree_util.tree_flatten_with_path(params)[0]}
        err = {path: jnp.zeros_like(by_path[path]) for path in q}
    return {"params": params, "m": zeros(params), "v": zeros(params),
            "step": jnp.zeros((), jnp.int32), "q": q, "err": err}


def leaf_norms(tree) -> dict[str, float]:
    """Per-leaf Euclidean norms, by path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(jnp.square(
        l.astype(F32)))) for l in ls])([l for _, l in flat])
    return {path_of(kp): float(n) for (kp, _), n in zip(flat, norms)}


def run(model: dict, wl: dict, seed: int, batches, steps: int = 3,
        precision: str = "f32", rows: int = 4, update: bool = True) -> dict:
    """The readings of ``steps`` reference steps on ``batches``: each step's
    loss, the step-0 entropy, the first gradient as Adam takes it (Adam's
    m after one step over 1 - beta1), and each leaf's change of parameters
    after all steps."""
    b1 = wl["adam"].get("betas", (0.9, 0.95))[0]
    state = init_state(model, wl, seed)
    dt = jnp.dtype(model.get("dtype", "float32"))   # holds them exactly
    p0 = jax.tree_util.tree_map(lambda p: jnp.copy(p.astype(dt)),
                                state["params"])
    fn = make_step(model, wl, precision, rows, update)
    losses, ent, grad = [], None, None
    for i in range(steps):
        b = batches[i]
        state, loss, h = fn(state, jnp.asarray(b["tokens"]),
                            jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if i == 0:
            ent = float(h)
            grad = {k: v / (1 - b1)
                    for k, v in leaf_norms(state["m"]).items()}
    change = leaf_norms(jax.tree_util.tree_map(
        lambda p, q: p - q.astype(F32), state["params"], p0))
    del state
    return {"loss": losses, "entropy": ent, "grad": grad, "change": change}
