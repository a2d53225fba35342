#!/usr/bin/env python3
"""Where the program's first steps depart from the reference, leaf by leaf.

    python bench/diagnose.py --workload <cell> --seed <n>

Builds the cell's trainer as a run does, takes its parameters before and
after each of the first three steps (and Adam's moments after the first),
frees it, runs the plain reference on the same seed and batches, and
prints one JSON line per leaf: elements whose initial weights differ, the
norm of each side's change after one and three steps, the share of
elements that differ after one step, the first moments' and second
moments' relative gap, and how many elements each side's own Adam
arithmetic fails to reproduce from its own moments. Then the float32
loss of each side's parameters after one and two updates, which tells
parameters that differ from a loss that is only evaluated differently.
For the look at a number the check cannot explain; runs do not call it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def flat(tree) -> dict:
    import jax
    import numpy as np
    from reference import path_of
    return {path_of(kp): np.array(v, np.float32) for kp, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def adam_once(p0, m, v, adam: dict, lr: float, dtype):
    """Step 1 of AdamW from the moments, rounded to ``dtype`` (numpy)."""
    import numpy as np
    b1, b2 = adam.get("betas", (0.9, 0.95))
    upd = (m / (1 - b1)) / (np.sqrt(v / (1 - b2)) + adam.get("eps", 1e-8))
    if p0.ndim >= 2:
        upd = upd + adam.get("weight_decay", 0.1) * p0
    return (p0 - np.float32(lr) * upd).astype(dtype).astype(np.float32)


def compare(model: dict, wl: dict, seed: int, devices) -> dict:
    """The per-leaf rows and the cross-evaluated losses of one seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import harness
    from reference import path_of
    reference = harness.reference_for(model)

    host = harness.make_ring(model, wl, seed)
    ring = [jax.device_put(b, devices[0]) for b in host]
    tr = harness.build_trainer(model, wl, seed, devices)
    data = harness.feed(ring)
    P = [flat(tr.state["params"])]
    tr.run(data, 1)
    P.append(flat(tr.state["params"]))
    M, V = flat(tr.state["opt_m"]), flat(tr.state["opt_v"])
    for _ in range(2):
        tr.run(data, 1)
        P.append(flat(tr.state["params"]))
    hist = [{k: h[k] for k in ("loss", "grad_norm", "lr")}
            for h in tr.history[:3]]
    harness.free(tr.state)
    del tr, data, ring
    gc.collect()

    seed32 = harness.program_seed(seed)
    state = reference.init_state(model, wl, seed32)
    step = reference.make_step(model, wl)
    R, ref_loss = [flat(state["params"])], []
    for i in range(3):
        state, loss, _ = step(state, jnp.asarray(host[i]["tokens"]),
                              jnp.asarray(host[i]["labels"]))
        ref_loss.append(float(loss))
        R.append(flat(state["params"]))
        if i == 0:
            Mr, Vr = flat(state["m"]), flat(state["v"])
    del state
    gc.collect()

    like = reference.init_params(model, jax.random.PRNGKey(seed32))
    leaves, tdef = jax.tree_util.tree_flatten_with_path(like)
    del like
    mm = reference.MATMUL["f32"]
    rows = min(4, wl["batch"])
    f32_loss = jax.jit(lambda p, t, l: jnp.mean(jax.lax.map(
        lambda x: reference.loss_fn(p, x[0], x[1], model, mm),
        (t.reshape(-1, rows, t.shape[1]), l.reshape(-1, rows, l.shape[1])))))
    cross = {}
    for k in (1, 2):
        for who, src in (("program", P), ("reference", R)):
            p = jax.tree_util.tree_unflatten(tdef, [
                jnp.asarray(src[k][path_of(kp)])
                for kp, _ in leaves])
            cross[f"{who}_after_{k}"] = float(f32_loss(
                p, jnp.asarray(host[k]["tokens"]),
                jnp.asarray(host[k]["labels"])))
            del p

    n = lambda x: float(np.sqrt(np.sum(np.square(x.astype(np.float64)))))
    rel = lambda a, b: n(a - b) / max(n(b), 1e-30)
    dt = jnp.dtype(model.get("dtype", "float32"))
    out = []
    for k in R[0]:
        row = {"leaf": k, "size": int(R[0][k].size),
               "init_differ": int(np.sum(P[0][k] != R[0][k])),
               "change1": [n(P[1][k] - P[0][k]), n(R[1][k] - R[0][k])],
               "change3": [n(P[3][k] - P[0][k]), n(R[3][k] - R[0][k])],
               "step1_differ": float(np.mean(P[1][k] != R[1][k])),
               "m_gap": rel(M[k], Mr[k]), "v_gap": rel(V[k], Vr[k]),
               "adam_misses": [
                   float(np.mean(adam_once(P[0][k], M[k], V[k], wl["adam"],
                                           hist[0]["lr"], dt) != P[1][k])),
                   float(np.mean(adam_once(R[0][k], Mr[k], Vr[k], wl["adam"],
                                           hist[0]["lr"], dt) != R[1][k]))]}
        c = row["change3"]
        row["change3_gap"] = abs(c[0] - c[1]) / max(c[1], 1e-30)
        out.append(row)
    return {"program": hist, "reference_loss": ref_loss,
            "f32_loss": cross, "leaves": out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    import jax
    import harness
    import run
    run.setup_cache(jax)
    wl, cfg = harness.load_cell(args.workload)
    res = compare(cfg["model"], wl, args.seed, jax.devices())
    for row in res.pop("leaves"):
        print(json.dumps(row))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
