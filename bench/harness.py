"""One run of one benchmark cell: set-up, the timed window, the check.

Everything that belongs to one cell is read from files found by name:
``bench/workloads/<cell>.json`` (model configuration, chips, policy, rank,
GDS/DAC, batch, optimizer, limits) and ``bench/configs/<config>.json`` (the
model as it is run). The metrics are the readers ``bench/metrics/<name>.py``
that ``BENCHMARK.json`` lists for the cell; the plain reference is the
module of the configuration's family (``reference_for``).

The window drives the program's normal path, ``repro.train.trainer.Trainer``
built as ``repro.launch.train`` builds one. Set-up builds that one trainer,
drives it from the seed through its first steps (which compiles both step
variants the window uses, entropy on and off), and hands the same object to
the window. The first three steps are compared with the reference after
the window has closed and the program's state is freed.

A metric reader's ``read(ctx)`` gets one context (``run_cell``):
``setup_s``, ``window_s``, ``steps``, ``tokens``, ``chips``,
``flops_per_token``, ``device_kind``, ``window_compiles``, ``workload`` and
``model`` (the cell's files), ``events`` (every record the trainer's
``MetricsRegistry`` emitted in set-up and the window, by name: events,
counters, scalars, series, as ``repro.obs.MemorySink`` keeps them) and,
with ``--trace 1``, ``reduced`` (``reduce_trace``: busy and idle time, step
modules and the window by ``edgc.*`` scope, op and Pallas kernel, and host
span, ``xplane.reduce_window`` and ``xplane.reduce_scopes``); ``None``
without a trace.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECKED_STEPS = 3
PERIOD = 10            # steps per entropy period (GDS alpha 0.1)
WINDOW_SPAN = "bench.window"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict]:
    """The workload and configuration files of cell ``name``."""
    wl = load_json(BENCH / "workloads" / f"{name}.json")
    cfg = load_json(BENCH / "configs" / f"{wl['config']}.json")
    return wl, cfg


def cell_metrics(name: str, trace: bool) -> list[dict]:
    """``BENCHMARK.json``'s metrics of this cell: the end-to-end ones, or
    with ``trace`` the per-layer ones."""
    spec = load_json(ROOT / "BENCHMARK.json")
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in spec[kind] if name in m.get("workloads", [name])]


def read_metric(name: str, ctx) -> float | None:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def reference_for(model: dict):
    """The plain reference of ``model``'s family: ``reference.py`` for
    ``dense``, else ``reference_<family>.py`` beside it, as
    ``flops.flops_per_token`` finds ``flops_<family>.py``.

    A family module gives:

    * ``init_params(model, key)``: the initial weights that the program
      derives from ``key``, as a pytree of the program's parameter paths;
    * ``run(model, wl, seed, batches, steps=3, precision="f32", rows=4,
      update=True)``: the first ``steps`` steps on ``batches``, a dict with
      ``loss`` (one per step), ``entropy`` (step 0's, or ``None``),
      ``grad`` (each leaf's norm of the first gradient as the optimizer
      gets it) and ``change`` (each leaf's norm of the change after
      ``steps``), leaves named by ``reference.path_of`` and normed by
      ``reference.leaf_norms``. ``precision="fp8"`` is the control (model
      matrix products' operands in scaled float8); ``rows`` the rows per
      block of the gradient; ``update=False`` skips every update;
    * ``COMPRESSED``: the names of the leaves it compresses.

    ``bench/diagnose.py`` also takes ``init_state``, ``make_step``,
    ``loss_fn`` and ``MATMUL`` as ``reference.py`` has them."""
    family = model.get("family", "dense")
    return importlib.import_module(
        "reference" if family == "dense" else f"reference_{family}")


class CompileClock:
    """Counts XLA backend compiles, and their seconds, as JAX reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.count = 0

        def listen(event: str, duration: float, **_) -> None:
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def program_seed(seed: int) -> int:
    """``jax.random.PRNGKey`` keeps 32 bits of a seed."""
    return seed % (1 << 32)


def build_trainer(model: dict, wl: dict, seed: int, devices):
    """A Trainer configured as ``repro.launch.train`` configures one."""
    from repro.core import EDGCConfig, GDSConfig, SyncConfig
    from repro.core.dac import DACConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import ModelConfig, build_model
    from repro.optim.adam import AdamConfig
    from repro.pipeline import PipelineConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = ModelConfig(**model)
    mesh = make_host_mesh(devices=devices[:wl["chips"]])
    pipe = PipelineConfig(num_stages=cfg.num_stages, schedule="1f1b")
    sync = SyncConfig()
    adam = dict(wl["adam"])
    adam["betas"] = tuple(adam.get("betas", (0.9, 0.95)))
    edgc = EDGCConfig(
        policy=wl["policy"], fixed_rank=wl.get("rank") or 64,
        total_iterations=adam["total_steps"],
        gds=GDSConfig(alpha=wl["gds"]["alpha"], beta=wl["gds"]["beta"]),
        dac=DACConfig(window=wl["dac_window"]), pipeline=pipe, sync=sync)
    tcfg = TrainerConfig(
        total_steps=adam["total_steps"], log_every=1,
        measure_entropy=wl["measure_entropy"], pipeline=pipe, sync=sync,
        adam=AdamConfig(**adam))
    return Trainer(build_model(cfg), mesh, edgc, tcfg,
                   seed=program_seed(seed))


def make_ring(model: dict, wl: dict, seed: int) -> list[dict]:
    """Distinct host batches drawn from the seed, in the order fed."""
    from synthetic import SyntheticLM
    gen = SyntheticLM(model["vocab_size"], wl["seq_len"], wl["batch"], seed)
    return [gen.batch() for _ in range(wl["ring"])]


def feed(ring):
    """The window's input: the ring, placed on the device in set-up."""
    import jax
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.feed"):
            b = ring[i % len(ring)]
        i += 1
        yield b


def change_norms(params_host, model: dict, seed: int) -> dict[str, float]:
    """Each leaf's norm of ``params_host`` minus the initial weights that
    the seed gives (by the reference's own derivation). Eager, op by op,
    as the program makes them: a jitted init fuses the sampling with the
    scale and can round a few weights differently."""
    import jax
    import jax.numpy as jnp
    import reference
    p0 = reference_for(model).init_params(
        model, jax.random.PRNGKey(program_seed(seed)))
    delta = jax.tree_util.tree_map(
        lambda p, q: jnp.asarray(p).astype(jnp.float32) - q, params_host, p0)
    del p0
    return reference.leaf_norms(delta)


def free(tree) -> None:
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def run_cell(name: str, wl: dict, cfg: dict, seed: int, seconds: float,
             trace: bool, devices, t_start: float, observe=None) -> dict:
    """Set up, time the window, check; return the result line's fields.
    ``observe``, where given, is called with the metric context once the
    metrics are read."""
    import jax
    import numpy as np
    import check
    import flops
    import reference
    from repro.obs import MemorySink

    model = cfg["model"]
    clock = CompileClock()
    host_ring = make_ring(model, wl, seed)
    ring = [jax.device_put(b, devices[0]) for b in host_ring]
    tr = build_trainer(model, wl, seed, devices)
    records = MemorySink()      # host-side list appends at the flushes
    tr.metrics.sinks.append(records)
    data = feed(ring)
    b1 = wl["adam"].get("betas", (0.9, 0.95))[0]

    # The first steps: the window's own call and feed, one step per call so
    # each step's loss is recorded; they compile both step variants.
    prog: dict = {}
    tr.run(data, 1)
    prog["grad"] = {k: v / (1.0 - b1) for k, v in
                    reference.leaf_norms(tr.state["opt_m"]).items()}
    tr.run(data, CHECKED_STEPS - 1)
    # np.array copies: a zero-copy view of a donated buffer would change
    params_host = jax.tree_util.tree_map(np.array, tr.state["params"])
    prog["loss"] = [h["loss"] for h in tr.history[:CHECKED_STEPS]]
    hist = tr.controller.entropy_history
    prog["entropy"] = hist[0][1] if wl["measure_entropy"] and hist else None
    step_s = []
    for _ in range(2):
        t = time.perf_counter()
        tr.run(data, 1)
        step_s.append(time.perf_counter() - t)
    periods = max(1, round(seconds / (PERIOD * min(step_s))))
    steps = PERIOD * periods
    tr.tcfg.log_every = PERIOD          # the loop blocks once per period
    gc.collect()
    setup_s = time.perf_counter() - t_start

    # Every step's loss, by reference as each flush drains it: the window
    # counts non-finite losses over all its steps, not the logged ones.
    window_losses: list = []
    drain = tr._flush_pending

    def flush(pending, t0):
        window_losses.extend(m["loss"] for _, _, m, *_ in pending)
        drain(pending, t0)

    tr._flush_pending = flush
    compiles0 = clock.count
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.run"):
            tr.run(data, steps)
        jax.block_until_ready(tr.state)
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = clock.count - compiles0

    used = devices[:wl["chips"]]
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
    losses = np.asarray(jax.device_get(window_losses), np.float64)
    failed = steps - int(np.sum(np.isfinite(losses)))
    free(tr.state)
    del tr, data, ring
    gc.collect()

    reduced = None
    if trace:
        reduced = reduce_trace(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)

    t_check = time.perf_counter()
    prog["change"] = change_norms(params_host, model, seed)
    del params_host
    ref = reference_for(model).run(model, wl, program_seed(seed),
                                   host_ring[:CHECKED_STEPS], CHECKED_STEPS)
    values = check.readings(prog, ref)
    correct, table = check.judge(values, wl["limits"])

    dev = used[0]
    events: dict[str, list[dict]] = {}
    for rec in records.records:
        events.setdefault(rec["name"], []).append(rec)
    ctx = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, steps=steps,
        tokens=steps * wl["batch"] * wl["seq_len"], chips=len(used),
        flops_per_token=flops.flops_per_token(model, wl["seq_len"]),
        device_kind=dev.device_kind, reduced=reduced,
        window_compiles=window_compiles, workload=wl, model=model,
        events=events)
    metrics = {}
    for m in cell_metrics(name, trace):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if observe is not None:
        observe(ctx)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = (reduced["busy_ns"] or 0) / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        ops = sorted(((n, row["ns"]) for n, row in reduced["ops"].items()),
                     key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t / 1e9] for n, t in reduced["idle_gaps"]]}
    result["check"] = table
    print(f"[bench] {name} seed {seed}: setup_s {setup_s:.3f}, {steps} steps "
          f"in {window_s:.4f} s, calibration step_s {step_s}, compiles "
          f"{clock.count} ({clock.seconds:.1f} s), {window_compiles} in the "
          f"window, check {time.perf_counter() - t_check:.1f} s",
          file=sys.stderr)
    for what, keys in (("grad", None), ("change", check.moved(ref))):
        for gap, k in check.leaf_gaps(prog[what], ref[what], keys)[:3]:
            print(f"[bench] {what} {k}: program {prog[what][k]!r} reference "
                  f"{ref[what][k]!r} gap {gap!r}", file=sys.stderr)
    print(f"[bench] program {json.dumps(prog['loss'])} entropy "
          f"{prog['entropy']}; reference {json.dumps(ref['loss'])} entropy "
          f"{ref['entropy']}", file=sys.stderr)
    return result


def reduce_trace(log_dir: str) -> dict:
    """The window of the trace in ``log_dir``, reduced: busy and idle time,
    step modules and breakdown (``xplane.reduce_window``), and the window
    by scope, op, kernel and host span (``xplane.reduce_scopes``)."""
    import xplane
    path = xplane.find_xplane(log_dir)
    return reduce_loaded(xplane.load(path), xplane.hlo_instructions(path))


def reduce_loaded(tr, instrs: dict) -> dict:
    """``reduce_trace`` of a loaded trace and its programs' instructions."""
    import xplane
    found = xplane.span(tr, WINDOW_SPAN)
    if found is None:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN} span")
    line, lo, hi = found
    return {**xplane.reduce_window(tr, lo, hi, line),
            **xplane.reduce_scopes(tr, instrs, lo, hi)}
