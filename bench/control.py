#!/usr/bin/env python3
"""The readings that a cell's limits are set from, on the chip at its size.

    python bench/control.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 11,12,13 [--seconds 1]

For each of ``--seeds``: one whole run of the cell (``harness.run_cell``, a
short window) and its numbers against the reference, the lower readings.
For each of ``--control-seeds``: the reference put in the program's place,
``control`` at float8 (the precision below the configuration's bfloat16),
``half_batch`` on the first half of each batch and ``unchanged`` with every
update skipped, each against the float32 reference: the upper readings. One JSON line per reading on standard output.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def half_rows(batch: dict) -> dict:
    return {k: v[: v.shape[0] // 2] for k, v in batch.items()}


def upper_readings(model: dict, wl: dict, seed: int) -> dict:
    """The control's and the half-batch fault's gaps to the reference."""
    import check
    import harness
    import reference
    batches = harness.make_ring(model, wl, seed)[:harness.CHECKED_STEPS]
    seed32 = harness.program_seed(seed)
    ref = reference.run(model, wl, seed32, batches)
    out = {}
    for name, kw in (("control", {"precision": "fp8"}),
                     ("half_batch", {}), ("unchanged", {"update": False})):
        feed = [half_rows(b) for b in batches] if name == "half_batch" \
            else batches
        got = reference.run(model, wl, seed32, feed, **kw)
        if wl["measure_entropy"] is False:
            got["entropy"] = None
        out[name] = check.readings(got, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    import jax
    import harness
    import run
    run.setup_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 1
    wl, cfg = harness.load_cell(args.workload)
    for s in [int(x) for x in args.seeds.split(",") if x]:
        t = time.perf_counter()
        res = harness.run_cell(args.workload, wl, cfg, s, args.seconds, False,
                               devices, t)
        print(json.dumps({"seed": s, "kind": "program",
                          "correct": res["correct"],
                          "values": {k: v["value"]
                                     for k, v in res["check"].items()},
                          "metrics": res["metrics"]}), flush=True)
    for s in [int(x) for x in args.control_seeds.split(",") if x]:
        for kind, values in upper_readings(cfg["model"], wl, s).items():
            print(json.dumps({"seed": s, "kind": kind, "values": values}),
                  flush=True)
    print(f"control: {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
