#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. With ``--trace 0`` the result's metrics are the
cell's end-to-end metrics, timed with the profiler off; with ``--trace 1``
the window runs under the JAX profiler and the metrics are the cell's
per-layer ones, reduced from the trace. Either way the run checks the
program against the plain reference and prints each number compared beside
its limit, as its last lines on standard error and under ``check`` in the
result. It exits non-zero, with no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def setup_cache(jax) -> None:
    """Persistent compilation cache at a fixed path in the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every compile kept."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program (src/repro) in {ROOT}")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness
    try:
        wl, cfg = harness.load_cell(args.workload)
    except FileNotFoundError as e:
        return fail(f"unknown cell {args.workload!r}: {e}")

    import jax
    setup_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < wl["chips"]:
        return fail(f"cell {args.workload} needs {wl['chips']} chips, JAX "
                    f"found {len(devices)}")

    result = harness.run_cell(args.workload, wl, cfg, args.seed, args.seconds,
                              bool(args.trace), devices, T_START)
    for name, row in result["check"].items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
