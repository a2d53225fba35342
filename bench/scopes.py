#!/usr/bin/env python3
"""The traced window's device time by layer of the train step, and the host
loop's own time, from the program's ``edgc.*`` scopes and spans.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs one traced window as ``bench/run.py --trace 1`` does and prints, on the
line before the result, what the harness reduced from that window's trace
(``xplane.reduce_scopes``), per step: the device self time of every
``edgc.<name>`` scope as ``<name>_ms`` (``forward_ms``, ``backward_ms``,
``compress_ms``, ``entropy_ms``, ``optimizer_ms``, and any scope the
program adds) and of the rest as ``step_unscoped_ms``, ``host_step_ms``,
the window's ``edgc.*`` host spans by name, its longest device idle gaps
named by the ``edgc.*`` span that covered each, the unscoped ops and the
Pallas kernels that take the most time, and ``step_device_ms``.

The classification lives in ``xplane.py`` (its module docstring); this file
only reads and prints what the harness reduced.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from xplane import (HOST_SPANS, LAYERS, UNSCOPED, device_layers,  # noqa: E402,F401
                    hlo_op_names, host_spans, host_step_ms, idle_gaps,
                    op_paths, scope_ms, scope_of, step_self_ns, unscoped_top)


def layer_of(path: str) -> str:
    """Which of the train step's five layers (``LAYERS``) an op's
    ``op_name`` path belongs to, or ``unscoped``."""
    return scope_of(path, LAYERS)


def summary(reduced: dict, steps: int) -> dict:
    """The per-step readings of one reduced window (``harness.reduce_trace``)."""
    out = {("step_unscoped_ms" if k == UNSCOPED else f"{k}_ms"):
           scope_ms(reduced, k, steps) for k in reduced["scopes"]}
    out["host_step_ms"] = host_step_ms(reduced)
    out["host_spans"] = {k: v["calls"] for k, v in
                         reduced["host_spans"].items()}
    out["idle_gaps_ms"] = [(n, t / 1e6) for n, t in reduced["span_gaps"]]
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]
    out["unscoped_top_ms"] = [(n, t / steps / 1e6) for n, t in
                              top(reduced["unscoped_ops"])]
    out["ops_top_ms"] = [(n, t / steps / 1e6) for n, t in
                         top({k: v["ns"] for k, v in reduced["ops"].items()})]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import harness
    import run

    def show(ctx) -> None:
        out = summary(ctx.reduced, ctx.steps)
        out["step_device_ms"] = harness.read_metric("step_device_ms", ctx)
        print(json.dumps({"workload": args.workload, "scopes": out}),
              flush=True)

    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"],
                    observe=show)


if __name__ == "__main__":
    sys.exit(main())
