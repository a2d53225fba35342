#!/usr/bin/env python3
"""The traced window's device time by layer of the train step, and the host
loop's own time, from the program's ``edgc.*`` scopes and spans.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs one traced window as ``bench/run.py --trace 1`` does and prints, on the
line before the result, the per-step device self time of each layer
(``forward_ms``, ``backward_ms``, ``compress_ms``, ``entropy_ms``,
``optimizer_ms``, ``step_unscoped_ms``; null where the layer has no op),
``host_step_ms``, the window's ``edgc.*`` host spans by name, its longest
device idle gaps named by the ``edgc.*`` span that covered each, the
unscoped ops that take the most time per step, and ``step_device_ms`` as
the harness reads it.

An op's layer comes from its ``op_name`` path, which the program's
``jax.named_scope``s write into the compiled program's metadata: the
innermost known scope wins; ``transpose(`` above ``edgc.forward`` (the
gradient of the loss, remat's recompute included) or any path under
``edgc.backward`` is backward; of a ``;``-joined fusion path the first
counts; anything else is unscoped. The scope names are written out here,
not imported, so that this reads a trace of any commit: where the program
has no scopes every op is unscoped and there is no ``edgc.*`` span.
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent

LAYERS = ("forward", "backward", "compress", "entropy", "optimizer")
UNSCOPED = "unscoped"
SCOPE = re.compile(r"(?<![\w.])edgc\.(" + "|".join(LAYERS) + r")(?![\w.])")
STEP_SPAN = "edgc.step"
HOST_SPANS = (STEP_SPAN, "edgc.flush", "edgc.window_end", "edgc.checkpoint")
METADATA_PLANE = "/host:metadata"


def layer_of(path: str) -> str:
    """The train-step layer that an op's ``op_name`` path belongs to."""
    path = path.split(";")[0]
    found = list(SCOPE.finditer(path))
    if not found:
        return UNSCOPED
    last = found[-1]
    if last.group(1) == "forward" and "transpose(" in path[:last.start()]:
        return "backward"
    return last.group(1)


def _varint(buf, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return n, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message, in order: varints
    as ints, length-delimited values as memoryviews."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _repeated(buf, number: int):
    return (v for n, v in _fields(buf) if n == number)


def hlo_op_names(path: str) -> dict[int, dict[str, str]]:
    """Program id -> {instruction name: ``op_name`` path}, from the HLO
    that the trace keeps in its metadata plane.

    ``ProfileData`` does not show a plane's event metadata, where the HLO
    sits, so this walks the file's protobuf fields: ``XSpace.planes`` (1) >
    ``XPlane.name`` (2) and ``.event_metadata`` (4; key 1 the program id,
    value 2) > ``XEventMetadata.stats`` (5) > ``XStat.bytes_value`` (6), an
    ``HloProto`` > ``.hlo_module`` (1) > ``HloModuleProto.computations``
    (3) > ``.instructions`` (2) > ``HloInstructionProto.name`` (1) and
    ``.metadata`` (7) > ``OpMetadata.op_name`` (2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[int, dict[str, str]] = {}
    for plane in _repeated(space, 1):
        fields = list(_fields(plane))
        if bytes(next((v for n, v in fields if n == 2), b"")) != \
                METADATA_PLANE.encode():
            continue
        for n, entry in fields:
            if n != 4:
                continue
            kv = dict(_fields(entry))
            names = out.setdefault(kv.get(1, 0), {})
            for stat in _repeated(kv.get(2, b""), 5):
                for hlo in _repeated(stat, 6):
                    for module in _repeated(hlo, 1):
                        for comp in _repeated(module, 3):
                            for instr in _repeated(comp, 2):
                                ins = dict(_fields(instr))
                                meta = dict(_fields(ins.get(7, b"")))
                                names[bytes(ins.get(1, b"")).decode()] = \
                                    bytes(meta.get(2, b"")).decode()
    return out


def op_paths(ops: list[tuple[str, int, int]],
             modules: list[tuple[str, int, int]],
             names: dict[int, dict[str, str]]) -> list[tuple[str, int, int]]:
    """One device's op events with each name replaced by its ``op_name``
    path: the op's program is the module execution (``jit_step(12)``,
    program 12) that holds its start; an op whose path is not known keeps
    its instruction name. Program ids are 64-bit: unsigned in the metadata
    plane, so a name's id is read modulo 2**64."""
    import xplane
    spans = sorted((s, e, int(m.group(1)) % (1 << 64))
                   for n, s, e in modules
                   if (m := re.search(r"\((-?\d+)\)$", n)))
    starts = [s for s, _, _ in spans]
    out = []
    for name, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        pid = spans[k][2] if k >= 0 and s < spans[k][1] else None
        instr = xplane.op_name(name)
        out.append((names.get(pid, {}).get(instr) or instr, s, e))
    return out


def load_op_paths(tr, path: str) -> dict[str, list[tuple[str, int, int]]]:
    """Device plane -> ``(op_name path, start_ns, end_ns)`` of its ops, for
    the trace ``tr`` that ``xplane.load`` read from ``path``."""
    names = hlo_op_names(path)
    return {plane: op_paths(events, tr.modules.get(plane, []), names)
            for plane, events in tr.ops.items()}


def device_layers(ops: dict[str, list[tuple[str, int, int]]], lo: int,
                  hi: int) -> dict[str, float]:
    """Self time (ns) of each layer's ops inside ``[lo, hi)``, mean over
    devices. The layers and ``unscoped`` add up to the window's op self
    time."""
    import xplane
    out: dict[str, float] = defaultdict(float)
    for events in ops.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        for path, t in xplane.self_times(inside):
            out[layer_of(path)] += t / len(ops)
    return dict(out)


def unscoped_top(ops: dict[str, list[tuple[str, int, int]]], lo: int,
                 hi: int, top: int = 10) -> list[tuple[str, float]]:
    """The unscoped ops that take the most self time (ns) inside
    ``[lo, hi)``, mean over devices: by ``op_name`` path, or by
    instruction kind where the compiled program gave the op none."""
    import xplane
    out: dict[str, float] = defaultdict(float)
    for events in ops.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        for path, t in xplane.self_times(inside):
            if layer_of(path) == UNSCOPED:
                key = path if "/" in path else xplane.op_kind(path)
                out[key] += t / len(ops)
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


def host_spans(host: dict[str, list[tuple[str, int, int]]], lo: int,
               hi: int) -> list[tuple[str, int, int]]:
    """The ``edgc.*`` host spans whose midpoint lies in ``[lo, hi)``, in
    order of start."""
    return sorted((ev for events in host.values() for ev in events
                   if ev[0] in HOST_SPANS and lo <= (ev[1] + ev[2]) // 2 < hi),
                  key=lambda ev: ev[1])


def step_self_ns(spans: list[tuple[str, int, int]]) -> list[int]:
    """Each ``edgc.step`` span's duration less the other ``edgc.*`` spans
    nested in it (flush, window end, checkpoint)."""
    steps = [ev for ev in spans if ev[0] == STEP_SPAN]
    inner = [ev for ev in spans if ev[0] != STEP_SPAN]
    return [e - s - sum(ie - is_ for _, is_, ie in inner
                        if s <= is_ and ie <= e)
            for _, s, e in steps]


def idle_gaps(ops: dict[str, list[tuple[str, int, int]]],
              spans: list[tuple[str, int, int]], lo: int, hi: int,
              top: int = 10) -> list[tuple[str, int]]:
    """The longest gaps (ns) in which a device runs no op inside
    ``[lo, hi)``, each named by the innermost ``edgc.*`` host span that
    covers its midpoint."""
    import xplane
    found = []
    for events in ops.values():
        merged = xplane.clip(xplane.merge((s, e) for _, s, e in events),
                             lo, hi)
        found.extend(xplane.gaps(merged, lo, hi))
    found.sort(key=lambda g: g[0] - g[1])
    return [(xplane.host_doing(spans, (s + e) // 2), e - s)
            for s, e in found[:top]]


def summary(ops, host, lo: int, hi: int, steps: int) -> dict:
    """The per-step readings of one window."""
    layers = device_layers(ops, lo, hi)
    spans = host_spans(host, lo, hi)
    own = step_self_ns(spans)
    per_step = lambda k: (layers[k] / steps / 1e6 if k in layers else None)
    out = {f"{k}_ms": per_step(k) for k in LAYERS}
    out["step_unscoped_ms"] = per_step(UNSCOPED)
    out["host_step_ms"] = sum(own) / len(own) / 1e6 if own else None
    counts: dict[str, int] = defaultdict(int)
    for name, _, _ in spans:
        counts[name] += 1
    out["host_spans"] = dict(counts)
    out["idle_gaps_ms"] = [(n, t / 1e6)
                           for n, t in idle_gaps(ops, spans, lo, hi)]
    out["unscoped_top_ms"] = [(n, t / steps / 1e6)
                              for n, t in unscoped_top(ops, lo, hi)]
    return out


def with_scopes(harness) -> None:
    """Make ``harness.run_cell``, when traced, also print the ``scopes``
    line of its window (before the result line)."""
    import xplane
    seen: dict = {}
    reduce_trace, run_cell = harness.reduce_trace, harness.run_cell

    def reduce_with_scopes(log_dir):
        path = xplane.find_xplane(log_dir)
        tr = xplane.load(path)
        _, seen["lo"], seen["hi"] = xplane.span(tr, harness.WINDOW_SPAN)
        seen["ops"], seen["host"] = load_op_paths(tr, path), tr.host
        return reduce_trace(log_dir)

    def run_and_sum(name, *a, **k):
        result = run_cell(name, *a, **k)
        if not seen:
            return result
        out = summary(seen["ops"], seen["host"], seen["lo"], seen["hi"],
                      result["attempted"])
        out["step_device_ms"] = result["metrics"].get(
            "step_device_ms", {}).get("value")
        print(json.dumps({"workload": name, "scopes": out}), flush=True)
        return result

    harness.reduce_trace = reduce_with_scopes
    harness.run_cell = run_and_sum


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness
    import run
    with_scopes(harness)
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
