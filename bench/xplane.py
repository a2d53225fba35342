"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Pure interval arithmetic over ``(start_ns, end_ns)`` pairs, and one loader
that reads the trace with ``jax.profiler.ProfileData``:

* device op intervals: the ``XLA Ops`` line of every ``/device:`` plane;
* device module executions: the ``XLA Modules`` line of those planes;
* host spans: every line of the ``/host:`` planes (the benchmark's own
  ``TraceAnnotation``s and, where the profiler records them, Python frames).
* the compiled programs' HLO, which the ``/host:metadata`` plane keeps: each
  instruction's ``op_name`` path and, for a Pallas kernel, the name the
  program gave it (``hlo_instructions``).

Busy time is the union of a device's op intervals inside the window; idle
share is one minus busy over the window. A collective's exposed time is the
part of its intervals during which no other op runs on that device.

``reduce_scopes`` splits the window's op self time by the program's
``edgc.<name>`` scopes, by op name (Pallas kernels under their own names)
and reads the ``edgc.*`` host spans. An op's scope comes from its
``op_name`` path, which the program's ``jax.named_scope``s write into the
compiled program's metadata: the innermost ``edgc.<name>`` wins, whatever
the name; of a ``;``-joined fusion path the first counts. An op is in the
backward pass where ``transpose(`` or ``edgc.backward`` lies above its
scope: ``edgc.forward`` there is ``backward`` (the gradient of the loss,
remat's recompute included), another scope ``<name>`` is
``<name>.backward``. An op under no scope is ``unscoped``. The names are
matched as text, not imported, so that this reads a trace of any commit.
"""
from __future__ import annotations

import base64
import bisect
import dataclasses
import glob
import json
import os
import re
from collections import defaultdict

Interval = tuple[int, int]
Event = tuple[str, int, int]        # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def merge(intervals) -> list[Interval]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted((int(s), int(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def clip(merged, lo: int, hi: int) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list[Interval]:
    """``merge(a)`` minus ``merge(b)``."""
    a, b = merge(a), merge(b)
    out: list[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo: int, hi: int) -> list[Interval]:
    """The parts of ``[lo, hi)`` that no interval of ``merged`` covers."""
    return subtract([(lo, hi)], merged)


def exposed(ops: list[Event], pattern: str) -> int:
    """Nanoseconds in which an op matching ``pattern`` runs and no other op
    does: the collective time that compute does not hide."""
    rx = re.compile(pattern)
    coll = [(s, e) for n, s, e in ops if rx.search(n)]
    other = [(s, e) for n, s, e in ops if not rx.search(n)]
    return total(subtract(coll, other))


def base_name(name: str) -> str:
    """A module's name without the program id XLA appends, ``jit_f(12)``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(hlo: str) -> str:
    """An op event's instruction name from its HLO text, ``%fusion.12 = ...``."""
    m = re.match(r"%?([\w.\-]+)", hlo)
    return m.group(1) if m else hlo


def op_kind(name: str) -> str:
    """The instruction name without its number: ``fusion.12`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", name)


def self_times(events: list[Event]) -> list[tuple[str, int]]:
    """Each event's duration less that of the events nested in it on the
    same line (a ``while`` op holds its body's ops)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = {i: events[i][2] - events[i][1] for i in order}
    stack: list[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], own[i]) for i in order]


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]          # device plane -> op events
    modules: dict[str, list[Event]]      # device plane -> module executions
    host: dict[str, list[Event]]         # host line -> spans


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = defaultdict(list)
    modules: dict[str, list[Event]] = defaultdict(list)
    host: dict[str, list[Event]] = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name].extend(
                        (op_name(ev.name), int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events)
                elif line.name == MODULES_LINE:
                    modules[plane.name].extend(
                        (ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host[f"{plane.name}/{line.name}"].extend(
                    (ev.name, int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in line.events)
    return Trace(dict(ops), dict(modules), dict(host))


def span(trace: Trace, name: str) -> tuple[str, int, int] | None:
    """The host line and interval of the first span called ``name``."""
    for line, events in trace.host.items():
        for n, s, e in events:
            if n == name:
                return line, s, e
    return None


def host_doing(events: list[Event], t: int) -> str:
    """The innermost of ``events`` (one host thread's spans) covering ``t``."""
    best: Event | None = None
    for ev in events:
        if ev[1] <= t < ev[2] and (best is None
                                   or ev[2] - ev[1] < best[2] - best[1]):
            best = ev
    return best[0] if best else "no host span"


def reduce_window(trace: Trace, lo: int, hi: int, host_line: str) -> dict:
    """Busy/idle, module durations and the breakdown inside ``[lo, hi)``;
    idle gaps are named by what ``host_line``'s thread was doing in them.
    ``busy_ns`` is ``None`` where no device op was traced."""
    window_ns = hi - lo
    busy, op_time = [], defaultdict(int)
    longest: list[tuple[int, Interval]] = []
    for plane, events in trace.ops.items():
        merged = clip(merge((s, e) for _, s, e in events), lo, hi)
        busy.append(total(merged))
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        for n, t in self_times(inside):
            op_time[op_kind(n)] += t
        longest.extend((e - s, (s, e)) for s, e in gaps(merged, lo, hi))
    modules: dict[str, list[int]] = defaultdict(list)
    for events in trace.modules.values():
        for n, s, e in events:
            if lo <= (s + e) // 2 < hi:
                modules[base_name(n)].append(e - s)
    longest.sort(reverse=True)
    busy_ns = sum(busy) / len(busy) if busy and sum(busy) > 0 else None
    n_dev = max(1, len(busy))
    return {
        "window_ns": window_ns,
        "busy_ns": busy_ns,
        "devices": len(busy),
        "modules": dict(modules),
        "device_ops": [(n, t / n_dev) for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [(host_doing(trace.host[host_line], (s + e) // 2), d)
                      for d, (s, e) in longest[:10]],
    }


# ------------------------------------------------- scopes, kernels, spans

FORWARD, BACKWARD, UNSCOPED = "forward", "backward", "unscoped"
# The train step's device scopes and host spans as the program writes them
# today; any other ``edgc.<name>`` is read the same way.
LAYERS = (FORWARD, BACKWARD, "compress", "entropy", "optimizer")
STEP_SPAN = "edgc.step"
HOST_SPANS = (STEP_SPAN, "edgc.flush", "edgc.window_end", "edgc.checkpoint")
SPAN_PREFIX = "edgc."
SCOPE = re.compile(r"(?<![\w.])edgc\.([A-Za-z_][\w.]*)(?![\w.])")
METADATA_PLANE = "/host:metadata"
MOSAIC_TARGET = b"tpu_custom_call"


def scope_of(path: str, names=None) -> str:
    """The scope that an op's ``op_name`` path belongs to (module docstring);
    with ``names``, only those scopes count."""
    path = path.split(";")[0]
    found = [m for m in SCOPE.finditer(path)
             if names is None or m.group(1) in names]
    if not found:
        return UNSCOPED
    *outer, last = found
    name = last.group(1)
    grad = ("transpose(" in path[:last.start()]
            or any(m.group(1) == BACKWARD for m in outer))
    if name == FORWARD and grad:
        return BACKWARD
    return f"{name}.{BACKWARD}" if grad and name != BACKWARD else name


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


def mosaic_kernel(backend_config: bytes) -> str | None:
    """The name of the Pallas kernel in a ``tpu_custom_call``'s backend
    config: the ``sym_name`` of the serialized Mosaic module it carries
    (the kernel function's name, or the ``name=`` given to
    ``pallas_call``); ``None`` where it cannot be read."""
    from jaxlib.mlir import ir
    try:
        body = json.loads(bytes(backend_config))["custom_call_config"]["body"]
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            return ir.StringAttr(module.operation.attributes["sym_name"]).value
    except (ValueError, KeyError, TypeError, ir.MLIRError):
        return None         # not a Mosaic module this can read


def hlo_instructions(path: str) -> dict[int, dict[str, tuple[str, str | None]]]:
    """Program id -> {instruction name: (``op_name`` path, Pallas kernel
    name or ``None``)}, from the HLO that the trace keeps in its metadata
    plane.

    ``ProfileData`` does not show a plane's event metadata, where the HLO
    sits, so this walks the file's protobuf fields: ``XSpace.planes`` (1) >
    ``XPlane.name`` (2) and ``.event_metadata`` (4; key 1 the program id,
    value 2) > ``XEventMetadata.stats`` (5) > ``XStat.bytes_value`` (6), an
    ``HloProto`` > ``.hlo_module`` (1) > ``HloModuleProto.computations``
    (3) > ``.instructions`` (2) > ``HloInstructionProto.name`` (1),
    ``.metadata`` (7) > ``OpMetadata.op_name`` (2), ``.custom_call_target``
    (28) and ``.backend_config`` (43)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[int, dict[str, tuple[str, str | None]]] = {}
    kernels: dict[bytes, str | None] = {}
    for plane in _repeated(space, 1):
        fields = list(_fields(plane))
        if bytes(next((v for n, v in fields if n == 2), b"")) != \
                METADATA_PLANE.encode():
            continue
        for n, entry in fields:
            if n != 4:
                continue
            kv = dict(_fields(entry))
            table = out.setdefault(kv.get(1, 0), {})
            for stat in _repeated(kv.get(2, b""), 5):
                for hlo in _repeated(stat, 6):
                    for module in _repeated(hlo, 1):
                        for comp in _repeated(module, 3):
                            for instr in _repeated(comp, 2):
                                ins = dict(_fields(instr))
                                meta = dict(_fields(ins.get(7, b"")))
                                kernel = None
                                if bytes(ins.get(28, b"")) == MOSAIC_TARGET:
                                    cfg = bytes(ins.get(43, b""))
                                    if cfg not in kernels:
                                        kernels[cfg] = mosaic_kernel(cfg)
                                    kernel = kernels[cfg]
                                table[bytes(ins.get(1, b"")).decode()] = (
                                    bytes(meta.get(2, b"")).decode(), kernel)
    return out


def hlo_op_names(path: str) -> dict[int, dict[str, str]]:
    """Program id -> {instruction name: ``op_name`` path}
    (``hlo_instructions``)."""
    return {pid: {k: p for k, (p, _) in table.items()}
            for pid, table in hlo_instructions(path).items()}


def op_paths(ops: list[Event], modules: list[Event],
             names: dict[int, dict[str, str]]) -> list[Event]:
    """One device's op events with each name replaced by its entry in
    ``names`` (an ``op_name`` path, or a kernel name): the op's program is
    the module execution (``jit_step(12)``, program 12) that holds its
    start; an op that ``names`` does not know keeps its instruction name.
    Program ids are 64-bit: unsigned in the metadata plane, so a name's id
    is read modulo 2**64."""
    spans = sorted((s, e, int(m.group(1)) % (1 << 64))
                   for n, s, e in modules
                   if (m := re.search(r"\((-?\d+)\)$", n)))
    starts = [s for s, _, _ in spans]
    out = []
    for name, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        pid = spans[k][2] if k >= 0 and s < spans[k][1] else None
        instr = op_name(name)
        out.append((names.get(pid, {}).get(instr) or instr, s, e))
    return out


def self_time_by(ops: dict[str, list[Event]], key, lo: int, hi: int
                 ) -> dict[str, list[float]]:
    """``key(name)`` -> [self time (ns), executions] of the ops inside
    ``[lo, hi)``, each the mean over devices."""
    out: dict[str, list[float]] = defaultdict(lambda: [0.0, 0.0])
    for events in ops.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        for name, t in self_times(inside):
            row = out[key(name)]
            row[0] += t / len(ops)
            row[1] += 1 / len(ops)
    return dict(out)


def device_layers(ops: dict[str, list[Event]], lo: int, hi: int
                  ) -> dict[str, float]:
    """Self time (ns) of each scope's ops (``ops``: op events named by
    ``op_name`` path) inside ``[lo, hi)``, mean over devices. The scopes
    and ``unscoped`` add up to the window's op self time."""
    return {k: t for k, (t, _) in self_time_by(ops, scope_of, lo,
                                                hi).items()}


def unscoped_top(ops: dict[str, list[Event]], lo: int, hi: int,
                 top: int | None = 10) -> list[tuple[str, float]]:
    """The unscoped ops that take the most self time (ns) inside
    ``[lo, hi)``, mean over devices: by ``op_name`` path, or by
    instruction kind where the compiled program gave the op none."""
    def key(path):
        if scope_of(path) != UNSCOPED:
            return None
        return path if "/" in path else op_kind(path)
    got = self_time_by(ops, key, lo, hi)
    got.pop(None, None)
    return sorted(((k, t) for k, (t, _) in got.items()),
                  key=lambda kv: -kv[1])[:top]


def host_spans(host: dict[str, list[Event]], lo: int, hi: int
               ) -> list[Event]:
    """The ``edgc.*`` host spans whose midpoint lies in ``[lo, hi)``, in
    order of start."""
    return sorted((ev for events in host.values() for ev in events
                   if ev[0].startswith(SPAN_PREFIX)
                   and lo <= (ev[1] + ev[2]) // 2 < hi),
                  key=lambda ev: ev[1])


def step_self_ns(spans: list[Event]) -> list[int]:
    """Each ``edgc.step`` span's duration less the other ``edgc.*`` spans
    nested in it (flush, window end, checkpoint)."""
    steps = [ev for ev in spans if ev[0] == STEP_SPAN]
    inner = [ev for ev in spans if ev[0] != STEP_SPAN]
    return [e - s - sum(ie - is_ for _, is_, ie in inner
                        if s <= is_ and ie <= e)
            for _, s, e in steps]


def idle_gaps(ops: dict[str, list[Event]], spans: list[Event], lo: int,
              hi: int, top: int = 10) -> list[tuple[str, int]]:
    """The longest gaps (ns) in which a device runs no op inside
    ``[lo, hi)``, each named by the innermost ``edgc.*`` host span that
    covers its midpoint."""
    found = []
    for events in ops.values():
        found.extend(gaps(clip(merge((s, e) for _, s, e in events), lo, hi),
                          lo, hi))
    found.sort(key=lambda g: g[0] - g[1])
    return [(host_doing(spans, (s + e) // 2), e - s) for s, e in found[:top]]


def reduce_scopes(trace: Trace, instrs: dict, lo: int, hi: int) -> dict:
    """The window ``[lo, hi)`` by scope, op and host span (``instrs``: what
    ``hlo_instructions`` read from the same trace). Times are ns, each the
    mean over devices:

    * ``scopes``: scope -> op self time, ``unscoped`` included; the values
      add up to the op self time of the window;
    * ``ops``: op name -> {``ns``, ``calls``}: a Pallas kernel under its
      own name, any other op under its instruction kind (``fusion``);
    * ``unscoped_ops``: the unscoped ops' self time by ``op_name`` path, or
      by instruction kind where the op has none;
    * ``host_spans``: ``edgc.*`` span name -> {``ns``, ``calls``};
    * ``host_step_ns``: each ``edgc.step``'s own time, its nested
      ``edgc.*`` spans left out;
    * ``span_gaps``: the longest device idle gaps, each named by the
      innermost ``edgc.*`` span over it."""
    names = {pid: {k: p for k, (p, _) in t.items()} for pid, t in
             instrs.items()}
    kernels = {pid: {k: kn for k, (_, kn) in t.items() if kn} for pid, t in
               instrs.items()}
    paths, kinds = {}, {}
    for plane, events in trace.ops.items():
        modules = trace.modules.get(plane, [])
        paths[plane] = op_paths(events, modules, names)
        kinds[plane] = [(op_kind(k), s, e) for k, s, e in
                        op_paths(events, modules, kernels)]
    spans = host_spans(trace.host, lo, hi)
    by_span: dict[str, dict[str, float]] = {}
    for name, s, e in spans:
        row = by_span.setdefault(name, {"ns": 0, "calls": 0})
        row["ns"] += e - s
        row["calls"] += 1
    return {
        "scopes": device_layers(paths, lo, hi),
        "ops": {k: {"ns": t, "calls": c} for k, (t, c) in
                self_time_by(kinds, lambda k: k, lo, hi).items()},
        "unscoped_ops": dict(unscoped_top(paths, lo, hi, None)),
        "host_spans": by_span,
        "host_step_ns": step_self_ns(spans),
        "span_gaps": idle_gaps(trace.ops, spans, lo, hi),
    }


def scope_ms(reduced: dict | None, scope: str, steps: int) -> float | None:
    """Device self time per step (ms) of ``scope`` in a reduced window;
    ``None`` where the window has no trace or the scope no op."""
    if reduced is None or scope not in reduced["scopes"]:
        return None
    return reduced["scopes"][scope] / steps / 1e6


def host_step_ms(reduced: dict | None) -> float | None:
    """The host loop's own time per step (ms): the mean ``edgc.step`` span
    less its nested ``edgc.*`` spans; ``None`` where there is none."""
    if reduced is None or not reduced["host_step_ns"]:
        return None
    own = reduced["host_step_ns"]
    return sum(own) / len(own) / 1e6
