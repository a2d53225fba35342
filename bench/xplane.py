"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Pure interval arithmetic over ``(start_ns, end_ns)`` pairs, and one loader
that reads the trace with ``jax.profiler.ProfileData``:

* device op intervals: the ``XLA Ops`` line of every ``/device:`` plane;
* device module executions: the ``XLA Modules`` line of those planes;
* host spans: every line of the ``/host:`` planes (the benchmark's own
  ``TraceAnnotation``s and, where the profiler records them, Python frames).

Busy time is the union of a device's op intervals inside the window; idle
share is one minus busy over the window. A collective's exposed time is the
part of its intervals during which no other op runs on that device.
"""
from __future__ import annotations

import dataclasses
import glob
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
