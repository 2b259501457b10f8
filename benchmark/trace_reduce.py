"""From a profiler trace (`*.xplane.pb`) to device busy time, idle share, the
operations that took most time and the idle gaps by what the host was doing.

The interval arithmetic takes plain (start, end) pairs in seconds and is
checked on hand-made intervals by `selftest.py`; only `read_trace` touches
JAX.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"            # one event per HLO op, nested under while/call
MODULES_LINE = "XLA Modules"    # one event per program run
MARK = "bench/"                 # the benchmark's own TraceAnnotations
NAMED_GAPS = 200                # the longest gaps get the host's doing


def short_name(op: str) -> str:
    """'%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)' -> 'fusion.3 f32[8,128]':
    the op and the shape it writes, without the operands."""
    head, _, rest = op.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return (head.lstrip("%") + (" " + shape if shape else ""))[:120]


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def busy_seconds(merged, lo, hi) -> float:
    return sum(e - s for s, e in clip(merged, lo, hi))


def gaps(merged, lo, hi):
    """The idle intervals inside [lo, hi]."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_seconds(events):
    """Seconds per name with nested children taken out of their parents:
    events are (name, start, end) on ONE line, where a `while` or a call
    encloses the ops of its body."""
    total, stack = defaultdict(float), []   # [name, end, start, child_s]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, child = stack.pop()
            total[name] += (end - start) - child
            if stack:
                stack[-1][3] += end - start

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        stack.append([name, e, s, 0.0])
    close(float("inf"))
    return dict(total)


def enclosing(marks, t):
    """Name of the innermost (name, start, end) mark that holds time t."""
    best = None
    for name, s, e in marks:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside " + MARK + "*"


def name_gaps(idle, marks, host_events):
    """[(name, seconds)] summed by name. A gap is named by the benchmark mark
    that holds its middle; the NAMED_GAPS longest also by the host event
    (not a mark) that overlaps it most."""
    host = sorted(host_events, key=lambda ev: ev[1])
    starts = [ev[1] for ev in host]
    longest = set(sorted(idle, key=lambda g: g[0] - g[1])[:NAMED_GAPS])
    summed = defaultdict(float)
    for s, e in idle:
        name = enclosing(marks, 0.5 * (s + e))
        if (s, e) in longest and host:
            best, best_overlap = None, 0.0
            # host events are short next to a fit; look back a bounded way
            for ev in host[max(0, bisect.bisect_left(starts, s) - 64):
                           bisect.bisect_right(starts, e)]:
                overlap = min(e, ev[2]) - max(s, ev[1])
                if overlap > best_overlap:
                    best, best_overlap = ev[0], overlap
            if best is not None:
                name = f"{name} | {best}"
        summed[name] += e - s
    return sorted(summed.items(), key=lambda kv: -kv[1])


def read_trace(trace_dir: str):
    """{device planes: {line: [(name, start_s, end_s)]}, host: [...]}."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        is_device = DEVICE_PLANE.match(plane.name)
        if not (is_device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            events = [(short_name(ev.name) if is_device else ev.name,
                       ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ev in line.events]
            if is_device:
                devices.setdefault(plane.name, {})[line.name] = events
            else:
                host.extend(events)
    return {"devices": devices, "host": host, "path": paths[-1]}


def reduce_trace(trace_dir: str, top: int = 10):
    """The run record's `trace` entry, or None where no device op was traced.

    Busy time is the union of the events of ONE line of each device plane,
    the ops (falling back to the program runs), averaged over the device
    planes that ran anything. The window is from the first benchmark mark's
    start to the last one's end."""
    trace = read_trace(trace_dir)
    if trace is None:
        return None
    marks = [ev for ev in trace["host"] if ev[0].startswith(MARK)]
    host = [ev for ev in trace["host"] if not ev[0].startswith(MARK)]
    per_device, all_ops = [], []
    for plane, lines in sorted(trace["devices"].items()):
        events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if events:
            per_device.append(merge((s, e) for _, s, e in events))
            all_ops.extend(events)
    if not per_device:
        return None
    if marks:
        lo, hi = min(m[1] for m in marks), max(m[2] for m in marks)
    else:
        lo = min(m[0][0] for m in per_device)
        hi = max(m[-1][1] for m in per_device)
    busy = sum(busy_seconds(m, lo, hi) for m in per_device) / len(per_device)
    per_mark = [{"name": name, "start": s, "end": e,
                 "busy_s": sum(busy_seconds(m, s, e) for m in per_device)
                 / len(per_device)} for name, s, e in sorted(
                     marks, key=lambda m: m[1])]
    ops = sorted(self_seconds([ev for ev in all_ops
                               if ev[2] > lo and ev[1] < hi]).items(),
                 key=lambda kv: -kv[1])
    idle = name_gaps(gaps(per_device[0], lo, hi), marks, host)
    return {
        "busy_s": busy, "window_s": hi - lo,
        "idle_share": 1.0 - busy / (hi - lo),
        "marks": per_mark,
        "device_ops": [[k, v / len(per_device)] for k, v in ops[:top]],
        "idle_gaps": [[k, v] for k, v in idle[:top]],
        "lines": {p: {k: len(v) for k, v in ls.items()}
                  for p, ls in trace["devices"].items()},
        "path": trace["path"],
    }
