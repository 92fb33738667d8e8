"""Reduce a profiler trace to what the per-layer metrics read.

``compact(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
and keeps only what the reduction needs, as plain lists (times in ns on
the profiler's clock):

    {"devices": {"0": {"ops": [[name, start, dur], ...],
                       "op_detail": {name: detail, ...},
                       "modules": [[name, start, dur], ...]}, ...},
     "host": [[name, start, dur], ...]}

``ops`` are the events of a TPU plane's "XLA Ops" line, by the op's short
name (``%fusion.12``), with ``op_detail`` saying what each op is (kernel
and JAX op names in its text, and its opcode); ``modules`` are the events
of its "XLA Modules" line, and ``host`` the benchmark's own spans
(``SPANS``) on the host threads.

``reduce(compact)`` clips everything to the ``window`` span and gives,
per device, the busy time (the union of op intervals), time per op and
per module, the clipped op and module spans, and the idle gaps; and the
host spans' time, with each idle gap attributed to the spans it overlaps
("other host" for the rest).
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

WINDOW = "window"
SPANS = ("fetch", "round_sync", WINDOW)
OTHER = "other host"
_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
# names an op's text gives its Pallas kernel or JAX op, and its opcode
_NAMED = re.compile(r"\w*(?:kernel|pallas)\w*")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"\}? ([a-z][\w-]*)\(")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _describe(text: str, stats) -> tuple:
    """(short name, detail) of one "XLA Ops" event. The trace names an op
    by its whole HLO instruction; the short name is the part before
    " = ", and the detail keeps the op's ``tf_op`` stat, the names the
    text gives its kernel or JAX op, and its opcode."""
    short, _, rest = text.partition(" = ")
    words = [str(v) for k, v in stats if k == "tf_op"]
    words += _OP_NAME.findall(rest)[:1] + sorted(set(_NAMED.findall(rest)))
    m = _OPCODE.search(rest)
    if m:
        words.append(m.group(1))
    return short, " ".join(words)


def compact(path: str) -> dict:
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.fullmatch(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"ops": [], "op_detail": {},
                                                  "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        short, detail = _describe(ev.name, ev.stats)
                        dev["op_detail"].setdefault(short, detail)
                        dev["ops"].append([short, ev.start_ns,
                                           ev.duration_ns])
                elif line.name == "XLA Modules":
                    dev["modules"].extend([ev.name, ev.start_ns,
                                           ev.duration_ns]
                                          for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events if ev.name in SPANS)
    return {"devices": devices, "host": host}


def load(path: str) -> dict:
    """A compact trace kept as gzipped JSON (the tests' recorded one)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _clip(start, dur, lo, hi):
    return max(start, lo), min(start + dur, hi)


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a0, a1, intervals):
    return sum(max(0, min(a1, e) - max(a0, s)) for s, e in intervals)


def reduce(data: dict) -> dict:
    """Per-device busy/idle, op and module times, host spans and the idle
    gaps set beside them, all inside the ``window`` span."""
    windows = [(s, s + d) for name, s, d in data["host"] if name == WINDOW]
    if not windows:
        raise ValueError("trace has no window span")
    lo, hi = windows[0]
    host = collections.defaultdict(list)
    for name, s, d in data["host"]:
        if name != WINDOW:
            a, b = _clip(s, d, lo, hi)
            if b > a:
                host[name].append((a, b))
    host_merged = {k: _union(v) for k, v in host.items()}

    devices = {}
    for dev_id, dev in data["devices"].items():
        op_spans, op_time = [], collections.Counter()
        for name, s, d in dev["ops"]:
            a, b = _clip(s, d, lo, hi)
            if b > a:
                op_spans.append((name, a, b))
                op_time[name] += b - a
        busy = _union((a, b) for _, a, b in op_spans)
        module_spans, module_time = [], collections.Counter()
        for name, s, d in dev["modules"]:
            a, b = _clip(s, d, lo, hi)
            if b > a:
                module_spans.append((name, a, b))
                module_time[name] += b - a
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_by = collections.Counter()
        labelled = []
        for a, b in gaps:
            parts = {k: _overlap(a, b, iv) for k, iv in host_merged.items()}
            parts[OTHER] = max(0, (b - a) - sum(parts.values()))
            for k, v in parts.items():
                idle_by[k] += v
            labelled.append((max(parts, key=parts.get), b - a))
        devices[dev_id] = {
            "busy_ns": sum(e - s for s, e in busy),
            "op_ns": dict(op_time),
            "op_detail": {k: dev["op_detail"].get(k, "") for k in op_time},
            "module_ns": dict(module_time),
            "op_spans": op_spans, "module_spans": module_spans,
            "idle_by_host_ns": dict(idle_by),
            "gaps": sorted(labelled, key=lambda x: -x[1]),
        }
    if not devices:
        raise ValueError("trace has no TPU device plane")
    return {"window_ns": hi - lo, "devices": devices,
            "host_ns": {k: sum(e - s for s, e in iv)
                        for k, iv in host_merged.items()},
            "host_count": {k: len(iv) for k, iv in host.items()}}


def breakdown(summary: dict, top: int = 10) -> dict:
    """The longest device ops (summed by name over the chips) and the
    longest idle gaps with what the host was doing, in seconds."""
    op_ns, gaps = collections.Counter(), []
    for dev in summary["devices"].values():
        op_ns.update(dev["op_ns"])
        gaps.extend(dev["gaps"])
    gaps.sort(key=lambda x: -x[1])
    detail = {}
    for dev in summary["devices"].values():
        detail.update(dev["op_detail"])
    return {"device_ops": [[f"{n} {detail.get(n, '')}".strip(), t / 1e9]
                           for n, t in op_ns.most_common(top)],
            "idle_gaps": [[n, t / 1e9] for n, t in gaps[:top]]}


def matching(times: dict, detail: dict, patterns) -> int:
    """Summed ns of the ops or modules whose name (or detail) contains
    any of ``patterns``."""
    return sum(t for name, t in times.items()
               if any(p in name or p in detail.get(name, "")
                      for p in patterns))


def phase_ns(dev: dict, module: str, after: str, until) -> int:
    """Summed ns, over the runs of the modules whose name holds
    ``module``, from the end of the last op whose detail holds ``after``
    (the module's start where none ran) to the end of the last op whose
    name holds any of ``until``; 0 where no such op ran."""
    total = 0
    ops = sorted(dev["op_spans"], key=lambda x: x[1])
    for name, m0, m1 in dev["module_spans"]:
        if module not in name:
            continue
        inside = [(n, a, b) for n, a, b in ops if m0 <= a and b <= m1]
        start = max((b for n, a, b in inside
                     if after in dev["op_detail"].get(n, "")), default=m0)
        end = max((b for n, a, b in inside
                   if any(p in n for p in until)), default=None)
        if end is not None and end > start:
            total += end - start
    return total
