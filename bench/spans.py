"""The program's own spans and scopes in a profiler trace.

``bench/trace.py`` reads the device planes and the benchmark's own host
spans. This module reads, from the same ``.xplane.pb`` and on the same
clock, what the program writes itself (``repro.fl.runtime.SPANS``): the
``fl.*`` host spans with their stats, and the name stack of each device
op, which carries the round program's ``jax.named_scope`` scopes
(``SCOPES``).

``compact(path)`` gives ``trace.compact``'s dict, key for key, plus

    {"program": [[name, start, dur, {stat: value}], ...],
     "devices": {"0": {..., "op_scope": [i, ...], "stacks": [stack, ...]}}}

``program`` holds the host events whose name starts with ``fl.``;
``op_scope[k]`` indexes the name stack of ``ops[k]`` in ``stacks``. A TPU
trace keeps an op's name stack (its HLO ``op_name``) in the ``tf_op``
stat of the op's event metadata, which ``ProfileData`` does not show:
``op_stacks`` reads it from the file with a schema of just those fields.
The stack rides beside ``op_detail``, never in it: ``trace.phase_ns``
matches ``"while"`` in the detail, and the stack of an op inside the
local phase's loop holds ``while`` too.

``reduce(data)`` gives ``trace.reduce(data)``, every key as it is, plus
the keys of ``ADDED``: time and count of each program span inside the
window, and per device the idle time under the innermost program span
that covers it (``OTHER`` where none does), the idle time under each
span at any depth, the device time (union of op intervals) under each
scope, and the idle gaps named by the innermost span that covers most of
each (the benchmark's span labels where no program span does).

Metric readers (``bench/metrics/``) call ``of(ctx)``: ``bench/run.py``
hands them ``trace.reduce``'s summary, so ``of`` reads the run's trace
file once more and adds these keys to that summary.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys

from bench import trace

PREFIX = "fl."
SCOPES = ("local", "codec", "fuse", "server")
OTHER = "no fl span"
ADDED = ("program_ns", "program_count", "program_devices")
STACK_STAT = "tf_op"


def _xspace_subset():
    """A message class that parses an ``.xplane.pb`` (tsl's XSpace) into
    just each plane's name and its event and stat metadata tables; the
    other fields are skipped as unknown."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    field = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane_subset",
        syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, kind in fields:
            many = kind.endswith("*")        # "X*": repeated message X
            fd = m.field.add(name=fname, number=number,
                             label=(field.LABEL_REPEATED if many
                                    else field.LABEL_OPTIONAL))
            if kind[0].isupper():
                fd.type = field.TYPE_MESSAGE
                fd.type_name = f".{f.package}.{kind.rstrip('*')}"
            else:
                fd.type = getattr(field, f"TYPE_{kind.upper()}")
    message("XStat", ("metadata_id", 1, "int64"), ("str_value", 5, "string"),
            ("ref_value", 7, "uint64"))
    message("XEventMetadata", ("name", 2, "string"), ("stats", 5, "XStat*"))
    message("XStatMetadata", ("name", 2, "string"))
    # map<int64, X> fields are repeated (key = 1, value = 2) entries
    message("EventEntry", ("value", 2, "XEventMetadata"))
    message("StatEntry", ("key", 1, "int64"), ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, "string"),
            ("event_metadata", 4, "EventEntry*"),
            ("stat_metadata", 5, "StatEntry*"))
    message("XSpace", ("planes", 1, "XPlane*"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{f.package}.XSpace"))


def op_stacks(path: str) -> dict:
    """{plane name: {op event name: name stack}} for the TPU planes of the
    trace file, from the ``tf_op`` stat of each event's metadata (the HLO
    ``op_name``, less the ``:type`` the profiler appends). A name whose
    stacks differ between programs is left out."""
    space = _xspace_subset()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        if not trace._DEVICE_PLANE.fullmatch(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        stacks = collections.defaultdict(set)
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if names.get(st.metadata_id) == STACK_STAT:
                    value = st.str_value or names.get(st.ref_value, "")
                    stacks[entry.value.name].add(value.rpartition(":")[0]
                                                 if ":" in value else value)
        out[plane.name] = {k: v.pop() for k, v in stacks.items()
                           if len(v) == 1}
    return out


def compact(path: str) -> dict:
    from jax.profiler import ProfileData

    devices, host, program = {}, [], []
    stacks_of = op_stacks(path)
    for plane in ProfileData.from_file(path).planes:
        m = trace._DEVICE_PLANE.fullmatch(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {
                "ops": [], "op_detail": {}, "modules": [], "op_scope": [],
                "stacks": []})
            index, stack = {}, stacks_of.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        short, detail = trace._describe(ev.name, ev.stats)
                        dev["op_detail"].setdefault(short, detail)
                        dev["ops"].append([short, ev.start_ns,
                                           ev.duration_ns])
                        dev["op_scope"].append(index.setdefault(
                            stack.get(ev.name, ""), len(index)))
                elif line.name == "XLA Modules":
                    dev["modules"].extend([ev.name, ev.start_ns,
                                           ev.duration_ns]
                                          for ev in line.events)
            dev["stacks"] = list(index)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in trace.SPANS:
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
                    elif ev.name.startswith(PREFIX):
                        program.append([ev.name, ev.start_ns,
                                        ev.duration_ns, dict(ev.stats)])
    return {"devices": devices, "host": host, "program": program}


def in_scope(stack: str, scope: str) -> bool:
    """Whether the name stack (or any of the ``;``-joined stacks of a
    fused op) holds ``scope`` as one of its parts, bare or inside
    transforms (``transpose(jvp(local))``)."""
    return any(part.split("(")[-1].rstrip(")") == scope
               for part in re.split("[/;]", stack))


def _segments(spans, lo: int, hi: int) -> list:
    """The stretches of [lo, hi) in which some span is open, as
    (start, end, innermost, open names): the innermost span is the open
    one that started last."""
    points = sorted({lo, hi} | {x for _, a, b in spans for x in (a, b)
                                if lo < x < hi})
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    out, active, k = [], [], 0
    for a, b in zip(points, points[1:]):
        while k < len(spans) and spans[k][1] <= a:
            active.append(spans[k])
            k += 1
        active = [s for s in active if s[2] > a]
        if active:
            out.append((a, b, active[-1][0], {s[0] for s in active}))
    return out


def _overlaps(gaps, segments):
    """For each (start, end) gap in order, its overlap with each
    segment, as a list of (segment, ns); both inputs sorted by start."""
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        parts, i = [], j
        while i < len(segments) and segments[i][0] < b:
            s = segments[i]
            parts.append((s, min(b, s[1]) - max(a, s[0])))
            i += 1
        yield parts


def reduce(data: dict) -> dict:
    """``trace.reduce(data)`` plus the keys of ``ADDED`` (the module
    docstring says what each holds)."""
    summary = trace.reduce(data)
    lo, hi = next((s, s + d) for name, s, d in data["host"]
                  if name == trace.WINDOW)
    spans = []
    for name, s, d, _ in data.get("program", ()):
        a, b = trace._clip(s, d, lo, hi)
        if b > a:
            spans.append((name, a, b))
    by_name = collections.defaultdict(list)
    for name, a, b in spans:
        by_name[name].append((a, b))
    segments = _segments(spans, lo, hi)
    bench_spans = collections.defaultdict(list)
    for name, s, d in data["host"]:
        a, b = trace._clip(s, d, lo, hi)
        if name != trace.WINDOW and b > a:
            bench_spans[name].append((a, b))
    bench_spans = {k: trace._union(v) for k, v in bench_spans.items()}

    devices = {}
    for dev_id, dev in data["devices"].items():
        busy = trace._union(trace._clip(s, d, lo, hi)
                            for _, s, d in dev["ops"])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        inner, under, labelled = (collections.Counter(),
                                  collections.Counter(), [])
        for (a, b), parts in zip(gaps, _overlaps(gaps, segments)):
            named = collections.Counter()
            for seg, ns in parts:
                named[seg[2]] += ns
                for n in seg[3]:
                    under[n] += ns
            inner.update(named)
            inner[OTHER] += (b - a) - sum(named.values())
            if named:
                label = named.most_common(1)[0][0]
            else:
                own = {k: trace._overlap(a, b, iv)
                       for k, iv in bench_spans.items()}
                own[trace.OTHER] = max(0, (b - a) - sum(own.values()))
                label = max(own, key=own.get)
            labelled.append((label, b - a))
        scoped = collections.defaultdict(list)
        stacks = dev.get("stacks", [])
        for (_, s, d), i in zip(dev["ops"], dev.get("op_scope", ())):
            for scope in SCOPES:
                if in_scope(stacks[i], scope):
                    scoped[scope].append(trace._clip(s, d, lo, hi))
        devices[dev_id] = {
            "idle_by_program_ns": {k: v for k, v in inner.items() if v},
            "idle_under_program_ns": dict(under),
            "scope_ns": {k: sum(e - s for s, e in trace._union(v))
                         for k, v in scoped.items()},
            "gaps": sorted(labelled, key=lambda x: -x[1]),
        }
    summary["program_ns"] = {k: sum(e - s for s, e in trace._union(v))
                             for k, v in by_name.items()}
    summary["program_count"] = {k: len(v) for k, v in by_name.items()}
    summary["program_devices"] = devices
    return summary


def breakdown(summary: dict, top: int = 10) -> dict:
    """``trace.breakdown``'s, with the idle gaps named by the program's
    spans (``reduce``'s labels)."""
    out = trace.breakdown(summary, top)
    gaps = sorted((g for d in summary["program_devices"].values()
                   for g in d["gaps"]), key=lambda x: -x[1])
    out["idle_gaps"] = [[n, t / 1e9] for n, t in gaps[:top]]
    return out


def mean_over_chips(summary: dict, key: str, name: str) -> float:
    """Mean over the chips of ``program_devices[chip][key][name]``."""
    devs = summary["program_devices"].values()
    return sum(d[key].get(name, 0) for d in devs) / len(devs)


def describe(summary: dict, rounds: int) -> list:
    """Lines for the traced run's log: the idle time per round under the
    innermost program span, the share of the idle time no program span
    covers, the device time per round under each scope beside the round
    program's, and the longest idle gaps by name."""
    idle = collections.Counter()
    for d in summary["program_devices"].values():
        idle.update(d["idle_by_program_ns"])
    chips = len(summary["program_devices"])
    total = sum(idle.values())
    lines = ["idle per round under program spans (ms): " + ", ".join(
        f"{k} {v / chips / rounds / 1e6:.3f}" for k, v in idle.most_common())]
    if total:
        lines.append(f"idle no fl span covers: "
                     f"{100.0 * idle[OTHER] / total:.2f} % of "
                     f"{total / chips / 1e6:.3f} ms idle")
    scoped = {s: mean_over_chips(summary, "scope_ns", s) for s in SCOPES}
    module = sum(trace.matching(d["module_ns"], {}, ("round_fn",))
                 for d in summary["devices"].values()) / chips
    lines.append("device per round under scopes (ms): " + ", ".join(
        f"{k} {v / rounds / 1e6:.3f}" for k, v in scoped.items())
        + f"; round program {module / rounds / 1e6:.3f}")
    lines.append(f"idle gaps by program span: "
                 f"{breakdown(summary)['idle_gaps']}")
    return lines


def _latest_trace() -> str | None:
    from bench.run import TRACE_DIR
    paths = glob.glob(os.path.join(TRACE_DIR, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def of(ctx) -> dict | None:
    """The summary a reader gets, with the keys of ``ADDED``; None where
    the traced run's trace file cannot be found. Where the summary lacks
    them, they are read from the newest trace file under the benchmark's
    trace directory, if its window is the summary's, added to the summary
    (so the file is read once for every reader) and logged."""
    s = ctx.summary
    if ADDED[0] not in s:
        path = _latest_trace()
        full = reduce(compact(path)) if path else None
        if full is None or full["window_ns"] != s["window_ns"]:
            s[ADDED[0]] = None
        else:
            s.update({k: full[k] for k in ADDED})
            for line in describe(s, ctx.rounds):
                print(f"[bench] {line}", file=sys.stderr, flush=True)
    return s if s[ADDED[0]] is not None else None
