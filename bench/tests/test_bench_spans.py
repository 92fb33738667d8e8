"""The program's spans and scopes in a trace (``bench/spans.py``) and the
per-layer readers on them; every existing reader reads what it read
before from the same trace."""
from __future__ import annotations

import copy
import os

import jax
import pytest

from bench import cells, spans, trace
from bench.tests.test_bench_trace import RECORDED, SMALL, _ctx, _read

OLD_READERS = ("device_idle_pct", "round_program_ms", "eval_program_ms",
               "fetch_ms", "paired_fusion_roofline_pct", "round_mfu_pct")
NEW_READERS = ("pack_ms", "pack_idle_pct", "local_phase_ms", "fuse_ms")
RECORDED_SPANS = os.path.join(cells.BENCH_DIR, "testdata",
                              "trace_excerpt_spans.json.gz")

# SMALL (window [100, 200), busy [100, 116), [120, 130), [140, 150)) with
# the program's spans: the round from 121, a pack [121, 138) holding a
# stack in busy time and a load [131, 137) over the benchmark's fetch, a
# dispatch [139, 142), the wait [150, 185) and log [185, 190). The idle
# gap [116, 120) lies under no program span. Op scopes: the clipped
# fusion and the loop under ``local`` (one inside a transform), the
# kernel under ``fuse``, the all-reduce under ``server``.
SMALL_SPANS = copy.deepcopy(SMALL)
SMALL_SPANS["program"] = [
    ["fl.round", 121, 77, {"_r": 1, "step_num": 0, "participants": 2,
                           "tiles": 1}],
    ["fl.pack", 121, 17, {"clients": 2, "steps": 2, "batch": 4}],
    ["fl.stack", 125, 2, {}], ["fl.load", 131, 6, {}],
    ["fl.dispatch", 139, 3, {}], ["fl.wait", 150, 35, {}],
    ["fl.log", 185, 5, {}]]
SMALL_SPANS["devices"]["0"]["stacks"] = [
    "jit(round_fn)/local/transpose(jvp(client))/dot_general",
    "jit(round_fn)/local/while",
    "jit(round_fn)/fuse/jit(paired_fusion_kernel)/paired_fusion/pallas_call",
    "jit(round_fn)/server/add"]
SMALL_SPANS["devices"]["0"]["op_scope"] = [0, 1, 2, 3]


def test_old_keys_and_readers_unchanged():
    """On SMALL and on the recorded chip excerpt, ``spans.reduce`` keeps
    ``trace.reduce``'s every key as it was, and the six readers read
    exactly today's values from it."""
    for old_data, new_data in ((SMALL, SMALL_SPANS),
                               (trace.load(RECORDED),) * 2):
        old, new = trace.reduce(old_data), spans.reduce(new_data)
        assert set(new) == set(old) | set(spans.ADDED)
        assert {k: new[k] for k in old} == old
        for name in OLD_READERS:
            assert _read(name, _ctx(new)) == _read(name, _ctx(old))


def test_idle_split_by_program_span():
    s = spans.reduce(SMALL_SPANS)
    dev = s["program_devices"]["0"]
    # gaps [116, 120): none; [130, 140): pack 1, load 6, pack 1, round 1,
    # dispatch 1; [150, 200): wait 35, log 5, round 8, none 2
    assert dev["idle_by_program_ns"] == {
        spans.OTHER: 6, "fl.pack": 2, "fl.load": 6, "fl.round": 9,
        "fl.dispatch": 1, "fl.wait": 35, "fl.log": 5}
    assert sum(dev["idle_by_program_ns"].values()) == 64
    assert dev["idle_under_program_ns"] == {
        "fl.round": 58, "fl.pack": 8, "fl.load": 6, "fl.dispatch": 1,
        "fl.wait": 35, "fl.log": 5}
    assert dev["gaps"] == [("fl.wait", 50), ("fl.load", 10),
                           (trace.OTHER, 4)]
    assert dev["scope_ns"] == {"local": 16, "fuse": 10, "server": 10}
    assert s["program_ns"] == {"fl.round": 77, "fl.pack": 17,
                               "fl.stack": 2, "fl.load": 6,
                               "fl.dispatch": 3, "fl.wait": 35, "fl.log": 5}
    assert set(s["program_count"].values()) == {1}
    b = spans.breakdown(s)
    assert b["idle_gaps"] == [["fl.wait", 50e-9], ["fl.load", 10e-9],
                              [trace.OTHER, 4e-9]]
    assert b["device_ops"] == trace.breakdown(s)["device_ops"]


def test_new_readers_on_the_small_trace():
    ctx = _ctx(spans.reduce(SMALL_SPANS))
    assert _read("pack_ms", ctx) == pytest.approx(17e-6)
    assert _read("pack_idle_pct", ctx) == pytest.approx(8.0)
    assert _read("local_phase_ms", ctx) == pytest.approx(16e-6)
    assert _read("fuse_ms", ctx) == pytest.approx(10e-6)


@pytest.mark.parametrize("data", [SMALL, "recorded"])
def test_new_readers_without_program_spans_return_nothing(data):
    """A program without the spans and scopes, as the parent commit:
    nothing to read, and no error."""
    data = trace.load(RECORDED) if data == "recorded" else data
    ctx = _ctx(spans.reduce(data))
    for name in NEW_READERS:
        assert _read(name, ctx) is None


def test_readers_find_the_trace_file_once(monkeypatch, capsys):
    """Given ``trace.reduce``'s summary, as ``bench/run.py`` gives it, the
    readers read the newest trace file once, when its window is the
    summary's, and log the split; else they read nothing."""
    reads = []

    def compact(path):
        reads.append(path)
        return SMALL_SPANS
    monkeypatch.setattr(spans, "compact", compact)
    monkeypatch.setattr(spans, "_latest_trace", lambda: "run.xplane.pb")
    ctx = _ctx(trace.reduce(SMALL))
    assert [_read(n, ctx) for n in NEW_READERS] == pytest.approx(
        [17e-6, 8.0, 16e-6, 10e-6])
    assert reads == ["run.xplane.pb"]
    err = capsys.readouterr().err
    assert "program spans (ms): fl.wait" in err
    assert "no fl span covers: 9.38 %" in err
    other = copy.deepcopy(SMALL)
    other["host"][0] = ["window", 100, 99]
    for latest in (lambda: "run.xplane.pb", lambda: None):
        monkeypatch.setattr(spans, "_latest_trace", latest)
        ctx = _ctx(trace.reduce(other))
        assert [_read(n, ctx) for n in NEW_READERS] == [None] * 4


def test_compact_keeps_what_trace_compact_keeps(tmp_path):
    """On a trace file (a host-only one: this machine has no TPU plane),
    ``spans.compact`` gives ``trace.compact``'s keys as they are and the
    ``fl.*`` events with their stats."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.StepTraceAnnotation("fl.round", step_num=4):
                with jax.profiler.TraceAnnotation("fetch"):
                    with jax.profiler.TraceAnnotation("fl.load"):
                        pass
                with jax.profiler.TraceAnnotation("fl.pack", clients=3):
                    pass
    path = trace.find_xplane(str(tmp_path))
    old, new = trace.compact(path), spans.compact(path)
    assert {k: new[k] for k in old} == old
    assert [h[0] for h in old["host"]] == ["window", "fetch"]
    assert [(p[0], p[3]) for p in new["program"]] == [
        ("fl.round", {"_r": 1, "step_num": 4}), ("fl.load", {}),
        ("fl.pack", {"clients": 3})]


@pytest.mark.parametrize("scope,stack,inside", [
    ("local", "jit(round_fn)/local/while/body/conv", True),
    ("local", "jit(round_fn)/transpose(jvp(local))/mul", True),
    ("local", "jit(round_fn)/local_step/pallas_call", False),
    ("fuse", "jit(round_fn)/fused/add", False),
    ("fuse", "", False)])
def test_in_scope(scope, stack, inside):
    assert spans.in_scope(stack, scope) is inside


def test_recorded_chip_trace_with_spans():
    """``RECORDED_SPANS``: ``spans.compact`` of a ``--trace 1`` run of
    ``vgg9_fed2.xdev`` on a TPU v5 lite (seed 2300000011), cut to the one
    ``fl.round`` span wholly inside the traced window, which becomes the
    excerpt's ``window``. Every op, module and host span that overlaps it
    is kept (10,875 ops, none capped), and the stacks the kept ops use."""
    s = spans.reduce(trace.load(RECORDED_SPANS))
    ctx = _ctx(s)
    assert [_read(n, ctx) for n in NEW_READERS] == pytest.approx(
        [1166.309446, 47.13069941095412, 1188.145231, 0.599765])
    dev, prog = s["devices"]["0"], s["program_devices"]["0"]
    idle = s["window_ns"] - dev["busy_ns"]
    assert sum(prog["idle_by_program_ns"].values()) == idle
    assert prog["idle_by_program_ns"].get(spans.OTHER, 0) <= 0.1 * idle
    module = trace.matching(dev["module_ns"], {}, ("round_fn",))
    assert 0.95 * module <= sum(prog["scope_ns"].values()) <= module
    assert s["program_count"]["fl.load"] == 500
    assert s["program_count"]["fl.pack"] == 1
    assert spans.breakdown(s)["idle_gaps"][0][0] == "fl.load"


def test_op_stacks_reads_the_tf_op_of_each_op(tmp_path):
    """An ``.xplane.pb`` with a TPU plane whose event metadata carry
    ``tf_op`` as a string or as a reference to a stat's name: the stack
    less its ``:type``; a name two programs give different stacks is left
    out."""
    space = spans._xspace_subset()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "flops"), (3, "jit(f)/fuse/add:")):
        plane.stat_metadata.add(key=key).value.name = name
    for name, value in (("%a = add", "jit(f)/local/mul:mul"),
                        ("%b = add", 3), ("%c = add", "jit(f)/x:"),
                        ("%c = add", "jit(g)/y:")):
        meta = plane.event_metadata.add().value
        meta.name = name
        if isinstance(value, int):
            meta.stats.add(metadata_id=1, ref_value=value)
        else:
            meta.stats.add(metadata_id=1, str_value=value)
        meta.stats.add(metadata_id=2, str_value="ignored")
    space.planes.add(name="/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert spans.op_stacks(str(path)) == {"/device:TPU:0": {
        "%a = add": "jit(f)/local/mul", "%b = add": "jit(f)/fuse/add"}}
