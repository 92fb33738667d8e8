"""The trace reduction: busy union, module and kernel times, idle gaps set
beside the benchmark's host spans; and the per-layer readers on it."""
from __future__ import annotations

import os

import pytest

from bench import cells, run, trace
from bench.work import cnn as work

# one device, window [100, 200): an op clipped to [100, 110) overlaps the
# local phase's loop [106, 116); the kernel runs in [120, 130), an
# all-reduce in [140, 150); the round module spans [105, 155); host spans
# cover parts of the idle gaps
SMALL = {
    "devices": {"0": {
        "ops": [["fusion.1", 90, 20], ["while.1", 106, 10],
                ["paired_fusion_kernel.2", 120, 10],
                ["all-reduce.3", 140, 10]],
        "op_detail": {"fusion.1": "fusion", "while.1": "while",
                      "paired_fusion_kernel.2": "custom-call",
                      "all-reduce.3": "all-reduce"},
        "modules": [["jit_round_fn(7)", 105, 50],
                    ["jit_counts(9)", 170, 20]]}},
    "host": [["window", 100, 100], ["fetch", 95, 10], ["fetch", 132, 4],
             ["round_sync", 150, 30]],
}
RECORDED = os.path.join(cells.BENCH_DIR, "testdata",
                        "trace_excerpt.json.gz")


def test_busy_union_times_and_gaps():
    s = trace.reduce(SMALL)
    dev = s["devices"]["0"]
    assert s["window_ns"] == 100
    # busy [100, 116), [120, 130), [140, 150)
    assert dev["busy_ns"] == 16 + 10 + 10
    assert dev["op_ns"] == {"fusion.1": 10, "while.1": 10,
                            "paired_fusion_kernel.2": 10,
                            "all-reduce.3": 10}
    assert dev["module_ns"] == {"jit_round_fn(7)": 50, "jit_counts(9)": 20}
    # gaps [116, 120), [130, 140) and [150, 200): fetch covers 4 of the
    # second, round_sync 30 of the third
    assert dev["idle_by_host_ns"] == {"fetch": 4, "round_sync": 30,
                                      trace.OTHER: 30}
    assert dev["gaps"] == [("round_sync", 50), (trace.OTHER, 10),
                           (trace.OTHER, 4)]
    assert s["host_ns"] == {"fetch": 9, "round_sync": 30}
    assert trace.matching(dev["op_ns"], dev["op_detail"],
                          ("paired_fusion",)) == 10
    # fusion: from the loop's end to the kernel's, inside the round module
    assert trace.phase_ns(dev, "round_fn", "while", ("paired_fusion",)) \
        == 130 - 116


def _ctx(summary, rounds=1, samples_per_s=1000.0):
    model = cells.resolve("vgg9_fed2.xdev").config["model"]
    return run.MetricContext(
        summary=summary, rounds=rounds, samples_per_s=samples_per_s,
        chips=1, cohort=10, participants=10.0, tiles=1.0, model=model,
        work=work,
        peak=cells.peaks("TPU v5 lite"))


def _read(name, ctx):
    cell = cells.resolve("vgg9_fed2.xdev")
    return cells.metric_reader(cell, name)(ctx)


def test_readers_on_the_small_trace():
    ctx = _ctx(trace.reduce(SMALL))
    assert _read("device_idle_pct", ctx) == pytest.approx(64.0)
    assert _read("round_program_ms", ctx) == pytest.approx(50e-6)
    assert _read("eval_program_ms", ctx) == pytest.approx(20e-6)
    assert _read("fetch_ms", ctx) == pytest.approx(9e-6)
    model = ctx.model
    nbytes = sum(work.paired_fusion_bytes(m, 10)
                 for m in work.leaf_sizes(model))
    assert _read("paired_fusion_roofline_pct", ctx) == pytest.approx(
        100 * nbytes / 819e9 / 14e-9)
    assert _read("round_mfu_pct", ctx) == pytest.approx(
        100 * work.train_flops_per_sample(model) * 1000.0 / 197e12)


def test_a_reader_with_nothing_to_read_returns_nothing():
    quiet = {"devices": {"0": {"ops": [["fusion.1", 110, 15]],
                               "op_detail": {}, "modules": []}},
             "host": [["window", 100, 100]]}
    ctx = _ctx(trace.reduce(quiet))
    for name in ("paired_fusion_roofline_pct", "fetch_ms",
                 "round_program_ms", "eval_program_ms"):
        assert _read(name, ctx) is None


def test_recorded_chip_trace():
    s = trace.reduce(trace.load(RECORDED))
    for dev in s["devices"].values():
        idle = s["window_ns"] - dev["busy_ns"]
        assert 0 < dev["busy_ns"] <= s["window_ns"]
        assert sum(dev["idle_by_host_ns"].values()) == idle
        assert trace.matching(dev["module_ns"], {}, ("round_fn",)) > 0
    ctx = _ctx(s)
    pct = _read("paired_fusion_roofline_pct", ctx)
    assert pct is not None and 0 < pct
    b = trace.breakdown(s)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
