#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
their files give the launcher flags, joined into one argv with
``--seed``. The run is the program's own path: ``repro.launch.train``
parses the argv and builds the run (data, partition, model, loader), and
``repro.fl.runtime.run_federated`` runs it with ``log=`` set (the
launcher's per-round sync on the eval result), on a ``"data"`` mesh over
the cell's chips when it has more than one. A round runs as one engine
call (``run_round``) or, where it has more participants than the engine's
width, as several cohort tiles (``run_tile`` each, then
``finish_round``); the harness follows both. What differs by model family
is read through the hooks of ``bench/reference/<family>.py``
(``bench/cells.py`` lists them).

The configuration's ``matmul_precision`` is set in JAX before anything is
built. Set-up: a warm-up call of ``WARM_ROUNDS`` rounds compiles every
program the cell uses and times a round (the quicker of the two after
the first, so that one host stall does not cut the window short); then
the timed call runs
``SETUP_ROUNDS`` rounds before the window, the rounds the correctness
check follows. The window
is whole rounds: it starts at the end of the last set-up round and ends
at the first round end at or after ``--seconds``; each round ends when
``log`` is called. Compilations inside the window are counted.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window, which then lasts at most ``TRACE_SECONDS``. After the window the
plain reference (``bench/reference/<family>.py``) follows the set-up
rounds from the seed and ``bench/check.py`` compares; ``correct`` holds
when every compared number is within its limit
(``bench/limits/<cell>.json``). The last lines of standard error give
each number beside its limit; the last line of standard output is the
result, one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time

import numpy as np

T0 = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SETUP_ROUNDS = 3          # timed-call rounds before the window, checked
WARM_ROUNDS = 3           # warm-up call: compile, then time two rounds
ROUNDS_MARGIN = 1.2       # timed-call rounds over the estimate
TRACE_SECONDS = 5.0       # the longest window a --trace 1 run traces
TRACE_DIR = os.path.join(HERE, ".traces")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


AGE_AT_T0 = process_age()


class Probe:
    """The benchmark's hooks around ``run_federated``: the loader span,
    the round clock (``log``), the window, and the record of the set-up
    rounds the reference follows."""

    def __init__(self, get_batch, seconds: float, chips: int,
                 fault: str | None, trace_dir: str | None):
        import jax
        self.jax = jax
        self.get_batch = get_batch
        self.seconds = seconds
        self.chips = chips
        self.fault = fault
        self.trace_dir = trace_dir
        self.compiles = 0
        self.start_call(timed=False)

    def start_call(self, timed: bool) -> None:
        self.timed = timed
        self.ends = []
        self.sels = [[]]             # per set-up round, the loader's rows
        self.thetas = []             # global before round 0, then after
        self.window = [None, None]   # perf_counter
        self.window_compiles = [None, None]
        self._pending = None
        self._first_tile = True

    # -- hooks ---------------------------------------------------------
    def fetch(self, sel):
        with self.jax.profiler.TraceAnnotation("fetch"):
            b = self.get_batch(sel)
        if self.timed and len(self.ends) < SETUP_ROUNDS:
            self.sels[-1].append(np.array(sel))
        return b

    def wrap_engine(self, make_round_engine):
        """Wrap the engine's round entries: a whole round
        (``run_round``), or a tiled round's tiles (``run_tile``) and its
        server step (``finish_round``). The faults go in through each
        tile's fusion weights and the global the round returns; the
        global a round starts from and the one it returns are recorded."""
        from bench import faults

        def tile_weights(weights):
            w = faults.weights(self.fault, weights, self.chips,
                               first=self._first_tile)
            self._first_tile = False
            return w

        def round_end(gp, new):
            new = faults.output(self.fault, gp, new)
            self._first_tile = True
            if self.timed and len(self.ends) < SETUP_ROUNDS:
                self._pending = (gp, new)
            return new

        def make(*a, **k):
            engine = make_round_engine(*a, **k)
            run_round = engine.run_round
            run_tile = engine.run_tile
            finish_round = engine.finish_round

            def timed_round(state, gp, batches, weights=None, **kw):
                state, new = run_round(state, gp, batches,
                                       weights=tile_weights(weights), **kw)
                return state, round_end(gp, new)

            def timed_tile(client_states, server_state, gp, batches,
                           weights=None, **kw):
                return run_tile(client_states, server_state, gp, batches,
                                weights=tile_weights(weights), **kw)

            def timed_finish(server_state, gp, fused):
                server_state, new = finish_round(server_state, gp, fused)
                return server_state, round_end(gp, new)
            engine.run_round = timed_round
            engine.run_tile = timed_tile
            engine.finish_round = timed_finish
            return engine
        return make

    def on_compile(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def on_round(self, msg: str) -> None:
        with self.jax.profiler.TraceAnnotation("round_sync"):
            t = time.perf_counter()
            self.ends.append(t)
            r = len(self.ends) - 1
            if not self.timed:
                return
            if r < SETUP_ROUNDS:
                gp, new = self._pending
                to_host = lambda t_: self.jax.tree_util.tree_map(  # noqa
                    np.asarray, t_)
                if r == 0:
                    self.thetas.append(to_host(gp))
                self.thetas.append(to_host(new))
                self.sels.append([])
                if r == SETUP_ROUNDS - 1:
                    self._open_window()
            elif (self.window[1] is None
                  and t - self.window[0] >= self.seconds):
                self._close_window(t)

    # -- window --------------------------------------------------------
    def _open_window(self) -> None:
        if self.trace_dir:
            self.jax.profiler.start_trace(self.trace_dir)
            self._span = self.jax.profiler.TraceAnnotation("window")
            self._span.__enter__()
        self.window_compiles[0] = self.compiles
        self.window[0] = time.perf_counter()

    def _close_window(self, t: float) -> None:
        self.window[1] = t
        self.window_compiles[1] = self.compiles
        if self.trace_dir:
            self._span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()

    def finish(self) -> None:
        """After the timed call: a window that the rounds ran out before
        ``--seconds`` closes at the last round."""
        if self.window[1] is None:
            log(f"window closed at the last round, "
                f"{self.ends[-1] - self.window[0]:.3f} s < {self.seconds} s")
            self._close_window(self.ends[-1])

    def window_indices(self) -> list:
        """The timed call's indices of the rounds that ended inside the
        window."""
        t0, t1 = self.window
        return [r for r, t in enumerate(self.ends) if t0 < t <= t1]

    def window_rounds(self) -> list:
        """Wall seconds of each round that ended inside the window."""
        ends = [self.ends[r] for r in self.window_indices()]
        return [b - a for a, b in zip([self.window[0]] + ends[:-1], ends)]


def _expect(cell, task, fl, parts, get_batch, test_batches,
            family) -> None:
    """The built run has the shapes the traffic and config files state:
    the generic ones here, the batch's through the family's
    ``batch_shapes``. A size that the configuration's ``model`` states and
    the task also carries (a classifier's class count) must agree."""
    e = dict(cell.traffic["expect"],
             train_size=cell.config["train_size"],
             test_size=cell.config["test_size"])
    got = {"population": fl.population, "cohort": fl.cohort_size,
           "steps": fl.local_epochs * fl.steps_per_epoch,
           "batch": fl.batch_size,
           "train_size": int(sum(len(p) for p in parts)),
           "test_size": int(np.shape(family.test_set(test_batches)[0])[0]),
           **family.batch_shapes(get_batch(np.zeros(1, int)))}
    bad = {k: (e[k], got.get(k)) for k in e if e[k] != got.get(k)}
    if cell.traffic["chips"] != cell.chips:
        bad["chips"] = (cell.traffic["chips"], cell.chips)
    sgd = cell.config["local_sgd"]
    if (fl.lr, fl.momentum) != (sgd["lr"], sgd["momentum"]):
        bad["local_sgd"] = (sgd, (fl.lr, fl.momentum))
    model = cell.config["model"]
    for f in dataclasses.fields(task):
        if f.name in model and getattr(task, f.name) != model[f.name]:
            bad[f.name] = (model[f.name], getattr(task, f.name))
    if bad:
        raise SystemExit(f"bench: the built run differs from the cell's "
                         f"files (want, got): {bad}")


def checked_rounds(sels: list, parts, steps: int,
                   participants: list) -> list:
    """The set-up rounds as the reference takes them: each participant's
    rows (P, S, B), in slot order, and its fusion weight, the size of its
    own shard (at least 1), found from the rows it drew. A client with an
    empty shard draws row 0 every time.

    The round's loader calls hold ``steps`` calls for each engine slot,
    tile after tile; of a round's ``participants[r]`` clients only the
    last tile may be short, and its slots past them are padding (the
    first participant of the tile again, at weight 0): they are left out."""
    owner = np.full(sum(len(p) for p in parts), -1, np.int64)
    for i, p in enumerate(parts):
        owner[np.asarray(p, np.int64)] = i
    sizes = np.array([len(p) for p in parts])
    rounds = []
    for calls, n in zip(sels[:SETUP_ROUNDS], participants):
        slots = np.stack(calls).reshape((-1, steps) + np.shape(calls[0]))
        rows = slots[:n]
        ids = owner[rows[:, 0, 0]]
        w = np.maximum(sizes[ids], 1).astype(np.float64)
        empty = np.all(rows == 0, axis=(1, 2)) & (sizes[owner[0]] > 1)
        w[empty] = 1.0
        rounds.append({"sels": rows, "weights": w})
    return rounds


def run_cell(cell, seed: int, seconds: float, *, trace: bool = False,
             fault: str | None = None, keep_detail: bool = False) -> dict:
    """One run of ``cell``: set-up, window, reference, checks. Returns the
    result object (the caller prints it); ``keep_detail`` adds the
    per-leaf readings the compared numbers were taken from."""
    import jax

    from bench import cells, check, faults
    from bench import trace as trace_lib
    from repro.fl import runtime
    from repro.launch import train
    from repro.launch.mesh import make_data_mesh

    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    args = train.parse_args(cell.argv + ["--seed", str(seed)])
    task, fl, parts, get_batch, test_batches = train.build_fl_run(args)
    family = cells.family_module(cell, "reference")
    _expect(cell, task, fl, parts, get_batch, test_batches, family)
    faults.plant_task(fault, task, family)
    mesh = make_data_mesh(cell.chips) if cell.chips > 1 else None
    devices = jax.devices()[:cell.chips]

    trace_dir = None
    if trace:
        # a trace of a few seconds of rounds is enough, and reading a
        # longer one would outlast the run's time limit
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = os.path.join(TRACE_DIR, cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    probe = Probe(get_batch, seconds, cell.chips, fault, trace_dir)
    jax.monitoring.register_event_duration_secs_listener(probe.on_compile)
    make_round_engine = runtime.make_round_engine
    runtime.make_round_engine = probe.wrap_engine(make_round_engine)
    try:
        def call(rounds):
            return runtime.run_federated(
                task, dataclasses.replace(fl, rounds=rounds), parts,
                probe.fetch, test_batches, log=probe.on_round, mesh=mesh)

        call(WARM_ROUNDS)
        round_s = min(b - a for a, b in zip(probe.ends, probe.ends[1:]))
        n_window = math.ceil(seconds / round_s * ROUNDS_MARGIN) + 1
        log(f"warm-up: round {round_s:.4f} s; timed call "
            f"{SETUP_ROUNDS} + {n_window} rounds")
        probe.start_call(timed=True)
        h = call(SETUP_ROUNDS + n_window)
        probe.finish()
    finally:
        runtime.make_round_engine = make_round_engine
        jax.monitoring.unregister_event_duration_listener(probe.on_compile)
    setup_s = AGE_AT_T0 + (probe.window[0] - T0)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    rounds = probe.window_rounds()
    window_s = probe.window[1] - probe.window[0]
    steps = fl.local_epochs * fl.steps_per_epoch
    # each round's real participants, its padded slots left out
    participants = [len(p) for p in h["participants"]]
    in_window = [participants[r] for r in probe.window_indices()]
    samples = sum(in_window) * steps * fl.batch_size
    finite = all(bool(np.all(np.isfinite(np.asarray(x))))
                 for x in jax.tree_util.tree_leaves(h["final_params"]))
    window_compiles = probe.window_compiles[1] - probe.window_compiles[0]
    values = {"samples_per_s": samples / window_s,
              "peak_hbm_gib": peak / 2**30,
              "setup_s": setup_s}
    log(f"window: {len(rounds)} rounds in {window_s:.4f} s, "
        f"{window_compiles} compilations inside; round seconds min "
        f"{min(rounds):.4f} median {np.median(rounds):.4f} max "
        f"{max(rounds):.4f}")

    # the reference runs once the window is closed, the peak read and
    # the program's state freed
    prog_thetas = probe.thetas
    prog_eval = family.program_eval(h, SETUP_ROUNDS)
    test = family.test_set(test_batches)
    del h, task, test_batches
    gc.collect()
    ref_rounds = checked_rounds(probe.sels, parts, steps, participants)

    def follow(precision):
        # clients in blocks of the engine's width, vmapped as its tiles are
        return family.run_rounds(
            cell.config["model"], seed, ref_rounds, test, get_batch,
            lr=cell.config["local_sgd"]["lr"],
            momentum=cell.config["local_sgd"]["momentum"],
            devices=devices, block=max(1, fl.cohort_size // cell.chips),
            precision=precision)

    t_ref = time.perf_counter()
    ref = follow(cell.config["matmul_precision"])
    sound = None
    if fault == "control":
        # the reference in the program's place, a precision lower; the
        # program's own run is sound, and its numbers come along
        sound, _, sound_detail = check.compare(prog_thetas, prog_eval, ref,
                                               family)
        ctrl = follow(faults.CONTROL_PRECISION)
        prog_thetas = ctrl["thetas"]
        prog_eval = family.program_eval(ctrl, SETUP_ROUNDS)
    numbers, left_out, detail = check.compare(prog_thetas, prog_eval, ref,
                                              family)
    if sound is not None:
        detail["program"] = sound
        detail["program_leaves"] = sound_detail["leaves"]
    log(f"reference: {SETUP_ROUNDS} rounds in "
        f"{time.perf_counter() - t_ref:.2f} s; {left_out} leaves left out")
    numbers["window_compiles"] = window_compiles
    numbers["failed_rounds"] = 0 if finite else len(rounds)
    # a cell compares the numbers its limits file names (PERF.md says
    # why a number is left out of a cell)
    checks = {k: {"value": numbers[k], "limit": v}
              for k, v in cell.limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    dev = devices[0]
    result = {"correct": correct, "attempted": len(rounds),
              "failed": numbers["failed_rounds"], "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    if not trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        summary = trace_lib.reduce(trace_lib.compact(
            trace_lib.find_xplane(trace_dir)))
        busy = [d["busy_ns"] / 1e9 for d in summary["devices"].values()]
        result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = summary["window_ns"] / 1e9
        ctx = MetricContext(
            summary=summary,
            rounds=len(rounds),
            samples_per_s=values["samples_per_s"], chips=cell.chips,
            cohort=fl.cohort_size,
            participants=sum(in_window) / len(in_window),
            tiles=sum(-(-p // fl.cohort_size) for p in in_window)
            / len(in_window),
            model=cell.config["model"],
            work=cells.family_module(cell, "work"),
            peak=cells.peaks(dev.device_kind, cell.root))
        for m in cell.per_layer:
            v = cells.metric_reader(cell, m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["breakdown"] = trace_lib.breakdown(summary)
    result["checks"] = checks
    if keep_detail:
        detail["window"] = {"rounds": len(rounds), "participants": in_window,
                            "samples": samples, "seconds": window_s}
        result["detail"] = detail
    return result


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) gets:
    the reduced trace of the window (``bench/trace.py``), the rounds it
    holds, the traced run's throughput, and the cell's sizes, work
    counter and device peaks. ``cohort`` is the engine's width, the
    clients one round program or tile fuses; ``participants`` and
    ``tiles`` are the real clients and the engine tiles of a round, the
    mean over the window's rounds (100 and 1 where one tile holds a
    cohort of 100)."""
    summary: dict
    rounds: int
    samples_per_s: float
    chips: int
    cohort: int
    participants: float
    tiles: float
    model: dict
    work: object
    peak: dict


def use_compile_cache() -> None:
    """The program's persistent compilation cache (in the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program of
    the cell, however quick to compile, for the next run."""
    import jax

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cells
    cell = cells.resolve(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    use_compile_cache()
    emit(run_cell(cell, args.seed, args.seconds, trace=bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
