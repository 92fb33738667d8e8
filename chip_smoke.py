#!/usr/bin/env python3
"""Smoke run of the federated round on a TPU chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the cohort sharded over four chips

One chip: Fed2 on ``configs/vgg9.full`` (the paper's VGG9 at its published
widths) with 8 clients, through the launcher a user calls
(``repro.launch.train.main``) for 3 rounds. Then one more round of the
same config, driven through the round engine twice from the trained
global: with the Pallas fusion and local_step kernels on, and with both
off. Each is timed as a user runs it, then compiled again with fp32
matmuls and convolutions; those two new globals must agree within ATOL,
the kernel round's compiled HLO must hold a ``tpu_custom_call`` (the chip
ran compiled kernels, not the interpreter), and params and accuracy must
be finite.

``--chips 4``: only the sharded path. The same config runs through
``run_federated`` with the cohort on a 4-device "data" mesh. Then one
more round from its global runs on that mesh, where every chip must hold
its C/4 clients, and with ``mesh=None`` on one chip, in this process;
compiled with fp32 matmuls and convolutions, the two new globals must
agree within the same tolerance.

Compile and steady round seconds are printed on the way, each timed after
``block_until_ready``: information, not a benchmark. The last line of
standard output is the result, ``{"ok": true, "device": {...}}``. Without
a TPU the script exits non-zero before running anything. Any failure
exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Fed2 on VGG9 at full width, as a user launches it
FED2_ARGV = ["--mode", "fl", "--arch", "vgg9", "--method", "fed2",
             "--nodes", "8", "--rounds", "3", "--batch", "32"]

# Kernel-on vs kernel-off and sharded vs one-chip globals: max |diff| over
# all leaves, each side compiled with fp32 matmuls and convolutions
# ("highest" precision). Measured on a TPU v5e, one round of this config:
# at the default precision, which rounds their operands to bf16, the
# kernel and reference rounds drift apart by 4.2e-4; at fp32 by 2.2e-5
# (8.4e-5 from a random init), while weighting one client x1.5 moves the
# global by 1.6e-3. ATOL sits between the drift and that fault. Both
# comparisons cover one round: over three, a sharded and a one-chip run
# drift apart by 1.7e-3, the size of that fault.
ATOL = 3e-4
STEADY_ROUNDS = 3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _block(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: a.block_until_ready(), tree)


def _check_finite(tree, what: str) -> None:
    import jax
    import numpy as np
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        check(bool(np.all(np.isfinite(np.asarray(leaf)))),
              f"{what}: non-finite values in {jax.tree_util.keystr(path)}")


def _max_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _compare(a, b, what: str) -> None:
    """Log max |a - b| over all leaves; raise past ATOL."""
    import jax
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        check(la.shape == lb.shape, f"{what}: shape {la.shape} != {lb.shape}")
    worst = _max_diff(a, b)
    log(f"{what}: max |diff| {worst} (atol {ATOL})")
    check(worst <= ATOL, f"{what}: max |diff| {worst} > atol {ATOL}")


def phase_train(argv):
    """Fed2 through the launcher; returns its history."""
    from repro.launch import train
    t0 = time.perf_counter()
    h = train.main(argv)
    _block(h["final_params"])
    log(f"train: {len(h['acc'])} rounds in {time.perf_counter() - t0} s "
        "(compile included), acc " + ", ".join(map(str, h["acc"])))
    check(all(0.0 <= a <= 1.0 for a in h["acc"]),
          f"train: accuracy out of range or not finite: {h['acc']}")
    _check_finite(h["final_params"], "train: final params")
    return h


def _build(argv):
    """The launcher's run for ``argv``: (task, fl, parts, get_batch,
    test_batches)."""
    from repro.launch import train
    return train.build_fl_run(train.parse_args(argv))


def _round_inputs(fl, parts, get_batch):
    """One full-cohort round's inputs (state comes from each engine), as
    ``run_sampled_round`` packs them: (weights, group weights, batches)."""
    import numpy as np

    from repro.fl.population import Population
    from repro.fl.runtime import pad_tile_inputs

    pop = Population.from_parts(parts)
    rng = np.random.default_rng(fl.seed)
    ids = np.arange(fl.cohort_size)
    _, w, gw, batches = pad_tile_inputs(
        pop, ids, fl.cohort_size, get_batch,
        fl.local_epochs * fl.steps_per_epoch, fl.batch_size, rng)
    return w, gw, batches


def _round_args(engine, state, global_params, batches, w, gw):
    import jax.numpy as jnp
    return (state, global_params, engine.place_cohort(batches),
            jnp.asarray(w, jnp.float32),
            None if gw is None else jnp.asarray(gw, jnp.float32), None)


def _timed_round(engine, args, label):
    """AOT-compile the engine's round as a user runs it, then run it;
    returns (compiled round, new global). Compile and steady seconds are
    printed."""
    t0 = time.perf_counter()
    compiled = engine.round_fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    _, new_global = _block(compiled(*args))
    times = []
    for _ in range(STEADY_ROUNDS):
        t0 = time.perf_counter()
        _block(compiled(*args))
        times.append(time.perf_counter() - t0)
    log(f"round [{label}]: compile {compile_s} s, steady round "
        f"{min(times)} s (min of {STEADY_ROUNDS}: {times})")
    return compiled, new_global


def _fp32_round(engine, args):
    """The round's new global, compiled with fp32 matmuls and convs."""
    import jax
    with jax.default_matmul_precision("highest"):
        compiled = engine.round_fn.lower(*args).compile()
    return _block(compiled(*args))[1]


def phase_kernels(argv, global_params):
    """One more round from ``global_params``, kernels on and off."""
    from repro.fl.engine import make_round_engine
    task, fl, parts, get_batch, _ = _build(argv)
    w, gw, batches = _round_inputs(fl, parts, get_batch)
    out, default = {}, {}
    for on in (True, False):
        engine = make_round_engine(task, fl, global_params, use_kernel=on,
                                   use_local_kernel=on)
        args = _round_args(engine, engine.init_state(global_params),
                           global_params, batches, w, gw)
        label = "kernels on" if on else "kernels off"
        compiled, default[on] = _timed_round(engine, args, label)
        if on:
            check("tpu_custom_call" in compiled.as_text(),
                  "kernel round: no tpu_custom_call in the compiled HLO — "
                  "the Pallas kernels did not compile for the chip")
            log("round [kernels on]: compiled HLO holds tpu_custom_call")
        out[on] = _fp32_round(engine, args)
        _check_finite(out[on], f"round [{label}]")
    log("kernels on vs off at the default precision: max |diff| "
        f"{_max_diff(default[True], default[False])} (not checked)")
    _compare(out[True], out[False], "kernels on vs off, fp32")


def _check_placement(engine, compiled, batches, n_chips: int) -> None:
    """A round's cohort inputs, and the compiled round's view of them,
    hold C / n_chips clients on each chip."""
    import jax
    placed = engine.place_cohort(batches)
    per_chip = engine.cohort_size // n_chips
    for name, arr in placed.items():
        rows = {s.device.id: s.data.shape[0] for s in arr.addressable_shards}
        check(len(rows) == n_chips and set(rows.values()) == {per_chip},
              f"placement: batches[{name!r}] rows per chip {rows}, "
              f"want {per_chip} on each of {n_chips}")
    in_batches = compiled.input_shardings[0][2]
    for (path, sh), arr in zip(
            jax.tree_util.tree_flatten_with_path(in_batches)[0],
            jax.tree_util.tree_leaves(placed)):
        check(sh.shard_shape(arr.shape)[0] == per_chip,
              f"placement: the compiled round takes "
              f"{sh.shard_shape(arr.shape)[0]} clients of batches"
              f"{jax.tree_util.keystr(path)} per chip, want {per_chip}")
    log(f"placement: {per_chip} of {engine.cohort_size} clients on each of "
        f"chips {sorted(rows)}")


def phase_mesh(argv, n_chips: int):
    """The config through ``run_federated`` with the cohort sharded over
    ``n_chips``; then one more round from its global, on that mesh and on
    one chip."""
    import jax
    import numpy as np

    from repro.fl.engine import make_round_engine
    from repro.fl.runtime import run_federated
    from repro.launch.mesh import make_data_mesh

    task, fl, parts, get_batch, test_batches = _build(argv)
    mesh = make_data_mesh(n_chips)
    t0 = time.perf_counter()
    h = run_federated(task, fl, parts, get_batch, test_batches, mesh=mesh)
    _block(h["final_params"])
    log(f"run_federated [mesh x{n_chips}]: {len(h['acc'])} rounds in "
        f"{time.perf_counter() - t0} s (compile included), acc "
        + ", ".join(map(str, h["acc"])))
    check(all(0.0 <= a <= 1.0 for a in h["acc"]),
          f"mesh: accuracy out of range or not finite: {h['acc']}")
    _check_finite(h["final_params"], "mesh: final params")

    # on the host, so the one-chip round is not handed mesh-placed arrays
    gp = jax.tree_util.tree_map(np.asarray, h["final_params"])
    w, gw, batches = _round_inputs(fl, parts, get_batch)
    out = {}
    for name, m in ((f"mesh x{n_chips}", mesh), ("one chip", None)):
        engine = make_round_engine(task, fl, gp, mesh=m)
        args = _round_args(engine, engine.init_state(gp), gp, batches, w, gw)
        if m is not None:
            compiled, _ = _timed_round(engine, args, name)
            _check_placement(engine, compiled, batches, n_chips)
        out[name] = _fp32_round(engine, args)
        _check_finite(out[name], f"round [{name}]")
    _compare(*out.values(), f"mesh x{n_chips} vs one chip, fp32")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cohort-sharded path on 4 chips")
    args = ap.parse_args(argv)

    dev = device_info()
    log(f"platform {dev['platform']}, device_kind {dev['kind']}, "
        f"devices {dev['count']}")
    if dev["platform"] != "tpu":
        log("no TPU: this smoke run needs the chip")
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import setup_compile_cache
    log(f"compile cache: {setup_compile_cache()}")

    if args.chips == 4:
        phase_mesh(FED2_ARGV, 4)
    else:
        h = phase_train(FED2_ARGV)
        phase_kernels(FED2_ARGV, h["final_params"])
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
