#!/usr/bin/env python3
"""Read the compared numbers of a cell under the lower-precision control
and the planted faults (``bench/faults.py``), on the chip, at the cell's
own size. The limits in ``bench/limits/<cell>.json`` are set between
these readings and those of sound runs; the benchmark's own runs never
run this.

    python3 bench/controls.py --workload vgg9_fed2.xdev \
        --seeds 11,12,13 --faults none,control,half_batch,dropped

``none`` reads sound runs; a ``control`` run also reads its program's
own sound numbers, printed as a ``none`` line. Every run is in this one
process, with a short window; each prints one JSON line with the fault,
the seed and the compared numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
    __file__)))]

from bench import cells, faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--faults", default="control",
                    help="comma-separated: none or names in faults.FAULTS")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    names = args.faults.split(",")
    unknown = set(names) - set(faults.FAULTS) - {"none"}
    if unknown:
        ap.error(f"unknown faults {sorted(unknown)}")
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("the readings need the chip")
        return 2
    run.use_compile_cache()
    for name in names:
        for seed in (int(s) for s in args.seeds.split(",")):
            r = run.run_cell(cell, seed, args.seconds,
                             fault=None if name == "none" else name,
                             keep_detail=True)
            if "program" in r["detail"]:
                print(json.dumps({"workload": cell.name, "fault": "none",
                                  "seed": seed,
                                  "numbers": r["detail"]["program"],
                                  "detail": {"leaves": r["detail"][
                                      "program_leaves"]}}),
                      flush=True)
            print(json.dumps({"workload": cell.name, "fault": name,
                              "seed": seed, "correct": r["correct"],
                              "numbers": {k: c["value"] for k, c
                                          in r["checks"].items()},
                              "detail": r["detail"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
