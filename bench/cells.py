"""Resolve a benchmark cell from ``BENCHMARK.json`` and the files it names.

Nothing here lists a cell, configuration, traffic mix or metric: each is
found by its name.

- ``BENCHMARK.json`` ``workloads[]`` names the cell, its configuration,
  its traffic mix and its chips.
- ``configs[].file`` is the configuration: the launcher flags that pick
  the model, the model's sizes for the plain reference (``model``), and
  the ``family`` whose ``bench/work/<family>.py`` counts its work and
  whose ``bench/reference/<family>.py`` computes it plainly.
- ``bench/traffic/<traffic>.json``: the launcher flags that shape the job
  and ``expect``, the shapes the built run must have.
- ``bench/limits/<workload>.json``: the limit of each number the
  correctness check compares.
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A model family is two modules, and the harness (``bench/run.py``,
``bench/check.py``, ``bench/faults.py``) reads nothing else of it, so a
configuration of a new family is new files only.

``bench/reference/<family>.py``, the plain reference and the family's
side of the harness:

- ``run_rounds(model, seed, rounds, test, fetch, *, lr, momentum,
  devices, block, precision)``: the federated rounds from the seed's
  initialisation, ``rounds`` one dict per checked round with ``sels``
  (P, S, B), each participant's training rows, and ``weights`` (P,);
  clients vmapped in blocks of at most ``block`` a device (the engine's
  width over the cell's chips), the mean taken as a round of such tiles
  takes it; ``precision`` ``"highest"``, or ``"high"`` for the control. Returns
  ``thetas``, the global before the first round and after each, and the
  reference's eval after each under the key the program's history uses
  for it, so that ``program_eval`` reads either;
- ``batch_shapes(batch) -> dict``: the traffic file's ``expect`` entries
  that one loader batch fixes, beside the generic ones (population,
  cohort, steps, batch, train and test size);
- ``test_set(test_batches) -> tuple``: the eval set as host arrays for
  ``run_rounds``, its first array as long as the test set;
- ``program_eval(history, rounds) -> list``: what the program's eval gave
  after each of the first ``rounds`` rounds;
- ``eval_numbers(prog_eval, ref) -> dict``: the family's compared eval
  numbers (a cell's limits file names those it checks);
- ``plant_wrong_answer(task)``: the family's eval-side fault, an answer
  altered where it is produced.

``bench/work/<family>.py``, the work counted from the shapes, which the
per-layer readers take as ``ctx.work``: ``train_flops_per_sample(model)``
(``round_mfu_pct``), and ``leaf_sizes(model)``,
``paired_fusion_bytes(leaf_size, clients)`` and
``paired_fusion_flops(leaf_size, clients)``
(``paired_fusion_roofline_pct``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple       # metric entries this cell reports, trace 0
    per_layer: tuple        # metric entries this cell reports, trace 1
    root: str

    @property
    def argv(self) -> list:
        """The launcher's argv for this cell, without ``--seed``."""
        return list(self.config["argv"]) + list(self.traffic["argv"])

    @property
    def family(self) -> str:
        return self.config["family"]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with every file it needs read.
    Raises KeyError for a cell, configuration or file that is missing."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {workload!r} names config "
                       f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(root, "bench", "limits",
                                     f"{workload}.json"))
    e2e = tuple(m for m in bench["end_to_end"] if _in_cell(m, workload))
    e2e_names = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"]
                  if _in_cell(m, workload) and m["moves"] in e2e_names)
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=e2e, per_layer=layer, root=root)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(cell: Cell, kind: str):
    """``bench/<kind>/<family>.py`` (kind: ``work`` or ``reference``)."""
    return load_module(os.path.join(cell.root, "bench", kind,
                                    f"{cell.family}.py"),
                       f"bench_{kind}_{cell.family}")


def metric_reader(cell: Cell, metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return load_module(os.path.join(cell.root, "bench", "metrics",
                                    f"{metric}.py"),
                       "bench_metric_" + metric.replace(".", "_")).read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    ``bench/peaks.json`` is an error, not a default."""
    table = _load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
