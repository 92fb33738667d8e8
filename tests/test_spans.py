"""The program's own trace (``repro.fl.runtime.SPANS``).

- A fed2 run of two rounds, the first one untiled cohort and the second
  every client in two cohort tiles, runs under ``jax.profiler.trace``
  and is read back with ``ProfileData``: one ``fl.round`` per round with
  its ``step_num``, one ``fl.pack`` per tile with its stats, clients x
  steps ``fl.load`` spans inside each ``fl.pack`` and one ``fl.stack``
  with the bytes it put, for a loader of device arrays or of host
  arrays, the wait on the eval result before ``log``.
- The lowered round program carries the ``local``, ``codec``, ``fuse``
  and ``server`` scopes and both kernels' names.
- No span's stats cost anything with no profiler running: every stat is
  a value the code holds (read from the source), or is set under
  ``is_enabled()``.
- ``history["wall"]`` is stamped after the per-round wait where ``log``
  asks for one, and no wait is added where it does not.
"""
import ast
import dataclasses
import glob
import inspect
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import vgg9
from repro.data.synthetic import make_image_dataset, nxc_partition
from repro.fl import async_engine, capacity, engine, population, runtime
from repro.fl.runtime import FLConfig, cnn_task, run_federated

_DS = make_image_dataset(160, n_classes=4, seed=0, noise=0.8)
_TEST = make_image_dataset(40, n_classes=4, seed=9, noise=0.8)
_TEST_BATCHES = [{"images": jnp.asarray(_TEST.images),
                  "labels": jnp.asarray(_TEST.labels)}]
_TASK = cnn_task(vgg9.reduced(n_classes=4, fed2_groups=2, decouple=1,
                              norm="gn"))
COHORT, STEPS, BATCH = 2, 2, 4


def _get_batch(sel):
    return {"images": jnp.asarray(_DS.images[sel]),
            "labels": jnp.asarray(_DS.labels[sel])}


def _host_batch(sel):
    return {"images": _DS.images[sel], "labels": _DS.labels[sel]}


LOADERS = {"device": _get_batch, "host": _host_batch}


def _fl(population=4, method="fed2", **kw):
    return FLConfig(population=population, cohort_size=COHORT, rounds=2,
                    local_epochs=1, steps_per_epoch=STEPS, batch_size=BATCH,
                    lr=0.02, momentum=0.9, method=method, seed=0, **kw)


def _parts(population):
    return nxc_partition(_DS.labels, population, 2, 4, seed=1)


class _CohortThenAll(population.ClientSampler):
    """Round 0: the first cohort (one engine call); round 1: every
    client (cohort tiles)."""
    name = "cohort_then_all"

    def sample(self, round_idx, population, cohort_size, rng, weights=None):
        return np.arange(cohort_size if round_idx == 0 else population)


@pytest.fixture(scope="module", params=sorted(LOADERS))
def loader(request):
    """Which loader the traced run packs from: device or host arrays."""
    return request.param


@pytest.fixture(scope="module")
def program_events(loader, tmp_path_factory):
    """The ``fl.*`` host events of the traced run, in start order, as
    (name, start, end, stats)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(runtime.population_lib, "get",
                   lambda name: _CohortThenAll())
        cfg = _fl(sampler="uniform")
        with jax.profiler.trace(trace_dir):
            run_federated(_TASK, cfg, _parts(4), LOADERS[loader],
                          _TEST_BATCHES, log=lambda msg: None)
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    events = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("fl.")]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def _inside(events, outer):
    _, a, b, _ = outer
    return [e for e in events if a <= e[1] and e[2] <= b and e is not outer]


def test_one_round_span_per_round(program_events):
    rounds = [e for e in program_events if e[0] == "fl.round"]
    assert [r[3]["step_num"] for r in rounds] == [0, 1]
    assert [(r[3]["participants"], r[3]["tiles"]) for r in rounds] == \
        [(COHORT, 1), (4, 2)]
    assert not any({"attn_blocks", "attn_blocks_live"} & set(r[3])
                   for r in rounds)           # a CNN has no attention
    assert {e[0] for e in program_events} <= set(runtime.SPANS)


def test_lm_round_states_its_attention_blocks(tmp_path):
    """A language model's ``fl.round`` carries the attention blocks a
    layer has over one training sequence and those it computes."""
    from repro.configs import get_config
    seq = 32
    cfg = dataclasses.replace(get_config("llama3.2-1b", reduced=True),
                              n_layers=1, attn_q_chunk=8, attn_kv_chunk=16)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (6, seq + 1))

    def rows(t):
        return {"tokens": t[:, :-1], "labels": t[:, 1:],
                "mask": np.ones((len(t), seq), np.float32)}

    task = runtime.lm_task(cfg, seq=seq)
    assert task.attn_blocks == (6, 8)   # query chunks see 1, 1, 2, 2
    fl = FLConfig(population=2, cohort_size=2, rounds=1, local_epochs=1,
                  steps_per_epoch=1, batch_size=1, lr=0.01, momentum=0.9,
                  method="fedavg", seed=0)
    with jax.profiler.trace(str(tmp_path)):
        run_federated(task, fl, [np.arange(2), np.arange(2, 4)],
                      lambda sel: rows(toks[sel]), [rows(toks[4:])],
                      log=lambda msg: None)
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    rounds = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name == "fl.round"]
    assert [(r["attn_blocks"], r["attn_blocks_live"]) for r in rounds] == \
        [(8, 6)]


def test_one_pack_per_tile_with_its_loads(program_events):
    """Each tile's pack holds one load a (client, step) and, whether the
    loader returns host or device arrays, one stack span: the single put
    of the packed host buffers, carrying the bytes it copied."""
    tile_bytes = COHORT * STEPS * BATCH * (
        _DS.images.itemsize * int(np.prod(_DS.images.shape[1:]))
        + _DS.labels.itemsize)
    rounds = [e for e in program_events if e[0] == "fl.round"]
    for r, tiles in zip(rounds, (1, 2)):
        inside = _inside(program_events, r)
        packs = [e for e in inside if e[0] == "fl.pack"]
        assert len(packs) == tiles
        assert sum(e[0] == "fl.dispatch" for e in inside) == tiles + (
            tiles > 1)                  # the tiled round's server step
        assert sum(e[0] == "fl.gather" for e in inside) == tiles
        for p in packs:
            assert p[3] == {"clients": COHORT, "steps": STEPS,
                            "batch": BATCH}
            names = [e[0] for e in _inside(program_events, p)]
            assert names.count("fl.load") == COHORT * STEPS
            stacks = [e[3] for e in _inside(program_events, p)
                      if e[0] == "fl.stack"]
            assert stacks == [{"bytes": tile_bytes}]


def test_wait_on_the_result_precedes_log(program_events):
    for r in (e for e in program_events if e[0] == "fl.round"):
        tail = [e for e in _inside(program_events, r)
                if e[0] in ("fl.eval", "fl.wait", "fl.log")]
        assert [e[0] for e in tail] == ["fl.eval", "fl.wait", "fl.log"]
        assert tail[0][2] <= tail[1][1] and tail[1][2] <= tail[2][1]


@pytest.mark.parametrize("method,codec,scopes", [
    ("fed2", None, {"local", "fuse"}),        # fed2's server step is the
    ("fed2", "int8", {"local", "codec", "fuse"}),   # fused global itself
    ("fedavgm", None, {"local", "fuse", "server"})])
def test_round_program_carries_scopes_and_kernel_names(method, codec,
                                                       scopes):
    task = _TASK if method == "fed2" else cnn_task(
        vgg9.reduced(n_classes=4, fed2_groups=0, norm="none"))
    cfg = _fl(population=COHORT, method=method, codec=codec)
    gp = task.init_fn(jax.random.PRNGKey(0))
    eng = engine.make_round_engine(task, cfg, gp, use_kernel=True,
                                   use_local_kernel=True)
    batches = jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.stack([x] * STEPS)] * COHORT),
        _get_batch(np.arange(BATCH)))
    text = eng.round_fn.lower(
        eng.init_state(gp), gp, batches, jnp.ones(COHORT) / COHORT, None,
        None).as_text(debug_info=True)
    # the parts of every name stack (a nested jit's restart at its body)
    parts = {p for loc in re.findall(r'loc\("([^"]*)"', text)
             for p in loc.split("/")}
    assert parts & {"local", "codec", "fuse", "server"} == scopes
    assert {"paired_fusion", "local_step"} <= parts


def _stat_is_free(node) -> bool:
    """A stat's value is a name, an attribute, a constant or the length
    of one of those: nothing is computed for the span."""
    if isinstance(node, (ast.Name, ast.Attribute, ast.Constant)):
        return True
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "len" and len(node.args) == 1
            and isinstance(node.args[0], (ast.Name, ast.Attribute)))


@pytest.mark.parametrize("module", [runtime, population, capacity,
                                    async_engine])
def test_span_stats_cost_nothing_without_a_profiler(module):
    """Each span is named in ``SPANS``; a stat that takes computing is
    set under ``is_enabled()``, which is false with no profiler running.
    (TraceMe formats the stats themselves only while one runs.)"""
    tree = ast.parse(inspect.getsource(module))
    guarded = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.If) and "is_enabled" in ast.dump(node.test):
            guarded |= {id(n) for n in ast.walk(node)}
    spans = 0
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not isinstance(
                node.func, ast.Attribute):
            continue
        name = node.func.attr
        if name in ("TraceAnnotation", "StepTraceAnnotation"):
            spans += 1
            assert node.args[0].value in runtime.SPANS
        elif name != "set_metadata":
            continue
        for kw in node.keywords:
            assert _stat_is_free(kw.value) or id(node) in guarded, \
                ast.unparse(node)
    assert spans and not jax.profiler.TraceAnnotation.is_enabled()


@pytest.mark.parametrize("with_log", [True, False])
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_wall_is_stamped_after_the_wait(monkeypatch, mode, with_log):
    """The order of the clock reads (``stamp``: ``t0``, each round's
    ``wall``, ``wall_total``) and the waits on an eval result: with
    ``log``, each round waits, then stamps, then logs; without it no
    round waits, and the results are read once the loop is done."""
    order = []
    count_acc = runtime._count_acc

    def waiting(c):
        order.append("wait")
        return count_acc(c)

    def stamp():
        order.append("stamp")
        return float(len(order))

    monkeypatch.setattr(runtime, "_count_acc", waiting)
    for module in (runtime, async_engine):
        monkeypatch.setattr(module, "time", types.SimpleNamespace(time=stamp))
    log = (lambda msg: order.append("log")) if with_log else None
    run_federated(_TASK, _fl(population=COHORT, mode=mode), _parts(COHORT),
                  _get_batch, _TEST_BATCHES, log=log)
    rounds = ["wait", "stamp", "log"] * 2 if with_log else ["stamp"] * 2
    assert order == ["stamp"] + rounds + ["wait"] * 2 + ["stamp"]
