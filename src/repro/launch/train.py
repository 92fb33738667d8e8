"""Training launcher.

Two modes:
  --mode lm    : language-model pretraining on the synthetic token corpus
                 for any assigned arch (reduced or full), on the host mesh
                 or a real TPU mesh.
  --mode fl    : the paper's federated scenario (CNN + Fed2/fedavg/...),
                 or federated fine-tuning of a language model (an --arch
                 whose config is a transformer, e.g. deepseek-v2-lite):
                 the synthetic 8-domain token corpus, partitioned over
                 domains, --seq tokens a sequence; --layers and
                 --chips-per-layer cut it to one chip's share
                 (configs.common.chip_share).

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode lm \
      --arch llama3.2-1b --reduced --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --mode fl \
      --arch vgg9 --method fed2 --rounds 10 --nodes 6 --classes-per-node 5
  PYTHONPATH=src python -m repro.launch.train --mode fl \
      --arch deepseek-v2-lite --layers 5 --chips-per-layer 8 --nodes 4 \
      --cohort-size 1 --dirichlet 0.5 --steps-per-epoch 2 --batch 1 \
      --seq 4096 --train-size 32                  # LM, one chip's share
  PYTHONPATH=src python -m repro.launch.train --mode fl --nodes 64 \
      --cohort-size 16 --sampler uniform          # partial participation
  PYTHONPATH=src python -m repro.launch.train --mode fl --nodes 6 \
      --method fedavg --tiers 1.0x2,0.5x2,0.25x2  # capacity tiers
  PYTHONPATH=src python -m repro.launch.train --mode fl --nodes 8 \
      --cohort-size 4 --sampler uniform --fed-mode async --buffer-k 2 \
      --staleness 'polynomial(0.5)' --latency 'pareto(1.5)'
                                                  # buffered-async
  PYTHONPATH=src python -m repro.launch.train --mode fl --nodes 10 \
      --attack 'sign_flip(4)' --attack-fraction 0.2 \
      --robust 'trimmed_mean(0.25)'               # adversarial + robust
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np


def run_lm(args):
    from repro.checkpoint.io import save_checkpoint
    from repro.configs import get_config
    from repro.configs.common import with_fed2
    from repro.data.synthetic import lm_batch_from_tokens, make_token_dataset
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_train_step
    from repro.models.transformer import init_params

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.fed2:
        cfg = with_fed2(cfg, groups=args.fed2_groups)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    step_fn, opt = make_train_step(cfg, lr=args.lr,
                                   microbatches=args.microbatches)
    ostate = opt.init(params)
    step_jit = jax.jit(step_fn)

    toks, _ = make_token_dataset(args.batch * args.steps, args.seq + 1,
                                 cfg.vocab, seed=args.seed)
    mesh = make_host_mesh()
    t0 = time.time()
    with mesh:
        for i in range(args.steps):
            sl = toks[i * args.batch:(i + 1) * args.batch]
            batch = lm_batch_from_tokens(sl)
            params, ostate, loss = step_jit(params, ostate, jnp.int32(i),
                                            batch)
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss {float(loss):.4f} "
                      f"({time.time() - t0:.1f}s)")
    if args.ckpt:
        save_checkpoint(args.ckpt, params, step=args.steps)
        print("checkpoint ->", args.ckpt)
    return float(loss)


def _partition(args, labels, n_classes):
    from repro.data.synthetic import dirichlet_partition, nxc_partition
    if args.dirichlet > 0:
        return dirichlet_partition(labels, args.nodes, args.dirichlet,
                                   n_classes, seed=args.seed)
    return nxc_partition(labels, args.nodes, args.classes_per_node,
                         n_classes, seed=args.seed)


def _cnn_run(args, mod):
    """The image run: (task, parts, get_batch, test_batches)."""
    from repro.data.synthetic import make_image_dataset
    from repro.fl import alignment as alignment_lib
    from repro.fl import methods as methods_lib
    from repro.fl.runtime import cnn_task

    # model construction routes through THE alignment rule
    # (fl/alignment.py): "grouped" is each method's own structural
    # declaration (the historical branch), "pan"/"none" build plain
    cfg = alignment_lib.build_model_config(
        alignment_lib.get(args.alignment), methods_lib.get(args.method),
        grouped_fn=lambda: (mod.reduced() if args.reduced else
                            mod.full(fed2_groups=args.fed2_groups)),
        plain_fn=lambda: (mod.reduced(fed2_groups=0, norm="none")
                          if args.reduced else mod.baseline()))
    ds = make_image_dataset(args.train_size, n_classes=cfg.n_classes,
                            seed=args.seed, noise=args.noise)
    test = make_image_dataset(args.train_size // 4,
                              n_classes=cfg.n_classes, seed=args.seed + 99,
                              noise=args.noise)
    parts = _partition(args, ds.labels, cfg.n_classes)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    test_batches = [{"images": jnp.asarray(test.images),
                     "labels": jnp.asarray(test.labels)}]
    return cnn_task(cfg), parts, get_batch, test_batches


N_DOMAINS = 8       # the token corpus's domains, the LM partition's labels


def _lm_run(args, cfg):
    """The language-model run: one chip's share of ``cfg`` in float32;
    ``--train-size`` sequences of ``--seq`` tokens from the synthetic
    corpus (``make_token_dataset``, ids in the share's vocabulary slice),
    partitioned over its domains, and ``--train-size // 4`` test
    sequences of the same corpus. Fed2's structure groups are the held
    experts (``fusion.lm_group_axes``); every other layer is shared and
    fused by the weighted mean. Returns (task, parts, get_batch,
    test_batches)."""
    from repro.configs.common import chip_share
    from repro.data.synthetic import make_token_dataset
    from repro.fl.runtime import lm_task

    if args.alignment != "grouped":
        raise ValueError(f"--alignment {args.alignment}: a language "
                         "model's structure groups are its experts; only "
                         "'grouped' applies")
    cfg = chip_share(cfg, layers=args.layers, chips=args.chips_per_layer)
    n_test = args.train_size // 4
    toks, domains = make_token_dataset(args.train_size + n_test,
                                       args.seq + 1, cfg.vocab,
                                       n_domains=N_DOMAINS, seed=args.seed)
    parts = _partition(args, domains[:args.train_size], N_DOMAINS)

    def rows(t):
        return {"tokens": t[:, :-1], "labels": t[:, 1:],
                "mask": np.ones((len(t), args.seq), np.float32)}

    def get_batch(sel):
        return rows(toks[sel])

    return (lm_task(cfg, seq=args.seq), parts, get_batch,
            [rows(toks[args.train_size:])])


def build_fl_run(args):
    """The fl-mode run that the parsed flags describe, ready for
    ``run_federated``: (task, FLConfig, parts, get_batch, test_batches).
    The configuration's kind picks the family: a transformer
    (``ModelConfig``) trains as a language model, anything else as the
    image CNN."""
    import importlib

    from repro.fl.runtime import FLConfig
    from repro.models.transformer import ModelConfig

    mod = importlib.import_module(
        f"repro.configs.{args.arch.replace('-', '_').replace('.', '_')}")
    lm = isinstance(mod.full(), ModelConfig)
    if lm:
        task, parts, get_batch, test_batches = _lm_run(
            args, (mod.reduced if args.reduced else mod.full)(
                dtype=jnp.float32))
    else:
        task, parts, get_batch, test_batches = _cnn_run(args, mod)
    fl = FLConfig(population=args.nodes, cohort_size=args.cohort_size,
                  sampler=args.sampler, rounds=args.rounds,
                  local_epochs=args.local_epochs,
                  steps_per_epoch=args.steps_per_epoch,
                  batch_size=args.batch, lr=args.lr, momentum=0.9,
                  method=args.method, seed=args.seed,
                  tiers=args.tiers or None, mode=args.fed_mode,
                  buffer_k=args.buffer_k, staleness=args.staleness,
                  store=args.store, chunk_size=args.chunk_size,
                  attack=args.attack or None,
                  attack_fraction=args.attack_fraction,
                  robust=args.robust or None,
                  compute_dtype=args.compute_dtype,
                  codec=args.codec or None,
                  local_unroll=args.local_unroll,
                  alignment=args.alignment,
                  # an LM's eval tile is one training batch of sequences
                  **({"eval_batch": args.batch} if lm else {}))
    return task, fl, parts, get_batch, test_batches


def run_fl(args):
    from repro.fl.runtime import run_federated

    if args.scenario:
        # a registered scenario IS the full run config — everything else
        # on the command line is pinned by the spec (fl/scenarios.py)
        from repro.fl import scenarios as scenarios_lib
        spec = scenarios_lib.get(args.scenario)
        rec = scenarios_lib.run_scenario(spec, log=print)
        print(f"scenario {spec.name} ({spec.protocol_label()}, "
              f"{spec.method}): final acc {rec.final_acc:.4f}, "
              f"best {rec.best_acc:.4f}")
        return rec

    if args.dry_run:
        # lower (don't run) one engine round on the 1-device host mesh —
        # the sharded code path without TPUs. Uses fl_dryrun's reduced
        # vgg9 case regardless of --arch; see repro.launch.fl_dryrun for
        # the production-mesh matrix.
        from repro.launch.fl_dryrun import run_matrix
        recs = run_matrix(mesh_kind="host", methods=(args.method,),
                          families=("cnn",), clients=args.nodes,
                          local_steps=args.local_epochs *
                          args.steps_per_epoch,
                          batch=args.batch)
        return recs

    task, fl, parts, get_batch, test_batches = build_fl_run(args)
    with (jax.profiler.trace(args.profile_dir) if args.profile_dir
          else contextlib.nullcontext()):
        h = run_federated(task, fl, parts, get_batch, test_batches,
                          latency=args.latency, log=print,
                          use_local_kernel=args.use_local_kernel)
    print("final acc:", h["acc"][-1])
    return h


def parse_args(argv=None):
    """Parse and cross-check the launcher's flags (``argv=None``: the
    process's own command line)."""
    from repro.fl import alignment as alignment_lib
    from repro.fl import attacks as attacks_lib
    from repro.fl import codec as codec_lib
    from repro.fl import methods as methods_lib
    from repro.fl import population as population_lib
    from repro.fl import robust as robust_lib
    from repro.fl import statestore as statestore_lib

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["lm", "fl"], default="fl")
    ap.add_argument("--arch", default="vgg9")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--fed2", action="store_true")
    ap.add_argument("--fed2-groups", type=int, default=8)
    ap.add_argument("--method", default="fed2",
                    choices=list(methods_lib.available()))
    ap.add_argument("--scenario", default="",
                    help="fl mode: run a registered scenario from "
                         "fl/scenarios.py verbatim (see python -m "
                         "repro.launch.scenarios --list); overrides the "
                         "per-knob flags")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--nodes", type=int, default=10,
                    help="logical client population")
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="engine width (participants per tile); default "
                         "= the full population")
    ap.add_argument("--sampler", default="full",
                    choices=list(population_lib.available()),
                    help="per-round participation strategy")
    ap.add_argument("--store", default="memory",
                    choices=list(statestore_lib.available()),
                    help="fl mode: client-state store backend — 'memory' "
                         "stacks all P client rows in RAM; 'mmap' keeps "
                         "them in chunked on-disk shards so server memory "
                         "is O(cohort) (fl/statestore.py)")
    ap.add_argument("--chunk-size", type=int, default=1024,
                    help="fl mode: client rows per on-disk shard for "
                         "--store mmap")
    ap.add_argument("--tiers", default="",
                    help="fl mode: heterogeneous capacity tiers as "
                         "<width>x<count> pairs summing to --nodes, e.g. "
                         "1.0x2,0.5x2,0.25x2 (fl/capacity.py; "
                         "group-structured methods need width*G integer)")
    ap.add_argument("--fed-mode", default="sync",
                    choices=["sync", "async", "one_shot"],
                    help="fl mode: 'async' = buffered-async federation "
                         "(fl/async_engine.py) — --rounds counts fusion "
                         "events, --cohort-size is the in-flight "
                         "concurrency; 'one_shot' = train the whole "
                         "round budget locally and fuse exactly once "
                         "(fl/runtime.py one_shot_config)")
    ap.add_argument("--buffer-k", type=int, default=None,
                    help="async: updates fused per event (default = the "
                         "cohort size, the sync-equivalent bound)")
    ap.add_argument("--staleness", default="constant",
                    help="async: staleness discount — 'constant' or "
                         "'polynomial(a)'")
    ap.add_argument("--latency", default="zero",
                    help="async: seed-deterministic client-latency trace "
                         "— 'zero', 'pareto(a)' or 'lognormal(sigma)'")
    ap.add_argument("--attack", default="",
                    help="fl mode: byzantine client behavior as "
                         "name[(param)], e.g. label_flip or sign_flip(4) "
                         "(fl/attacks.py registry: "
                         + ", ".join(attacks_lib.available()) + ")")
    ap.add_argument("--attack-fraction", type=float, default=0.0,
                    help="fl mode: attacker share of the population in "
                         "(0, 1), or an explicit count >= 1; assignment "
                         "is seed-deterministic (requires --attack)")
    ap.add_argument("--robust", default="",
                    help="fl mode: robust fusion rule as name[(param)], "
                         "e.g. coordinate_median or trimmed_mean(0.25) "
                         "(fl/robust.py registry: "
                         + ", ".join(robust_lib.available()) + ")")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="fl mode: local-phase compute dtype; bfloat16 "
                         "casts at the round boundary and fuses in fp32 "
                         "(DESIGN.md §15; tier-fusion methods only)")
    ap.add_argument("--codec", default="",
                    help="fl mode: uplink codec as name[(param)], e.g. "
                         "'int8' or 'topk(0.05)' (fl/codec.py registry: "
                         + ", ".join(codec_lib.available()) + ")")
    ap.add_argument("--local-unroll", type=int, default=1,
                    help="fl mode: batch this many local SGD steps into "
                         "one dispatch (scan unroll; 1 = seed-identical)")
    ap.add_argument("--alignment", default="grouped",
                    choices=list(alignment_lib.available()),
                    help="fl mode: feature-alignment strategy "
                         "(fl/alignment.py) — 'grouped' = the method's "
                         "own structural declaration (Fed2 adaptation "
                         "for uses_groups methods; the default), 'pan' "
                         "= PAN position encodings on a plain net, "
                         "'none' = unaligned plain-net control")
    ap.add_argument("--list-capabilities", action="store_true",
                    help="print the method x feature capability table "
                         "(fl/compat.py) and exit")
    ap.add_argument("--use-local-kernel", action="store_true",
                    help="fl mode: route the local phase through the "
                         "fused Pallas local_step kernel (methods on "
                         "the default client_update/local_opt only)")
    ap.add_argument("--classes-per-node", type=int, default=5)
    ap.add_argument("--dirichlet", type=float, default=0.0)
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--steps-per-epoch", type=int, default=8)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--layers", type=int, default=None,
                    help="fl mode, language models: hold the first N "
                         "layers (the rest lie on further chips as "
                         "pipeline stages; configs.common.chip_share)")
    ap.add_argument("--chips-per-layer", type=int, default=1,
                    help="fl mode, language models: this chip is one of "
                         "K sharing each layer by expert parallelism, and "
                         "holds 1/K of each MoE layer's experts and of the "
                         "vocabulary")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--train-size", type=int, default=4000)
    ap.add_argument("--noise", type=float, default=1.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--profile-dir", default="",
                    help="fl mode: write a profiler trace of the run here "
                         "(the program's fl.* host spans and device "
                         "scopes, repro.fl.runtime.SPANS)")
    ap.add_argument("--dry-run", action="store_true",
                    help="fl mode: lower+compile one engine round (reduced "
                         "vgg9, chosen --method) on the host mesh instead "
                         "of training")
    args = ap.parse_args(argv)
    if args.dry_run and args.mode != "fl":
        ap.error("--dry-run is only supported with --mode fl")
    if args.scenario and args.mode != "fl":
        ap.error("--scenario is only supported with --mode fl")
    if args.tiers and args.mode != "fl":
        ap.error("--tiers is only supported with --mode fl")
    if args.mode != "fl" and (args.fed_mode != "sync"
                              or args.buffer_k is not None
                              or args.staleness != "constant"
                              or args.latency != "zero"):
        ap.error("--fed-mode/--buffer-k/--staleness/--latency are only "
                 "supported with --mode fl")
    if args.mode != "fl" and (args.attack or args.attack_fraction
                              or args.robust):
        ap.error("--attack/--attack-fraction/--robust are only supported "
                 "with --mode fl")
    if args.mode != "fl" and (args.compute_dtype != "float32"
                              or args.codec or args.local_unroll != 1
                              or args.use_local_kernel):
        ap.error("--compute-dtype/--codec/--local-unroll/"
                 "--use-local-kernel are only supported with --mode fl")
    if args.mode != "fl" and args.alignment != "grouped":
        ap.error("--alignment is only supported with --mode fl")
    if args.profile_dir and (args.mode != "fl" or args.scenario
                             or args.dry_run):
        ap.error("--profile-dir traces an fl-mode run: not with --mode lm, "
                 "--scenario or --dry-run")
    return args


def main(argv=None):
    """Run the launcher on ``argv`` (None: the process's command line) and
    return the run's result — fl mode: ``run_federated``'s history."""
    args = parse_args(argv)
    if args.list_capabilities:
        from repro.fl import compat as compat_lib
        print(compat_lib.capability_table())
        return None
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    return (run_lm if args.mode == "lm" else run_fl)(args)


if __name__ == "__main__":
    main()
