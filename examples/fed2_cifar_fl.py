"""End-to-end driver for the paper's scenario: federated image
classification under non-IID skew, Fed2 vs any set of registered methods
(fl/methods.py — ``--methods all`` runs the whole registry), with the
population decoupled from the per-round cohort (fl/population.py):
``--population`` logical clients, of which ``--cohort-size`` train each
round under the ``--sampler`` participation strategy.

  PYTHONPATH=src python examples/fed2_cifar_fl.py [--rounds 10]
  PYTHONPATH=src python examples/fed2_cifar_fl.py --methods all
  # partial participation on the host mesh (sharded cohort axis):
  PYTHONPATH=src python examples/fed2_cifar_fl.py --population 64 \
      --cohort-size 16 --sampler uniform --mesh host
"""
import argparse

import jax.numpy as jnp

from repro.configs import vgg9
from repro.data.synthetic import make_image_dataset, nxc_partition
from repro.fl import methods as methods_lib
from repro.fl import population as population_lib
from repro.fl.runtime import FLConfig, cnn_task, run_federated


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--population", type=int, default=6,
                    help="logical clients behind the run")
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="participants per round (engine width); "
                         "default = the full population")
    ap.add_argument("--sampler", default="full",
                    choices=list(population_lib.available()))
    ap.add_argument("--mesh", default="none", choices=["none", "host"],
                    help="host: shard the cohort axis over the 1-device "
                         "host mesh (the TPU code path on CPU)")
    ap.add_argument("--classes-per-node", type=int, default=5)
    ap.add_argument("--noise", type=float, default=1.6)
    ap.add_argument("--methods", default="fedavg,fed2",
                    help="comma list from "
                         f"{','.join(methods_lib.available())}, or 'all'")
    args = ap.parse_args()

    ds = make_image_dataset(3000, n_classes=10, seed=0, noise=args.noise)
    test = make_image_dataset(600, n_classes=10, seed=99, noise=args.noise)
    parts = nxc_partition(ds.labels, args.population,
                          args.classes_per_node, 10, seed=1)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    test_batches = [{"images": jnp.asarray(test.images),
                     "labels": jnp.asarray(test.labels)}]

    mesh = None
    if args.mesh == "host":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()

    results = {}
    chosen = (methods_lib.available() if args.methods == "all"
              else args.methods.split(","))
    for method in chosen:
        cfg = (vgg9.reduced(fed2_groups=5, decouple=3, norm="gn")
               if methods_lib.get(method).uses_groups else
               vgg9.reduced(fed2_groups=0, norm="none"))
        fl = FLConfig(population=args.population,
                      cohort_size=args.cohort_size, sampler=args.sampler,
                      rounds=args.rounds, local_epochs=1,
                      steps_per_epoch=6, batch_size=16, lr=0.015,
                      momentum=0.9, method=method, seed=0)
        print(f"=== {method} (population {fl.population}, cohort "
              f"{fl.cohort_size}, sampler {fl.sampler}) ===")
        h = run_federated(cnn_task(cfg), fl, parts, get_batch, test_batches,
                          log=print, mesh=mesh)
        results[method] = h

    print("\nmethod, best_acc, final_acc, acc_curve")
    for m, h in results.items():
        accs = h["acc"]
        print(f"{m}, {max(accs):.4f}, {accs[-1]:.4f}, "
              f"{['%.3f' % a for a in accs]}")

    # final-round per-group accuracy (fl/evaluation.py confusion counts):
    # group g is scored over the eval samples whose label is in its
    # logit signature — Eq. 19's pairing key
    from repro.core.grouping import GroupSpec
    from repro.fl.evaluation import group_accuracy
    spec = GroupSpec.contiguous(5, 10)
    print("\nper-group accuracy (final round, groups of "
          f"{10 // 5} classes):")
    for m, h in results.items():
        ga = group_accuracy(h["confusion"][-1], spec)
        print(f"{m}, {['%.3f' % a for a in ga]}")


if __name__ == "__main__":
    main()
