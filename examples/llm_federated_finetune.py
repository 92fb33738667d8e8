"""Beyond-paper example: FEDERATED LM fine-tuning with Fed2 vocab-cluster
groups (DESIGN.md §3). Clients hold disjoint token *domains* (the LM analog
of non-IID classes); the Fed2-adapted transformer isolates each domain's
features in its own FFN/unembed group, and fusion pairs groups by vocab
cluster.

  PYTHONPATH=src python examples/llm_federated_finetune.py --rounds 4
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.common import with_fed2
from repro.data.synthetic import make_token_dataset
from repro.fl import methods as methods_lib
from repro.fl.runtime import FLConfig, lm_task, run_federated


def main():
    from repro.fl import population as population_lib

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--nodes", type=int, default=4,
                    help="logical client population (one token domain "
                         "per client)")
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="participants per round; default = all nodes")
    ap.add_argument("--sampler", default="full",
                    choices=list(population_lib.available()))
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--methods", default="fedavg,fed2",
                    help="comma list from "
                         f"{','.join(methods_lib.available())}, or 'all' "
                         "(host-fusion methods need a CNN task and are "
                         "skipped for the LM)")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=True)
    cfg = with_fed2(cfg, groups=4, decouple=1)
    n_domains = 4

    toks, domains = make_token_dataset(800, args.seq + 1, cfg.vocab,
                                       n_domains=n_domains, seed=0)
    # non-IID: client j holds only domain j's sequences
    parts = [np.flatnonzero(domains == j) for j in range(args.nodes)]

    def get_batch(sel):
        sl = toks[sel]
        return {"tokens": sl[:, :-1], "labels": sl[:, 1:],
                "mask": np.ones((len(sel), args.seq), np.float32)}

    test_toks, _ = make_token_dataset(64, args.seq + 1, cfg.vocab,
                                      n_domains=n_domains, seed=7)
    test_batches = [{"tokens": jnp.asarray(test_toks[:, :-1]),
                     "labels": jnp.asarray(test_toks[:, 1:]),
                     "mask": jnp.ones((64, args.seq), jnp.float32)}]

    chosen = (methods_lib.available() if args.methods == "all"
              else args.methods.split(","))
    for method in chosen:
        if methods_lib.get(method).host_fusion:
            print(f"{method}: skipped (host matched averaging is defined "
                  "for non-grouped CNNs; no LM analog)")
            continue
        fl = FLConfig(population=args.nodes, cohort_size=args.cohort_size,
                      sampler=args.sampler, rounds=args.rounds,
                      local_epochs=1, steps_per_epoch=4, batch_size=8,
                      lr=0.01, momentum=0.9, method=method, seed=0)
        h = run_federated(lm_task(cfg), fl, parts, get_batch, test_batches,
                          log=None)
        print(f"{method}: next-token acc per round: "
              f"{['%.3f' % a for a in h['acc']]}")


if __name__ == "__main__":
    main()
