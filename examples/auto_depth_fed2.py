"""Paper Fig. 10 workflow, end to end: measure the layer-wise feature
total-variance profile (Eq. 17) on a warmup model, pick the decouple depth
where TV surges, build the Fed2-adapted model at that depth, and run FL.

  PYTHONPATH=src python examples/auto_depth_fed2.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import vgg9
from repro.core.feature_stats import class_preference_vectors, total_variance
from repro.core.grouping import choose_decouple_depth
from repro.data.synthetic import make_image_dataset, nxc_partition
from repro.fl.runtime import FLConfig, cnn_task, run_federated
from repro.models.cnn import cnn_loss, init_cnn
from repro.optim.optimizers import sgd


def main():
    ds = make_image_dataset(2000, n_classes=10, seed=0, noise=1.2)
    test = make_image_dataset(400, n_classes=10, seed=99, noise=1.2)

    # 1. warmup a plain model briefly (the paper uses a short pretrain)
    base_cfg = vgg9.reduced(fed2_groups=0, norm="none")
    p = init_cnn(jax.random.PRNGKey(0), base_cfg)
    opt = sgd(0.01, 0.9)
    st = opt.init(p)

    @jax.jit
    def step(p, st, i, b):
        g = jax.grad(cnn_loss)(p, base_cfg, b)
        return opt.update(g, st, p, i)

    rng = np.random.default_rng(0)
    for i in range(40):
        sel = rng.integers(0, len(ds.labels), 32)
        p, st = step(p, st, jnp.int32(i),
                     {"images": jnp.asarray(ds.images[sel]),
                      "labels": jnp.asarray(ds.labels[sel])})

    # 2. TV profile -> decouple depth (Eq. 17 + Fig. 10 threshold rule)
    pv = class_preference_vectors(p, base_cfg, jnp.asarray(ds.images[:64]),
                                  jnp.asarray(ds.labels[:64]))
    tvs = [float(total_variance(v)) for v in pv]
    depth = choose_decouple_depth(tvs, threshold_frac=0.5, min_shared=2)
    depth = max(depth, 1)
    print("TV profile:", [f"{t:.4f}" for t in tvs], "-> decouple", depth)

    # 3. Fed2 run at the chosen depth
    cfg = vgg9.reduced(fed2_groups=5, decouple=depth, norm="gn")
    parts = nxc_partition(ds.labels, 6, 5, 10, seed=1)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    fl = FLConfig(population=6, rounds=6, local_epochs=1, steps_per_epoch=8,
                  batch_size=16, lr=0.008, momentum=0.9, method="fed2")
    h = run_federated(cnn_task(cfg), fl, parts, get_batch,
                      [{"images": jnp.asarray(test.images),
                        "labels": jnp.asarray(test.labels)}], log=print)
    print("auto-depth fed2 accs:", ["%.3f" % a for a in h["acc"]])


if __name__ == "__main__":
    main()
