"""Plain reference of the Fed2-adapted VGG family and its federated rounds.

Written from the published description, in straightforward ``jax.numpy``
at float32 with every matmul and convolution at ``highest`` precision; it
imports nothing of the program. At ``precision="high"`` each matmul and
convolution is instead the sum of three bf16 passes (the operands split
into a bf16 high part and a bf16 remainder, the remainders' product left
out), as a TPU computes float32 at ``high``: the lower-precision control,
the same on every backend. The model (Fed2, KDD 2021, §5.1):

- 3x3 ``SAME`` convolutions with bias, GroupNorm (G groups when the
  channels divide, else one), ReLU, 2x2 max-pooling as the plan says;
- the last ``decouple`` weight layers grouped into G blocks: grouped
  convolutions, block-diagonal FCs, with the flattened conv features
  kept group-contiguous so FC block g reads conv group g;
- logits rounded up to a multiple of G and sliced back to the classes.

Initialisation follows the same published recipe from the seed: one
PRNG key per weight layer, weights N(0, 1/fan_in), biases zero, norm
scale one and bias zero.

A federated round: every client starts from the global, runs momentum
SGD on its batches, and the server takes the mean of the clients'
parameters weighted by each client's shard size (Fed2's paired averaging
with all groups present is that mean, Eq. 19). Evaluation counts a
(gold, predicted) confusion matrix over the test set.

The family's hooks (``bench/cells.py`` lists the contract): batches of
``images`` and ``labels``; the program's eval is its confusion matrix
after each round, compared as ``eval_moved``; the eval-side fault moves
each predicted class to the next.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def round_ch(c: int, groups: int) -> int:
    return c if groups == 0 else -(-c // groups) * groups


def layers(model: dict) -> list:
    """Weight layers in order: dicts of kind ("conv", "fc", "logits"),
    groups, c_in, c_out and, for convs, the input's side ``hw``."""
    g = model["fed2_groups"]
    n_weight = (sum(1 for s in model["plan"] if s[0] != "p")
                + len(model["fc_dims"]) + 1)
    first_grouped = n_weight - model["decouple"] if g else n_weight
    out, c_in, hw = [], model.get("input_channels", 3), model["input_hw"]
    for step in model["plan"]:
        if step[0] == "p":
            hw //= 2
            continue
        c_out = round_ch(step[1], g)
        grouped = len(out) >= first_grouped and g > 1 and c_in % g == 0
        out.append({"kind": "conv", "groups": g if grouped else 1,
                    "c_in": c_in, "c_out": c_out, "hw": hw})
        c_in = c_out
    d_in = hw * hw * c_in
    dims = [round_ch(d, g) for d in model["fc_dims"]]
    dims.append(round_ch(model["n_classes"], g))
    for i, d_out in enumerate(dims):
        grouped = len(out) >= first_grouped and g > 1 and d_in % g == 0
        out.append({"kind": "logits" if i == len(dims) - 1 else "fc",
                    "groups": g if grouped else 1, "c_in": d_in,
                    "c_out": d_out})
        d_in = d_out
    return out


def init(seed: int, model: dict, dtype=jnp.float32) -> dict:
    metas = layers(model)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(metas))
    convs, fcs = [], []
    for m, k in zip(metas, keys):
        g = m["groups"]
        if m["kind"] == "conv":
            fan_in = (m["c_in"] // g) * 9
            layer = {"w": jax.random.normal(
                        k, (3, 3, m["c_in"] // g, m["c_out"]), dtype)
                     * float(1.0 / np.sqrt(fan_in)),
                     "b": jnp.zeros((m["c_out"],), dtype)}
            if model["norm"] == "gn":
                layer["norm"] = {"scale": jnp.ones((m["c_out"],), dtype),
                                 "bias": jnp.zeros((m["c_out"],), dtype)}
            convs.append(layer)
        elif g > 1:
            gi, go = m["c_in"] // g, m["c_out"] // g
            fcs.append({"w": jax.random.normal(k, (g, gi, go), dtype)
                        * float(1.0 / np.sqrt(gi)),
                        "b": jnp.zeros((g, go), dtype)})
        else:
            fcs.append({"w": jax.random.normal(k, (m["c_in"], m["c_out"]),
                                               dtype)
                        * float(1.0 / np.sqrt(m["c_in"])),
                        "b": jnp.zeros((m["c_out"],), dtype)})
    return {"convs": convs, "fcs": fcs}


def _group_norm(x, p, groups, eps=1e-5):
    b, h, w, c = x.shape
    xg = x.astype(jnp.float32).reshape(b, h, w, groups, c // groups)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(x.shape).astype(x.dtype)
    return y * p["scale"] + p["bias"]


HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _passes(make_op, precision: str):
    """``make_op(p)(a, b)``, bilinear, at ``highest``, or as three bf16
    passes (``high``): forward and both backward products alike. A pass
    multiplies bf16 values at the default precision, exactly, and sums in
    float32."""
    if precision == "highest":
        return make_op(jax.lax.Precision.HIGHEST)
    op = make_op(jax.lax.Precision.DEFAULT)

    def three(f, a, b):
        (ah, al), (bh, bl) = _split(a), _split(b)
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    @jax.custom_vjp
    def passes(a, b):
        return three(op, a, b)

    def fwd(a, b):
        return three(op, a, b), (a, b)

    def bwd(res, ct):
        a, b = res
        (ah, al), (bh, bl), (ch, cl) = _split(a), _split(b), _split(ct)

        def da(b_, c):
            return jax.vjp(lambda x: op(x, b_), a)[1](c)[0]

        def db(a_, c):
            return jax.vjp(lambda y: op(a_, y), b)[1](c)[0]
        return (da(bh, ch) + da(bl, ch) + da(bh, cl),
                db(ah, ch) + db(al, ch) + db(ah, cl))

    passes.defvjp(fwd, bwd)
    return passes


def forward(params, model: dict, x, precision: str = "highest"):
    """x: (B, H, W, C) images -> (B, n_classes) logits."""
    metas = layers(model)
    g = model["fed2_groups"]
    ci = 0
    for step in model["plan"]:
        if step[0] == "p":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        layer = params["convs"][ci]
        def conv(p, groups=metas[ci]["groups"]):
            return functools.partial(
                jax.lax.conv_general_dilated, window_strides=(1, 1),
                padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups, precision=p)
        x = _passes(conv, precision)(x, layer["w"]) + layer["b"]
        if "norm" in layer:
            groups = g or model.get("gn_groups", 8)
            x = _group_norm(x, layer["norm"],
                            1 if x.shape[-1] % groups else groups)
        x = jax.nn.relu(x)
        ci += 1
    b, h, w, c = x.shape
    if g and c % g == 0:          # group-contiguous features
        x = x.reshape(b, h, w, g, c // g).transpose(0, 3, 1, 2, 4)
    x = x.reshape(b, -1)
    for m, fc in zip(metas[ci:], params["fcs"]):
        if m["groups"] > 1:
            gr, gi, go = fc["w"].shape
            x = _passes(lambda p: functools.partial(
                jnp.einsum, "bgi,gio->bgo", precision=p), precision)(
                x.reshape(b, gr, gi), fc["w"]) + fc["b"]
            x = x.reshape(b, gr * go)
        else:
            x = _passes(lambda p: functools.partial(jnp.matmul,
                                                    precision=p),
                        precision)(x, fc["w"]) + fc["b"]
        if m["kind"] != "logits":
            x = jax.nn.relu(x)
    return x[:, :model["n_classes"]]


def loss(params, model, images, labels, precision="highest"):
    logp = jax.nn.log_softmax(
        forward(params, model, images, precision).astype(jnp.float32),
        axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _local(model, lr, momentum, precision):
    """(global, images (S, B, ...), labels (S, B)) -> the client's params
    after S momentum-SGD steps from the global."""
    def client(theta, images, labels):
        def step(carry, batch):
            p, v = carry
            grad = jax.grad(loss)(p, model, *batch, precision)
            v = jax.tree_util.tree_map(lambda v_, g_: momentum * v_ + g_,
                                       v, grad)
            p = jax.tree_util.tree_map(lambda p_, v_: p_ - lr * v_, p, v)
            return (p, v), None
        v0 = jax.tree_util.tree_map(jnp.zeros_like, theta)
        (p, _), _ = jax.lax.scan(step, (theta, v0), (images, labels))
        return p
    return client


def run_rounds(model: dict, seed: int, rounds: list, test: tuple, fetch, *,
               lr: float, momentum: float, devices=None,
               block: int | None = None,
               precision: str = "highest") -> dict:
    """Federated rounds from the seed's initialisation.

    rounds: one dict per round with ``sels`` (C, S, B), the training-set
    rows of each client's batches in step order, and ``weights`` (C,),
    each client's shard size. ``fetch(rows)`` returns the ``images`` and
    ``labels`` of those rows. Clients run on each of ``devices`` (default:
    the first local device) in blocks of ``block`` per device, equal ones
    where there are several devices, so that one program serves them all
    (on one device, a short last block is padded as a tiled round's last
    tile is, with its first client again at weight 0); by default the whole
    cohort is one block, vmapped as the program's round vmaps it (a
    smaller block compiles to another program whose rounding differs, and
    the local steps amplify that). Give ``block`` the engine's width per
    device where a round runs as several tiles. Then the server's mean is
    taken as a tiled round takes it: each block's mean, by its own
    weights, scaled by the block's weight and summed, over the round's
    weight. That is the same mean; its rounding of the parameters, which
    in a leaf far larger than its update (a norm's scale of one) reads as
    a gap in the update's norm, is then the tiled round's.
    test: (images, labels) of the evaluation set.

    Returns ``thetas``, the global before the first round and after each
    (host arrays), and ``confusion``, the (n_classes, n_classes) counts of
    each round's new global on the test set."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n_cls = model["n_classes"]
    devices = devices or jax.local_devices()[:1]
    mesh = jax.sharding.Mesh(np.array(devices), ("clients",))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    split = jax.sharding.NamedSharding(mesh,
                                       jax.sharding.PartitionSpec("clients"))
    n = len(rounds[0]["weights"]) if rounds else 1
    block = block or n
    if len(devices) > 1:      # each block splits evenly over the devices
        block = max(d for d in range(1, n + 1)
                    if n % d == 0 and d <= block * len(devices))
    theta = jax.jit(lambda: init(seed, model), out_shardings=whole)()
    local = jax.jit(jax.vmap(_local(model, lr, momentum, precision),
                             in_axes=(None, 0, 0)),
                    in_shardings=(whole, split, split), out_shardings=split)

    @functools.partial(jax.jit, out_shardings=whole)
    def weighted_sum(acc, stacked, w):
        return jax.tree_util.tree_map(
            lambda a, s: a + jnp.tensordot(w, s, axes=1,
                                           precision=HIGHEST), acc, stacked)

    @jax.jit
    def confusion(params, images, labels):
        pred = jnp.argmax(forward(params, model, images, precision), axis=-1)
        return (jax.nn.one_hot(labels, n_cls, dtype=jnp.float32).T
                @ jax.nn.one_hot(pred, n_cls, dtype=jnp.float32))

    thetas = [jax.tree_util.tree_map(np.asarray, theta)]
    confs = []
    for rnd in rounds:
        sels = np.asarray(rnd["sels"])
        w_all = np.asarray(rnd["weights"], np.float64)
        n = len(w_all)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, theta)
        means = []
        for c0 in range(0, n, block):
            real = np.arange(c0, min(n, c0 + block))
            pad = block - len(real) if len(devices) == 1 else 0
            slots = np.concatenate([real, np.full(pad, c0)])
            got = fetch(sels[slots].ravel())
            batch = [jax.device_put(np.asarray(got[k]).reshape(
                sels[slots].shape + np.shape(got[k])[1:]), split)
                for k in ("images", "labels")]
            stacked = local(theta, *batch)
            s = float(w_all[real].sum())
            w = np.concatenate([w_all[real] / s,
                                np.zeros(pad)]).astype(np.float32)
            means.append((weighted_sum(zeros, stacked,
                                       jax.device_put(w, split)), s))
        if len(means) == 1:       # one block: the round's own mean
            theta = means[0][0]
        else:
            acc = jax.tree_util.tree_map(lambda *ls: sum(
                leaf * s for leaf, (_, s) in zip(ls, means)),
                *[m for m, _ in means])
            total = sum(s for _, s in means)
            theta = jax.tree_util.tree_map(lambda a: a / total, acc)
        thetas.append(jax.tree_util.tree_map(np.asarray, theta))
        images, labels = test
        conf = np.zeros((n_cls, n_cls), np.float64)
        for t0 in range(0, len(labels), 500):
            conf += np.asarray(confusion(
                theta, jnp.asarray(images[t0:t0 + 500]),
                jnp.asarray(labels[t0:t0 + 500])))
        confs.append(conf)
    return {"thetas": thetas, "confusion": confs}


# -- the family's hooks for the harness ----------------------------------

def batch_shapes(batch) -> dict:
    """The traffic file's ``expect`` entries that one batch fixes."""
    return {"image_shape": list(np.shape(batch["images"])[1:])}


def test_set(test_batches) -> tuple:
    """(images, labels) of the eval set as host arrays."""
    return tuple(np.asarray(test_batches[0][k]) for k in ("images",
                                                          "labels"))


def program_eval(history, rounds: int) -> list:
    """The eval engine's confusion matrix after each of the first
    ``rounds`` rounds; ``run_rounds`` records its own under the same key."""
    return [np.asarray(c) for c in history["confusion"][:rounds]]


def eval_numbers(prog_eval: list, ref: dict) -> dict:
    """``eval_moved``: the share of the test set whose predicted class the
    program's eval engine puts elsewhere than the reference does, at
    least: half the summed absolute gap of the two confusion matrices over
    the test set's size, after the first round (later rounds carry the
    rounding of more local steps, PERF.md)."""
    p_conf = np.asarray(prog_eval[0], np.float64)
    r_conf = ref["confusion"][0]
    return {"eval_moved": float(np.abs(p_conf - r_conf).sum() / 2
                                / r_conf.sum())}


def plant_wrong_answer(task) -> None:
    """The eval engine's predicted class moves to the next class where it
    is produced."""
    predict_fn = task.predict_fn

    def shifted(params, batch):
        pred, gold, w = predict_fn(params, batch)
        return (pred + 1) % task.n_classes, gold, w
    task.predict_fn = shifted
