"""Work of the VGG family, counted from its shapes.

FLOPs count the multiply-adds of convolutions and FCs (2 per MAC), at
their grouped cost; norms, activations and pooling are left out. A
training sample costs three forward passes' worth: the forward, and a
backward pass that computes gradients for activations and weights.
"""
from __future__ import annotations

from bench.reference.cnn import layers


def forward_flops_per_sample(model: dict) -> int:
    total = 0
    for m in layers(model):
        macs = (m["c_in"] // m["groups"]) * m["c_out"]
        if m["kind"] == "conv":
            macs *= 9 * m["hw"] * m["hw"]
        total += 2 * macs
    return total


def train_flops_per_sample(model: dict) -> int:
    return 3 * forward_flops_per_sample(model)


def leaf_sizes(model: dict) -> list:
    """Element counts of every parameter leaf, one entry per leaf."""
    sizes = []
    for m in layers(model):
        g = m["groups"]
        if m["kind"] == "conv":
            sizes.append(9 * (m["c_in"] // g) * m["c_out"])     # w
            sizes.append(m["c_out"])                            # b
            if model["norm"] == "gn":
                sizes += [m["c_out"], m["c_out"]]               # norm
        else:
            sizes.append((m["c_in"] // g) * m["c_out"])          # w
            sizes.append(m["c_out"])                            # b
    return sizes


def param_count(model: dict) -> int:
    return sum(leaf_sizes(model))


def paired_fusion_bytes(leaf_size: int, clients: int,
                        itemsize: int = 4) -> int:
    """HBM bytes one ``paired_fusion`` call needs for one leaf: read
    every client's copy and the weights, write the fused leaf."""
    return itemsize * (clients * leaf_size + clients + leaf_size)


def paired_fusion_flops(leaf_size: int, clients: int) -> int:
    """One multiply and one add per client element."""
    return 2 * clients * leaf_size
