"""Work of one chip's share of a DeepSeek-V2 language model, counted from
its shapes (``bench/reference/lm.py`` states the model).

A sample is one sequence of ``seq`` tokens. FLOPs count the multiply-adds
of every product (2 per MAC) that a token needs: the projections of the
attention, dense FFN, router, shared expert and head, the routed experts
at the share of the top-k that lands on the held experts on average
(``top_k * held / router_experts`` experts a token), and the attention
scores and values over the full square of ``seq`` keys, as the chunked
kernel computes them. Norms, activations, rope and the embedding gather
are left out. A training sample costs three forward passes' worth;
recomputation under remat is not counted.
"""
from __future__ import annotations


def _attn_params(m: dict) -> int:
    d, h = m["d_model"], m["n_heads"]
    qk = m["qk_nope"] + m["qk_rope"]
    return (d * h * qk + d * (m["kv_lora"] + m["qk_rope"])
            + m["kv_lora"] * h * (m["qk_nope"] + m["v_head"])
            + h * m["v_head"] * d)


def active_params_per_token(m: dict) -> float:
    """Weights a token multiplies by, the held experts at their average
    share of its top-k."""
    d = m["d_model"]
    expert = 3 * d * m["expert_ff"]
    per_moe = (d * m["router_experts"] + 3 * d * m["shared_ff"]
               + expert * m["top_k"] * m["held"][1] / m["router_experts"])
    n_moe = m["n_layers"] - m["first_dense"]
    return (m["n_layers"] * _attn_params(m)
            + m["first_dense"] * 3 * d * m["dense_ff"]
            + n_moe * per_moe + d * m["vocab"])


def forward_flops_per_sample(m: dict) -> float:
    s = m["seq"]
    scores = m["n_layers"] * 2 * s * m["n_heads"] * (
        m["qk_nope"] + m["qk_rope"] + m["v_head"])
    return s * (2 * active_params_per_token(m) + scores)


def train_flops_per_sample(m: dict) -> float:
    return 3 * forward_flops_per_sample(m)


def leaf_sizes(m: dict) -> list:
    """Element counts of every parameter leaf, in no particular order."""
    d, h = m["d_model"], m["n_heads"]
    qk = m["qk_nope"] + m["qk_rope"]
    attn = [d * h * qk, d * (m["kv_lora"] + m["qk_rope"]), m["kv_lora"],
            m["kv_lora"] * h * m["qk_nope"], m["kv_lora"] * h * m["v_head"],
            h * m["v_head"] * d]
    n_dense = m["first_dense"]
    n_moe = m["n_layers"] - n_dense
    e, f = m["held"][1], m["expert_ff"]
    dense = attn + [d, d] + [d * m["dense_ff"]] * 3
    moe = (attn + [d, d, d * m["router_experts"]]
           + [d * m["shared_ff"]] * 3 + [e * d * f] * 3)
    return ([n_dense * x for x in dense] + [n_moe * x for x in moe]
            + [m["vocab"] * d, d, d * m["vocab"]])


def param_count(m: dict) -> int:
    return sum(leaf_sizes(m))


def paired_fusion_bytes(leaf_size: int, clients: int,
                        itemsize: int = 4) -> int:
    """HBM bytes one ``paired_fusion`` call needs for one leaf: read
    every client's copy and the weights, write the fused leaf."""
    return itemsize * (clients * leaf_size + clients + leaf_size)


def paired_fusion_flops(leaf_size: int, clients: int) -> int:
    """One multiply and one add per client element."""
    return 2 * clients * leaf_size
