"""Shared helpers for arch configs."""
import dataclasses

import jax.numpy as jnp


def with_fed2(cfg, groups: int = 8, decouple: int | None = None):
    """Apply Fed2 structure adaptation to a transformer config: the last
    ``decouple`` blocks get block-diagonal FFNs, the unembedding becomes
    block-diagonal over vocab clusters (DESIGN.md §3)."""
    if decouple is None:
        decouple = max(1, min(6, cfg.n_layers // 4))
    if cfg.family in ("ssm", "hybrid"):
        # channel grouping for SSM mixers is carried by Fed2 fusion group
        # maps (core/grouping.py); block-diagonal unembed still applies.
        decouple = 0
    if cfg.family == "moe":
        # experts ARE the isolated structure groups (DESIGN.md §3); fusion
        # pairs experts by logit signature, FFN stays expert-partitioned.
        decouple = 0
    if decouple > 0:
        assert cfg.d_model % groups == 0 and cfg.d_ff % groups == 0, \
            (cfg.arch_id, groups)
    return dataclasses.replace(cfg, fed2_groups=groups,
                               fed2_decouple=decouple)


FULL_DTYPE = jnp.bfloat16
REDUCED_DTYPE = jnp.float32


def chip_share(cfg, *, layers: int | None = None, chips: int = 1):
    """One chip's share of a deployment that divides every layer of
    ``cfg`` over ``chips`` chips by expert parallelism, the layers past
    the first ``layers`` lying on further chips as pipeline stages: the
    first ``layers`` layers, the first 1/chips of each MoE layer's experts
    (the router still scores all of them), and the first 1/chips of the
    vocabulary. Widths are never cut (model-configs guide, section 4)."""
    layers = cfg.n_layers if layers is None else layers
    if not cfg.moe_first_dense < layers <= cfg.n_layers:
        raise ValueError(f"{cfg.arch_id}: --layers {layers} must keep the "
                         f"{cfg.moe_first_dense} leading dense layer(s) and "
                         f"at least one more, of {cfg.n_layers}")
    moe = cfg.moe
    if chips > 1:
        if moe is None or moe.n_experts % chips or cfg.vocab % chips:
            raise ValueError(f"{cfg.arch_id}: {chips} chips cannot share "
                             "its experts and vocabulary evenly")
        moe = dataclasses.replace(moe, held=(0, moe.n_experts // chips))
    return dataclasses.replace(cfg, n_layers=layers, moe=moe,
                               vocab=cfg.vocab // chips)
