"""deepseek-v2-lite [moe] — arXiv:2405.04434, the published config
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json.
27L d_model=2048 16H; MLA kv_lora=512 with no query low-rank path
(q_lora_rank null), qk 128 nope + 64 rope, v 128, YaRN x40 over 4,096
positions (beta 32/1, mscale 0.707); MoE: 2 shared + 64 routed experts of
width 1,408, top-6 of a softmax, no top-k renormalisation, routed scale 1;
the first layer dense (FFN 10,944); SiLU, RMSNorm eps 1e-6; vocab 102,400,
untied head. The catalog's config gives no load-balance weight: 0 here.

One chip's share of a deployment (``configs.common.chip_share``; launcher
flags ``--layers`` and ``--chips-per-layer``): every layer is divided over
``chips_per_layer`` chips by expert parallelism, and the layers this chip
does not hold lie on further chips as pipeline stages. The chip holds the
first ``layers`` layers, experts [0, 64 / chips) of each MoE layer (the
router keeps its 64 outputs and top-6), both shared experts, and the first
1 / chips of the vocabulary. No width changes. The benchmark's cell is
``--layers 5 --chips-per-layer 8``: the dense layer and four MoE layers,
8 of 64 experts, 12,800 ids."""
from repro.configs.common import FULL_DTYPE, REDUCED_DTYPE
from repro.models.attention import MLAConfig
from repro.models.layers import YaRN
from repro.models.moe import MoEConfig
from repro.models.transformer import ModelConfig


def full(dtype=FULL_DTYPE, **kw):
    return ModelConfig(
        arch_id="deepseek-v2-lite", family="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv_heads=16, head_dim=128, d_ff=10944, vocab=102400,
        rope_theta=10000.0,
        mla=MLAConfig(d_model=2048, n_heads=16, kv_lora=512, q_lora=None,
                      qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                      rope_theta=10000.0, rope_scaling=YaRN()),
        moe=MoEConfig(d_model=2048, d_ff_expert=1408, n_experts=64, top_k=6,
                      n_shared=2, d_ff_shared=2 * 1408,
                      router_norm_topk=False, held=(0, 64)),
        moe_first_dense=1, moe_dense_ff=10944, aux_loss_weight=0.0,
        dtype=dtype, **kw)


def reduced(dtype=REDUCED_DTYPE, **kw):
    """The same layer kinds at toy widths, for CPU tests: one dense and
    two MoE layers, 16 experts of which 4 per token."""
    return ModelConfig(
        arch_id="deepseek-v2-lite-reduced", family="moe", n_layers=3,
        d_model=64, n_heads=4, n_kv_heads=4, head_dim=24, d_ff=128,
        vocab=512,
        mla=MLAConfig(d_model=64, n_heads=4, kv_lora=32, q_lora=None,
                      qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                      rope_scaling=YaRN()),
        moe=MoEConfig(d_model=64, d_ff_expert=32, n_experts=16, top_k=4,
                      n_shared=2, d_ff_shared=64, router_norm_topk=False,
                      held=(0, 16)),
        moe_first_dense=1, moe_dense_ff=128, aux_loss_weight=0.0,
        attn_q_chunk=16, attn_kv_chunk=16, loss_chunk=16,
        dtype=dtype, **kw)
