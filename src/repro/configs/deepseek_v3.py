"""deepseek-v3 [moe] — the architecture of DeepSeek-R1, the paper's
derivation model.  [arXiv:2412.19437; hf:deepseek-ai/DeepSeek-V3]

61 layers: the first 3 dense (SwiGLU, d_ff 18,432), then 58 MoE layers of
256 routed experts (width 2,048) and 1 shared expert, routed by the
``noaux_tc`` rule (sigmoid scores, selection bias, 8 groups of which the
best 4 are kept, top-8, normalised, x 2.5).  MLA with 128 heads (q_lora
1,536, kv_lora 512, nope 128, rope 64, v 128) and YaRN RoPE (factor 40 over
4,096 original positions; mscale = mscale_all_dim = 1).

``CONFIG`` is the whole model (dry-run and specs).  ``EP32`` is one chip's
share of the expert-parallel deployment of arXiv:2412.19437 §3.4 (each MoE
layer's 256 experts over 32 chips, 8 a chip): the pipeline stage holding
layers 0-7 (3 dense + 5 MoE), with experts 0-7 of every MoE layer.  The
router still routes over all 256; only the held experts' part is computed
here.  Attention, dense FFNs, shared expert, router, embedding and head are
whole.

Departures: the MTP (multi-token prediction) layer is left out — it feeds
only speculative decoding, which the engine does not have.  The rope
columns use the program's rotate-half layout where the published weights
are interleaved: the same up to a fixed permutation of the rope columns of
random weights (``rope_interleave=False`` in ``transformers``).
"""
from repro.configs.base import ModelConfig, Yarn

ARCH_ID = "deepseek-v3"

CONFIG = ModelConfig(
    arch_id=ARCH_ID, family="moe",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, head_dim=128,
    d_ff=18432, vocab_size=129280, rope_theta=10000.0, norm_eps=1e-6,
    rope_scaling=Yarn(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0),
    attention_type="mla",
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128,
    first_dense_layers=3,
    n_experts=256, moe_top_k=8, expert_d_ff=2048, n_shared_experts=1,
    router="sigmoid_group", n_groups=8, topk_groups=4, routed_scaling=2.5,
)

EP32 = CONFIG.replace(arch_id="deepseek-v3-ep32", n_layers=8,
                      experts_held=8)

#: one chip's shares of the model, by arch id
SHARES = {EP32.arch_id: EP32}


def smoke_config(cfg: ModelConfig = CONFIG) -> ModelConfig:
    """1 dense + 2 MoE layers, 16 experts in 4 groups (2 kept), top-4, YaRN
    on; a share holds 4 of the 16 experts."""
    return cfg.replace(
        n_layers=3, first_dense_layers=1, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=96, vocab_size=256,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16,
        n_experts=16, moe_top_k=4, expert_d_ff=32, n_shared_experts=1,
        n_groups=4, topk_groups=2, experts_held=4 if cfg.experts_held else 0,
        max_seq=64, dtype="float32",
    )
