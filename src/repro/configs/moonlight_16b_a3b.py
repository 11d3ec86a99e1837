"""moonlight-16b-a3b [moe] — the DeepSeek-V3 block (arXiv:2412.19437):
latent attention (DeepSeek-V2, arXiv:2405.04434; kv_lora_rank 512, no query
latent), 64 routed experts top-6 with sigmoid scores and a selection bias
(noaux_tc, one group), 2 shared experts, one leading dense layer
[hf:moonshotai/Moonlight-16B-A3B config.json]."""
from repro.configs.base import ArchConfig, MLASpec, MoESpec

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,  # MLA: every head has its own key and value
    d_head=128,  # v_head_dim
    d_ff=11264,  # the leading dense layer
    vocab=163840,
    activation="swiglu",
    rope_theta=50000.0,
    norm_eps=1e-5,
    default_mixer="mla",
    mla=MLASpec(kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128),
    moe=MoESpec(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                scoring="sigmoid", select_bias=True, routed_scale=2.446),
    first_k_dense=1,
)
