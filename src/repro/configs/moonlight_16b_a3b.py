"""moonlight-16b-a3b [moe]: the DeepSeek-V3 block at 16B.
27L d_model=2048 16H, MLA without q LoRA (kv_lora 512, qk 128+64 rope,
v 128); the first layer dense (d_ff 11264), then 64 routed experts of
width 1408, top-6, and 2 shared experts; sigmoid scores with the noaux_tc
correction bias, one group, gates normalised and scaled by 2.446, the
sequence-wise balance loss; vocab 163840 untied, rope 50000, context 8192
[hf:moonshotai/Moonlight-16B-A3B config.json].  The balance loss's alpha
and the bias's gamma are not in the config: DeepSeek-V3's 0.0001 and
0.001.  Full attention -> long_500k skipped."""
from repro.models.config import ModelConfig

ROUTING = dict(scoring="sigmoid", router_bias=True, routed_scale=2.446,
               seq_aux=True, router_aux_weight=0.0001)

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840, period=(("mla", "moe"),), first_k_dense=1,
    n_experts=64, top_k=6, d_expert=1408, n_shared_experts=2, **ROUTING,
    mla=True, q_lora_rank=0, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=50_000.0, norm_eps=1e-5, max_seq=8192)

SMOKE = ModelConfig(
    name="moonlight-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=160, vocab=256, period=(("mla", "moe"),), first_k_dense=1,
    n_experts=4, router_experts=16, top_k=3, d_expert=32,
    n_shared_experts=2, **ROUTING,
    mla=True, q_lora_rank=0, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=50_000.0, dtype="float32")
