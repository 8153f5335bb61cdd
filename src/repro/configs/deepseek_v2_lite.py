"""DeepSeek-V2-Lite (15.7B total / 2.4B active) — MLA (kv_lora 512, no
query compression) with YaRN rope, 64 routed experts top-6 by greedy
softmax without renormalisation, 2 shared experts, first layer dense.
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite; hf config.json]"""
from repro.configs.base import ArchSpec, reduce_for_smoke
from repro.models.common import YaRN
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab_size=102400, max_seq_len=163840,
    use_mla=True, kv_lora_rank=512, q_lora_rank=0,
    rope_head_dim=64, nope_head_dim=128, v_head_dim=128,
    n_experts=64, experts_per_token=6, moe_d_ff=1408, norm_topk_prob=False,
    n_shared_experts=2, shared_d_ff=1408,
    first_dense_layers=1, dense_d_ff=10944,
    rope_theta=10_000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    norm_eps=1e-6, tie_embeddings=False,
)

SPEC = ArchSpec(
    arch_id="deepseek-v2-lite", config=CONFIG,
    # one dense and one expert layer: the smallest model with both kinds
    smoke=reduce_for_smoke(CONFIG, n_layers=2),
    source="[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite; hf]",
    long_context_ok=False,
    notes="Served on one chip as the chip share of 8-way expert "
          "parallelism: the router spans all 64 experts, the chip holds "
          "and computes 8 of them (ModelConfig.held_experts).",
)
