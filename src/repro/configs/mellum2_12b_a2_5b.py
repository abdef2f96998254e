"""Mellum2-12B-A2.5B-Instruct [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]:
JetBrains' code model.  28 layers, each a sparse MoE (64 experts, top-8,
expert width 896, SiLU-gated, renormalized top-k, no shared expert);
GQA 32/4 with heads of 128; attention kinds sliding, sliding, sliding,
full, repeated, the sliding layers windowed at 1024 with plain RoPE and
the full layers under YaRN (factor 16 over 8192 positions); RoPE theta
500000 on both, RMSNorm eps 1e-6, untied head.  The MTP head of the
family has no key in the config and is not served.  Assumed where the
config is silent: no q/k norm, a softmax router."""
from repro.models.common import ModelConfig

PERIOD = ("sliding", "sliding", "sliding", "full")


def full() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b-a2.5b", family="moe", vocab=98304, d_model=2304,
        n_layers=28, n_heads=32, n_kv=4, d_head=128, d_ff=7168,
        act="swiglu", norm="rmsnorm", norm_eps=1e-6, pos="rope",
        rope_theta=500000.0, window=1024, layer_types=PERIOD,
        yarn_factor=16.0, yarn_original_max=8192, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_attention_factor=1.2772588722239782,
        n_experts=64, top_k=8, moe_ffn=896, tie_embeddings=False,
        max_seq=131072)


def smoke() -> ModelConfig:
    """One period (S S S F), 8 experts top-2, window 8, YaRN factor 4
    over 16 positions: every mechanism at a size a CPU test runs."""
    return ModelConfig(
        name="mellum2-smoke", family="moe", vocab=256, d_model=64,
        n_layers=4, n_heads=4, n_kv=2, d_head=16, d_ff=128, act="swiglu",
        norm="rmsnorm", norm_eps=1e-6, pos="rope", rope_theta=500000.0,
        window=8, layer_types=PERIOD, yarn_factor=4.0, yarn_original_max=16,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0, n_experts=8, top_k=2,
        moe_ffn=32, tie_embeddings=False, attn_chunk=8, max_seq=512)
