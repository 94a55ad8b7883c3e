"""DeepSeek-V2-Lite [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

27L, d_model 2048, vocab 102400, embeddings not tied, RMSNorm eps 1e-6.
MLA with 16 heads and no query LoRA (a direct query projection),
kv_lora_rank 512, qk_nope 128, qk_rope 64 (interleaved rotary pairs),
v_head 128; YaRN rope (factor 40 over 4096 positions, theta 1e4,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707).
Layer 0 is a dense SwiGLU of width 10944; every other layer has 64 routed
experts of width 1408, top-6 softmax gates with no renormalisation
(``norm_topk_prob`` false, ``routed_scaling_factor`` 1), and 2 shared
experts. FULL holds all 64 experts, dropless; a chip's share of an
expert-parallel layer holds fewer (``MoEDims.experts_held``).
"""

from .base import ArchConfig, register
from ..models.layers import Yarn
from ..models.mla import MLADims
from ..models.moe import MoEDims

YARN = Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

FULL = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab_size=102400,
    mla=MLADims(d_model=2048, n_heads=16, q_lora_rank=None,
                kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                v_head_dim=128, rope_interleaved=True, yarn=YARN),
    moe=MoEDims(d_model=2048, n_experts=64, top_k=6, d_expert=1408,
                n_shared=2, norm_topk=False, experts_held=(0, 64)),
    moe_first_dense=1, moe_dense_ff=10944,
    rope_theta=1e4,
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)

SMOKE = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=32, vocab_size=128,
    mla=MLADims(d_model=64, n_heads=4, q_lora_rank=None, kv_lora_rank=16,
                qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8,
                rope_interleaved=True,
                yarn=Yarn(factor=4.0, original_max_position=16,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707)),
    moe=MoEDims(d_model=64, n_experts=8, top_k=3, d_expert=32, n_shared=1,
                norm_topk=False, experts_held=(2, 4)),
    moe_first_dense=1, moe_dense_ff=128,
    rope_theta=1e4,
)

register(FULL, SMOKE)
