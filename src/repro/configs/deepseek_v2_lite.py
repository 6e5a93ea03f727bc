"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, MLA (kv_lora=512, no
q_lora, qk_nope 128, qk_rope 64, v_head 128) with YaRN rope scaling (factor
40 over an original 4096 positions, beta_fast 32, beta_slow 1, mscale and
mscale_all_dim 0.707); layer 0 dense (d_ff=10944), layers 1-26 MoE: 64
routed experts of d_ff=1408, softmax router, greedy top-6 without
renormalisation (routed_scaling_factor 1), 2 shared experts; vocab=102400,
untied head.  Parameters are held in bfloat16, as the published checkpoint
is (``lm_init(..., dtype=jnp.bfloat16)``).
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]

``make_full(held=(first, count))`` serves the share of each MoE layer that
one chip of an expert-parallel deployment holds: the router keeps all 64
outputs and its 6 picks, this chip runs only ``count`` experts from
``first`` on (``repro.nn.moe``)."""
from repro.configs import Arch
from repro.configs.common import deepseek_lm
from repro.nn.rotary import YaRN

YARN = YaRN(factor=40.0, original_max_positions=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def make_full(window=None, remat=False, held=None):
    return deepseek_lm("deepseek-v2-lite", layers=27, dense_layers=1,
                       d_model=2048, n_heads=16, vocab=102400,
                       moe_d_ff=1408, dense_d_ff=10944, n_experts=64,
                       top_k=6, n_shared=2, kv_lora_rank=512,
                       q_lora_rank=0, qk_nope_dim=128, qk_rope_dim=64,
                       v_head_dim=128, window=window, remat=remat,
                       yarn=YARN, norm_topk_prob=False, held=held)


def make_smoke(held=None):
    return deepseek_lm("deepseek-v2-lite-smoke", layers=3, dense_layers=1,
                       d_model=64, n_heads=4, vocab=256, moe_d_ff=32,
                       dense_d_ff=128, n_experts=8, top_k=3, n_shared=2,
                       kv_lora_rank=32, q_lora_rank=0, qk_nope_dim=16,
                       qk_rope_dim=8, v_head_dim=16, yarn=YARN,
                       norm_topk_prob=False, held=held)


ARCH = Arch(name="deepseek-v2-lite", family="moe", cite="arXiv:2405.04434",
            make_full=make_full, make_smoke=make_smoke)
