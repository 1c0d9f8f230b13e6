"""kimi-vl-a3b — Kimi-VL-A3B-Instruct, trained as one chip's share.

[hf:moonshotai/Kimi-VL-A3B-Instruct config.json; Kimi-VL technical report,
arXiv:2504.07491]

Published language model (the DeepSeek-V3 block, as Moonlight-16B-A3B):
27 layers at hidden 2,048, the first dense (SwiGLU 11,264), the other 26
MoE; MLA with ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128, 16 heads, ``q_lora_rank``
null; 64 routed experts of width 1,408, 6 a token, sigmoid scores with a
correction bias in the choice (``noaux_tc``, one group), the chosen scores
normalised and scaled by 2.446; 2 shared experts; ``seq_aux``; vocabulary
163,840, untied; ``rope_theta`` 800,000; ``rms_norm_eps`` 1e-5.

Vision tower (MoonViT; not in the catalog row, so assumed from the report
and the model's published ``vision_config``): patch 14, hidden 1,152, 16
heads of 72, GELU (tanh) MLP of 4,304, pre-LayerNorm blocks, a learned
64x64 position table bicubically resized to the patch grid, 2D RoPE (theta
10,000; half the rotation pairs on the column, half on the row), 27 layers;
a 2x2 merge to 4,608, then LayerNorm(1,152), Linear 4,608->4,608, GELU,
Linear ->2,048.

The deployment this configuration is one chip of: 8 chips share each layer.
Each MoE layer's 64 experts are split 8 a chip (expert parallelism; the
router keeps all 64 outputs and its top-6, and this chip computes experts
0-7), the vocabulary is sliced 8 ways (ids, logits and loss over the
slice), and the layers left out lie on further pipeline stages. Cut here:
27 -> 5 LM layers (1 dense + 4 MoE, the floor), 64 -> 8 held experts,
163,840 -> 20,480 vocabulary rows, 27 -> 4 vision layers. No width is cut.

Assumed: 448x448 frames (32x32 patches, 256 image tokens after the merge,
at positions 0-255); the bias rule of DeepSeek-V3 with gamma 0.001; the
sequence-wise balance loss with alpha 1e-4; LayerNorm eps 1e-5.

Precision: float32 parameters and AdamW state; bfloat16 matmul inputs with
float32 accumulation; float32 norms, softmax, router and loss.

Training only: no decode path for MLA yet.
"""
import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-vl-a3b",
    family="vlm",
    num_layers=5,                 # published 27
    first_dense_layers=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,                 # v_head_dim (MLA sets the q/k widths)
    d_ff=11_264,
    vocab_size=20_480,            # published 163,840; one eighth
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    experts_per_token=6,
    experts_held=8,               # experts 0-7 of the router's 64
    moe_d_ff=1408,
    num_shared_experts=2,
    router_scoring="sigmoid",
    routed_scaling_factor=2.446,
    bias_update_rate=1e-3,
    seq_aux_weight=1e-4,
    rope_theta=800_000.0,
    norm_eps=1e-5,
    num_image_tokens=256,
    vision_layers=4,              # published 27
    vision_d_model=1152,
    vision_heads=16,
    vision_d_ff=4304,
    vision_patch=14,
    vision_pos_grid=64,
    vision_rope_theta=10_000.0,
    vision_merge=2,
    image_hw=448,
    act="silu",
    param_dtype="float32",
    compute_dtype="bfloat16",
    sub_quadratic=False,
)

# CPU size: every layer kind, the router over more experts than are held,
# float32 throughout so that the tests meet the reference at round-off.
SMOKE = dataclasses.replace(
    CONFIG, num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
    d_ff=96, vocab_size=256, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=8, experts_per_token=3,
    experts_held=4, moe_d_ff=32, num_image_tokens=4, vision_layers=2,
    vision_d_model=32, vision_heads=4, vision_d_ff=48, vision_patch=4,
    vision_pos_grid=6, image_hw=16, compute_dtype="float32", is_smoke=True,
    remat=False)
