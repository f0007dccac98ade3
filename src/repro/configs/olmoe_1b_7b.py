"""olmoe-1b-7b [moe]: 16L d_model=2048 16H (MHA, kv=16) head_dim 128,
every layer a sparse MoE of 64 SiLU-gated experts of width 1024, 8 per
token, softmax router without top-k renormalisation, no shared expert;
RMSNorm (eps 1e-5) over the whole q and k projections before the head
split; RoPE theta 1e4, vocab 50304, untied embeddings, 4096 positions.
[arXiv:2409.02060; hf:allenai/OLMoE-1B-7B-0924 config.json]

The published model is dropless.  Training and the dry-runs use the
capacity formulation (``capacity_factor``); the serving cut
(``olmoe_1b_7b_ep8``) runs the dropless layer.

long_500k: skipped -- pure full attention (see DESIGN.md).
"""

from repro.configs.base import ArchConfig, BlockCfg

CONFIG = ArchConfig(
    name="olmoe-1b-7b",
    family="moe",
    n_layers=16,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=1024,
    vocab_size=50304,
    period=(BlockCfg(mixer="attn", use_moe=True),),
    moe_experts=64,
    moe_topk=8,
    moe_norm_topk=False,
    capacity_factor=1.25,
    qk_norm=True,
    qk_norm_mode="full",
    norm_eps=1e-5,
    ffn_activation="silu",
    tied_embeddings=False,
    rope_theta=10000.0,
    param_dtype="float32",
    compute_dtype="bfloat16",
    supported_shapes=("train_4k", "prefill_32k", "decode_32k"),
    microbatch={"train_4k": 4},
)
