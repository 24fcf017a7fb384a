"""Mixtral family (Mixtral-8x7B/-8x22B, tiny MoE test configs).

The MoE analogue of the reference's SGLang WideEP deployments
(examples/sglang dsr1-wideep.md: dp-attention + deepep-moe on 104 GPUs):
here a Mixtral-style model is a LlamaConfig with num_experts > 0 — the
attention stack, paged cache, context-parallel prefill, and engine are
shared with the dense family (models/llama.py), the FFN routes through
ops/moe.py (dropless sort and grouped products; under an `ep` mesh axis the
experts shard over it and tokens reach them by all-to-all or psum).

This module is the HF-facing front-end: config presets + weight loading
glue for `model_type: mixtral` checkpoints.
"""

from __future__ import annotations

from dynamo_tpu.models.llama import (  # noqa: F401 — re-exported surface
    LlamaConfig,
    decode,
    init_params,
    prefill,
    prefill_context_parallel,
)

MixtralConfig = LlamaConfig  # one unified family; num_experts>0 == MoE


def mixtral_8x7b() -> LlamaConfig:
    """Mixtral-8x7B-v0.1 shapes (HF mistralai/Mixtral-8x7B)."""
    return LlamaConfig(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1e6,
        max_position_embeddings=32768,
        num_experts=8,
        num_experts_per_tok=2,
    )


def tiny_moe(vocab_size: int = 256, num_experts: int = 4) -> LlamaConfig:
    """CPU-test MoE config (the mocker-style all-logic-no-scale shape)."""
    return LlamaConfig(
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        max_position_embeddings=512,
        num_experts=num_experts,
        num_experts_per_tok=2,
    )
