"""Tier-1 guard: NO model family may silently select the XLA fallback when
the pallas path is requested.

Instantiates every family ops/attention.py serves through the config
detection in models/llama.py (llama, qwen2, mistral, gemma 1/2/3, mixtral)
at tiny sizes, runs one prefill + one decode step per family with
attn_impl="pallas_interpret", and counts trace-time entries into the
kernel programs. A future kernel regression that re-introduces a
feature-based punt (the pre-PR-2 behavior: any layer with window/scale/
softcap fell back to the dense gather) fails THIS test loudly instead of
silently serving Mistral/Gemma at O(context) KV traffic per step.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import llama as L
from dynamo_tpu.ops import pallas_attention as PA

_TINY = {
    "vocab_size": 128,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "max_position_embeddings": 256,
}

FAMILIES = {
    "llama": {"model_type": "llama", **_TINY},
    "qwen2": {"model_type": "qwen2", **_TINY,
              "sliding_window": 64, "use_sliding_window": False},
    "mistral": {"model_type": "mistral", **_TINY, "sliding_window": 16},
    "gemma": {"model_type": "gemma", **_TINY},
    "gemma2": {"model_type": "gemma2", **_TINY, "num_hidden_layers": 4,
               "sliding_window": 16, "attn_logit_softcapping": 50.0,
               "final_logit_softcapping": 30.0,
               "query_pre_attn_scalar": 16.0},
    "gemma3": {"model_type": "gemma3_text", **_TINY,
               "num_hidden_layers": 6, "sliding_window": 16,
               "sliding_window_pattern": 6,
               "rope_local_base_freq": 10_000.0,
               "query_pre_attn_scalar": 16.0},
    "mixtral": {"model_type": "mixtral", **_TINY,
                "num_local_experts": 4, "num_experts_per_tok": 2},
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_never_falls_back_to_xla(family, monkeypatch):
    cfg = L.LlamaConfig.from_hf_dict(FAMILIES[family])
    cfg = dataclasses.replace(cfg, attn_impl="pallas_interpret")
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    counts = {"prefill": 0, "decode": 0}
    real_p = PA.flash_prefill_attention_pallas
    real_d = PA.paged_decode_attention_pallas

    def count_p(*a, **kw):
        counts["prefill"] += 1
        return real_p(*a, **kw)

    def count_d(*a, **kw):
        counts["decode"] += 1
        return real_d(*a, **kw)

    monkeypatch.setattr(PA, "flash_prefill_attention_pallas", count_p)
    monkeypatch.setattr(PA, "paged_decode_attention_pallas", count_d)

    bs, nb, P = 8, 12, 16
    cache_shape = (cfg.num_layers, cfg.num_kv_heads, nb, bs, cfg.head_dim)
    kc = layer_caches(cache_shape, jnp.float32)
    vc = layer_caches(cache_shape, jnp.float32)
    tokens = jnp.arange(P, dtype=jnp.int32) % cfg.vocab_size
    table = jnp.arange(1, 1 + P // bs, dtype=jnp.int32)
    logits, kc, vc = L.prefill(params, cfg, tokens, jnp.int32(P), kc, vc, table)
    assert counts["prefill"] == cfg.num_layers, (
        f"{family}: {cfg.num_layers - counts['prefill']} prefill layer(s) "
        "silently took the XLA fallback under impl=pallas_interpret"
    )
    assert np.isfinite(np.asarray(logits)).all()

    bt = jnp.tile(jnp.arange(1, nb, dtype=jnp.int32)[None, :], (2, 1))
    positions = jnp.array([P, P], jnp.int32)
    slots = bt[jnp.arange(2), positions // bs] * bs + positions % bs
    logits_d, kc, vc = L.decode(
        params, cfg, jnp.array([1, 2], jnp.int32), positions, kc, vc, bt,
        slots,
    )
    assert counts["decode"] == cfg.num_layers, (
        f"{family}: {cfg.num_layers - counts['decode']} decode layer(s) "
        "silently took the XLA fallback under impl=pallas_interpret"
    )
    assert np.isfinite(np.asarray(logits_d)).all()


def test_family_feature_detection_sanity():
    """The families exercise the distinct feature combinations the guard
    claims coverage of (a regression in config detection would otherwise
    quietly weaken the kernel guard)."""
    mistral = L.LlamaConfig.from_hf_dict(FAMILIES["mistral"])
    assert mistral.sliding_window == 16 and mistral.layer_pattern is None
    qwen2 = L.LlamaConfig.from_hf_dict(FAMILIES["qwen2"])
    assert qwen2.sliding_window is None  # use_sliding_window=false
    g2 = L.LlamaConfig.from_hf_dict(FAMILIES["gemma2"])
    assert g2.attn_logit_softcap == 50.0 and g2.attn_scale is not None
    assert g2.layer_pattern is not None and any(g2.layer_pattern)
    g3 = L.LlamaConfig.from_hf_dict(FAMILIES["gemma3"])
    assert g3.layer_pattern == (True,) * 5 + (False,)
    assert g3.rope_local_theta == 10_000.0
    mixtral = L.LlamaConfig.from_hf_dict(FAMILIES["mixtral"])
    assert mixtral.num_experts == 4
