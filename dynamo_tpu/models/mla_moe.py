"""Latent-attention, sparse-expert family (JoyAI-LLM-Flash; DeepSeek-V3's
forms) as pure JAX functions over a paged *latent* cache.

A second block beside `models/llama.py`'s, with a config and a forward of
its own (ROADMAP D3: the bag of flags on `LlamaConfig` should not grow); it
shares `ops/`, the runner, the engine and the cache manager. The forward
functions have llama's names and signatures; `v_cache` is an empty tuple
here, because a layer keeps one plane (`cache_kind`).

The block (pre-norm residual, RMS norms, no biases):

* attention: `cq = norm(x Wqa)`; `q = cq Wqb` as heads of `[q_nope | q_pe]`;
  `[ckv | k_pe] = x Wkva`, `ckv = norm(ckv)`; rope on `q_pe` and `k_pe`
  rotates adjacent pairs; `k_pe` is one vector a token for all heads; per
  head `[k_nope | v] = ckv Wkvb`; scores `(q_nope.k_nope + q_pe.k_pe) /
  sqrt(nope + rope)`, causal softmax, `Wo`. Cached per token: `[ckv | k_pe]`.
  Which program uses the per-head form and which the absorbed one is said in
  `ops/mla.py`.
* layers below `first_k_dense`: SwiGLU of `intermediate_size`. The others:
  `s = sigmoid(x Wr)` in float32; the k experts with the largest `s + b`
  (`b` a correction bias, selection only); weights `scale * s_sel / (sum
  s_sel + 1e-20)`; `y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)`. Dropless:
  `ops/moe.dropless_experts`, the single-chip path.
* untied embedding and head, final RMS norm.

Not served and refused in words where asked for: group-limited routing
(`n_group` > 1), rope scaling, softmax scoring, int8 weights, a mesh. The
multi-token-prediction module (`num_nextn_predict_layers`) takes no part in
the next-token logits and is ignored, with a log line (ROADMAP M5).
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models import CacheKind, latent_cache, layer_body, programs
from dynamo_tpu.models.programs import Body, Family
from dynamo_tpu.ops import mla
from dynamo_tpu.ops.basics import rms_norm, rope_freqs, swiglu
from dynamo_tpu.ops.kv_quant import scatter_token_rows
from dynamo_tpu.ops.linear import linear
# `STEP_STATS` is read off the family's module by the runner (`decode_multi`)
from dynamo_tpu.ops.moe import (  # noqa: F401
    STEP_STATS, dropless_experts, expert_step_stats, router_sigmoid_topk,
)
from dynamo_tpu.runtime.logging import get_logger

logger = get_logger("dynamo_tpu.models.mla_moe")

MODEL_TYPES = ("joyai_llm_flash",)


@dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 768
    num_layers: int = 40
    first_k_dense: int = 1
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rms_eps: float = 1e-6
    max_position_embeddings: int = 131072
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False
    # set by the runner (`dataclasses.replace`), as on LlamaConfig
    attn_impl: Optional[str] = None
    fused_decode: bool = False
    collective_overlap: bool = False

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "MlaMoeConfig":
        unsupported = {
            "n_group": d.get("n_group", 1) not in (None, 1),
            "topk_group": d.get("topk_group", 1) not in (None, 1),
            "rope_scaling": d.get("rope_scaling") is not None,
            "scoring_func": d.get("scoring_func", "sigmoid") != "sigmoid",
            "attention_bias": bool(d.get("attention_bias", False)),
            "q_lora_rank": not d.get("q_lora_rank"),
            "rope_interleave": not d.get("rope_interleave", True),
            "moe_layer_freq": d.get("moe_layer_freq", 1) != 1,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"model_type {d.get('model_type')!r}: this value of {bad} is "
                "not implemented (served: one routing group, no rope scaling, "
                "sigmoid scores, no attention bias, a query bottleneck, "
                "interleaved rope, experts in every layer after the dense ones)"
            )
        if d.get("num_nextn_predict_layers"):
            logger.info(
                "multi-token prediction module (%d layer(s) behind the %d "
                "served) takes no part in the next-token logits: not served",
                d["num_nextn_predict_layers"], d["num_hidden_layers"],
            )
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            moe_intermediate_size=d["moe_intermediate_size"],
            num_layers=d["num_hidden_layers"],
            first_k_dense=d.get("first_k_dense_replace", 0),
            num_heads=d["num_attention_heads"],
            q_lora_rank=d["q_lora_rank"],
            kv_lora_rank=d["kv_lora_rank"],
            qk_nope_head_dim=d["qk_nope_head_dim"],
            qk_rope_head_dim=d["qk_rope_head_dim"],
            v_head_dim=d["v_head_dim"],
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_eps=float(d.get("rms_norm_eps", 1e-6)),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            n_routed_experts=d["n_routed_experts"],
            num_experts_per_tok=d["num_experts_per_tok"],
            n_shared_experts=d.get("n_shared_experts", 0) or 0,
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        )

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "MlaMoeConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    @classmethod
    def tiny(cls, vocab_size: int = 256) -> "MlaMoeConfig":
        """CPU-test size of the same structure: a leading dense layer, a
        shared expert, a correction bias, a scale, a query bottleneck."""
        return cls(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_layers=3, first_k_dense=1,
            num_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=10000.0, max_position_embeddings=512,
            n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
        )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_head_dim)

    def is_moe_layer(self, i: int) -> bool:
        return i >= self.first_k_dense

    def cache_kind(self) -> CacheKind:
        return latent_cache(self.kv_lora_rank + self.qk_rope_head_dim)


CONFIG = MlaMoeConfig  # `models.served_model_types` reads it


# ------------------------------------------------------------------ params

KEYS_PER_LAYER = 16


def refuse_int8_weights(quantize: bool) -> None:
    if quantize:
        raise ValueError(
            "int8 weights (DYN_JAX_QUANTIZE_INT8) are not implemented for "
            "expert stacks: serve this family in bfloat16"
        )


def init_params(
    config: MlaMoeConfig,
    rng: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    quantize: bool = False,
) -> dict:
    """Random weights: normal / sqrt(fan_in) in float32, cast to `dtype`;
    the correction bias normal * 0.01, so that selection by score + bias
    differs from selection by score (for six tokens in ten at 256 experts)
    and the experts' loads stay what the router's own scores make them: at
    the top-8 cut of 256 a sigmoid score moves 0.12 a unit of logit, so a
    bias is worth eight times itself in logits, and one of 0.3 would put
    its expert into a third of all tokens (PERF.md section 6, PR 28).
    `cellbench/reference/mla_moe.py` makes the same draw from the same key,
    on its own."""
    refuse_int8_weights(quantize)
    c = config
    keys = iter(jax.random.split(rng, 4 + KEYS_PER_LAYER * c.num_layers))

    def dense(shape, fan_in):
        w = jax.random.normal(next(keys), shape, dtype=jnp.float32)
        return (w / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    H, Hq = c.hidden_size, c.num_heads
    F, E = c.moe_intermediate_size, c.n_routed_experts
    layers = []
    for i in range(c.num_layers):
        layer = {
            "attn_norm": jnp.ones((H,), dtype),
            "wq_a": dense((H, c.q_lora_rank), H),
            "q_norm": jnp.ones((c.q_lora_rank,), dtype),
            "wq_b": dense((c.q_lora_rank, Hq * c.qk_head_dim), c.q_lora_rank),
            "wkv_a": dense((H, c.kv_lora_rank + c.qk_rope_head_dim), H),
            "kv_norm": jnp.ones((c.kv_lora_rank,), dtype),
            "wkv_b": dense(
                (c.kv_lora_rank, Hq * (c.qk_nope_head_dim + c.v_head_dim)),
                c.kv_lora_rank,
            ),
            "wo": dense((Hq * c.v_head_dim, H), Hq * c.v_head_dim),
            "mlp_norm": jnp.ones((H,), dtype),
        }
        if c.is_moe_layer(i):
            layer.update(
                router=dense((H, E), H),
                router_bias=0.01 * jax.random.normal(next(keys), (E,), jnp.float32),
                wg=dense((E, H, F), H),
                wu=dense((E, H, F), H),
                wd=dense((E, F, H), F),
            )
            if c.n_shared_experts:
                S = F * c.n_shared_experts
                layer.update(
                    sg=dense((H, S), H), su=dense((H, S), H), sd=dense((S, H), S),
                )
        else:
            I = c.intermediate_size
            layer.update(
                wg=dense((H, I), H), wu=dense((H, I), H), wd=dense((I, H), I),
            )
        layers.append(layer)
    params = {
        "embed": (
            jax.random.normal(next(keys), (c.vocab_size, H), jnp.float32) * 0.02
        ).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = dense((H, c.vocab_size), H)
    return params


def param_count(config: MlaMoeConfig) -> int:
    c = config
    H, Hq = c.hidden_size, c.num_heads
    attn = (
        H * c.q_lora_rank
        + c.q_lora_rank * Hq * c.qk_head_dim
        + H * (c.kv_lora_rank + c.qk_rope_head_dim)
        + c.kv_lora_rank * Hq * (c.qk_nope_head_dim + c.v_head_dim)
        + Hq * c.v_head_dim * H
    )
    vectors = 2 * H + c.q_lora_rank + c.kv_lora_rank
    expert = 3 * H * c.moe_intermediate_size
    moe = (
        (c.n_routed_experts + c.n_shared_experts) * expert
        + H * c.n_routed_experts + c.n_routed_experts
    )
    n_moe = max(0, c.num_layers - c.first_k_dense)
    n_dense = c.num_layers - n_moe
    embed = c.vocab_size * H * (1 if c.tie_word_embeddings else 2)
    return (
        c.num_layers * (attn + vectors)
        + n_dense * 3 * H * c.intermediate_size
        + n_moe * moe + embed + H
    )


def expert_param_count(config: MlaMoeConfig) -> int:
    """Parameters in routed expert stacks (what an `ep` share would divide)."""
    c = config
    n_moe = max(0, c.num_layers - c.first_k_dense)
    return n_moe * c.n_routed_experts * 3 * c.hidden_size * c.moe_intermediate_size


# ----------------------------------------------------------------- forward


def _up_projection(layer, cfg):
    """Wkvb as (keys [C, Hq, nope], values [C, Hq, v])."""
    w = layer["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _latent_head(x, layer, cfg, inv_freqs, positions):
    """The projections in front of attention. Returns q_nope [T, Hq, nope],
    q_pe [T, Hq, rope] (rotated) and the row to cache [T, stored_width]."""
    T = x.shape[0]
    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    cq = rms_norm(linear(h, layer["wq_a"]), layer["q_norm"], cfg.rms_eps)
    q = linear(cq, layer["wq_b"]).reshape(T, cfg.num_heads, cfg.qk_head_dim)
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_pe = mla.rope_interleaved(q[..., cfg.qk_nope_head_dim:], positions, inv_freqs)
    kv = linear(h, layer["wkv_a"])
    ckv = rms_norm(kv[:, : cfg.kv_lora_rank], layer["kv_norm"], cfg.rms_eps)
    k_pe = mla.rope_interleaved(kv[:, cfg.kv_lora_rank:], positions, inv_freqs)
    kind = cfg.cache_kind()
    row = jnp.concatenate(
        [ckv, k_pe, jnp.zeros((T, kind.stored_width - kind.width), ckv.dtype)],
        axis=-1,
    )
    return q_nope, q_pe, row


def _absorbed_query(q_nope, q_pe, layer, cfg):
    """[q' | q_pe | 0] as wide as a cached row: q' = q_nope Wkvb_k^T."""
    wk, _ = _up_projection(layer, cfg)
    q_abs = jnp.einsum("thd,chd->thc", q_nope, wk.astype(q_nope.dtype))
    kind = cfg.cache_kind()
    pad = jnp.zeros(q_pe.shape[:-1] + (kind.stored_width - kind.width,), q_pe.dtype)
    return jnp.concatenate([q_abs, q_pe, pad], axis=-1)


def _attn_out(o_latent, x, layer, cfg):
    """o = o' Wkvb_v per head, then Wo and the residual."""
    _, wv = _up_projection(layer, cfg)
    o = jnp.einsum("thc,chd->thd", o_latent, wv.astype(o_latent.dtype))
    return x + linear(o.reshape(o.shape[0], -1), layer["wo"])


def _write_rows(plane, row, slot_indices):
    return scatter_token_rows(plane, row[:, None, :], slot_indices)


def _ffn(x, layer, cfg, valid):
    """Returns x and what an expert layer counted (`STEP_STATS`; None for a
    dense layer)."""
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    if "router" not in layer:
        return x + linear(swiglu(linear(h, layer["wg"]), linear(h, layer["wu"])), layer["wd"]), None
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(
            h.astype(jnp.float32), layer["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        idx, weights = router_sigmoid_topk(
            logits, layer["router_bias"], cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor, renormalize=cfg.norm_topk_prob,
        )
    with jax.named_scope("moe.experts"):
        y, group_sizes = dropless_experts(
            h, idx, weights, layer["wg"], layer["wu"], layer["wd"], valid=valid,
            impl=cfg.attn_impl,
        )
    counted = expert_step_stats(group_sizes)
    if "sg" in layer:
        with jax.named_scope("moe.shared"):
            y = y + linear(
                swiglu(linear(h, layer["sg"]), linear(h, layer["su"])), layer["sd"]
            ).astype(jnp.float32)
    return x + y.astype(x.dtype), counted


def _inv_freqs(cfg):
    return rope_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, None)


# One body for each kind of pass over the layers, called once a layer by the
# forwards below (`models.layer_body`: traced and lowered once a program for
# each kind of layer, the dense one and the expert one, which differ by their
# keys). A body returns x, the layer's plane and what `_ffn` counted; the
# caller keeps the counters where it was given a list (`stats`).


@layer_body("cfg")
def _packed_layer(x, layer, plane, inv_freqs, positions, segment_ids, slot_indices, *, cfg):
    q_nope, q_pe, row = _latent_head(x, layer, cfg, inv_freqs, positions)
    plane = _write_rows(plane, row, slot_indices)
    with jax.named_scope("mla.attend"):
        wk, wv = _up_projection(layer, cfg)
        ckv = row[:, : cfg.kv_lora_rank]
        k_pe = row[:, cfg.kv_lora_rank: cfg.kv_lora_rank + cfg.qk_rope_head_dim]
        k = jnp.concatenate([
            jnp.einsum("tc,chd->thd", ckv, wk.astype(ckv.dtype)),
            jnp.broadcast_to(k_pe[:, None, :], q_pe.shape),
        ], axis=-1)
        v = jnp.einsum("tc,chd->thd", ckv, wv.astype(ckv.dtype))
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        o = mla.packed_attention(q, k, v, segment_ids, cfg.attn_scale)
        x = x + linear(o.reshape(o.shape[0], -1), layer["wo"])
    x, counted = _ffn(x, layer, cfg, segment_ids >= 0)
    return x, plane, counted


@layer_body("cfg")
def _chunk_layer(x, layer, plane, inv_freqs, positions, valid, slots, block_table, chunk_start, *, cfg):
    q_nope, q_pe, row = _latent_head(x, layer, cfg, inv_freqs, positions)
    plane = _write_rows(plane, row, slots)
    with jax.named_scope("mla.attend"):
        o = mla.chunk_attention(
            _absorbed_query(q_nope, q_pe, layer, cfg), plane, block_table,
            chunk_start, value_width=cfg.kv_lora_rank, scale=cfg.attn_scale,
        )
        x = _attn_out(o, x, layer, cfg)
    x, counted = _ffn(x, layer, cfg, valid)
    return x, plane, counted


@layer_body("cfg")
def _decode_layer(x, layer, plane, inv_freqs, positions, live, context, block_tables, slot_indices, *, cfg):
    q_nope, q_pe, row = _latent_head(x, layer, cfg, inv_freqs, positions)
    plane = _write_rows(plane, row, slot_indices)
    with jax.named_scope("mla.attend"):
        q = _absorbed_query(q_nope, q_pe, layer, cfg)
    # outside the scope: a Pallas call takes its instruction's name from
    # the scope it is in, and the benchmark finds attention kernels as
    # unnamed `custom-call`s, which the grouped-query kernel is
    o = mla.decode_attention(
        q, plane, block_tables, context, value_width=cfg.kv_lora_rank,
        scale=cfg.attn_scale, impl=cfg.attn_impl,
    )
    with jax.named_scope("mla.attend"):
        x = _attn_out(o, x, layer, cfg)
    x, counted = _ffn(x, layer, cfg, live)
    return x, plane, counted


# Every layer alike: it keeps one plane `[1, num_blocks, block_size,
# stored_width]` and nothing beside it (`v_cache` is `()`). Fresh prompts
# attend in the per-head form; a chunk and a decode step in the absorbed form
# over everything the cache holds.
FAMILY = Family(
    kind=lambda cfg, i: "latent",
    behind_embedding={"inv_freqs": _inv_freqs},
    packed={"latent": Body(_packed_layer, 1, (
        "inv_freqs", "positions", "segment_ids", "slot_indices"))},
    chunk={"latent": Body(_chunk_layer, 1, (
        "inv_freqs", "positions", "valid", "slot_indices", "block_table",
        "chunk_start"))},
    decode={"latent": Body(_decode_layer, 1, (
        "inv_freqs", "positions", "live", "context", "block_tables",
        "slot_indices"))},
)
prefill_packed, prefill, prefill_chunk, decode = programs.bound(FAMILY)
prefill_mm, prefill_context_parallel, embed_pooled, decode_verify = programs.refused(
    "the latent-attention family"
)
