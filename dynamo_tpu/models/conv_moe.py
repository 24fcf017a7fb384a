"""Short-convolution, sparse-expert family (`model_type: lfm2_moe`, Liquid
AI's LFM2-8B-A1B): gated short convolutions with a grouped-query attention
layer among every few, a routed feed-forward behind every layer but the
leading dense ones, as pure JAX functions over two kinds of cache side by
side.

A fourth block beside `models/llama.py`'s, `models/mla_moe.py`'s and
`models/hybrid_ssm.py`'s, with a config and forwards of its own under the
family's names and signatures; it shares `ops/`, the runner, the engine and
the cache manager. What a layer keeps is what it declares
(`layer_cache_kinds`): an attention layer rows per token in paged blocks, a
convolution layer one slot a sequence of ONE array, the last `conv_L_cache -
1` gated inputs (`[(L - 1) * hidden]` in the model's dtype, oldest first:
`ops/ssm.py`'s tail). The slot rides where a paged layer's keys do (`k_cache[i]`, one row a
lane and one more, the null lane's); nothing rides where its values would
(`v_cache[i]` is None). A decode lane's slot is its row in the batch; prefill
programs are told each sequence's slot (`state_slots`), start it from zeros at
the sequence's position 0 and leave the tail there between the chunks of a
chunked prefill, as the Mamba tail is left.

The block, from the published config and Hugging Face's `Lfm2Moe*` classes
(pre-norm residual, RMS norms with a learned weight, `eps = norm_eps`, no
bias anywhere, the head tied to the embedding, a final RMS norm):
`h = h + op_i(norm(h)); h = h + ff_i(norm(h))`.

* `op_i`, `layer_types[i] == "conv"`: `(B, C, x) = split3(W_in u)`; `g = B *
  x`; `c_t = sum_k w[k] * g_{t-(L-1)+k}` (depthwise, causal, zeros before a
  sequence's start, no activation); `out = W_out (C * c)`.
* `op_i`, `layer_types[i] == "full_attention"`: grouped-query heads; an RMS
  norm with its own `head_dim`-wide weight over each head of `q` and of `k`
  before the rotary embedding; rotary over the whole head in the half-split
  form at `rope_theta`; causal softmax at `1/sqrt(head_dim)`.
* `ff_i`, `i < num_dense_layers`: `W2(silu(W1 x) * W3 x)` at
  `intermediate_size`. Elsewhere: `s = sigmoid(W_gate x)`; the
  `num_experts_per_tok` experts with the largest `s + expert_bias` (the bias
  takes part in the choice only); weights the chosen `s` over `(their sum +
  1e-6)`, times `routed_scaling_factor`; experts of `moe_intermediate_size`;
  no shared expert. Dropless: `ops/moe.dropless_experts`.

Departures from the public implementation, each said where it is made:

* the gated product `g = B * x` is rounded to the model's dtype
  (`conv_dtype`: bfloat16, config.json's `torch_dtype`) as the public
  implementation's is, and a lane's tail keeps it so (8 KB a layer and lane);
  the convolution's three products and their sum, and the product with `C`,
  are float32 here (`ops/ssm.py`'s arithmetic, shared with the Mamba tail)
  where the public implementation rounds each to bfloat16. Every program
  rounds `g` before it convolves, so a token's output does not depend on
  whether its predecessors came from the slot or from the same chunk. What
  one precision lower would cost the logits is the reference's `fp8_conv`
  control.
* the router's logits and scores are float32 (the public implementation
  multiplies in the model's dtype and takes the sigmoid of that).
* the convolution's taps are `[conv_L_cache, hidden]`, the last tap on the
  newest input (PyTorch's `conv1d` weight `[hidden, 1, L]`, transposed).
* 64-wide heads are cached two to a row of 128 lanes
  (`kv_heads_cache(8, 64, pack=2)`: `ops/attention.py`): the same 2 x 8 x 64
  values a token, in rows the paged kernels can tile.

Not served and refused in words where asked for: a convolution bias, rope
scaling, layer kinds other than the two above, int8 weights, a mesh, an
int8-resident cache, the fused decode step, tiers and transfer of slots,
speculative verification (a rejected draft would need the tail rolled back).
"""

from __future__ import annotations

import functools
import json
import math
import os
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models import (
    CacheKind, kv_heads_cache, layer_body, programs, recurrent_state,
)
from dynamo_tpu.models.programs import Body, Family
from dynamo_tpu.models.programs import dense as _dense, normal as _normal
from dynamo_tpu.ops import ssm
from dynamo_tpu.ops.attention import (
    causal_prefill_attention, chunked_prefill_attention,
    decode_append_attention, packed_prefill_attention, write_decode_kv,
)
from dynamo_tpu.ops.basics import apply_rope, rms_norm, rope_freqs, swiglu
from dynamo_tpu.ops.linear import linear
# `STEP_STATS` is read off the family's module by the runner (`decode_multi`)
from dynamo_tpu.ops.moe import (  # noqa: F401
    STEP_STATS, dropless_experts, expert_step_stats, router_sigmoid_topk,
)

MODEL_TYPES = ("lfm2_moe",)
F32 = jnp.float32
LAYER_KINDS = ("conv", "full_attention")
# the published routing's normaliser: the chosen scores over (their sum + this)
ROUTE_EPS = 1e-6
# lanes of a tile: narrower heads are cached side by side in rows this wide
LANES = 128
CONV_DTYPES = ("bfloat16", "float32")


@dataclass(frozen=True)
class ConvMoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1792
    num_layers: int = 24
    # one kind a layer, a literal list (the published one is not periodic)
    layer_types: tuple = (
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv",
    )
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_L_cache: int = 3
    num_experts: int = 32
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    # the gated product's and the tail's dtype: the model's (`torch_dtype`)
    conv_dtype: str = "bfloat16"
    # set by the runner (`dataclasses.replace`), as on LlamaConfig
    attn_impl: Optional[str] = None
    fused_decode: bool = False
    collective_overlap: bool = False

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "ConvMoeConfig":
        kinds = tuple(d.get("layer_types") or ())
        unsupported = {
            "conv_bias": bool(d.get("conv_bias", False)),
            "rope_scaling": d.get("rope_scaling") is not None,
            "layer_types": (
                len(kinds) != d["num_hidden_layers"]
                or any(k not in LAYER_KINDS for k in kinds)
                or "full_attention" not in kinds or "conv" not in kinds
            ),
            "conv_L_cache": d.get("conv_L_cache", 3) < 2,
            "num_experts": (d.get("num_experts") or 0) < 2,
            "torch_dtype": d.get("torch_dtype", "bfloat16") not in CONV_DTYPES,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"model_type {d.get('model_type')!r}: this value of {bad} is "
                "not implemented (served: no convolution bias, no rope "
                "scaling, a `layer_types` entry for every layer, each "
                f"one of {list(LAYER_KINDS)} and both kinds present, a "
                "convolution over two or more positions, routed experts, "
                f"a torch_dtype of {list(CONV_DTYPES)})"
            )
        hidden, heads = d["hidden_size"], d["num_attention_heads"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=hidden,
            intermediate_size=d["intermediate_size"],
            moe_intermediate_size=d["moe_intermediate_size"],
            num_layers=d["num_hidden_layers"],
            layer_types=kinds,
            num_dense_layers=d.get("num_dense_layers", 0),
            num_heads=heads,
            num_kv_heads=d.get("num_key_value_heads", heads),
            head_dim=d.get("head_dim") or hidden // heads,
            conv_L_cache=d.get("conv_L_cache", 3),
            num_experts=d["num_experts"],
            num_experts_per_tok=d["num_experts_per_tok"],
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            use_expert_bias=bool(d.get("use_expert_bias", True)),
            rope_theta=float(d.get("rope_theta", 1000000.0)),
            rms_eps=float(d.get("norm_eps", 1e-5)),
            max_position_embeddings=d.get("max_position_embeddings", 128000),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", True)),
            conv_dtype=d.get("torch_dtype", "bfloat16"),
        )

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "ConvMoeConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    @classmethod
    def tiny(cls, vocab_size: int = 256) -> "ConvMoeConfig":
        """CPU-test size of the same shape: two leading dense layers, a
        literal `layer_types` that is not periodic with both kinds behind
        them, 8 experts, 4 a token, two KV heads a cached row."""
        return cls(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_layers=6,
            layer_types=(
                "conv", "conv", "full_attention", "conv", "conv",
                "full_attention",
            ),
            num_dense_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            conv_L_cache=3, num_experts=8, num_experts_per_tok=4,
            rope_theta=10000.0, max_position_embeddings=512,
        )

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def kv_pack(self) -> int:
        """KV heads a cached row: as many narrow heads as fill a tile's
        lanes and divide the heads (2 of 64; 1 for heads of 128 or wider)."""
        return max(
            p for p in range(1, max(1, LANES // self.head_dim) + 1)
            if self.num_kv_heads % p == 0
        )

    def is_attn_layer(self, i: int) -> bool:
        return self.layer_types[i] == "full_attention"

    def is_moe_layer(self, i: int) -> bool:
        return i >= self.num_dense_layers

    def tail_kind(self) -> CacheKind:
        return recurrent_state(
            (((self.conv_L_cache - 1) * self.hidden_size,), self.conv_dtype),
        )

    def layer_cache_kinds(self) -> tuple[CacheKind, ...]:
        paged = kv_heads_cache(self.num_kv_heads, self.head_dim, self.kv_pack)
        tail = self.tail_kind()
        return tuple(
            paged if self.is_attn_layer(i) else tail
            for i in range(self.num_layers)
        )


CONFIG = ConvMoeConfig  # `models.served_model_types` reads it


# ------------------------------------------------------------------ params

KEYS_PER_LAYER = 12
# the draw of `expert_bias`: 0.01 x normal, as the latent-attention family
# draws its correction bias and for its reason (models/mla_moe.py
# `init_params`): at the top-4 cut of 32 a sigmoid score moves 0.18 a unit of
# logit, so this is 0.055 of a logit: it changes the chosen set of 18% of
# tokens and leaves the experts' loads within a sixth of their mean (20,000
# unit-norm tokens through a router of the published widths, on the CPU;
# 0.03 changes half the tokens' sets and moves loads by a half)
EXPERT_BIAS_SCALE = 0.01


def refuse_int8_weights(quantize: bool) -> None:
    if quantize:
        raise ValueError(
            "int8 weights (DYN_JAX_QUANTIZE_INT8) are not implemented for "
            "expert stacks and short convolutions: serve this family in "
            "bfloat16"
        )


def init_params(
    config: ConvMoeConfig,
    rng: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    quantize: bool = False,
) -> dict:
    """Random weights: matrices normal / sqrt(fan_in) in float32, cast to
    `dtype` (the convolution's taps by their `conv_L_cache` inputs); norms
    ones; `expert_bias` `EXPERT_BIAS_SCALE` x normal, float32.
    `cellbench/reference/conv_moe.py` makes the same draw from the same
    key, on its own."""
    refuse_int8_weights(quantize)
    c = config
    keys = jax.random.split(rng, 4 + KEYS_PER_LAYER * c.num_layers)
    layers, used = [], 0
    for i in range(c.num_layers):
        kind = (c.is_attn_layer(i), c.is_moe_layer(i))
        n = _layer_keys(*kind)
        layers.append(_draw_layer(
            keys[used: used + n], c=c, dtype=dtype, attends=kind[0], routed=kind[1],
        ))
        used += n
    return {"layers": layers, **_draw_top(keys[used: used + 2], c=c, dtype=dtype)}


def _layer_keys(attends: bool, routed: bool) -> int:
    """Keys a layer's draw consumes."""
    return (4 if attends else 3) + (5 if routed else 3)


@functools.partial(jax.jit, static_argnames=("c", "dtype", "attends", "routed"))
def _draw_layer(keys, *, c, dtype, attends, routed):
    """One layer's weights, its keys consumed in order; one program for each
    kind of layer (a start with an empty compile cache otherwise compiles a
    small program a tensor: `models/hybrid_ssm.py` `_draw_layer`)."""
    keys = iter(keys)
    dense = lambda shape, fan_in: _dense(next(keys), shape, fan_in, dtype)
    H, D, K = c.hidden_size, c.head_dim, c.conv_L_cache
    layer = {"op_norm": jnp.ones((H,), dtype)}
    if attends:
        layer.update(
            wq=dense((H, c.q_dim), H), wk=dense((H, c.kv_dim), H),
            wv=dense((H, c.kv_dim), H), wo=dense((c.q_dim, H), c.q_dim),
            q_norm=jnp.ones((D,), dtype), k_norm=jnp.ones((D,), dtype),
        )
    else:
        layer.update(
            w_in=dense((H, 3 * H), H), conv_w=dense((K, H), K),
            w_out=dense((H, H), H),
        )
    layer["ffn_norm"] = jnp.ones((H,), dtype)
    if routed:
        E, F = c.num_experts, c.moe_intermediate_size
        layer.update(
            router=dense((H, E), H),
            router_bias=EXPERT_BIAS_SCALE * _normal(next(keys), (E,)),
            wg=dense((E, H, F), H), wu=dense((E, H, F), H), wd=dense((E, F, H), F),
        )
        if not c.use_expert_bias:
            layer["router_bias"] = jnp.zeros((E,), F32)
    else:
        I = c.intermediate_size
        layer.update(wg=dense((H, I), H), wu=dense((H, I), H), wd=dense((I, H), I))
    return layer


def _draw_top(keys, *, c, dtype):
    # not under a jit: fused, the embedding's `normal * 0.02` folds two
    # constants into one and rounds one value in 20,000 differently
    H = c.hidden_size
    params = {
        "embed": (
            jax.random.normal(keys[0], (c.vocab_size, H), F32) * 0.02
        ).astype(dtype),
        "final_norm": jnp.ones((H,), dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = _dense(keys[1], (H, c.vocab_size), H, dtype)
    return params


def mixer_param_counts(config: ConvMoeConfig) -> tuple[int, int]:
    """(a convolution mixer, an attention mixer), without the layer's norms."""
    c = config
    H = c.hidden_size
    conv = H * 3 * H + H * H + c.conv_L_cache * H
    attn = 2 * H * c.q_dim + 2 * H * c.kv_dim + 2 * c.head_dim
    return conv, attn


def routed_ffn_params(config: ConvMoeConfig) -> int:
    """One expert layer's experts, router and bias."""
    c = config
    return (
        c.num_experts * 3 * c.hidden_size * c.moe_intermediate_size
        + c.hidden_size * c.num_experts + c.num_experts
    )


def param_count(config: ConvMoeConfig) -> int:
    c = config
    conv, attn = mixer_param_counts(c)
    H = c.hidden_size
    total = c.vocab_size * H * (1 if c.tie_word_embeddings else 2) + H
    for i in range(c.num_layers):
        total += (attn if c.is_attn_layer(i) else conv) + 2 * H
        total += (
            routed_ffn_params(c) if c.is_moe_layer(i)
            else 3 * H * c.intermediate_size
        )
    return total


def expert_param_count(config: ConvMoeConfig) -> int:
    """Parameters in routed expert stacks (what an `ep` share would divide)."""
    c = config
    n_moe = sum(c.is_moe_layer(i) for i in range(c.num_layers))
    return n_moe * c.num_experts * 3 * c.hidden_size * c.moe_intermediate_size


# ----------------------------------------------------------------- forward


def _ffn(x, layer, cfg, valid):
    """Returns x and what an expert layer counted (`STEP_STATS`; None for a
    dense layer)."""
    h = rms_norm(x, layer["ffn_norm"], cfg.rms_eps)
    if "router" not in layer:
        return x + linear(swiglu(linear(h, layer["wg"]), linear(h, layer["wu"])), layer["wd"]), None
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(
            h.astype(F32), layer["router"].astype(F32),
            precision=lax.Precision.HIGHEST,
        )
        idx, weights = router_sigmoid_topk(
            logits, layer["router_bias"], cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor, renormalize=cfg.norm_topk_prob,
            eps=ROUTE_EPS,
        )
    with jax.named_scope("moe.experts"):
        y, group_sizes = dropless_experts(
            h, idx, weights, layer["wg"], layer["wu"], layer["wd"], valid=valid,
            impl=cfg.attn_impl,
        )
    return x + y.astype(x.dtype), expert_step_stats(group_sizes)


def _inv_freqs(cfg):
    return rope_freqs(cfg.head_dim, cfg.rope_theta, None)


def _qkv(x, layer, cfg, inv_freqs, positions):
    """q [T, Hq, D] and k, v [T, Hkv, D]: projections, the heads' own norms
    over q and k, then the rotary embedding."""
    T = x.shape[0]
    h = rms_norm(x, layer["op_norm"], cfg.rms_eps)
    q = linear(h, layer["wq"]).reshape(T, cfg.num_heads, cfg.head_dim)
    k = linear(h, layer["wk"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = linear(h, layer["wv"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(rms_norm(q, layer["q_norm"], cfg.rms_eps), positions, inv_freqs)
    k = apply_rope(rms_norm(k, layer["k_norm"], cfg.rms_eps), positions, inv_freqs)
    return q, k, v


def _rows(x, cfg):
    """Keys or values [T, Hkv, D] as the cache stores them: `kv_pack` heads
    side by side a row (a reshape of what the projection produced)."""
    p = cfg.kv_pack
    return x.reshape(x.shape[0], cfg.num_kv_heads // p, p * cfg.head_dim)


def _attn_out(attn, x, layer, cfg):
    return x + linear(attn.reshape(x.shape[0], cfg.q_dim), layer["wo"])


def _gated_input(x, layer, cfg):
    """(g = B * x [T, hidden] in `conv_dtype`, the output gate C [T, hidden])."""
    H = cfg.hidden_size
    h = rms_norm(x, layer["op_norm"], cfg.rms_eps)
    bcx = linear(h, layer["w_in"])
    g = bcx[:, :H].astype(F32) * bcx[:, 2 * H:].astype(F32)
    return g.astype(cfg.conv_dtype), bcx[:, H: 2 * H]


def _conv_out(conv, gate, x, layer):
    y = gate.astype(F32) * conv
    return x + linear(y.astype(x.dtype), layer["w_out"])


def _no_bias(cfg):
    return jnp.zeros((cfg.hidden_size,), F32)


# A body for each kind of pass over the layers (`models.layer_body`: traced
# and lowered once a program for each distinct parameter tree): the
# convolution one, which the dense and the expert layers each trace once,
# and the attention one: three bodies a program at the published layout. A
# convolution body takes the layer's tail array, an attention body its two
# planes; both return what `_ffn` counted last.


@layer_body("cfg")
def _conv_packed_layer(x, layer, tail, positions, valid, last_idx, seg_slots, *, cfg):
    g, gate = _gated_input(x, layer, cfg)
    K = cfg.conv_L_cache
    with jax.named_scope("conv.mix"):
        conv, _ = ssm.conv_sequence(
            g, jnp.zeros((K - 1, cfg.hidden_size), F32), positions,
            layer["conv_w"], _no_bias(cfg),
        )
        tail = tail.at[seg_slots].set(
            ssm.packed_tails(g, positions, last_idx, K).astype(tail.dtype)
        )
        x = _conv_out(conv, gate, x, layer)
    x, counted = _ffn(x, layer, cfg, valid)
    return x, tail, counted


@layer_body("cfg")
def _attn_packed_layer(x, layer, k_l, v_l, inv_freqs, positions, segment_ids, slot_indices, *, cfg):
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    k_l, v_l = write_decode_kv(k_l, v_l, _rows(k, cfg), _rows(v, cfg), slot_indices)
    attn = packed_prefill_attention(q, k, v, segment_ids, scale=cfg.attn_scale)
    x, counted = _ffn(_attn_out(attn, x, layer, cfg), layer, cfg, segment_ids >= 0)
    return x, k_l, v_l, counted


@layer_body("cfg", "mesh", "head_axis")
def _attn_prefill_layer(x, layer, k_l, v_l, inv_freqs, positions, valid_len, slot_indices, *, cfg, mesh, head_axis):
    # one whole prompt: the flash prefill kernel, keys and values handed
    # over as the rows they are stored in
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    k, v = _rows(k, cfg), _rows(v, cfg)
    k_l, v_l = write_decode_kv(k_l, v_l, k, v, slot_indices)
    attn = causal_prefill_attention(
        q, k, v, valid_len, impl=cfg.attn_impl, mesh=mesh, head_axis=head_axis,
        scale=cfg.attn_scale,
    )
    x, counted = _ffn(_attn_out(attn, x, layer, cfg), layer, cfg, positions < valid_len)
    return x, k_l, v_l, counted


@layer_body("cfg")
def _conv_chunk_layer(x, layer, tail, positions, valid, slot, chunk_start, *, cfg):
    g, gate = _gated_input(x, layer, cfg)
    K = cfg.conv_L_cache
    with jax.named_scope("conv.mix"):
        prev = jnp.where(chunk_start == 0, 0.0, tail[slot])
        conv, stream = ssm.conv_sequence(
            g, prev.reshape(K - 1, cfg.hidden_size), positions,
            layer["conv_w"], _no_bias(cfg),
        )
        tail = tail.at[slot].set(
            ssm.tail_after(stream, jnp.sum(valid), K).astype(tail.dtype)
        )
        x = _conv_out(conv, gate, x, layer)
    x, counted = _ffn(x, layer, cfg, valid)
    return x, tail, counted


@layer_body("cfg")
def _attn_chunk_layer(x, layer, k_l, v_l, inv_freqs, positions, valid, slots, block_table, chunk_start, *, cfg):
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    k_l, v_l = write_decode_kv(k_l, v_l, _rows(k, cfg), _rows(v, cfg), slots)
    attn = chunked_prefill_attention(
        q, k_l, v_l, block_table, chunk_start, scale=cfg.attn_scale
    )
    x, counted = _ffn(_attn_out(attn, x, layer, cfg), layer, cfg, valid)
    return x, k_l, v_l, counted


@layer_body("cfg")
def _conv_decode_layer(x, layer, tail, live, *, cfg):
    # every row of the slot array is updated under one mask, the null
    # lane's with them (it is never live): no slice of the array, no update
    # of a slice, so the step writes it where it lies
    B, S = x.shape[0], tail.shape[0]
    g, gate = _gated_input(x, layer, cfg)
    rows = lambda v: jnp.pad(v, ((0, S - B),) + ((0, 0),) * (v.ndim - 1))
    with jax.named_scope("conv.mix"):
        conv, new_tail = ssm.conv_step(rows(g), tail, layer["conv_w"], _no_bias(cfg))
        tail = jnp.where(rows(live)[:, None], new_tail.astype(tail.dtype), tail)
        x = _conv_out(conv[:B], gate, x, layer)
    x, counted = _ffn(x, layer, cfg, live)
    return x, tail, counted


@layer_body("cfg", "mesh", "head_axis")
def _attn_decode_layer(x, layer, k_l, v_l, inv_freqs, positions, live, context, block_tables, slot_indices, *, cfg, mesh, head_axis):
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    attn, k_l, v_l = decode_append_attention(
        q, k_l, v_l, _rows(k, cfg), _rows(v, cfg), slot_indices, block_tables,
        context, impl=cfg.attn_impl, mesh=mesh, head_axis=head_axis,
        scale=cfg.attn_scale,
    )
    x, counted = _ffn(_attn_out(attn, x, layer, cfg), layer, cfg, live)
    return x, k_l, v_l, counted


def _rope(cfg, **_):
    return {"inv_freqs": _inv_freqs(cfg)}


def _packed_values(cfg, *, segment_ids, state_slots, null, **_):
    """`seg_slots` [N]: each segment's lane slot, the null lane's for a
    segment that holds no prompt; and rope's frequencies."""
    used = jnp.arange(state_slots.shape[0]) <= jnp.max(segment_ids)
    return {"seg_slots": jnp.where(used, state_slots, null), **_rope(cfg)}


# A layer by whether it attends: an attention layer keeps pages of keys and
# values `[Hkv/pack, nb, bs, pack*D]`, a convolution layer its tail
# `[S, (L-1)*H]` and nothing beside it. One whole prompt is a program of its
# own: one segment through the convolution layers' packed body, the
# attention layers through the flash prefill kernel.
_CONV_PACKED = Body(_conv_packed_layer, 1, (
    "positions", "valid", "last_idx", "seg_slots"))
FAMILY = Family(
    kind=ConvMoeConfig.is_attn_layer,
    prepare={
        "packed": _packed_values, "whole": _rope, "chunk": _rope, "decode": _rope,
    },
    packed={
        False: _CONV_PACKED,
        True: Body(_attn_packed_layer, 2, (
            "inv_freqs", "positions", "segment_ids", "slot_indices")),
    },
    whole={
        False: _CONV_PACKED,
        True: Body(
            _attn_prefill_layer, 2,
            ("inv_freqs", "positions", "valid_len", "slot_indices"),
            static=("mesh", "head_axis"),
        ),
    },
    chunk={
        False: Body(_conv_chunk_layer, 1, (
            "positions", "valid", "lane_slot", "chunk_start")),
        True: Body(_attn_chunk_layer, 2, (
            "inv_freqs", "positions", "valid", "slot_indices", "block_table",
            "chunk_start")),
    },
    decode={
        False: Body(_conv_decode_layer, 1, ("live",)),
        True: Body(
            _attn_decode_layer, 2, (
                "inv_freqs", "positions", "live", "context", "block_tables",
                "slot_indices"),
            static=("mesh", "head_axis"),
        ),
    },
)
prefill_packed, prefill, prefill_chunk, decode = programs.bound(FAMILY)
prefill_mm, prefill_context_parallel, embed_pooled, decode_verify = programs.refused(
    "the short-convolution family",
    "speculative verification (a rejected draft would need the tail rolled back)",
)
