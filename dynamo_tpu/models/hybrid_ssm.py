"""Hybrid state-space family (`model_type: jamba`): Mamba-1 mixers with an
attention mixer among every few, a SwiGLU feed-forward behind each, as pure
JAX functions over two kinds of cache side by side.

A third block beside `models/llama.py`'s and `models/mla_moe.py`'s, with a
config and forwards of its own under the family's names and signatures; it
shares `ops/`, the runner, the engine and the cache manager. What a layer
keeps is what it declares (`layer_cache_kinds`): an attention layer rows per
token in paged blocks (keys and values of `num_kv_heads` heads), a Mamba
layer one slot a sequence (`ops/ssm.py`: the state `[d_state, d_inner]`
float32 and the convolution's tail). The slot arrays ride where a paged
layer's two planes do (`k_cache[i]`, `v_cache[i]`), with one row a lane and
one more, the null lane's, that padding writes to. A decode lane's slot is
its row in the batch; prefill programs are told each sequence's slot
(`state_slots`), zero it at the sequence's position 0 and leave the state
there between the chunks of a chunked prefill.

The block (pre-norm residual, RMS norms, no positional embedding anywhere):
`h = h + mixer_i(norm(h)); h = h + mlp(norm(h))`, `mlp(x) = Wd(silu(Wg x) *
(Wu x))`. `mixer_i` is attention where `i % attn_layer_period ==
attn_layer_offset`: grouped-query heads, no bias, no rope, causal softmax at
`1/sqrt(head_dim)`. Elsewhere Mamba-1: `(x, z) = split(W_in u)`; `x =
silu(conv1d_causal_depthwise(x) + b_conv)`; `(dt, B, C) = split(W_x x)`,
each through its own RMS norm; `delta = softplus(W_dt dt + b_dt)`; `A =
-exp(A_log)`; the selective scan; `y = scan + D * x`; `out = W_out (y *
silu(z))`. Projections run in the weights' dtype, the convolution, `delta`
and the scan in float32. Tied embedding and head, final RMS norm.

Not served and refused in words where asked for: routed expert layers
(`num_experts` > 1), a sliding window, projection biases, int8 weights, a
mesh, an int8-resident cache, the fused decode step, speculative
verification (a rejected draft would need the state rolled back).
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

from dynamo_tpu.models import (
    CacheKind, kv_heads_cache, layer_body, programs, recurrent_state,
)
from dynamo_tpu.models.programs import Body, Family
from dynamo_tpu.ops import ssm
from dynamo_tpu.ops.attention import (
    chunked_prefill_attention, decode_append_attention,
    packed_prefill_attention, write_decode_kv,
)
from dynamo_tpu.ops.basics import rms_norm, swiglu
from dynamo_tpu.ops.linear import linear

MODEL_TYPES = ("jamba",)
F32 = jnp.float32


@dataclass(frozen=True)
class HybridSsmConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    rms_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    # set by the runner (`dataclasses.replace`), as on LlamaConfig
    attn_impl: Optional[str] = None
    fused_decode: bool = False
    collective_overlap: bool = False

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "HybridSsmConfig":
        unsupported = {
            "num_experts": (d.get("num_experts") or 1) > 1,
            "sliding_window": d.get("sliding_window") is not None,
            "mamba_proj_bias": bool(d.get("mamba_proj_bias", False)),
            "mamba_conv_bias": not d.get("mamba_conv_bias", True),
            "hidden_act": d.get("hidden_act", "silu") != "silu",
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"model_type {d.get('model_type')!r}: this value of {bad} is "
                "not implemented (served: one expert a layer, that is a "
                "dense feed-forward in every layer, no sliding window, no "
                "projection bias, a convolution bias, silu)"
            )
        hidden, heads = d["hidden_size"], d["num_attention_heads"]
        dt_rank = d.get("mamba_dt_rank", "auto")
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=hidden,
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=d.get("num_key_value_heads", heads),
            head_dim=d.get("head_dim") or hidden // heads,
            attn_layer_period=d.get("attn_layer_period", 8),
            attn_layer_offset=d.get("attn_layer_offset", 4),
            d_state=d.get("mamba_d_state", 16),
            d_conv=d.get("mamba_d_conv", 4),
            dt_rank=math.ceil(hidden / 16) if dt_rank == "auto" else dt_rank,
            expand=d.get("mamba_expand", 2),
            rms_eps=float(d.get("rms_norm_eps", 1e-6)),
            max_position_embeddings=d.get("max_position_embeddings", 262144),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", True)),
        )

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "HybridSsmConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    @classmethod
    def tiny(cls, vocab_size: int = 256) -> "HybridSsmConfig":
        """CPU-test size of the same pattern: a toy period of 3 with the
        attention layer in its middle (layers 1 and 4 of 5 attend)."""
        return cls(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=160,
            num_layers=5, num_heads=4, num_kv_heads=1, head_dim=16,
            attn_layer_period=3, attn_layer_offset=1, d_state=8, d_conv=4,
            dt_rank=8, expand=2, max_position_embeddings=512,
        )

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    def is_attn_layer(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    def state_kind(self) -> CacheKind:
        return recurrent_state(
            ((self.d_state, self.d_inner), "float32"),
            (((self.d_conv - 1) * self.d_inner,), "float32"),
        )

    def layer_cache_kinds(self) -> tuple[CacheKind, ...]:
        paged = kv_heads_cache(self.num_kv_heads, self.head_dim)
        state = self.state_kind()
        return tuple(
            paged if self.is_attn_layer(i) else state
            for i in range(self.num_layers)
        )


CONFIG = HybridSsmConfig  # `models.served_model_types` reads it


# ------------------------------------------------------------------ params

KEYS_PER_LAYER = 12
# delta's range at init, as Mamba's: b_dt = softplus^-1(dt), dt log-uniform
DT_MIN, DT_MAX = 1e-3, 1e-1


def refuse_int8_weights(quantize: bool) -> None:
    if quantize:
        raise ValueError(
            "int8 weights (DYN_JAX_QUANTIZE_INT8) are not implemented for "
            "state-space mixers: serve this family in bfloat16"
        )


def init_params(
    config: HybridSsmConfig,
    rng: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    quantize: bool = False,
) -> dict:
    """Random weights: matrices normal / sqrt(fan_in) in float32, cast to
    `dtype` (the convolution's taps by their four inputs, its bias 0.1 x
    normal). The recurrence's own constants as Mamba draws them, float32:
    `A_log = log(1..d_state)` for every channel, `D = 1`, `b_dt` the inverse
    softplus of a step drawn log-uniform in [0.001, 0.1]; with `dt` through
    its RMS norm, `W_dt dt` is a unit normal a channel, so `delta` lies
    about 0.0004 to 0.3 and a state forgets over 3 to 2,500 tokens
    (PERF.md section 6, PR 38, has the readings over 576 tokens).
    `cellbench/reference/hybrid_ssm.py` makes the same draw from the same
    key, on its own."""
    refuse_int8_weights(quantize)
    c = config
    keys = jax.random.split(rng, 4 + KEYS_PER_LAYER * c.num_layers)
    layers, used = [], 0
    for i in range(c.num_layers):
        attends = c.is_attn_layer(i)
        n = _LAYER_KEYS[attends]
        layers.append(_draw_layer(keys[used: used + n], c=c, dtype=dtype, attends=attends))
        used += n
    return {"layers": layers, **_draw_top(keys[used: used + 2], c=c, dtype=dtype)}


# keys a layer's draw consumes, by whether it attends
_LAYER_KEYS = {True: 7, False: 10}


def _dense(key, shape, fan_in, dtype):
    w = jax.random.normal(key, shape, dtype=F32)
    return (w / jnp.sqrt(F32(fan_in))).astype(dtype)


@functools.partial(jax.jit, static_argnames=("c", "dtype", "attends"))
def _draw_layer(keys, *, c, dtype, attends):
    """One layer's weights, its keys consumed in order. One program for
    each kind of layer: drawn tensor by tensor outside a jit, a start with
    an empty compile cache compiled some 30 small programs first (26 s of a
    cold start on the chip; PERF.md section 6, PR 38). The values are the
    same either way (tests/test_hybrid_ssm.py holds them to the
    reference's own draw)."""
    keys = iter(keys)
    dense = lambda shape, fan_in: _dense(next(keys), shape, fan_in, dtype)
    H, Di, N, R, K = c.hidden_size, c.d_inner, c.d_state, c.dt_rank, c.d_conv
    layer = {"mix_norm": jnp.ones((H,), dtype)}
    if attends:
        layer.update(
            wq=dense((H, c.q_dim), H),
            wk=dense((H, c.num_kv_heads * c.head_dim), H),
            wv=dense((H, c.num_kv_heads * c.head_dim), H),
            wo=dense((c.q_dim, H), c.q_dim),
        )
    else:
        dt = jnp.exp(
            jax.random.uniform(next(keys), (Di,), F32)
            * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)
        )
        layer.update(
            w_in=dense((H, 2 * Di), H),
            conv_w=dense((K, Di), K),
            conv_b=(0.1 * jax.random.normal(next(keys), (Di,), F32)).astype(dtype),
            w_x=dense((Di, R + 2 * N), Di),
            dt_norm=jnp.ones((R,), dtype),
            b_norm=jnp.ones((N,), dtype),
            c_norm=jnp.ones((N,), dtype),
            w_dt=dense((R, Di), R),
            b_dt=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=F32))[:, None], (N, Di)
            ),
            D=jnp.ones((Di,), F32),
            w_out=dense((Di, H), Di),
        )
    layer.update(
        mlp_norm=jnp.ones((H,), dtype),
        wg=dense((H, c.intermediate_size), H),
        wu=dense((H, c.intermediate_size), H),
        wd=dense((c.intermediate_size, H), c.intermediate_size),
    )
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


def mixer_param_counts(config: HybridSsmConfig) -> tuple[int, int]:
    """(a Mamba mixer, an attention mixer), without the layer's own norm."""
    c = config
    H, Di, N, R, K = c.hidden_size, c.d_inner, c.d_state, c.dt_rank, c.d_conv
    mamba = (
        H * 2 * Di + K * Di + Di + Di * (R + 2 * N) + (R + 2 * N)
        + R * Di + Di + N * Di + Di + Di * H
    )
    attn = 2 * H * c.q_dim + 2 * H * c.num_kv_heads * c.head_dim
    return mamba, attn


def param_count(config: HybridSsmConfig) -> int:
    c = config
    mamba, attn = mixer_param_counts(c)
    n_attn = sum(c.is_attn_layer(i) for i in range(c.num_layers))
    H = c.hidden_size
    embed = c.vocab_size * H * (1 if c.tie_word_embeddings else 2)
    return (
        (c.num_layers - n_attn) * mamba + n_attn * attn
        + c.num_layers * (3 * H * c.intermediate_size + 2 * H) + embed + H
    )


def expert_param_count(config: HybridSsmConfig) -> int:
    return 0


# ----------------------------------------------------------------- forward


def _mlp(x, layer, cfg):
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    return x + linear(swiglu(linear(h, layer["wg"]), linear(h, layer["wu"])), layer["wd"])


def _qkv(x, layer, cfg):
    T = x.shape[0]
    h = rms_norm(x, layer["mix_norm"], cfg.rms_eps)
    q = linear(h, layer["wq"]).reshape(T, cfg.num_heads, cfg.head_dim)
    k = linear(h, layer["wk"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = linear(h, layer["wv"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _attn_out(attn, x, layer, cfg):
    return x + linear(attn.reshape(x.shape[0], cfg.q_dim), layer["wo"])


def _in_proj(x, layer, cfg):
    """(the mixer's stream, its gate), each [T, d_inner]."""
    h = rms_norm(x, layer["mix_norm"], cfg.rms_eps)
    xz = linear(h, layer["w_in"])
    return xz[:, : cfg.d_inner], xz[:, cfg.d_inner:]

def _scan_inputs(conv, layer, cfg):
    """From the convolution's output [T, d_inner] float32: the scan's stream
    (silu, float32), delta [T, d_inner], B and C [T, d_state], and A."""
    xs = jax.nn.silu(conv)
    R, N = cfg.dt_rank, cfg.d_state
    proj = linear(xs.astype(layer["w_x"].dtype), layer["w_x"])
    dt = rms_norm(proj[:, :R], layer["dt_norm"], cfg.rms_eps)
    b = rms_norm(proj[:, R: R + N], layer["b_norm"], cfg.rms_eps).astype(F32)
    c = rms_norm(proj[:, R + N:], layer["c_norm"], cfg.rms_eps).astype(F32)
    delta = jax.nn.softplus(linear(dt, layer["w_dt"]).astype(F32) + layer["b_dt"])
    return xs, delta, b, c, -jnp.exp(layer["A_log"])


def _mixer_out(y, xs, z, x, layer, cfg):
    y = (y + layer["D"] * xs) * jax.nn.silu(z.astype(F32))
    return x + linear(y.astype(x.dtype), layer["w_out"])


# Two bodies for each kind of pass over the layers (`models.layer_body`:
# traced and lowered once a program each): the Mamba one and the attention
# one, told apart by what the layer declares it keeps. A body takes the
# layer's two cache arrays and returns them.


@layer_body("cfg")
def _mamba_packed_layer(x, layer, state, tail, positions, valid, write_slots, last_idx, seg_slots, *, cfg):
    xs, z = _in_proj(x, layer, cfg)
    K = cfg.d_conv
    with jax.named_scope("ssm.conv"):
        conv, _ = ssm.conv_sequence(
            xs, jnp.zeros((K - 1, cfg.d_inner), F32), positions,
            layer["conv_w"], layer["conv_b"],
        )
        tail = tail.at[seg_slots].set(ssm.packed_tails(xs, positions, last_idx, K))
    xs, delta, b, c, a_neg = _scan_inputs(conv, layer, cfg)
    with jax.named_scope("ssm.scan"):
        y, state = ssm.scan_packed(
            state, xs, delta, b, c, a_neg, positions, valid, write_slots
        )
    return _mlp(_mixer_out(y, xs, z, x, layer, cfg), layer, cfg), state, tail


@layer_body("cfg")
def _attn_packed_layer(x, layer, k_l, v_l, segment_ids, slot_indices, *, cfg):
    q, k, v = _qkv(x, layer, cfg)
    k_l, v_l = write_decode_kv(k_l, v_l, k, v, slot_indices)
    attn = packed_prefill_attention(q, k, v, segment_ids, scale=cfg.attn_scale)
    return _mlp(_attn_out(attn, x, layer, cfg), layer, cfg), k_l, v_l


@layer_body("cfg")
def _mamba_chunk_layer(x, layer, state, tail, positions, valid, slot, chunk_start, *, cfg):
    xs, z = _in_proj(x, layer, cfg)
    K = cfg.d_conv
    fresh = chunk_start == 0
    with jax.named_scope("ssm.conv"):
        prev = jnp.where(fresh, 0.0, tail[slot]).reshape(K - 1, cfg.d_inner)
        conv, stream = ssm.conv_sequence(
            xs, prev, positions, layer["conv_w"], layer["conv_b"]
        )
        tail = tail.at[slot].set(ssm.tail_after(stream, jnp.sum(valid), K))
    xs, delta, b, c, a_neg = _scan_inputs(conv, layer, cfg)
    with jax.named_scope("ssm.scan"):
        y, h = ssm.scan_chunk(
            jnp.where(fresh, 0.0, state[slot]), xs, delta, b, c, a_neg, valid
        )
        state = state.at[slot].set(h)
    return _mlp(_mixer_out(y, xs, z, x, layer, cfg), layer, cfg), state, tail


@layer_body("cfg")
def _attn_chunk_layer(x, layer, k_l, v_l, slots, block_table, chunk_start, *, cfg):
    q, k, v = _qkv(x, layer, cfg)
    k_l, v_l = write_decode_kv(k_l, v_l, k, v, slots)
    attn = chunked_prefill_attention(
        q, k_l, v_l, block_table, chunk_start, scale=cfg.attn_scale
    )
    return _mlp(_attn_out(attn, x, layer, cfg), layer, cfg), k_l, v_l


@layer_body("cfg")
def _mamba_decode_layer(x, layer, state, tail, live, *, cfg):
    # every row of the slot arrays is updated under one mask, the null
    # lane's with them (it is never live): no slice of the arrays, no
    # update of a slice, so the step writes them where they lie
    B, S = x.shape[0], state.shape[0]
    xs, z = _in_proj(x, layer, cfg)
    rows = lambda v: jnp.pad(v, ((0, S - B),) + ((0, 0),) * (v.ndim - 1))
    live_rows = rows(live)
    with jax.named_scope("ssm.conv"):
        conv, new_tail = ssm.conv_step(rows(xs), tail, layer["conv_w"], layer["conv_b"])
        tail = jnp.where(live_rows[:, None], new_tail, tail)
    xs, delta, b, c, a_neg = _scan_inputs(conv[:B], layer, cfg)
    with jax.named_scope("ssm.step"):
        state, y = ssm.scan_step(
            state, rows(xs), rows(delta), rows(b), rows(c), a_neg, live_rows
        )
    return _mlp(_mixer_out(y[:B], xs, z, x, layer, cfg), layer, cfg), state, tail


@layer_body("cfg", "mesh", "head_axis")
def _attn_decode_layer(x, layer, k_l, v_l, context, block_tables, slot_indices, *, cfg, mesh, head_axis):
    q, k, v = _qkv(x, layer, cfg)
    attn, k_l, v_l = decode_append_attention(
        q, k_l, v_l, k, v, slot_indices, block_tables, context,
        impl=cfg.attn_impl, mesh=mesh, head_axis=head_axis,
        scale=cfg.attn_scale,
    )
    return _mlp(_attn_out(attn, x, layer, cfg), layer, cfg), k_l, v_l


def _packed_slots(cfg, *, positions, segment_ids, last_idx, state_slots, null, **_):
    """The packed program's lane slots: each segment's (`seg_slots` [N]) and
    each token's (`write_slots` [P])."""
    n_seg, n_tok = last_idx.shape[0], positions.shape[0]
    # a segment that holds no prompt sends what is computed for it to the
    # null lane; a sequence's last token writes its state to its slot
    used = jnp.arange(n_seg) <= jnp.max(segment_ids)
    seg_slots = jnp.where(used, state_slots, null)
    write_slots = jnp.full((n_tok,), null, jnp.int32).at[
        jnp.where(used, last_idx, n_tok)
    ].set(seg_slots.astype(jnp.int32), mode="drop")
    return {"seg_slots": seg_slots, "write_slots": write_slots}


# A layer by whether it attends. Either kind keeps two arrays: pages of keys
# and values `[Hkv, nb, bs, D]`, or the state `[S, N, Di]` and the tail
# `[S, (K-1)*Di]`.
FAMILY = Family(
    kind=HybridSsmConfig.is_attn_layer,
    prepare={"packed": _packed_slots},
    packed={
        False: Body(_mamba_packed_layer, 2, (
            "positions", "valid", "write_slots", "last_idx", "seg_slots")),
        True: Body(_attn_packed_layer, 2, ("segment_ids", "slot_indices")),
    },
    chunk={
        False: Body(_mamba_chunk_layer, 2, (
            "positions", "valid", "lane_slot", "chunk_start")),
        True: Body(_attn_chunk_layer, 2, (
            "slot_indices", "block_table", "chunk_start")),
    },
    decode={
        False: Body(_mamba_decode_layer, 2, ("live",)),
        True: Body(
            _attn_decode_layer, 2, ("context", "block_tables", "slot_indices"),
            static=("mesh", "head_axis"),
        ),
    },
)
prefill_packed, prefill, prefill_chunk, decode = programs.bound(FAMILY)
prefill_mm, prefill_context_parallel, embed_pooled, decode_verify = programs.refused(
    "the hybrid state-space family",
    "speculative verification (a rejected draft would need the state rolled back)",
)
