"""Window-and-full attention, sparse-expert family (`model_type: afmoe`, Arcee
AI's Trinity): grouped-query attention under an output gate, most layers
over a sliding window with a rotary embedding and every few over the whole
context with none, a routed feed-forward with a shared expert behind every
layer but the leading dense ones, four norms a layer.

A sixth block beside the other five, with a config and layer bodies of its
own and the four programs of `models/programs.py`; it shares `ops/`, the
runner, the engine and the cache manager. What is its own is how long a
layer's pages live: a full layer keeps every position, a window layer the
last `sliding_window` and no more, so the paged layers are two groups
(`models.page_groups`), each with its pool and its block table, and the
engine gives a window block back when it has wholly left the window
(`engine/jax_engine/kv_cache.py`).

The block, from the published config and the family's public implementation
(Hugging Face `modeling_afmoe.py`). Every norm is an RMS norm with its own
gain, `eps = rms_norm_eps`; no projection has a bias; the head is untied.

* Model: `x0 = embed[token] * sqrt(hidden)` (`mup_enabled`); the layers; a
  final norm; the head.
* Attention, every layer: `a = norm_in(x)`; `q, k, v = W_q a, W_k a, W_v a`;
  `g = W_g a` (as wide as `q`: the gate); `q` and `k` normed over each head
  (gains `q_norm`, `k_norm`). A window layer (`layer_types[i] ==
  "sliding_attention"`) rotates `q` and `k` over the whole head in the
  half-split form at `rope_theta` and lets query `i` see key `j` iff `j <= i`
  and `i - j < sliding_window`; a full layer (`"full_attention"`) applies no
  rotary embedding and the plain causal mask. `o = softmax(q k^T /
  sqrt(head)) v`; `h = x + norm_post_attn(W_o (o * sigmoid(g)))`.
* Feed-forward: `b = norm_pre_mlp(h)`. A layer below `num_dense_layers`:
  `f = W_down(silu(W_gate b) * W_up b)` at `intermediate_size`. The others:
  `s = sigmoid(W_r b)` in float32; the `num_experts_per_tok` experts with the
  largest `s + expert_bias` (the bias in the choice only); weights the chosen
  `s` over (their sum + 1e-20) where `route_norm`, times `route_scale`;
  `f = shared(b) + sum_e w_e expert_e(b)`, each a SwiGLU of
  `moe_intermediate_size`. `y = h + norm_post_mlp(f)`.

A held share of the experts: the config may say that this chip holds the
routed experts `[first_held_expert, first_held_expert + num_experts)` of
`num_experts_published`, as one of the chips that share a layer. The router
keeps its published width and its choice; the sum runs over the chosen
experts that are held, and what the absent ones would have added is left out
(`ops/moe.dropless_experts(first_held=)`); the shared expert is computed
here. The exchange between the chips is not written.

Departures from the public implementation, each said where it is made: the
router's logits and scores are float32 at the highest matmul precision over
a bfloat16 weight; the gate's sigmoid and its product are float32, rounded
once; "depth-scaled" names how the published gains were initialised and
changes no equation.

No program builds scores over the whole width of a block table: a decode
step goes through the paged kernel, over a window layer's last
`window / block + 1` blocks alone (a table made of those, so a block given
back is named by nothing the call is handed); a chunk's window layer attends
over the `window + chunk` keys before its end, its full layer over blocks of
2,048 keys with a running maximum and sum
(`ops.attention.chunked_prefill_attention_by_blocks`); one whole prompt goes
through the flash prefill kernel.

Not served and refused in words where asked for: rope scaling, expert groups
(`n_group` > 1), another score function than the sigmoid, layer kinds other
than the two above, int8 weights, a mesh, an int8-resident cache, the fused
decode step, tiers and transfer of blocks, speculative verification.
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

from dynamo_tpu.models import CacheKind, kv_heads_cache, layer_body, programs
from dynamo_tpu.models.programs import Body, Family
from dynamo_tpu.models.programs import dense as _dense, normal as _normal
from dynamo_tpu.ops.attention import (
    causal_prefill_attention, chunked_prefill_attention,
    chunked_prefill_attention_by_blocks, decode_append_attention,
    packed_prefill_attention, write_decode_kv,
)
from dynamo_tpu.ops.basics import apply_rope, rms_norm, rope_freqs, swiglu
from dynamo_tpu.ops.linear import linear
# `STEP_STATS` is read off the family's module by the runner (`decode_multi`)
from dynamo_tpu.ops.moe import (  # noqa: F401
    HELD_STEP_STATS as STEP_STATS, dropless_experts, expert_step_stats,
    router_sigmoid_topk,
)

MODEL_TYPES = ("afmoe",)
F32 = jnp.float32
LAYER_KINDS = ("sliding_attention", "full_attention")
# the routing's normaliser: the chosen scores over (their sum + this)
ROUTE_EPS = 1e-20
# keys a chunk's full layer scores at a time
CHUNK_KEY_BLOCK = 2048


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 3072
    num_layers: int = 60
    # one kind a layer, a literal list
    layer_types: tuple = (
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention",
    ) * 15
    num_dense_layers: int = 6
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    # the experts this chip holds, the router's width, the first held one
    num_experts: int = 256
    router_experts: int = 256
    first_held_expert: int = 0
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_scale: float = 2.448
    route_norm: bool = True
    mup_enabled: bool = True
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # set by the runner (`dataclasses.replace`), as on LlamaConfig
    attn_impl: Optional[str] = None
    fused_decode: bool = False
    collective_overlap: bool = False

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "AfmoeConfig":
        kinds = tuple(d.get("layer_types") or ())
        held = d["num_experts"]
        published = d.get("num_experts_published", held)
        first = d.get("first_held_expert", 0)
        unsupported = {
            "rope_scaling": d.get("rope_scaling") is not None,
            "layer_types": (
                len(kinds) != d["num_hidden_layers"]
                or any(k not in LAYER_KINDS for k in kinds)
            ),
            "sliding_window": (
                "sliding_attention" in kinds
                and not (d.get("sliding_window") or 0) > 0
            ),
            "n_group": d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1,
            "score_func": d.get("score_func", "sigmoid") != "sigmoid",
            "hidden_act": d.get("hidden_act", "silu") != "silu",
            "num_shared_experts": d.get("num_shared_experts", 1) != 1,
            "num_experts": held < 1 or d["num_experts_per_tok"] > published,
            "first_held_expert": first < 0 or first + held > published,
            "torch_dtype": d.get("torch_dtype", "bfloat16") != "bfloat16",
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"model_type {d.get('model_type')!r}: this value of {bad} is "
                "not implemented (served: no rope scaling, a `layer_types` "
                f"entry for every layer, each one of {list(LAYER_KINDS)}, a "
                "`sliding_window` where a layer has one, one expert group, "
                "sigmoid scores, SiLU, one shared expert, a held share "
                "`[first_held_expert, first_held_expert + num_experts)` "
                "inside `num_experts_published`, torch_dtype bfloat16)"
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
            sliding_window=int(d.get("sliding_window") or 0),
            num_experts=held,
            router_experts=published,
            first_held_expert=first,
            num_experts_per_tok=d["num_experts_per_tok"],
            route_scale=float(d.get("route_scale", 1.0)),
            route_norm=bool(d.get("route_norm", True)),
            mup_enabled=bool(d.get("mup_enabled", False)),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_eps=float(d.get("rms_norm_eps", 1e-5)),
            max_position_embeddings=d.get("max_position_embeddings", 262144),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        )

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "AfmoeConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    @classmethod
    def tiny(
        cls, vocab_size: int = 256, first_held_expert: int = 0,
        sliding_window: int = 16, num_experts: int = 4,
    ) -> "AfmoeConfig":
        """CPU-test size of the same shape: one leading dense layer and one
        whole period of expert layers behind it (window, window, full,
        window), a window of a block or two, 16 experts of which 4 are held,
        4 a token, three query heads to a KV head."""
        return cls(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_layers=5,
            layer_types=(
                "sliding_attention", "sliding_attention", "sliding_attention",
                "full_attention", "sliding_attention",
            ),
            num_dense_layers=1, num_heads=6, num_kv_heads=2, head_dim=128,
            sliding_window=sliding_window, num_experts=num_experts,
            router_experts=16, first_held_expert=first_held_expert,
            num_experts_per_tok=4, max_position_embeddings=1024,
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

    def is_window_layer(self, i: int) -> bool:
        return self.layer_types[i] == "sliding_attention"

    def is_moe_layer(self, i: int) -> bool:
        return i >= self.num_dense_layers

    def layers_of(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_types)

    def layer_cache_kinds(self) -> tuple[CacheKind, ...]:
        full = kv_heads_cache(self.num_kv_heads, self.head_dim)
        window = kv_heads_cache(
            self.num_kv_heads, self.head_dim, window=self.sliding_window
        )
        return tuple(
            window if self.is_window_layer(i) else full
            for i in range(self.num_layers)
        )


CONFIG = AfmoeConfig  # `models.served_model_types` reads it


# ------------------------------------------------------------------ params

KEYS_PER_LAYER = 14
# the draw of `expert_bias` (published as a buffer that starts at zero and is
# moved by the balancing rule in training): 0.003 x normal. At the top-4 cut
# of 256 a sigmoid score is 0.90 and moves 0.09 a unit of logit, and the
# scores next to the cut lie a third as far apart as at the top-4 cut of 32
# (`models/conv_moe.py`, 0.01), so a third of that scale does what it does
# there: it changes the choice of some tokens and leaves the experts' loads
# to the router (the configuration's file gives the counts)
EXPERT_BIAS_SCALE = 0.003


def refuse_int8_weights(quantize: bool) -> None:
    if quantize:
        raise ValueError(
            "int8 weights (DYN_JAX_QUANTIZE_INT8) are not implemented for "
            "expert stacks and the gated attention of this family: serve it "
            "in bfloat16"
        )


def init_params(
    config: AfmoeConfig,
    rng: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    quantize: bool = False,
) -> dict:
    """Random weights: matrices normal / sqrt(fan_in) in float32, cast to
    `dtype`; norms ones; `expert_bias` `EXPERT_BIAS_SCALE` x normal, float32;
    the held experts from the layer's keys folded with `first_held_expert`,
    so that two shares of one layer hold different experts.
    `cellbench/reference/afmoe.py` makes the same draw from the same key, on
    its own."""
    refuse_int8_weights(quantize)
    c = config
    keys = jax.random.split(rng, 4 + KEYS_PER_LAYER * c.num_layers)
    layers = [
        _draw_layer(
            keys[KEYS_PER_LAYER * i: KEYS_PER_LAYER * (i + 1)], c=c, dtype=dtype,
            routed=c.is_moe_layer(i),
        )
        for i in range(c.num_layers)
    ]
    top = _draw_top(keys[KEYS_PER_LAYER * c.num_layers:], c=c, dtype=dtype)
    return {"layers": layers, **top}


@functools.partial(jax.jit, static_argnames=("c", "dtype", "routed"))
def _draw_layer(keys, *, c, dtype, routed):
    """One layer's weights, its keys consumed in order; one program for each
    kind of feed-forward (`models/hybrid_ssm.py` `_draw_layer` says why)."""
    keys = iter(keys)
    dense = lambda shape, fan_in: _dense(next(keys), shape, fan_in, dtype)
    H, D = c.hidden_size, c.head_dim
    ones = lambda n: jnp.ones((n,), dtype)
    layer = {
        "attn_norm": ones(H), "post_attn_norm": ones(H),
        "pre_mlp_norm": ones(H), "post_mlp_norm": ones(H),
        "wq": dense((H, c.q_dim), H), "wk": dense((H, c.kv_dim), H),
        "wv": dense((H, c.kv_dim), H), "w_gate": dense((H, c.q_dim), H),
        "wo": dense((c.q_dim, H), c.q_dim),
        "q_norm": ones(D), "k_norm": ones(D),
    }
    if not routed:
        I = c.intermediate_size
        layer.update(wg=dense((H, I), H), wu=dense((H, I), H), wd=dense((I, H), I))
        return layer
    E, R, F = c.num_experts, c.router_experts, c.moe_intermediate_size
    layer.update(
        router=dense((H, R), H),
        router_bias=EXPERT_BIAS_SCALE * _normal(next(keys), (R,)),
        shared_wg=dense((H, F), H), shared_wu=dense((H, F), H),
        shared_wd=dense((F, H), F),
    )
    held = lambda: jax.random.fold_in(next(keys), c.first_held_expert)
    layer["wg"] = _dense(held(), (E, H, F), H, dtype)
    layer["wu"] = _dense(held(), (E, H, F), H, dtype)
    layer["wd"] = _dense(held(), (E, F, H), F, dtype)
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
        w = jax.random.normal(keys[1], (H, c.vocab_size), dtype=F32)
        params["lm_head"] = (w / jnp.sqrt(F32(H))).astype(dtype)
    return params


def attention_params(config: AfmoeConfig) -> int:
    """One layer's attention: q, the gate and o, k and v, the heads' two
    norms."""
    c = config
    return 3 * c.hidden_size * c.q_dim + 2 * c.hidden_size * c.kv_dim + 2 * c.head_dim


def routed_expert_params(config: AfmoeConfig) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * config.hidden_size * config.moe_intermediate_size


def expert_param_count(config: AfmoeConfig) -> int:
    """Parameters in the routed expert stacks this chip holds."""
    c = config
    n_moe = sum(c.is_moe_layer(i) for i in range(c.num_layers))
    return n_moe * c.num_experts * routed_expert_params(c)


def param_count(config: AfmoeConfig) -> int:
    c = config
    H = c.hidden_size
    n_moe = sum(c.is_moe_layer(i) for i in range(c.num_layers))
    per_layer = attention_params(c) + 4 * H
    routed_beside = H * c.router_experts + c.router_experts + routed_expert_params(c)
    return (
        c.num_layers * per_layer
        + (c.num_layers - n_moe) * 3 * H * c.intermediate_size
        + n_moe * routed_beside + expert_param_count(c)
        + c.vocab_size * H * (1 if c.tie_word_embeddings else 2) + H
    )


# ----------------------------------------------------------------- forward


def routed_and_shared(b, layer, cfg, valid):
    """(the held experts' part of the routed sum, the shared expert's
    output, the held experts' live tokens [E]) of normed tokens b [T, H]:
    what the chips that share a layer each compute; the shares' first parts
    and one second part add up to the uncut layer's `f`."""
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(
            b.astype(F32), layer["router"].astype(F32),
            precision=lax.Precision.HIGHEST,
        )
        idx, weights = router_sigmoid_topk(
            logits, layer["router_bias"], cfg.num_experts_per_tok,
            scale=cfg.route_scale, renormalize=cfg.route_norm, eps=ROUTE_EPS,
        )
    with jax.named_scope("moe.experts"):
        routed, group_sizes = dropless_experts(
            b, idx, weights, layer["wg"], layer["wu"], layer["wd"], valid=valid,
            first_held=cfg.first_held_expert, impl=cfg.attn_impl,
        )
    with jax.named_scope("moe.shared"):
        shared = linear(
            swiglu(linear(b, layer["shared_wg"]), linear(b, layer["shared_wu"])),
            layer["shared_wd"],
        )
    return routed, shared, group_sizes


def _ffn(h, layer, cfg, valid):
    """The feed-forward and its residual, between its two norms. Returns x
    and what an expert layer counted (`STEP_STATS`; None for a dense one)."""
    b = rms_norm(h, layer["pre_mlp_norm"], cfg.rms_eps)
    if "router" not in layer:
        f = linear(swiglu(linear(b, layer["wg"]), linear(b, layer["wu"])), layer["wd"])
        return h + rms_norm(f, layer["post_mlp_norm"], cfg.rms_eps), None
    routed, shared, group_sizes = routed_and_shared(b, layer, cfg, valid)
    f = shared + routed.astype(h.dtype)
    made = jnp.sum(valid.astype(jnp.int32)) * cfg.num_experts_per_tok
    return (
        h + rms_norm(f, layer["post_mlp_norm"], cfg.rms_eps),
        expert_step_stats(group_sizes, made),
    )


def _qkvg(x, layer, cfg, positions, inv_freqs):
    """q [T, Hq, D], k and v [T, Hkv, D] and the gate [T, Hq * D]:
    projections, the heads' own norms over q and k, then the rotary embedding
    where the layer has one (`inv_freqs` None: a full layer)."""
    T = x.shape[0]
    a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q = linear(a, layer["wq"]).reshape(T, cfg.num_heads, cfg.head_dim)
    k = linear(a, layer["wk"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = linear(a, layer["wv"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    q = rms_norm(q, layer["q_norm"], cfg.rms_eps)
    k = rms_norm(k, layer["k_norm"], cfg.rms_eps)
    if inv_freqs is not None:
        q = apply_rope(q, positions, inv_freqs)
        k = apply_rope(k, positions, inv_freqs)
    return q, k, v, linear(a, layer["w_gate"])


def _attn_out(attn, gate, x, layer, cfg):
    # the gate's sigmoid and the product in float32, rounded once
    o = attn.reshape(x.shape[0], cfg.q_dim).astype(F32) * jax.nn.sigmoid(gate.astype(F32))
    out = linear(o.astype(x.dtype), layer["wo"])
    return x + rms_norm(out, layer["post_attn_norm"], cfg.rms_eps)


def _scope(window):
    return jax.named_scope("attn.window" if window else "attn.full")


# A body for each program, told whether its layer has a window
# (`models.layer_body`: traced and lowered once a program for each distinct
# parameter tree and `window`): window or full attention by dense or expert
# feed-forward, four bodies a program at most. A body takes the layer's two
# planes and the values of its own group (`_groups`).


@layer_body("cfg", "window")
def _packed_layer(x, layer, k_l, v_l, inv_freqs, positions, segment_ids, slots, *, cfg, window):
    with _scope(window):
        q, k, v, gate = _qkvg(x, layer, cfg, positions, inv_freqs)
        k_l, v_l = write_decode_kv(k_l, v_l, k, v, slots)
        attn = packed_prefill_attention(
            q, k, v, segment_ids, window=window, scale=cfg.attn_scale
        )
        h = _attn_out(attn, gate, x, layer, cfg)
    x, counted = _ffn(h, layer, cfg, segment_ids >= 0)
    return x, k_l, v_l, counted


@layer_body("cfg", "window", "mesh", "head_axis")
def _whole_layer(x, layer, k_l, v_l, inv_freqs, positions, valid_len, slots, *, cfg, window, mesh, head_axis):
    # one whole prompt: the flash prefill kernel
    with _scope(window):
        q, k, v, gate = _qkvg(x, layer, cfg, positions, inv_freqs)
        k_l, v_l = write_decode_kv(k_l, v_l, k, v, slots)
        attn = causal_prefill_attention(
            q, k, v, valid_len, impl=cfg.attn_impl, mesh=mesh,
            head_axis=head_axis, window=window, scale=cfg.attn_scale,
        )
        h = _attn_out(attn, gate, x, layer, cfg)
    x, counted = _ffn(h, layer, cfg, positions < valid_len)
    return x, k_l, v_l, counted


@layer_body("cfg", "window")
def _chunk_layer(x, layer, k_l, v_l, inv_freqs, positions, valid, slots, table, start, *, cfg, window):
    """`table` and `start`: a full layer's whole table and the chunk's first
    position; a window layer's table of the blocks that hold the `window +
    chunk` keys before the chunk's end, and the chunk's first position
    counted from the first of them."""
    with _scope(window):
        q, k, v, gate = _qkvg(x, layer, cfg, positions, inv_freqs)
        k_l, v_l = write_decode_kv(k_l, v_l, k, v, slots)
        if window:
            attn = chunked_prefill_attention(
                q, k_l, v_l, table, start, window=window, scale=cfg.attn_scale
            )
        else:
            attn = chunked_prefill_attention_by_blocks(
                q, k_l, v_l, table, start, key_block=CHUNK_KEY_BLOCK,
                scale=cfg.attn_scale,
            )
        h = _attn_out(attn, gate, x, layer, cfg)
    x, counted = _ffn(h, layer, cfg, valid)
    return x, k_l, v_l, counted


@layer_body("cfg", "window", "mesh", "head_axis")
def _decode_layer(x, layer, k_l, v_l, inv_freqs, positions, live, context, tables, slots, *, cfg, window, mesh, head_axis):
    """`tables`, `context`, `slots`: a full layer's own; a window layer's
    table of its last `window / block + 1` blocks and the context counted
    from the first of them."""
    with _scope(window):
        q, k, v, gate = _qkvg(x, layer, cfg, positions, inv_freqs)
        attn, k_l, v_l = decode_append_attention(
            q, k_l, v_l, k, v, slots, tables, context, impl=cfg.attn_impl,
            mesh=mesh, head_axis=head_axis, window=window, scale=cfg.attn_scale,
        )
        h = _attn_out(attn, gate, x, layer, cfg)
    x, counted = _ffn(h, layer, cfg, live)
    return x, k_l, v_l, counted


# ------------------------------------------- the two groups' program values
#
# The programs hand a family with two page groups both groups' tables in one
# table argument and both groups' packed slots in one slot argument
# (`models/programs.py` `Family`). What follows takes them apart and makes a
# window layer's values: everything a window body is given names blocks
# inside the window alone.


def _inv_freqs(cfg):
    return rope_freqs(cfg.head_dim, cfg.rope_theta, None)


def _window_blocks(cfg, bs: int, rows: int = 1) -> int:
    """Blocks that hold the `sliding_window` keys before a query and the
    `rows - 1` queries behind it, wherever the first of them lies in its
    block."""
    return -(-(cfg.sliding_window + rows - 1) // bs) + 1


def _slice_table(table, first, n: int):
    """`n` entries of a table [nb] from entry `first` (traced), zeros (the
    null block) behind the table's end."""
    padded = jnp.concatenate([table, jnp.zeros((n,), table.dtype)])
    return lax.dynamic_slice(padded, (first,), (n,))


def _halves(cfg, x, axis: int = 0):
    """(the full group's, the window group's) of a table or slot argument:
    its two halves along `axis`, or the one argument twice for a model whose
    layers are all of one kind (one page group: `models.page_groups`)."""
    if len(set(cfg.layer_types)) < 2:
        return x, x
    return jnp.split(x, 2, axis=axis)


def _packed_values(cfg, *, slot_indices, **_):
    full, window = _halves(cfg, slot_indices)
    return {
        "inv_freqs": _inv_freqs(cfg), "no_rope": None,
        "full_slots": full, "window_slots": window,
    }


def _whole_values(cfg, *, positions, valid, slot_indices, block_table, page_size, **_):
    # `slot_indices` are the full group's (`programs.prefill` reads the
    # table's first half); the window group's come from its own half
    _, window = _halves(cfg, block_table)
    nb, bs = window.shape[0], page_size
    page = window[jnp.minimum(positions // bs, nb - 1)]
    return {
        "inv_freqs": _inv_freqs(cfg), "no_rope": None,
        "full_slots": slot_indices,
        "window_slots": jnp.where(valid, page * bs + positions % bs, 0),
    }


def _chunk_values(cfg, *, positions, valid, slot_indices, block_table, chunk_start, page_size, **_):
    full, window = _halves(cfg, block_table)
    nb, bs, C = window.shape[0], page_size, positions.shape[0]
    page = jnp.where(positions // bs < nb, window[jnp.minimum(positions // bs, nb - 1)], 0)
    first = jnp.maximum(chunk_start - cfg.sliding_window + 1, 0) // bs
    return {
        "inv_freqs": _inv_freqs(cfg), "no_rope": None,
        "full_slots": slot_indices, "full_table": full,
        "window_slots": jnp.where(valid, page * bs + positions % bs, 0),
        "window_table": _slice_table(window, first, _window_blocks(cfg, bs, C)),
        "window_start": chunk_start - first * bs,
    }


def _decode_values(cfg, *, positions, live, context, block_tables, page_size, **_):
    full, window = _halves(cfg, block_tables, axis=1)
    nb, bs = window.shape[1], page_size
    n = min(_window_blocks(cfg, bs), nb)
    first = jnp.maximum(context - cfg.sliding_window, 0) // bs
    cols = jnp.minimum(first[:, None] + jnp.arange(n)[None, :], nb - 1)
    page = jnp.take_along_axis(window, (positions // bs)[:, None], axis=1)[:, 0]
    return {
        "inv_freqs": _inv_freqs(cfg), "no_rope": None,
        "full_tables": full,
        "window_tables": jnp.take_along_axis(window, cols, axis=1),
        "window_context": jnp.where(live, context - first * bs, 0),
        "window_slots": jnp.where(live, page * bs + positions % bs, 0),
    }


def _bodies(fn, full: tuple, window: tuple, static: tuple = ()) -> dict:
    """A program's two rows of the family's table: the full layers' body and
    the window layers', each given its own group's values."""
    return {
        False: Body(functools.partial(fn, window=None), 2, ("no_rope",) + full, static),
        True: Body(_windowed(fn), 2, ("inv_freqs",) + window, static),
    }


def _windowed(fn):
    def call(*args, cfg, **kw):
        return fn(*args, cfg=cfg, window=cfg.sliding_window, **kw)

    return call


# A layer by whether it has a window. Both kinds keep pages of keys and
# values `[Hkv, nb, bs, D]`, each of its own group's block count.
FAMILY = Family(
    kind=AfmoeConfig.is_window_layer,
    embed_scale=lambda cfg: math.sqrt(cfg.hidden_size) if cfg.mup_enabled else 1.0,
    prepare={
        "packed": _packed_values, "whole": _whole_values,
        "chunk": _chunk_values, "decode": _decode_values,
    },
    packed=_bodies(
        _packed_layer,
        ("positions", "segment_ids", "full_slots"),
        ("positions", "segment_ids", "window_slots"),
    ),
    whole=_bodies(
        _whole_layer,
        ("positions", "valid_len", "full_slots"),
        ("positions", "valid_len", "window_slots"),
        static=("mesh", "head_axis"),
    ),
    chunk=_bodies(
        _chunk_layer,
        ("positions", "valid", "full_slots", "full_table", "chunk_start"),
        ("positions", "valid", "window_slots", "window_table", "window_start"),
    ),
    decode=_bodies(
        _decode_layer,
        ("positions", "live", "context", "full_tables", "slot_indices"),
        ("positions", "live", "window_context", "window_tables", "window_slots"),
        static=("mesh", "head_axis"),
    ),
)
prefill_packed, prefill, prefill_chunk, decode = programs.bound(FAMILY)
prefill_mm, prefill_context_parallel, embed_pooled, decode_verify = programs.refused(
    "the window-and-full attention family",
    "speculative verification (no verify program over two page groups)",
)
