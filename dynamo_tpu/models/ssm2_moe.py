"""Mamba-2, latent-expert family (`model_type: nemotron_h`, NVIDIA's
Nemotron-H / Nemotron 3 models): every layer is ONE mixer and nothing behind
it, a Mamba-2 mixer, a grouped-query attention mixer or a sparse-expert
mixer whose routed experts work in a latent width, as pure JAX functions
over two kinds of cache side by side.

A fifth block beside `models/llama.py`'s, `models/mla_moe.py`'s,
`models/hybrid_ssm.py`'s and `models/conv_moe.py`'s, with a config and
forwards of its own under the family's names and signatures; it shares
`ops/`, the runner, the engine and the cache manager. What a layer keeps is
what it declares (`layer_cache_kinds`): an attention layer rows per token in
paged blocks, a Mamba-2 layer one slot a sequence of two arrays (`ops/ssm.py`:
the state `[heads, head_dim, d_state]` float32, 4 MiB at the published
sizes, and the convolution's tail, the last `conv_kernel - 1` rows of `x, B,
C` together), an expert layer nothing (`keeps_nothing`: None in both
places). The slot arrays ride where a paged layer's two planes do, with one
row a lane and one more, the null lane's. A decode lane's slot is its row in
the batch; prefill programs are told each sequence's slot (`state_slots`),
zero it at the sequence's position 0 and leave the state there between the
chunks of a chunked prefill.

The block, from the published config and Hugging Face's `NemotronH*` classes
(pre-norm residual, RMS norms with a learned weight, `eps =
layer_norm_epsilon`, no bias but the convolution's, no positional embedding
anywhere, a final RMS norm and an untied head): `h = h + mixer_i(norm_i(h))`,
`mixer_i` by `hybrid_override_pattern[i]`:

* `M`, Mamba-2: `(z, xBC, dt) = split(W_in u)`; `xBC = silu(conv1d_causal_
  depthwise(xBC) + b_conv)`; `(x, B, C) = split(xBC)`, `x` as `[heads,
  head_dim]`, `B` and `C` as `[n_groups, d_state]`, head `j` reads group `j
  // (heads / n_groups)`; `dt = softplus(dt + dt_bias)`; `S_j <- exp(-exp(
  A_log_j) dt_j) S_j + dt_j x_j (outer) B_g`; `y_j = S_j C_g + D_j x_j`; `y =
  group_rms(y * silu(z)) * w_norm` over `n_groups` groups; `out = W_out y`.
  Prefill computes the same recurrence in chunks of `chunk_size`
  (`ops.ssm.ssd_chunk`, `ssd_packed`).
* `*`, attention: grouped-query heads, no bias, NO rotary embedding (the
  public attention applies none; `rope_theta` and `partial_rotary_factor`
  are read by nothing), causal softmax at `1/sqrt(head_dim)`.
* `E`, experts: `s = sigmoid(W_gate u)` over the router's experts; the
  `num_experts_per_tok` with the largest `s + e_score_correction_bias` (one
  group; the bias takes part in the choice only); weights the chosen `s` over
  `(their sum + 1e-20)`, times `routed_scaling_factor`; `v = W_fc1 u` (hidden
  to `moe_latent_size`); expert `e` is `W_down_e relu(W_up_e v)^2`; `out =
  W_fc2 (sum of w_e expert_e(v)) + W_d relu(W_u u)^2`, the shared expert on
  the full width.

A held share of the experts: the config may say that this chip holds the
routed experts `[first_held_expert, first_held_expert + n_routed_experts)`
of `n_routed_experts_published`, as one of the chips that share a layer
does. The router stays as wide as published and chooses among all of them;
the sum runs over the chosen experts that are held, and what the absent ones
would have added is left out (`ops/moe.dropless_experts(first_held=)`); that
partial result goes on to the next layer. No code stands in for the other
chips or their traffic: the exchange between them is not written (ROADMAP
M1).

Departures from the public implementation, each said where it is made:

* the router's logits and scores are float32 at the highest matmul precision
  over weights kept in the model's dtype (the public gate keeps a float32
  weight).
* `A_log`, `D` and `dt_bias` are `[heads]` float32; the state is held
  `[heads, head_dim, d_state]` float32 and the tail float32 (the public
  cache keeps the tail in the model's dtype); the convolution, `dt`, the
  decay and the recurrence are float32, the projections in the weights'
  dtype.
* the convolution's taps are `[conv_kernel, channels]`, the last tap on the
  newest input (PyTorch's `conv1d` weight `[channels, 1, kernel]`,
  transposed).

Not served and refused in words where asked for: a dense feed-forward layer
(`-` in the pattern), the multi-token prediction head
(`num_nextn_predict_layers` > 0), expert groups (`n_group` > 1), projection
biases, other activations, int8 weights, a mesh, an int8-resident cache, the
fused decode step, tiers and transfer of slots, prefix reuse, speculative
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
from jax import lax

from dynamo_tpu.models import (
    CacheKind, keeps_nothing, kv_heads_cache, layer_body, programs,
    recurrent_state,
)
from dynamo_tpu.models.programs import Body, Family
from dynamo_tpu.models.programs import dense as _dense, normal as _normal
from dynamo_tpu.ops import pallas_ssm, ssm
from dynamo_tpu.ops.attention import (
    chunked_prefill_attention, decode_append_attention,
    packed_prefill_attention, write_decode_kv,
)
from dynamo_tpu.ops.basics import rms_norm
from dynamo_tpu.ops.linear import linear
# `STEP_STATS` is read off the family's module by the runner (`decode_multi`)
from dynamo_tpu.ops.moe import (
    HELD_STEP_STATS as STEP_STATS, dropless_experts, expert_step_stats,
    router_sigmoid_topk,
)

MODEL_TYPES = ("nemotron_h",)
# `decode` takes `settle` (`models.programs.decode`): the runner's dispatch of
# several steps says which is its last, as it hands `stats` to a module that
# has `STEP_STATS`
DECODE_SETTLES = True
F32 = jnp.float32
LAYER_KINDS = "M*E"
# the published routing's normaliser: the chosen scores over (their sum + this)
ROUTE_EPS = 1e-20


@dataclass(frozen=True)
class Ssm2MoeConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_layers: int = 88
    # one kind a layer, a literal string (the published one is not periodic)
    pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
    )
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    d_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    shared_intermediate_size: int = 5376
    # the routed experts this chip holds, and which of the router's they are
    num_experts: int = 512
    router_experts: int = 512
    first_held_expert: int = 0
    num_experts_per_tok: int = 22
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    rms_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # set by the runner (`dataclasses.replace`), as on LlamaConfig
    attn_impl: Optional[str] = None
    fused_decode: bool = False
    collective_overlap: bool = False

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "Ssm2MoeConfig":
        pattern = str(d.get("hybrid_override_pattern") or "")
        hidden, heads = d["hidden_size"], d["num_attention_heads"]
        m_heads, m_dim = d["mamba_num_heads"], d["mamba_head_dim"]
        held = d["n_routed_experts"]
        published = d.get("n_routed_experts_published", held)
        first = d.get("first_held_expert", 0)
        unsupported = {
            "hybrid_override_pattern": (
                len(pattern) != d["num_hidden_layers"]
                or any(k not in LAYER_KINDS for k in pattern)
                or any(k not in pattern for k in LAYER_KINDS)
            ),
            "num_nextn_predict_layers": (d.get("num_nextn_predict_layers") or 0) > 0,
            "n_group": d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1,
            "mamba_hidden_act": d.get("mamba_hidden_act", "silu") != "silu",
            "mlp_hidden_act": d.get("mlp_hidden_act", "relu2") != "relu2",
            "use_conv_bias": not d.get("use_conv_bias", True),
            "bias": any(
                d.get(k, False)
                for k in ("mamba_proj_bias", "attention_bias", "mlp_bias", "use_bias")
            ),
            "sliding_window": d.get("sliding_window") is not None,
            "moe_latent_size": not d.get("moe_latent_size"),
            "moe_shared_expert_overlap": bool(d.get("moe_shared_expert_overlap", False)),
            "expand": d.get("expand", 2) * hidden != m_heads * m_dim,
            "n_groups": m_heads % d.get("n_groups", 8) != 0,
            "first_held_expert": first < 0 or first + held > published,
            "torch_dtype": d.get("torch_dtype", "bfloat16") != "bfloat16",
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"model_type {d.get('model_type')!r}: this value of {bad} is "
                "not implemented (served: a `hybrid_override_pattern` entry "
                f"for every layer, each one of {list(LAYER_KINDS)} and all "
                "three kinds present, so no dense feed-forward layer; no "
                "multi-token prediction head (`num_nextn_predict_layers` 0); "
                "one expert group; silu in the Mamba-2 mixer and relu2 in the "
                "experts; a convolution bias and no other bias; no sliding "
                "window; experts in a latent width; no shared-expert overlap; "
                "`expand * hidden_size` equal to `mamba_num_heads * "
                "mamba_head_dim`, heads that divide into `n_groups`; a held "
                "range of experts inside the published count; bfloat16)"
            )
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=hidden,
            num_layers=d["num_hidden_layers"],
            pattern=pattern,
            num_heads=heads,
            num_kv_heads=d.get("num_key_value_heads", heads),
            head_dim=d.get("head_dim") or hidden // heads,
            mamba_heads=m_heads,
            mamba_head_dim=m_dim,
            d_state=d["ssm_state_size"],
            n_groups=d.get("n_groups", 8),
            conv_kernel=d.get("conv_kernel", 4),
            chunk_size=d.get("chunk_size", 128),
            moe_intermediate_size=d["moe_intermediate_size"],
            moe_latent_size=d["moe_latent_size"],
            shared_intermediate_size=(
                d.get("n_shared_experts", 1) * d["moe_shared_expert_intermediate_size"]
            ),
            num_experts=held,
            router_experts=published,
            first_held_expert=first,
            num_experts_per_tok=d["num_experts_per_tok"],
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            rms_eps=float(d.get("layer_norm_epsilon", 1e-5)),
            max_position_embeddings=d.get("max_position_embeddings", 262144),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        )

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "Ssm2MoeConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    @classmethod
    def tiny(cls, vocab_size: int = 256, first_held_expert: int = 0) -> "Ssm2MoeConfig":
        """CPU-test size of the same shape: a literal pattern with all three
        kinds, 16 experts of which 4 are held, 3 a token, 2 groups of `B` and
        `C`, a chunk of 8."""
        return cls(
            vocab_size=vocab_size, hidden_size=64, num_layers=6,
            pattern="MEM*EM", num_heads=4, num_kv_heads=2, head_dim=16,
            mamba_heads=8, mamba_head_dim=16, d_state=16, n_groups=2,
            conv_kernel=4, chunk_size=8, moe_intermediate_size=48,
            moe_latent_size=32, shared_intermediate_size=96, num_experts=4,
            router_experts=16, first_held_expert=first_held_expert,
            num_experts_per_tok=3, routed_scaling_factor=2.5,
            max_position_embeddings=512,
        )

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: `x, B, C` together."""
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    def kind(self, i: int) -> str:
        return self.pattern[i]

    def layers_of(self, kind: str) -> int:
        return self.pattern.count(kind)

    def state_kind(self) -> CacheKind:
        return recurrent_state(
            ((self.mamba_heads, self.mamba_head_dim, self.d_state), "float32"),
            (((self.conv_kernel - 1) * self.conv_dim,), "float32"),
        )

    def layer_cache_kinds(self) -> tuple[CacheKind, ...]:
        by_kind = {
            "M": self.state_kind(),
            "*": kv_heads_cache(self.num_kv_heads, self.head_dim),
            "E": keeps_nothing(),
        }
        return tuple(by_kind[k] for k in self.pattern)


CONFIG = Ssm2MoeConfig  # `models.served_model_types` reads it


# ------------------------------------------------------------------ params

KEYS_PER_LAYER = 12
# keys a layer's draw consumes, by its kind
_LAYER_KEYS = {"M": 6, "*": 4, "E": 8}
# `dt` at init, as Mamba-2 draws it (`time_step_min`, `time_step_max`,
# `time_step_floor`): dt_bias = softplus^-1(dt), dt log-uniform; and the
# decay's rate, `A` uniform in [1, 16]
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)
# the draw of `e_score_correction_bias`: 0.001 x normal, a tenth of the other
# two routed families' scale for their reason (`models/conv_moe.py`
# `EXPERT_BIAS_SCALE`): at the top-22 cut of 512 near-tied scores (0.85 there,
# 0.13 a unit of logit) neighbours lie ten times closer than at a top-4 cut of
# 32. This changes the chosen set of 19% of tokens (0.19 of a token's 22
# experts) and leaves the experts' loads within 0.83 to 1.17 of their mean
# (0.83 to 1.14 with no bias; 20,000 unit-norm tokens through a router of the
# published widths, on the CPU); 0.01 changes 91% of the sets and spreads the
# loads over 0.56 to 1.52
EXPERT_BIAS_SCALE = 0.001


def refuse_int8_weights(quantize: bool) -> None:
    if quantize:
        raise ValueError(
            "int8 weights (DYN_JAX_QUANTIZE_INT8) are not implemented for "
            "state-space mixers and expert stacks: serve this family in "
            "bfloat16"
        )


def init_params(
    config: Ssm2MoeConfig,
    rng: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    quantize: bool = False,
) -> dict:
    """Random weights: matrices normal / sqrt(fan_in) in float32, cast to
    `dtype` (the convolution's taps by their `conv_kernel` inputs, its bias
    0.1 x normal); norms ones; the recurrence's constants as Mamba-2 draws
    them, float32 (`A_log = log(A)`, `A` uniform in [1, 16]; `D = 1`;
    `dt_bias` the inverse softplus of a step log-uniform in [0.001, 0.1]);
    `e_score_correction_bias` `EXPERT_BIAS_SCALE` x normal, float32. Only
    the held experts are drawn, from the layer's key folded with
    `first_held_expert`, so that two shares of one layer hold different
    experts. `cellbench/reference/ssm2_moe.py` makes the same draw from the
    same key, on its own."""
    refuse_int8_weights(quantize)
    c = config
    keys = jax.random.split(rng, 4 + KEYS_PER_LAYER * c.num_layers)
    layers, used = [], 0
    for kind in c.pattern:
        n = _LAYER_KEYS[kind]
        layers.append(_draw_layer(keys[used: used + n], c=c, dtype=dtype, kind=kind))
        used += n
    return {"layers": layers, **_draw_top(keys[used: used + 2], c=c, dtype=dtype)}


@functools.partial(jax.jit, static_argnames=("c", "dtype", "kind"))
def _draw_layer(keys, *, c, dtype, kind):
    """One layer's weights, its keys consumed in order; one program for each
    kind of layer (`models/hybrid_ssm.py` `_draw_layer` says why)."""
    keys = iter(keys)
    dense = lambda shape, fan_in: _dense(next(keys), shape, fan_in, dtype)
    H = c.hidden_size
    layer = {"norm": jnp.ones((H,), dtype)}
    if kind == "*":
        layer.update(
            wq=dense((H, c.q_dim), H), wk=dense((H, c.kv_dim), H),
            wv=dense((H, c.kv_dim), H), wo=dense((c.q_dim, H), c.q_dim),
        )
    elif kind == "M":
        Di, Hm, K = c.d_inner, c.mamba_heads, c.conv_kernel
        uniform = lambda: lax.optimization_barrier(
            jax.random.uniform(next(keys), (Hm,), F32)
        )
        layer["w_in"] = dense((H, Di + c.conv_dim + Hm), H)
        layer["conv_w"] = dense((K, c.conv_dim), K)
        layer["conv_b"] = (0.1 * _normal(next(keys), (c.conv_dim,))).astype(dtype)
        dt = jnp.maximum(DT_FLOOR, jnp.exp(
            uniform() * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)
        ))
        layer["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        layer["A_log"] = jnp.log(A_RANGE[0] + uniform() * (A_RANGE[1] - A_RANGE[0]))
        layer["D"] = jnp.ones((Hm,), F32)
        layer["gate_norm"] = jnp.ones((Di,), dtype)
        layer["w_out"] = dense((Di, H), Di)
    else:
        E, R, L = c.num_experts, c.router_experts, c.moe_latent_size
        F, S = c.moe_intermediate_size, c.shared_intermediate_size
        layer["router"] = dense((H, R), H)
        layer["router_bias"] = EXPERT_BIAS_SCALE * _normal(next(keys), (R,))
        layer["w_fc1"] = dense((H, L), H)
        layer["w_fc2"] = dense((L, H), L)
        held = lambda: jax.random.fold_in(next(keys), c.first_held_expert)
        layer["wu"] = _dense(held(), (E, L, F), L, dtype)
        layer["wd"] = _dense(held(), (E, F, L), F, dtype)
        layer["shared_wu"] = dense((H, S), H)
        layer["shared_wd"] = dense((S, H), S)
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


def mixer_param_counts(config: Ssm2MoeConfig) -> dict[str, int]:
    """A mixer of each kind with the layer's own norm; `E` beside its
    routed experts."""
    c = config
    H, Di, Hm = c.hidden_size, c.d_inner, c.mamba_heads
    return {
        "M": (
            H * (Di + c.conv_dim + Hm) + c.conv_kernel * c.conv_dim + c.conv_dim
            + 3 * Hm + Di + Di * H + H
        ),
        "*": 2 * H * c.q_dim + 2 * H * c.kv_dim + H,
        "E": (
            H * c.router_experts + c.router_experts
            + 2 * H * c.moe_latent_size + 2 * H * c.shared_intermediate_size + H
        ),
    }


def routed_expert_params(config: Ssm2MoeConfig) -> int:
    """One routed expert: up and down, in the latent width."""
    return 2 * config.moe_latent_size * config.moe_intermediate_size


def expert_param_count(config: Ssm2MoeConfig) -> int:
    """Parameters in the routed expert stacks this chip holds."""
    c = config
    return c.layers_of("E") * c.num_experts * routed_expert_params(c)


def param_count(config: Ssm2MoeConfig) -> int:
    c = config
    per = mixer_param_counts(c)
    H = c.hidden_size
    return (
        sum(per[k] for k in c.pattern) + expert_param_count(c)
        + c.vocab_size * H * (1 if c.tie_word_embeddings else 2) + H
    )


# ----------------------------------------------------------------- forward


def _qkv(x, layer, cfg):
    T = x.shape[0]
    h = rms_norm(x, layer["norm"], cfg.rms_eps)
    q = linear(h, layer["wq"]).reshape(T, cfg.num_heads, cfg.head_dim)
    k = linear(h, layer["wk"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = linear(h, layer["wv"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _attn_out(attn, x, layer, cfg):
    return x + linear(attn.reshape(x.shape[0], cfg.q_dim), layer["wo"])


def _relu2(v):
    v = jax.nn.relu(v)
    return v * v


def _experts(x, layer, valid, *, cfg):
    """The expert mixer and its residual. Returns x and what the layer
    counted (`STEP_STATS`)."""
    h = rms_norm(x, layer["norm"], cfg.rms_eps)
    k = cfg.num_experts_per_tok
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(
            h.astype(F32), layer["router"].astype(F32),
            precision=lax.Precision.HIGHEST,
        )
        idx, weights = router_sigmoid_topk(
            logits, layer["router_bias"], k, scale=cfg.routed_scaling_factor,
            renormalize=cfg.norm_topk_prob, eps=ROUTE_EPS,
        )
    with jax.named_scope("moe.latent"):
        v = linear(h, layer["w_fc1"])
    with jax.named_scope("moe.experts"):
        y, group_sizes = dropless_experts(
            v, idx, weights, None, layer["wu"], layer["wd"], valid=valid,
            first_held=cfg.first_held_expert, form="relu2", impl=cfg.attn_impl,
        )
    with jax.named_scope("moe.latent"):
        routed = linear(y.astype(x.dtype), layer["w_fc2"])
    with jax.named_scope("moe.shared"):
        shared = linear(_relu2(linear(h, layer["shared_wu"])), layer["shared_wd"])
    made = jnp.sum(valid.astype(jnp.int32)) * k
    return x + routed + shared, expert_step_stats(group_sizes, made)


def _in_proj(x, layer, cfg):
    """(the gate z [T, d_inner], the convolution's input xBC [T, conv_dim],
    dt [T, heads] before its bias)."""
    Di, Dc = cfg.d_inner, cfg.conv_dim
    proj = linear(rms_norm(x, layer["norm"], cfg.rms_eps), layer["w_in"])
    return proj[:, :Di], proj[:, Di: Di + Dc], proj[:, Di + Dc:]


def _update_inputs(conv, dt, layer, cfg):
    """From the convolution's output [T, conv_dim] float32: x [T, heads,
    head_dim], B and C [T, groups, d_state] (behind the silu), dt [T, heads]
    behind its softplus, and the heads' decay rates (negative)."""
    T = conv.shape[0]
    Di, G, N = cfg.d_inner, cfg.n_groups, cfg.d_state
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :Di].reshape(T, cfg.mamba_heads, cfg.mamba_head_dim)
    b = xbc[:, Di: Di + G * N].reshape(T, G, N)
    c = xbc[:, Di + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])
    return xs, b, c, dt, -jnp.exp(layer["A_log"])


def _mixer_out(y, xs, z, x, layer, cfg):
    """y, xs [T, heads, head_dim] float32: the skip, the gate, the grouped
    norm, the projection and the residual."""
    T = x.shape[0]
    y = (y + layer["D"][:, None] * xs).reshape(T, cfg.d_inner)
    y = (y * jax.nn.silu(z.astype(F32))).reshape(T, cfg.n_groups, -1)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
    y = y.reshape(T, cfg.d_inner) * layer["gate_norm"].astype(F32)
    return x + linear(y.astype(x.dtype), layer["w_out"])


# Three bodies for each kind of pass over the layers (`models.layer_body`:
# traced and lowered once a program each): the Mamba-2 one, the attention
# one and the expert one. A Mamba-2 or attention body takes the layer's two
# cache arrays and returns them; an expert body returns what it counted.


@layer_body("cfg")
def _mamba_packed_layer(x, layer, state, tail, positions, valid, last_idx, seg_slots, count, *, cfg):
    z, xbc, dt = _in_proj(x, layer, cfg)
    K = cfg.conv_kernel
    with jax.named_scope("ssm2.mix"):
        conv, _ = ssm.conv_sequence(
            xbc, jnp.zeros((K - 1, cfg.conv_dim), F32), positions,
            layer["conv_w"], layer["conv_b"],
        )
        tail = tail.at[seg_slots].set(ssm.packed_tails(xbc, positions, last_idx, K))
        xs, b, c, dt, a = _update_inputs(conv, dt, layer, cfg)
        with jax.named_scope("ssm2.update"):
            y, state = ssm.ssd_packed(
                state, xs, dt, a, b, c, positions, valid, last_idx, seg_slots,
                count, cfg.chunk_size,
            )
        return _mixer_out(y, xs, z, x, layer, cfg), state, tail


@layer_body("cfg")
def _attn_packed_layer(x, layer, k_l, v_l, segment_ids, slot_indices, *, cfg):
    q, k, v = _qkv(x, layer, cfg)
    k_l, v_l = write_decode_kv(k_l, v_l, k, v, slot_indices)
    attn = packed_prefill_attention(q, k, v, segment_ids, scale=cfg.attn_scale)
    return _attn_out(attn, x, layer, cfg), k_l, v_l


@layer_body("cfg")
def _mamba_chunk_layer(x, layer, state, tail, positions, valid, slot, chunk_start, *, cfg):
    z, xbc, dt = _in_proj(x, layer, cfg)
    K = cfg.conv_kernel
    fresh = chunk_start == 0
    with jax.named_scope("ssm2.mix"):
        prev = jnp.where(fresh, 0.0, tail[slot]).reshape(K - 1, cfg.conv_dim)
        conv, stream = ssm.conv_sequence(
            xbc, prev, positions, layer["conv_w"], layer["conv_b"]
        )
        tail = tail.at[slot].set(ssm.tail_after(stream, jnp.sum(valid), K))
        xs, b, c, dt, a = _update_inputs(conv, dt, layer, cfg)
        with jax.named_scope("ssm2.update"):
            y, h = ssm.ssd_chunk(
                jnp.where(fresh, 0.0, state[slot]), xs, dt, a, b, c, valid,
                cfg.chunk_size,
            )
            state = state.at[slot].set(h)
        return _mixer_out(y, xs, z, x, layer, cfg), state, tail


@layer_body("cfg")
def _attn_chunk_layer(x, layer, k_l, v_l, slots, block_table, chunk_start, *, cfg):
    q, k, v = _qkv(x, layer, cfg)
    k_l, v_l = write_decode_kv(k_l, v_l, k, v, slots)
    attn = chunked_prefill_attention(
        q, k_l, v_l, block_table, chunk_start, scale=cfg.attn_scale
    )
    return _attn_out(attn, x, layer, cfg), k_l, v_l


@layer_body("cfg", "settle")
def _mamba_decode_layer(x, layer, state, tail, live, *, cfg, settle):
    # the tail's every row is updated under one mask, the null lane's with
    # them (it is never live), and so is the state's where the plain form
    # runs: no slice of the arrays, no update of a slice, so the step writes
    # them where they lie. Where the kernel runs it visits the live lanes'
    # rows alone, and `state` is what the dispatch's step before this one
    # deferred (`ops.pallas_ssm`)
    B, S = x.shape[0], tail.shape[0]
    z, xbc, dt = _in_proj(x, layer, cfg)
    rows = lambda v: jnp.pad(v, ((0, S - B),) + ((0, 0),) * (v.ndim - 1))
    live_rows = rows(live)
    with jax.named_scope("ssm2.mix"):
        conv, new_tail = ssm.conv_step(rows(xbc), tail, layer["conv_w"], layer["conv_b"])
        tail = jnp.where(live_rows[:, None], new_tail, tail)
        xs, b, c, dt, a = _update_inputs(conv[:B], dt, layer, cfg)
        with jax.named_scope("ssm2.update"):
            state, y = pallas_ssm.ssd_update(
                state, xs, dt, a, b, c, live, settle=settle, impl=cfg.attn_impl
            )
        return _mixer_out(y, xs, z, x, layer, cfg), state, tail


@layer_body("cfg", "mesh", "head_axis")
def _attn_decode_layer(x, layer, k_l, v_l, context, block_tables, slot_indices, *, cfg, mesh, head_axis):
    q, k, v = _qkv(x, layer, cfg)
    attn, k_l, v_l = decode_append_attention(
        q, k_l, v_l, k, v, slot_indices, block_tables, context,
        impl=cfg.attn_impl, mesh=mesh, head_axis=head_axis,
        scale=cfg.attn_scale,
    )
    return _attn_out(attn, x, layer, cfg), k_l, v_l


_expert_layer = layer_body("cfg")(_experts)


def _packed_slots(cfg, *, segment_ids, last_idx, state_slots, null, **_):
    """The packed program's lane slots (`seg_slots` [N]) and how many of its
    segments hold a prompt (`count`)."""
    # a segment that holds no prompt sends what is computed for it to the
    # null lane
    count = jnp.max(segment_ids) + 1
    used = jnp.arange(last_idx.shape[0]) < count
    seg_slots = jnp.where(used, state_slots, null).astype(jnp.int32)
    return {"seg_slots": seg_slots, "count": count}


def _settles(cfg, *, settle, **_):
    """Whether this decode step writes the Mamba-2 layers' state: the
    dispatch's word where the kernel runs; always where the plain form does,
    whose every step writes, so that its layers are one body as ever."""
    kernel = pallas_ssm.tiling(
        cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state, cfg.n_groups,
        cfg.attn_impl,
    )
    return {"settle": settle or kernel is None}


# A layer by its letter in the pattern. `M` keeps the state `[S, Hm, P, N]`
# and the tail `[S, (K-1)*conv_dim]`, `*` pages of keys and values
# `[Hkv, nb, bs, D]`; `E` keeps nothing and is told which tokens are real.
FAMILY = Family(
    kind=Ssm2MoeConfig.kind,
    prepare={"packed": _packed_slots, "decode": _settles},
    packed={
        "M": Body(_mamba_packed_layer, 2, (
            "positions", "valid", "last_idx", "seg_slots", "count")),
        "*": Body(_attn_packed_layer, 2, ("segment_ids", "slot_indices")),
        "E": Body(_expert_layer, 0, ("valid",)),
    },
    chunk={
        "M": Body(_mamba_chunk_layer, 2, (
            "positions", "valid", "lane_slot", "chunk_start")),
        "*": Body(_attn_chunk_layer, 2, (
            "slot_indices", "block_table", "chunk_start")),
        "E": Body(_expert_layer, 0, ("valid",)),
    },
    decode={
        "M": Body(_mamba_decode_layer, 2, ("live",), static=("settle",)),
        "*": Body(
            _attn_decode_layer, 2, ("context", "block_tables", "slot_indices"),
            static=("mesh", "head_axis"),
        ),
        "E": Body(_expert_layer, 0, ("live",)),
    },
)
prefill_packed, prefill, prefill_chunk, decode = programs.bound(FAMILY)
prefill_mm, prefill_context_parallel, embed_pooled, decode_verify = programs.refused(
    "the Mamba-2, latent-expert family",
    "speculative verification (a rejected draft would need the state rolled back)",
)
