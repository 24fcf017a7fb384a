"""Plain float32 forward pass of the latent-attention, sparse-expert block of
JoyAI-LLM-Flash, and the seeded weights it runs on.

The block, as the model's `config.json` and DeepSeek-V3's published
`modeling_deepseek` code (whose forms this family takes at its own sizes)
describe it. Pre-norm RMSNorm (float32, weight times the normalised value,
eps 1e-6), residual after attention and after the feed-forward.

* Attention (multi-head latent attention): `cq = norm(x Wqa)`; `q = cq Wqb`,
  split per head into `q_nope` (128) and `q_pe` (64). `x Wkva` gives the
  latent `ckv` (512), normed, and one rope key `k_pe` (64) that all heads
  share; per head `[k_nope | v] = ckv Wkvb`. Rotary embedding on `q_pe` and
  `k_pe` rotates adjacent pairs (2i, 2i+1) by `pos * theta^(-2i/64)`
  (`rope_interleave`; `rope_scaling` null: no scaling of lengths or of the
  softmax). Scores `(q_nope.k_nope + q_pe.k_pe) / sqrt(192)`, causal softmax,
  output `sum p v`, then `Wo`. No biases. This file computes the per-head
  form only: keys and values of every head are made from the latent; nothing
  is cached and nothing absorbed.
* Feed-forward, layers below `first_k_dense_replace`: SwiGLU (`silu(gate) *
  up`, then `down`) of `intermediate_size`. The others: `s = sigmoid(x Wr)`;
  the `num_experts_per_tok` experts with the largest `s + b` (`b` the
  score-correction bias; one group, so no group limit); weights
  `routed_scaling_factor * s_sel / (sum s_sel + 1e-20)`, from `s` and not
  from `s + b`; `y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)`. Computed as a
  loop over the experts, each on the tokens routed to it and on no other.
* Final RMSNorm and an untied output head.

Departures from the published description, each forced by the benchmark:

* weights are random, from a seed: the same draw the program's
  `models.mla_moe.init_params` makes (threefry keys split 4 + 16 * layers
  ways and consumed in order; normal / sqrt(fan_in), cast to bfloat16; the
  correction bias 0.01 * normal in float32, so that it changes selections
  and leaves the experts' loads to the router's scores),
  copied here: the reference makes its own weights from the seed and takes
  nothing the program made. The pass multiplies by the bfloat16 weights
  widened to float32.
* the published code applies rope after de-interleaving the pairs into
  halves; here the pairs are rotated where they lie. The two differ by one
  fixed permutation of the 64 rope dimensions, applied to queries and keys
  alike, so every score is the same.
* the multi-token-prediction module (`num_nextn_predict_layers`) takes no
  part in the next-token logits and is not computed.
* the expert loop pads each expert's tokens to the next power of two with
  zero rows (fewer shapes to compile); the rows are dropped again.

`lower` selects the control: the same pass in the nearest precision below
the one the configuration states ("int8_weights": every weight matrix
re-quantised to int8 per output channel, symmetric; "int8_activations":
every matmul's input rounded to int8 per token).
"""

from __future__ import annotations

import functools
import math
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
KEYS_PER_LAYER = 16


def dims(hf: dict) -> dict:
    """The sizes the pass needs, from a Hugging Face config.json dict."""
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("the reference knows one routing group only")
    if hf.get("rope_scaling") is not None:
        raise ValueError("the reference knows no rope scaling")
    return {
        "hidden": hf["hidden_size"],
        "inter": hf["intermediate_size"],
        "moe_inter": hf["moe_intermediate_size"],
        "layers": hf["num_hidden_layers"],
        "first_dense": hf.get("first_k_dense_replace", 0),
        "heads": hf["num_attention_heads"],
        "q_rank": hf["q_lora_rank"],
        "kv_rank": hf["kv_lora_rank"],
        "nope": hf["qk_nope_head_dim"],
        "rope": hf["qk_rope_head_dim"],
        "v_dim": hf["v_head_dim"],
        "experts": hf["n_routed_experts"],
        "top_k": hf["num_experts_per_tok"],
        "shared": hf.get("n_shared_experts", 0) or 0,
        "route_scale": float(hf.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "vocab": hf["vocab_size"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "tied": bool(hf.get("tie_word_embeddings", False)),
    }


# ------------------------------------------------------------ seeded weights


def seeded_layers(d: dict, seed: int) -> Iterator[dict]:
    """Layer after layer of bfloat16 weights, then a last dict with the
    embedding, the final norm and the head."""
    keys = iter(jax.random.split(
        jax.random.PRNGKey(seed), 4 + KEYS_PER_LAYER * d["layers"]
    ))

    def dense(shape, fan_in):
        w = jax.random.normal(next(keys), shape, dtype=F32)
        return (w / jnp.sqrt(F32(fan_in))).astype(BF16)

    H, Hq = d["hidden"], d["heads"]
    F, E = d["moe_inter"], d["experts"]
    for i in range(d["layers"]):
        layer = {
            "attn_norm": jnp.ones((H,), F32),
            "wq_a": dense((H, d["q_rank"]), H),
            "q_norm": jnp.ones((d["q_rank"],), F32),
            "wq_b": dense((d["q_rank"], Hq * (d["nope"] + d["rope"])), d["q_rank"]),
            "wkv_a": dense((H, d["kv_rank"] + d["rope"]), H),
            "kv_norm": jnp.ones((d["kv_rank"],), F32),
            "wkv_b": dense((d["kv_rank"], Hq * (d["nope"] + d["v_dim"])), d["kv_rank"]),
            "wo": dense((Hq * d["v_dim"], H), Hq * d["v_dim"]),
            "mlp_norm": jnp.ones((H,), F32),
        }
        if i >= d["first_dense"]:
            layer["router"] = dense((H, E), H)
            layer["router_bias"] = 0.01 * jax.random.normal(next(keys), (E,), F32)
            layer["wg"] = dense((E, H, F), H)
            layer["wu"] = dense((E, H, F), H)
            layer["wd"] = dense((E, F, H), F)
            if d["shared"]:
                S = F * d["shared"]
                layer["sg"] = dense((H, S), H)
                layer["su"] = dense((H, S), H)
                layer["sd"] = dense((S, H), S)
        else:
            layer["wg"] = dense((H, d["inter"]), H)
            layer["wu"] = dense((H, d["inter"]), H)
            layer["wd"] = dense((d["inter"], H), d["inter"])
        yield layer
    embed = (jax.random.normal(next(keys), (d["vocab"], H), F32) * 0.02).astype(BF16)
    top = {"embed": embed, "final_norm": jnp.ones((H,), F32)}
    if not d["tied"]:
        top["lm_head"] = dense((H, d["vocab"]), H)
    yield top


# ---------------------------------------------------------------- the pass


def _weight(w, lower: Optional[str]):
    """Float32 weight; the int8 control re-quantises it per output channel."""
    w = w.astype(F32)
    if lower == "int8_weights":
        amax = jnp.max(jnp.abs(w), axis=0)
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        w = jnp.clip(jnp.round(w / s), -127, 127) * s
    return w


def _matmul(x, w, lower: Optional[str]):
    if lower == "int8_activations":
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        x = jnp.clip(jnp.round(x / s), -127, 127) * s
    return jnp.matmul(x, _weight(w, lower))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope_pairs(x, positions, theta):
    """x [T, ..., R]: rotate each adjacent pair (2i, 2i+1)."""
    R = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=F32) / R))
    ang = positions.astype(F32)[:, None] * inv  # [T, R/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (R // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack(
        [even * jnp.cos(ang) - odd * jnp.sin(ang),
         odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1,
    )
    return out.reshape(x.shape)


def attention(x, layer, d: dict, lower: Optional[str] = None):
    """One sequence, all positions at once, per head. x [T, hidden]."""
    T = x.shape[0]
    Hq, N, R, V = d["heads"], d["nope"], d["rope"], d["v_dim"]
    pos = jnp.arange(T)
    h = _rms(x, layer["attn_norm"], d["eps"])
    cq = _rms(_matmul(h, layer["wq_a"], lower), layer["q_norm"], d["eps"])
    q = _matmul(cq, layer["wq_b"], lower).reshape(T, Hq, N + R)
    q_nope, q_pe = q[..., :N], _rope_pairs(q[..., N:], pos, d["theta"])
    kv = _matmul(h, layer["wkv_a"], lower)
    ckv = _rms(kv[:, : d["kv_rank"]], layer["kv_norm"], d["eps"])
    k_pe = _rope_pairs(kv[:, d["kv_rank"]:], pos, d["theta"])  # [T, R]
    up = _matmul(ckv, layer["wkv_b"], lower).reshape(T, Hq, N + V)
    k_nope, v = up[..., :N], up[..., N:]
    scores = (
        jnp.einsum("thd,shd->hts", q_nope, k_nope)
        + jnp.einsum("thd,sd->hts", q_pe, k_pe)
    ) / math.sqrt(N + R)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return x + _matmul(out.reshape(T, Hq * V), layer["wo"], lower)


def _swiglu(h, wg, wu, wd, lower):
    act = jax.nn.silu(_matmul(h, wg, lower)) * _matmul(h, wu, lower)
    return _matmul(act, wd, lower)


def route(h, layer, d: dict, lower: Optional[str] = None):
    """Expert ids [N, k] and weights [N, k] of tokens h [N, hidden]."""
    s = jax.nn.sigmoid(_matmul(h, layer["router"], lower))
    _, idx = jax.lax.top_k(s + layer["router_bias"], d["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if d["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * d["route_scale"]


@functools.partial(jax.jit, static_argnums=(4,))
def _expert(xe, wg, wu, wd, lower):
    return _swiglu(xe, wg, wu, wd, lower)


def experts(h, layer, d: dict, lower: Optional[str] = None):
    """The routed experts as a loop over the experts, each on the tokens
    routed to it; plus the shared expert. h [N, hidden] -> [N, hidden]."""
    idx, w = route(h, layer, d, lower)
    idx_np, w_np = np.asarray(idx), np.asarray(w, np.float32)
    y = np.zeros(h.shape, np.float32)
    for e in range(d["experts"]):
        tok, slot = np.nonzero(idx_np == e)
        if tok.size == 0:
            continue
        bucket = 1 << (int(tok.size) - 1).bit_length()
        rows = np.zeros(bucket, np.int64)
        rows[: tok.size] = tok
        xe = jnp.where(
            (jnp.arange(bucket) < tok.size)[:, None], h[jnp.asarray(rows)], 0.0
        )
        ye = _expert(xe, layer["wg"][e], layer["wu"][e], layer["wd"][e], lower)
        y[tok] += np.asarray(ye[: tok.size]) * w_np[tok, slot][:, None]
    out = jnp.asarray(y)
    if "sg" in layer:
        out = out + _swiglu(h, layer["sg"], layer["su"], layer["sd"], lower)
    return out


class _Static(dict):
    """A dict of sizes that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention_batch(x, layer, d, lower):
    return jax.vmap(lambda seq: attention(seq, layer, d, lower))(x)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_ffn(x, layer, d, lower):
    h = _rms(x, layer["mlp_norm"], d["eps"])
    return x + _swiglu(h, layer["wg"], layer["wu"], layer["wd"], lower)


def layer_forward(x, layer, d: dict, lower: Optional[str] = None):
    """One block on sequences x [P, T, hidden] of one length."""
    d = _Static(d)
    attn = {k: v for k, v in layer.items() if k in (
        "attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
    )}
    x = _attention_batch(x, attn, d, lower)
    if "router" not in layer:
        ffn = {k: layer[k] for k in ("mlp_norm", "wg", "wu", "wd")}
        return _dense_ffn(x, ffn, d, lower)
    P, T, H = x.shape
    h = _rms(x, layer["mlp_norm"], d["eps"]).reshape(P * T, H)
    return x + experts(h, layer, d, lower).reshape(P, T, H)


def head_forward(x, top, d: dict, lower: Optional[str] = None):
    h = _rms(x, top["final_norm"], d["eps"])
    w = top["embed"].T if d["tied"] else top["lm_head"]
    return _matmul(h, w, lower)


def forward(layers, top: dict, d: dict, tokens, rows=None,
            lower: Optional[str] = None):
    """Logits [P, rows, vocab] of P sequences of one length (`tokens`
    [P, T]) at the positions `rows` (all if None), in float32 at the highest
    matmul precision. `layers` is any iterable of layer dicts."""
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(F32)[jnp.asarray(tokens)]
        for layer in layers:
            x = layer_forward(x, layer, d, lower)
        if rows is not None:
            x = x[:, jnp.asarray(rows)]
        return head_forward(x, top, d, lower)
