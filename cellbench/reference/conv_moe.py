"""Plain float32 forward pass of the short-convolution, sparse-expert block
of Liquid AI's LFM2-8B-A1B (`model_type: lfm2_moe`), and the seeded weights
it runs on.

The block, as the model's `config.json` and Hugging Face's `Lfm2Moe*`
classes describe it. Pre-norm RMSNorm (float32, weight times the normalised
value, `eps = norm_eps`); `h = h + op_i(norm(h)); h = h + ff_i(norm(h))`; no
bias anywhere; a final RMSNorm and a head tied to the embedding.

* `layer_types[i] == "conv"`: `(B, C, x) = split3(W_in u)`, in that order;
  `g = B * x`; a depthwise causal convolution over the last `conv_L_cache`
  gated inputs, zeros before the sequence's start, no bias and no
  activation; `out = W_out (C * conv)`. The convolution is a sum of the
  whole sequence shifted by 0, 1, ..., `conv_L_cache - 1` positions: nothing
  is kept between calls.
* `layer_types[i] == "full_attention"`: `num_attention_heads` query heads
  over `num_key_value_heads` key-value heads of `hidden / heads`; an RMSNorm
  with its own weight over each head of `q` and of `k`, then the rotary
  embedding over the whole head in the half-split form (`x1 cos - x2 sin |
  x2 cos + x1 sin`, the halves `x1 = x[:D/2]`, `x2 = x[D/2:]`) at
  `rope_theta`; causal softmax at `1/sqrt(head)`. Computed per head over the
  whole sequence.
* feed-forward, layers below `num_dense_layers`: `W2(silu(W1 x) * W3 x)` at
  `intermediate_size`. The others: `s = sigmoid(x W_gate)`; the
  `num_experts_per_tok` experts with the largest `s + expert_bias`; weights
  `routed_scaling_factor * s_sel / (sum s_sel + 1e-6)`, from `s` and not
  from `s + expert_bias`; `y = sum_e w_e SwiGLU_e(x)` at
  `moe_intermediate_size`, no shared expert. Computed as a loop over the
  experts, each on the tokens routed to it and on no other.

Departures from the public implementation, each forced by the benchmark:

* weights are random, from a seed: the same draw the program's
  `models.conv_moe.init_params` makes (threefry keys split 4 + 12 * layers
  ways and consumed in order; matrices normal / sqrt(fan_in) cast to
  bfloat16, the convolution's taps by their `conv_L_cache` inputs;
  `expert_bias` 0.01 x normal in float32, so that it changes selections and
  leaves the experts' loads to the router's scores), copied here: the
  reference makes its own weights from the seed and takes nothing the
  program made. The pass multiplies by the bfloat16 weights widened to
  float32.
* the convolution's taps are `[conv_L_cache, hidden]`, the last tap on the
  newest input (PyTorch's `conv1d` weight `[hidden, 1, L]`, transposed).
* the published code keeps every activation in bfloat16; here everything is
  float32.
* the expert loop pads each expert's tokens to the next power of two with
  zero rows (fewer shapes to compile); the rows are dropped again.

`lower` selects the control: the same pass in the nearest precision below
the one the configuration states ("int8_weights": every weight matrix
re-quantised to int8 per output channel, symmetric; "fp8_conv": the gated
product, which is what a lane's tail keeps, rounded to float8_e4m3fn, the
8-bit float with the most mantissa: one precision below the bfloat16 that
the configuration states for it and the public implementation keeps it in).
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
KEYS_PER_LAYER = 12
EXPERT_BIAS_SCALE = 0.01
ROUTE_EPS = 1e-6


def dims(hf: dict) -> dict:
    """The sizes the pass needs, from a Hugging Face config.json dict."""
    if hf.get("conv_bias"):
        raise ValueError("the reference knows no convolution bias")
    if hf.get("rope_scaling") is not None:
        raise ValueError("the reference knows no rope scaling")
    kinds = tuple(hf["layer_types"])
    layers = hf["num_hidden_layers"]
    if len(kinds) != layers or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("layer_types must name conv or full_attention for every layer")
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    taps = hf.get("conv_L_cache", 3)
    return {
        "hidden": hidden,
        "inter": hf["intermediate_size"],
        "moe_inter": hf["moe_intermediate_size"],
        "layers": layers,
        "kinds": kinds,
        "attn_layers": sum(k == "full_attention" for k in kinds),
        "conv_layers": sum(k == "conv" for k in kinds),
        "first_dense": hf.get("num_dense_layers", 0),
        "heads": heads,
        "kv_heads": hf.get("num_key_value_heads", heads),
        "head_dim": hf.get("head_dim") or hidden // heads,
        "taps": taps,
        # a lane's slot: the last taps - 1 gated inputs, flat
        "tail_width": (taps - 1) * hidden,
        "experts": hf["num_experts"],
        "top_k": hf["num_experts_per_tok"],
        "route_scale": float(hf.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "expert_bias": bool(hf.get("use_expert_bias", True)),
        "vocab": hf["vocab_size"],
        "theta": float(hf.get("rope_theta", 1000000.0)),
        "eps": float(hf.get("norm_eps", 1e-5)),
        "tied": bool(hf.get("tie_word_embeddings", True)),
    }


def attends(d: dict, i: int) -> bool:
    return d["kinds"][i] == "full_attention"


# ------------------------------------------------------------ seeded weights


def seeded_layers(d: dict, seed: int) -> Iterator[dict]:
    """Layer after layer of weights (matrices bfloat16), then a last dict
    with the embedding and the final norm (and the head, if untied)."""
    keys = iter(jax.random.split(
        jax.random.PRNGKey(seed), 4 + KEYS_PER_LAYER * d["layers"]
    ))

    def dense(shape, fan_in):
        w = jax.random.normal(next(keys), shape, dtype=F32)
        return (w / jnp.sqrt(F32(fan_in))).astype(BF16)

    H, D, K = d["hidden"], d["head_dim"], d["taps"]
    q_dim, kv_dim = d["heads"] * D, d["kv_heads"] * D
    E, F = d["experts"], d["moe_inter"]
    for i in range(d["layers"]):
        layer = {"op_norm": jnp.ones((H,), F32)}
        if attends(d, i):
            layer["wq"] = dense((H, q_dim), H)
            layer["wk"] = dense((H, kv_dim), H)
            layer["wv"] = dense((H, kv_dim), H)
            layer["wo"] = dense((q_dim, H), q_dim)
            layer["q_norm"] = jnp.ones((D,), F32)
            layer["k_norm"] = jnp.ones((D,), F32)
        else:
            layer["w_in"] = dense((H, 3 * H), H)
            layer["conv_w"] = dense((K, H), K)
            layer["w_out"] = dense((H, H), H)
        layer["ffn_norm"] = jnp.ones((H,), F32)
        if i >= d["first_dense"]:
            layer["router"] = dense((H, E), H)
            bias = EXPERT_BIAS_SCALE * jax.random.normal(next(keys), (E,), F32)
            layer["router_bias"] = bias if d["expert_bias"] else jnp.zeros((E,), F32)
            layer["wg"] = dense((E, H, F), H)
            layer["wu"] = dense((E, H, F), H)
            layer["wd"] = dense((E, F, H), F)
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
    return jnp.matmul(x, _weight(w, lower))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope_halves(x, positions, theta):
    """x [T, heads, D]: rotate (x[i], x[i + D/2]) by `pos * theta^(-2i/D)`."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = positions.astype(F32)[:, None, None] * inv  # [T, 1, D/2]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1,
    )


def attention(x, layer, d: dict, lower: Optional[str] = None):
    """One sequence, all positions at once, per head. x [T, hidden]."""
    T = x.shape[0]
    Hq, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(T)
    h = _rms(x, layer["op_norm"], d["eps"])
    q = _matmul(h, layer["wq"], lower).reshape(T, Hq, D)
    k = _matmul(h, layer["wk"], lower).reshape(T, Hkv, D)
    v = _matmul(h, layer["wv"], lower).reshape(T, Hkv, D)
    q = _rope_halves(_rms(q, layer["q_norm"], d["eps"]), pos, d["theta"])
    k = _rope_halves(_rms(k, layer["k_norm"], d["eps"]), pos, d["theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(D)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return x + _matmul(out.reshape(T, Hq * D), layer["wo"], lower)


def gated_input(x, layer, d: dict, lower: Optional[str] = None):
    """(g = B * x, the output gate C), each [T, hidden], of one sequence."""
    H = d["hidden"]
    bcx = _matmul(_rms(x, layer["op_norm"], d["eps"]), layer["w_in"], lower)
    b, c, xs = bcx[:, :H], bcx[:, H: 2 * H], bcx[:, 2 * H:]
    g = b * xs
    if lower == "fp8_conv":
        g = g.astype(jnp.float8_e4m3fn).astype(F32)
    return g, c


def short_conv(x, layer, d: dict, lower: Optional[str] = None):
    """One sequence through one gated short convolution and its residual:
    the sequence of gated inputs shifted by 0 .. taps - 1 positions."""
    T, K = x.shape[0], d["taps"]
    g, c = gated_input(x, layer, d, lower)
    padded = jnp.concatenate([jnp.zeros((K - 1, g.shape[1]), F32), g], axis=0)
    taps = layer["conv_w"].astype(F32)
    conv = sum(taps[k] * padded[k: k + T] for k in range(K))
    return x + _matmul(c * conv, layer["w_out"], lower)


def _swiglu(h, wg, wu, wd, lower):
    act = jax.nn.silu(_matmul(h, wg, lower)) * _matmul(h, wu, lower)
    return _matmul(act, wd, lower)


def route(h, layer, d: dict, lower: Optional[str] = None):
    """Expert ids [N, k] and weights [N, k] of tokens h [N, hidden]."""
    s = jax.nn.sigmoid(_matmul(h, layer["router"], lower))
    _, idx = jax.lax.top_k(s + layer["router_bias"], d["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if d["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return idx, w * d["route_scale"]


@functools.partial(jax.jit, static_argnums=(4,))
def _expert(xe, wg, wu, wd, lower):
    return _swiglu(xe, wg, wu, wd, lower)


def experts(h, layer, d: dict, lower: Optional[str] = None):
    """The routed experts as a loop over the experts, each on the tokens
    routed to it. h [N, hidden] -> [N, hidden]."""
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
    return jnp.asarray(y)


class _Static(dict):
    """A dict of sizes that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mixer(x, layer, d, lower):
    mixer = attention if "wq" in layer else short_conv
    return jax.vmap(lambda seq: mixer(seq, layer, d, lower))(x)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_ffn(x, layer, d, lower):
    h = _rms(x, layer["ffn_norm"], d["eps"])
    return x + _swiglu(h, layer["wg"], layer["wu"], layer["wd"], lower)


_FFN_KEYS = ("ffn_norm", "router", "router_bias", "wg", "wu", "wd")


def layer_forward(x, layer, d: dict, lower: Optional[str] = None):
    """One block on sequences x [P, T, hidden] of one length."""
    d = _Static(d)
    x = _mixer(x, {k: v for k, v in layer.items() if k not in _FFN_KEYS}, d, lower)
    if "router" not in layer:
        ffn = {k: layer[k] for k in ("ffn_norm", "wg", "wu", "wd")}
        return _dense_ffn(x, ffn, d, lower)
    P, T, H = x.shape
    h = _rms(x, layer["ffn_norm"], d["eps"]).reshape(P * T, H)
    return x + experts(h, layer, d, lower).reshape(P, T, H)


def head_forward(x, top, d: dict, lower: Optional[str] = None):
    h = _rms(x, top["final_norm"], d["eps"])
    w = top["embed"].T if d["tied"] else top["lm_head"]
    return _matmul(h, w, lower)


def forward(layers, top: dict, d: dict, tokens, rows=None,
            lower: Optional[str] = None):
    """Logits [P, rows, vocab] of P sequences of one length (`tokens`
    [P, T]) at the positions `rows` (all if None), in float32 at the highest
    matmul precision. `layers` is any iterable of layer dicts, taken one at
    a time: a layer's bfloat16 weights are widened where they are used, so
    the model's float32 weights never stand at once."""
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(F32)[jnp.asarray(tokens)]
        for layer in layers:
            x = layer_forward(x, layer, d, lower)
        if rows is not None:
            x = x[:, jnp.asarray(rows)]
        return head_forward(x, top, d, lower)
