"""Plain float32 forward pass of the hybrid state-space block of AI21's Jamba
family (Mamba-1 mixers with an attention mixer among them, a SwiGLU
feed-forward behind each), and the seeded weights it runs on.

The block, as the model's `config.json` and Hugging Face's `modeling_jamba`
describe it. Pre-norm RMSNorm (float32, weight times the normalised value,
eps 1e-6); `h = h + mixer_i(norm(h)); h = h + mlp(norm(h))`; `mlp(x) =
Wd(silu(Wg x) * (Wu x))` in every layer (`num_experts` 1). No positional
embedding anywhere. Tied embedding and head, final RMSNorm.

* Layer `i` attends where `i % attn_layer_period == attn_layer_offset`:
  `num_attention_heads` query heads over `num_key_value_heads` key-value
  heads of `hidden / heads`, no bias, no rope, causal softmax at
  `1/sqrt(head)`. Computed per head over the whole sequence.
* Elsewhere Mamba-1 at `d_inner = mamba_expand * hidden`: `(x, z) =
  split(W_in u)`; a causal depthwise convolution over the last `d_conv`
  inputs plus its bias, then silu; `(dt, B, C) = split(W_x x)` at `dt_rank`,
  `d_state`, `d_state`, each through its own RMSNorm (Jamba's
  `dt_layernorm`, `b_layernorm`, `c_layernorm`); `delta = softplus(W_dt dt +
  b_dt)`; `A = -exp(A_log)`; `s_t = exp(delta_t * A) * s_{t-1} + (delta_t *
  x_t) outer B_t`; `y_t = s_t C_t + D * x_t`; `out = W_out (y * silu(z))`.
  The recurrence is a loop over tokens, one token at a time from a zero
  state (`lax.scan`, so that the loop compiles once); nothing is chunked,
  cached or kept between calls.

Departures from `modeling_jamba.py`, each forced by the benchmark:

* weights are random, from a seed: the same draw the program's
  `models.hybrid_ssm.init_params` makes (threefry keys split 4 + 12 * layers
  ways and consumed in order; matrices normal / sqrt(fan_in) cast to
  bfloat16, the convolution's taps by their 4 inputs, its bias 0.1 x normal;
  `A_log = log(1..d_state)`, `D = 1` and `b_dt = softplus^-1(dt)`, `dt`
  log-uniform in [0.001, 0.1], in float32, as Mamba's own initialisation),
  copied here: the reference makes its own weights from the seed and takes
  nothing the program made. The pass multiplies by the bfloat16 weights
  widened to float32.
* `A_log` and the state are held `[d_state, d_inner]` (the published
  parameter is `[d_inner, d_state]`): a transposition of storage, the same
  arithmetic.
* the convolution's taps are `[d_conv, d_inner]`, the last tap on the newest
  input (PyTorch's `conv1d` weight `[d_inner, 1, d_conv]`, transposed).
* the published code keeps the hidden states in bfloat16 between layers and
  runs the recurrence in float32; here everything is float32.

`lower` selects the control: the same pass in the nearest precision below
the one the configuration states ("int8_weights": every weight matrix
re-quantised to int8 per output channel, symmetric; "bf16_state": the state
and the scan's arithmetic in bfloat16, what a faster recurrent step would be
tempted by).
"""

from __future__ import annotations

import functools
import math
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
KEYS_PER_LAYER = 12
DT_MIN, DT_MAX = 1e-3, 1e-1


def dims(hf: dict) -> dict:
    """The sizes the pass needs, from a Hugging Face config.json dict."""
    if (hf.get("num_experts") or 1) != 1:
        raise ValueError("the reference knows no routed layer")
    if hf.get("sliding_window") is not None:
        raise ValueError("the reference knows no sliding window")
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    dt_rank = hf.get("mamba_dt_rank", "auto")
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    layers = hf["num_hidden_layers"]
    return {
        "hidden": hidden,
        "inter": hf["intermediate_size"],
        "layers": layers,
        "attn_layers": sum(i % period == offset for i in range(layers)),
        "period": period,
        "offset": offset,
        "heads": heads,
        "kv_heads": hf.get("num_key_value_heads", heads),
        "head_dim": hf.get("head_dim") or hidden // heads,
        "d_inner": hf.get("mamba_expand", 2) * hidden,
        "d_state": hf.get("mamba_d_state", 16),
        "d_conv": hf.get("mamba_d_conv", 4),
        "dt_rank": math.ceil(hidden / 16) if dt_rank == "auto" else dt_rank,
        "vocab": hf["vocab_size"],
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "tied": bool(hf.get("tie_word_embeddings", True)),
    }


def attends(d: dict, i: int) -> bool:
    return i % d["period"] == d["offset"]


# ------------------------------------------------------------ seeded weights


def seeded_layers(d: dict, seed: int) -> Iterator[dict]:
    """Layer after layer of weights (matrices bfloat16, the recurrence's own
    constants float32), then a last dict with the embedding and the final
    norm (and the head, if untied)."""
    keys = iter(jax.random.split(
        jax.random.PRNGKey(seed), 4 + KEYS_PER_LAYER * d["layers"]
    ))

    def dense(shape, fan_in):
        w = jax.random.normal(next(keys), shape, dtype=F32)
        return (w / jnp.sqrt(F32(fan_in))).astype(BF16)

    H, Di, N, R, K = d["hidden"], d["d_inner"], d["d_state"], d["dt_rank"], d["d_conv"]
    q_dim, kv_dim = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    for i in range(d["layers"]):
        layer = {"mix_norm": jnp.ones((H,), F32)}
        if attends(d, i):
            layer["wq"] = dense((H, q_dim), H)
            layer["wk"] = dense((H, kv_dim), H)
            layer["wv"] = dense((H, kv_dim), H)
            layer["wo"] = dense((q_dim, H), q_dim)
        else:
            dt = jnp.exp(
                jax.random.uniform(next(keys), (Di,), F32)
                * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)
            )
            layer["w_in"] = dense((H, 2 * Di), H)
            layer["conv_w"] = dense((K, Di), K)
            layer["conv_b"] = (0.1 * jax.random.normal(next(keys), (Di,), F32)).astype(BF16)
            layer["w_x"] = dense((Di, R + 2 * N), Di)
            layer["dt_norm"] = jnp.ones((R,), F32)
            layer["b_norm"] = jnp.ones((N,), F32)
            layer["c_norm"] = jnp.ones((N,), F32)
            layer["w_dt"] = dense((R, Di), R)
            layer["b_dt"] = dt + jnp.log(-jnp.expm1(-dt))
            layer["A_log"] = jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=F32))[:, None], (N, Di)
            )
            layer["D"] = jnp.ones((Di,), F32)
            layer["w_out"] = dense((Di, H), Di)
        layer["mlp_norm"] = jnp.ones((H,), F32)
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


def attention(x, layer, d: dict, lower: Optional[str] = None):
    """One sequence, all positions at once, per head. x [T, hidden]."""
    T = x.shape[0]
    Hq, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
    h = _rms(x, layer["mix_norm"], d["eps"])
    q = _matmul(h, layer["wq"], lower).reshape(T, Hq, D)
    k = _matmul(h, layer["wk"], lower).reshape(T, Hkv, D)
    v = _matmul(h, layer["wv"], lower).reshape(T, Hkv, D)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    pos = jnp.arange(T)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(D)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return x + _matmul(out.reshape(T, Hq * D), layer["wo"], lower)


def scan_inputs(x, layer, d: dict, lower: Optional[str] = None):
    """Everything of a Mamba mixer in front of the recurrence, for one
    sequence x [T, hidden]: the stream xs [T, d_inner] (after convolution
    and silu), its gate z, delta [T, d_inner], B and C [T, d_state]."""
    T = x.shape[0]
    Di, N, R, K = d["d_inner"], d["d_state"], d["dt_rank"], d["d_conv"]
    h = _rms(x, layer["mix_norm"], d["eps"])
    xz = _matmul(h, layer["w_in"], lower)
    xs, z = xz[:, :Di], xz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), F32), xs], axis=0)
    taps = layer["conv_w"].astype(F32)
    conv = layer["conv_b"].astype(F32) + sum(
        taps[k] * padded[k: k + T] for k in range(K)
    )
    xs = jax.nn.silu(conv)
    proj = _matmul(xs, layer["w_x"], lower)
    dt = _rms(proj[:, :R], layer["dt_norm"], d["eps"])
    b = _rms(proj[:, R: R + N], layer["b_norm"], d["eps"])
    c = _rms(proj[:, R + N:], layer["c_norm"], d["eps"])
    delta = jax.nn.softplus(_matmul(dt, layer["w_dt"], lower) + layer["b_dt"])
    return xs, z, delta, b, c


def recurrence(xs, delta, b, c, a_log, lower: Optional[str] = None):
    """The loop over tokens from a zero state. Returns (y [T, d_inner], the
    state after every token [T, d_state, d_inner])."""
    dtype = BF16 if lower == "bf16_state" else F32
    a_neg = -jnp.exp(a_log).astype(dtype)

    def token(s, inp):
        x_t, d_t, b_t, c_t = (v.astype(dtype) for v in inp)
        s = jnp.exp(d_t[None, :] * a_neg) * s + (d_t * x_t)[None, :] * b_t[:, None]
        return s, (jnp.sum(s * c_t[:, None], axis=0), s)

    _, (y, states) = jax.lax.scan(
        token, jnp.zeros(a_neg.shape, dtype), (xs, delta, b, c)
    )
    return y.astype(F32), states.astype(F32)


def mamba(x, layer, d: dict, lower: Optional[str] = None):
    """One sequence through one Mamba mixer and its residual."""
    xs, z, delta, b, c = scan_inputs(x, layer, d, lower)
    y, _ = recurrence(xs, delta, b, c, layer["A_log"], lower)
    y = (y + layer["D"] * xs) * jax.nn.silu(z)
    return x + _matmul(y, layer["w_out"], lower)


class _Static(dict):
    """A dict of sizes that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, layer, d, lower):
    mixer = attention if "wq" in layer else mamba
    x = jax.vmap(lambda seq: mixer(seq, layer, d, lower))(x)
    h = _rms(x, layer["mlp_norm"], d["eps"])
    act = jax.nn.silu(_matmul(h, layer["wg"], lower)) * _matmul(h, layer["wu"], lower)
    return x + _matmul(act, layer["wd"], lower)


def layer_forward(x, layer, d: dict, lower: Optional[str] = None):
    """One block on sequences x [P, T, hidden] of one length."""
    return _layer(x, layer, _Static(d), lower)


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
