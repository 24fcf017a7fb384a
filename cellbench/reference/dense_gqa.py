"""Plain float32 forward pass of the dense grouped-query block that
Mistral-7B and Qwen2.5-7B share, and the seeded weights it runs on.

The block, as the two model cards and the Hugging Face `modeling_mistral` /
`modeling_qwen2` files describe it: pre-norm RMSNorm (float32, weight times
the normalised value), q/k/v projections (Qwen2: with bias), rotary embedding
in the rotate-half convention (the head split into two halves, not
interleaved pairs), grouped heads (query head h reads key/value head
h // (n_q / n_kv)), causal softmax attention with an optional sliding window
(token i sees (i - window, i]), output projection, residual; SwiGLU MLP
(silu(gate) * up, then down), residual; final RMSNorm and an untied output
head. No kernel, no cache, no batching: one sequence, all positions at once.

Departures from the published description, each forced by the benchmark:

* weights are random, from a seed: the same draw the program's
  `models.llama.init_params` makes (threefry keys split 4 + 10 * layers
  ways and consumed in order; normal / sqrt(fan_in), cast to bfloat16), then
  int8 per-output-channel symmetric quantisation with a bfloat16 scale, as
  `ops.linear.quantize_int8` does it. That algorithm is copied here; the
  reference makes its own weights from the seed and takes nothing the
  program made. The forward pass multiplies by the dequantised weights
  (int8 value times scale) in float32.
* Qwen2's q/k/v biases are zeros in a random initialisation (the program's
  choice); the bias path is exercised by the tiny-size test with values.
* the context the cells serve (4096) never exceeds Mistral's window, so the
  window masks nothing there; the mask is implemented and tested at a tiny
  size.

`lower` selects the control: the same pass in the nearest precision below
the one the configuration states ("int4_weights": weights re-quantised to 15
levels a channel; "int8_activations": every matmul's input rounded to int8
per token, the W8A8 step a v5e's int8 peak tempts).
"""

from __future__ import annotations

import functools
import math
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(hf: dict) -> dict:
    """The sizes the pass needs, from a Hugging Face config.json dict."""
    heads = hf["num_attention_heads"]
    qwen2 = str(hf.get("model_type", "")).startswith("qwen2")
    window = hf.get("sliding_window")
    if not hf.get("use_sliding_window", True):
        window = None
    return {
        "hidden": hf["hidden_size"],
        "inter": hf["intermediate_size"],
        "layers": hf["num_hidden_layers"],
        "heads": heads,
        "kv_heads": hf.get("num_key_value_heads", heads),
        "head_dim": hf.get("head_dim", hf["hidden_size"] // heads),
        "vocab": hf["vocab_size"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "bias": qwen2,
        "window": window,
        "tied": bool(hf.get("tie_word_embeddings", False)),
    }


# ------------------------------------------------------------ seeded weights


@jax.jit
def _quantise(w_bf16):
    # under one jit XLA fuses the divide, and about one value in 100,000,
    # sitting on .5, rounds to the neighbouring level; op by op, as the
    # program does it, is four times slower and no nearer to what a TPU's
    # own divide gives
    wf = w_bf16.astype(F32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dense(key, shape, fan_in):
    w = jax.random.normal(key, shape, dtype=F32) / jnp.sqrt(F32(fan_in))
    q, s = _quantise(w.astype(jnp.bfloat16))
    return {"q": q, "s": s}


def seeded_layers(d: dict, seed: int) -> Iterator[dict]:
    """Layer after layer of int8 weights, then a last dict with the
    embedding, the final norm and the head. Memory at any time: one layer."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4 + 10 * d["layers"]))
    H, Q, KV, I = (
        d["hidden"], d["heads"] * d["head_dim"],
        d["kv_heads"] * d["head_dim"], d["inter"],
    )
    for _ in range(d["layers"]):
        layer = {
            "attn_norm": jnp.ones((H,), F32),
            "wq": _dense(next(keys), (H, Q), H),
            "wk": _dense(next(keys), (H, KV), H),
            "wv": _dense(next(keys), (H, KV), H),
            "wo": _dense(next(keys), (Q, H), Q),
            "mlp_norm": jnp.ones((H,), F32),
        }
        if d["bias"]:
            layer.update(
                bq=jnp.zeros((Q,), F32), bk=jnp.zeros((KV,), F32),
                bv=jnp.zeros((KV,), F32),
            )
        layer.update(
            wg=_dense(next(keys), (H, I), H),
            wu=_dense(next(keys), (H, I), H),
            wd=_dense(next(keys), (I, H), I),
        )
        yield layer
    embed = (
        jax.random.normal(next(keys), (d["vocab"], H), F32) * 0.02
    ).astype(jnp.bfloat16)
    top = {"embed": embed, "final_norm": jnp.ones((H,), F32)}
    if not d["tied"]:
        top["lm_head"] = _dense(next(keys), (H, d["vocab"]), H)
    yield top


# ---------------------------------------------------------------- the pass


def _weight(w, lower: Optional[str]):
    """Dequantised float32 weight; the int4 control re-quantises it."""
    if isinstance(w, dict):
        w = w["q"].astype(F32) * w["s"].astype(F32)
    else:
        w = w.astype(F32)
    if lower == "int4_weights":
        amax = jnp.max(jnp.abs(w), axis=0)
        s4 = jnp.where(amax > 0, amax / 7.0, 1.0)
        w = jnp.clip(jnp.round(w / s4), -7, 7) * s4
    return w


def _matmul(x, w, lower: Optional[str]):
    if lower == "int8_activations":
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        x = jnp.clip(jnp.round(x / s), -127, 127) * s
    return jnp.matmul(x, _weight(w, lower))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    # x [T, heads, D]; rotate-half convention
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = positions.astype(F32)[:, None] * inv  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(x, layer, d: dict, lower: Optional[str] = None):
    """One block on one sequence. x [T, hidden] float32."""
    T = x.shape[0]
    Hq, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(T)
    h = _rms(x, layer["attn_norm"], d["eps"])
    q = _matmul(h, layer["wq"], lower)
    k = _matmul(h, layer["wk"], lower)
    v = _matmul(h, layer["wv"], lower)
    if "bq" in layer:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    q = _rope(q.reshape(T, Hq, D), pos, d["theta"])
    k = _rope(k.reshape(T, Hkv, D), pos, d["theta"])
    v = v.reshape(T, Hkv, D)
    group = Hq // Hkv
    k = jnp.repeat(k, group, axis=1)  # query head h reads kv head h // group
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(D)
    i, j = pos[:, None], pos[None, :]
    mask = j <= i
    if d["window"] is not None:
        mask = mask & (j > i - d["window"])
    scores = jnp.where(mask[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _matmul(attn.reshape(T, Hq * D), layer["wo"], lower)
    h = _rms(x, layer["mlp_norm"], d["eps"])
    act = jax.nn.silu(_matmul(h, layer["wg"], lower)) * _matmul(
        h, layer["wu"], lower
    )
    return x + _matmul(act, layer["wd"], lower)


def head_forward(x, top, d: dict, lower: Optional[str] = None):
    """Final norm and output head: logits [T, vocab]."""
    h = _rms(x, top["final_norm"], d["eps"])
    w = top["embed"].T if d["tied"] else top["lm_head"]
    return _matmul(h, w, lower)


class _Static(dict):
    """A dict of sizes that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_batch(x, layer, d, lower):
    return jax.vmap(lambda seq: layer_forward(seq, layer, d, lower))(x)


def forward(layers, top: dict, d: dict, tokens, rows=None,
            lower: Optional[str] = None):
    """Logits [P, rows, vocab] of P sequences of one length (`tokens`
    [P, T]) at the positions `rows` (all if None), in float32 at the highest
    matmul precision. `layers` is any iterable of layer dicts."""
    d = _Static(d)
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(F32)[jnp.asarray(tokens)]
        for layer in layers:
            x = _layer_batch(x, layer, d, lower)
        if rows is not None:
            x = x[:, jnp.asarray(rows)]
        return head_forward(x, top, d, lower)
