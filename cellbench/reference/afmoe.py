"""Plain float32 forward pass of the window-and-full attention, sparse-expert
block of Arcee AI's Trinity (`model_type: afmoe`), and the seeded weights it
runs on.

The block, as the model's `config.json` and the family's public
implementation (Hugging Face `modeling_afmoe.py`) describe it. Every norm is
an RMSNorm (float32, weight times the normalised value, `eps =
rms_norm_eps`); no projection has a bias; the head is untied.

* Model: `x0 = embed[token] * sqrt(hidden)` where `mup_enabled`; the layers;
  a final norm; the head.
* Attention, every layer: `a = norm_in(x)`; `q = W_q a`, `k = W_k a`, `v = W_v
  a`, `g = W_g a` (as wide as `q`); an RMSNorm with its own weight over each
  head of `q` and of `k`. `layer_types[i] == "sliding_attention"`: the rotary
  embedding over the whole head in the half-split form (`x1 cos - x2 sin | x2
  cos + x1 sin`, the halves `x1 = x[:D/2]`, `x2 = x[D/2:]`) at `rope_theta`,
  and query `i` sees key `j` iff `j <= i` and `i - j < sliding_window`.
  `"full_attention"`: no rotary embedding, `j <= i`. Softmax at
  `1/sqrt(head)`, `heads / kv_heads` query heads to a key-value head; `h = x +
  norm_post_attn(W_o (o * sigmoid(g)))`. Computed by head over the whole
  sequence with the mask written out, in blocks of queries (each block
  against the keys up to its last query, and in a window layer from the
  first key its first query sees) so that a sequence of some thousands of
  tokens fits the host; the last block of the model at the wanted positions
  alone; nothing is kept between calls: no cache, page, window buffer or
  batching.
* Feed-forward, `b = norm_pre_mlp(h)`. Layers below `num_dense_layers`:
  `W_down(silu(W_gate b) * W_up b)` at `intermediate_size`. The others: `s =
  sigmoid(b W_r)` over `num_experts_published` experts; the
  `num_experts_per_tok` with the largest `s + expert_bias`; weights
  `route_scale * s_sel / (sum s_sel + 1e-20)` where `route_norm`, from `s`
  and not from `s + expert_bias`; `f = shared(b) + sum_e w_e SwiGLU_e(b)` at
  `moe_intermediate_size`. `y = h + norm_post_mlp(f)`.

Departures from the published description, each forced by the benchmark:

* the configuration holds a share of the routed experts: `num_experts` of
  `num_experts_published`, from `first_held_expert`. The router keeps its
  published width and its choice; the sum runs, as a loop over the held
  experts, each on the tokens routed to it and on no other, and what the
  absent experts would have added is left out; the shared expert is whole.
* weights are random, from a seed: the same draw the program's
  `models.afmoe.init_params` makes (threefry keys split 4 + 14 * layers ways,
  a layer's 14 consumed in order; matrices normal / sqrt(fan_in) cast to
  bfloat16; the held experts' stacks from their keys folded with
  `first_held_expert`; `expert_bias` 0.003 x normal in float32, where the
  published buffer starts at zero; norms ones, so "depth-scaled", which names
  how the published gains were initialised, is not drawn), copied here: the
  reference makes its own weights from the seed and takes nothing the program
  made. The pass multiplies by the bfloat16 weights widened to float32.
* the published code keeps activations in bfloat16; here everything is
  float32.
* the expert loop pads each expert's tokens to the next power of two with
  zero rows (fewer shapes to compile); the rows are dropped again.

`lower` selects the control: the same pass in the nearest precision below
the one the configuration states ("int8_weights": every weight matrix
re-quantised to int8 per output channel, symmetric; "int8_kv": every key and
value row, which is what the cache keeps, rounded to int8 by its head's
largest magnitude in the row, where the configuration states bfloat16).
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
KEYS_PER_LAYER = 14
EXPERT_BIAS_SCALE = 0.003
ROUTE_EPS = 1e-20
QUERY_BLOCK = 512
KINDS = ("sliding_attention", "full_attention")


def dims(hf: dict) -> dict:
    """The sizes the pass needs, from a Hugging Face config.json dict."""
    if hf.get("rope_scaling") is not None:
        raise ValueError("the reference knows no rope scaling")
    if hf.get("n_group", 1) != 1 or hf.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("the reference knows one expert group and sigmoid scores")
    kinds = tuple(hf["layer_types"])
    layers = hf["num_hidden_layers"]
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types must name one of {KINDS} for every layer")
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    held = hf["num_experts"]
    return {
        "hidden": hidden,
        "inter": hf["intermediate_size"],
        "moe_inter": hf["moe_intermediate_size"],
        "layers": layers,
        "kinds": kinds,
        "window_layers": sum(k == "sliding_attention" for k in kinds),
        "full_layers": sum(k == "full_attention" for k in kinds),
        "first_dense": hf.get("num_dense_layers", 0),
        "heads": heads,
        "kv_heads": hf.get("num_key_value_heads", heads),
        "head_dim": hf.get("head_dim") or hidden // heads,
        "window": int(hf.get("sliding_window") or 0),
        # the served context: no lane's is longer
        "max_context": int(hf.get("max_position_embeddings") or 0),
        "experts": held,
        "router_experts": hf.get("num_experts_published", held),
        "first_held": hf.get("first_held_expert", 0),
        "top_k": hf["num_experts_per_tok"],
        "route_scale": float(hf.get("route_scale", 1.0)),
        "route_norm": bool(hf.get("route_norm", True)),
        "mup": bool(hf.get("mup_enabled", False)),
        "vocab": hf["vocab_size"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "tied": bool(hf.get("tie_word_embeddings", False)),
    }


def windowed(d: dict, i: int) -> bool:
    return d["kinds"][i] == "sliding_attention"


# ------------------------------------------------------------ seeded weights


def seeded_layers(d: dict, seed: int) -> Iterator[dict]:
    """Layer after layer of weights (matrices bfloat16), then a last dict
    with the embedding, the final norm and the head."""
    keys = jax.random.split(
        jax.random.PRNGKey(seed), 4 + KEYS_PER_LAYER * d["layers"]
    )

    def dense(key, shape, fan_in):
        w = jax.random.normal(key, shape, dtype=F32)
        return (w / jnp.sqrt(F32(fan_in))).astype(BF16)

    H, D = d["hidden"], d["head_dim"]
    q_dim, kv_dim = d["heads"] * D, d["kv_heads"] * D
    E, R, F = d["experts"], d["router_experts"], d["moe_inter"]
    for i in range(d["layers"]):
        k = iter(keys[KEYS_PER_LAYER * i: KEYS_PER_LAYER * (i + 1)])
        layer = {
            "window": d["window"] if windowed(d, i) else 0,
            "attn_norm": jnp.ones((H,), F32), "post_attn_norm": jnp.ones((H,), F32),
            "pre_mlp_norm": jnp.ones((H,), F32), "post_mlp_norm": jnp.ones((H,), F32),
            "wq": dense(next(k), (H, q_dim), H),
            "wk": dense(next(k), (H, kv_dim), H),
            "wv": dense(next(k), (H, kv_dim), H),
            "w_gate": dense(next(k), (H, q_dim), H),
            "wo": dense(next(k), (q_dim, H), q_dim),
            "q_norm": jnp.ones((D,), F32), "k_norm": jnp.ones((D,), F32),
        }
        if i < d["first_dense"]:
            I = d["inter"]
            layer["wg"] = dense(next(k), (H, I), H)
            layer["wu"] = dense(next(k), (H, I), H)
            layer["wd"] = dense(next(k), (I, H), I)
        else:
            layer["router"] = dense(next(k), (H, R), H)
            layer["router_bias"] = EXPERT_BIAS_SCALE * jax.random.normal(next(k), (R,), F32)
            layer["shared_wg"] = dense(next(k), (H, F), H)
            layer["shared_wu"] = dense(next(k), (H, F), H)
            layer["shared_wd"] = dense(next(k), (F, H), F)
            held = lambda: jax.random.fold_in(next(k), d["first_held"])
            layer["wg"] = dense(held(), (E, H, F), H)
            layer["wu"] = dense(held(), (E, H, F), H)
            layer["wd"] = dense(held(), (E, F, H), F)
        yield layer
    top_keys = keys[KEYS_PER_LAYER * d["layers"]:]
    embed = (jax.random.normal(top_keys[0], (d["vocab"], H), F32) * 0.02).astype(BF16)
    top = {"embed": embed, "final_norm": jnp.ones((H,), F32)}
    if not d["tied"]:
        top["lm_head"] = dense(top_keys[1], (H, d["vocab"]), H)
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


def _cached_row(x, lower: Optional[str]):
    """A key or value row as the cache would keep it one precision lower:
    int8 by the largest magnitude of the head's row."""
    if lower != "int8_kv":
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def attention(x, layer, window: int, d: dict, lower: Optional[str] = None, rows=None):
    """One sequence, per head, the mask written out; the queries taken
    `QUERY_BLOCK` at a time, each block against the keys up to its last query
    (and, in a window layer, from the first key its first query sees: the
    others are masked whatever they hold). x [T, hidden]; `window` 0: a full
    layer; `rows`: the positions whose output is wanted (all if None: the
    keys and values are every position's either way)."""
    T = x.shape[0]
    Hq, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(T)
    at = pos if rows is None else jnp.asarray(rows)
    a = _rms(x, layer["attn_norm"], d["eps"])
    q = _rms(_matmul(a[at], layer["wq"], lower).reshape(-1, Hq, D), layer["q_norm"], d["eps"])
    k = _rms(_matmul(a, layer["wk"], lower).reshape(T, Hkv, D), layer["k_norm"], d["eps"])
    v = _matmul(a, layer["wv"], lower).reshape(T, Hkv, D)
    g = _matmul(a[at], layer["w_gate"], lower)
    if window:
        q = _rope_halves(q, at, d["theta"])
        k = _rope_halves(k, pos, d["theta"])
    k = jnp.repeat(_cached_row(k, lower), Hq // Hkv, axis=1)
    v = jnp.repeat(_cached_row(v, lower), Hq // Hkv, axis=1)
    wanted = list(range(T)) if rows is None else [int(r) for r in rows]
    out = []
    for lo in range(0, len(wanted), QUERY_BLOCK):
        qpos = at[lo: lo + QUERY_BLOCK]
        first, last = min(wanted[lo: lo + QUERY_BLOCK]), max(wanted[lo: lo + QUERY_BLOCK])
        k_lo = max(0, first - window + 1) if window else 0
        kpos = pos[k_lo: last + 1]
        scores = jnp.einsum("thd,shd->hts", q[lo: lo + QUERY_BLOCK], k[k_lo: last + 1]) / math.sqrt(D)
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v[k_lo: last + 1]))
    o = jnp.concatenate(out, axis=0).reshape(len(wanted), Hq * D) * jax.nn.sigmoid(g)
    return x[at] + _rms(_matmul(o, layer["wo"], lower), layer["post_attn_norm"], d["eps"])


def _swiglu(h, wg, wu, wd, lower):
    act = jax.nn.silu(_matmul(h, wg, lower)) * _matmul(h, wu, lower)
    return _matmul(act, wd, lower)


def route(b, layer, d: dict, lower: Optional[str] = None):
    """Expert ids [N, k] (of the published router) and weights [N, k] of
    tokens b [N, hidden]."""
    s = jax.nn.sigmoid(_matmul(b, layer["router"], lower))
    _, idx = jax.lax.top_k(s + layer["router_bias"], d["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if d["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return idx, w * d["route_scale"]


@functools.partial(jax.jit, static_argnums=(4,))
def _expert(xe, wg, wu, wd, lower):
    return _swiglu(xe, wg, wu, wd, lower)


def held_experts(b, layer, d: dict, lower: Optional[str] = None):
    """The held experts' part of the routed sum, as a loop over them, each
    on the tokens routed to it. b [N, hidden] -> [N, hidden]."""
    idx, w = route(b, layer, d, lower)
    idx_np, w_np = np.asarray(idx), np.asarray(w, np.float32)
    y = np.zeros(b.shape, np.float32)
    for e in range(d["experts"]):
        tok, slot = np.nonzero(idx_np == d["first_held"] + e)
        if tok.size == 0:
            continue
        bucket = 1 << (int(tok.size) - 1).bit_length()
        rows = np.zeros(bucket, np.int64)
        rows[: tok.size] = tok
        xe = jnp.where(
            (jnp.arange(bucket) < tok.size)[:, None], b[jnp.asarray(rows)], 0.0
        )
        ye = _expert(xe, layer["wg"][e], layer["wu"][e], layer["wd"][e], lower)
        y[tok] += np.asarray(ye[: tok.size]) * w_np[tok, slot][:, None]
    return jnp.asarray(y)


class _Static(dict):
    """A dict of sizes that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _mixer(x, layer, window, d, lower, rows):
    return attention(x, layer, window, d, lower, rows)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_ffn(h, layer, d, lower):
    b = _rms(h, layer["pre_mlp_norm"], d["eps"])
    f = _swiglu(b, layer["wg"], layer["wu"], layer["wd"], lower)
    return h + _rms(f, layer["post_mlp_norm"], d["eps"])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _shared(b, layer, d, lower):
    return _swiglu(b, layer["shared_wg"], layer["shared_wu"], layer["shared_wd"], lower)


_ATTN_KEYS = (
    "attn_norm", "post_attn_norm", "wq", "wk", "wv", "w_gate", "wo", "q_norm",
    "k_norm",
)


def layer_forward(x, layer, d: dict, lower: Optional[str] = None, rows=None):
    """One block on one sequence x [T, hidden]; with `rows`, its output at
    those positions alone (the model's last block needs no other)."""
    d = _Static(d)
    rows = None if rows is None else tuple(int(r) for r in rows)
    h = _mixer(x, {k: layer[k] for k in _ATTN_KEYS}, int(layer["window"]), d, lower, rows)
    if "router" not in layer:
        ffn = {k: layer[k] for k in ("pre_mlp_norm", "post_mlp_norm", "wg", "wu", "wd")}
        return _dense_ffn(h, ffn, d, lower)
    b = _rms(h, layer["pre_mlp_norm"], d["eps"])
    shared = _shared(b, {k: layer[k] for k in ("shared_wg", "shared_wu", "shared_wd")}, d, lower)
    f = shared + held_experts(b, layer, d, lower)
    return h + _rms(f, layer["post_mlp_norm"], d["eps"])


def head_forward(x, top, d: dict, lower: Optional[str] = None):
    h = _rms(x, top["final_norm"], d["eps"])
    w = top["embed"].T if d["tied"] else top["lm_head"]
    return _matmul(h, w, lower)


def forward(layers, top: dict, d: dict, tokens, rows=None,
            lower: Optional[str] = None):
    """Logits [P, rows, vocab] of P sequences of one length (`tokens`
    [P, T]) at the positions `rows` (all if None), in float32 at the highest
    matmul precision, one sequence after another. `layers` is a list of layer
    dicts: a layer's bfloat16 weights are widened where they are used, so
    the model's float32 weights never stand at once."""
    layers = list(layers)
    out = []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = top["embed"].astype(F32)[jnp.asarray(seq)]
            if d["mup"]:
                x = x * math.sqrt(d["hidden"])
            for i, layer in enumerate(layers):
                # the last block at the wanted positions alone
                x = layer_forward(x, layer, d, lower, rows if i == len(layers) - 1 else None)
            out.append(head_forward(x, top, d, lower))
    return jnp.stack(out)
