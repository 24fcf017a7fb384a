"""Plain float32 forward pass of the Mamba-2, latent-expert block of NVIDIA's
Nemotron-H family (`model_type: nemotron_h`: Nemotron 3 Super 120B-A12B),
and the seeded weights it runs on.

The block, as the model's `config.json` and Hugging Face's `NemotronH*`
classes describe it. Every layer is one mixer: `h = h + mixer_i(norm_i(h))`,
RMSNorm (float32, weight times the normalised value, `eps =
layer_norm_epsilon`), the mixer by `hybrid_override_pattern[i]`; a final
RMSNorm and an untied head; no positional embedding anywhere.

* `M`, Mamba-2: `(z, xBC, dt) = split(W_in u)` at `d_inner`, `d_inner + 2 *
  n_groups * ssm_state_size`, `mamba_num_heads`; a causal depthwise
  convolution over the last `conv_kernel` rows of `xBC` plus its bias, then
  silu; `(x, B, C) = split(xBC)`, `x` as `[heads, head_dim]`, `B` and `C` as
  `[n_groups, state]`, head `j` reads group `j // (heads / n_groups)`; `dt =
  softplus(dt + dt_bias)`; `S_j <- exp(-exp(A_log_j) dt_j) S_j + dt_j x_j
  (outer) B_g`; `y_j = S_j C_g + D_j x_j`; `y = group_rms(y * silu(z)) *
  w_norm` over `n_groups` groups of `d_inner / n_groups`; `out = W_out y`.
  The recurrence is a loop over positions, one token at a time from a zero
  state (`lax.scan`, so that the loop compiles once); nothing is chunked,
  cached or kept between calls.
* `*`, attention: `num_attention_heads` query heads over
  `num_key_value_heads` key-value heads of `head_dim`, no bias, no rotary
  embedding (the public attention applies none), causal softmax at
  `1/sqrt(head_dim)`. Computed per head over the whole sequence.
* `E`, experts: `s = sigmoid(u W_gate)` over the router's
  `n_routed_experts_published` experts; the `num_experts_per_tok` with the
  largest `s + e_score_correction_bias`; weights `routed_scaling_factor *
  s_sel / (sum s_sel + 1e-20)`; `v = W_fc1 u`; `y = W_fc2 (sum over the
  chosen experts that are HELD of w_e W_down_e relu(W_up_e v)^2) + W_d
  relu(W_u u)^2`. The held experts are `[first_held_expert,
  first_held_expert + n_routed_experts)`; what a chosen expert outside the
  range would have added is left out, as on the chip that holds this share.
  Computed as a loop over the held experts, each on the tokens routed to it
  and on no other.

Departures from the public implementation, each forced by the benchmark:

* weights are random, from a seed: the same draw the program's
  `models.ssm2_moe.init_params` makes (threefry keys split 4 + 12 * layers
  ways and consumed in order; matrices normal / sqrt(fan_in) cast to
  bfloat16, the convolution's taps by their `conv_kernel` inputs, its bias
  0.1 x normal; `A_log = log(A)`, `A` uniform in [1, 16], `D = 1`, `dt_bias
  = softplus^-1(dt)`, `dt` log-uniform in [0.001, 0.1], float32, as
  Mamba-2's own initialisation; `e_score_correction_bias` 0.001 x normal,
  float32; the held experts from the layer's keys folded with
  `first_held_expert`), copied here: the reference makes its own weights
  from the seed and takes nothing the program made. The pass multiplies by
  the bfloat16 weights widened to float32.
* the state is held `[heads, head_dim, state]` and the convolution's taps
  `[conv_kernel, channels]`, the last tap on the newest input: storage,
  the same arithmetic.
* the published code keeps every activation in bfloat16 and the recurrence
  in float32; here everything is float32.
* the expert loop pads each expert's tokens to the next power of two with
  zero rows (fewer shapes to compile); the rows are dropped again.

`lower` selects the control: the same pass in the nearest precision below
the one the configuration states ("int8_weights": every weight matrix
re-quantised to int8 per output channel, symmetric; "bf16_state": the state
and the recurrence's arithmetic in bfloat16, where the configuration states
a float32 state).
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
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)
EXPERT_BIAS_SCALE = 0.001
ROUTE_EPS = 1e-20
KINDS = "M*E"


def dims(hf: dict) -> dict:
    """The sizes the pass needs, from a Hugging Face config.json dict."""
    pattern = hf["hybrid_override_pattern"]
    layers = hf["num_hidden_layers"]
    if len(pattern) != layers or set(pattern) - set(KINDS):
        raise ValueError("hybrid_override_pattern must name M, * or E for every layer")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("the reference knows one expert group")
    if (hf.get("num_nextn_predict_layers") or 0) > 0:
        raise ValueError("the reference knows no prediction head")
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    m_heads, m_dim = hf["mamba_num_heads"], hf["mamba_head_dim"]
    groups, state = hf.get("n_groups", 8), hf["ssm_state_size"]
    taps = hf.get("conv_kernel", 4)
    d_inner = m_heads * m_dim
    conv_dim = d_inner + 2 * groups * state
    held = hf["n_routed_experts"]
    n_moe = pattern.count("E")
    return {
        "hidden": hidden,
        "layers": layers,
        "pattern": pattern,
        "attn_layers": pattern.count("*"),
        "mamba_layers": pattern.count("M"),
        "expert_layers": n_moe,
        # the name under which `cellbench/readers/expert_layers.py` asks for
        # the layers in front of the expert layers: `layers - first_dense`
        # is their number
        "first_dense": layers - n_moe,
        "heads": heads,
        "kv_heads": hf.get("num_key_value_heads", heads),
        "head_dim": hf.get("head_dim") or hidden // heads,
        "ssm_heads": m_heads,
        "ssm_head_dim": m_dim,
        "d_state": state,
        "groups": groups,
        "d_inner": d_inner,
        "conv_dim": conv_dim,
        "taps": taps,
        "chunk": hf.get("chunk_size", 128),
        # a lane's tail: the last taps - 1 rows of xBC, flat
        "tail_width": (taps - 1) * conv_dim,
        "moe_inter": hf["moe_intermediate_size"],
        "latent": hf["moe_latent_size"],
        "shared_inter": hf.get("n_shared_experts", 1) * hf["moe_shared_expert_intermediate_size"],
        "experts": held,
        "router_experts": hf.get("n_routed_experts_published", held),
        "first_held": hf.get("first_held_expert", 0),
        "top_k": hf["num_experts_per_tok"],
        "route_scale": float(hf.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "vocab": hf["vocab_size"],
        "eps": float(hf.get("layer_norm_epsilon", 1e-5)),
        "tied": bool(hf.get("tie_word_embeddings", False)),
    }


# ------------------------------------------------------------ seeded weights

_LAYER_KEYS = {"M": 6, "*": 4, "E": 8}


def seeded_layers(d: dict, seed: int) -> Iterator[dict]:
    """Layer after layer of weights (matrices bfloat16, the recurrence's own
    constants float32), then a last dict with the embedding, the final norm
    and the head."""
    every = jax.random.split(jax.random.PRNGKey(seed), 4 + KEYS_PER_LAYER * d["layers"])
    used = 0

    def dense_from(key, shape, fan_in):
        w = jax.random.normal(key, shape, dtype=F32)
        return (w / jnp.sqrt(F32(fan_in))).astype(BF16)

    H, Di, Dc, Hm, K = d["hidden"], d["d_inner"], d["conv_dim"], d["ssm_heads"], d["taps"]
    q_dim, kv_dim = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    for kind in d["pattern"]:
        keys = iter(every[used: used + _LAYER_KEYS[kind]])
        used += _LAYER_KEYS[kind]
        dense = lambda shape, fan_in: dense_from(next(keys), shape, fan_in)
        layer = {"norm": jnp.ones((H,), F32)}
        if kind == "*":
            layer["wq"] = dense((H, q_dim), H)
            layer["wk"] = dense((H, kv_dim), H)
            layer["wv"] = dense((H, kv_dim), H)
            layer["wo"] = dense((q_dim, H), q_dim)
        elif kind == "M":
            layer["w_in"] = dense((H, Di + Dc + Hm), H)
            layer["conv_w"] = dense((K, Dc), K)
            layer["conv_b"] = (0.1 * jax.random.normal(next(keys), (Dc,), F32)).astype(BF16)
            dt = jnp.maximum(DT_FLOOR, jnp.exp(
                jax.random.uniform(next(keys), (Hm,), F32)
                * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)
            ))
            layer["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            layer["A_log"] = jnp.log(
                A_RANGE[0]
                + jax.random.uniform(next(keys), (Hm,), F32) * (A_RANGE[1] - A_RANGE[0])
            )
            layer["D"] = jnp.ones((Hm,), F32)
            layer["gate_norm"] = jnp.ones((Di,), F32)
            layer["w_out"] = dense((Di, H), Di)
        else:
            E, R, L, F, S = d["experts"], d["router_experts"], d["latent"], d["moe_inter"], d["shared_inter"]
            layer["router"] = dense((H, R), H)
            layer["router_bias"] = EXPERT_BIAS_SCALE * jax.random.normal(next(keys), (R,), F32)
            layer["w_fc1"] = dense((H, L), H)
            layer["w_fc2"] = dense((L, H), L)
            held = lambda: jax.random.fold_in(next(keys), d["first_held"])
            layer["wu"] = dense_from(held(), (E, L, F), L)
            layer["wd"] = dense_from(held(), (E, F, L), F)
            layer["shared_wu"] = dense((H, S), H)
            layer["shared_wd"] = dense((S, H), S)
        yield layer
    embed = (jax.random.normal(every[used], (d["vocab"], H), F32) * 0.02).astype(BF16)
    top = {"embed": embed, "final_norm": jnp.ones((H,), F32)}
    if not d["tied"]:
        top["lm_head"] = dense_from(every[used + 1], (H, d["vocab"]), H)
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


def _relu2(v):
    v = jax.nn.relu(v)
    return v * v


def attention(x, layer, d: dict, lower: Optional[str] = None):
    """One sequence, all positions at once, per head. x [T, hidden]."""
    T = x.shape[0]
    Hq, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
    h = _rms(x, layer["norm"], d["eps"])
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


def update_inputs(x, layer, d: dict, lower: Optional[str] = None):
    """Everything of a Mamba-2 mixer in front of the recurrence, for one
    sequence x [T, hidden]: the gate z [T, d_inner], the convolution's input
    xBC [T, conv_dim], xs [T, heads, head_dim], B and C [T, groups, state]
    (behind the convolution and silu), dt [T, heads] behind its softplus."""
    T = x.shape[0]
    Di, Dc, K = d["d_inner"], d["conv_dim"], d["taps"]
    G, N = d["groups"], d["d_state"]
    proj = _matmul(_rms(x, layer["norm"], d["eps"]), layer["w_in"], lower)
    z, xbc, dt = proj[:, :Di], proj[:, Di: Di + Dc], proj[:, Di + Dc:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Dc), F32), xbc], axis=0)
    taps = layer["conv_w"].astype(F32)
    conv = jax.nn.silu(layer["conv_b"].astype(F32) + sum(
        taps[k] * padded[k: k + T] for k in range(K)
    ))
    xs = conv[:, :Di].reshape(T, d["ssm_heads"], d["ssm_head_dim"])
    b = conv[:, Di: Di + G * N].reshape(T, G, N)
    c = conv[:, Di + G * N:].reshape(T, G, N)
    return z, xbc, xs, b, c, jax.nn.softplus(dt + layer["dt_bias"])


def recurrence(xs, dt, b, c, a_log, lower: Optional[str] = None):
    """The loop over positions from a zero state. Returns (y [T, heads,
    head_dim], the state after every token [T, heads, head_dim, state])."""
    dtype = BF16 if lower == "bf16_state" else F32
    heads, groups = xs.shape[1], b.shape[1]
    a_neg = -jnp.exp(a_log).astype(dtype)
    by_head = lambda v: jnp.repeat(v, heads // groups, axis=0)  # [groups, N] -> [heads, N]

    def token(s, inp):
        x_t, d_t, b_t, c_t = (v.astype(dtype) for v in inp)
        s = (
            jnp.exp(d_t * a_neg)[:, None, None] * s
            + (d_t[:, None] * x_t)[:, :, None] * by_head(b_t)[:, None, :]
        )
        return s, (jnp.sum(s * by_head(c_t)[:, None, :], axis=-1), s)

    s0 = jnp.zeros(xs.shape[1:] + (b.shape[-1],), dtype)
    _, (y, states) = jax.lax.scan(token, s0, (xs, dt, b, c))
    return y.astype(F32), states.astype(F32)


def mamba(x, layer, d: dict, lower: Optional[str] = None):
    """One sequence through one Mamba-2 mixer and its residual."""
    T = x.shape[0]
    z, _, xs, b, c, dt = update_inputs(x, layer, d, lower)
    y, _ = recurrence(xs, dt, b, c, layer["A_log"], lower)
    y = (y + layer["D"][:, None] * xs).reshape(T, d["d_inner"]) * jax.nn.silu(z)
    y = y.reshape(T, d["groups"], -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + d["eps"])
    y = y.reshape(T, d["d_inner"]) * layer["gate_norm"]
    return x + _matmul(y, layer["w_out"], lower)


def route(h, layer, d: dict, lower: Optional[str] = None):
    """Expert ids [N, k] (of the router's experts) and weights [N, k] of
    tokens h [N, hidden]."""
    s = jax.nn.sigmoid(_matmul(h, layer["router"], lower))
    _, idx = jax.lax.top_k(s + layer["router_bias"], d["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if d["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return idx, w * d["route_scale"]


@functools.partial(jax.jit, static_argnums=(3,))
def _expert(ve, wu, wd, lower):
    return _matmul(_relu2(_matmul(ve, wu, lower)), wd, lower)


def held_experts(h, layer, d: dict, lower: Optional[str] = None):
    """The held routed experts' part of the sum, in the latent width, as a
    loop over the held experts, each on the tokens routed to it. h [N,
    hidden] -> [N, latent]."""
    idx, w = route(h, layer, d, lower)
    v = _matmul(h, layer["w_fc1"], lower)
    idx_np, w_np = np.asarray(idx), np.asarray(w, np.float32)
    y = np.zeros(v.shape, np.float32)
    for e in range(d["experts"]):
        tok, slot = np.nonzero(idx_np == d["first_held"] + e)
        if tok.size == 0:
            continue
        bucket = 1 << (int(tok.size) - 1).bit_length()
        rows = np.zeros(bucket, np.int64)
        rows[: tok.size] = tok
        ve = jnp.where(
            (jnp.arange(bucket) < tok.size)[:, None], v[jnp.asarray(rows)], 0.0
        )
        ye = _expert(ve, layer["wu"][e], layer["wd"][e], lower)
        y[tok] += np.asarray(ye[: tok.size]) * w_np[tok, slot][:, None]
    return jnp.asarray(y)


class _Static(dict):
    """A dict of sizes that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mixer(x, layer, d, lower):
    mixer = attention if "wq" in layer else mamba
    return jax.vmap(lambda seq: mixer(seq, layer, d, lower))(x)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _experts_out(x, routed, layer, d, lower):
    """The way back from the latent width, the shared expert, the residual."""
    h = _rms(x, layer["norm"], d["eps"])
    shared = _matmul(_relu2(_matmul(h, layer["shared_wu"], lower)), layer["shared_wd"], lower)
    return x + _matmul(routed, layer["w_fc2"], lower) + shared


def layer_forward(x, layer, d: dict, lower: Optional[str] = None):
    """One block on sequences x [P, T, hidden] of one length."""
    d = _Static(d)
    if "router" not in layer:
        return _mixer(x, layer, d, lower)
    P, T, H = x.shape
    h = _rms(x, layer["norm"], d["eps"]).reshape(P * T, H)
    routed = held_experts(h, layer, d, lower).reshape(P, T, -1)
    out = {k: layer[k] for k in ("norm", "w_fc2", "shared_wu", "shared_wd")}
    return _experts_out(x, routed, out, d, lower)


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
