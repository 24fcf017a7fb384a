"""What one decode step of the short-convolution, sparse-expert model needs:
operations and bytes, from shapes alone.

Counted is what the algorithm needs at the published bytes, not what a
program happens to move or how it lays its cache out:

* every weight of the convolution mixers, the attention mixers, the dense
  feed-forwards, the routers and the output head read once (the tied
  embedding table is read once as the head; the token's own row is a gather
  of one row a lane);
* of the routed experts, those that at least one live lane chose. Under
  uniform routing a token's k experts miss a given one of E with probability
  1 - k/E, so `lanes` tokens touch E * (1 - (1 - k/E)**lanes) of a layer's
  experts on average: 31.4 of 32 at 30 lanes, 21 at 8, 13 at 4. An upper
  estimate: a router that is not uniform touches fewer. The counter
  `dyn_llm_moe_experts_touched` says what the steps of a window did touch,
  and `experts_bytes` is for that reading;
* each live lane's tail (`conv_L_cache - 1` gated inputs of `hidden` values
  a convolution layer, in the model's dtype: 8 KB a layer and lane) read and
  written once: the tail shifts by one input every step, so unlike a Mamba
  state it must be stored after every step, not once a dispatch;
* each live lane's cached keys and values read once and the new token's
  written, for the attention layers only (`kv_bytes`: 4 layers x 2 planes x 8
  heads x 64 x 2 bytes = 8,192 bytes a token at the cut's sizes);
* the multiply-adds of the live lanes only: the matrix products (a token's
  k experts, not every expert), attention over the context, the
  convolution's taps and its two gates.

Activations between programs, padding lanes, the sort and the gathers around
the grouped products and the zeros a paired-head query carries are not needed
by the algorithm and not counted, so a share of the roofline built on the
counter's experts (`routed_ffn_roofline`) cannot pass 100%; one built on
`step_counts` (`decode_step_roofline`) overstates by what a router's skew
saves, which at the cell's lanes is under a percent (32 of 32 experts are
touched either way).
"""

from __future__ import annotations


def conv_layers(d: dict) -> int:
    return d["conv_layers"]


# the name under which `cellbench/readers/ssm_layers.py` (`slots_live`) asks a
# configuration's counts for its recurrent layers
mamba_layers = conv_layers


def expert_layers(d: dict) -> int:
    return max(0, d["layers"] - d["first_dense"])


def conv_mixer_params(d: dict) -> int:
    """One convolution mixer: in and out projections and the taps."""
    h = d["hidden"]
    return h * 3 * h + h * h + d["taps"] * h


def attention_mixer_params(d: dict) -> int:
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    return d["hidden"] * (q + 2 * kv) + q * d["hidden"] + 2 * d["head_dim"]


def expert_params(d: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * d["hidden"] * d["moe_inter"]


def param_count(d: dict) -> int:
    """Every parameter of the model `d` describes (the embedding once where
    the head is tied to it)."""
    n_moe = expert_layers(d)
    h = d["hidden"]
    return (
        conv_layers(d) * conv_mixer_params(d)
        + d["attn_layers"] * attention_mixer_params(d)
        + (d["layers"] - n_moe) * 3 * h * d["inter"]
        + n_moe * (d["experts"] * expert_params(d) + h * d["experts"] + d["experts"])
        + d["layers"] * 2 * h
        + d["vocab"] * h * (1 if d["tied"] else 2) + h
    )


def expected_experts_touched(d: dict, lanes: float) -> float:
    """Distinct experts of one layer that `lanes` tokens choose, uniform."""
    e, k = d["experts"], d["top_k"]
    return e * (1.0 - (1.0 - k / e) ** max(0.0, lanes))


def experts_bytes(d: dict, experts_touched: float, weight_bytes: float = 2.0) -> float:
    """Bytes of `experts_touched` routed experts' weights (a counter's
    reading, or `expected_experts_touched`)."""
    return experts_touched * expert_params(d) * weight_bytes


def tail_bytes_per_lane(d: dict, tail_bytes: float = 2.0) -> float:
    """The slot a sequence keeps, whatever its length: every convolution
    layer's tail."""
    return conv_layers(d) * d["tail_width"] * tail_bytes


def tail_step_bytes(d: dict, lanes: float, tail_bytes: float = 2.0) -> float:
    """Bytes a decode step must move for the tails alone: read and written."""
    return 2.0 * lanes * tail_bytes_per_lane(d, tail_bytes)


def mixers_bytes(d: dict, weight_bytes: float = 2.0) -> float:
    """Bytes of both kinds of mixer's weights, every layer's."""
    return weight_bytes * (
        conv_layers(d) * conv_mixer_params(d)
        + d["attn_layers"] * attention_mixer_params(d)
    )


def kv_values_per_token(d: dict) -> int:
    return 2 * d["attn_layers"] * d["kv_heads"] * d["head_dim"]


def step_counts(d: dict, lanes: float, context: float, *,
                weight_bytes: float = 2.0, kv_bytes: float = 2.0,
                tail_bytes: float = 2.0) -> dict:
    """Operations (multiply and add counted separately) and HBM bytes of one
    decode step with `lanes` live lanes whose mean context is `context`
    tokens. `d` as `reference.conv_moe.dims` gives it."""
    n_moe = expert_layers(d)
    n_dense = d["layers"] - n_moe
    head = d["hidden"] * d["vocab"]
    always = (
        conv_layers(d) * conv_mixer_params(d)
        + d["attn_layers"] * attention_mixer_params(d)
        + n_dense * 3 * d["hidden"] * d["inter"]
        + n_moe * d["hidden"] * d["experts"]
        + head
    )
    touched = n_moe * expected_experts_touched(d, lanes)
    per_token = always + n_moe * d["top_k"] * expert_params(d)
    weights = always * weight_bytes + experts_bytes(d, touched, weight_bytes)
    tails = tail_step_bytes(d, lanes, tail_bytes)
    kv_read = lanes * context * kv_values_per_token(d) * kv_bytes
    kv_write = lanes * kv_values_per_token(d) * kv_bytes
    embed = lanes * d["hidden"] * 2
    attn_ops = 4 * lanes * d["attn_layers"] * d["heads"] * d["head_dim"] * context
    # the gated product, the taps' products and sums, the output gate
    conv_ops = lanes * conv_layers(d) * d["hidden"] * (2 * d["taps"] + 1)
    return {
        "ops": 2 * lanes * per_token + attn_ops + conv_ops,
        "bytes": weights + tails + kv_read + kv_write + embed,
        "weight_bytes": weights,
        "mixer_bytes": mixers_bytes(d, weight_bytes),
        "tail_bytes": tails,
        "kv_bytes": kv_read + kv_write,
        "expert_bytes": experts_bytes(d, touched, weight_bytes),
        "experts_touched": touched,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    by_ops = counts["ops"] / peaks["bf16_flops_per_s"]
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops > by_bytes else (by_bytes, "bytes")
