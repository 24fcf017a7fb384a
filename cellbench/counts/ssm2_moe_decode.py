"""What one decode step of the Mamba-2, latent-expert model needs: operations
and bytes, from shapes alone.

Counted is what the algorithm needs at the published bytes, not what a
program happens to move or how it lays its slots out:

* every weight of the Mamba-2 mixers, the attention mixers, the expert
  layers' routers, latent projections and shared experts, and the output
  head read once (the head is untied: the token's own row of the embedding is
  a gather of one row a lane);
* of the routed experts this chip HOLDS (`experts` of the router's
  `router_experts`), those that at least one live lane chose. Under uniform
  routing a token's k experts miss a given one with probability 1 - k /
  router_experts (490/512 at the published sizes), so `lanes` tokens touch
  `experts * (1 - (1 - k / router_experts)**lanes)` of a layer's held experts
  on average: 112 of 128 at 48 lanes, 121 at 64. An upper estimate: a router
  that is not uniform touches fewer. The counter `dyn_llm_moe_experts_touched`
  says what the steps of a window did touch, and `experts_bytes` is for that
  reading;
* each live lane's Mamba-2 state (`heads x head_dim x d_state` float32 a
  layer: 4 MiB) read once a step and written once a dispatch, `1 + 1 /
  horizon` passes a step (`cellbench/counts/hybrid_ssm_decode.py` says why: a
  program may recompute a layer's state from the dispatch's first instead of
  storing it after every step), and its convolution tail (`conv_kernel - 1`
  rows of `x, B, C` together, float32) read and written every step, since it
  shifts by one row a step. A lane that holds no sequence needs nothing,
  whatever a program reads for it;
* each live lane's cached keys and values read once and the new token's
  written, for the attention layers only (`kv_bytes`: 1 layer x 2 planes x 2
  heads x 128 x 2 bytes = 1,024 bytes a token at the cut's sizes);
* the multiply-adds of the live lanes only: the matrix products (of the
  routed experts a token's held ones, k x experts / router_experts of them on
  average, not every expert), attention over the context, the convolution's
  taps, and the recurrence's update (for each of `heads x head_dim x d_state`
  values a decay times the state, an input's product and its sum, and the
  product with C and its sum: 5 operations).

Activations between programs, padding lanes, the sort and the gathers around
the grouped products, and the state of idle lanes are not needed by the
algorithm and not counted, so a share of the roofline built on these counts
cannot pass 100%.
"""

from __future__ import annotations

# the engine's default decode horizon, which every cell's server runs
# (`facts.decode_horizon`); `step_counts` is called without one
HORIZON = 4


def mamba_layers(d: dict) -> int:
    return d["mamba_layers"]


def expert_layers(d: dict) -> int:
    return d["expert_layers"]


def mamba_mixer_params(d: dict) -> int:
    """One Mamba-2 mixer: projections, convolution and bias, the heads'
    constants and the gated norm."""
    h, di, dc, hm = d["hidden"], d["d_inner"], d["conv_dim"], d["ssm_heads"]
    return h * (di + dc + hm) + d["taps"] * dc + dc + 3 * hm + di + di * h


def attention_mixer_params(d: dict) -> int:
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    return d["hidden"] * (q + 2 * kv) + q * d["hidden"]


def expert_layer_params(d: dict) -> int:
    """One expert layer beside its routed experts: the router and its bias,
    the two latent projections, the shared expert."""
    h = d["hidden"]
    return (
        h * d["router_experts"] + d["router_experts"]
        + 2 * h * d["latent"] + 2 * h * d["shared_inter"]
    )


def expert_params(d: dict) -> int:
    """One routed expert: up and down, in the latent width."""
    return 2 * d["latent"] * d["moe_inter"]


def param_count(d: dict) -> int:
    """Every parameter of the model `d` describes, with the experts it
    holds (every layer's own norm, the final norm, embedding and head)."""
    return (
        mamba_layers(d) * mamba_mixer_params(d)
        + d["attn_layers"] * attention_mixer_params(d)
        + expert_layers(d) * (expert_layer_params(d) + d["experts"] * expert_params(d))
        + d["layers"] * d["hidden"]
        + d["vocab"] * d["hidden"] * (1 if d["tied"] else 2) + d["hidden"]
    )


def expected_experts_touched(d: dict, lanes: float) -> float:
    """Distinct held experts of one layer that `lanes` tokens choose, under a
    uniform router over all of its experts."""
    miss = 1.0 - d["top_k"] / d["router_experts"]
    return d["experts"] * (1.0 - miss ** max(0.0, lanes))


def experts_bytes(d: dict, experts_touched: float, weight_bytes: float = 2.0) -> float:
    """Bytes of `experts_touched` routed experts' weights (a counter's
    reading, or `expected_experts_touched`)."""
    return experts_touched * expert_params(d) * weight_bytes


def state_values(d: dict) -> int:
    """One layer's state of one lane."""
    return d["ssm_heads"] * d["ssm_head_dim"] * d["d_state"]


def state_bytes_per_lane(d: dict) -> int:
    """The slot a sequence keeps, whatever its length: float32 state and
    tail of every Mamba-2 layer."""
    return mamba_layers(d) * (state_values(d) + d["tail_width"]) * 4


def state_passes_a_step(horizon: int = HORIZON) -> float:
    """Times a live lane's state crosses the memory bus in one step of a
    dispatch of `horizon` steps: read in every step, written after the last."""
    return 1.0 + 1.0 / max(1, int(horizon))


def scan_state_step_bytes(d: dict, lanes: float, horizon: int = HORIZON) -> float:
    """Bytes a decode step must move for the state alone, without the
    convolution's tail: what the operations that `ssm2_step_ms` times read
    and write."""
    return state_passes_a_step(horizon) * lanes * mamba_layers(d) * state_values(d) * 4


def state_step_bytes(d: dict, lanes: float, horizon: int = HORIZON) -> float:
    """Bytes a decode step must move for the slots: the state, and the tail
    read and written."""
    tails = 2.0 * lanes * mamba_layers(d) * d["tail_width"] * 4
    return scan_state_step_bytes(d, lanes, horizon) + tails


def kv_values_per_token(d: dict) -> int:
    return 2 * d["attn_layers"] * d["kv_heads"] * d["head_dim"]


def step_counts(d: dict, lanes: float, context: float, *,
                weight_bytes: float = 2.0, kv_bytes: float = 2.0,
                horizon: int = HORIZON) -> dict:
    """Operations (multiply and add counted separately) and HBM bytes of one
    decode step with `lanes` live lanes whose mean context is `context`
    tokens. `d` as `reference.ssm2_moe.dims` gives it."""
    n_mamba, n_attn, n_moe = mamba_layers(d), d["attn_layers"], expert_layers(d)
    head = d["hidden"] * d["vocab"]
    always = (
        n_mamba * mamba_mixer_params(d) + n_attn * attention_mixer_params(d)
        + n_moe * expert_layer_params(d) + head
    )
    touched = n_moe * expected_experts_touched(d, lanes)
    held_a_token = d["top_k"] * d["experts"] / d["router_experts"]
    per_token = always + n_moe * held_a_token * expert_params(d)
    weights = always * weight_bytes + experts_bytes(d, touched, weight_bytes)
    state = state_step_bytes(d, lanes, horizon)
    kv_read = lanes * context * kv_values_per_token(d) * kv_bytes
    kv_write = lanes * kv_values_per_token(d) * kv_bytes
    embed = lanes * d["hidden"] * 2
    attn_ops = 4 * lanes * n_attn * d["heads"] * d["head_dim"] * context
    update_ops = 5 * lanes * n_mamba * state_values(d)
    conv_ops = 2 * lanes * n_mamba * d["conv_dim"] * d["taps"]
    return {
        "ops": 2 * lanes * per_token + attn_ops + update_ops + conv_ops,
        "bytes": weights + state + kv_read + kv_write + embed,
        "weight_bytes": weights,
        "state_bytes": state,
        "kv_bytes": kv_read + kv_write,
        "expert_bytes": experts_bytes(d, touched, weight_bytes),
        "experts_touched": touched,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    by_ops = counts["ops"] / peaks["bf16_flops_per_s"]
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops > by_bytes else (by_bytes, "bytes")
