"""What one decode step of the latent-attention, sparse-expert model needs:
operations and bytes, from shapes alone.

Counted is what the algorithm needs, not what a program happens to move:

* every weight of attention, of the dense layers, of the routers, of the
  shared experts and of the output head read once (the embedding table is a
  gather of one row a lane);
* of the routed experts, those that at least one live lane chose. Under
  uniform routing a token's k experts miss a given one of E with
  probability 1 - k/E, so `lanes` tokens touch E * (1 - (1 - k/E)**lanes)
  of a layer's experts on average: 184 of 256 at 40 lanes, 102 at 16. This
  is an upper estimate: any router that is not uniform touches fewer, and
  the step then needs fewer bytes than are charged here (in the cell of
  record the counter reads within a few percent of it: PERF.md section 6,
  PR 28). The counter `dyn_llm_moe_experts_touched` says what the steps of
  a window did touch, and `experts_bytes` is for that reading;
* each live lane's cached rows read once and its new row written, at the
  576 values a token that the layer declares (512 of latent, 64 of rope
  key), not at the 640 the plane stores them in;
* the multiply-adds of the live lanes only, attention in the absorbed form
  (a query of 576 against each cached row, values the row's first 512).

Activations between programs, padding lanes, the sort and the gathers
around the grouped products are not needed by the algorithm and not
counted. A share of the roofline built on the counter's experts
(`moe_experts_roofline`) cannot pass 100%; one built on `step_counts`
(`decode_step_roofline`) overstates by what a router's skew saves, and
under a skewed router would have to be recounted from the counter before it
could be read near 100%.
"""

from __future__ import annotations


def attention_params(d: dict) -> int:
    heads = d["heads"]
    return (
        d["hidden"] * d["q_rank"]
        + d["q_rank"] * heads * (d["nope"] + d["rope"])
        + d["hidden"] * (d["kv_rank"] + d["rope"])
        + d["kv_rank"] * heads * (d["nope"] + d["v_dim"])
        + heads * d["v_dim"] * d["hidden"]
    )


def expert_params(d: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * d["hidden"] * d["moe_inter"]


def expected_experts_touched(d: dict, lanes: float) -> float:
    """Distinct experts of one layer that `lanes` tokens choose, uniform."""
    e, k = d["experts"], d["top_k"]
    return e * (1.0 - (1.0 - k / e) ** max(0.0, lanes))


def experts_bytes(d: dict, experts_touched: float, weight_bytes: float = 2.0) -> float:
    """Bytes of `experts_touched` routed experts' weights (a counter's
    reading, or `expected_experts_touched`)."""
    return experts_touched * expert_params(d) * weight_bytes


def latent_values_per_token(d: dict) -> int:
    return d["layers"] * (d["kv_rank"] + d["rope"])


def step_counts(d: dict, lanes: float, context: float, *,
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    """Operations (multiply and add counted separately) and HBM bytes of one
    decode step with `lanes` live lanes whose mean context is `context`
    tokens. `d` as `reference.mla_moe.dims` gives it."""
    n_moe = max(0, d["layers"] - d["first_dense"])
    n_dense = d["layers"] - n_moe
    head = d["hidden"] * d["vocab"]
    always = (
        d["layers"] * attention_params(d)
        + n_dense * 3 * d["hidden"] * d["inter"]
        + n_moe * (d["hidden"] * d["experts"] + d["shared"] * expert_params(d))
        + head
    )
    touched = n_moe * expected_experts_touched(d, lanes)
    per_token = always + n_moe * d["top_k"] * expert_params(d)
    weights = always * weight_bytes + experts_bytes(d, touched, weight_bytes)
    row = latent_values_per_token(d) * kv_bytes
    kv_read = lanes * context * row
    kv_write = lanes * row
    embed = lanes * d["hidden"] * 2
    attn_ops = (
        2 * lanes * d["layers"] * d["heads"] * context
        * ((d["kv_rank"] + d["rope"]) + d["kv_rank"])
    )
    return {
        "ops": 2 * lanes * per_token + attn_ops,
        "bytes": weights + kv_read + kv_write + embed,
        "weight_bytes": weights,
        "kv_bytes": kv_read + kv_write,
        "expert_bytes": experts_bytes(d, touched, weight_bytes),
        "experts_touched": touched,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    by_ops = counts["ops"] / peaks["bf16_flops_per_s"]
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops > by_bytes else (by_bytes, "bytes")
