"""What one decode step of the window-and-full attention, sparse-expert model
needs: operations and bytes, from shapes alone.

Counted is what the algorithm needs at the published bytes, not what a
program happens to move or how it lays its pages out:

* every weight of the attention (q, k, v, the gate, o, the heads' norms), the
  four norms, the dense layers' feed-forward, the expert layers' routers and
  shared experts, and the output head read once (the head is untied: the
  token's own row of the embedding is a gather of one row a lane);
* of the routed experts this chip HOLDS (`experts` of the router's
  `router_experts`), those that at least one live lane chose. Under uniform
  routing a token's k experts miss a given one with probability 1 - k /
  router_experts (252/256 at the published sizes), so `lanes` tokens touch
  `experts * (1 - (1 - k / router_experts)**lanes)` of a layer's held experts
  on average: 17.4 of 32 at 50 lanes. An upper estimate: a router that is not
  uniform touches fewer. The counter `dyn_llm_moe_experts_touched` says what
  the steps of a window did touch, and `experts_bytes` is for that reading;
* each live lane's cached keys and values read once and the new token's
  written: the whole context in a full layer, the last `window` positions in
  a window layer (`kv_values_per_token_layer`: 2 planes x 8 heads x 128 x 2
  bytes = 4,096 bytes a token and layer);
* the multiply-adds of the live lanes only: the matrix products (of the
  routed experts a token's held ones, k x experts / router_experts of them on
  average, not every expert) and attention over the keys a layer sees.

**The window layers' rows, from a mean.** The accepted readers call
`step_counts(d, lanes, context)` with the MEAN lanes and context of a
stretch. A step's window layers read `sum_i min(c_i, window)` rows, and
`min(mean, window)` times the lanes is MORE than that whenever long and short
lanes share a step (`min` is concave: about 28% more at this cell's mix), so
a share built on it could overstate. `window_rows_at_least` is therefore the
LEAST the sum can be for any lanes whose contexts add up to `lanes x context`
and of which none is past the served context: as many lanes as that total
fills stand at the served context and read a window each, one lane holds the
remainder, the others hold nothing. No split of the lanes reads fewer rows,
so neither `attn_kernel_roofline` nor `decode_step_mfu` can overstate in this
cell; they understate (by about 40% of the window layers' rows at this mix),
and the exact shares are `window_attn_roofline` and `full_attn_roofline`,
from the engine's own count of `min(context, window)` over live lanes
(`goodput.POOL_COUNTERS`: `window_rows`, `full_rows`).

Activations between programs, padding lanes, the sort and the gathers around
the grouped products are not needed by the algorithm and not counted, so a
share of the roofline built on these counts cannot pass 100%.
"""

from __future__ import annotations


def expert_layers(d: dict) -> int:
    return d["layers"] - d["first_dense"]


def attention_params(d: dict) -> int:
    """One layer's attention: q, the gate and o, k and v, the heads' norms."""
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    return 3 * d["hidden"] * q + 2 * d["hidden"] * kv + 2 * d["head_dim"]


def expert_params(d: dict) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * d["hidden"] * d["moe_inter"]


def expert_layer_params(d: dict) -> int:
    """One expert layer's feed-forward beside its routed experts: the router,
    its bias and the shared expert."""
    return d["hidden"] * d["router_experts"] + d["router_experts"] + expert_params(d)


def param_count(d: dict) -> int:
    """Every parameter of the model `d` describes, with the experts it holds
    (four norms a layer, the final norm, embedding and head)."""
    n_moe = expert_layers(d)
    return (
        d["layers"] * (attention_params(d) + 4 * d["hidden"])
        + d["first_dense"] * 3 * d["hidden"] * d["inter"]
        + n_moe * (expert_layer_params(d) + d["experts"] * expert_params(d))
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


def kv_values_per_token_layer(d: dict) -> int:
    """Keys and values one layer caches of one token."""
    return 2 * d["kv_heads"] * d["head_dim"]


def rows_bytes(d: dict, rows: float, layers: int, kv_bytes: float = 2.0) -> float:
    """Bytes of `rows` cached tokens in each of `layers` layers."""
    return rows * layers * kv_values_per_token_layer(d) * kv_bytes


def window_rows_at_least(d: dict, lanes: float, context: float) -> float:
    """The least `sum_i min(c_i, window)` over any lanes whose contexts add
    up to `lanes x context`, none past the served context (the module's
    docstring says why a mean needs this)."""
    total = max(0.0, lanes * context)
    longest = max(float(d["max_context"]), float(d["window"]), 1.0)
    full_lanes = int(total // longest)
    return full_lanes * d["window"] + min(total - full_lanes * longest, d["window"])


def step_counts(d: dict, lanes: float, context: float, *,
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    """Operations (multiply and add counted separately) and HBM bytes of one
    decode step with `lanes` live lanes whose mean context is `context`
    tokens. `d` as `reference.afmoe.dims` gives it. The window layers' rows
    are a lower bound (`window_rows_at_least`)."""
    n_moe = expert_layers(d)
    head = d["hidden"] * d["vocab"]
    always = (
        d["layers"] * attention_params(d)
        + d["first_dense"] * 3 * d["hidden"] * d["inter"]
        + n_moe * expert_layer_params(d) + head
    )
    touched = n_moe * expected_experts_touched(d, lanes)
    held_a_token = d["top_k"] * d["experts"] / d["router_experts"]
    per_token = always + n_moe * held_a_token * expert_params(d)
    weights = always * weight_bytes + experts_bytes(d, touched, weight_bytes)
    full_rows = lanes * context
    window_rows = window_rows_at_least(d, lanes, context)
    kv_read = (
        rows_bytes(d, full_rows, d["full_layers"], kv_bytes)
        + rows_bytes(d, window_rows, d["window_layers"], kv_bytes)
    )
    kv_write = rows_bytes(d, lanes, d["layers"], kv_bytes)
    embed = lanes * d["hidden"] * 2
    attn_ops = 4 * d["heads"] * d["head_dim"] * (
        d["full_layers"] * full_rows + d["window_layers"] * window_rows
    )
    return {
        "ops": 2 * lanes * per_token + attn_ops,
        "bytes": weights + kv_read + kv_write + embed,
        "weight_bytes": weights,
        "kv_bytes": kv_read + kv_write,
        "expert_bytes": experts_bytes(d, touched, weight_bytes),
        "experts_touched": touched,
        "window_rows_at_least": window_rows,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    by_ops = counts["ops"] / peaks["bf16_flops_per_s"]
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops > by_bytes else (by_bytes, "bytes")
