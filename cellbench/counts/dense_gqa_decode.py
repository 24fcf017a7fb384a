"""What one decode step of the dense grouped-query model needs: operations
and bytes, from shapes alone (the arithmetic of
`dynamo_tpu/engine/jax_engine/perf_model.py::decode_hbm_bytes_per_token`,
copied, and counted per step instead of per token).

Counted is what the algorithm needs, not what a program happens to move:
every weight of the layers and of the output head read once (the embedding
table is a gather of one row a lane), each live lane's keys and values read
once at their stored width, the new token's keys and values written, and the
multiply-adds of the live lanes only. Activations between programs, padding
lanes and copies of the cache are not needed by the algorithm and are not
counted, so a share of the roofline built on these counts cannot pass 100%.
"""

from __future__ import annotations


def layer_matmul_params(d: dict) -> int:
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    return d["hidden"] * (q + 2 * kv) + q * d["hidden"] + 3 * d["hidden"] * d["inter"]


def step_counts(d: dict, lanes: float, context: float, *,
                weight_bytes: float = 1.0, kv_bytes: float = 2.0) -> dict:
    """Operations (multiply and add counted separately) and HBM bytes of one
    decode step with `lanes` live lanes whose mean context is `context`
    tokens. `d` as `reference.dense_gqa.dims` gives it."""
    matmul_params = d["layers"] * layer_matmul_params(d) + d["hidden"] * d["vocab"]
    kv_per_position = 2 * d["layers"] * d["kv_heads"] * d["head_dim"]
    weights = matmul_params * weight_bytes
    kv_read = lanes * context * kv_per_position * kv_bytes
    kv_write = lanes * kv_per_position * kv_bytes
    embed = lanes * d["hidden"] * 2
    attn_ops = 4 * lanes * d["layers"] * d["heads"] * d["head_dim"] * context
    return {
        "ops": 2 * lanes * matmul_params + attn_ops,
        "bytes": weights + kv_read + kv_write + embed,
        "weight_bytes": weights,
        "kv_bytes": kv_read + kv_write,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it. int8
    weights are widened to bfloat16 for the multiply, so the bf16 peak."""
    by_ops = counts["ops"] / peaks["bf16_flops_per_s"]
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops > by_bytes else (by_bytes, "bytes")
