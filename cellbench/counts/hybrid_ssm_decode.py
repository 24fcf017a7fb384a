"""What one decode step of the hybrid state-space model needs: operations
and bytes, from shapes alone.

Counted is what the algorithm needs, not what a program happens to move:

* every weight of the Mamba mixers, the attention mixers, the feed-forwards
  and the output head read once (the tied embedding table is read once as
  the head; the token's own row is a gather of one row a lane);
* each live lane's recurrent state (`d_state x d_inner` float32 a Mamba
  layer, and the convolution's tail, `(d_conv - 1) x d_inner` float32) read
  once a step and written once a dispatch: a step's token depends on the last
  layer of the step before, so every layer's state comes from memory again in
  every step (64 lanes' states are 0.55 GB, no chip holds them between
  layers), but a dispatch of `horizon` steps need keep only its last state: a
  program may recompute a layer's state from the dispatch's first instead of
  storing it after every step, and the compiled horizon does (PERF.md
  section 6, PR 38: four reads and one write a layer in four steps). So
  `1 + 1 / horizon` passes a step, not two. A lane that holds no sequence
  needs nothing, whatever a program reads for it;
* each live lane's cached keys and values read once and the new token's
  written, for the attention layers only (`kv_bytes`: 2 layers x 2 planes x
  128 x 2 bytes = 1,024 bytes a token at the published sizes);
* the multiply-adds of the live lanes only: the matrix products, attention
  over the context, and the recurrence's update (for each of `d_state x
  d_inner` values a decay times the state plus an input, and the product
  with C: 6 operations, the exponential counted as one).

Activations between programs, padding lanes and the state of idle lanes are
not needed by the algorithm and not counted, so a share of the roofline built
on these counts cannot pass 100%.
"""

from __future__ import annotations

# the engine's default decode horizon, which every cell's server runs
# (`facts.decode_horizon`); `step_counts` is called without one
HORIZON = 4


def mamba_layers(d: dict) -> int:
    return d["layers"] - d["attn_layers"]


def mamba_mixer_params(d: dict) -> int:
    """One Mamba mixer: its projections, convolution, norms and constants."""
    h, di, n, r, k = d["hidden"], d["d_inner"], d["d_state"], d["dt_rank"], d["d_conv"]
    return (
        h * 2 * di + k * di + di + di * (r + 2 * n) + (r + 2 * n)
        + r * di + di + n * di + di + di * h
    )


def attention_mixer_params(d: dict) -> int:
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    return d["hidden"] * (q + 2 * kv) + q * d["hidden"]


def mlp_params(d: dict) -> int:
    return 3 * d["hidden"] * d["inter"]


def state_bytes_per_lane(d: dict) -> int:
    """The slot a sequence keeps, whatever its length: float32 state and
    tail of every Mamba layer."""
    return mamba_layers(d) * (d["d_state"] + d["d_conv"] - 1) * d["d_inner"] * 4


def state_passes_a_step(horizon: int = HORIZON) -> float:
    """Times a live lane's state crosses the memory bus in one step of a
    dispatch of `horizon` steps: read in every step, written after the last."""
    return 1.0 + 1.0 / max(1, int(horizon))


def state_step_bytes(d: dict, lanes: float, horizon: int = HORIZON) -> float:
    """Bytes a decode step must move for the recurrent state alone."""
    return state_passes_a_step(horizon) * lanes * state_bytes_per_lane(d)


def scan_state_step_bytes(d: dict, lanes: float, horizon: int = HORIZON) -> float:
    """The same for the scan's state alone, without the convolution's tail:
    what the operations that `ssm_step_ms` times read and write."""
    return (
        state_passes_a_step(horizon) * lanes
        * mamba_layers(d) * d["d_state"] * d["d_inner"] * 4
    )


def kv_values_per_token(d: dict) -> int:
    return 2 * d["attn_layers"] * d["kv_heads"] * d["head_dim"]


def step_counts(d: dict, lanes: float, context: float, *,
                weight_bytes: float = 2.0, kv_bytes: float = 2.0,
                horizon: int = HORIZON) -> dict:
    """Operations (multiply and add counted separately) and HBM bytes of one
    decode step with `lanes` live lanes whose mean context is `context`
    tokens. `d` as `reference.hybrid_ssm.dims` gives it."""
    n_mamba, n_attn = mamba_layers(d), d["attn_layers"]
    head = d["hidden"] * d["vocab"]
    # the recurrence's constants (A_log, D, b_dt) are float32 in the program;
    # counted at the weights' width like everything else: 0.3% of a mixer
    matmul_params = (
        n_mamba * mamba_mixer_params(d) + n_attn * attention_mixer_params(d)
        + d["layers"] * mlp_params(d) + head
    )
    weights = matmul_params * weight_bytes
    state = state_step_bytes(d, lanes, horizon)
    kv_read = lanes * context * kv_values_per_token(d) * kv_bytes
    kv_write = lanes * kv_values_per_token(d) * kv_bytes
    embed = lanes * d["hidden"] * 2
    attn_ops = 4 * lanes * n_attn * d["heads"] * d["head_dim"] * context
    scan_ops = 6 * lanes * n_mamba * d["d_state"] * d["d_inner"]
    return {
        "ops": 2 * lanes * matmul_params + attn_ops + scan_ops,
        "bytes": weights + state + kv_read + kv_write + embed,
        "weight_bytes": weights,
        "state_bytes": state,
        "kv_bytes": kv_read + kv_write,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    by_ops = counts["ops"] / peaks["bf16_flops_per_s"]
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops > by_bytes else (by_bytes, "bytes")
