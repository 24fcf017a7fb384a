"""The plain reference against the program at a tiny size on the CPU, the
seeded weights it makes for itself, and the control that must fail."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench.compare import logit_error  # noqa: E402
from cellbench.reference import dense_gqa as ref  # noqa: E402
from dynamo_tpu.models import llama as L  # noqa: E402

MISTRAL = {
    "model_type": "mistral", "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 2, "num_attention_heads": 8, "num_key_value_heads": 2,
    "vocab_size": 300, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "sliding_window": 6, "max_position_embeddings": 64,
    "tie_word_embeddings": False,
}
QWEN = {
    "model_type": "qwen2", "architectures": ["Qwen2ForCausalLM"],
    "hidden_size": 56, "intermediate_size": 144, "num_hidden_layers": 2,
    "num_attention_heads": 7, "num_key_value_heads": 1, "vocab_size": 400,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-6, "sliding_window": 4,
    "use_sliding_window": False, "max_position_embeddings": 64,
    "tie_word_embeddings": False,
}
BLOCK = 4


def program_params(layers, top, dtype):
    """The reference's own weights, handed to the program's forward pass."""
    out = {"layers": [], "embed": top["embed"].astype(dtype),
           "final_norm": top["final_norm"].astype(dtype), "lm_head": top["lm_head"]}
    for layer in layers:
        out["layers"].append({
            k: (v if isinstance(v, dict) else v.astype(dtype)) for k, v in layer.items()
        })
    return out


def program_logits(cfg, params, tokens, n_prompt):
    """Prefill of the first `n_prompt` tokens, then one decode step per
    further token through the paged cache: logits at every row from
    n_prompt - 1 on."""
    T = len(tokens)
    nb = -(-T // BLOCK) + 1
    shape = (cfg.num_layers, cfg.num_kv_heads, nb + 1, BLOCK, cfg.head_dim)
    kc = jnp.zeros(shape, params["embed"].dtype)
    vc = jnp.zeros_like(kc)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)
    pad = -(-n_prompt // BLOCK) * BLOCK
    prompt = jnp.zeros((pad,), jnp.int32).at[:n_prompt].set(jnp.asarray(tokens[:n_prompt]))
    logits, kc, vc = L.prefill(
        params, cfg, prompt, jnp.int32(n_prompt), kc, vc, table[: pad // BLOCK]
    )
    rows = [np.asarray(logits, np.float32)]
    for pos in range(n_prompt, T):
        slot = table[pos // BLOCK] * BLOCK + pos % BLOCK
        logits, kc, vc = L.decode(
            params, cfg, jnp.asarray([tokens[pos]], jnp.int32),
            jnp.asarray([pos], jnp.int32), kc, vc, table[None, :],
            jnp.asarray([slot], jnp.int32),
        )
        rows.append(np.asarray(logits[0], np.float32))
    return np.stack(rows)


def weights(hf, seed, with_bias=False):
    d = ref.dims(hf)
    *layers, top = list(ref.seeded_layers(d, seed))
    if with_bias:
        key = jax.random.PRNGKey(99)
        for layer in layers:
            for name in ("bq", "bk", "bv"):
                key, sub = jax.random.split(key)
                layer[name] = 0.5 * jax.random.normal(sub, layer[name].shape, jnp.float32)
    return d, layers, top


@pytest.mark.parametrize("hf,bias", [(MISTRAL, False), (QWEN, True)], ids=["mistral", "qwen2"])
def test_program_in_float32_matches_the_reference(hf, bias):
    """Same weights, float32 activations in the program: the two passes are
    the same mathematics (rotate-half rope, grouped heads, window, bias),
    prefill and then decode through the cache against one full pass."""
    d, layers, top = weights(hf, 3, bias)
    cfg = L.LlamaConfig.from_hf_dict(hf)
    assert cfg.attn_bias == bias
    assert (cfg.sliding_window is not None) == (d["window"] is not None)
    tokens = list(np.random.default_rng(0).integers(3, hf["vocab_size"], 19))
    n_prompt = 13
    got = program_logits(cfg, program_params(layers, top, jnp.float32), tokens, n_prompt)
    rows = list(range(n_prompt - 1, len(tokens)))
    want = np.asarray(ref.forward(layers, top, d, [tokens], rows))[0]
    assert got.shape == want.shape
    scale = float(np.std(want))
    assert np.max(np.abs(got - want)) / scale < 2e-3


def test_window_and_bias_change_the_reference():
    d, layers, top = weights(MISTRAL, 3)
    tokens = [list(range(5, 25))]
    a = np.asarray(ref.forward(layers, top, d, tokens))
    b = np.asarray(ref.forward(layers, top, dict(d, window=None), tokens))
    # rows inside the window agree; later rows see what the window hid
    assert np.allclose(a[0, :6], b[0, :6], atol=1e-5)
    assert np.abs(a[0, 12:] - b[0, 12:]).max() > 1e-3
    dq, lq, tq = weights(QWEN, 3, with_bias=True)
    with_b = np.asarray(ref.forward(lq, tq, dq, tokens))
    for layer in lq:
        for name in ("bq", "bk", "bv"):
            layer[name] = jnp.zeros_like(layer[name])
    assert np.abs(with_b - np.asarray(ref.forward(lq, tq, dq, tokens))).max() > 1e-3


def assert_same_levels(mine, theirs):
    """The same int8 levels, but for the rare value on .5 that a fused
    divide rounds to the neighbouring level (see the reference's note)."""
    a, b = np.asarray(mine, np.int32), np.asarray(theirs, np.int32)
    assert np.abs(a - b).max() <= 1
    assert np.mean(a != b) < 1e-3


@pytest.mark.parametrize("hf", [MISTRAL, QWEN], ids=["mistral", "qwen2"])
def test_seeded_weights_are_the_programs_draw(hf):
    """The reference draws its own weights; they are the ones the program's
    random initialisation makes from the same key."""
    d, layers, top = weights(hf, 0)
    cfg = L.LlamaConfig.from_hf_dict(hf)
    prog = L.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16, True)
    for mine, theirs in zip(layers, prog["layers"]):
        for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
            assert_same_levels(mine[name]["q"], theirs[name]["q"])
            assert np.array_equal(
                np.asarray(mine[name]["s"], np.float32), np.asarray(theirs[name]["s"], np.float32)
            )
        assert ("bq" in mine) == ("bq" in theirs)
    assert np.array_equal(
        np.asarray(top["embed"], np.float32), np.asarray(prog["embed"], np.float32)
    )
    assert_same_levels(top["lm_head"]["q"], prog["lm_head"]["q"])


def compared(d, layers, top, served_logits, rows, tokens, k=20):
    want = np.asarray(ref.forward(layers, top, d, [tokens], rows))[0]
    ids = np.argsort(-served_logits, axis=-1)[:, :k]
    served = [list(map(float, served_logits[r, ids[r]])) for r in range(len(rows))]
    reference = [list(map(float, want[r, ids[r]])) for r in range(len(rows))]
    return logit_error(served, reference, [float(np.std(want[r])) for r in range(len(rows))])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("hf", [MISTRAL, QWEN], ids=["mistral", "qwen2"])
def test_served_precision_passes_and_the_control_fails(hf, seed):
    """The program as served (bfloat16 activations, int8 weights) lies
    within the limit; the reference in the next lower precision, put in the
    program's place, does not. The limits of the cells are read on the chip
    at the published widths; this keeps the comparison itself honest."""
    d, layers, top = weights(hf, seed)
    cfg = L.LlamaConfig.from_hf_dict(hf)
    tokens = list(np.random.default_rng(seed).integers(3, hf["vocab_size"], 22))
    n_prompt = 14
    rows = list(range(n_prompt - 1, len(tokens)))
    prog = L.init_params(cfg, jax.random.PRNGKey(seed), jnp.bfloat16, True)
    served = compared(d, layers, top, program_logits(cfg, prog, tokens, n_prompt), rows, tokens)
    control = compared(
        d, layers, top,
        np.asarray(ref.forward(layers, top, d, [tokens], rows, lower="int4_weights"))[0],
        rows, tokens,
    )
    limit = 0.05
    assert served["rms_rel"] < limit < control["rms_rel"]
    assert control["rms_rel"] > 3 * served["rms_rel"]
    assert served["positions"] == len(rows) and served["values"] == 20 * len(rows)


def test_logit_error_ignores_a_shift_and_sees_a_difference():
    ref_vals = [[1.0, 2.0, 4.0], [0.0, -1.0, 3.0]]
    shifted = [[v - 7.5 for v in row] for row in ref_vals]
    assert logit_error(shifted, ref_vals, [1.0, 1.0])["rms_rel"] == pytest.approx(0.0, abs=1e-12)
    off = [[1.0, 2.0, 4.3], [0.0, -1.0, 3.0]]
    err = logit_error(off, ref_vals, [2.0, 2.0])
    assert err["rms_rel"] == pytest.approx(((0.01 + 0.01 + 0.04) / 6) ** 0.5 / 2.0)
    assert err["max_rel"] == pytest.approx(0.1)
    assert logit_error([[float("nan"), 1.0]], [[0.0, 1.0]], [1.0])["rms_rel"] == float("inf")
    with pytest.raises(ValueError):
        logit_error([], [], [])
