"""The Mamba-2, latent-expert family (`models/ssm2_moe.py`) at a toy size of
the same shape (a literal pattern with all three kinds of mixer, 16 experts
of which 4 are held, 3 a token, 2 groups of `B` and `C`, a chunk of 8), held
to the plain float32 reference of `cellbench/reference/ssm2_moe.py` on
logits; the state slots (two arrays a Mamba-2 layer, nothing for an expert
layer) through the runner and the engine; the add-up test of the held share;
what the factory refuses for it; its checkpoint names; and the rule that
finds a family by its `model_type`.

Tolerances. In float32 the program and the reference compute the same
numbers in another order (the recurrence in chunks of 8 by products against
the decay where the reference loops over positions; a chunk's convolution
from a carried tail; paged attention against per-head attention; grouped
products against a loop over the held experts): 5e-5 of the logits' spread
is ten times what such runs read and a hundredth of the smallest difference
a wrong form makes (a state not zeroed at a pack's boundary, a neighbour
group's `B`, an absent expert's part added, the bias in the weights read
1e-2 and more). In bfloat16 the toy reads 0.02 to 0.08 (a score near the
top-3 cut of 16 experts flips an expert now and then); 0.15 holds it to the
same order. Where two of the program's own forms are compared the
arithmetic is the same: a reused slot against a fresh one runs one program
twice and must be equal to the last bit.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cellbench.compare import logit_error  # noqa: E402
from cellbench.reference import ssm2_moe as R  # noqa: E402
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner  # noqa: E402
from dynamo_tpu.models import (  # noqa: E402
    config_from_model_dir, layer_cache_kinds, paged_layers, recurrent_layers,
)
from dynamo_tpu.models import ssm2_moe as M  # noqa: E402
from dynamo_tpu.ops.sampling import MAX_EOS_IDS  # noqa: E402
# the toy harness of the other family with state slots: the same block size,
# lanes, tables and vocabulary
from tests.test_hybrid_ssm import (  # noqa: E402
    BS, LANES, MAX_BLOCKS, NB, chunk_args, decode_multi, greedy, pack,
    prompt_tokens, rel, tables_for,
)
from tests.test_hybrid_ssm import write_model_dir as _write_model_dir  # noqa: E402

HF = {
    "model_type": "nemotron_h", "hidden_size": 64, "num_hidden_layers": 6,
    "hybrid_override_pattern": "MEM*EM", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2, "expand": 2,
    "conv_kernel": 4, "chunk_size": 8, "use_conv_bias": True,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_shared_experts": 1,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "first_held_expert": 0, "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "num_nextn_predict_layers": 0,
    "vocab_size": 300, "max_position_embeddings": 128,
    "tie_word_embeddings": False,
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = os.path.join(REPO, "cellbench", "configs", "nemotron3-super-bf16-l11-e128.json")
assert (BS, NB, MAX_BLOCKS, LANES) == (4, 48, 8, 3)
F32_TOL, BF16_TOL = 5e-5, 0.15
MAMBA_LAYERS, ATTN_LAYERS, EXPERT_LAYERS = (0, 2, 5), (3,), (1, 4)
FLOAT32_ALWAYS = ("router_bias", "dt_bias", "A_log", "D")


@functools.lru_cache(maxsize=None)
def reference_weights(first_held: int = 0, held: int = 4):
    d = R.dims(dict(HF, first_held_expert=first_held, n_routed_experts=held))
    *layers, top = list(R.seeded_layers(d, 0))
    return d, layers, top


def params_from(layers, top, dtype):
    return {
        "layers": [
            {k: v.astype(jnp.float32 if k in FLOAT32_ALWAYS else dtype) for k, v in l.items()}
            for l in layers
        ],
        **{k: v.astype(dtype) for k, v in top.items()},
    }


def toy(attn_impl: str = "xla", dtype=jnp.float32):
    """(config, params handed over from the reference's own draw, the
    reference's dims, layers and top)."""
    cfg = dataclasses.replace(M.Ssm2MoeConfig.from_hf_dict(HF), attn_impl=attn_impl)
    d, layers, top = reference_weights()
    return cfg, params_from(layers, top, dtype), d, layers, top


def caches(cfg, dtype=jnp.float32, fill: float = 0.0, lanes: int = LANES, blocks: int = NB):
    """The runner's two containers for `lanes` lanes and the null lane: pages
    for the attention layer; the state and the tail, filled with `fill` (a
    slot's content before a sequence starts must not count), for a Mamba-2
    layer; None in both places for an expert layer."""
    (state, _), (tail, _) = cfg.state_kind().slot
    pages = lambda: jnp.zeros((cfg.num_kv_heads, blocks, BS, cfg.head_dim), dtype)
    k = {"M": lambda: jnp.full((lanes + 1,) + state, fill, jnp.float32),
         "*": pages, "E": lambda: None}
    v = {"M": lambda: jnp.full((lanes + 1,) + tail, fill, jnp.float32),
         "*": pages, "E": lambda: None}
    return tuple(k[c]() for c in cfg.pattern), tuple(v[c]() for c in cfg.pattern)


def reference_logits(sequences, rows, lower=None):
    d, layers, top = reference_weights()
    return np.asarray(R.forward(layers, top, d, sequences, rows, lower=lower))


def against_reference(sequences, rows, top_ids, top_lps):
    want = reference_logits(sequences, rows)
    served, reference, stds = [], [], []
    for i in range(len(sequences)):
        for r in range(len(rows)):
            ids = np.asarray(top_ids[i][r], np.int64)
            served.append([float(x) for x in top_lps[i][r]])
            reference.append([float(x) for x in want[i, r, ids]])
            stds.append(float(np.std(want[i, r])))
    return logit_error(served, reference, stds)["rms_rel"]


def slots_of(n: int, lanes: list[int]):
    return jnp.asarray(lanes + [0] * (n - len(lanes)), jnp.int32)


def reference_slot(prompt):
    """(state, tail) the reference's own pass leaves behind `prompt` in the
    first Mamba-2 layer (the model's layer 0, which sees the embedding)."""
    d, layers, top = reference_weights()
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(prompt)]
        _, xbc, xs, b, c, dt = R.update_inputs(x, layers[0], d)
        _, states = R.recurrence(xs, dt, b, c, layers[0]["A_log"])
    return np.asarray(states[-1]), np.asarray(xbc[-3:]).reshape(-1)


# ------------------------------------------- (a) the forward, every position


def test_full_forward_against_the_reference():
    """One sequence through the packed program alone (23 tokens: two of the
    recurrence's own chunk boundaries and a padded third chunk): the logits
    at its last position, and the state and tail it leaves in its slot
    against the reference's loop over positions; no other lane's slot is
    touched, and an expert layer keeps nothing."""
    cfg, params, *_ = toy()
    n = 23
    prompt = prompt_tokens(n, 11)
    head, last = pack([prompt], tables_for(), 32)
    kc, vc = caches(cfg, fill=3.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, [1])
    )
    assert rel(logits[0], reference_logits([prompt], [n - 1])[0, 0]) < F32_TOL
    assert all(kc[i] is None and vc[i] is None for i in EXPERT_LAYERS)
    state, tail = reference_slot(prompt)
    np.testing.assert_allclose(np.asarray(kc[0][1]), state, atol=2e-5)
    np.testing.assert_allclose(np.asarray(vc[0][1]), tail, atol=2e-5)
    for lane in (0, 2):
        assert np.all(np.asarray(kc[0][lane]) == 3.0) and np.all(np.asarray(vc[0][lane]) == 3.0)


# ------------------------- (b) two sequences in one pack, then decode_multi


@pytest.mark.parametrize("attn_impl,dtype,tol", [
    ("xla", "float32", F32_TOL), ("pallas_interpret", "float32", F32_TOL),
    ("xla", "bfloat16", BF16_TOL), ("pallas_interpret", "bfloat16", BF16_TOL),
])
def test_packed_prefill_then_decode_through_slots_against_the_reference(attn_impl, dtype, tol):
    """Two prompts of unlike lengths packed into one prefill (the boundary
    falls inside one of the recurrence's chunks of 8: the carry is zeroed
    there and the second sequence sees nothing of the first), written to
    slots 2 and 0 of dirty slot arrays; then `decode_multi@H4` with lane 1
    idle: the top-20 log-probs of every generated position against the
    reference's full pass; and the experts' counters that ride the same
    fetch, with the assignments made beside the held ones."""
    dt = jnp.dtype(dtype)
    cfg, params, *_ = toy(attn_impl, dt)
    H, n0, n1 = 4, 13, 6
    prompts = [prompt_tokens(n0, 1), prompt_tokens(n1, 2)]
    tables = tables_for()
    lanes = [2, 0]  # the first prompt lives in lane 2, the second in lane 0
    head, last = pack(prompts, tables[lanes], 32)
    kc, vc = caches(cfg, dt, fill=5.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, lanes)
    )
    for i, p in enumerate(prompts):
        assert rel(logits[i], reference_logits([p], [len(p) - 1])[0, 0]) < tol
    if dtype == "float32":
        state, tail = reference_slot(prompts[1])
        np.testing.assert_allclose(np.asarray(kc[0][0]), state, atol=2e-5)
        np.testing.assert_allclose(np.asarray(vc[0][0]), tail, atol=2e-5)
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    packed, kc, vc = decode_multi(
        cfg, params, H, kc, vc, [first[1], 0, first[0]], [n1, 0, n0],
        tables, [True, False, True], [100] * LANES,
    )
    packed = np.asarray(packed)
    # a row behind the lanes carries what the expert layers counted
    assert packed.shape[1] == LANES + 1 and (packed[:, 1, 0] == -1).all()
    K = (packed.shape[-1] - 2) // 2
    for lane, prompt, tok0 in ((2, prompts[0], first[0]), (0, prompts[1], first[1])):
        toks = packed[:, lane, 0].astype(np.int64).tolist()
        n = len(prompt)
        err = against_reference(
            [prompt + [int(tok0)] + toks], [n + h for h in range(H)],
            [[packed[h, lane, 2:2 + K] for h in range(H)]],
            [[packed[h, lane, 2 + K:] for h in range(H)]],
        )
        assert err < tol, (lane, err)
    # the idle lane's slot is as it was: a sequence in the middle of a
    # chunked prefill may own it
    assert np.all(np.asarray(kc[0][1]) == 5.0) and np.all(np.asarray(vc[0][1]) == 5.0)
    # 2 expert layers x 4 steps; 2 live lanes x 3 experts each made, of which
    # those among the 4 held of 16 are counted; an idle lane makes none
    layer_steps, held, touched, busiest, made = packed[:, -1, :5].sum(0)
    assert layer_steps == 2 * H and made == 2 * H * 2 * 3
    assert 0 <= held <= made and touched <= min(held, 2 * H * 4) and busiest <= 2 * H * 2
    assert M.STEP_STATS[-1] == "assignments_made"


# --------------------------------- (c) a prompt in three chunks, mixed steps


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_a_prompt_prefilled_in_three_chunks_equals_one_pass(attn_impl):
    """A 37-token prompt enters lane 1 as chunks of 16, 16 and 5 tokens (two
    chunk boundaries for the state and the tail to cross in their slot, two
    of the recurrence's own chunks inside each; the last chunk's padded rows
    must not move either), each in a mixed step on a batch whose lanes 0 and
    2 decode: the chunk's first token and the lanes' tokens against the
    reference, and the slot the three chunks leave against the slot one
    packed pass leaves."""
    cfg, params, *_ = toy(attn_impl)
    n, C, n_long = 9, 16, 37
    prompts = [prompt_tokens(n, 3), prompt_tokens(n, 4)]
    long_prompt = prompt_tokens(n_long, 5)
    tables = tables_for(LANES)
    wide = np.zeros((LANES, 12), np.int32)  # lane 1 needs 10 blocks of 4
    wide[:, :MAX_BLOCKS] = tables
    wide[1, MAX_BLOCKS:] = np.arange(1 + LANES * MAX_BLOCKS, 5 + LANES * MAX_BLOCKS)
    tables = wide
    head, last = pack(prompts, tables[[0, 2]], 32)
    kc, vc = caches(cfg, fill=2.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, [0, 2])
    )
    tok = np.zeros(LANES, np.int32)
    tok[[0, 2]] = np.asarray(jnp.argmax(logits, axis=-1), np.int32)[:2]
    keys, temps, top_ps, top_ks, want = greedy(LANES)
    mixed = jax.jit(functools.partial(ModelRunner._mixed_impl, cfg, None, None))
    sequences = {0: prompts[0] + [int(tok[0])], 2: prompts[1] + [int(tok[2])]}
    lane_ids, lane_lps = {0: [], 2: []}, {0: [], 2: []}
    chunk_out = None
    starts = (0, C, 2 * C)
    for step, start in enumerate(starts):
        chunk = chunk_args(long_prompt[start:start + C], start, n_long, tables[1], 1, C)
        positions = np.asarray([n + step, 0, n + step], np.int32)
        slots = tables[np.arange(LANES), positions // BS] * BS + positions % BS
        slots[1] = 0  # lane 1 does not decode: its write goes to the null block
        outs, kc, vc = mixed(
            params, kc, vc, (chunk,), jnp.asarray(tok), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(slots), keys, temps, top_ps, top_ks, want,
            jnp.full((LANES, MAX_EOS_IDS), -1, jnp.int32), jnp.zeros(LANES, bool),
        )
        chunk_out, (new, _, ids, lps) = outs[:4], outs[4:8]
        new = np.asarray(new, np.int32)
        for i in (0, 2):
            tok[i] = new[i]
            sequences[i].append(int(new[i]))
            lane_ids[i].append(np.asarray(ids[i]))
            lane_lps[i].append(np.asarray(lps[i]))
    rows = [n + s for s in range(len(starts))]
    for i in (0, 2):
        err = against_reference([sequences[i][:-1]], rows, [lane_ids[i]], [lane_lps[i]])
        assert err < F32_TOL, (i, err)
    err = against_reference(
        [long_prompt], [n_long - 1], [[np.asarray(chunk_out[2])]], [[np.asarray(chunk_out[3])]],
    )
    assert err < F32_TOL, err
    state, tail = reference_slot(long_prompt)
    np.testing.assert_allclose(np.asarray(kc[0][1]), state, atol=2e-5)
    np.testing.assert_allclose(np.asarray(vc[0][1]), tail, atol=2e-5)
    # the same prompt in one packed pass, into a fresh lane
    head, last = pack([long_prompt], tables[[1]], 64)
    k1, v1 = caches(cfg, blocks=NB + 16)
    _, k1, v1 = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, k1, v1, last, state_slots=slots_of(LANES, [1])
    )
    for i in MAMBA_LAYERS:
        np.testing.assert_allclose(np.asarray(kc[i][1]), np.asarray(k1[i][1]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(vc[i][1]), np.asarray(v1[i][1]), atol=2e-5)


def test_a_whole_prompt_equals_the_packed_program():
    """`prefill` (one whole prompt padded to a bucket) is the packed program
    with one segment: the reference's logits, the same slot."""
    cfg, params, *_ = toy("xla")
    n = 27
    prompt = prompt_tokens(n, 12)
    tokens = np.zeros(32, np.int32)
    tokens[:n] = prompt
    table = tables_for()[1]
    kc, vc = caches(cfg, fill=4.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill, params, cfg))(
        jnp.asarray(tokens), jnp.int32(n), kc, vc, jnp.asarray(table),
        state_slots=jnp.int32(1),
    )
    assert rel(logits, reference_logits([prompt], [n - 1])[0, 0]) < F32_TOL
    state, tail = reference_slot(prompt)
    np.testing.assert_allclose(np.asarray(kc[0][1]), state, atol=2e-5)
    np.testing.assert_allclose(np.asarray(vc[0][1]), tail, atol=2e-5)
    assert np.all(np.asarray(kc[0][0]) == 4.0)


# ------------------------------ (d) a horizon against single steps, (e) reuse


def test_decode_multi_equals_single_steps_with_a_lane_that_ends_inside():
    """`decode_multi@H4` against four `decode` steps from the same caches:
    lane 0 may emit two tokens and then stops (a frozen lane's state is
    masked as an idle lane's is), lane 2 runs all four. The same tokens;
    log-probs and both lanes' slots to float32 roundings."""
    cfg, params, *_ = toy("xla")
    n = 10
    prompts = [prompt_tokens(n, 6), prompt_tokens(n, 7)]
    tables = tables_for()
    head, last = pack(prompts, tables[[0, 2]], 32)
    kc, vc = caches(cfg)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, [0, 2])
    )
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    H = 4
    packed, km, vm = decode_multi(
        cfg, params, H, kc, vc, [first[0], 0, first[1]], [n, 0, n], tables,
        [True, False, True], [2, 1, 100],
    )
    packed = np.asarray(packed)
    assert (packed[2:, 0, 0] == -1).all() and (packed[:2, 0, 0] >= 0).all()
    keys, temps, top_ps, top_ks, want = greedy(LANES)
    single = jax.jit(functools.partial(ModelRunner._decode_impl, cfg, None, None))
    tok = np.asarray([first[0], 0, first[1]], np.int32)
    pos = np.asarray([n, 0, n], np.int32)
    k1, v1 = kc, vc
    for h in range(H):
        live = np.asarray([h < 2, False, True])
        slots = np.where(live, tables[np.arange(LANES), pos // BS] * BS + pos % BS, 0)
        step_keys = keys.at[:, 1].add(jnp.uint32(h))
        (t, lp, _, _), k1, v1 = single(
            params, k1, v1, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(slots.astype(np.int32)), step_keys, temps, top_ps, top_ks, want,
        )
        t, lp = np.asarray(t), np.asarray(lp)
        for lane in (0, 2):
            if live[lane]:
                assert packed[h, lane, 0] == t[lane]
                assert abs(packed[h, lane, 1] - lp[lane]) < 1e-5
                tok[lane], pos[lane] = t[lane], pos[lane] + 1
    for i in MAMBA_LAYERS:
        for lane in (0, 2):
            np.testing.assert_allclose(np.asarray(km[i][lane]), np.asarray(k1[i][lane]), atol=2e-5)
            np.testing.assert_allclose(np.asarray(vm[i][lane]), np.asarray(v1[i][lane]), atol=2e-5)


# the toy with a Mamba-2 state the update kernel can tile (`ops/pallas_ssm.py`
# `tiling`): 16 heads of 16 in 2 groups, so a row of 128 `dt x` is one group's
# heads, and a state 128 wide
KERNEL_HF = dict(HF, mamba_num_heads=16, ssm_state_size=128, expand=4)


def test_decode_through_the_update_kernel_equals_the_plain_form():
    """`decode` and `decode_multi@H4` with the Mamba-2 update in the Pallas
    kernel (interpreted) against the same programs in the plain form, from
    the same caches: lanes 0 and 2 decode (lane 0 may emit two tokens and then
    stops), lane 1's slot is a chunked prefill's between two chunks. The same
    tokens, logits and log-probs within the file's tolerance, the decoding
    lanes' slots to float32 roundings; lane 1's slot and the null lane's are
    bit for bit what they were; a dispatch's layers take the kernel once a
    layer and step, the three calls that do not settle in a body of their
    own each."""
    from dynamo_tpu.models import forms_called, layer_bodies_called

    cfgs = {
        impl: dataclasses.replace(M.Ssm2MoeConfig.from_hf_dict(KERNEL_HF), attn_impl=impl)
        for impl in ("xla", "pallas_interpret")
    }
    params = M.init_params(cfgs["xla"], jax.random.PRNGKey(3), jnp.float32)
    n = 10
    prompts = [prompt_tokens(n, 6), prompt_tokens(n, 7)]
    tables = tables_for()
    head, last = pack(prompts, tables[[0, 2]], 32)
    kc, vc = caches(cfgs["xla"])
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfgs["xla"]))(
        *head, kc, vc, last, state_slots=slots_of(LANES, [0, 2])
    )
    owned = np.random.default_rng(5)
    kc = tuple(
        k.at[1].set(jnp.asarray(owned.standard_normal(k.shape[1:]), jnp.float32))
        if i in MAMBA_LAYERS else k for i, k in enumerate(kc)
    )
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    tok, pos = [first[0], 0, first[1]], [n, 0, n]
    live = np.asarray([True, False, True])
    slots = np.where(live, tables[np.arange(LANES), n // BS] * BS + n % BS, 0)
    one, four, counted = {}, {}, {}
    for impl, cfg in cfgs.items():
        jax.clear_caches()  # a body traced for the other form would not be counted
        with layer_bodies_called():
            one[impl] = jax.jit(functools.partial(M.decode, params, cfg))(
                jnp.asarray(tok, jnp.int32), jnp.asarray(pos, jnp.int32), kc, vc,
                jnp.asarray(tables), jnp.asarray(slots.astype(np.int32)),
            )
        counted[impl, "decode"] = forms_called()
        with layer_bodies_called() as bodies:
            four[impl] = decode_multi(
                cfg, params, 4, kc, vc, tok, pos, tables, list(live), [2, 1, 100]
            )
        counted[impl, "decode_multi"] = forms_called()
        # a body a kind of layer; where the kernel runs, the Mamba-2 layer's
        # three steps that do not settle are three more
        assert len(bodies) == (3 if impl == "xla" else 6)
    M_ = len(MAMBA_LAYERS)
    forms = lambda impl, program: tuple(
        counted[impl, program][f] for f in ("ssd_step_kernel", "ssd_step_xla")
    )
    assert forms("xla", "decode") == forms("xla", "decode_multi") == (0, M_)
    assert forms("pallas_interpret", "decode") == (M_, 0)
    assert forms("pallas_interpret", "decode_multi") == (4 * M_, 0)

    (l_x, k_x, v_x), (l_k, k_k, v_k) = one["xla"], one["pallas_interpret"]
    for lane in (0, 2):
        assert rel(l_k[lane], l_x[lane]) < F32_TOL
    (p_x, km_x, vm_x), (p_k, km_k, vm_k) = four["xla"], four["pallas_interpret"]
    p_x, p_k = np.asarray(p_x), np.asarray(p_k)
    assert (p_k[2:, 0, 0] == -1).all() and np.array_equal(p_k[:, :LANES, 0], p_x[:, :LANES, 0])
    emitted = p_x[:, :LANES, 0] >= 0
    assert np.abs(p_k[:, :LANES, 1] - p_x[:, :LANES, 1])[emitted].max() < 1e-5
    for i in MAMBA_LAYERS:
        for kept_k, kept_x in ((k_k, k_x), (km_k, km_x)):
            for lane in (0, 2):
                np.testing.assert_allclose(
                    np.asarray(kept_k[i][lane]), np.asarray(kept_x[i][lane]), atol=2e-5
                )
            for row in (1, LANES):
                assert np.array_equal(
                    np.asarray(kept_k[i][row]).view(np.uint32),
                    np.asarray(kc[i][row]).view(np.uint32),
                )
        for lane in (0, 2):
            np.testing.assert_allclose(
                np.asarray(vm_k[i][lane]), np.asarray(vm_x[i][lane]), atol=2e-5
            )


def test_a_reused_slot_gives_what_a_fresh_slot_gives():
    """Lane 1 serves one sequence (prefill and four decode steps), then a
    second one is prefilled into the same lane without any clearing: its
    logits and the four tokens behind them equal, bit for bit, what an
    untouched cache gives."""
    cfg, params, *_ = toy("xla")
    tables = tables_for()
    prefill = jax.jit(functools.partial(M.prefill_packed, params, cfg))

    def serve(kc, vc, prompt):
        head, last = pack([prompt], tables[[1]], 32)
        logits, kc, vc = prefill(*head, kc, vc, last, state_slots=slots_of(LANES, [1]))
        first = int(jnp.argmax(logits[0]))
        packed, kc, vc = decode_multi(
            cfg, params, 4, kc, vc, [0, first, 0], [0, len(prompt), 0], tables,
            [False, True, False], [100] * LANES,
        )
        return np.asarray(logits[0]), np.asarray(packed)[:, 1], kc, vc

    kc, vc = caches(cfg)
    _, _, kc, vc = serve(kc, vc, prompt_tokens(17, 8))
    second = prompt_tokens(9, 9)
    used_logits, used_steps, _, _ = serve(kc, vc, second)
    fresh_logits, fresh_steps, _, _ = serve(*caches(cfg), second)
    np.testing.assert_array_equal(used_logits, fresh_logits)
    np.testing.assert_array_equal(used_steps, fresh_steps)


# --------------------------------------------------- (f) the shares add up


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer on the same tokens as each of the four chips that
    share it would run it (experts 0-3, 4-7, 8-11, 12-15 of 16; the router,
    the latent projections and the shared expert on every chip): the four
    routed parts, each through `W_fc2`, plus the shared expert counted once
    equal the uncut reference's layer (a config that holds all 16), in the
    program and in the reference alike. The router is the published width
    on every share, and every assignment is held by exactly one of them."""
    shares = [reference_weights(first, 4) for first in (0, 4, 8, 12)]
    d0, layers0, top = shares[0]
    e = EXPERT_LAYERS[0]
    common = ("norm", "router", "router_bias", "w_fc1", "w_fc2", "shared_wu", "shared_wd")
    for _, layers, _ in shares[1:]:
        for name in common:
            np.testing.assert_array_equal(
                np.asarray(layers[e][name].astype(jnp.float32)),
                np.asarray(layers0[e][name].astype(jnp.float32)))
        assert not np.array_equal(
            np.asarray(layers[e]["wu"].astype(jnp.float32)),
            np.asarray(layers0[e]["wu"].astype(jnp.float32)))
    whole_d = R.dims(dict(HF, n_routed_experts=16))
    whole = dict(layers0[e])
    whole["wu"] = jnp.concatenate([s[1][e]["wu"] for s in shares])
    whole["wd"] = jnp.concatenate([s[1][e]["wd"] for s in shares])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.layer_forward(x, whole, whole_d))
        shared_once = np.asarray(R.layer_forward(
            x, dict(whole, wu=whole["wu"][:0], wd=whole["wd"][:0]),
            R.dims(dict(HF, n_routed_experts=0, first_held_expert=0)),
        )) - np.asarray(x)
        ref_parts = [
            np.asarray(R.layer_forward(x, s[1][e], s[0])) - np.asarray(x) - shared_once
            for s in shares
        ]
    np.testing.assert_allclose(
        np.asarray(x) + sum(ref_parts) + shared_once, want, atol=2e-5)
    valid = jnp.ones((40,), bool)
    parts, made, held = [], [], []
    for first, (d, layers, _) in zip((0, 4, 8, 12), shares):
        cfg = M.Ssm2MoeConfig.from_hf_dict(dict(HF, first_held_expert=first))
        layer = params_from([layers[e]], {}, jnp.float32)["layers"][0]
        out, counted = M._experts(x[0], layer, valid, cfg=cfg)
        parts.append(np.asarray(out) - np.asarray(x[0]) - shared_once[0])
        held.append(float(counted[1]))
        made.append(float(counted[4]))
    np.testing.assert_allclose(
        np.asarray(x[0]) + sum(parts) + shared_once[0], want[0], atol=5e-5)
    for got, ref in zip(parts, ref_parts):
        np.testing.assert_allclose(got, ref[0], atol=5e-5)
    assert made == [40 * 3] * 4 and sum(held) == 40 * 3 and all(h > 0 for h in held)


# ------------------------------------------------ (g) both controls fail


def serve_one(cfg, params, seq, n_pre, blocks):
    """Logits of one sequence served as the cell serves it, in bfloat16: a
    packed prefill of its first `n_pre` tokens, then decode steps through
    the slot and the pages; rows n_pre - 1 to the end."""
    prefill = jax.jit(functools.partial(M.prefill_packed, params, cfg))
    single = jax.jit(functools.partial(M.decode, params, cfg))
    table = np.zeros((LANES, blocks), np.int32)
    table[0] = np.arange(1, blocks + 1)
    kc, vc = caches(cfg, jnp.bfloat16, blocks=blocks + 1)
    head, last = pack([seq[:n_pre]], table[[0]], -(-n_pre // 32) * 32)
    logits, kc, vc = prefill(*head, kc, vc, last, state_slots=slots_of(LANES, [0]))
    got = [np.asarray(logits[0], np.float32)]
    for p in range(n_pre, len(seq)):
        slot = table[0, p // BS] * BS + p % BS
        lg, kc, vc = single(
            jnp.asarray([seq[p], 0, 0], jnp.int32), jnp.asarray([p, 0, 0], jnp.int32),
            kc, vc, jnp.asarray(table), jnp.asarray([slot, 0, 0], jnp.int32),
        )
        got.append(np.asarray(lg[0], np.float32))
    return np.stack(got)


def test_both_controls_differ_from_the_reference_and_the_state_control_with_the_length():
    """The cell's controls at the toy's size (a control is the reference in
    the program's place, one precision lower): int8 weights, and the state
    and the recurrence in bfloat16. Both read a hundred times what float32
    arithmetic reordered reads (the float32 program: under 5e-5) while the
    served bfloat16 path stays inside its tolerance; the state control grows
    with the length (a slow head's state adds inputs a hundredth of its size,
    which a bfloat16 state rounds away one by one), which is why the cell's
    check holds most of its positions past 1,100 tokens, and the state the
    bfloat16 recurrence leaves is off by a hundred times what the program's
    slot is held to. The toy's experts are too few for the published widths'
    flips: the cell's own readings are in its configuration's `check.why`."""
    d, layers, top = reference_weights()
    cfg, params, *_ = toy("xla", jnp.bfloat16)
    n, seeds = 28, (20, 21, 22, 23)
    seqs = [prompt_tokens(n, s) for s in seeds]
    rows = list(range(n - 12, n))
    want = reference_logits(seqs, rows)
    served = rel(np.stack([serve_one(cfg, params, s, n - 11, -(-n // BS)) for s in seqs]), want)
    assert served < BF16_TOL
    assert rel(reference_logits(seqs, rows, lower="int8_weights"), want) > 100 * F32_TOL
    by_length = {}
    for length in (n, 120):
        long = [prompt_tokens(length, s) for s in seeds[:2]]
        last = list(range(length - 12, length))
        by_length[length] = rel(
            reference_logits(long, last, lower="bf16_state"), reference_logits(long, last))
    # the toy reads 0.002 at 28 tokens and 0.007 at 120
    assert by_length[120] > 100 * F32_TOL and by_length[120] > 2 * by_length[n] > 20 * F32_TOL
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(prompt_tokens(120, 20))]
        _, _, xs, b, c, dt = R.update_inputs(x, layers[0], d)
        _, exact = R.recurrence(xs, dt, b, c, layers[0]["A_log"])
        _, lowered = R.recurrence(xs, dt, b, c, layers[0]["A_log"], lower="bf16_state")
    assert float(jnp.max(jnp.abs(exact[-1] - lowered[-1]))) > 100 * 2e-5


# ------------------------------------------------ (h) the family is found


def write_model_dir(path, hf=HF) -> str:
    return _write_model_dir(path, hf)


def test_the_family_is_chosen_by_model_type_and_what_it_lacks_is_refused(tmp_path):
    cfg = config_from_model_dir(write_model_dir(tmp_path / "a"))
    assert isinstance(cfg, M.Ssm2MoeConfig) and cfg.pattern == "MEM*EM"
    assert (cfg.num_experts, cfg.router_experts, cfg.first_held_expert) == (4, 16, 0)
    kinds = layer_cache_kinds(cfg)
    assert [k.name for k in kinds] == [
        "recurrent", "nothing", "recurrent", "kv_heads", "nothing", "recurrent"]
    # a slot of two arrays: the state [heads, head_dim, d_state] and the last
    # three rows of x, B, C together (128 + 2 x 2 x 16 = 192 channels)
    assert recurrent_layers(cfg) == 3 and paged_layers(cfg) == 1
    assert kinds[0].slot == (((8, 16, 16), "float32"), ((3 * 192,), "float32"))
    assert kinds[0].slot_bytes == (8 * 16 * 16 + 576) * 4 and kinds[1].slot_bytes == 0
    assert kinds[3].stored_values_per_token() == 2 * 2 * 16
    for bad, words in (
        (dict(HF, hybrid_override_pattern="MEM*E"), "hybrid_override_pattern"),
        (dict(HF, hybrid_override_pattern="MEM*E-"), "hybrid_override_pattern"),
        (dict(HF, hybrid_override_pattern="MEMMEM"), "hybrid_override_pattern"),
        (dict(HF, num_nextn_predict_layers=1), "num_nextn_predict_layers"),
        (dict(HF, n_group=2), "n_group"),
        (dict(HF, mlp_hidden_act="silu"), "mlp_hidden_act"),
        (dict(HF, use_conv_bias=False), "use_conv_bias"),
        (dict(HF, mamba_proj_bias=True), "bias"),
        (dict(HF, moe_latent_size=None), "moe_latent_size"),
        (dict(HF, first_held_expert=14), "first_held_expert"),
        (dict(HF, expand=4), "expand"),
    ):
        with pytest.raises(ValueError, match=words + ".*not implemented"):
            M.Ssm2MoeConfig.from_hf_dict(bad)
    for unknown in ("nemotron", "mamba2"):
        with pytest.raises(ValueError, match=f"model_type '{unknown}' is not served.*nemotron_h"):
            config_from_model_dir(write_model_dir(tmp_path / unknown, dict(HF, model_type=unknown)))
    for call in (M.prefill_mm, M.prefill_context_parallel, M.embed_pooled, M.decode_verify):
        with pytest.raises(NotImplementedError, match="Mamba-2, latent-expert family"):
            call()


def catalog_row() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(
            r for r in map(json.loads, f)
            if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        )


def test_the_catalog_rows_config_whole_and_cut(tmp_path):
    """The parent refused `nemotron_h` in words; the change builds the
    family's config from the catalog row's (its prediction head taken off:
    refused in words where asked for), at the widths published: 40 Mamba-2,
    40 expert and 8 attention layers, 120,668,707,840 parameters; the
    benchmark's cut (its first 11 layers, 128 of 512 experts held) holds
    5,453,470,080."""
    hf = catalog_row()["config"]
    with pytest.raises(ValueError, match="num_nextn_predict_layers.*not implemented"):
        config_from_model_dir(write_model_dir(tmp_path / "mtp", hf))
    cfg = config_from_model_dir(write_model_dir(tmp_path / "whole", dict(hf, num_nextn_predict_layers=0)))
    assert isinstance(cfg, M.Ssm2MoeConfig)
    assert (cfg.layers_of("M"), cfg.layers_of("E"), cfg.layers_of("*")) == (40, 40, 8)
    assert (cfg.d_inner, cfg.conv_dim, cfg.rms_eps) == (8192, 10240, 1e-5)
    assert (cfg.num_experts, cfg.router_experts, cfg.num_experts_per_tok) == (512, 512, 22)
    per = M.mixer_param_counts(cfg)
    assert per == {"M": 109_640_064, "*": 35_655_680, "E": 54_530_560}
    assert M.routed_expert_params(cfg) == 5_505_024
    assert M.param_count(cfg) == 120_668_707_840
    with open(CUT) as f:
        cut_hf = json.load(f)
    cut = M.Ssm2MoeConfig.from_hf_dict(cut_hf)
    assert cut.pattern == hf["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert (cut.num_experts, cut.router_experts, cut.first_held_expert) == (128, 512, 0)
    assert M.param_count(cut) == 5_453_470_080
    assert M.expert_param_count(cut) == 5 * 128 * 5_505_024
    shapes = jax.eval_shape(lambda: M.init_params(cut, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == M.param_count(cut)
    kinds = layer_cache_kinds(cut)
    # a lane's slot: 5 x (4 MiB of state + 3 x 10,240 float32 of tail); a
    # token's rows: 1 layer x 2 planes x 2 heads x 128 x 2 bytes
    assert sum(k.slot_bytes for k in kinds) == 5 * (4_194_304 + 122_880)
    assert sum(k.stored_values_per_token() * 2 for k in kinds) == 1024
    # every number of the catalog's config stands in the file under its key,
    # but for the keys the file lists as reduced
    reduced = set(cut_hf["bench"]["reduced"])
    for key, value in hf.items():
        if key not in reduced:
            assert cut_hf[key] == value, key
    assert reduced == {"num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
                       "num_nextn_predict_layers", "max_position_embeddings",
                       "bos_token_id", "eos_token_id"}


def test_the_programs_draw_is_the_references():
    """`init_params` draws a layer in one jitted program; the reference draws
    tensor by tensor from the same keys. Every matrix is the same to the bit
    (`_dense` keeps the draw and its divisor behind a barrier); the
    recurrence's float32 constants, which pass through a logarithm or an
    exponential that a fused program may round in its last place, to 1e-6."""
    cfg = M.Ssm2MoeConfig.from_hf_dict(HF)
    _, layers, top = reference_weights()
    mine = M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    assert len(mine["layers"]) == len(layers) == cfg.num_layers
    for got, want in zip(mine["layers"], layers):
        assert set(got) == set(want)
        for name in got:
            a, b = (np.asarray(x.astype(jnp.float32)) for x in (got[name], want[name]))
            if name in ("dt_bias", "A_log"):
                np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=name)
            else:
                np.testing.assert_array_equal(a, b, err_msg=name)
    for name in ("embed", "lm_head"):
        np.testing.assert_array_equal(
            np.asarray(mine[name].astype(jnp.float32)), np.asarray(top[name].astype(jnp.float32)))
    # another share of the same layer holds other experts behind the same router
    other = M.init_params(dataclasses.replace(cfg, first_held_expert=4), jax.random.PRNGKey(0))
    a, b = mine["layers"][1], other["layers"][1]
    assert np.array_equal(np.asarray(a["router"], np.float32), np.asarray(b["router"], np.float32))
    assert not np.array_equal(np.asarray(a["wu"], np.float32), np.asarray(b["wu"], np.float32))


def test_block_budget_takes_the_state_slots_off_first(monkeypatch):
    from dynamo_tpu.engine.jax_engine import factory

    with open(CUT) as f:
        cfg = M.Ssm2MoeConfig.from_hf_dict(json.load(f))
    # wanted: 64 lanes x 512 blocks + 64 = 32,832 blocks of ONE layer's rows
    # (16 x 2 x 2 x 128 x 2 = 16,384 bytes): they fit beside 10.9 GB of
    # weights and the 65 slots of 21.6 MB; on a smaller chip what is left
    # after both decides
    monkeypatch.setattr(factory, "hbm_budget_bytes", lambda: 16_909_336_064)
    assert factory.default_num_blocks(cfg, 8192, 64) == 32_832
    monkeypatch.setattr(factory, "hbm_budget_bytes", lambda: 14 * 2**30)
    room = int(14 * 2**30 * 0.85) - 2 * M.param_count(cfg) - 65 * 5 * (4_194_304 + 122_880)
    assert factory.default_num_blocks(cfg, 8192, 64) == room // 16_384 < 32_832


@pytest.mark.parametrize("asked,words", [
    (dict(kv_dtype="int8"), "int8-resident cache"),
    (dict(quantize=True), "int8 weights.*state-space mixers.*expert stacks"),
    (dict(meshed=True), "mesh.*exchange between the chips"),
    (dict(fused_decode=True), "fused decode"),
    (dict(env={"DYN_KV_HOST_OFFLOAD_GB": "1"}), "block-manager tiers .*prefix reuse"),
    (dict(env={"DYN_SPEC_K": "3"}), "rejected draft would need the state rolled back"),
])
def test_what_the_family_is_not_served_with_is_refused_in_words(monkeypatch, asked, words):
    from dynamo_tpu.engine.jax_engine.factory import refuse_unsupported

    asked = dict(asked)
    for k, v in asked.pop("env", {}).items():
        monkeypatch.setenv(k, v)
    cfg, *_ = toy("xla")
    with pytest.raises(ValueError, match="recurrent state a sequence in 3 of its 6 layers.*" + words):
        refuse_unsupported(cfg, **asked)
    monkeypatch.undo()
    refuse_unsupported(cfg)  # nothing asked, nothing refused


def test_the_runner_allocates_by_layer_and_nothing_for_an_expert_layer():
    cfg, params, *_ = toy("xla")
    kw = dict(num_blocks=NB, block_size=BS, max_batch=2, max_model_len=32, attn_impl="xla")
    with pytest.raises(ValueError, match="int8-resident"):
        ModelRunner(cfg, params, kv_dtype="int8", **kw)
    runner = ModelRunner(cfg, params, kv_dtype=jnp.float32, **kw)
    assert runner.state_slots == 3 and runner.recurrent_layers == 3
    assert len(runner.k_cache) == len(runner.v_cache) == 6
    shapes = lambda cache: [None if a is None else tuple(a.shape) for a in cache]
    assert shapes(runner.k_cache) == [
        (3, 8, 16, 16), None, (3, 8, 16, 16), (2, NB, BS, 16), None, (3, 8, 16, 16)]
    assert shapes(runner.v_cache) == [
        (3, 576), None, (3, 576), (2, NB, BS, 16), None, (3, 576)]
    # nothing stands in for what an expert layer does not keep: no leaf
    assert len(jax.tree.leaves(runner.k_cache)) == len(jax.tree.leaves(runner.v_cache)) == 4
    assert runner.k_cache[0].dtype == runner.v_cache[0].dtype == jnp.float32
    for call in (
        lambda: runner.extract_blocks([1, 2]),
        lambda: runner.inject_blocks([1], None, None),
    ):
        with pytest.raises(ValueError, match="3 of this model's 6 layers keep a recurrent state"):
            call()
    with pytest.raises(ValueError, match="must name the lane slot"):
        runner.pack_prefill([])


def test_checkpoint_names_round_trip_to_the_seeded_logits(tmp_path):
    """The seeded weights written under the names and layouts of Hugging
    Face's `NemotronH*` classes (matrices `[out, in]`, the convolution
    `[channels, 1, kernel]`, the gate's correction bias float32), with all
    16 experts of each expert layer in the file as a published checkpoint
    has them: the loader reads the 4 this chip holds and the same logits
    come back. A synthetic state dict: no published checkpoint is at hand,
    and the loader says so."""
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

    held_from = 4
    hf = dict(HF, first_held_expert=held_from)
    cfg = dataclasses.replace(M.Ssm2MoeConfig.from_hf_dict(hf), attn_impl="xla")
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    out: dict[str, np.ndarray] = {}

    def put(name, w, transpose=True):
        w = np.asarray(w, np.float32)
        out[name] = np.ascontiguousarray(w.T if transpose else w)

    rng = np.random.default_rng(0)
    for i, layer in enumerate(params["layers"]):
        p = f"backbone.layers.{i}."
        put(p + "norm.weight", layer["norm"], False)
        m = p + "mixer."
        if cfg.kind(i) == "*":
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                put(f"{m}{theirs}.weight", layer[ours])
        elif cfg.kind(i) == "M":
            put(m + "in_proj.weight", layer["w_in"])
            put(m + "out_proj.weight", layer["w_out"])
            out[m + "conv1d.weight"] = np.ascontiguousarray(
                np.asarray(layer["conv_w"], np.float32).T[:, None, :])
            put(m + "conv1d.bias", layer["conv_b"], False)
            for ours, theirs in (("dt_bias", "dt_bias"), ("A_log", "A_log"), ("D", "D")):
                put(m + theirs, layer[ours], False)
            put(m + "norm.weight", layer["gate_norm"], False)
        else:
            put(m + "gate.weight", layer["router"])
            put(m + "gate.e_score_correction_bias", layer["router_bias"], False)
            put(m + "fc1_latent_proj.weight", layer["w_fc1"])
            put(m + "fc2_latent_proj.weight", layer["w_fc2"])
            put(m + "shared_experts.up_proj.weight", layer["shared_wu"])
            put(m + "shared_experts.down_proj.weight", layer["shared_wd"])
            for e in range(cfg.router_experts):
                k = e - held_from
                if 0 <= k < cfg.num_experts:
                    up, down = layer["wu"][k], layer["wd"][k]
                else:  # an absent chip's expert: in the file, never read
                    up = rng.standard_normal(layer["wu"][0].shape)
                    down = rng.standard_normal(layer["wd"][0].shape)
                put(f"{m}experts.{e}.up_proj.weight", up)
                put(f"{m}experts.{e}.down_proj.weight", down)
    put("backbone.embeddings.weight", params["embed"], False)
    put("backbone.norm_f.weight", params["final_norm"], False)
    put("lm_head.weight", params["lm_head"])
    model_dir = write_model_dir(tmp_path, hf)
    save_file(out, os.path.join(model_dir, "model.safetensors"))
    loaded = load_or_init_params(model_dir, cfg, dtype=jnp.float32)
    assert len(loaded["layers"]) == cfg.num_layers and "lm_head" in loaded
    assert loaded["layers"][0]["conv_w"].shape == (4, 192)
    assert loaded["layers"][1]["wu"].shape == (4, 32, 48)
    assert loaded["layers"][1]["router_bias"].dtype == jnp.float32
    assert loaded["layers"][0]["A_log"].dtype == jnp.float32
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    prompt = prompt_tokens(12, 9)
    head, last = pack([prompt], tables_for()[[0]], 16)
    slots = slots_of(LANES, [0])
    a, *_ = M.prefill_packed(params, cfg, *head, *caches(cfg), last, state_slots=slots)
    b, *_ = M.prefill_packed(loaded, cfg, *head, *caches(cfg), last, state_slots=slots)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="int8 weights"):
        load_or_init_params(model_dir, cfg, quantize=True)


# ------------------------------------------------------ (i) the engine


async def build(tmp_path, monkeypatch, **kw):
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine

    monkeypatch.setenv("DYN_DECODE_HORIZON", "4")
    engine, _ = await build_jax_engine(
        write_model_dir(tmp_path), name="t",
        kv_block_size=4, max_batch=4, **{"num_blocks": 96, **kw},
    )
    assert isinstance(engine.runner.config, M.Ssm2MoeConfig)
    return engine


async def test_served_through_the_engine_with_both_ledgers_and_no_block_hashes(tmp_path, monkeypatch):
    """`build_jax_engine` on a `nemotron_h` directory: the same engine,
    programs and cache manager. Two prompts (one chunked beside the other's
    decoding, at an 8-token step budget) stream exactly their tokens, alike
    in two runs; the ledger's `ssm` slot counts what the lane arrays said of
    the three Mamba-2 layers' slots and its `moe` slot what the device
    counted of the held experts and of the assignments made; three layer
    bodies a pass; no block hash is published; wiring disaggregation or a
    peer pull is refused in words."""
    from tests.test_colocated_disagg import collect_tokens

    monkeypatch.setenv("DYN_PREFILL_CHUNK_TOKENS", "8")
    engine = await build(tmp_path, monkeypatch)
    stored = []
    engine.on_blocks_stored = stored.extend
    try:
        short, long = list(range(3, 12)), [3 + (7 * i) % 40 for i in range(30)]
        first = await asyncio.gather(
            collect_tokens(engine, short, 12), collect_tokens(engine, long, 6)
        )
        again = await asyncio.gather(
            collect_tokens(engine, short, 12), collect_tokens(engine, long, 6)
        )
        assert first == again and [len(t) for t in first] == [12, 6]
        summary = engine.stats.goodput.summary()
        ssm, moe = summary["ssm"], summary["moe"]
        assert ssm["slot_resets"] == 4 and ssm["scan_tokens"] == 2 * (9 + 30)
        assert ssm["layer_steps"] > 0 and ssm["layer_steps"] % 3 == 0
        assert 0 < ssm["slots_live"] <= 4 * ssm["layer_steps"] // 3
        # 2 expert layers a step; a live lane's token makes 3 assignments, of
        # which those to the 4 held of 16 experts are counted as held
        assert moe["layer_steps"] > 0 and moe["layer_steps"] % 2 == 0
        assert moe["assignments_made"] % 3 == 0 and moe["assignments_made"] >= 3 * moe["layer_steps"]
        assert 0 < moe["assignments"] < moe["assignments_made"]
        assert moe["experts_touched"] <= 4 * moe["layer_steps"]
        labels = set(summary["compile_s_by_label"])
        assert labels <= {"prefill_packed", "prefill_chunk", "mixed_step@c1", "mixed_step@c2",
                          "decode", "decode_multi@H4B4"}, labels
        bodies = {k: v["layer_bodies"] for k, v in summary["first_dispatch_by_label"].items()}
        assert all(bodies[k] == (6 if k.startswith("mixed") else 3) for k in bodies), bodies
        assert stored == []
        for wire in ("remote_prefill_client", "peer_block_client"):
            with pytest.raises(ValueError, match="keep a recurrent state"):
                setattr(engine, wire, object())
            setattr(engine, wire, None)
    finally:
        await engine.close()


async def test_a_preempted_sequence_replays_to_the_same_greedy_tokens(tmp_path, monkeypatch):
    """A sequence is preempted in the middle of its answer (its slot and
    blocks freed), and its replay from position 0 (prompt and generated
    tokens through the prefill program, the state zeroed there) streams the
    tokens an undisturbed run streams. With float32 weights: the replay
    computes in chunks of the prefill program what decode steps computed
    token by token, which agree to float32 roundings; in bfloat16 the two
    differ by enough to move a score across the top-3 cut of the toy's 16
    experts now and then, and a greedy token with it."""
    from dynamo_tpu.engine.jax_engine import factory
    from tests.test_colocated_disagg import collect_tokens

    monkeypatch.setattr(
        factory, "load_or_init_params",
        lambda path, config, **kw: M.init_params(config, jax.random.PRNGKey(0), jnp.float32),
    )
    engine = await build(tmp_path, monkeypatch)
    try:
        assert engine.runner.params["layers"][0]["w_in"].dtype == jnp.float32
        prompt = list(range(5, 19))
        undisturbed = await collect_tokens(engine, prompt, 24)

        async def preempt_once():
            while True:
                await asyncio.sleep(0.001)
                for seq in list(engine.slots):
                    if seq is not None and 6 <= seq.num_generated <= 16 and not seq.prefilling:
                        async with engine._device_lock:
                            if seq.slot is not None:
                                engine._preempt_seq(seq)
                                return

        task = asyncio.ensure_future(preempt_once())
        replayed = await collect_tokens(engine, prompt, 24)
        await task
        assert replayed == undisturbed and len(replayed) == 24
    finally:
        await engine.close()


# ------------------------------------------- (j) run in=http out=jax


def test_run_http_jax_streams_exact_token_counts(tmp_path):
    """`python -m dynamo_tpu.run in=http out=jax` on the toy directory, no
    option, variable or model name beyond what every model gets: streamed
    completions of exactly the tokens asked for, and `/debug/goodput` with
    the `ssm` and the `moe` slots, `assignments_made` among the latter."""
    import http.client
    import signal
    import socket
    import subprocess
    import time

    model_dir = write_model_dir(tmp_path / "m")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items() if not k.startswith("DYN_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, DYN_DECODE_HORIZON="4")
    log = open(tmp_path / "server.log", "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.run", "in=http", "out=jax",
         "--model-path", model_dir, "--model-name", "toy", "--http-host", "127.0.0.1",
         "--http-port", str(port), "--context-length", "128", "--max-batch", "4"],
        env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
    )
    try:
        deadline = time.monotonic() + 180
        while True:
            assert proc.poll() is None, open(tmp_path / "server.log").read()[-3000:]
            assert time.monotonic() < deadline, "server not ready"
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
                conn.request("GET", "/health")
                if conn.getresponse().status == 200:
                    break
            except OSError:
                pass
            time.sleep(0.5)
        from tests.util import make_test_tokenizer

        vocab = make_test_tokenizer()._hf.get_vocab()
        words = [w for w, i in sorted(vocab.items(), key=lambda kv: kv[1]) if i >= 3][:20]
        for n_out in (5, 17):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            body = json.dumps({
                "model": "toy", "prompt": " ".join(words[:12]), "max_tokens": n_out,
                "stream": True, "temperature": 0.0, "ignore_eos": True,
                "nvext": {"ignore_eos": True},
            })
            conn.request("POST", "/v1/completions", body, {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            usage, reasons = None, []
            for raw in resp.read().decode().splitlines():
                if raw.startswith("data: ") and raw != "data: [DONE]":
                    chunk = json.loads(raw[6:])
                    usage = chunk.get("usage") or usage
                    reasons += [c.get("finish_reason") for c in chunk.get("choices", []) if c.get("finish_reason")]
            assert reasons == ["length"]
            if usage is not None:
                assert usage["completion_tokens"] == n_out
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/debug/goodput")
        ledger = json.loads(conn.getresponse().read())["goodput"]
        assert ledger["ssm"]["slot_resets"] == 2 and ledger["ssm"]["scan_tokens"] >= 24
        assert ledger["moe"]["layer_steps"] > 0 and ledger["moe"]["assignments_made"] > 0
        assert ledger["decode_tokens"] + 2 >= 5 + 17 - 2
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()
