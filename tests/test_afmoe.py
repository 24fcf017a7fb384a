"""The window-and-full attention, sparse-expert family (`models/afmoe.py`,
`model_type: afmoe`) at a toy size, against the plain reference
(`cellbench/reference/afmoe.py`): a window of two blocks and sequences
several windows long, so that every program attends across the window's edge
while the window layers' blocks are being given back.

* the program's draw is the reference's;
* chunked prefill across the edge, then decode steps, through pages that
  are given back (and filled with a sentinel) as they leave the window, in
  XLA and through the interpreted paged kernel that appends: the logits are
  the reference's and no given-back block is written;
* a packed prefill and one whole prompt longer than the window;
* mixed steps and `decode_multi` through the runner, its tables built from
  the allocator's companions;
* the eight shares of a layer add up to the uncut layer, the shared expert
  counted once;
* the allocator; the budget of two pools; what is refused, in words;
* through the engine with a launch ahead in flight: a block a window layer
  has given back is another sequence's, is untouched while it is free, and
  the tokens are those of an engine that gives nothing back; a preempted
  sequence replays from 0; `run in=http out=jax`.
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
from cellbench.reference import afmoe as R  # noqa: E402
from dynamo_tpu.engine.jax_engine.kv_cache import BlockAllocator, OutOfBlocks  # noqa: E402
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner  # noqa: E402
from dynamo_tpu.models import (  # noqa: E402
    afmoe as M, config_from_model_dir, forward_for, layer_cache_kinds, page_groups,
)

BS, WINDOW = 8, 16
NB_FULL, NB_WINDOW, MAX_BLOCKS = 64, 24, 16
SENTINEL = 777.0
HF = {
    "model_type": "afmoe", "architectures": ["AfmoeForCausalLM"],
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"],
    "num_dense_layers": 1, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 128, "num_experts": 4, "num_experts_published": 16,
    "first_held_expert": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "route_scale": 2.448, "route_norm": True, "score_func": "sigmoid",
    "mup_enabled": True, "n_group": 1, "topk_group": 1, "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "sliding_window": WINDOW,
    "vocab_size": 300, "max_position_embeddings": 128, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOL = 3e-5


@functools.lru_cache(maxsize=None)
def reference_weights():
    d = R.dims(HF)
    *layers, top = list(R.seeded_layers(d, 0))
    return d, layers, top


def toy(attn_impl: str = "xla", dtype=jnp.float32):
    """(config, params handed over from the reference's own draw)."""
    cfg = dataclasses.replace(M.AfmoeConfig.from_hf_dict(HF), attn_impl=attn_impl)
    _, layers, top = reference_weights()
    params = {
        "layers": [
            {k: (v if k == "router_bias" else v.astype(dtype)) for k, v in l.items() if k != "window"}
            for l in layers
        ],
        **{k: v.astype(dtype) for k, v in top.items()},
    }
    return cfg, params


def reference_logits(seq):
    d, layers, top = reference_weights()
    return np.asarray(R.forward(layers, top, d, [seq]))[0]


def tokens(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng(seed).integers(3, HF["vocab_size"], size=n).tolist()


def caches(cfg, dtype=jnp.float32):
    shape = lambda i: (
        cfg.num_kv_heads, NB_WINDOW if cfg.is_window_layer(i) else NB_FULL, BS, cfg.head_dim)
    k = tuple(jnp.zeros(shape(i), dtype) for i in range(cfg.num_layers))
    return k, tuple(jnp.zeros_like(x) for x in k)


class Pages:
    """What the engine does for one sequence, by hand: blocks with their
    window companions, given back when the window has left them and filled
    with a sentinel there and then."""

    def __init__(self, cfg, alloc: BlockAllocator):
        self.cfg, self.alloc, self.ids, self.released = cfg, alloc, [], 0

    def cover(self, n_tokens: int):
        need = -(-n_tokens // BS) - len(self.ids)
        if need > 0:
            self.ids += self.alloc.alloc(need)

    def table(self, nb: int = MAX_BLOCKS) -> np.ndarray:
        t = np.zeros(2 * nb, np.int32)
        t[: len(self.ids)] = self.ids
        t[nb: nb + len(self.ids)] = self.alloc.window_of[self.ids]
        return t

    def give_back(self, kc, vc, q_min: int):
        upto = min(max(0, q_min - WINDOW + 1) // BS, len(self.ids))
        if upto <= self.released:
            return kc, vc
        given = self.alloc.give_back(self.ids[self.released: upto])
        self.alloc.free_window(given)
        self.released = upto
        fill = lambda c: tuple(
            x.at[:, jnp.asarray(given)].set(SENTINEL) if self.cfg.is_window_layer(i) else x
            for i, x in enumerate(c)
        )
        return fill(kc), fill(vc)


def free_window_blocks_hold_the_sentinel_or_nothing(cfg, alloc, kc, vc):
    free = np.asarray(alloc._free_window)
    for i in range(cfg.num_layers):
        if cfg.is_window_layer(i):
            for plane in (kc[i], vc[i]):
                blocks = np.moveaxis(np.asarray(plane[:, free]), 1, 0)
                for b, x in zip(free, blocks):
                    assert np.all(x == SENTINEL) or np.all(x == 0), (i, int(b))


# ------------------------------------------------------------ the draw


def test_the_programs_draw_is_the_references():
    cfg, _ = toy()
    own = M.init_params(cfg, jax.random.PRNGKey(0))
    _, layers, top = reference_weights()
    assert len(own["layers"]) == len(layers) == 5
    for a, b in zip(own["layers"], layers):
        assert set(a) == set(b) - {"window"}
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k], np.float32), np.asarray(b[k], np.float32))
    for k in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(np.asarray(own[k], np.float32), np.asarray(top[k], np.float32))
    # two shares of one layer hold different experts and the same router
    other = M.init_params(dataclasses.replace(cfg, first_held_expert=8), jax.random.PRNGKey(0))
    assert not np.array_equal(np.asarray(other["layers"][1]["wg"], np.float32),
                              np.asarray(own["layers"][1]["wg"], np.float32))
    np.testing.assert_array_equal(np.asarray(other["layers"][1]["router"], np.float32),
                                  np.asarray(own["layers"][1]["router"], np.float32))
    assert M.param_count(cfg) == sum(x.size for x in jax.tree.leaves(own))
    assert forward_for(cfg) is M and M.STEP_STATS[-1] == "assignments_made"


# ------------------------- chunks and decode steps through pages given back


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_chunked_prefill_and_decode_through_pages_being_given_back(attn_impl):
    """A prompt of 50 tokens in chunks of 16 (three windows long: every
    chunk but the first attends across the window's edge), then 20 decode
    steps in a batch of three with idle lanes beside it; before every call
    the blocks that have wholly left the window go back and are filled with
    a sentinel. The logits are the reference's full forward's, and no free
    block is written, by a chunk's rows or by the paged call's append."""
    cfg, params = toy(attn_impl)
    alloc = BlockAllocator(NB_FULL, NB_WINDOW)
    seq, n_pre, C = tokens(70), 50, 16
    want = reference_logits(seq)
    chunk = jax.jit(functools.partial(M.prefill_chunk, params, cfg))
    step = jax.jit(functools.partial(M.decode, params, cfg))
    kc, vc = caches(cfg)
    pages = Pages(cfg, alloc)
    pages.cover(n_pre)
    for start in range(0, n_pre, C):
        kc, vc = pages.give_back(kc, vc, start)
        toks = np.zeros(C, np.int32)
        n = min(C, n_pre - start)
        toks[:n] = seq[start: start + n]
        logits, kc, vc = chunk(
            jnp.asarray(toks), jnp.int32(start), jnp.int32(n_pre), kc, vc, jnp.asarray(pages.table()))
    assert pages.released == 4 and alloc.window_given_back == 4
    np.testing.assert_allclose(np.asarray(logits), want[n_pre - 1], atol=TOL)
    free_window_blocks_hold_the_sentinel_or_nothing(cfg, alloc, kc, vc)
    for p in range(n_pre, len(seq)):
        pages.cover(p + 1)
        kc, vc = pages.give_back(kc, vc, p)
        tables = np.zeros((3, 2 * MAX_BLOCKS), np.int32)
        tables[1] = pages.table()
        slot = pages.ids[p // BS] * BS + p % BS
        logits, kc, vc = step(
            jnp.asarray([0, seq[p], 0], jnp.int32), jnp.asarray([0, p, 0], jnp.int32), kc, vc,
            jnp.asarray(tables), jnp.asarray([0, slot, 0], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits[1]), want[p], atol=TOL)
    # a lane 70 tokens deep holds the window's blocks and one of slack, not 9
    assert len(pages.ids) == 9 and len(pages.ids) - pages.released == 3
    assert (alloc.window_of[pages.ids] > 0).sum() == 3
    free_window_blocks_hold_the_sentinel_or_nothing(cfg, alloc, kc, vc)
    # what was given back is free for another sequence
    other = alloc.alloc(NB_WINDOW - 1 - 3)
    assert set(alloc.window_of[other]) == set(range(1, NB_WINDOW)) - set(alloc.window_of[pages.ids])


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_packed_and_whole_prefill_of_prompts_longer_than_the_window(attn_impl):
    cfg, params = toy(attn_impl)
    alloc = BlockAllocator(NB_FULL, NB_WINDOW)
    a, b = tokens(40, 1), tokens(20, 2)
    want_a, want_b = reference_logits(a), reference_logits(b)
    P = 64
    toks, pos = np.zeros(P, np.int32), np.zeros(P, np.int32)
    seg, slots = np.full(P, -1, np.int32), np.zeros(2 * P, np.int32)
    off, held = 0, []
    for i, seq in enumerate((a, b)):
        ids = np.asarray(alloc.alloc(-(-len(seq) // BS)))
        held.append(ids)
        t = np.arange(len(seq))
        toks[off: off + len(seq)], pos[off: off + len(seq)], seg[off: off + len(seq)] = seq, t, i
        slots[off: off + len(seq)] = ids[t // BS] * BS + t % BS
        slots[P + off: P + off + len(seq)] = alloc.window_of[ids[t // BS]] * BS + t % BS
        off += len(seq)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(seg), jnp.asarray(slots), *caches(cfg),
        jnp.asarray([39, 59, 0], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), want_a[-1], atol=TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), want_b[-1], atol=TOL)
    # the rows went to each group's own blocks: a decode step finds them
    nxt = [int(np.argmax(want_a[-1])), int(np.argmax(want_b[-1]))]
    tables, slot = np.zeros((2, 2 * MAX_BLOCKS), np.int32), []
    for lane, (ids, p) in enumerate(zip(held, (40, 20))):
        ids = np.append(ids, alloc.alloc(1))  # the next token opens a block
        tables[lane, : len(ids)] = ids
        tables[lane, MAX_BLOCKS: MAX_BLOCKS + len(ids)] = alloc.window_of[ids]
        slot.append(ids[p // BS] * BS + p % BS)
    logits, *_ = jax.jit(functools.partial(M.decode, params, cfg))(
        jnp.asarray(nxt, jnp.int32), jnp.asarray([40, 20], jnp.int32), kc, vc,
        jnp.asarray(tables), jnp.asarray(slot, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), reference_logits(a + nxt[:1])[-1], atol=TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), reference_logits(b + nxt[1:])[-1], atol=TOL)
    # one whole prompt, through the flash prefill kernel where one is asked for
    alloc = BlockAllocator(NB_FULL, NB_WINDOW)
    pages = Pages(cfg, alloc)
    pages.cover(40)
    padded = np.zeros(64, np.int32)
    padded[:40] = a
    logits, *_ = jax.jit(functools.partial(M.prefill, params, cfg))(
        jnp.asarray(padded), jnp.int32(40), *caches(cfg), jnp.asarray(pages.table(8)))
    np.testing.assert_allclose(np.asarray(logits), want_a[-1], atol=TOL)


# ------------------------------------------------------ through the runner


def served_error(seq, rows, top_ids, top_lps) -> float:
    want = reference_logits(seq)
    served, reference, stds = [], [], []
    for r, ids, lps in zip(rows, top_ids, top_lps):
        ids = np.asarray(ids, np.int64)
        served.append([float(x) for x in lps])
        reference.append([float(x) for x in want[r, ids]])
        stds.append(float(np.std(want[r])))
    return logit_error(served, reference, stds)["rms_rel"]


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_mixed_steps_and_decode_multi_through_the_runner(attn_impl):
    """The runner builds both groups' tables from the allocator's companions
    (`_table`, `window_of`): a long prompt rides three mixed steps beside a
    decoding lane, then both decode through horizons of four while their
    window blocks go back between the calls; the top log-probs of every
    position are the reference's logits."""
    cfg, params = toy(attn_impl)
    alloc = BlockAllocator(NB_FULL, NB_WINDOW)
    runner = ModelRunner(
        cfg, params, num_blocks=NB_FULL, window_blocks=NB_WINDOW, block_size=BS, max_batch=4,
        max_model_len=MAX_BLOCKS * BS, kv_dtype=jnp.float32, attn_impl=attn_impl,
        prefill_chunk_tokens=16,
    )
    runner.window_of = alloc.window_of
    assert runner.table_width == 2 * MAX_BLOCKS and len(runner.page_groups) == 2
    assert runner.k_cache[0].shape[1] == NB_WINDOW and runner.k_cache[3].shape[1] == NB_FULL
    short, long = tokens(14, 3), tokens(44, 4)
    B = 4
    lanes = {0: (short, Pages(cfg, alloc)), 2: (long, Pages(cfg, alloc))}
    # the short prompt: a packed prefill; its top log-probs predict token 14
    lanes[0][1].cover(14)
    neutral = (0.0, 1.0, 0, 1.0, np.zeros(2, np.uint32), np.full(4, -1, np.int32), False)
    packed = runner.pack_prefill([(short, lanes[0][1].ids, *neutral)])
    assert packed["slot_indices"].shape == (2 * 16,)
    out = runner.fetch_sample(runner.prefill_packed_arrays(**packed))
    rows_short, ids_short, lps_short = [13], [out[2][0]], [out[3][0]]
    short.append(int(out[0][0]))
    # the long prompt: three mixed steps of one chunk beside the decoding lane
    lanes[2][1].cover(44)
    def lane_arrays(feed):
        tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        tabs, slots = np.zeros((B, runner.table_width), np.int32), np.zeros(B, np.int32)
        for lane, p in feed.items():
            seq, pages = lanes[lane]
            pages.cover(p + 1)
            runner.k_cache, runner.v_cache = pages.give_back(runner.k_cache, runner.v_cache, p)
            tok[lane], pos[lane] = seq[p], p
            tabs[lane] = pages.table()
            slots[lane] = pages.ids[p // BS] * BS + p % BS
        return tok, pos, tabs, slots
    temps, top_ps, top_ks = np.zeros(B, np.float32), np.ones(B, np.float32), np.zeros(B, np.int32)
    keys = np.zeros((B, 2), np.uint32)
    for start in (0, 16, 32):
        pages = lanes[2][1]
        runner.k_cache, runner.v_cache = pages.give_back(runner.k_cache, runner.v_cache, start)
        tok, pos, tabs, slots = lane_arrays({0: len(short) - 1})
        chunk = (long[start: start + 16], start, 44, pages.ids, 0.0, 1.0, 0, 1.0, None, None, False)
        chunk_outs, d_out = runner.mixed_step([chunk], tok, pos, tabs, slots, keys, temps, top_ps, top_ks)
        d = runner.fetch_sample(d_out)
        rows_short.append(len(short) - 1), ids_short.append(d[2][0]), lps_short.append(d[3][0])
        short.append(int(d[0][0]))
    c = runner.fetch_sample(chunk_outs[0])
    rows_long, ids_long, lps_long = [43], [c[2]], [c[3]]
    long.append(int(c[0]))
    # horizons of four
    H = 4
    for _ in range(5):
        feed = {0: len(short) - 1, 2: len(long) - 1}
        for lane, p in feed.items():
            lanes[lane][1].cover(p + H)
        tok, pos, tabs, _ = lane_arrays(feed)
        active = np.asarray([True, False, True, False])
        packed = runner.fetch_horizon(runner.decode_multi(
            H, tok, pos, tabs, temps, top_ps, top_ks, keys, active,
            np.full(B, 99, np.int32), np.zeros(B, np.int32), np.full((B, 4), -1, np.int32)))
        K = (packed.shape[-1] - 2) // 2
        for h in range(H):
            for lane, (seq, rows, ids, lps) in {
                0: (short, rows_short, ids_short, lps_short), 2: (long, rows_long, ids_long, lps_long),
            }.items():
                rows.append(len(seq) - 1)
                ids.append(packed[h, lane, 2: 2 + K].astype(np.int64))
                lps.append(packed[h, lane, 2 + K:])
                seq.append(int(packed[h, lane, 0]))
    assert lanes[2][1].released >= 4 and len(long) == 65
    assert served_error(short[:-1], rows_short, ids_short, lps_short) < 1e-4
    assert served_error(long[:-1], rows_long, ids_long, lps_long) < 1e-4
    free_window_blocks_hold_the_sentinel_or_nothing(cfg, alloc, runner.k_cache, runner.v_cache)
    with pytest.raises(ValueError, match="two groups"):
        runner.extract_blocks([1, 2])


# ------------------------------------------------------------ the shares


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips share a layer of 16 experts, two a chip. Each computes its
    held experts' part of the routed sum and the shared expert; the eight
    parts and the shared expert, counted once, are the uncut layer's `f`,
    whose stacks are the shares' side by side."""
    base = dataclasses.replace(M.AfmoeConfig.from_hf_dict(HF), attn_impl="xla", num_experts=2)
    shares = [dataclasses.replace(base, first_held_expert=2 * i) for i in range(8)]
    layers = [M.init_params(c, jax.random.PRNGKey(0), jnp.float32)["layers"][2] for c in shares]
    b = jax.random.normal(jax.random.PRNGKey(5), (24, 64), jnp.float32)
    valid = jnp.arange(24) < 21
    parts = [M.routed_and_shared(b, l, c, valid) for l, c in zip(layers, shares)]
    for _, shared, _ in parts[1:]:
        np.testing.assert_array_equal(np.asarray(shared), np.asarray(parts[0][1]))
    uncut_cfg = dataclasses.replace(base, num_experts=16, first_held_expert=0)
    uncut = dict(layers[0])
    for k in ("wg", "wu", "wd"):
        uncut[k] = jnp.concatenate([l[k] for l in layers], axis=0)
    routed, shared, sizes = M.routed_and_shared(b, uncut, uncut_cfg, valid)
    np.testing.assert_allclose(
        np.asarray(sum(p[0] for p in parts) + parts[0][1]), np.asarray(routed + shared), atol=2e-5)
    # every live token's four assignments fall in exactly one share each
    assert int(sum(p[2].sum() for p in parts)) == int(sizes.sum()) == 21 * 4
    np.testing.assert_array_equal(np.concatenate([np.asarray(p[2]) for p in parts]), np.asarray(sizes))
    assert not np.allclose(np.asarray(parts[0][0]), 0) and np.all(np.asarray(routed)[21:] == 0)


# --------------------------------------------------- allocator and budget


def test_the_allocator_keeps_a_companion_until_the_window_has_left_it():
    a = BlockAllocator(10, 6)
    assert a.free_count == 5 and a.window_in_use == 0
    ids = a.alloc(4)
    assert sorted(a.window_of[ids]) == [1, 2, 3, 4] and a.free_count == 1 and a.window_in_use == 4
    with pytest.raises(OutOfBlocks):
        a.alloc(2)
    given = a.give_back(ids[:2])
    assert list(a.window_of[ids[:2]]) == [0, 0] and a.free_count == 1 and a.window_given_back == 2
    a.free_window(given)  # the dispatch that named them has landed
    assert a.free_count == 3 and a.window_in_use == 2
    other = a.alloc(3)
    assert set(a.window_of[other]) >= set(given)
    a.free(ids)  # the full blocks, and the two companions that were left
    assert a.free_count == min(9 - 3, 5 - 3) and a.window_given_back == 2
    a.free(other)
    assert a.free_count == 5 and not a.window_of.any()
    plain = BlockAllocator(10)
    assert plain.free_count == 9 and plain.window_of.size == 0 and plain.alloc(9) and plain.free_count == 0


def test_the_block_budget_counts_a_block_at_its_groups_rows(monkeypatch):
    from dynamo_tpu.engine.jax_engine import factory

    cfg = M.AfmoeConfig.from_hf_dict(HF)
    full, window = page_groups(cfg)
    assert (full.window, window.window) == (None, WINDOW)
    assert [k.window for k in layer_cache_kinds(cfg)] == [16, 16, 16, None, 16]
    weights = 2 * M.param_count(cfg)
    row = 2 * 2 * 128 * 2  # planes x heads x width x bytes, a layer and token
    # room for every lane at full context: both pools get what they want
    monkeypatch.setattr(factory, "hbm_budget_bytes", lambda: 10 * 2**30)
    blocks, window_blocks = factory.default_block_pools(cfg, 128, 4, block_size=BS)
    assert blocks == 4 * 16 + 64 and window_blocks == 4 * 16 + 64  # the window's cap is the context here
    # short of it: one factor off a batch at three quarters of the context
    monkeypatch.setattr(factory, "hbm_budget_bytes", lambda: int((weights + 2_500_000) / 0.85))
    blocks, window_blocks = factory.default_block_pools(cfg, 128, 4, block_size=BS)
    spent = blocks * 1 * BS * row + window_blocks * 4 * BS * row
    assert 0.95 * 2_500_000 < spent <= 2_500_000
    assert blocks / window_blocks == pytest.approx((4 * 12 + 64) / (4 * 16 + 64), rel=0.05)
    assert factory.default_num_blocks(cfg, 128, 4, block_size=BS) == blocks


@pytest.mark.parametrize("asked,words", [
    ({"quantize": True}, "int8 weights"),
    ({"kv_dtype": "int8"}, "an int8-resident cache"),
    ({"meshed": True}, "mesh"),
    ({"fused_decode": True}, "fused decode"),
])
def test_what_the_family_is_not_served_with_is_refused_in_words(asked, words):
    from dynamo_tpu.engine.jax_engine.factory import refuse_unsupported

    cfg = M.AfmoeConfig.from_hf_dict(HF)
    refuse_unsupported(cfg)
    with pytest.raises(ValueError, match=words) as e:
        refuse_unsupported(cfg, **asked)
    assert "give their blocks back" in str(e.value)


def test_the_config_is_read_by_its_keys_and_what_it_cannot_serve_is_refused(tmp_path, monkeypatch):
    cfg = M.AfmoeConfig.from_hf_dict(HF)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_held_expert) == (4, 16, 4)
    assert cfg.sliding_window == WINDOW and cfg.mup_enabled and not cfg.tie_word_embeddings
    for key, value in (("rope_scaling", {"rope_type": "yarn"}), ("n_group", 2), ("score_func", "softmax"),
                       ("first_held_expert", 14), ("layer_types", ["chunked_attention"] * 5),
                       ("sliding_window", None), ("torch_dtype", "float16")):
        with pytest.raises(ValueError, match=key):
            M.AfmoeConfig.from_hf_dict(dict(HF, **{key: value}))
    os.makedirs(tmp_path / "m")
    with open(tmp_path / "m" / "config.json", "w") as f:
        json.dump(HF, f)
    assert isinstance(config_from_model_dir(str(tmp_path / "m")), M.AfmoeConfig)
    monkeypatch.setenv("DYN_SPEC_K", "2")
    from dynamo_tpu.engine.jax_engine.factory import refuse_unsupported

    with pytest.raises(ValueError, match="speculative decoding"):
        refuse_unsupported(cfg)
    for refused in (M.decode_verify, M.prefill_mm, M.embed_pooled):
        with pytest.raises(NotImplementedError, match="window-and-full attention family"):
            refused()
    with pytest.raises(ValueError, match="window layers that give their pages back"):
        ModelRunner(cfg, None, num_blocks=8, block_size=BS, max_batch=2, max_model_len=64, kv_dtype="int8")


def test_the_catalog_rows_config_whole_and_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Large-Preview")
    whole = M.AfmoeConfig.from_hf_dict(row["config"])
    assert M.param_count(whole) == 398_635_286_016
    assert whole.layers_of("sliding_attention") == 45 and whole.layers_of("full_attention") == 15
    cut = M.AfmoeConfig.from_hf_dict(dict(
        row["config"], num_hidden_layers=5, num_dense_layers=1, layer_types=row["config"]["layer_types"][:5],
        num_experts=32, num_experts_published=256, vocab_size=25024))
    assert M.attention_params(cut) == 62_914_816 and M.routed_expert_params(cut) == 28_311_552
    assert M.param_count(cut) == 4_321_903_872 and M.expert_param_count(cut) == 4 * 32 * 28_311_552


def test_checkpoint_names_round_trip_to_the_seeded_logits(tmp_path):
    """The seeded weights written under the names and layouts of Hugging
    Face's `modeling_afmoe.py` (matrices `[out, in]`, `expert_bias` float32,
    all 16 experts of a layer) load back, the held four alone, to the same
    logits. A synthetic state dict: no published checkpoint is at hand, and
    the loader says so."""
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

    cfg, _ = toy()
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    out: dict[str, np.ndarray] = {}

    def put(name, w, transpose=True):
        w = np.asarray(w, np.float32)
        out[name] = np.ascontiguousarray(w.T if transpose else w)

    names = (("wg", "gate_proj"), ("wu", "up_proj"), ("wd", "down_proj"))
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in (("attn_norm", "input_layernorm"), ("post_attn_norm", "post_attention_layernorm"),
                             ("pre_mlp_norm", "pre_mlp_layernorm"), ("post_mlp_norm", "post_mlp_layernorm")):
            put(f"{p}{theirs}.weight", layer[ours], False)
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("w_gate", "gate_proj"), ("wo", "o_proj")):
            put(f"{p}self_attn.{theirs}.weight", layer[ours])
        put(p + "self_attn.q_norm.weight", layer["q_norm"], False)
        put(p + "self_attn.k_norm.weight", layer["k_norm"], False)
        if cfg.is_moe_layer(i):
            put(p + "mlp.router.gate.weight", layer["router"])
            put(p + "mlp.expert_bias", layer["router_bias"], False)
            for ours, theirs in names:
                put(f"{p}mlp.shared_experts.{theirs}.weight", layer["shared_" + ours])
            for e in range(cfg.router_experts):
                held = e - cfg.first_held_expert
                for ours, theirs in names:
                    w = layer[ours][held] if 0 <= held < cfg.num_experts else jnp.zeros_like(layer[ours][0])
                    put(f"{p}mlp.experts.{e}.{theirs}.weight", w)
        else:
            for ours, theirs in names:
                put(f"{p}mlp.{theirs}.weight", layer[ours])
    put("model.embed_tokens.weight", params["embed"], False)
    put("model.norm.weight", params["final_norm"], False)
    put("lm_head.weight", params["lm_head"])
    os.makedirs(tmp_path / "m")
    with open(tmp_path / "m" / "config.json", "w") as f:
        json.dump(HF, f)
    save_file(out, str(tmp_path / "m" / "model.safetensors"))
    loaded = load_or_init_params(str(tmp_path / "m"), cfg, dtype=jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    assert loaded["layers"][1]["wg"].shape == (4, 64, 32) and loaded["layers"][1]["router_bias"].dtype == jnp.float32
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="int8 weights"):
        load_or_init_params(str(tmp_path / "m"), cfg, quantize=True)


# ------------------------------------------------------------- the engine


def write_model_dir(path, hf=HF) -> str:
    from tests.test_hybrid_ssm import write_model_dir as write

    return write(path, hf)


async def build(tmp_path, monkeypatch, **kw):
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine

    monkeypatch.setenv("DYN_DECODE_HORIZON", "4")
    engine, _ = await build_jax_engine(
        write_model_dir(tmp_path), name="t", kv_block_size=BS, max_batch=4,
        **{"num_blocks": 64, **kw},
    )
    assert isinstance(engine.runner.config, M.AfmoeConfig)
    return engine


def watch_the_window_pool(engine):
    """Fill every window block with the sentinel when it goes back to the
    pool, forget it when it is handed out again, and hold every block that is
    free in between to the sentinel: nothing reads it into a result that
    stays the same, and nothing writes it. Returns what it saw."""
    alloc, runner = engine.allocator, engine.runner
    cfg = runner.config
    seen = {"free": set(), "given_back": [], "handed_out_again": set(), "checks": 0}
    free_window, alloc_blocks = alloc.free_window, alloc.alloc

    def layers():
        return [i for i in range(cfg.num_layers) if cfg.is_window_layer(i)]

    def check():
        if not seen["free"]:
            return
        ids = jnp.asarray(sorted(seen["free"]))
        for i in layers():
            for plane in (runner.k_cache[i], runner.v_cache[i]):
                assert bool(jnp.all(plane[:, ids] == SENTINEL)), f"a free window block of layer {i} was written"
        seen["checks"] += 1

    def freeing(blocks):
        check()
        if blocks:
            ids = jnp.asarray(blocks)
            fill = lambda c: tuple(
                x.at[:, ids].set(SENTINEL) if i in layers() else x for i, x in enumerate(c))
            runner.k_cache, runner.v_cache = fill(runner.k_cache), fill(runner.v_cache)
            seen["free"] |= set(blocks)
            seen["given_back"] += list(blocks)
        free_window(blocks)

    def allocating(n):
        out = alloc_blocks(n)
        again = set(int(b) for b in alloc.window_of[out]) & seen["free"]
        seen["handed_out_again"] |= again
        seen["free"] -= again
        return out

    alloc.free_window, alloc.alloc = freeing, allocating
    seen["check"] = check
    return seen


async def test_through_the_engine_a_given_back_block_is_free_unread_and_unwritten(tmp_path, monkeypatch):
    """`build_jax_engine` on an `afmoe` directory: the same engine, programs
    and cache manager, a launch ahead in flight (horizon 4). Three prompts,
    one of them chunked beside the others' decoding at an 8-token step
    budget, decode to several windows' length. Window blocks go back in
    decode and inside the chunked prefill, are handed to other sequences, and
    stay at the sentinel while they are free; the tokens are those of an
    engine that gives nothing back; the ledger's `pool` group counts rows,
    blocks and lanes; no block hash is published; transfer is refused."""
    from tests.test_colocated_disagg import collect_tokens

    monkeypatch.setenv("DYN_PREFILL_CHUNK_TOKENS", "8")
    prompts = [tokens(9, 5), tokens(52, 6), tokens(21, 7)]
    lengths = [60, 40, 50]

    async def serve(engine):
        return await asyncio.gather(*(
            collect_tokens(engine, p, n) for p, n in zip(prompts, lengths)))

    plain = await build(tmp_path / "plain", monkeypatch)
    try:
        plain._give_back_window = lambda: None
        want = await serve(plain)
        assert plain.allocator.window_given_back == 0
    finally:
        await plain.close()
    engine = await build(tmp_path / "pool", monkeypatch)
    assert engine.runner.window_blocks == 64 and engine._window == WINDOW
    stored = []
    engine.on_blocks_stored = stored.extend
    seen = watch_the_window_pool(engine)
    try:
        got = await serve(engine)
        again = await serve(engine)
        seen["check"]()
        assert got == want == again and [len(t) for t in got] == lengths
        alloc = engine.allocator
        # 52 + 40 tokens are 12 blocks, of which the window keeps 3 at most
        assert alloc.window_given_back >= 2 * (5 + 9 + 5) and seen["checks"] > 10
        assert seen["handed_out_again"], "no given-back block was handed out again"
        assert alloc.free_count == 63 and alloc.window_in_use == 0 and not alloc.window_of.any()
        summary = engine.stats.goodput.summary()
        pool, launch = summary["pool"], summary["launch"]
        assert launch["chained"] > 0, "no dispatch was launched ahead"
        assert pool["window_blocks_given_back"] == alloc.window_given_back
        assert 0 < pool["window_rows"] < pool["full_rows"]
        assert pool["window_rows"] <= WINDOW * pool["lane_steps"]
        past = pool["window_blocks_past"] / pool["lanes_past_window"]
        assert WINDOW / BS <= past <= WINDOW / BS + 2.5, past  # the window and the launch ahead's slack
        assert pool["window_in_use_steps"] < pool["full_in_use_steps"] <= pool["full_capacity_steps"]
        assert summary["moe"]["assignments_made"] > summary["moe"]["assignments"] > 0
        bodies = {k: v["layer_bodies"] for k, v in summary["first_dispatch_by_label"].items()}
        assert all(v <= 8 for v in bodies.values()), bodies
        assert stored == []
        for wire in ("remote_prefill_client", "peer_block_client"):
            with pytest.raises(ValueError, match="two groups"):
                setattr(engine, wire, object())
            setattr(engine, wire, None)
    finally:
        await engine.close()


async def test_a_preempted_sequence_replays_from_zero_to_the_same_greedy_tokens(tmp_path, monkeypatch):
    from tests.test_colocated_disagg import collect_tokens

    engine = await build(tmp_path, monkeypatch)
    try:
        prompt = tokens(14, 8)
        undisturbed = await collect_tokens(engine, prompt, 40)

        async def preempt_once():
            while True:
                await asyncio.sleep(0.001)
                for seq in list(engine.slots):
                    if seq is not None and 24 <= seq.num_generated <= 32 and not seq.prefilling:
                        async with engine._device_lock:
                            if seq.slot is not None:
                                assert seq.window_released > 0
                                engine._preempt_seq(seq)
                                assert seq.window_released == 0 and not seq.block_ids
                                return

        task = asyncio.ensure_future(preempt_once())
        replayed = await collect_tokens(engine, prompt, 40)
        await task
        assert replayed == undisturbed and len(replayed) == 40
        assert engine.allocator.free_count == 63 and engine.allocator.window_in_use == 0
    finally:
        await engine.close()


def test_run_http_jax_streams_exact_token_counts(tmp_path):
    """`python -m dynamo_tpu.run in=http out=jax` on the toy directory, no
    option, variable or model name beyond what every model gets: streamed
    completions of exactly the tokens asked for, several windows long, and
    `/debug/goodput` with the `pool` and the `moe` groups."""
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
        for n_out in (5, 57):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
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
        assert ledger["pool"]["window_blocks_given_back"] >= 3 and ledger["pool"]["decode_steps"] > 0
        assert ledger["moe"]["layer_steps"] > 0 and ledger["moe"]["assignments_made"] > 0
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        assert "dyn_llm_pool_window_rows_total" in text or "dyn_llm_pool_window_rows" in text
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()
