"""Unified mixed prefill+decode device steps (ISSUE 16).

The mixed stepper packs every active decode lane plus up to
``chunk_budget`` prefill-chunk tokens into ONE device program per engine
iteration. These tests pin its acceptance contract on CPU:

  * token identity — streams are bit-identical to the phase-separated
    scheduler, greedy AND seeded-temperature, while prefill and decode
    genuinely overlap (the mixed program must have run);
  * the brownout ``chunk_cap`` rung latches at the NEXT step boundary
    instead of re-slicing work mid-iteration (the satellite bugfix);
  * goodput labels — mixed steps land under their own label with
    prefill-token and decode-lane occupancy split out, and never form a
    phase boundary with themselves.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from tests.test_jax_engine import collect, greedy_request, make_chunked_engine


def _seeded_request(prompt, max_tokens, seed):
    return PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.9, top_k=8, seed=seed),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )


async def _overlapped_run(engine, make_long):
    """A short prompt decodes while a long prompt prefills chunk-by-chunk
    — the workload where the two schedulers take different step shapes."""
    short = asyncio.create_task(
        collect(engine, greedy_request([1, 2, 3], 24))
    )
    await asyncio.sleep(0.05)  # let the short prompt enter decode
    long_prompt = list(np.random.default_rng(1).integers(1, 64, size=40))
    long = asyncio.create_task(collect(engine, make_long(long_prompt)))
    seeded = asyncio.create_task(
        collect(engine, _seeded_request([9, 8, 7], 12, seed=4242))
    )
    out_s = await short
    out_l = await long
    out_t = await seeded
    await engine.close()
    return out_s, out_l, out_t


def test_mixed_step_token_identical_to_phase_separated():
    """Pinned-seed parity: the mixed stepper must produce bit-identical
    token streams to the alternating chunk/decode scheduler for greedy
    and seeded-temperature sampling — AND must actually have run mixed
    programs (a gate that silently falls back would pass vacuously)."""

    def make_long(p):
        return greedy_request(p, 4)

    sep = make_chunked_engine(8, mixed_step=False)
    ref = asyncio.run(_overlapped_run(sep, make_long))

    mixed = make_chunked_engine(8, mixed_step=True)
    mixed_calls = []
    orig = mixed.runner.mixed_step

    def spy(chunks, *a, **k):
        mixed_calls.append(len(chunks))
        return orig(chunks, *a, **k)

    mixed.runner.mixed_step = spy
    gp = mixed.stats.goodput
    got = asyncio.run(_overlapped_run(mixed, make_long))

    for (toks_ref, r_ref), (toks, r) in zip(ref, got):
        assert r == r_ref
        assert toks == toks_ref, "mixed stepper diverged from reference"
    assert mixed_calls, "mixed stepper never engaged"
    assert gp.mixed_steps == len(mixed_calls)
    assert gp.mixed_prefill_tokens > 0
    assert gp.mixed_decode_tokens > 0


def _spy_programs(engine):
    """Record which step programs the packer dispatched: the chunk count
    of every mixed step, and (H, longest lane tail) of every horizon."""
    mixed_calls, horizons = [], []
    orig_mixed = engine.runner.mixed_step
    orig_pick = engine._horizon_for

    def mixed_spy(chunks, *a, **k):
        mixed_calls.append(len(chunks))
        return orig_mixed(chunks, *a, **k)

    def pick_spy(active, **chained):
        H = orig_pick(active, **chained)
        horizons.append(
            (H, max(engine._lane_remaining(s) for s in active))
        )
        return H

    engine.runner.mixed_step = mixed_spy
    engine._horizon_for = pick_spy
    return mixed_calls, horizons


async def _short_tails(engine, make_long):
    """Four lanes admitted together whose budgets leave every lane 2 or 3
    tokens after the first full horizon (and a last token after the
    second): the tails the one H program now serves."""
    reqs = [
        greedy_request([1, 2, 3], 7),
        _seeded_request([4, 5, 6], 8, seed=11),
        greedy_request([7, 8, 9, 10], 8),
        make_long([11, 12, 13]),
    ]
    outs = await asyncio.gather(*(collect(engine, r) for r in reqs))
    await engine.close()
    return outs


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
@pytest.mark.parametrize("workload", ["prefill_mid_decode", "short_tails"])
def test_mixed_step_at_horizon_4_token_identical(workload, sampling):
    """The TPU default: decode horizon 4 WITH mixed steps. A prefill
    arriving mid-decode interleaves mixed steps with decode_multi@H4, and
    a batch whose lanes all have 2-3 tokens left runs the full H program.
    Both must stream exactly what horizon 1 with mixed steps off streams,
    greedy and seeded-temperature — and both programs must have run."""
    run = {
        "prefill_mid_decode": _overlapped_run,
        "short_tails": _short_tails,
    }[workload]

    def make_long(p):
        if sampling == "greedy":
            return greedy_request(p, 7)
        return _seeded_request(p, 7, seed=77)

    ref_engine = make_chunked_engine(8, mixed_step=False, decode_horizon=1)
    ref = asyncio.run(run(ref_engine, make_long))

    engine = make_chunked_engine(8, mixed_step=True, decode_horizon=4)
    mixed_calls, horizons = _spy_programs(engine)
    got = asyncio.run(run(engine, make_long))

    for (toks_ref, r_ref), (toks, r) in zip(ref, got):
        assert r == r_ref
        assert toks == toks_ref, "H=4 + mixed diverged from H=1 unmixed"
    assert {H for H, _ in horizons} <= {1, 4}, horizons
    assert any(H == 4 for H, _ in horizons), "horizon never dispatched"
    if workload == "prefill_mid_decode":
        assert mixed_calls, "mixed stepper never engaged at horizon 4"
    else:
        assert any(H == 4 and 1 < tail < 4 for H, tail in horizons), (
            "no 2..3-token tail reached the H program", horizons
        )


def test_mixed_step_budget_packs_multiple_chunks():
    """chunk_budget=16 with 8-token chunks allows two chunk slots per
    step: the same 40-token prompt finishes in fewer mixed steps, still
    token-identically."""

    async def run(engine):
        short = asyncio.create_task(
            collect(engine, greedy_request([4, 5, 6], 16))
        )
        await asyncio.sleep(0.05)
        long_prompt = list(
            np.random.default_rng(3).integers(1, 64, size=40)
        )
        long = asyncio.create_task(
            collect(engine, greedy_request(long_prompt, 4))
        )
        out = (await short, await long)
        await engine.close()
        return out

    ref = asyncio.run(run(make_chunked_engine(8, mixed_step=False)))
    wide = make_chunked_engine(8, mixed_step=True, chunk_budget=16)
    assert wide._mixed_max_slots == 2
    slots_seen = []
    orig = wide.runner.mixed_step

    def spy(chunks, *a, **k):
        slots_seen.append(len(chunks))
        return orig(chunks, *a, **k)

    wide.runner.mixed_step = spy
    got = asyncio.run(run(wide))
    for (toks_ref, r_ref), (toks, r) in zip(ref, got):
        assert r == r_ref and toks == toks_ref
    assert slots_seen and max(slots_seen) == 2, slots_seen


def test_a_long_prompt_alone_takes_the_mixed_step():
    """No lane is decoding when the long prompt comes: it runs the mixed
    programs it would run on a busy server (so a server's compiled set does
    not depend on what its first long prompt met), never the lone-prompt
    chunk program, and its stream is the phase-separated scheduler's."""

    async def run(engine):
        prompt = list(np.random.default_rng(5).integers(1, 64, size=40))
        out = await collect(engine, greedy_request(prompt, 6))
        await engine.close()
        return out

    ref = asyncio.run(run(make_chunked_engine(8, mixed_step=False)))
    mixed = make_chunked_engine(8, mixed_step=True, chunk_budget=16)
    mixed_calls, _ = _spy_programs(mixed)

    def no_chunk_program(*a, **k):
        raise AssertionError("the lone-prompt chunk program was dispatched")

    mixed.runner.prefill_chunk = no_chunk_program
    got = asyncio.run(run(mixed))
    assert got == ref
    assert mixed_calls == [2, 2, 1]  # 40 tokens: 16 + 16 + 8


async def test_chunk_cap_waits_for_step_boundary():
    """Satellite bugfix: a brownout chunk_cap transition landing
    mid-iteration (after the loop-top latch) must NOT re-slice the chunk
    the iteration already planned — the halved budget applies from the
    next step boundary."""
    engine = make_chunked_engine(8)
    sizes = []
    orig_chunk = engine.runner.prefill_chunk

    def spy(chunk, *a, **k):
        sizes.append(len(chunk))
        return orig_chunk(chunk, *a, **k)

    engine.runner.prefill_chunk = spy
    orig_admit = engine._admit_phase
    fired = False

    async def admit_then_brownout(loop):
        nonlocal fired
        admitted = await orig_admit(loop)
        if engine._prefilling and not fired:
            fired = True
            engine.apply_brownout(3)  # lands after this step's latch
        return admitted

    engine._admit_phase = admit_then_brownout
    long_prompt = list(np.random.default_rng(2).integers(1, 64, size=20))
    toks, reason = await collect(engine, greedy_request(long_prompt, 2))
    await engine.close()
    assert reason is FinishReason.LENGTH and len(toks) == 2
    assert fired
    # iteration that latched BEFORE the transition keeps its full chunk;
    # every later chunk runs at the halved budget
    assert sizes[0] == 8, sizes
    assert sizes[1:] and all(s <= 4 for s in sizes[1:]), sizes


async def test_chunk_cap_latch_mechanism():
    """The latch itself: apply_brownout never touches the in-flight
    step's latched values; _chunk_tokens/_chunk_budget (read at the next
    boundary) are halved, floored at one KV block, and restore."""
    engine = make_chunked_engine(8, mixed_step=True)
    engine._step_chunk_tokens = engine._chunk_tokens()
    engine._step_chunk_budget = engine._chunk_budget()
    full_tokens = engine._step_chunk_tokens
    full_budget = engine._step_chunk_budget
    assert full_tokens == 8 and full_budget == 16
    engine.apply_brownout(3)
    assert engine._step_chunk_tokens == full_tokens
    assert engine._step_chunk_budget == full_budget
    assert engine._chunk_tokens() == max(4, full_tokens // 2)
    assert engine._chunk_budget() == max(4, full_budget // 2)
    engine.apply_brownout(0)
    assert engine._chunk_tokens() == full_tokens
    assert engine._chunk_budget() == full_budget
    await engine.close()


def test_goodput_mixed_labels_and_phase_gap():
    """Ledger semantics for the new label family: mixed_step@cK steps
    split occupancy into prefill tokens and decode lanes, and a
    mixed->mixed boundary never counts toward the phase-gap total while
    prefill<->decode alternation does."""
    from dynamo_tpu.telemetry.goodput import GoodputLedger, step_phase

    assert step_phase("mixed_step@c2") == "mixed"
    assert step_phase("prefill_chunk") == "prefill"
    assert step_phase("decode_multi@H4") == "decode"

    gp = GoodputLedger()
    t = 100.0
    # alternating scheduler: every gap sits at a phase boundary
    for i in range(4):
        gp.record_step("prefill_chunk", 0.010, prefill_tokens=8, t_start=t)
        t += 0.012  # 2 ms gap
        gp.record_step("decode", 0.010, lanes=3, capacity=4, t_start=t)
        t += 0.012
    sep_gap = gp.phase_gap_s_total
    assert sep_gap == pytest.approx(0.002 * 7)
    assert gp.phase_bubble_fraction == pytest.approx(
        sep_gap / (gp.busy_s_total + gp.bubble_s_total)
    )

    gp2 = GoodputLedger()
    t = 100.0
    for i in range(8):
        gp2.record_step(
            "mixed_step@c1", 0.010,
            lanes=3, capacity=4, prefill_tokens=8, t_start=t,
        )
        t += 0.012
    assert gp2.mixed_steps == 8
    assert gp2.mixed_prefill_tokens == 64
    assert gp2.mixed_decode_tokens == 24
    assert gp2.phase_gap_s_total == 0.0
    assert gp2.bubble_s_total == pytest.approx(0.002 * 7)
    assert gp2.phase_bubble_fraction == 0.0

    # summaries carry the new fields through the wire round trip
    from dynamo_tpu.telemetry.goodput import GoodputStats

    back = GoodputStats.from_dict(gp2.to_dict())
    assert back.summary() == gp2.summary()
    assert back.summary()["mixed_steps"] == 8
