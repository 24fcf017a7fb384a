"""What a dispatch produced for a sequence travels as one item (PR 39).

The engine gathers a sequence's tokens of one dispatch into one
`LLMEngineOutput` and the frontend sends it as one frame. Held here on the
CPU: a toy engine at horizons 1 and 4 streams the same tokens and log-probs,
which are the parent tree's (written below, read from the parent commit by
`recorded`); at a horizon of 4 an item carries the dispatch's four tokens; a
finish never overtakes a token; a four-token item is one frame whose arrays
stay aligned, whatever the request asks for; and the inter-token histograms
count the streamed tokens less one a stream.
"""

import asyncio
import functools
import time

import pytest

from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.testing import faults

SAMPLING = {
    "greedy": lambda seed: SamplingOptions(
        greedy=True, logprobs=True, top_logprobs=3
    ),
    "seeded": lambda seed: SamplingOptions(
        temperature=0.9, seed=seed, logprobs=True, top_logprobs=3
    ),
}
PROMPTS = (([1, 2, 3], 14, 7), ([9, 8, 7, 6, 5], 11, 77))


def make_engine(horizon, num_blocks=64):
    """The two-layer float32 toy of `tests/test_layer_bodies.py` (there the
    CPU's compiler gives both trees one program, bit for bit)."""
    from tests.test_layer_bodies import make_engine as toy

    return toy(num_blocks=num_blocks, decode_horizon=horizon)


def request(prompt, max_tokens, sampling, priority=None, **stop):
    stop.setdefault("ignore_eos", True)
    eos = stop.pop("eos", [])
    return PreprocessedRequest(
        token_ids=prompt, sampling=sampling,
        stop=StopConditions(max_tokens=max_tokens, **stop),
        extra={"priority": priority} if priority else {},
        eos_token_ids=eos,
    )


async def items_of(engine, req, ctx=None, on_item=None):
    """A stream's items, each with the moment it arrived."""
    got = []
    async for out in engine.generate(req, ctx or Context()):
        got.append((time.monotonic(), out))
        if on_item is not None:
            on_item(got)
    return got


def tokens_of(items):
    return [t for _, o in items for t in o.token_ids]


async def _streamed(sampling, horizon):
    engine = make_engine(horizon)
    try:
        streams = await asyncio.gather(*(
            items_of(engine, request(p, n, SAMPLING[sampling](seed)))
            for p, n, seed in PROMPTS
        ))
        stats = engine.stats
        return {
            "streams": streams,
            "stream_items": getattr(stats, "stream_items", None),
            "generated_tokens": stats.generated_tokens,
            "ledger": stats.goodput.summary(),
            "inter_token": stats.phase_histograms.get("inter_token"),
        }
    finally:
        await engine.close()


@functools.lru_cache(maxsize=None)
def streamed(sampling, horizon):
    return asyncio.run(_streamed(sampling, horizon))


def recorded(sampling, horizon):
    """A run's tokens, log-probs and top ids, stream by stream: what
    `PARENT` holds, written by this function on the parent commit."""
    out = []
    for items in streamed(sampling, horizon)["streams"]:
        outs = [o for _, o in items]
        out.append({
            "tokens": [t for o in outs for t in o.token_ids],
            "log_probs": [
                round(lp, 4) for o in outs for lp in (o.log_probs or [])
            ],
            "top_ids": [
                [int(t) for t, _ in top]
                for o in outs for top in (o.top_logprobs or [])
            ],
        })
    return out


# read from the parent commit (b0dd559, one item a token), where a horizon of
# 1 and a horizon of 4 gave the same record
PARENT = {
    "greedy": [
        {
            "tokens": [19, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33],
            "log_probs": [-2.1702, -1.7078, -2.1405, -1.7496, -1.8755, -1.7706, -1.6, -1.4099, -1.4828, -1.529, -1.6978, -1.7601, -1.8452, -2.1993],
            "top_ids": [[19, 18, 61], [33, 21, 34], [33, 52, 34], [33, 52, 11], [33, 52, 11], [33, 55, 52], [33, 55, 11], [33, 11, 55], [33, 11, 52], [33, 11, 52], [33, 11, 39], [33, 11, 55], [33, 39, 11], [33, 39, 16]],
        },
        {
            "tokens": [12, 12, 38, 38, 38, 13, 38, 38, 38, 48, 48],
            "log_probs": [-1.8846, -1.8909, -1.9937, -1.9958, -2.2898, -2.1407, -1.7032, -2.1082, -2.1839, -2.2716, -2.3098],
            "top_ids": [[12, 55, 54], [12, 38, 55], [38, 12, 55], [38, 48, 13], [38, 13, 33], [13, 38, 48], [38, 48, 33], [38, 13, 48], [38, 13, 48], [48, 38, 13], [48, 38, 59]],
        },
    ],
    "seeded": [
        {
            "tokens": [37, 32, 42, 28, 0, 13, 9, 57, 11, 52, 30, 27, 32, 18],
            "log_probs": [-3.25, -3.3104, -5.0589, -2.0496, -4.6351, -3.6121, -5.9085, -2.7016, -2.3851, -3.6143, -2.7957, -3.6105, -4.4914, -3.6735],
            "top_ids": [[19, 18, 61], [52, 21, 28], [21, 34, 33], [55, 28, 33], [11, 48, 21], [30, 11, 33], [16, 52, 23], [57, 23, 52], [33, 11, 53], [11, 21, 34], [30, 21, 23], [21, 10, 14], [23, 52, 6], [11, 10, 23]],
        },
        {
            "tokens": [12, 12, 43, 26, 8, 51, 22, 2, 38, 16, 24],
            "log_probs": [-1.8846, -1.8909, -3.9471, -3.5076, -3.1594, -2.75, -4.6409, -4.7046, -1.8395, -3.8067, -5.522],
            "top_ids": [[12, 55, 54], [12, 38, 55], [38, 12, 55], [38, 18, 12], [38, 51, 33], [38, 33, 40], [51, 41, 33], [38, 33, 54], [38, 13, 21], [48, 38, 13], [39, 6, 7]],
        },
    ],
}


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
@pytest.mark.parametrize("what", ["tokens", "log_probs", "top_ids"])
def test_both_horizons_stream_what_the_parent_streamed(sampling, what):
    for horizon in (1, 4):
        got = recorded(sampling, horizon)
        for stream, parent, (_, n, _) in zip(got, PARENT[sampling], PROMPTS):
            assert len(stream[what]) == n
            if what == "log_probs":
                assert stream[what] == pytest.approx(parent[what], abs=2e-4)
            else:
                assert stream[what] == parent[what]


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_an_item_carries_what_its_dispatch_gave(sampling):
    """At a horizon of 4 a stream is its prefill's token, then four tokens an
    item, then what the budget left; an item's lists are aligned position for
    position and the finish is an item of its own. At a horizon of 1 every
    item is one token."""
    for horizon, want in ((4, ([1, 4, 4, 4, 1, 0], [1, 4, 4, 2, 0])),
                          (1, ([1] * 14 + [0], [1] * 11 + [0]))):
        run = streamed(sampling, horizon)
        for items, sizes in zip(run["streams"], want):
            outs = [o for _, o in items]
            assert [len(o.token_ids) for o in outs] == sizes
            for o in outs[:-1]:
                assert o.finish_reason is None
                assert len(o.log_probs) == len(o.token_ids) == len(o.top_logprobs)
                assert all(len(top) == 3 for top in o.top_logprobs)
            assert outs[-1].finish_reason is FinishReason.LENGTH
            assert outs[-1].log_probs is None


@pytest.mark.parametrize("horizon,tokens_an_item", [(1, 1.0), (4, 25 / 9)])
def test_stream_items_count_beside_generated_tokens(horizon, tokens_an_item):
    """`stream_items` in the engine's stats and the ledger's `stream` slot
    (`/debug/goodput`, `dyn_llm_stream_*_total`) count at one place, so
    tokens an item is two reads: 1.0 at a horizon of 1."""
    from prometheus_client import generate_latest

    from dynamo_tpu.http.metrics import ServiceMetrics
    from dynamo_tpu.telemetry.goodput import GoodputStats

    run = streamed("greedy", horizon)
    counted = run["ledger"]["stream"]
    assert counted["tokens"] == run["generated_tokens"] == 25
    assert counted["items"] == run["stream_items"]
    assert counted["tokens"] / counted["items"] == pytest.approx(tokens_an_item)
    ledger = GoodputStats()
    ledger.stream = dict(counted)
    assert GoodputStats.from_dict(ledger.to_dict()).summary()["stream"] == counted
    metrics = ServiceMetrics()
    metrics.attach_goodput({"goodput": ledger}, None)
    text = generate_latest(metrics.registry).decode()
    for name, value in counted.items():
        assert f"dyn_llm_stream_{name}_total {float(value)}" in text


@pytest.mark.parametrize("horizon", [1, 4])
def test_the_engines_inter_token_histogram_counts_tokens_less_one(horizon):
    """`_observe_stream`: an item of n tokens is n gaps of its arrival gap
    over n, so the count is the streamed tokens less one a stream and the sum
    the last arrival less the first."""
    run = streamed("greedy", horizon)
    hist = run["inter_token"]
    assert hist.count == 25 - len(PROMPTS)
    spans = sum(
        [t for t, o in items if o.token_ids][-1] - items[0][0]
        for items in run["streams"]
    )
    assert hist.sum_ms == pytest.approx(spans * 1e3, abs=20.0)


# ------------------------------------------- a finish never overtakes a token


async def _reference():
    engine = make_engine(1)
    try:
        items = await items_of(engine, request([1, 2, 3], 24, SAMPLING["greedy"](0)))
        return tokens_of(items)
    finally:
        await engine.close()


@functools.lru_cache(maxsize=None)
def reference():
    """The undisturbed greedy stream of [1, 2, 3] at a horizon of 1."""
    return asyncio.run(_reference())


async def _length_at_step_2_of_4(engine):
    items = await items_of(engine, request([1, 2, 3], 7, SAMPLING["greedy"](0)))
    return items, reference()[:7], FinishReason.LENGTH, [1, 4, 2, 0]


async def _eos_after_min_tokens(engine):
    # the device masks the stop id until six tokens are out: it is this
    # stream's second token, so without the mask the stream would end at one
    eos = reference()[1]
    items = await items_of(engine, request(
        [1, 2, 3], 12, SAMPLING["greedy"](0), ignore_eos=False, min_tokens=6,
        eos=[eos],
    ))
    toks = tokens_of(items)
    assert len(toks) >= 6
    return items, toks, items[-1][1].finish_reason, None


async def _eos_past_the_device_mask(engine):
    # five stop ids where the device masks four: the fifth is sampled with
    # min_tokens unmet, dropped and redrawn, and after five drops the stream
    # ends; what was streamed before stays first
    ref = reference()
    eos = ref[1]
    fillers = [t for t in range(64) if t < eos and t not in ref][:4]
    assert len(fillers) == 4
    items = await items_of(engine, request(
        [1, 2, 3], 12, SAMPLING["greedy"](0), ignore_eos=False, min_tokens=10,
        eos=fillers + [eos],
    ))
    return items, ref[:1], FinishReason.EOS, None


async def _cancelled_between_dispatches(engine):
    ctx = Context()

    def stop(got):
        if len(tokens_of(got)) >= 5:
            ctx.stop_generating()

    items = await items_of(
        engine, request([1, 2, 3], 24, SAMPLING["greedy"](0)), ctx, stop
    )
    toks = tokens_of(items)
    assert 5 <= len(toks) < 24
    return items, reference()[:len(toks)], FinishReason.CANCELLED, None


async def _preempted_between_dispatches(engine):
    async def preempt_once():
        while True:
            await asyncio.sleep(0.001)
            for seq in list(engine.slots):
                if seq is not None and seq.num_generated >= 5:
                    async with engine._device_lock:
                        if seq.slot is not None:
                            engine._preempt_seq(seq)
                            return

    task = asyncio.ensure_future(preempt_once())
    items = await items_of(engine, request([1, 2, 3], 24, SAMPLING["greedy"](0)))
    await task
    assert sum(engine.stats.preemptions_by_class.values()) == 1
    return items, reference(), FinishReason.LENGTH, None


async def _preempted_inside_a_replay(engine):
    # 9 usable blocks where each of two sequences wants 7: the bulk one is
    # preempted by the other's block growth while a dispatch is replayed,
    # with tokens of that dispatch still pending
    tight = make_engine(4, num_blocks=10)
    try:
        bulk, _ = await asyncio.wait_for(asyncio.gather(
            items_of(tight, request([1, 2, 3], 24, SAMPLING["greedy"](0), "bulk")),
            items_of(tight, request([40, 41, 42, 43], 20, SAMPLING["greedy"](0), "interactive")),
        ), timeout=120)
        assert tight.stats.preemptions_by_class.get("bulk", 0) >= 1
        return bulk, reference(), FinishReason.LENGTH, None
    finally:
        await tight.close()


async def _aborted_by_an_injected_fault(engine):
    faults.set_injector(
        faults.FaultInjector(faults.FaultSpec(abort_after_tokens=7))
    )
    try:
        items = await items_of(
            engine, request([1, 2, 3], 24, SAMPLING["greedy"](0))
        )
    finally:
        faults.set_injector(None)
    final = items[-1][1]
    assert final.error["code"] == "injected_fault"
    # the fault fires on the seventh token, before it is appended
    return items, reference()[:6], FinishReason.ERROR, [1, 4, 1, 0]


FINISHES = {
    f.__name__.lstrip("_"): f for f in (
        _length_at_step_2_of_4, _eos_after_min_tokens,
        _eos_past_the_device_mask, _cancelled_between_dispatches,
        _preempted_between_dispatches, _preempted_inside_a_replay,
        _aborted_by_an_injected_fault,
    )
}


@pytest.mark.parametrize("case", sorted(FINISHES))
def test_a_finish_never_overtakes_a_token(case):
    reference()  # an engine run of its own: not from inside this one's loop

    async def run():
        engine = make_engine(4)
        try:
            got = await FINISHES[case](engine)
            return got, engine.stats.goodput.summary()["stream"]
        finally:
            await engine.close()

    (items, want, reason, sizes), counted = asyncio.run(run())
    outs = [o for _, o in items]
    assert tokens_of(items) == want
    # the finish is the last item and carries no token; no token item
    # follows it and none is left behind in the engine
    assert outs[-1].finish_reason is reason and not outs[-1].token_ids
    assert all(o.finish_reason is None and o.token_ids for o in outs[:-1])
    if sizes is not None:
        assert [len(o.token_ids) for o in outs] == sizes
    if case != "preempted_inside_a_replay":  # that engine is its own
        assert counted["tokens"] == len(want)


# ------------------------------------------------ the frontend: one frame an item

WORDS = "the quick brown fox jumps over lazy dog a".split()


def execution(per_item):
    """A `ModelExecution` over an engine that streams `WORDS` with log-probs
    and two alternatives a token, one token in its first item and then
    `per_item` tokens an item, and a finish of its own."""
    from dynamo_tpu.http.service import ModelExecution
    from tests.util import make_test_mdc, make_test_tokenizer

    tok = make_test_tokenizer()
    ids = [tok.token_to_id(w) for w in WORDS]
    other = tok.token_to_id("hello")

    async def engine_fn(req, ctx):
        shift = req.sampling.seed or 0  # n > 1: the choices differ
        rolled = ids[shift:] + ids[:shift]
        cuts = [0, 1] + list(range(1 + per_item, len(ids), per_item)) + [len(ids)]
        for a, b in zip(cuts, cuts[1:]):
            part = rolled[a:b]
            yield LLMEngineOutput(
                token_ids=part,
                log_probs=[-0.25 * (a + j + 1) for j in range(len(part))],
                top_logprobs=[
                    [[t, -0.25 * (a + j + 1)], [other, -9.0]]
                    for j, t in enumerate(part)
                ],
            )
            await asyncio.sleep(0)
        yield LLMEngineOutput.final(FinishReason.LENGTH)

    return ModelExecution(make_test_mdc("items"), engine_fn)


async def frames_of(per_item, kind, **asked):
    from dynamo_tpu.http.metrics import ServiceMetrics, TokenTimer
    from dynamo_tpu.protocols.openai import (
        ChatCompletionRequest,
        CompletionRequest,
    )

    ex = execution(per_item)
    timer = TokenTimer(ServiceMetrics(), "items")
    common = dict(
        model="items", stream=True, max_tokens=len(WORDS),
        stream_options={"include_usage": True}, **asked,
    )
    if kind == "chat":
        req = ChatCompletionRequest(
            messages=[{"role": "user", "content": "one two three"}],
            logprobs=True, top_logprobs=2, **common,
        )
        stream = ex.chat_stream(req, Context(), timer)
    else:
        req = CompletionRequest(prompt="one two three", logprobs=2, **common)
        stream = ex.completion_stream(req, Context(), timer)
    frames = [a.data async for a in stream if a.data is not None]
    return frames, timer


def summed(frames, kind):
    """What a client adds up from a stream's frames, choice by choice."""
    out = {"usage": None, "choices": {}}
    for f in frames:
        if f.get("usage"):
            out["usage"] = f["usage"]["completion_tokens"]
        for c in f.get("choices", []):
            got = out["choices"].setdefault(c["index"], {
                "text": "", "finish": None, "tokens": [], "token_logprobs": [],
                "top_logprobs": [], "text_offset": [], "tool_calls": [],
            })
            if kind == "chat":
                delta = c.get("delta", {})
                got["text"] += delta.get("content") or ""
                got["tool_calls"] += delta.get("tool_calls") or []
                for e in (c.get("logprobs") or {}).get("content") or []:
                    got["tokens"].append(e["token"])
                    got["token_logprobs"].append(e["logprob"])
                    got["top_logprobs"].append(
                        {t["token"]: t["logprob"] for t in e["top_logprobs"]}
                    )
            else:
                got["text"] += c.get("text") or ""
                for key, values in (c.get("logprobs") or {}).items():
                    got[key] += values
            got["finish"] = c.get("finish_reason") or got["finish"]
    return out


@pytest.mark.parametrize("kind", ["completion", "chat"])
def test_a_four_token_item_is_one_frame_with_aligned_arrays(kind):
    frames, timer = asyncio.run(frames_of(4, kind))
    carrying = [
        f["choices"][0] for f in frames
        if f.get("choices") and f["choices"][0].get("logprobs")
    ]
    if kind == "chat":
        sizes = [len(c["logprobs"]["content"]) for c in carrying]
    else:
        sizes = [len(c["logprobs"]["tokens"]) for c in carrying]
        for c in carrying:
            lp = c["logprobs"]
            assert len(lp["token_logprobs"]) == len(lp["top_logprobs"]) == len(
                lp["text_offset"]) == len(lp["tokens"])
            assert all(len(top) == 2 for top in lp["top_logprobs"])
    assert sizes == [1, 4, 4]
    got = summed(frames, kind)
    choice = got["choices"][0]
    assert choice["text"].split() == WORDS and choice["finish"] == "length"
    assert [t.strip() for t in choice["tokens"]] == WORDS
    assert choice["token_logprobs"] == [-0.25 * (i + 1) for i in range(9)]
    assert got["usage"] == 9
    if kind == "completion":
        # offsets continue across frames: each is the text before its token
        at = 0
        for token, offset in zip(choice["tokens"], choice["text_offset"]):
            assert offset == at
            at += len(token)
    # the timer saw three chunks of nine tokens
    hist = timer.metrics.phase_hist_for("items").get("inter_token")
    assert hist.count == 8


ASKED = {
    "plain": ("completion", {}),
    "echo": ("completion", {"echo": True}),
    "two_choices": ("completion", {"n": 2, "seed": 1}),
    "stop_inside_an_item": ("completion", {"stop": ["fox"]}),
    "stop_across_items": ("completion", {"stop": ["jumps over"]}),
    "chat": ("chat", {}),
    "chat_two_choices": ("chat", {"n": 2, "seed": 1}),
    "chat_stop_inside_an_item": ("chat", {"stop": ["fox"]}),
    "chat_tools_buffered": ("chat", {"tools": [{
        "type": "function",
        "function": {"name": "f", "parameters": {"type": "object"}},
    }]}),
}


@pytest.mark.parametrize("asked", sorted(ASKED))
def test_the_framing_changes_nothing_a_client_adds_up(asked):
    """Text, log-prob arrays, offsets, finish reasons and `usage` are the
    same whether the engine sends one token an item or four: a stop sequence
    that ends inside an item drops the item's later tokens, and `usage`
    counts what was emitted."""
    kind, extra = ASKED[asked]
    one = summed(asyncio.run(frames_of(1, kind, **extra))[0], kind)
    four = summed(asyncio.run(frames_of(4, kind, **extra))[0], kind)
    assert four == one
    assert len(one["choices"]) == extra.get("n", 1)
    first = one["choices"][0]
    if "stop" in extra:
        assert first["finish"] == "stop"
        assert extra["stop"][0] not in first["text"]
        assert one["usage"] < 9 and len(first["tokens"]) == one["usage"]
    else:
        assert first["finish"] == "length"
        assert one["usage"] == 9 * extra.get("n", 1)
    if extra.get("echo"):
        assert first["text"].startswith("one two three")
        assert first["text_offset"][0] == len("one two three")


# ------------------------------------------------------------- the timers


@pytest.mark.parametrize("chunks", [
    [(1.0, 1), (1.4, 4), (1.8, 4), (2.0, 2)],
    [(1.0, 3), (1.3, 4)],
    [(0.5, 1), (0.6, 1), (0.7, 1)],
], ids=["horizon_4", "a_first_chunk_of_three", "horizon_1"])
def test_token_timer_spreads_an_arrival_gap_over_its_tokens(monkeypatch, chunks):
    """Counts are the streamed tokens less one, the sum is the last arrival
    less the first, in our histogram and in Prometheus's; `max_itl_ms` stays
    the largest gap between arrivals."""
    from dynamo_tpu.http import metrics as M

    clock = [0.0]
    monkeypatch.setattr(M.time, "monotonic", lambda: clock[0])
    metrics = M.ServiceMetrics()
    timer = M.TokenTimer(metrics, "m")
    for at, count in chunks:
        clock[0] = at
        timer.on_token(count)
    tokens = sum(n for _, n in chunks)
    span = chunks[-1][0] - chunks[0][0]
    hist = metrics.phase_hist_for("m").get("inter_token")
    assert hist.count == tokens - 1
    assert hist.sum_ms == pytest.approx(span * 1e3)
    samples = {
        s.name: s.value
        for fam in metrics.registry.collect() for s in fam.samples
        if not s.labels.get("le")
    }
    prefix = "dyn_llm_http_service"
    assert samples[f"{prefix}_inter_token_latency_seconds_count"] == tokens - 1
    assert samples[f"{prefix}_inter_token_latency_seconds_sum"] == pytest.approx(span)
    assert samples[f"{prefix}_time_to_first_token_seconds_count"] == 1
    assert samples[f"{prefix}_output_tokens_total"] == tokens
    assert timer.ttft_ms == pytest.approx(chunks[0][0] * 1e3)
    gaps = [b[0] - a[0] for a, b in zip(chunks, chunks[1:])]
    assert timer.max_itl_ms == pytest.approx(max(gaps) * 1e3)


def test_a_weighted_observation_is_n_observations():
    from dynamo_tpu.telemetry.histogram import PhaseHistogram

    weighted, looped = PhaseHistogram(), PhaseHistogram()
    weighted.observe(4.5, 4)
    weighted.observe(-1.0, 2)
    for v in (4.5, 4.5, 4.5, 4.5, -1.0, -1.0):
        looped.observe(v)
    assert weighted.to_dict() == looped.to_dict()
    assert weighted.count == 6 and weighted.sum_ms == pytest.approx(18.0)
