"""A step's host inputs reach the device as one packed transfer (PR 42).

`model_runner.pack_inputs` writes every 32-bit or bool host leaf of a call
into one fresh int32 buffer and `unpack_inputs` takes it apart at the top of
the program, so that the impl receives what it received when each array was
committed alone. Held here on the CPU: the round trip is bit for bit over
every kind of leaf a step sends; what is on the device already passes beside
the buffer and is not counted; a toy engine streams, token for token and
log-prob for log-prob, what it streams when every leaf travels alone (the
parent's path, had again by telling `_packs` that nothing packs); and a
dispatch does not see what the engine wrote into its arrays afterwards.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import model_runner as MR
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.pipeline.context import Context
from tests.test_layer_bodies import make_engine

NAN_PAYLOAD = np.array([0x7FC12345, 0xFFC00001, 0x7F800001], np.uint32).view(np.float32)

LEAVES = {
    "int32": np.array([[0, -1, 2**31 - 1], [-(2**31), 7, 8]], np.int32),
    "uint32_high_bit": np.array([[0x80000000, 0xFFFFFFFF], [0x9E3779B9, 1]], np.uint32),
    "float32_specials": np.array([-0.0, 0.0, np.inf, -np.inf, 1e-45, 0.7], np.float32),
    "float32_nan_payload": NAN_PAYLOAD,
    "bool": np.array([True, False, False, True, True]),
    "scalar_int32": np.int32(-5),
    "scalar_float32": np.float32(-0.0),
    "scalar_bool": np.bool_(True),
    "scalar_uint32": np.uint32(0xDEADBEEF),
    "empty": np.zeros((0, 4), np.int32),
    "strided": np.arange(24, dtype=np.int32).reshape(4, 6)[:, ::2],
}


def roundtrip(tree):
    layout, buf, beside = MR.pack_inputs(tree)
    assert buf.dtype == np.int32 and buf.ndim == 1
    hash(layout)  # a static argument of the program
    unpack = jax.jit(
        lambda lay, b, *rest: MR.unpack_inputs(lay, b, rest), static_argnums=0
    )
    return layout, buf, beside, unpack(layout, jnp.asarray(buf), *beside)


def same_bits(host, dev):
    dev = np.asarray(dev)
    assert dev.shape == np.shape(host) and dev.dtype == host.dtype, (dev.dtype, host.dtype)
    assert dev.tobytes() == np.ascontiguousarray(host).tobytes()


@pytest.mark.parametrize("kind", sorted(LEAVES))
def test_a_leaf_comes_back_bit_for_bit(kind):
    """Pack, then unpack under `jax.jit`: the leaf's shape, dtype and every
    bit (a key's high bit, -0.0, an infinity, a NaN's payload), and a bool as
    a bool."""
    leaf = LEAVES[kind]
    _, buf, beside, (out,) = roundtrip((leaf,))
    assert beside == [] and buf.size == leaf.size
    same_bits(leaf, out)


def test_a_nested_tuple_and_a_keyword_tree_come_back_whole():
    """What `_launch` hands over: positional leaves, a tuple of per-chunk
    tuples (`mixed_step`), and a `pen=` keyword tree."""
    chunk = (LEAVES["int32"], np.int32(8), LEAVES["scalar_float32"], np.bool_(False))
    host = (
        (chunk, chunk), LEAVES["uint32_high_bit"], LEAVES["bool"],
        LEAVES["float32_specials"],
    )
    kw = {"pen": (LEAVES["int32"], LEAVES["float32_nan_payload"], LEAVES["scalar_bool"])}
    layout, buf, beside, (out, out_kw) = roundtrip((host, kw))
    assert beside == []
    assert jax.tree.structure((out, out_kw)) == jax.tree.structure((host, kw))
    for a, b in zip(jax.tree.leaves((host, kw)), jax.tree.leaves((out, out_kw))):
        same_bits(a, b)
    assert buf.size == sum(np.size(x) for x in jax.tree.leaves((host, kw)))
    # the same leaves give the same layout, other shapes another one
    assert MR.pack_inputs((host, kw))[0] == layout
    assert MR.pack_inputs((host[1:], kw))[0] != layout


@pytest.mark.parametrize("kind", ["device", "int64", "float64", "int8", "list"])
def test_what_does_not_pack_travels_beside_the_buffer(kind):
    """The rule reads the dtype: a leaf on the device already, or a host
    leaf of another width, keeps its place in the tree and its own way to
    the device."""
    other = {
        "device": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "int64": np.arange(4, dtype=np.int64),
        "float64": np.float64(0.25),
        "int8": np.arange(5, dtype=np.int8),
        "list": [1, 2, 3],
    }[kind]
    layout, buf, beside, out = roundtrip((LEAVES["int32"], other, LEAVES["bool"]))
    assert len(beside) == 1 and beside[0] is other
    assert layout[1][1] is None and buf.size == LEAVES["int32"].size + LEAVES["bool"].size
    same_bits(LEAVES["int32"], out[0])
    same_bits(LEAVES["bool"], out[2])
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(other))


def test_a_device_leaf_is_not_counted():
    """`_commit` counts one array a call, the buffer; `prefill_mm`'s
    embeddings already on the device ride along uncounted, a host leaf of
    another width is one more array."""
    runner = make_engine().runner
    on_device = jnp.ones((3, 8), jnp.float32)
    tokens = np.arange(8, dtype=np.int32)
    runner.launch.clear()
    layout, dev = runner._commit(tokens, on_device, np.float32(0.5), pen=(tokens,))
    assert runner.launch.upload_arrays == 1
    assert runner.launch.upload_bytes == 4 * (8 + 1 + 8)
    assert len(dev) == 2 and dev[1] is on_device
    host, kw = jax.jit(MR.unpack_inputs, static_argnums=0)(layout, dev[0], dev[1:])
    same_bits(tokens, host[0])
    same_bits(tokens, kw["pen"][0])
    assert float(host[2]) == 0.5
    runner.launch.clear()
    runner._commit(tokens, np.arange(3, dtype=np.int8))
    assert runner.launch.upload_arrays == 2


def test_a_commit_does_not_see_what_the_engine_writes_afterwards():
    """The engine keeps its lane arrays and rewrites them between dispatches:
    each commit packs into a buffer of its own, so the first dispatch's
    values are still the first's after the second was packed."""
    runner = make_engine().runner
    tokens = np.arange(16, dtype=np.int32)
    temps = np.full(16, 0.5, np.float32)
    active = np.ones(16, bool)
    lay_a, buf_a, _ = MR.pack_inputs(((tokens, temps, active), {}))
    _, dev_a = runner._commit(tokens, temps, active)
    before = (tokens.copy(), temps.copy(), active.copy())
    tokens += 100
    temps[:] = 2.0
    active[::2] = False
    lay_b, buf_b, _ = MR.pack_inputs(((tokens, temps, active), {}))
    _, dev_b = runner._commit(tokens, temps, active)
    assert lay_a == lay_b
    assert not np.shares_memory(buf_a, buf_b)
    assert not any(np.shares_memory(buf_b, x) for x in (tokens, temps, active))
    unpack = jax.jit(MR.unpack_inputs, static_argnums=0)
    for host, dev in ((before, dev_a), ((tokens, temps, active), dev_b)):
        out, _ = unpack(lay_a, dev[0], ())
        for a, b in zip(host, out):
            same_bits(a, b)


def test_the_lanes_logprob_flags_ride_the_packed_buffer(monkeypatch):
    """The lane array that says who asked for log-probs (PR 53) is one more
    bool leaf of the step's one buffer: still one array a dispatch, every
    leaf of every call bit for bit through `unpack_inputs`, the asking lane
    set in it while its stream lives and no lane after, and the ledger's
    `logprob_dispatches` counts the decode-family calls in which one was."""
    seen, program = [], [None]
    real_pack, real_launch = MR.pack_inputs, MR.ModelRunner._launch

    def launch(self, prog, *host, **kw):
        program[0] = prog
        return real_launch(self, prog, *host, **kw)

    def pack(tree):
        out = real_pack(tree)
        # the engine rewrites its lane arrays between dispatches: keep the values
        held = jax.tree.map(lambda x: x.copy() if isinstance(x, np.ndarray) else x, tree)
        seen.append((program[0], held, out))
        return out

    monkeypatch.setattr(MR.ModelRunner, "_launch", launch)
    monkeypatch.setattr(MR, "pack_inputs", pack)

    async def run():
        engine = make_engine(decode_horizon=4)
        asks = PreprocessedRequest(
            token_ids=[5, 6, 7],
            sampling=SamplingOptions(greedy=True, logprobs=True, top_logprobs=2),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        quiet = PreprocessedRequest(
            token_ids=[9, 8, 7, 6], sampling=SamplingOptions(greedy=True),
            stop=StopConditions(max_tokens=18, ignore_eos=True),
        )
        try:
            out = await asyncio.gather(stream(engine, asks), stream(engine, quiet))
            gp = engine.stats.goodput
            return out, engine.runner, dict(gp.launch), gp.summary()["sampler"]
        finally:
            await engine.close()

    (asked, quiet), runner, launch_counts, sampler = asyncio.run(run())
    assert launch_counts["upload_arrays"] == launch_counts["dispatches"] == len(seen)
    assert len(asked[1]) == len(asked[0]) == 6 and quiet[1] == [] and len(quiet[0]) == 18
    unpack = jax.jit(MR.unpack_inputs, static_argnums=0)
    # where the lanes' flags stand among a decode-family program's arguments
    flags_at = {
        runner._decode_multi_fn: 7, runner._decode_fn: 8, runner._decode_eos_fn: 8,
        **{fn: 9 for fn in runner._mixed_jits.values()},
    }
    taken = []
    for prog, tree, (layout, buf, beside) in seen:
        assert beside == [] or prog is runner._decode_multi_fn  # the carry
        back = unpack(layout, jnp.asarray(buf), tuple(beside))
        for host, dev in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            if isinstance(host, (np.ndarray, np.generic)):
                same_bits(host, dev)
        if prog in flags_at:
            flags = tree[0][flags_at[prog]]
            assert flags.dtype == np.bool_ and flags.shape == (4,)
            taken.append(bool(flags.any()))
    assert runner._decode_multi_fn in {prog for prog, _, _ in seen}
    assert sampler["dispatches"] == len(taken)
    assert sampler["logprob_dispatches"] == sum(taken)
    assert 0 < sum(taken) < len(taken) and not taken[-1]


# ------------------------------------------------------------- the engine


async def stream(engine, req):
    """Tokens, log-probs and top log-probs of one request, in order."""
    toks, lps, tops = [], [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
        lps.extend(out.log_probs or [])
        tops.extend(out.top_logprobs or [])
    return toks, lps, tops


def arrive_during(engine, program: str, nth: int, start) -> None:
    """Call `start()` on the event loop while the engine's `nth` dispatch of
    the runner's `program` is at the runner, and let that dispatch go on only
    once the engine's queue holds the arrival. An arrival tied to a stream's
    first token lands wherever the engine's loop happens to be by then (since
    the launch ahead of PR 45: one dispatch further on a busy machine, which
    moves a mixed step and with it the number of dispatches of the whole
    serving); tied to the engine's own count of dispatches, two servings
    take the same schedule."""
    loop = asyncio.get_running_loop()
    runner = engine.runner
    real = getattr(runner, program)
    calls = 0

    def spy(*args, **kwargs):
        nonlocal calls
        calls += 1
        if calls == nth:
            loop.call_soon_threadsafe(start)
            deadline = time.monotonic() + 30.0
            while not engine.waiting and time.monotonic() < deadline:
                time.sleep(0.001)  # the runner's thread; the loop runs beside it
            assert engine.waiting, "the arrival never reached the engine's queue"
        return real(*args, **kwargs)

    setattr(runner, program, spy)


async def serve(horizon: int):
    """Four requests through the toy engine: a short prompt that keeps its
    EOS masked for `min_tokens` decodes while a 29-token prompt arrives
    (during the engine's second decode dispatch: `arrive_during`) and rides
    mixed steps in 8-token chunks (a lane with penalties is never mixed);
    then one with all three penalties and `min_tokens` beside a seeded one
    with `top_k`. Gives the streams and the ledger."""
    engine = make_engine(decode_horizon=horizon)
    # the serial loop (a dispatch is launched, read and replayed before the
    # next): with PR 45's launch ahead, which steps of the first stream are
    # mixed steps and which a horizon's follows the host's timing, and a
    # mixed step's decode half and the horizon's program round a logit's
    # last bit differently, so two servings agree to the last bit only on
    # one schedule. That the chained engine streams the serial loop's tokens
    # is `tests/test_decode_horizon.py`'s to hold, family by family
    engine.runner.chains_horizons = False
    masked = PreprocessedRequest(
        token_ids=[5, 6, 7, 8, 9],
        sampling=SamplingOptions(greedy=True, logprobs=True, top_logprobs=3),
        stop=StopConditions(max_tokens=24, min_tokens=30),
        eos_token_ids=[33, 21, 16],
    )
    long = PreprocessedRequest(
        token_ids=list(range(1, 30)),
        sampling=SamplingOptions(greedy=True, logprobs=True, top_logprobs=2),
        stop=StopConditions(max_tokens=6, ignore_eos=True),
    )
    pen = PreprocessedRequest(
        token_ids=[3, 1, 4, 1, 5],
        sampling=SamplingOptions(
            greedy=True, frequency_penalty=0.4, presence_penalty=0.2,
            repetition_penalty=1.3, logprobs=True, top_logprobs=3,
        ),
        stop=StopConditions(max_tokens=14, min_tokens=20),
        eos_token_ids=[33, 35],
    )
    seeded = PreprocessedRequest(
        token_ids=[9, 8, 7],
        sampling=SamplingOptions(temperature=0.9, top_k=8, seed=0x9E3779B9, logprobs=True),
        stop=StopConditions(max_tokens=10, ignore_eos=True),
    )
    try:
        arrived: list = []
        arrive_during(
            engine, "decode_multi" if horizon > 1 else "decode", 2,
            lambda: arrived.append(asyncio.ensure_future(stream(engine, long))),
        )
        first = await stream(engine, masked)  # it decodes when the long one arrives
        streams = [await arrived[0], first]
        streams += await asyncio.gather(stream(engine, pen), stream(engine, seeded))
        gp = engine.stats.goodput
        return streams, dict(gp.launch), set(gp.summary()["compile_s_by_label"])
    finally:
        await engine.close()


@pytest.mark.parametrize("horizon", [1, 4])
def test_the_toy_engine_streams_what_separate_transfers_stream(monkeypatch, horizon):
    """One packed transfer a dispatch against the parent's one transfer a
    leaf (nothing packs, so every leaf goes through `_to_dev` alone and the
    program is handed them as they are): the same tokens, log-probs and top
    log-probs to the last bit, through a packed prefill, mixed steps, the
    penalty and EOS-mask programs and the horizon."""
    packed, launch, labels = asyncio.run(serve(horizon))
    assert launch["upload_arrays"] == launch["dispatches"] > 0
    assert "prefill_packed" in labels, labels
    assert any(l.startswith("mixed_step@c") for l in labels), labels
    assert ("decode_multi@H4B4" if horizon == 4 else "decode") in labels, labels
    monkeypatch.setattr(MR, "_packs", lambda dtype: False)
    alone, launch_alone, labels_alone = asyncio.run(serve(horizon))
    assert labels_alone == labels
    assert launch_alone["dispatches"] == launch["dispatches"]
    # the empty buffer and at least eight lane arrays a dispatch
    assert launch_alone["upload_arrays"] >= 9 * launch["dispatches"]
    assert packed == alone
    assert [len(toks) for toks, _, _ in packed] == [6, 24, 14, 10]
    for toks, lps, _ in packed:
        assert len(lps) == len(toks)
    assert all(len(top) == 3 for top in packed[1][2] + packed[2][2])
