"""The leader's broadcast and the follower's replay agree, op by op, with no
second process: a list stands in for `broadcast_one_to_all`, and a recording
runner on each side says what it was called with. A follower that reads a
word of the header or an array of the payload at another index than the
leader wrote it launches another program, and a slice then hangs where a
test would have failed (`tests/test_multihost.py` runs the real thing, two
processes, in the slow tier)."""

from __future__ import annotations

import collections
import inspect
import types

import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.ops.sampling import MAX_EOS_IDS
from dynamo_tpu.parallel import multihost
from dynamo_tpu.parallel.multihost import SpmdModelRunner, follower_loop

B, NB, LH = 4, 3, 16  # lanes, blocks a lane, the penalties' history


class ListChannel:
    """`SpmdStepChannel` over a queue. It holds the two sides to what the
    real broadcast needs: at most seven words behind the opcode, and a
    follower's template of the payload's very shapes and dtypes."""

    def __init__(self):
        self.sent = collections.deque()

    def send(self, op, dims, payload):
        assert len(dims) <= 7, dims
        header = np.zeros(8, np.int32)
        header[0] = op
        header[1: 1 + len(dims)] = dims
        self.sent.append(header)
        if payload:
            self.sent.append(tuple(np.asarray(a) for a in payload))
        return payload

    def recv_header(self):
        return self.sent.popleft()

    def recv_payload(self, template):
        payload = self.sent.popleft()
        assert [(np.shape(a), np.asarray(a).dtype) for a in template] == [
            (a.shape, a.dtype) for a in payload
        ]
        return payload


class RecordingRunner:
    """Answers the runner's device calls by writing them down."""

    CALLS = (
        "prefill", "prefill_chunk", "decode", "decode_multi",
        "prefill_packed_arrays", "prefill_mm",
    )
    config = types.SimpleNamespace(num_layers=2, num_kv_heads=2, head_dim=16)
    block_size = 16
    max_model_len = LH
    _want_lanes = staticmethod(ModelRunner._want_lanes)
    _fetch = staticmethod(lambda x: x)

    def __init__(self):
        self.calls = []

    def _next_key_data(self):
        return np.array([7, 9], np.uint32)

    def _next_decode_keys(self, n):
        return np.arange(2 * n, dtype=np.uint32).reshape(n, 2)

    def __getattr__(self, name):
        if name not in self.CALLS:
            raise AttributeError(name)

        def record(*args, **kwargs):
            # by the real runner's parameters, so that a value passed by
            # position on one side and by name on the other is one value
            bound = inspect.signature(getattr(ModelRunner, name)).bind(
                self, *args, **kwargs
            )
            bound.apply_defaults()
            self.calls.append((name, dict(bound.arguments, self=None)))
            return ()

        return record


def _same(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, tuple):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    return np.array_equal(np.asarray(a), np.asarray(b))


def _lanes(rng):
    vec = lambda dtype, hi=50: rng.integers(1, hi, B).astype(dtype)
    return dict(
        tokens=vec(np.int32), positions=vec(np.int32),
        tables=rng.integers(0, 9, (B, NB)).astype(np.int32),
        slots=vec(np.int32), temps=rng.random(B).astype(np.float32),
        top_ps=rng.random(B).astype(np.float32), top_ks=vec(np.int32),
        keys=rng.integers(0, 2**31, (B, 2)).astype(np.uint32),
        eos=rng.integers(-1, 60, (B, MAX_EOS_IDS)).astype(np.int32),
        flags=rng.random(B) < 0.5,
    )


def _penalties(rng, with_eos: bool):
    pen = (
        rng.integers(0, 60, (B, LH)).astype(np.int32),
        rng.integers(0, LH, B).astype(np.int32),
        rng.integers(0, LH, B).astype(np.int32),
        rng.random(B).astype(np.float32), rng.random(B).astype(np.float32),
        (1 + rng.random(B)).astype(np.float32),
    )
    if with_eos:
        pen += (rng.integers(-1, 60, (B, MAX_EOS_IDS)).astype(np.int32),
                rng.random(B) < 0.5)
    return pen


def _prefill(leader, rng, want):
    # scalars ride as float32: values that float32 holds exactly
    leader.prefill([5, 6, 7], [1, 2], 0.75, 0.875, 40, rep_pen=1.125,
                   eos_ids=np.array([3, -1, -1, -1]), eos_suppress=True,
                   want_logprobs=want)


def _prefill_chunk(leader, rng, want):
    leader.prefill_chunk([5, 6, 7, 8], 16, 23, [1, 2, 4], 0.0, 1.0, 0,
                         key_data=np.array([1, 2], np.uint32),
                         eos_suppress=False, want_logprobs=want)


def _prefill_mm(leader, rng, want):
    leader.prefill_mm([5, 6, 7, 8, 9], [1, 2], rng.random((2, 8)), 1, 0.5,
                      0.75, 20, rep_pen=1.25, eos_suppress=True,
                      want_logprobs=want)


def _decode(variant):
    def call(leader, rng, want):
        a = _lanes(rng)
        pen = _penalties(rng, with_eos=True) if variant == 1 else None
        mask = (a["eos"], a["flags"]) if variant == 2 else None
        leader.decode(a["tokens"], a["positions"], a["tables"], a["slots"],
                      a["temps"], a["top_ps"], a["top_ks"],
                      keys=None if variant == 0 else a["keys"],
                      penalties=pen, eos_mask=mask, want_logprobs=want)
    return call


def _decode_multi(with_penalties):
    def call(leader, rng, want):
        a = _lanes(rng)
        leader.decode_multi(
            3, a["tokens"], a["positions"], a["tables"], a["temps"],
            a["top_ps"], a["top_ks"], a["keys"], a["flags"], a["slots"],
            a["positions"], a["eos"],
            penalties=_penalties(rng, False) if with_penalties else None,
            want_logprobs=want,
        )
    return call


def _packed(leader, rng, want):
    a = _lanes(rng)
    tok = lambda: rng.integers(0, 60, 32).astype(np.int32)
    leader.prefill_packed_arrays(
        tok(), tok(), tok(), tok(), a["slots"], a["temps"], a["top_ps"],
        a["top_ks"], a["temps"], a["keys"], eos_ids=a["eos"],
        eos_suppress=a["flags"], want_logprobs=want,
    )


OPS = {
    "prefill": (_prefill, False),
    "prefill_chunk": (_prefill_chunk, False),
    "prefill_mm": (_prefill_mm, False),
    "decode": (_decode(0), True),
    "decode+penalties": (_decode(1), True),
    "decode+eos_mask": (_decode(2), True),
    "decode_multi": (_decode_multi(False), True),
    "decode_multi+penalties": (_decode_multi(True), True),
    "prefill_packed_arrays": (_packed, True),
}


@pytest.mark.parametrize("asks", ["none", "some", "unsaid"])
@pytest.mark.parametrize("op", list(OPS))
def test_follower_replays_the_leaders_call(op, asks):
    """Every argument of every sampling op reaches the follower's runner
    equal to what the leader's own runner got, `want_logprobs` among them:
    a lane's flag for the ops that take lanes, one flag for the others, and
    every lane asking where the caller did not say."""
    call, per_lane = OPS[op]
    if asks == "unsaid":
        want = None if per_lane else True
    elif per_lane:
        want = np.zeros(B, bool)
        want[1:3] = asks == "some"
    else:
        want = asks == "some"
    channel = ListChannel()
    ours, theirs = RecordingRunner(), RecordingRunner()
    leader = SpmdModelRunner(ours, channel)
    call(leader, np.random.default_rng(3), want)
    leader.stop_followers()
    follower_loop(theirs, channel)
    assert not channel.sent
    [(name, led)], [(name2, followed)] = ours.calls, theirs.calls
    assert name == name2 == op.split("+")[0]
    assert list(led) == list(followed)
    for key in led:
        assert _same(led[key], followed[key]), (key, led[key], followed[key])
    said = np.asarray(followed["want_logprobs"])
    expected = np.ones(B, bool) if want is None else np.asarray(want)
    assert said.dtype == bool and np.array_equal(said, expected)


def test_the_eos_width_is_the_samplers():
    assert multihost._EOS_K == MAX_EOS_IDS
