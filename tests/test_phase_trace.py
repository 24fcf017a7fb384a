"""`telemetry/trace.py::phase`: the process-level span primitive. The table
(count, total, self time), the enclosing phase being the task's and not the
thread's, the profiler annotation while a profile window is open, the
overhead guard; and one pass of the JAX engine's loop on the CPU toy model."""

from __future__ import annotations

import asyncio
import gc
import glob
import os
import threading
import time

import pytest

from dynamo_tpu.telemetry import profile as dprofile
from dynamo_tpu.telemetry import trace as dtrace


@pytest.fixture(autouse=True)
def clean_table():
    dtrace.reset_phases()
    yield
    dtrace.reset_phases()


def test_nesting_and_self_time():
    with dtrace.phase("outer", label="x"):
        time.sleep(0.004)
        with dtrace.phase("inner"):
            time.sleep(0.006)
        with dtrace.phase("inner"):
            time.sleep(0.002)
    t = dtrace.phase_summary()
    assert t["outer"]["count"] == 1 and t["inner"]["count"] == 2
    assert t["inner"]["ms"] == t["inner"]["self_ms"] >= 8.0
    assert t["outer"]["ms"] >= t["inner"]["ms"] + 4.0
    # self time is the duration less the children, to the table's rounding
    assert t["outer"]["self_ms"] == pytest.approx(t["outer"]["ms"] - t["inner"]["ms"], abs=2e-3)
    assert 4.0 <= t["outer"]["self_ms"] < t["outer"]["ms"] - 8.0 + 1e-3


def test_exception_still_records_and_restores_parent():
    with dtrace.phase("outer"):
        with pytest.raises(RuntimeError):
            with dtrace.phase("failing"):
                raise RuntimeError("boom")
        with dtrace.phase("after"):
            pass
    t = dtrace.phase_summary()
    assert t["failing"]["count"] == 1 and t["after"]["count"] == 1
    assert t["outer"]["self_ms"] <= t["outer"]["ms"]
    assert dtrace._current_phase.get() is None


def test_observe_phase_publishes_an_interval_timed_elsewhere():
    dtrace.observe_phase("queue_wait", 2_500_000)
    dtrace.observe_phase("queue_wait", 500_000)
    assert dtrace.phase_summary()["queue_wait"] == {"count": 2, "ms": 3.0, "self_ms": 3.0}


def test_two_threads_do_not_nest_into_each_other():
    """A phase on another thread is no child of the phase open here (the
    executor's `runner.call` under the event loop's `loop.dispatch`), and
    both threads' counts reach the one summary."""
    def work():
        for _ in range(200):
            with dtrace.phase("runner.call"):
                pass
        with dtrace.phase("runner.call"):
            time.sleep(0.01)

    with dtrace.phase("loop.dispatch"):
        threads = [threading.Thread(target=work) for _ in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    t = dtrace.phase_summary()
    assert t["runner.call"]["count"] == 402
    assert t["runner.call"]["ms"] >= 20.0
    assert t["loop.dispatch"]["self_ms"] == t["loop.dispatch"]["ms"] >= 10.0


async def test_parent_is_the_task_not_the_thread():
    """`loop.dispatch` spans an await; a phase that another task opens on the
    same thread meanwhile is not its child."""
    go = asyncio.Event()

    async def other():
        await go.wait()
        with dtrace.phase("frontend.sse"):
            time.sleep(0.005)

    # the frontend's task exists on its own, as a connection's handler does
    task = asyncio.get_running_loop().create_task(other())
    with dtrace.phase("loop.dispatch"):
        go.set()
        await asyncio.sleep(0.02)
        await task
        with dtrace.phase("child"):
            time.sleep(0.002)
    t = dtrace.phase_summary()
    assert t["frontend.sse"]["ms"] >= 5.0
    # only `child` came off loop.dispatch's self time
    assert t["loop.dispatch"]["self_ms"] == pytest.approx(
        t["loop.dispatch"]["ms"] - t["child"]["ms"], abs=2e-3
    )


def test_phase_is_an_annotation_while_a_profile_window_is_open(tmp_path):
    """With a window open the phase lies in the .xplane.pb as `dyn:<name>`
    with its attributes; with none open nothing is annotated."""
    from jax.profiler import ProfileData

    with dtrace.phase("before.window"):
        pass
    info = dprofile.start(30.0, str(tmp_path))
    assert "error" not in info, info
    try:
        with dtrace.phase("loop.dispatch", label="decode_multi@H4B8", lanes=3,
                          ctx_tokens=77, first=False):
            time.sleep(0.002)
    finally:
        dprofile.stop()
    with dtrace.phase("after.window"):
        pass
    found = glob.glob(os.path.join(info["profile_dir"], "**", "*.xplane.pb"), recursive=True)
    assert found
    events = [
        ev for plane in ProfileData.from_file(found[0]).planes
        for line in plane.lines for ev in line.events if ev.name.startswith("dyn:")
    ]
    assert [ev.name for ev in events] == ["dyn:loop.dispatch"]
    stats = dict(events[0].stats)
    assert stats["label"] == "decode_multi@H4B8" and int(stats["lanes"]) == 3
    assert int(stats["ctx_tokens"]) == 77
    assert events[0].duration_ns >= 2e6
    # and the table counted all three
    assert set(dtrace.phase_summary()) == {"before.window", "loop.dispatch", "after.window"}


def test_phase_overhead_guard():
    """Always on, in the class of the goodput ledger and held to its guard
    (tests/test_goodput.py::test_always_on_step_observe_overhead): under
    2 us an operation with no profile window open. Best of many short
    trials, since preemption and GC only ever inflate one. This box's speed
    halves for minutes at a time under its other tenants, so `record_step`
    is timed in alternation with the phase (which does what it does, a table
    update, and an object, two clock readings and the context variable
    besides): where the absolute figure is missed, the phase may cost at
    most twice the ledger's operation."""
    from dynamo_tpu.telemetry.goodput import GoodputLedger

    assert not dprofile.active()
    gp = GoodputLedger(enabled=True)
    trials, iters = 15, 5_000
    phase_ns = ledger_ns = float("inf")
    for _ in range(trials):
        gc.collect()
        t0 = time.perf_counter()
        for _ in range(iters):
            with dtrace.phase("loop.pack"):
                pass
        phase_ns = min(phase_ns, (time.perf_counter() - t0) / iters * 1e9)
        t0 = time.perf_counter()
        for _ in range(iters):
            gp.record_step("decode", 0.004, lanes=5, capacity=8, t_start=100.0)
        ledger_ns = min(ledger_ns, (time.perf_counter() - t0) / iters * 1e9)
    assert dtrace.phase_summary()["loop.pack"]["count"] == trials * iters
    assert phase_ns < 2000 or phase_ns < 2 * ledger_ns, (
        f"trace.phase cost {phase_ns:.0f}ns/op, record_step {ledger_ns:.0f}ns/op"
    )
    print(f"trace.phase {phase_ns:.0f}ns/op, record_step {ledger_ns:.0f}ns/op")


# ------------------------------------------------------- the engine's loop


async def test_engine_loop_records_its_phases_in_order(monkeypatch):
    """One request through the CPU toy model: every pass of the loop opens
    its phases in the loop's order, a dispatch is one `loop.dispatch` around
    one `runner.call`, the request phases are published, and the self times
    of the loop's phases add up to the passes' time."""
    from tests.test_jax_engine import collect, greedy_request, make_engine

    order: list[str] = []
    real = dtrace.phase

    def recording(name, **attrs):
        order.append(name)
        return real(name, **attrs)

    monkeypatch.setattr(dtrace, "phase", recording)
    engine = make_engine()
    try:
        toks, _ = await collect(engine, greedy_request([5, 6, 7, 8, 9], 6))
        assert len(toks) == 6
        await asyncio.sleep(0.05)  # the loop finds nothing left and goes idle
    finally:
        await engine.close()
    t = dtrace.phase_summary()
    loop_names = [n for n in order if n.startswith("loop.")]
    # a pass starts with reap, then admit; the first pass prefills inside
    # admit (pack, dispatch, emit) and decodes (pack, dispatch, emit), then
    # counts and yields
    assert loop_names[:3] == ["loop.iter", "loop.reap", "loop.admit"]
    first_pass = loop_names[1:loop_names.index("loop.iter", 1)]
    assert [n for n in first_pass if n in ("loop.pack", "loop.dispatch", "loop.emit")][:3] == [
        "loop.pack", "loop.dispatch", "loop.emit",
    ]
    assert first_pass[-2:] == ["loop.stats", "loop.yield"] or first_pass[-1] == "loop.stats"
    assert order.count("runner.call") == order.count("loop.dispatch") == t["loop.dispatch"]["count"]
    for i, name in enumerate(order):
        if name == "loop.dispatch":
            assert order[i + 1] == "runner.call"
    assert t["runner.call"]["ms"] <= t["loop.dispatch"]["ms"]
    assert t["queue_wait"]["count"] == 1 and t["prefill_wait"]["count"] == 1
    assert t["loop.idle"]["count"] >= 1  # the loop went idle after the request
    # nothing of a pass is counted twice or lost
    own = sum(v["self_ms"] for k, v in t.items() if k.startswith("loop."))
    assert own == pytest.approx(t["loop.iter"]["ms"], abs=0.001 * len(t) + 1e-6)


# ------------------------------------------------- the launch's three phases

LAUNCH = ["runner.upload", "runner.enqueue", "runner.fetch"]


class _Recorded(dtrace.phase):
    """A phase that also notes its exit: name, thread, and for a
    `runner.call` its label and what the runner counted for it (arrays and
    bytes committed)."""

    log: list = []
    runner = None

    def __exit__(self, et, ev, tb):
        out = super().__exit__(et, ev, tb)
        launch = self.runner.launch
        counted = (launch.upload_arrays, launch.upload_bytes) if self.name == "runner.call" else None
        self.log.append((self.name, threading.get_ident(), self.attrs.get("label"), counted))
        return out


async def _serve_toy(monkeypatch, horizon: int):
    """A short and a long prompt through the toy engine (8-token chunks,
    mixed steps): a packed prefill, mixed steps while the first decodes, and
    the horizon's dispatches. Gives the engine and the exits in order."""
    from tests.test_layer_bodies import make_engine, request
    from tests.test_jax_engine import collect
    from dynamo_tpu.protocols.common import SamplingOptions

    _Recorded.log = log = []
    monkeypatch.setattr(dtrace, "phase", _Recorded)
    engine = make_engine(decode_horizon=horizon)
    _Recorded.runner = engine.runner
    greedy = SamplingOptions(greedy=True)
    try:
        first = asyncio.ensure_future(collect(engine, request([5, 6, 7, 8, 9], 24, greedy)))
        await asyncio.sleep(0.3)  # the first decodes when the long one arrives
        await collect(engine, request(list(range(1, 30)), 6, greedy))
        await first
    finally:
        await engine.close()
    return engine, log


def _bytes_by_the_code(label: str) -> set[int]:
    """Bytes a call of `label` commits in its one array on the toy engine
    (4 lanes, 16 table columns, 8-token chunks, 4 EOS ids) without
    penalties: every lane array's elements, a bool as four bytes; a chunk
    of a mixed step brings 8 tokens, 16 table entries, a key, 4 EOS ids and
    eight scalars beside the decode half's eleven arrays. One vector a
    label and one scalar a chunk are PR 53's: who asked for log-probs."""
    B, W, C, E = 4, 16, 8, 4
    lanes = B * W + 2 * B  # block tables and keys
    if label.startswith("mixed_step@c"):
        k = int(label.rsplit("c", 1)[1])
        return {4 * (k * (C + W + 2 + E + 8) + lanes + 8 * B + B * E)}
    return {
        # the tenth vector is `chain`, the lanes that go on from the carry
        "decode_multi@H4B4": {4 * (lanes + 10 * B + B * E)},
        "decode": {4 * (lanes + 7 * B), 4 * (lanes + 8 * B + B * E)},
        "prefill_packed": {4 * (4 * C + 2 * B + 7 * B + B * E)},
    }[label]


@pytest.mark.parametrize("horizon, labels", [
    (4, ("prefill_packed", "mixed_step@c", "decode_multi@H4B4")),
    (1, ("prefill_packed", "mixed_step@c", "decode")),
])
async def test_every_runner_call_has_its_three_children(monkeypatch, horizon, labels):
    """Each `runner.call` holds one `runner.upload`, one `runner.enqueue` and
    one `runner.fetch`, in that order, on its own thread: a dispatch, whole.
    Since steady decode launches ahead (PR 45) a `decode_multi` call may also
    hold the first two alone (a chain's first dispatch, left on the device's
    queue) or the last alone (a chain's last, read), and the three of a call
    between them are the next dispatch's launch and then the last one's read;
    over a run there are as many launches as reads. The three are all of a
    call's children (their ms are its ms less its self ms); and the ledger's
    `launch` slot counts what the runner counted: one array a launch whatever
    the label, the packed buffer of its host inputs (eleven arrays for a
    `decode_multi` until PR 42), of the bytes the label's arrays hold."""
    engine, log = await _serve_toy(monkeypatch, horizon)
    calls: dict[str, list] = {}
    since: list = []
    shapes = []
    for name, thread, label, counted in log:
        if name in LAUNCH:
            since.append((name, thread))
        elif name == "runner.call":
            held = [n for n, _ in since]
            assert held in (LAUNCH, LAUNCH[:2], LAUNCH[2:]), (label, since)
            assert held == LAUNCH or label.startswith("decode_multi"), (label, held)
            assert {t for _, t in since} == {thread}
            shapes.append(held)
            if held != LAUNCH[2:]:
                calls.setdefault(label, []).append(counted)
            else:
                assert counted == (0, 0)  # a read commits nothing
            since = []
    assert not since
    for label in labels:
        assert any(seen.startswith(label) for seen in calls), (label, sorted(calls))
    t = dtrace.phase_summary()
    n_calls = len(shapes)
    n_launches = sum(len(v) for v in calls.values())
    assert n_launches == sum(h != LAUNCH[:2] for h in shapes)  # as many reads
    if horizon == 4:
        assert LAUNCH[:2] in shapes and LAUNCH[2:] in shapes  # chains ran
        assert engine.stats.goodput.launch["chained"] > 0
    else:
        assert n_calls == n_launches
    assert t["runner.call"]["count"] == n_calls == t["loop.dispatch"]["count"]
    for name in LAUNCH:
        assert t[name]["count"] == n_launches
        assert t[name]["ms"] == t[name]["self_ms"]  # no phase inside them
    assert sum(t[n]["ms"] for n in LAUNCH) == pytest.approx(
        t["runner.call"]["ms"] - t["runner.call"]["self_ms"], abs=5e-3
    )
    for label, counted in calls.items():
        assert {arrays for arrays, _ in counted} == {1}, (label, counted)
        assert {nbytes for _, nbytes in counted} <= _bytes_by_the_code(label), (label, counted)
    launch = engine.stats.goodput.launch
    assert launch["dispatches"] == n_launches == launch["upload_arrays"]
    assert launch["upload_bytes"] == sum(b for v in calls.values() for _, b in v)
    assert launch["fetch_bytes"] > 0


async def test_the_launch_phases_are_annotations_on_the_executor_thread(monkeypatch, tmp_path):
    """While a profile window is open the three lie in the trace as
    `dyn:runner.*`, on the line of `dyn:runner.call` (the executor's
    thread) and inside it, not on the event loop's line."""
    from jax.profiler import ProfileData

    info = dprofile.start(60.0, str(tmp_path))
    assert "error" not in info, info
    try:
        await _serve_toy(monkeypatch, 4)
    finally:
        dprofile.stop()
    found = glob.glob(os.path.join(info["profile_dir"], "**", "*.xplane.pb"), recursive=True)
    assert found
    # a list, one entry a line: the host plane names every Python thread's
    # line "python", and in a table keyed by the name one thread's line
    # replaced another's (whichever came last, by thread id)
    lines = []
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            evs = [(ev.name[4:], ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("dyn:")]
            if evs:
                lines.append(evs)
    executor = [evs for evs in lines if any(n == "runner.call" for n, _, _ in evs)]
    assert executor
    n_calls = n_kids = 0
    for evs in executor:
        assert not any(n.startswith("loop.") for n, _, _ in evs)
        calls = sorted((s, e) for n, s, e in evs if n == "runner.call")
        n_calls += len(calls)
        for name in LAUNCH:
            kids = [(s, e) for n, s, e in evs if n == name]
            n_kids += len(kids)
            for s, e in kids:
                assert any(lo <= s and e <= hi for lo, hi in calls), name
    # a call holds all three, or a chain's first launch or last read alone:
    # over the run as many of each as there were dispatches
    assert n_kids % 3 == 0 and 0 < n_kids <= 3 * n_calls
    loops = [evs for evs in lines if any(n == "loop.dispatch" for n, _, _ in evs)]
    assert loops
    for evs in loops:
        assert not any(n in LAUNCH for n, _, _ in evs)


def test_a_phase_keeps_its_start_and_duration_readable():
    """`_dispatch` reads the dispatch's start and length off its phase and
    times nothing a second time."""
    before = time.monotonic()
    with dtrace.phase("loop.dispatch") as ph:
        assert before <= ph.start_s <= time.monotonic()
        time.sleep(0.003)
    assert 0.003 <= ph.seconds < 1.0
    assert dtrace.phase_summary()["loop.dispatch"]["ms"] == pytest.approx(ph.seconds * 1e3, abs=1e-3)
    assert dtrace.phase("never.entered").seconds == 0.0
