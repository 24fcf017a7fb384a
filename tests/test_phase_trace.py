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
