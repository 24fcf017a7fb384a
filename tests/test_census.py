"""What a deletion must leave true (ISSUE 30).

The modelled-efficiency gauges are gone from every surface that carried
them, a peer of the other version still parses, and the count of options
cannot grow without a reviewer seeing a number change here.
"""

from __future__ import annotations

import asyncio
import dataclasses
import os
import re

import aiohttp
import pytest
from prometheus_client import generate_latest

from dynamo_tpu.components.metrics import MetricsComponent, MockWorkerMetrics
from dynamo_tpu.kv_router.protocols import (
    ForwardPassMetrics,
    KvStats,
    KvTransferStats,
    SpecDecodeStats,
    WorkerStats,
)
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.protocols import EndpointId
from dynamo_tpu.telemetry.goodput import GoodputLedger, GoodputStats
from tests.test_metrics_lint import (
    INTENTIONALLY_SHARED,
    _all_registries,
    _families,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the fields a worker of the version before this one still sends in
# `worker_stats`, and the names they were exported under
GONE_WIRE_FIELDS = {
    "decode_hbm_bytes_per_token": 3.9e8,
    "mfu_decode_est": 0.021,
    "tp_collective_bytes_per_step": 1.2e6,
}
GONE_METRICS = (
    "dyn_llm_mfu_achieved",
    "dyn_llm_hbm_bytes_per_token_achieved",
    "dyn_llm_decode_hbm_bytes_per_token",
    "dyn_llm_mfu_decode_est",
    "dyn_llm_tp_collective_bytes_per_step",
)
GONE_GOODPUT_KEYS = ("mfu_achieved", "hbm_bytes_per_token")

# Distinct `DYN_*` names under dynamo_tpu/. A PR that adds an option raises
# this number, where a reviewer sees it; one that removes an option lowers it.
MAX_DYN_NAMES = 135

_DYN = re.compile(r"DYN_[A-Z0-9_]+")


# ------------------------------------------------------------ the stats wire


def _frame() -> dict:
    return ForwardPassMetrics(
        worker_stats=WorkerStats(
            request_active_slots=3, request_total_slots=8,
            num_requests_waiting=2, preemptions_by_class={"bulk": 1},
        ),
        kv_stats=KvStats(kv_active_blocks=7, kv_total_blocks=64),
        spec_decode_stats=SpecDecodeStats(num_spec_tokens=2, num_drafts=5),
        kv_transfer_stats=KvTransferStats(kv_frames_tx=4),
    ).to_dict()


@pytest.mark.parametrize(
    "section, extra",
    [
        ("worker_stats", GONE_WIRE_FIELDS),
        ("worker_stats", {"field_of_a_later_version": [1, 2]}),
        ("kv_stats", {"field_of_a_later_version": 0.5}),
        ("spec_decode_stats", {"field_of_a_later_version": 1}),
        ("kv_transfer_stats", {"field_of_a_later_version": None}),
    ],
    ids=["the_three_removed", "worker_unknown", "kv_unknown", "spec_unknown",
         "transfer_unknown"],
)
def test_stats_frame_of_another_version_parses_to_the_same_object(
    section, extra
):
    """A rolling upgrade mixes versions: a receiver must read the frame of
    a sender that carries fields it does not declare (at the parent this
    raised `TypeError` on the first stats frame)."""
    want = ForwardPassMetrics.from_dict(_frame())
    frame = _frame()
    frame[section] = {**frame[section], **extra}
    got = ForwardPassMetrics.from_dict(frame)
    assert got == want
    assert got.to_dict() == want.to_dict()


def test_stats_frame_without_optional_sections_parses_to_defaults():
    """The other direction: a frame that lacks what this version declares
    (an old sender, or the removed fields seen from an old receiver) reads
    as the defaults."""
    got = ForwardPassMetrics.from_dict(
        {"worker_stats": {"request_active_slots": 1}, "kv_stats": {}}
    )
    assert got.worker_stats == dataclasses.replace(
        WorkerStats(), request_active_slots=1
    )
    assert got.kv_stats == KvStats()
    assert got.spec_decode_stats is None and got.kv_transfer_stats is None
    assert ForwardPassMetrics.from_dict({}) == ForwardPassMetrics()
    for name in GONE_WIRE_FIELDS:
        assert name not in ForwardPassMetrics().to_dict()["worker_stats"]
    # the ledger's wire: the removed (sum, n) keys of an old sender are
    # ignored, and a new sender no longer writes them
    gp = GoodputLedger(enabled=True)
    gp.record_step("decode", 0.01, lanes=2, capacity=4)
    wire = gp.to_dict()
    assert not {"mfu", "hbm", "n"} & set(wire)
    old = {**wire, "mfu": 0.4, "hbm": 1e8, "n": 1}
    assert GoodputStats.from_dict(old).summary() == gp.summary()


def test_ledger_sampler_slot_survives_the_wire_and_a_merge():
    """The `sampler` slot is a wire field like the others: it round-trips,
    two workers' counts add, a frame of a version without it reads as
    zeros, and a counter this version does not declare is dropped."""
    a, b = GoodputLedger(enabled=True), GoodputLedger(enabled=True)
    for pool in (True, False, False):
        a.record_sampler(pool, not pool)
    b.record_sampler(True, False)
    want = {"dispatches": 3, "pool_dispatches": 1, "logprob_dispatches": 2}
    assert a.summary()["sampler"] == want
    wire = a.to_dict()
    assert GoodputStats.from_dict(wire).summary()["sampler"] == want
    merged = GoodputStats.from_dict(wire)
    merged.merge(GoodputStats.from_dict(b.to_dict()))
    assert merged.summary()["sampler"] == {
        "dispatches": 4, "pool_dispatches": 2, "logprob_dispatches": 2,
    }
    assert merged.copy().summary()["sampler"] == merged.summary()["sampler"]
    without = {k: v for k, v in wire.items() if k != "smp"}
    none = {"dispatches": 0, "pool_dispatches": 0, "logprob_dispatches": 0}
    assert GoodputStats.from_dict(without).summary()["sampler"] == none
    later = {**wire, "smp": {**wire["smp"], "counter_of_a_later_version": 7}}
    assert GoodputStats.from_dict(later).summary()["sampler"] == want
    off = GoodputLedger(enabled=False)
    off.record_sampler(True, True)
    assert off.summary()["sampler"] == none


# ------------------------------------------- the surfaces the gauges were on


async def test_modelled_gauges_are_on_no_surface_and_the_listed_names_stay():
    """`/metrics` and `/debug/goodput` of the metrics component, scraped
    over HTTP from a publishing worker, carry none of the removed names;
    every series the lint lists as shared is still exported."""
    drt = await DistributedRuntime.from_settings()
    try:
        comp = drt.namespace("census").component("backend")
        eid = EndpointId("census", "backend", "generate")
        mock = MockWorkerMetrics(comp.endpoint("generate"), instance_id=5)
        await mock.start()
        metrics = MetricsComponent(comp, eid, poll_interval=0.05, port=0)
        port = await metrics.start()
        for _ in range(200):
            last = metrics.last
            if last is not None and last.goodput is not None:
                break
            await asyncio.sleep(0.05)
        async with aiohttp.ClientSession() as http:
            base = f"http://127.0.0.1:{port}"
            async with http.get(f"{base}/metrics") as r:
                text = await r.text()
            async with http.get(f"{base}/debug/goodput") as r:
                doc = await r.json()
        await metrics.close()
        await mock.stop()
    finally:
        await drt.close()
    assert "dyn_llm_steps_total" in text and doc["fleet"]["steps_total"] > 0
    for name in GONE_METRICS:
        assert name not in text, name
    for view in [doc["fleet"], *doc["workers"].values()]:
        assert view["compile_s_by_label"]
        for key in GONE_GOODPUT_KEYS:
            assert key not in view, key
    regs = _all_registries()
    for role, reg in regs.items():
        exposed = generate_latest(reg).decode()
        for name in GONE_METRICS:
            assert name not in exposed, (role, name)
    exported: dict[str, int] = {}
    for reg in regs.values():
        for fam in _families(reg):
            exported[fam.name] = exported.get(fam.name, 0) + 1
    for name in sorted(INTENTIONALLY_SHARED):
        assert exported.get(name, 0) >= 2, name


# ------------------------------------------------------ the count of options


def _dyn_names_in_code() -> set[str]:
    names: set[str] = set()
    for root, dirs, files in os.walk(os.path.join(REPO, "dynamo_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.endswith((".pyc", ".so")):
                continue
            with open(os.path.join(root, f), errors="replace") as fh:
                names.update(_DYN.findall(fh.read()))
    return names


def test_readme_documents_no_option_the_code_does_not_read():
    with open(os.path.join(REPO, "README.md")) as f:
        documented = set(_DYN.findall(f.read()))
    assert documented, "README.md names no DYN_* variable?"
    assert not sorted(documented - _dyn_names_in_code())


def test_the_count_of_options_does_not_grow_unseen():
    names = _dyn_names_in_code()
    assert len(names) <= MAX_DYN_NAMES, (
        f"{len(names)} distinct DYN_* names under dynamo_tpu/; a new "
        "option is a reviewed change of MAX_DYN_NAMES"
    )
    for gone in ("DYN_TPU_PEAK_FLOPS", "DYN_LAZY_HORIZON", "DYN_MIXED_STEP"):
        assert gone not in names
