"""Goodput ledger: always-on per-device-step efficiency accounting.

PRs 5-6 built the *latency* observability plane (phase histograms, SLO
burn); nothing recorded where device time and scheduled tokens actually
GO. This module is the efficiency sensing plane: every engine dispatch
("device step") is folded into fixed-log-bucket histograms keyed by its
dispatch label, alongside occupancy (lanes used vs capacity), prefill /
decode token throughput, phase-bubble time between dispatches, a
**token-waste taxonomy** of cumulative counters, and **recompile
forensics** — per-label compile time plus a counter of *unexpected*
recompiles after warmup.

Waste taxonomy (the `cause` label on `dyn_llm_tokens_wasted_total`):

  * ``spec_rejected``     — draft tokens the verify step rejected
  * ``preempt_replay``    — KV work (prompt + generated) discarded by a
                            preemption and recomputed on re-admission
  * ``migration_replay``  — already-streamed tokens re-prefilled on an
                            in-flight migration resume
  * ``deadline_partial``  — tokens generated for a request whose deadline
                            expired mid-generation (partial discarded)
  * ``cancelled_partial`` — tokens generated for a consumer that
                            disconnected (includes engine-side hedge
                            losers, which the engine cannot distinguish)
  * ``hedge_loser``       — tokens the losing hedge stream emitted
                            (frontend-attributed: hedging happens where
                            dispatch happens)

Recompile causes (`dyn_llm_recompiles_total{label,cause}`):

  * ``shape_miss``   — a warm label dispatched far off its EMA (a shape
                       bucket the jit cache had not seen)
  * ``prebake_miss`` — same, but the label was pre-baked by
                       `tools/prebake_cache.py` — cache drift, the image
                       no longer matches the serve shapes
  * ``stall``        — a warm label dispatched as far off its EMA with the
                       time outside the jitted call, where alone a compile
                       can happen: the host stalled, nothing compiled

Everything here follows the `telemetry/histogram.py` contract: fixed
grids, plain-addition merges (associative + commutative), sparse
msgpack/JSON-safe wire forms, and `observe()` cheap enough to stay
always-on in the engine hot path. `DYN_GOODPUT=0` disables recording
entirely (the overhead A/B knob used by `benchmarks/goodput_bench.py`).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Any, Optional

from dynamo_tpu.telemetry.histogram import (
    PhaseHistogram,
    PhaseHistograms,
    bucket_index,
)

logger = logging.getLogger(__name__)

# Fixed taxonomy — exporters iterate this so dashboards get stable,
# zero-valued series instead of appearing-on-first-waste label churn.
WASTE_CAUSES = (
    "spec_rejected",
    "preempt_replay",
    "migration_replay",
    "deadline_partial",
    "cancelled_partial",
    "hedge_loser",
)

RECOMPILE_CAUSES = ("shape_miss", "prebake_miss", "stall")

# Bound on dict-keyed state: dispatch labels are a small closed set, but
# a bug (label built from a shape) must never grow the ledger unbounded.
MAX_LABELS = 32


def enabled_from_env() -> bool:
    return os.environ.get("DYN_GOODPUT", "1") not in ("0", "false", "off")


# expert-layer counters of a sparse-expert model, computed on the device in
# `decode_multi` and fetched with the tokens (`ops.moe.STEP_STATS`):
# sums over (expert layer, step) pairs, whose number is `layer_steps`. A
# layer that holds a share of its router's experts counts what it holds
# under the first four and every assignment its router made for a live token
# under `assignments_made` (`ops.moe.HELD_STEP_STATS`; 0 for a model whose
# layers hold every expert: there `assignments` is all that were made)
MOE_COUNTERS = (
    "layer_steps", "assignments", "experts_touched", "max_expert_load",
    "assignments_made",
)

# the sampler's candidate pool (`ops.sampling.sample_tokens`): decode-family
# dispatches, and those among them whose batch held a sampled lane that
# restricts its draw (top_k or top_p), so that the device took the pool's
# branch in every step of the dispatch; and those whose batch held a lane
# that asked for log-probs, so that every step computed the log-prob surface
# (`ops.sampling.sample_tokens_full`: `dispatches - logprob_dispatches` is
# how often it was left out); counted on the host, before the call
SAMPLER_COUNTERS = ("dispatches", "pool_dispatches", "logprob_dispatches")


# a model with recurrent layers (a slot a sequence: a state-space layer's
# state and tail, a short convolution's tail): `layer_steps`, (recurrent
# layer, decode step) pairs; `slots_live`, live lanes summed over decode
# steps; `slot_resets`, sequences whose slot a prefill program started from
# zeros at position 0; `scan_tokens`, prompt tokens through the prefill scans
# or convolutions. Counted on the host where the lane arrays are built,
# before the call
SSM_COUNTERS = ("layer_steps", "slots_live", "slot_resets", "scan_tokens")


# a model whose paged layers are of two groups (window layers that give
# their pages back beside layers that keep every position), over its
# decode-family dispatches: `decode_steps`; `lane_steps`, live lanes summed
# over them; `window_rows` and `full_rows`, the rows a step's window layers
# and full layers must read of each live lane (`min(context, window)` and
# `context`), summed over lanes and steps; `lanes_past_window`, live lanes
# whose context is past the window, summed over steps, and
# `window_blocks_past`, the window blocks those lanes held; the two pools'
# blocks in use summed over steps beside their capacity summed likewise
# (`*_in_use_steps`, `*_capacity_steps`: a share in use is their quotient);
# `window_blocks_given_back`, window blocks that went back to their pool
# because the window had left them. Counted on the host where the lane
# arrays are built, before the call; nothing is fetched
POOL_COUNTERS = (
    "decode_steps", "lane_steps", "window_rows", "full_rows",
    "lanes_past_window", "window_blocks_past",
    "window_in_use_steps", "window_capacity_steps",
    "full_in_use_steps", "full_capacity_steps",
    "window_blocks_given_back",
)


# the stream edge of an engine: `items` put on sequences' streams that carried
# tokens (one holds what one dispatch produced for one sequence) and the
# `tokens` they carried, counted together where an item is put, so that
# tokens an item over any window is two reads
STREAM_COUNTERS = ("items", "tokens")


# the launch of a dispatch at the host's edge of the device: `dispatches`
# whose runner keeps the record, the host arrays they committed
# (`ModelRunner._to_dev`: `upload_arrays`, one a dispatch, the packed buffer
# of its host inputs, and `upload_bytes`) and the bytes of
# the results read back (`fetch_bytes`), counted by the runner where it
# commits and reads, entered here once a dispatch; `chained`, those of the
# dispatches that were enqueued behind a `decode_multi` the host had not read
# yet and took their lanes from its carry on the device (the engine's launch
# ahead: `JaxEngine._decode_multi_phase`)
LAUNCH_COUNTERS = (
    "dispatches", "upload_arrays", "upload_bytes", "fetch_bytes", "chained",
)

# why a dispatch was not chained, one count a dispatch, so that with
# `launch.chained` they add up to `launch.dispatches`: `arrival` (a prefill
# program, or the `decode_multi` that follows somebody's admission),
# `prefilling` (a mixed step or a chunk, or the `decode_multi` behind one),
# `penalties` (the penalties programs), `blocks` (the two horizons'
# preallocation failed, or a single step that ran for want of blocks),
# `other` (a label's first dispatch, a start after idleness, a verify pass,
# the last token of every lane, block movement waiting for the device)
CHAIN_BREAKS = ("arrival", "prefilling", "penalties", "blocks", "other")

# a dispatch in the order it happens: the hop from the event loop to the
# executor thread, the three phases of the runner's call (`runner.upload`,
# `runner.enqueue`, `runner.fetch`) and what else the call held, and the
# wait for the event loop to resume the engine's task
LAUNCH_PARTS = ("hop", "upload", "enqueue", "fetch", "call_rest", "resume")


def launch_parts(
    elapsed_s: float, hop_s: float, call_s: float, launch: Any
) -> dict[str, float]:
    """Where a dispatch of `elapsed_s` spent it, LAUNCH_PARTS in seconds:
    `hop_s` before its `runner.call` began, `call_s` inside it, of which
    `launch` (the runner's record: `upload_s`, `enqueue_s`, `fetch_s`)
    names three parts."""
    upload, enqueue, fetch = launch.upload_s, launch.enqueue_s, launch.fetch_s
    return dict(zip(LAUNCH_PARTS, (
        hop_s, upload, enqueue, fetch, call_s - upload - enqueue - fetch,
        elapsed_s - hop_s - call_s,
    )))


def long_part(parts: dict[str, float]) -> str:
    """The part that holds most of a dispatch. A dispatch ten times its
    usual length (`RecompileDetector`) spent nine tenths of it where it was
    held up, whichever part usually waits for the device."""
    return max(LAUNCH_PARTS, key=lambda k: parts.get(k, 0.0))


# what a label's first dispatch held beside the device's work
# (`first_dispatch_by_label`, beside `compile_s_by_label`): the seconds JAX
# itself reports on the dispatching thread for tracing, for building the MLIR
# module and for the backend (a compile, or the read of a cached one), and
# how many distinct layer bodies (`models.layer_body`) its programs called,
# each lowered once whatever the depth; the rest of the label's `compile_s`
# is argument handling, the upload, the run and the fetch. And of the
# layers its programs called (`models.forms_called`), how many append a
# decode token's keys and values to their cache inside the paged decode
# kernel and how many by the row scatter before it, and how many grouped
# products of their experts run in the Pallas kernel and how many in XLA's,
# and how many Mamba-2 state updates run in the Pallas kernel that visits the
# live lanes' rows alone and how many in XLA's over every row (a dispatch of
# several steps calls a layer's kernel in one shape a step, each counted)
LAYER_FORMS = (
    "kv_append_folded", "kv_append_scattered",
    "grouped_product_kernel", "grouped_product_xla",
    "ssd_step_kernel", "ssd_step_xla",
)
FIRST_DISPATCH_FIELDS = (
    "trace_s", "lower_s", "backend_s", "layer_bodies",
) + LAYER_FORMS

_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_collecting = threading.local()
_listener_lock = threading.Lock()
_listening = False


def _on_jax_span(event: str, start: float, end: float, **_kw) -> None:
    stage = _JAX_STAGES.get(event)
    spans = getattr(_collecting, "spans", None)
    if stage is None or spans is None:
        return
    # a jitted function traced inside another reports its span first, and
    # the outer's, which ends later, covers it: the outer's alone counts
    spans[stage] = [s for s in spans[stage] if s[0] < start] + [(start, end)]


@contextlib.contextmanager
def first_dispatch_split(into: dict):
    """Fills `into` with FIRST_DISPATCH_FIELDS for what the calling thread
    does inside the block (`jax.monitoring` time spans, so nothing is timed
    here and nothing at all outside a block)."""
    global _listening
    import jax.monitoring

    from dynamo_tpu.models import forms_called, layer_bodies_called

    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_jax_span)
            _listening = True
    spans = _collecting.spans = {stage: [] for stage in _JAX_STAGES.values()}
    try:
        with layer_bodies_called() as bodies:
            yield
    finally:
        _collecting.spans = None
        for stage, kept in spans.items():
            into[stage] = sum(end - start for start, end in kept)
        into["layer_bodies"] = len(bodies)
        counted = forms_called()
        into.update({f: counted[f] for f in LAYER_FORMS})


class GoodputStats:
    """Mergeable goodput snapshot (the wire/aggregate half).

    Merging follows the phase-histogram contract: counters add, bucket
    grids add, compile times take the max (worst worker).
    """

    __slots__ = (
        "step_hists",
        "steps_total",
        "bubble_s_total",
        "busy_s_total",
        "phase_gap_s_total",
        "mixed_steps",
        "mixed_prefill_tokens",
        "mixed_decode_tokens",
        "lane_steps",
        "lane_capacity_steps",
        "prefill_tokens",
        "decode_tokens",
        "waste_by_cause",
        "recompiles",
        "compile_s_by_label",
        "first_dispatch_by_label",
        "moe",
        "sampler",
        "ssm",
        "pool",
        "stream",
        "launch",
        "chain_breaks",
    )

    def __init__(self) -> None:
        # per-dispatch-label step-duration distributions (ms grid)
        self.step_hists = PhaseHistograms()
        self.steps_total = 0
        # idle gap between the end of one dispatch and the start of the
        # next while work was in flight — the "phase bubble" the unified
        # mixed-step ROADMAP item wants to close
        self.bubble_s_total = 0.0
        # device-attributed dispatch seconds (denominator for the bubble
        # fraction: wall ~ busy + bubble while work is in flight)
        self.busy_s_total = 0.0
        # the subset of bubble time accrued at PHASE TRANSITIONS (a
        # prefill-family dispatch followed by a decode-family one or vice
        # versa). Mixed steps are one phase by construction, so a unified
        # stepper drives this to ~0 while bubble_s_total keeps counting
        # ordinary inter-step host gaps.
        self.phase_gap_s_total = 0.0
        # mixed-step occupancy split: how many device steps carried both
        # phases, and how many prefill tokens / decode lanes rode them
        self.mixed_steps = 0
        self.mixed_prefill_tokens = 0
        self.mixed_decode_tokens = 0
        # occupancy: sum of lanes occupied / lane capacity per decode-
        # family step (occupancy = lane_steps / lane_capacity_steps)
        self.lane_steps = 0
        self.lane_capacity_steps = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.waste_by_cause: dict[str, int] = {}
        # "label|cause" -> count of unexpected post-warmup recompiles
        self.recompiles: dict[str, int] = {}
        # label -> first-dispatch (compile-inclusive) seconds
        self.compile_s_by_label: dict[str, float] = {}
        # label -> FIRST_DISPATCH_FIELDS of that dispatch
        self.first_dispatch_by_label: dict[str, dict[str, float]] = {}
        # what a sparse-expert model counted on the device, summed over
        # the decode horizons fetched so far (MOE_COUNTERS; empty for a
        # model without experts)
        self.moe: dict[str, float] = {}
        # SAMPLER_COUNTERS
        self.sampler: dict[str, int] = {}
        # SSM_COUNTERS (empty for a model without recurrent layers)
        self.ssm: dict[str, int] = {}
        # POOL_COUNTERS (empty for a model with one group of paged layers)
        self.pool: dict[str, int] = {}
        # STREAM_COUNTERS
        self.stream: dict[str, int] = {}
        # LAUNCH_COUNTERS
        self.launch: dict[str, int] = {}
        # CHAIN_BREAKS
        self.chain_breaks: dict[str, int] = {}

    # ------------------------------------------------------------- query

    @property
    def occupancy(self) -> float:
        if not self.lane_capacity_steps:
            return 0.0
        return self.lane_steps / self.lane_capacity_steps

    @property
    def phase_bubble_fraction(self) -> float:
        """Share of in-flight wall time lost at phase-transition
        boundaries. The headline number the mixed stepper collapses."""
        total = self.busy_s_total + self.bubble_s_total
        if total <= 0:
            return 0.0
        return self.phase_gap_s_total / total

    def wasted_total(self) -> int:
        return sum(self.waste_by_cause.values())

    def recompiles_total(self) -> int:
        return sum(self.recompiles.values())

    def total_events(self) -> int:
        """Nonzero iff this snapshot carries anything worth shipping."""
        return (
            self.steps_total
            + self.wasted_total()
            + self.recompiles_total()
            + len(self.compile_s_by_label)
        )

    # ------------------------------------------------------------- merge

    def merge(self, other: "GoodputStats") -> None:
        self.step_hists.merge(other.step_hists)
        self.steps_total += other.steps_total
        self.bubble_s_total += other.bubble_s_total
        self.busy_s_total += other.busy_s_total
        self.phase_gap_s_total += other.phase_gap_s_total
        self.mixed_steps += other.mixed_steps
        self.mixed_prefill_tokens += other.mixed_prefill_tokens
        self.mixed_decode_tokens += other.mixed_decode_tokens
        self.lane_steps += other.lane_steps
        self.lane_capacity_steps += other.lane_capacity_steps
        self.prefill_tokens += other.prefill_tokens
        self.decode_tokens += other.decode_tokens
        for k, v in other.waste_by_cause.items():
            self.waste_by_cause[k] = self.waste_by_cause.get(k, 0) + v
        for k, v in other.recompiles.items():
            self.recompiles[k] = self.recompiles.get(k, 0) + v
        for k, v in other.compile_s_by_label.items():
            if len(self.compile_s_by_label) < MAX_LABELS or (
                k in self.compile_s_by_label
            ):
                self.compile_s_by_label[k] = max(
                    self.compile_s_by_label.get(k, 0.0), v
                )
        for k, v in other.first_dispatch_by_label.items():
            self._merge_first_dispatch(k, v)
        for k, v in other.moe.items():
            self.moe[k] = self.moe.get(k, 0.0) + v
        for k, v in other.sampler.items():
            self.sampler[k] = self.sampler.get(k, 0) + v
        for k, v in other.ssm.items():
            self.ssm[k] = self.ssm.get(k, 0) + v
        for k, v in other.pool.items():
            self.pool[k] = self.pool.get(k, 0) + v
        for k, v in other.stream.items():
            self.stream[k] = self.stream.get(k, 0) + v
        for k, v in other.launch.items():
            self.launch[k] = self.launch.get(k, 0) + v
        for k, v in other.chain_breaks.items():
            self.chain_breaks[k] = self.chain_breaks.get(k, 0) + v

    def _merge_first_dispatch(self, label: str, split: dict) -> None:
        """Field by field the larger, as `compile_s_by_label` takes the
        slower worker."""
        mine = self.first_dispatch_by_label.get(label)
        if mine is None:
            if len(self.first_dispatch_by_label) >= MAX_LABELS:
                return
            mine = self.first_dispatch_by_label[label] = {}
        for f in FIRST_DISPATCH_FIELDS:
            mine[f] = max(mine.get(f, 0.0), float(split.get(f, 0.0)))

    def copy(self) -> "GoodputStats":
        out = GoodputStats()
        out.merge(self)
        return out

    # -------------------------------------------------------------- wire

    def to_dict(self) -> dict[str, Any]:
        return {
            "sh": self.step_hists.to_dict(),
            "st": self.steps_total,
            "bub": round(self.bubble_s_total, 6),
            "bus": round(self.busy_s_total, 6),
            "pg": round(self.phase_gap_s_total, 6),
            "ms": self.mixed_steps,
            "mpt": self.mixed_prefill_tokens,
            "mdt": self.mixed_decode_tokens,
            "ls": self.lane_steps,
            "lc": self.lane_capacity_steps,
            "pt": self.prefill_tokens,
            "dt": self.decode_tokens,
            "w": dict(self.waste_by_cause),
            "rc": dict(self.recompiles),
            "cs": {k: round(v, 4) for k, v in self.compile_s_by_label.items()},
            "fd": {
                k: {f: round(v[f], 4) for f in FIRST_DISPATCH_FIELDS}
                for k, v in self.first_dispatch_by_label.items()
            },
            "moe": dict(self.moe),
            "smp": dict(self.sampler),
            "ssm": dict(self.ssm),
            "pool": dict(self.pool),
            "str": dict(self.stream),
            "lch": dict(self.launch),
            "cb": dict(self.chain_breaks),
        }

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "GoodputStats":
        out = cls()
        if not isinstance(d, dict):
            return out
        out.step_hists = PhaseHistograms.from_dict(d.get("sh") or {})
        out.steps_total = int(d.get("st") or 0)
        out.bubble_s_total = float(d.get("bub") or 0.0)
        out.busy_s_total = float(d.get("bus") or 0.0)
        out.phase_gap_s_total = float(d.get("pg") or 0.0)
        out.mixed_steps = int(d.get("ms") or 0)
        out.mixed_prefill_tokens = int(d.get("mpt") or 0)
        out.mixed_decode_tokens = int(d.get("mdt") or 0)
        out.lane_steps = int(d.get("ls") or 0)
        out.lane_capacity_steps = int(d.get("lc") or 0)
        out.prefill_tokens = int(d.get("pt") or 0)
        out.decode_tokens = int(d.get("dt") or 0)
        for k, v in (d.get("w") or {}).items():
            out.waste_by_cause[str(k)] = int(v)
        for k, v in (d.get("rc") or {}).items():
            out.recompiles[str(k)] = int(v)
        for k, v in (d.get("cs") or {}).items():
            if len(out.compile_s_by_label) < MAX_LABELS:
                out.compile_s_by_label[str(k)] = float(v)
        for k, v in (d.get("fd") or {}).items():
            if isinstance(v, dict):
                out._merge_first_dispatch(str(k), v)
        for k, v in (d.get("moe") or {}).items():
            if k in MOE_COUNTERS:
                out.moe[k] = float(v)
        for k, v in (d.get("smp") or {}).items():
            if k in SAMPLER_COUNTERS:
                out.sampler[k] = int(v)
        for k, v in (d.get("ssm") or {}).items():
            if k in SSM_COUNTERS:
                out.ssm[k] = int(v)
        for k, v in (d.get("pool") or {}).items():
            if k in POOL_COUNTERS:
                out.pool[k] = int(v)
        for k, v in (d.get("str") or {}).items():
            if k in STREAM_COUNTERS:
                out.stream[k] = int(v)
        for k, v in (d.get("lch") or {}).items():
            if k in LAUNCH_COUNTERS:
                out.launch[k] = int(v)
        for k, v in (d.get("cb") or {}).items():
            if k in CHAIN_BREAKS:
                out.chain_breaks[k] = int(v)
        return out

    # ------------------------------------------------------------- debug

    def summary(self) -> dict[str, Any]:
        """Human-oriented JSON for `GET /debug/goodput`."""
        steps: dict[str, Any] = {}
        for label, h in self.step_hists.phases.items():
            steps[label] = {
                "count": h.count,
                "mean_ms": round(h.mean_ms, 3),
                "p50_ms": round(h.percentile(50), 3),
                "p99_ms": round(h.percentile(99), 3),
            }
        return {
            "steps_total": self.steps_total,
            "steps_by_label": steps,
            "occupancy": round(self.occupancy, 4),
            "phase_bubble_s": round(self.bubble_s_total, 4),
            "busy_s": round(self.busy_s_total, 4),
            "phase_gap_s": round(self.phase_gap_s_total, 4),
            "phase_bubble_fraction": round(self.phase_bubble_fraction, 5),
            "mixed_steps": self.mixed_steps,
            "mixed_prefill_tokens": self.mixed_prefill_tokens,
            "mixed_decode_tokens": self.mixed_decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "tokens_wasted": {
                c: self.waste_by_cause.get(c, 0) for c in WASTE_CAUSES
            },
            "tokens_wasted_total": self.wasted_total(),
            "recompiles": dict(self.recompiles),
            "compile_s_by_label": {
                k: round(v, 3) for k, v in self.compile_s_by_label.items()
            },
            "first_dispatch_by_label": {
                k: {f: round(v[f], 3) for f in FIRST_DISPATCH_FIELDS}
                for k, v in self.first_dispatch_by_label.items()
            },
            "moe": {k: self.moe.get(k, 0.0) for k in MOE_COUNTERS},
            "sampler": {k: self.sampler.get(k, 0) for k in SAMPLER_COUNTERS},
            "ssm": {k: self.ssm.get(k, 0) for k in SSM_COUNTERS},
            "pool": {k: self.pool.get(k, 0) for k in POOL_COUNTERS},
            "stream": {k: self.stream.get(k, 0) for k in STREAM_COUNTERS},
            "launch": {k: self.launch.get(k, 0) for k in LAUNCH_COUNTERS},
            "chain_breaks": {
                k: self.chain_breaks.get(k, 0) for k in CHAIN_BREAKS
            },
        }


class GoodputLedger(GoodputStats):
    """The recording half, embedded in a live engine.

    Adds the dispatch-edge state (`_last_end` for bubble accounting) and
    the record_* API the engines call. All recorders no-op when
    `DYN_GOODPUT=0`, and the ledger is bounded by construction: fixed
    histogram grids, the closed waste/recompile taxonomies, and a
    MAX_LABELS cap on every label-keyed dict.
    """

    __slots__ = ("enabled", "_last_end", "_last_phase")

    def __init__(self, enabled: Optional[bool] = None) -> None:
        super().__init__()
        self.enabled = enabled_from_env() if enabled is None else enabled
        self._last_end: Optional[float] = None
        self._last_phase: Optional[str] = None

    def record_step(
        self,
        label: str,
        elapsed_s: float,
        *,
        lanes: int = 0,
        capacity: int = 0,
        prefill_tokens: int = 0,
        t_start: Optional[float] = None,
    ) -> None:
        """One device dispatch completed. `t_start` (time.monotonic) feeds
        phase-bubble accounting: the gap since the previous dispatch's end
        is device idle time between phases."""
        if not self.enabled:
            return
        self.steps_total += 1
        self.busy_s_total += elapsed_s
        # inlined step_hists.observe(): every dispatch lands here, and
        # the two method hops + the per-call MAX_LABELS len() probe cost
        # more than the bucket math itself (the cap check only needs to
        # run for a label we haven't seen)
        phases = self.step_hists.phases
        h = phases.get(label)
        if h is None and len(phases) < MAX_LABELS:
            h = phases[label] = PhaseHistogram()
        if h is not None:
            ms = elapsed_s * 1e3 if elapsed_s > 0 else 0.0
            h.counts[bucket_index(ms)] += 1
            h.count += 1
            h.sum_ms += ms
        if capacity > 0:
            self.lane_steps += lanes
            self.lane_capacity_steps += capacity
        if prefill_tokens > 0:
            self.prefill_tokens += prefill_tokens
        # inline fast path of step_phase(): one dict probe per call (the
        # function-call fallback only runs once per distinct label)
        phase = _PHASE_CACHE.get(label)
        if phase is None:
            phase = step_phase(label)
        if phase == "mixed":
            self.mixed_steps += 1
            self.mixed_prefill_tokens += prefill_tokens
            self.mixed_decode_tokens += lanes
        if t_start is not None:
            if self._last_end is not None and t_start > self._last_end:
                gap = t_start - self._last_end
                self.bubble_s_total += gap
                # only a gap at a boundary CROSSING the prefill family is
                # the phase bubble: a pure-prefill program carries no
                # decode lane, so every lane sits serialized behind it.
                # decode->decode, mixed->mixed AND decode<->mixed
                # boundaries are ordinary host bookkeeping — the decode
                # lanes ride inside both step kinds, nothing is waiting
                if (
                    self._last_phase is not None
                    and phase != self._last_phase
                    and "prefill" in (phase, self._last_phase)
                ):
                    self.phase_gap_s_total += gap
            self._last_end = t_start + elapsed_s
            self._last_phase = phase

    def record_moe(self, counted: dict[str, float]) -> None:
        """One fetched decode horizon's expert counters (the runner's
        `step_stats`), summed."""
        if not self.enabled:
            return
        for k in MOE_COUNTERS:
            self.moe[k] = self.moe.get(k, 0.0) + float(counted.get(k, 0.0))

    def record_sampler(self, pool: bool, logprobs: bool) -> None:
        """One decode-family dispatch; `pool` says whether its lanes made
        the device compute the sampler's candidate pool, `logprobs` whether
        they made it compute the log-prob surface."""
        if not self.enabled:
            return
        for name, took in (
            ("dispatches", True), ("pool_dispatches", pool),
            ("logprob_dispatches", logprobs),
        ):
            if took:
                self.sampler[name] = self.sampler.get(name, 0) + 1

    def record_ssm(
        self, layers: int, *, decode_steps: int, lanes: int, resets: int,
        scan_tokens: int,
    ) -> None:
        """One dispatch of a model with `layers` recurrent layers: its
        decode steps at `lanes` live lanes, the sequences it starts at
        position 0, the prompt tokens it scans."""
        if not self.enabled:
            return
        for k, v in zip(SSM_COUNTERS, (
            layers * decode_steps, lanes * decode_steps, resets, scan_tokens,
        )):
            self.ssm[k] = self.ssm.get(k, 0) + int(v)

    def record_pool(self, **counted: int) -> None:
        """One decode-family dispatch of a model with a window group of
        pages: `counted` by POOL_COUNTERS' names."""
        if not self.enabled:
            return
        for k in POOL_COUNTERS:
            if k in counted:
                self.pool[k] = self.pool.get(k, 0) + int(counted[k])

    def record_stream(self, tokens: int) -> None:
        """One item of `tokens` tokens put on a sequence's stream."""
        if not self.enabled:
            return
        self.stream["items"] = self.stream.get("items", 0) + 1
        self.stream["tokens"] = self.stream.get("tokens", 0) + tokens

    def record_launch(
        self, upload_arrays: int, upload_bytes: int, fetch_bytes: int,
        *, dispatches: int = 1, chained: bool = False, why: str = "other",
    ) -> None:
        """What one hop to the runner counted. It launched one dispatch,
        `chained` or else not for the reason `why` (of CHAIN_BREAKS); or
        none (`dispatches` 0): it only read the result of one launched
        before."""
        if not self.enabled:
            return
        for k, v in zip(LAUNCH_COUNTERS, (
            dispatches, upload_arrays, upload_bytes, fetch_bytes,
            dispatches if chained else 0,
        )):
            self.launch[k] = self.launch.get(k, 0) + v
        if dispatches and not chained:
            if why not in CHAIN_BREAKS:
                why = "other"
            self.chain_breaks[why] = self.chain_breaks.get(why, 0) + dispatches

    def record_decode_tokens(self, n: int = 1) -> None:
        if self.enabled:
            self.decode_tokens += n

    def record_waste(self, cause: str, tokens: int) -> None:
        if not self.enabled or tokens <= 0:
            return
        self.waste_by_cause[cause] = self.waste_by_cause.get(cause, 0) + int(
            tokens
        )

    def record_compile(
        self, label: str, seconds: float, split: Optional[dict] = None
    ) -> None:
        """A label's first dispatch (includes its XLA compile); `split` is
        what `first_dispatch_split` collected around it."""
        if not self.enabled:
            return
        if split:
            self._merge_first_dispatch(label, split)
        if len(self.compile_s_by_label) < MAX_LABELS or (
            label in self.compile_s_by_label
        ):
            self.compile_s_by_label[label] = max(
                self.compile_s_by_label.get(label, 0.0), float(seconds)
            )

    def record_recompile(
        self, label: str, cause: str, shape: Optional[str] = None
    ) -> None:
        """An *unexpected* post-warmup recompile (shape-bucket miss, or
        cache drift on a prebaked label). Always WARNs naming the
        offending shape — a recompile mid-serving is an SLO incident."""
        if not self.enabled:
            return
        self._count_recompile(label, cause)
        logger.warning(
            "unexpected recompile of %s (%s): offending shape %s — "
            "a serve-time XLA compile stalls every lane; widen the shape "
            "buckets or re-run tools/prebake_cache.py",
            label,
            cause,
            shape or "unknown",
        )

    def _count_recompile(self, label: str, cause: str) -> None:
        key = f"{label}|{cause}"
        if len(self.recompiles) < MAX_LABELS or key in self.recompiles:
            self.recompiles[key] = self.recompiles.get(key, 0) + 1

    def record_stall(self, label: str, parts: dict[str, float]) -> None:
        """A warm dispatch as far off its EMA as a recompile would be, but
        long outside the jitted call (`launch_parts`, `long_part`): nothing
        compiled, the host held it up. Counted beside the recompiles under
        the cause `stall`, and WARNed with where the time went."""
        if not self.enabled:
            return
        self._count_recompile(label, "stall")
        logger.warning(
            "stalled dispatch of %s: %.3f s, long in its %s (%s) — nothing "
            "compiled: the time is outside the jitted call (a fetch waits "
            "for the device, every part for the host's threads)",
            label,
            sum(parts.values()),
            long_part(parts),
            ", ".join(f"{k} {parts[k]:.3f}" for k in LAUNCH_PARTS),
        )

    def mark_idle(self) -> None:
        """Nothing in flight: the next dispatch's gap is idleness, not a
        phase bubble. Resets the bubble baseline."""
        self._last_end = None
        self._last_phase = None


class RecompileDetector:
    """Warm-label recompile heuristic shared by engine + tools.

    A label's first dispatch is its compile (by construction of jit);
    after warmup, a dispatch taking `factor`× its EMA *and* over an
    absolute floor is a recompile — python-side jitter can double a step,
    but only an XLA compile multiplies it by orders of magnitude while
    also crossing hundreds of ms.
    """

    def __init__(
        self,
        min_s: Optional[float] = None,
        factor: Optional[float] = None,
    ) -> None:
        self.min_s = (
            float(os.environ.get("DYN_RECOMPILE_MIN_S", "0.2"))
            if min_s is None
            else min_s
        )
        self.factor = (
            float(os.environ.get("DYN_RECOMPILE_FACTOR", "10"))
            if factor is None
            else factor
        )

    def is_recompile(self, elapsed_s: float, ema_s: float) -> bool:
        return elapsed_s >= self.min_s and elapsed_s >= self.factor * ema_s


def normalize_label(label: str) -> str:
    """Map a prebake program label to its dispatch label: prebake bakes
    per-shape programs (`prefill@2048`, `decode_multi@H4`, `decode_eos`)
    while the engine dispatches under the base label."""
    base = label.split("@", 1)[0]
    return "decode" if base == "decode_eos" else base


# label -> phase memo: record_step runs on EVERY dispatch and the label
# set is tiny and closed, so the string work happens once per label
_PHASE_CACHE: dict[str, str] = {}


def step_phase(label: str) -> str:
    """Phase family of a dispatch label for bubble attribution: every
    prefill-shaped program is "prefill", every token-producing one is
    "decode", and a unified step is its own "mixed" phase (it contains
    both, so it never forms a phase boundary with itself)."""
    phase = _PHASE_CACHE.get(label)
    if phase is None:
        base = normalize_label(label)
        if base.startswith("prefill"):
            phase = "prefill"
        elif base in ("decode", "decode_multi", "spec_verify"):
            phase = "decode"
        elif base == "mixed_step":
            phase = "mixed"
        else:
            phase = base
        if len(_PHASE_CACHE) < 4096:  # unbounded labels must not leak
            _PHASE_CACHE[label] = phase
    return phase


PREBAKE_MANIFEST = "prebake_manifest.json"


def load_prebaked_labels(cache_dir: Optional[str]) -> frozenset[str]:
    """Dispatch labels covered by a prior `tools/prebake_cache.py` run
    (read from the manifest it drops in the cache dir). Serve-time
    recompiles of these labels are counted as `prebake_miss` — the baked
    cache has drifted from the serve shapes."""
    if not cache_dir:
        return frozenset()
    path = os.path.join(cache_dir, PREBAKE_MANIFEST)
    try:
        import json

        with open(path) as f:
            doc = json.load(f)
        labels = doc.get("labels") or []
        return frozenset(normalize_label(str(x)) for x in labels)
    except (OSError, ValueError):
        return frozenset()


def write_prebake_manifest(
    cache_dir: Optional[str], programs: list
) -> Optional[str]:
    """Drop the manifest `load_prebaked_labels` reads; called by
    tools/prebake_cache.py after baking."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return None
    import json

    path = os.path.join(cache_dir, PREBAKE_MANIFEST)
    doc = {
        "labels": sorted({normalize_label(lbl) for lbl, _ in programs}),
        "programs": [[lbl, s] for lbl, s in programs],
        "baked_at": time.time(),
    }
    try:
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    except OSError:
        return None
    return path
