"""JaxEngine: continuous-batching AsyncEngine over the ModelRunner.

The scheduler mirrors what the reference's workers get from vLLM (and what
its mocker simulates — lib/llm/src/mocker/scheduler.rs): FIFO admission with
a block watermark, iteration-level batching (admit prefills between decode
steps), LIFO preemption under block pressure, per-token streaming. The
asyncio loop overlaps host scheduling with device execution by syncing
sampled tokens in a worker thread.

KV events (block stored/removed) are emitted through hooks with the same
hash-chain identity the router indexes — the engine IS the KV event source
(no ZMQ shim needed; we own the engine).
"""

from __future__ import annotations

import asyncio
import bisect
import contextlib
import itertools
import os
import time
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Callable, Optional

import numpy as np

from dynamo_tpu import qos
from dynamo_tpu.telemetry import brownout as dbrownout
from dynamo_tpu.testing import faults

from dynamo_tpu.engine.jax_engine.kv_cache import (
    BlockAllocator,
    OutOfBlocks,
    SequenceState,
)
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.ops.sampling import draw_restrictions, surface_wanted
from dynamo_tpu.pipeline.context import Context, decisions_of
from dynamo_tpu.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
)
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.telemetry import provenance as dprov
from dynamo_tpu.telemetry import trace as dtrace
from dynamo_tpu.telemetry.goodput import (
    GoodputLedger,
    RecompileDetector,
    first_dispatch_split,
    launch_parts,
    load_prebaked_labels,
    long_part,
    normalize_label,
    step_phase,
)
from dynamo_tpu.telemetry.histogram import PhaseHistograms
from dynamo_tpu.tokens import TokenBlockSequence

logger = get_logger("dynamo_tpu.engine")


@dataclass
class JaxEngineConfig:
    max_batch: int = 8
    block_size: int = 16
    num_blocks: int = 512
    max_model_len: int = 2048
    watermark_blocks: int = 8  # admission reserve
    rng_seed: int = 0
    # decode horizon: H chained decode steps per device dispatch (ONE
    # host<->device round trip per H tokens instead of one per token).
    # 1 = classic per-token stepping.
    # Penalty batches ride the horizon via on-device count tables; only
    # min_tokens + more stop ids than the device mask carries falls back
    # to single-step for that iteration.
    decode_horizon: int = 1
    # mid-generation offload rate limit: max blocks copied to the host
    # tier per engine-loop iteration (reference offload.rs bounds its
    # transfer-manager queues the same way — copies must not crowd the
    # decode latency path)
    offload_per_step: int = 4
    # Self-drafting speculative decoding (0 = off): a host-side n-gram /
    # prompt-lookup drafter proposes up to spec_k tokens per lane and the
    # model verifies all k+1 positions in ONE weight pass
    # (runner.spec_verify). On a weight-bandwidth-bound chip each accepted
    # draft token is a token that skipped a full ~8 GB weight read. The
    # accept rule keeps the stream bit-identical to non-speculative
    # decoding under greedy AND temperature sampling (per-position threefry
    # counters line up with the per-token path). Composes with
    # decode_horizon: the dispatch chains horizon-1 plain decode steps
    # after the verify pass on device.
    spec_k: int = 0
    spec_drafter: str = "ngram"
    spec_ngram_min: int = 2
    spec_ngram_max: int = 4
    # minimum fraction of active lanes that must carry a draft before a
    # verify dispatch replaces a plain decode step: non-drafting lanes pay
    # the verify pass's extra logits columns for a single token, so a
    # sparsely-drafted batch is a net loss on FLOP-bound backends. On a
    # weight-bandwidth-bound chip the verify premium is small — deploy
    # with a lower value there (DYN_SPEC_COVERAGE).
    spec_min_coverage: float = 0.5
    # Stuck-horizon watchdog: a dispatch that exceeds watchdog_mult × its
    # EMA (floored at watchdog_min_s once warm; watchdog_cold_s covers the
    # first dispatch of a label, which includes its XLA compile) trips the
    # watchdog — the engine fails every lane with a structured error, stops
    # admitting, and fires on_watchdog_trip (discovery deregistration)
    # instead of hanging every stream. watchdog_min_s <= 0 disables.
    watchdog_mult: float = field(
        default_factory=lambda: float(os.environ.get("DYN_WATCHDOG_MULT", "8"))
    )
    watchdog_min_s: float = field(
        default_factory=lambda: float(os.environ.get("DYN_WATCHDOG_MIN_S", "30"))
    )
    watchdog_cold_s: float = field(
        default_factory=lambda: float(
            os.environ.get("DYN_WATCHDOG_COLD_S", "300")
        )
    )
    # Preemption-storm guard: a sequence preempted more than
    # max_preemptions times fails with a structured `preempted_too_often`
    # error instead of thrashing the cache forever; each re-queue also
    # waits out an exponential re-admission backoff (base
    # preempt_backoff_ms, doubled per preemption, capped at 2 s) so a
    # sustained-pressure victim stops ping-ponging with its preemptor.
    max_preemptions: int = field(
        default_factory=lambda: int(os.environ.get("DYN_MAX_PREEMPTIONS", "8"))
    )
    preempt_backoff_ms: float = field(
        default_factory=lambda: float(
            os.environ.get("DYN_PREEMPT_BACKOFF_MS", "25")
        )
    )
    # Unified mixed steps (ISSUE 16): the per-STEP prefill token budget —
    # how many prompt tokens may ride along the decode batch inside one
    # device program (chunks of several prompts can share a step). 0
    # resolves to two chunks' worth (2 × runner.prefill_chunk_tokens) at
    # engine init. Brownout's chunk_cap rung halves the effective value
    # (qos.effective_chunk_budget); the loop latches it once per step
    # boundary so a mid-step ladder transition never re-slices a chunk
    # already being packed.
    chunk_budget: int = field(
        default_factory=lambda: int(os.environ.get("DYN_CHUNK_BUDGET", "0"))
    )
    # Master toggle for the mixed stepper. Off restores the alternating
    # chunk-then-decode loop; the output streams are bit-identical either
    # way (the token-identity parity test pins this), only the step
    # schedule — and with it the phase bubble — changes.
    mixed_step: bool = True


@dataclass
class EngineStats:
    """Live load/cache stats (feeds WorkerMetricsPublisher, M5)."""

    active_slots: int = 0
    total_slots: int = 0
    waiting: int = 0
    used_blocks: int = 0
    total_blocks: int = 0
    generated_tokens: int = 0
    # items put on sequences' streams that carried tokens: one holds what
    # one dispatch produced for one sequence, so generated tokens that were
    # streamed, over stream_items, is the tokens an item (the ledger's
    # `stream` slot counts both at the flush)
    stream_items: int = 0
    # speculative decoding counters (SpecDecodeStats wire fields): one
    # "draft" = one lane-dispatch that carried >= 1 proposed token; all
    # monotonic over the engine's lifetime
    num_spec_tokens: int = 0  # configured spec_k (0 = spec off)
    num_drafts: int = 0
    num_draft_tokens: int = 0
    num_accepted_tokens: int = 0
    accepted_per_pos: list = field(default_factory=list)  # len spec_k
    # request lifeguard counters (monotonic; ride load_metrics to the
    # metrics plane): requests cancelled on deadline/TTFT expiry, and
    # stuck-horizon watchdog trips
    deadline_exceeded: int = 0
    watchdog_trips: int = 0
    # KV data-plane counters (streaming disagg, PR 4): tx = this worker in
    # its prefill role shipping frames; rx = this worker in its decode
    # role landing them. kv_bytes_overlapped counts payload bytes that
    # landed BEFORE the final frame — i.e. transfer hidden behind the
    # prefill compute still running on the remote worker.
    kv_frames_tx: int = 0
    kv_frames_rx: int = 0
    kv_wire_bytes_tx: int = 0
    kv_wire_bytes_rx: int = 0
    kv_bytes_overlapped: int = 0
    kv_frames_inflight: int = 0  # gauge (prefill role, bounded window)
    prefill_dropped_expired: int = 0  # queue entries dropped past deadline
    # QoS plane (ISSUE 7): per-class preemption counts (class-aware
    # KV-preserving preemption — bulk absorbs pressure first), storm-guard
    # kills, engine-side brownout sheds, and the live brownout rung
    preemptions_by_class: dict = field(default_factory=dict)
    preempted_too_often: int = 0
    shed_brownout: int = 0
    brownout_level: int = 0  # gauge
    # fleet prefix cache: prefix blocks pulled from peers instead of
    # recomputed, by outcome (peer.PULL_OUTCOMES keys; monotonic) —
    # mirrored from PeerBlockClient.pull_outcomes each stats refresh
    kv_pull_outcomes: dict = field(default_factory=dict)
    # always-on per-phase latency distributions (queue_wait / prefill /
    # ttft / inter_token / e2e) on the shared fixed-log bucket grid;
    # shipped on ForwardPassMetrics and merged fleet-wide by bucket
    # addition (telemetry/histogram.py). Unlike spans (DYN_TRACE-gated),
    # an observe() is a bisect + two adds — cheap enough to never gate.
    phase_histograms: PhaseHistograms = field(default_factory=PhaseHistograms)
    # goodput ledger (ISSUE 14): per-device-step efficiency accounting —
    # step-duration histograms by dispatch label, occupancy, phase
    # bubbles, the token-waste taxonomy, compile/recompile forensics, and
    # achieved MFU/HBM gauges. Always-on (DYN_GOODPUT=0 disables); ships
    # on ForwardPassMetrics and merges fleet-wide like the histograms.
    goodput: GoodputLedger = field(default_factory=GoodputLedger)

    @property
    def kv_usage(self) -> float:
        return self.used_blocks / max(1, self.total_blocks)

    @property
    def kv_stream_overlap(self) -> float:
        """Fraction of received KV wire bytes that landed before the final
        frame (transfer overlapped behind remote prefill compute)."""
        return self.kv_bytes_overlapped / max(1, self.kv_wire_bytes_rx)

    @property
    def draft_acceptance_rate(self) -> float:
        return self.num_accepted_tokens / max(1, self.num_draft_tokens)


class _Sequence(SequenceState):
    def __init__(self, seq_id: int, request: PreprocessedRequest, ctx: Context):
        super().__init__(
            seq_id=seq_id,
            token_ids=list(request.token_ids),
            num_prompt=len(request.token_ids),
        )
        # in-flight migration replay (router re-drives a dead worker's
        # request here): the tail of token_ids past resume_prompt_len is
        # output a previous worker already streamed — counting it as
        # GENERATED keeps max_tokens budgets, min_tokens, and the per-token
        # threefry counters (_key_row: counter = num_generated) exactly
        # where the unfaulted run would have them, so the resumed stream is
        # bit-identical under greedy and seeded sampling.
        resume = int(request.extra.get("resume_prompt_len") or 0)
        if 0 < resume < len(request.token_ids):
            self.num_prompt = resume
        self.request = request
        self.ctx = ctx
        # QoS class resolved at the edge (qos.stamp_priority): rides
        # Context.metadata across the wire, PreprocessedRequest.extra as
        # the transport-less fallback. Orders the waiting queue and picks
        # preemption victims (bulk first).
        self.priority = qos.priority_of(ctx, request)
        self.rank = qos.rank_of(self.priority)
        self.arrival_order = 0  # engine-assigned FIFO tiebreak
        self.preemptions = 0  # storm guard: count + re-admission backoff
        self.requeue_after = 0.0  # monotonic; 0 = admissible now
        self.deadline_fired = False  # structured deadline error sent once
        self.pending_remote = False  # admitted, awaiting remote prefill KV
        self.prefilling = False  # admitted, chunked prefill in progress
        self.prefill_pos = 0  # tokens already prefilled into the cache
        self.prefix_hashes: list[int] = []  # full-block hash chain
        self.cached_prefix_blocks = 0  # leading blocks found in G2/G3
        self.pending_chain: Optional[TokenBlockSequence] = None  # prebuilt
        self.out: asyncio.Queue = asyncio.Queue()
        # the tokens of the dispatch being replayed, not yet on `out`
        # (JaxEngine._append_token gathers, _flush_streams puts)
        self.pending: Optional[LLMEngineOutput] = None
        self.eos: set[int] = set()
        if not request.stop.ignore_eos:
            self.eos = set(request.eos_token_ids) | set(
                request.stop.stop_token_ids_hidden
            )
        s = request.sampling
        self.temperature = 0.0 if s.greedy else (
            s.temperature if s.temperature is not None else 1.0
        )
        self.top_p = s.top_p if s.top_p is not None else 1.0
        self.top_k = s.top_k if s.top_k is not None else 0
        # the device sampler draws restricted rows from a top-
        # SAMPLE_CANDIDATES pool; clamp here (with a log) so the behavior
        # is declared once instead of silently applied on device
        from dynamo_tpu.ops.sampling import SAMPLE_CANDIDATES

        if self.top_k > SAMPLE_CANDIDATES:
            logger.warning(
                "seq %d: top_k=%d clamped to the device sampler's "
                "candidate pool (%d)",
                seq_id, self.top_k, SAMPLE_CANDIDATES,
            )
            self.top_k = SAMPLE_CANDIDATES
        self.max_new = request.stop.max_tokens or 16
        self.min_tokens = request.stop.min_tokens or 0
        # penalties + per-request RNG stream + logprobs (reference
        # validate.rs:95-125 — implemented, not accepted-and-dropped)
        self.freq_pen = float(s.frequency_penalty or 0.0)
        self.pres_pen = float(s.presence_penalty or 0.0)
        self.rep_pen = float(s.repetition_penalty or 1.0) or 1.0
        self.has_penalties = bool(
            self.freq_pen or self.pres_pen or self.rep_pen != 1.0
        )
        self.seed = s.seed
        self.want_logprobs = bool(s.logprobs)
        self.num_top_lp = min(int(s.top_logprobs or 0), 20)
        # min_tokens: EOS logits are masked ON DEVICE until the minimum is
        # generated (appending a suppressed EOS would still stop the
        # HTTP-layer decoder); first MAX_EOS_IDS ids ride into the program
        from dynamo_tpu.ops.sampling import MAX_EOS_IDS

        self.eos_row = np.full(MAX_EOS_IDS, -1, np.int32)
        for j, t in enumerate(sorted(self.eos)[:MAX_EOS_IDS]):
            self.eos_row[j] = t
        self.eos_drops = 0  # suppressed-EOS resamples past the device mask
        self.offload_mark = 0  # chain blocks already queued for offload
        # speculative-decoding backoff: fully-rejected drafts cost a whole
        # verify premium for nothing, so a lane whose history stops
        # predicting (generated loops that drift, low-repetition text)
        # exponentially backs off drafting until a draft lands again
        self.spec_fail = 0
        self.spec_backoff = 0
        # open telemetry phase spans (queue_wait / prefill / decode / ...)
        self.spans: dict = {}
        # always-on phase-timing marks (feed EngineStats.phase_histograms)
        self.t_arrival = time.monotonic()
        self.t_admitted: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None

    @property
    def needs_eos_suppress(self) -> bool:
        return (
            self.min_tokens > 0
            and self.num_generated < self.min_tokens
            and bool(self.eos)
        )

    @property
    def num_generated(self) -> int:
        return len(self.token_ids) - self.num_prompt

    @property
    def kv_written(self) -> int:
        """Positions whose KV is actually in the device cache. A sampled
        token's KV is only written when it is FED on the next decode step,
        so the newest appended token is always unwritten — offloading a
        block that contains it would store a hole and corrupt every later
        onboard of that hash."""
        return self.num_prompt + max(0, self.num_generated - 1)


# why a dispatch that is no `decode_multi` was not chained, by the family of
# its label (goodput.step_phase): an arrival's prefill, a step that carries
# somebody's chunk; the decode family's callers say it themselves
_WHY_BY_PHASE = {"prefill": "arrival", "mixed": "prefilling"}


@dataclass(eq=False)
class _Flight:
    """A `decode_multi` dispatch on the device's queue whose tokens the host
    has not read yet. The engine keeps at most one between two passes of its
    loop, and a second, chained on the first's carry, while it waits for the
    first's fetch (`JaxEngine._decode_multi_phase`)."""

    label: str
    lanes: list  # the sequences it was launched with: its replay walks them
    H: int
    # when its time began for the watchdog, the EMA and the ledger: its own
    # launch, or the return of the fetch before it where that came later
    since: float = 0.0
    packed: Any = None  # the runner's device array, read when it lands
    # blocks of lanes the host alone ended while this dispatch named them
    # (`_free_seq`): given back when it has landed
    held: list = field(default_factory=list)
    # window blocks that left their window while this dispatch's tables
    # still named them (`_give_back_window`): given back when it has landed
    held_window: list = field(default_factory=list)
    # what its own launch took where that was a call of its own, and so
    # part of its time (`runner.upload`, `runner.enqueue`: seconds)
    launch_s: tuple = (0.0, 0.0)
    # the dispatch before it, while that one's fetch is awaited
    prev: Optional["_Flight"] = None

    def names(self, seq) -> bool:
        return any(s is seq for s in self.lanes) or (
            self.prev is not None and self.prev.names(seq)
        )


class JaxEngine:
    """AsyncEngine implementation backed by a ModelRunner."""

    def __init__(
        self,
        runner: ModelRunner,
        config: Optional[JaxEngineConfig] = None,
        on_blocks_stored: Optional[Callable[[list[dict]], None]] = None,
        on_blocks_removed: Optional[Callable[[list[int]], None]] = None,
        disagg_router: Optional[Any] = None,
        remote_prefill_client: Optional[Any] = None,
        block_manager: Optional[Any] = None,
        peer_block_client: Optional[Any] = None,
    ) -> None:
        self.runner = runner
        self.config = config or JaxEngineConfig(
            max_batch=runner.max_batch,
            block_size=runner.block_size,
            num_blocks=runner.num_blocks,
            max_model_len=runner.max_model_len,
        )
        # a model whose paged layers are of two groups (window layers beside
        # full ones) has a pool a group; the runner builds the window
        # group's tables from the allocator's own map of companions
        self._window = None
        window_blocks = getattr(runner, "window_blocks", 0)
        self.allocator = BlockAllocator(self.config.num_blocks, window_blocks)
        if window_blocks:
            self._window = runner.page_groups[1].window
            runner.window_of = self.allocator.window_of
        self.slots: list[Optional[_Sequence]] = [None] * self.config.max_batch
        # priority-then-deadline ordered admission queue (kept sorted by
        # _enqueue): (class rank, deadline, arrival) — interactive overtakes
        # bulk, and within a class the tightest deadline goes first
        self.waiting: list[_Sequence] = []
        self._arrivals = itertools.count(1)
        # brownout ladder rung applied by the host wiring (apply_brownout):
        # >=1 sheds bulk arrivals, >=2 pauses spec decode, >=3 caps the
        # prefill-chunk budget, >=4 sheds standard arrivals too
        self._brownout_level = 0
        self._spec_paused = False
        # long prompts being prefilled one chunk at a time; the loop runs
        # one chunk then a decode step so decode never stalls > one chunk
        self._prefilling: list[_Sequence] = []
        # unified mixed steps (ISSUE 16): resolved per-step prefill token
        # budget (config 0 -> two chunks' worth) and the cap on chunk
        # slots per mixed program (one compiled variant per slot count —
        # tools/prebake_cache.py bakes the same range)
        chunk_tokens = getattr(runner, "prefill_chunk_tokens", 0) or 0
        base = self.config.chunk_budget
        if base <= 0:
            base = 2 * chunk_tokens
        self._chunk_budget_base = base if chunk_tokens else 0
        self._mixed_max_slots = (
            max(1, -(-self._chunk_budget_base // chunk_tokens))
            if chunk_tokens
            else 0
        )
        self._mixed_enabled = (
            self.config.mixed_step
            and chunk_tokens > 0
            and hasattr(runner, "mixed_step")
        )
        # budgets latched once per loop iteration (step boundary): a
        # brownout transition landing while a dispatch is in flight takes
        # effect at the NEXT boundary, never mid-pack
        self._step_chunk_tokens = chunk_tokens
        self._step_chunk_budget = self._chunk_budget_base
        self._seq_ids = itertools.count(1)
        self._admit_order: list[_Sequence] = []  # for LIFO preemption
        self._loop_task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._closed = False
        self._fenced = False  # self-fenced on primary-lease loss
        self.stats = EngineStats(
            total_blocks=self.config.num_blocks - 1,
            total_slots=self.config.max_batch,
        )
        # self-drafting speculative decoding (spec_k > 0 and a runner that
        # carries the verify program)
        self.drafter = None
        if self.config.spec_k > 0 and hasattr(runner, "spec_verify"):
            from dynamo_tpu.engine.jax_engine.drafter import make_drafter

            self.drafter = make_drafter(
                self.config.spec_drafter,
                self.config.spec_k,
                min_n=self.config.spec_ngram_min,
                max_n=self.config.spec_ngram_max,
            )
            self.stats.num_spec_tokens = self.config.spec_k
            self.stats.accepted_per_pos = [0] * self.config.spec_k
        self.on_blocks_stored = on_blocks_stored
        self.on_blocks_removed = on_blocks_removed
        # fired by clear_kv_blocks so routers drop this worker's radix state
        self.on_cache_cleared: Optional[Callable[[], None]] = None
        # fired (once) when the stuck-horizon watchdog trips: the host
        # wiring deregisters this worker from discovery so routers stop
        # sending (entrypoint/inputs.run_endpoint)
        self.on_watchdog_trip: Optional[Callable[[], None]] = None
        # stuck-horizon watchdog state: the in-flight dispatch (label, t0)
        # and an EMA of past dispatch durations per label
        self._dispatch_info: Optional[tuple[str, float]] = None
        self._dispatch_ema: dict[str, float] = {}
        # the `decode_multi` dispatch left on the device's queue between two
        # passes of the loop (steady decode launches ahead), why the last
        # one was landed or none was left (of goodput.CHAIN_BREAKS: what the
        # next unchained `decode_multi` counts), and why `_horizon_for`
        # last answered 1
        self._flight: Optional[_Flight] = None
        self._chain_why = "other"
        self._horizon_why = "other"
        self._watchdog_task: Optional[asyncio.Task] = None
        self._tripped = False
        # recompile forensics (ISSUE 14): a warm label dispatching far off
        # its EMA is an unexpected serve-time XLA compile; labels covered
        # by tools/prebake_cache.py count separately (cache drift)
        self._recompile = RecompileDetector()
        from dynamo_tpu.runtime.config import jax_cache_dir

        self._prebaked_labels = load_prebaked_labels(jax_cache_dir())
        # Disaggregation (SURVEY §7.6): when both are set, long prompts are
        # shipped to the prefill fleet instead of running locally.
        self.disagg_router = disagg_router
        self.remote_prefill_client = remote_prefill_client  # checked setter
        # Tiered KV offload (KVBM equivalent): blocks are copied to the
        # host/disk tiers keyed by sequence hash — mid-generation at block
        # boundaries (rate-limited through the priority queue below, like
        # the reference's register-time offload in offload.rs), at
        # preemption time, and in bulk at sequence completion — and
        # onboarded on later prefix hits.
        self.block_manager = block_manager
        self._offload_queue = None
        if block_manager is not None:
            self.runner.require_block_transfer("a tiered block manager")
            from dynamo_tpu.block_manager.offload import OffloadQueue

            self._offload_queue = OffloadQueue()
        # G4-lite (block_manager/peer.py): pull a missing prefix from a
        # peer worker's host tier instead of recomputing it
        self.peer_block_client = peer_block_client
        self._remote_tasks: set[asyncio.Task] = set()
        # Landed remote prefills / failures, processed by the engine loop so
        # _append_token (which can preempt and reallocate blocks) never runs
        # concurrently with an in-flight decode step.
        # entries: (seq, sample | None, fail); sample = (token, logprob,
        # top [[id, lp], ...]) — logprobs ride along so the first token's
        # entry isn't missing from logprobs responses
        self._landed: list[tuple[_Sequence, Optional[tuple], Optional[FinishReason]]] = []
        # sequences whose `pending` item holds tokens of the dispatch being
        # replayed (_append_token adds, _flush_streams empties)
        self._unflushed: list[_Sequence] = []
        # Serializes every runner call: the cache arrays are DONATED through
        # prefill/decode/inject, so a concurrent caller (remote-prefill
        # landing, prefill_only service task) would read a deleted array.
        self._device_lock = asyncio.Lock()
        # hash -> number of active sequences that emitted a Stored for it;
        # Removed is only published when the LAST holder frees (the router
        # tree would otherwise lose blocks other sequences still cache)
        self._hash_refs: dict[int, int] = {}
        # layers that keep one state slot a sequence instead of rows per
        # token (what the runner's model declares; 0 for a paged-only model):
        # their prefill calls are told each sequence's lane slot, and no
        # block hash is published (`_emit_stored`)
        self._recurrent_layers = getattr(self.runner, "recurrent_layers", 0)
        # persistent host-side decode arrays
        B = self.config.max_batch
        self._tokens = np.zeros(B, np.int32)
        self._positions = np.zeros(B, np.int32)
        self._block_tables = np.zeros(
            (B, getattr(
                self.runner, "table_width", self.runner.max_blocks_per_seq
            )), np.int32,
        )
        self._slot_indices = np.zeros(B, np.int32)
        self._temps = np.ones(B, np.float32)
        self._top_ps = np.ones(B, np.float32)
        self._top_ks = np.zeros(B, np.int32)
        # lanes whose request set `logprobs`: the sampler computes the
        # log-prob surface only in a step where one of them is set
        self._want_lps = np.zeros(B, bool)
        self._keys = np.zeros((B, 2), np.uint32)
        # unseeded sequences draw from (engine seed base + seq_id) streams:
        # deterministic per engine run AND stable across preemption replay
        self._seed_base = (self.config.rng_seed ^ 0x9E3779B9) & 0x7FFFFFFF
        # trace process track (set by the worker host; None = process name)
        self.trace_proc: Optional[str] = None

    # ----------------------------------------------------------- telemetry

    def _sp_begin(self, seq: _Sequence, name: str, **attrs) -> None:
        sp = dtrace.begin(name, ctx=seq.ctx, proc=self.trace_proc, **attrs)
        if sp is not None:
            seq.spans[name] = sp

    def _sp_finish(self, seq: _Sequence, name: str, **attrs) -> None:
        dtrace.finish(seq.spans.pop(name, None), **attrs)

    def _sp_event(self, seq: _Sequence, name: str, **attrs) -> None:
        """Attach a point event to the sequence's (single) open span."""
        for sp in seq.spans.values():
            sp.event(name, **attrs)
            return

    def _sp_close_all(self, seq: _Sequence) -> None:
        for name in list(seq.spans):
            self._sp_finish(seq, name)

    def _observe_stream(self, seq: _Sequence, item: LLMEngineOutput) -> None:
        """Always-on phase histogram recording at the stream edge (what a
        consumer of this worker actually experiences): TTFT, prefill (the
        admitted-to-first-token span), inter-token gaps, end-to-end. An item
        of n tokens is n observations of its arrival gap over n (a first
        item's tokens past the first arrived with it: gaps of 0), so the
        inter-token count is the streamed tokens less one a stream and its
        sum the last arrival less the first."""
        ph = self.stats.phase_histograms
        now = time.monotonic()
        n = len(item.token_ids)
        if n:
            gap_ms = 0.0
            if seq.t_first is None:
                seq.t_first = now
                ph.observe("ttft", (now - seq.t_arrival) * 1e3)
                if seq.t_admitted is not None:
                    waited = now - seq.t_admitted
                    ph.observe("prefill", waited * 1e3)
                    dtrace.observe_phase("prefill_wait", int(waited * 1e9))
                n -= 1  # the first token's gap is the TTFT
            else:
                gap_ms = (now - seq.t_last) * 1e3 / n
            if n:
                ph.observe("inter_token", gap_ms, n)
            seq.t_last = now
        if item.finish_reason is not None:
            ph.observe("e2e", (now - seq.t_arrival) * 1e3)

    # --------------------------------------------------------------- api

    async def generate(
        self, request: PreprocessedRequest, context: Context
    ) -> AsyncIterator[LLMEngineOutput]:
        """Stream a request's output. An item carries what one dispatch
        produced for the sequence (one token from a prefill or a single
        step, a horizon's several, a verify pass's accepted run; `log_probs`
        and `top_logprobs` position for position where asked for), put on
        the stream once when the dispatch is replayed; the finish, or a
        structured error, follows as an item of its own."""
        if self._fenced:
            yield LLMEngineOutput.final_error(
                context.id, "admission",
                "worker is fenced (primary lease lost); request must be "
                "served elsewhere",
                "worker_fenced",
            )
            return
        if self._closed:
            yield LLMEngineOutput.final_error(
                context.id, "admission",
                "engine is closed or marked unhealthy",
                "worker_unavailable",
            )
            return
        if context.expired() or context.ttft_expired():
            self.stats.deadline_exceeded += 1
            yield LLMEngineOutput.final_error(
                context.id, "admission",
                "deadline expired before admission",
                "deadline_exceeded",
            )
            return
        if len(request.token_ids) > self.config.max_model_len:
            yield LLMEngineOutput.final_error(
                context.id, "admission",
                f"prompt of {len(request.token_ids)} tokens exceeds "
                f"max_model_len {self.config.max_model_len}",
                "prompt_too_long",
            )
            return
        if self._brownout_level:
            # engine-side brownout shed (direct-engine deployments; a
            # fronted fleet sheds at the HTTP AdmissionController first)
            prio = qos.priority_of(context, request)
            if prio in dbrownout.shed_classes_for(self._brownout_level):
                self.stats.shed_brownout += 1
                yield LLMEngineOutput.final_error(
                    context.id, "admission",
                    f"brownout level {self._brownout_level} "
                    f"({dbrownout.LADDER[self._brownout_level]}) sheds "
                    f"{prio}-class requests",
                    "brownout_shed",
                )
                return
        seq = _Sequence(next(self._seq_ids), request, context)
        if seq.num_prompt < len(request.token_ids):
            # in-flight migration resume: the tail past resume_prompt_len
            # was already streamed by a dead worker, but its KV must be
            # re-prefilled here — replayed work, not new goodput
            self.stats.goodput.record_waste(
                "migration_replay", len(request.token_ids) - seq.num_prompt
            )
        if dtrace.enabled():
            self._sp_begin(
                seq, "queue_wait",
                tokens=len(request.token_ids), priority=seq.priority,
            )
        self._enqueue(seq)
        self._ensure_loop()
        self._wake.set()
        try:
            while True:
                item = await seq.out.get()
                self._observe_stream(seq, item)
                yield item
                if item.finish_reason is not None:
                    return
        finally:
            # consumer went away (kill/disconnect): let the loop reap it
            context.kill()
            self._wake.set()

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(
                self._engine_loop()
            )
            self._loop_task.add_done_callback(self._on_loop_done)
        if (
            self.config.watchdog_min_s > 0
            and not self._tripped
            and (self._watchdog_task is None or self._watchdog_task.done())
        ):
            self._watchdog_task = asyncio.get_running_loop().create_task(
                self._watchdog_loop()
            )

    def _on_loop_done(self, task: asyncio.Task) -> None:
        """If the engine loop dies (e.g. a compile error on the first real
        batch), every parked generate() consumer would otherwise wait on
        its queue forever. Fail them all loudly — each sequence gets a
        structured error (request id, phase, cause) that reaches its SSE
        stream as a typed error event — and free/unpublish their KV blocks."""
        if task.cancelled():
            return
        exc = task.exception()
        if exc is None or self._closed:
            return
        logger.error("engine loop crashed: %r — failing all sequences", exc)
        cause = f"engine loop crashed: {type(exc).__name__}: {exc}"
        for seq in list(self.waiting):
            self.waiting.remove(seq)
            self._sp_close_all(seq)
            self._send_final(
                seq,
                LLMEngineOutput.final_error(
                    seq.ctx.id, "queue", cause, "engine_loop_crash"
                ),
            )
        # _finish_error frees the slot + KV blocks (and publishes Removed)
        # too: a restarted loop must not keep decoding zombie lanes that no
        # consumer is reading. Sequences with an in-flight remote-prefill
        # inject keep their blocks (the late inject would otherwise land in
        # recycled blocks and corrupt a new sequence — same hazard
        # _reap_cancelled guards); their killed context gets them reaped
        # once the inject lands.
        for seq in list(self._admit_order):
            if seq.pending_remote:
                seq.ctx.kill()
                self._send_final(
                    seq,
                    LLMEngineOutput.final_error(
                        seq.ctx.id, "remote_prefill", cause,
                        "engine_loop_crash",
                    ),
                )
            else:
                self._finish_error(
                    seq, "decode", cause, "engine_loop_crash"
                )

    # ---------------------------------------------------------- watchdog

    # Disaggregated prefill and peer pulls are wired by plain assignment
    # after the engine is built (graphs/disagg.py): the setters refuse, at
    # that moment and in words, a runner whose cache blocks cannot travel.
    @property
    def remote_prefill_client(self):
        return self._remote_prefill_client

    @remote_prefill_client.setter
    def remote_prefill_client(self, client) -> None:
        if client is not None:
            self.runner.require_block_transfer("disaggregated prefill")
        self._remote_prefill_client = client

    @property
    def peer_block_client(self):
        return self._peer_block_client

    @peer_block_client.setter
    def peer_block_client(self, client) -> None:
        if client is not None:
            self.runner.require_block_transfer("a peer block pull")
        self._peer_block_client = client

    async def _dispatch(
        self,
        label: str,
        fn,
        *,
        lanes: int = 0,
        capacity: int = 0,
        tokens: int = 0,
        ctx_tokens: int = 0,
        horizon: int = 1,
        state_resets: int = 0,
        why: Optional[str] = None,
        launches: Optional[_Flight] = None,
        lands: Optional[_Flight] = None,
    ) -> Any:
        """Run one device dispatch in the executor, visible to the
        stuck-horizon watchdog (and to fault injection). Callers hold
        self._device_lock. `lanes`/`capacity` (decode-family steps) and
        `tokens` (prefill chunk size) feed the goodput ledger;
        `ctx_tokens` (summed context of the live lanes) and `horizon` ride
        the `loop.dispatch` phase into an open profile window, and so does
        `pool`: whether a decode-family dispatch (`capacity` given; its
        lane arrays were packed just before) holds a sampled lane that
        restricts its draw, the sampler's own predicate asked of the same
        three arrays the program is about to receive; `logprobs` likewise,
        whether a lane of it asked for log-probs (`surface_wanted` of the
        fourth). For a model with
        recurrent layers `state_slots` (lanes that hold a sequence, and so a
        state) rides the phase too, and the ledger's `ssm` slot counts the
        dispatch from the same host-side numbers; `state_resets` is how many
        sequences start at position 0 in it.

        As a rule `fn` launches a program and reads its result: one
        dispatch, whole. The engine's launch ahead splits the two. With
        `launches`, `fn` launches that `decode_multi` and leaves it on the
        device's queue (`self._flight`); with `lands`, `fn` reads the result
        of the one launched before; with both, in that order: the new one is
        queued behind the old before the old one's tokens are waited for.
        What belongs to a launch (fault injection, the ledger's `launch`,
        `sampler` and `ssm`, and `why` it was not chained, of
        goodput.CHAIN_BREAKS) is recorded for the dispatch this call
        launches; what belongs to a dispatch's time (the watchdog, the EMA,
        the ledger's step record) for the one whose result it reads, from
        `lands.since` on: the older of the two."""
        launching = launches is not None or lands is None
        pool = launching and capacity > 0 and bool(
            draw_restrictions(self._temps, self._top_ps, self._top_ks)[1]
        )
        logprobs = launching and capacity > 0 and bool(
            surface_wanted(self._want_lps)
        )
        slow_factor = 1.0
        if launching and faults.active():
            inj = faults.get_injector()
            if inj is not None:
                await inj.on_dispatch()
                slow_factor = inj.dispatch_slow_factor()

        # a model with a window group of pages: what this dispatch's decode
        # steps must read and what the pools hold, from the host's numbers
        pool_counts = (
            self._pool_counts(horizon)
            if launching and capacity > 0 and self._window is not None
            else None
        )
        first = label not in self._dispatch_ema
        split: dict = {}  # FIRST_DISPATCH_FIELDS, filled by a first dispatch
        launch = self.runner.launch  # what the call costs at the device's edge

        # event-loop thread: hop to the executor, upload, launch, fetch,
        # and the wait for the event loop to resume this task. The phase's
        # own pair of clock readings is the dispatch's start and length for
        # the watchdog, the EMA and the ledger too
        dispatch = dtrace.phase(
            "loop.dispatch", label=label, lanes=lanes,
            ctx_tokens=ctx_tokens, prefill_tokens=tokens,
            horizon=horizon, first=first,
            pool=int(pool), logprobs=int(logprobs),
            state_slots=(
                sum(s is not None for s in self.slots)
                if self._recurrent_layers else 0
            ),
            **(
                {k: pool_counts[k] for k in ("window_rows", "full_rows")}
                if pool_counts else {}
            ),
        )
        call = dtrace.phase("runner.call", label=label)

        def run():
            # executor thread: the runner function and its fetch, each
            # part a child phase of the runner's own (`runner.upload`,
            # `runner.enqueue`, which the first time holds JAX's trace,
            # lowering and compile or cache read, and `runner.fetch`)
            launch.clear()
            with call:
                if not first:
                    return fn()
                with first_dispatch_split(split):
                    return fn()

        loop = asyncio.get_running_loop()
        try:
            with dispatch:
                t0 = dispatch.start_s
                if lands is None:
                    # nothing older is on the device: this one's time runs
                    self._dispatch_info = (label, t0)
                if launches is not None:
                    # from here on a lane the host ends is named by it
                    launches.since = t0
                    launches.prev = lands
                    self._flight = launches
                result = await loop.run_in_executor(None, run)
                if slow_factor > 1.0:
                    # injected gray-worker fault: stretch the dispatch to
                    # FACTOR times its real duration (the device did the
                    # work; the worker is throttled, not wedged — the
                    # watchdog's EMA budget tracks the stretched time so it
                    # doesn't trip)
                    await asyncio.sleep(
                        (slow_factor - 1.0) * (time.monotonic() - t0)
                    )
            return result
        finally:
            end = t0 + dispatch.seconds
            gp = self.stats.goodput
            if lands is not None:
                # it has landed: what waited for it goes back, and the one
                # queued behind it is the oldest on the device from now
                self._release_held(lands)
                if launches is not None:
                    launches.prev = None
                    launches.since = end
                elif self._flight is lands:
                    self._flight = None
            elif launches is not None:
                launches.launch_s = (launch.upload_s, launch.enqueue_s)
            self._watch_flight()
            if launches is None or lands is not None:
                # a dispatch's time ends with this call
                since = t0 if lands is None else lands.since
                elapsed = end - since
                ema = self._dispatch_ema.get(label)
                self._dispatch_ema[label] = (
                    elapsed if ema is None else 0.8 * ema + 0.2 * elapsed
                )
                if gp.enabled:
                    if ema is None:
                        # first dispatch of this label includes its XLA
                        # compile (same fact the cold watchdog budget uses)
                        gp.record_compile(label, elapsed, split)
                        logger.info(
                            "first dispatch of %s: %.2f s (%s)", label,
                            elapsed,
                            ", ".join(f"{k} {v:.2f}" for k, v in split.items()),
                        )
                        if (
                            normalize_label(label) in self._prebaked_labels
                            and elapsed >= self._recompile.min_s
                        ):
                            # a prebaked label should boot as a cache HIT;
                            # a compile-sized first dispatch is cache drift
                            gp.record_recompile(
                                label,
                                "prebake_miss",
                                shape=f"lanes={lanes},tokens={tokens}",
                            )
                    elif self._recompile.is_recompile(elapsed, ema):
                        # a compile can only happen inside the jitted call;
                        # a dispatch that was long elsewhere is the host's
                        # stall (the parts are this call's own)
                        parts = launch_parts(
                            end - t0, max(0.0, call.start_s - t0),
                            call.seconds, launch,
                        )
                        if lands is not None:
                            parts["upload"] += lands.launch_s[0]
                            parts["enqueue"] += lands.launch_s[1]
                        if long_part(parts) == "enqueue":
                            gp.record_recompile(
                                label,
                                "prebake_miss"
                                if normalize_label(label)
                                in self._prebaked_labels
                                else "shape_miss",
                                shape=f"lanes={lanes},tokens={tokens}",
                            )
                        else:
                            gp.record_stall(label, parts)
                    gp.record_step(
                        label,
                        elapsed,
                        lanes=lanes if lands is None else len(lands.lanes),
                        capacity=(
                            capacity if lands is None
                            else self.config.max_batch
                        ),
                        prefill_tokens=tokens,
                        t_start=since,
                    )
                    if dtrace.enabled():
                        dtrace.counter("step_ms", elapsed * 1e3)
            if gp.enabled:
                if why is None:
                    why = _WHY_BY_PHASE.get(step_phase(label), "other")
                gp.record_launch(
                    launch.upload_arrays, launch.upload_bytes,
                    launch.fetch_bytes,
                    dispatches=int(launching),
                    chained=launches is not None and lands is not None,
                    why=why,
                )
                if launching:
                    if capacity > 0:
                        gp.record_sampler(pool, logprobs)
                    if pool_counts:
                        gp.record_pool(**pool_counts)
                    if self._recurrent_layers:
                        gp.record_ssm(
                            self._recurrent_layers,
                            decode_steps=horizon if capacity > 0 else 0,
                            lanes=lanes, resets=state_resets,
                            scan_tokens=tokens,
                        )
                    if capacity > 0 and dtrace.enabled():
                        dtrace.counter("occupancy", lanes / capacity)

    def _watch_flight(self) -> None:
        """The watchdog times the oldest dispatch on the device: the one in
        flight, from when its time began, or nothing."""
        fl = self._flight
        if fl is not None and fl.prev is not None:
            fl = fl.prev
        self._dispatch_info = None if fl is None else (fl.label, fl.since)

    async def _watchdog_loop(self) -> None:
        poll = max(0.02, min(1.0, self.config.watchdog_min_s / 4))
        while not self._closed:
            await asyncio.sleep(poll)
            info = self._dispatch_info
            if info is None:
                continue
            label, t0 = info
            elapsed = time.monotonic() - t0
            ema = self._dispatch_ema.get(label)
            if ema is None:
                # first dispatch of this label includes its XLA compile
                budget = self.config.watchdog_cold_s
            else:
                budget = max(
                    self.config.watchdog_min_s, self.config.watchdog_mult * ema
                )
            if elapsed > budget:
                self._trip_watchdog(label, elapsed, budget)
                return

    def _trip_watchdog(self, label: str, elapsed: float, budget: float) -> None:
        """A dispatch wedged past its budget: fail every lane with a
        structured error, refuse new work, and tell the host wiring to pull
        this worker out of discovery — instead of hanging every stream."""
        self.stats.watchdog_trips += 1
        self._tripped = True
        self._closed = True  # loop exits when (if) the dispatch returns
        cause = (
            f"watchdog: {label} dispatch stuck {elapsed:.1f}s "
            f"(budget {budget:.1f}s)"
        )
        logger.error("%s — failing all lanes, marking worker unhealthy", cause)
        for seq in list(self.waiting):
            self.waiting.remove(seq)
            self._sp_event(seq, "watchdog_trip", label=label)
            self._sp_close_all(seq)
            self._send_final(
                seq,
                LLMEngineOutput.final_error(
                    seq.ctx.id, "queue", cause, "watchdog_stuck"
                ),
            )
        for seq in list(self._admit_order):
            # blocks are NOT freed: the wedged dispatch may still write
            # into them, and this engine is done serving anyway — the
            # supervisor recycles the whole process after deregistration
            seq.ctx.kill()
            self._sp_event(seq, "watchdog_trip", label=label)
            self._sp_close_all(seq)
            self._send_final(
                seq,
                LLMEngineOutput.final_error(
                    seq.ctx.id, label, cause, "watchdog_stuck"
                ),
            )
        if self.on_watchdog_trip is not None:
            with contextlib.suppress(Exception):
                self.on_watchdog_trip()

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._watchdog_task
        for t in list(self._remote_tasks):
            t.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await t
        if self._loop_task is not None:
            # a crashed loop already failed its sequences with structured
            # errors (_on_loop_done) — close() must not re-raise it
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await self._loop_task
        # finish every parked consumer so no generate() call hangs
        for seq in list(self.waiting):
            self.waiting.remove(seq)
            self._send_final(
                seq, LLMEngineOutput.final(FinishReason.CANCELLED)
            )
        for seq in list(self._admit_order):
            self._finish(seq, FinishReason.CANCELLED)

    def checkpoint_tiers(self, directory: Optional[str] = None) -> Optional[dict]:
        """Warm-restart hook (SIGTERM drain): checkpoint the offload
        tiers + prefix index to `directory` (default DYN_WARM_RESTART_DIR)
        so a planned restart boots with a hot prefix cache. Returns the
        checkpoint summary, or None when tiers/knob are absent."""
        d = directory or os.environ.get("DYN_WARM_RESTART_DIR")
        if not d or self.block_manager is None:
            return None
        try:
            return self.block_manager.checkpoint(d)
        except Exception:  # noqa: BLE001 — a failed checkpoint must not
            logger.exception("warm-restart checkpoint failed")  # block exit
            return None

    def restore_tiers(self, directory: Optional[str] = None) -> Optional[dict]:
        """Boot-side warm restart: restore verified checkpoint pages into
        the offload tiers (corrupt pages refused, never decoded). Call
        before serving; republish `block_manager.advert_blocks()` through
        the KV event publisher so routers learn the restored prefixes."""
        d = directory or os.environ.get("DYN_WARM_RESTART_DIR")
        if not d or self.block_manager is None:
            return None
        try:
            return self.block_manager.restore(d)
        except Exception:  # noqa: BLE001 — cold boot is always acceptable
            logger.exception("warm-restart restore failed")
            return None

    async def clear_kv_blocks(self) -> dict:
        """Flush reusable KV state: the tiered offload cache (G2 host + G3
        disk) and the router-visible hash bookkeeping. In-flight sequences
        keep their G1 device blocks — only *reusable* state is dropped
        (ref http/service/clear_kv_blocks.rs semantics: reset prefix reuse
        without killing live requests)."""
        tier_blocks = 0
        if self.block_manager is not None:
            s = self.block_manager.stats
            tier_blocks = s.host_blocks_used + s.disk_blocks_used
            self.block_manager.clear()
        self._hash_refs.clear()
        for seq in self._admit_order:
            # stored events already published for these sequences are about
            # to be wiped by the Cleared event; re-emitting on the next
            # block boundary re-registers live prefixes with the router
            # (and re-queues their offload into the freshly emptied tier)
            seq.emitted_hashes = 0
            seq.offload_mark = 0
        if self.on_cache_cleared is not None:
            self.on_cache_cleared()
        return {
            "status": "cleared",
            "offload_blocks_dropped": tier_blocks,
            "active_sequences_kept": sum(
                1 for s in self.slots if s is not None
            ),
        }

    # ------------------------------------------------------------- events

    def _emit_stored(self, seq: _Sequence) -> None:
        """Publish hash-chain events for newly completed blocks. None for
        a model with a recurrent layer: a router that sent a request here
        for its prefix would find keys and values for the attention layers
        and no state for the others. None for a model whose window layers
        give their pages back: the prefix's blocks are there for the full
        layers and gone for the others."""
        if (
            seq.hash_seq is None or self._recurrent_layers
            or self._window is not None
        ):
            return
        new = seq.hash_seq.blocks[seq.emitted_hashes :]
        for b in new:
            self._hash_refs[b.block_hash] = (
                self._hash_refs.get(b.block_hash, 0) + 1
            )
        if self._offload_queue is not None:
            # mid-generation offload: completed blocks become host-tier
            # candidates as soon as they are KV-complete, so waiting
            # requests can prefix-hit a sequence that is still generating
            # (reference offload.rs enqueues at block *registration*, not
            # completion). Hash-complete lags KV-complete by one token
            # (see kv_written), hence the separate offload_mark cursor.
            bs = self.config.block_size
            ready = min(len(seq.hash_seq.blocks), seq.kv_written // bs)
            if ready > seq.offload_mark:
                self._offload_queue.enqueue(
                    seq,
                    [
                        (b.block_hash, b.position)
                        for b in seq.hash_seq.blocks[seq.offload_mark:ready]
                        if b.block_hash not in self.block_manager
                        and not self.block_manager.is_quarantined(
                            b.block_hash
                        )
                    ],
                )
                seq.offload_mark = ready
        if not new or self.on_blocks_stored is None:
            seq.emitted_hashes = len(seq.hash_seq.blocks)
            return
        # quarantined hashes are never re-offered for prefix reuse: a
        # poison block must not re-enter the fleet's radix trees through
        # a fresh store event
        quarantined = (
            self.block_manager.is_quarantined
            if self.block_manager is not None
            else (lambda h: False)
        )
        events = [
            {
                "block_hash": b.block_hash,
                "parent_hash": b.parent_hash,
                "tokens": b.tokens,
                "block_id": seq.block_ids[b.position]
                if b.position < len(seq.block_ids)
                else -1,
            }
            for b in new
            if not quarantined(b.block_hash)
        ]
        seq.emitted_hashes = len(seq.hash_seq.blocks)
        self.on_blocks_stored(events)

    def _emit_removed(self, seq: _Sequence) -> None:
        if seq.hash_seq is None:
            return
        last_refs: list[int] = []
        for b in seq.hash_seq.blocks[: seq.emitted_hashes]:
            n = self._hash_refs.get(b.block_hash, 0) - 1
            if n <= 0:
                self._hash_refs.pop(b.block_hash, None)
                last_refs.append(b.block_hash)
            else:
                self._hash_refs[b.block_hash] = n
        if last_refs and self.on_blocks_removed is not None:
            self.on_blocks_removed(last_refs)

    # ----------------------------------------------------------- schedule

    @staticmethod
    def _queue_key(seq: _Sequence) -> tuple:
        """Priority-then-deadline admission order: class rank, then the
        request deadline (unbounded last), then arrival. A preempted
        sequence keeps its original arrival number, so it re-queues at the
        HEAD of its class — ahead of younger same-class work — without any
        special-casing."""
        dl = seq.ctx.deadline
        return (seq.rank, dl if dl is not None else float("inf"),
                seq.arrival_order)

    def _enqueue(self, seq: _Sequence) -> None:
        if not seq.arrival_order:
            seq.arrival_order = next(self._arrivals)
        bisect.insort(self.waiting, seq, key=self._queue_key)

    # ------------------------------------------------------------ brownout

    def apply_brownout(self, level: int) -> None:
        """Apply one brownout-ladder rung (telemetry/brownout.py; wired by
        the worker host from `slo-status` events + local burn rates):
        level >= 1 sheds bulk arrivals, >= 2 pauses speculative decoding,
        >= 3 halves the prefill-chunk budget per step, >= 4 sheds standard
        arrivals too. Idempotent; lowering the level restores everything."""
        self._brownout_level = max(0, int(level))
        self._spec_paused = self._brownout_level >= 2
        self.stats.brownout_level = self._brownout_level

    def _chunk_tokens(self) -> int:
        """Tokens per individual prefill chunk (the compiled chunk
        program's width); halved under brownout chunk-cap so the
        phase-separated path's decode lanes get the chip back — new
        prompts' TTFT is sacrificed for admitted requests' ITL."""
        c = getattr(self.runner, "prefill_chunk_tokens", 0)
        if c and dbrownout.chunk_capped(self._brownout_level):
            c = max(self.config.block_size, c // 2)
        return c

    def _chunk_budget(self) -> int:
        """Per-STEP prefill token budget: how many prompt tokens may ride
        along one device step across every packed chunk (ISSUE 16).
        Brownout's chunk_cap rung halves it via qos.effective_chunk_budget
        (floored at one KV block so in-flight prefills keep progressing).
        The loop latches the result once per step boundary — read
        self._step_chunk_tokens / _step_chunk_budget inside an iteration."""
        return qos.effective_chunk_budget(
            self._chunk_budget_base,
            chunk_cap=dbrownout.chunk_capped(self._brownout_level),
            block_size=self.config.block_size,
        )

    def _free_seq(self, seq: _Sequence, emit_remove: bool = True) -> None:
        if self._offload_queue is not None:
            # queued candidates now point at blocks about to be recycled;
            # drop them so their hashes can re-enqueue via another holder
            self._offload_queue.forget_seq(
                seq,
                cancelled=seq.ctx.is_killed() or seq.ctx.is_stopped(),
            )
        if seq.slot is not None:
            self.slots[seq.slot] = None
            seq.slot = None
        if seq.block_ids:
            fl = self._flight
            if fl is not None and fl.names(seq):
                # a dispatch on the device's queue may still write this
                # lane's rows (the host alone ended it: the device saw
                # neither an EOS nor its limit): the blocks go back when
                # that dispatch has landed. The lane's slot needs no such
                # care: only an admission takes a slot, and none runs while
                # a dispatch is in flight (`_chain_break`)
                fl.held.extend(seq.block_ids)
            else:
                self.allocator.free(seq.block_ids)
            seq.block_ids = []
            seq.window_released = 0
        if seq in self._admit_order:
            self._admit_order.remove(seq)
        if seq in self._prefilling:
            self._prefilling.remove(seq)
        seq.prefilling = False
        seq.prefill_pos = 0  # a preempted seq re-prefills from scratch
        if emit_remove:
            self._emit_removed(seq)

    def _finish(self, seq: _Sequence, reason: FinishReason) -> None:
        self._maybe_offload(seq, reason)
        self._free_seq(seq)
        if seq.spans:
            self._sp_finish(seq, "decode", tokens=seq.num_generated)
            self._sp_close_all(seq)
        self._send_final(seq, LLMEngineOutput.final(reason))

    def _finish_error(
        self, seq: _Sequence, phase: str, cause: str, code: str
    ) -> None:
        """Fail one admitted sequence with a structured error: free its
        slot + KV blocks (publishing Removed) and send the typed final."""
        self._free_seq(seq)
        if seq.spans:
            self._sp_event(seq, "error", phase=phase, code=code)
            self._sp_close_all(seq)
        self._send_final(
            seq, LLMEngineOutput.final_error(seq.ctx.id, phase, cause, code)
        )

    def _maybe_offload(self, seq: _Sequence, reason: FinishReason) -> None:
        """On normal completion, copy this sequence's full blocks to the
        host tier before the device blocks are recycled (KVBM G1->G2,
        reference offload.rs). Block ownership moves to the offload task so
        the allocator can't hand the blocks out mid-copy."""
        if (
            self.block_manager is None
            or self._closed
            or seq.hash_seq is None
            or not seq.block_ids
            or reason in (FinishReason.ERROR, FinishReason.CANCELLED)
        ):
            return
        pairs = [
            (h, seq.block_ids[i]) for h, i in self._offload_pairs(seq)
        ]
        if not pairs:
            return
        owned, seq.block_ids = seq.block_ids, []
        self._spawn_tracked(self._offload_task(owned, pairs))

    def _spawn_tracked(self, coro) -> asyncio.Task:
        t = asyncio.get_running_loop().create_task(coro)
        self._remote_tasks.add(t)
        t.add_done_callback(self._remote_tasks.discard)
        return t

    async def _copy_blocks_to_tier(
        self, ids: list[int], hashes: list[int]
    ) -> None:
        """Extract device blocks (serialized with all runner calls), then
        store them in the host tier from a background task — the memcpys
        and possible disk spill must not sit on the decode latency path.
        Returns once the device copies are safe on host (the extract), so
        callers may free/recycle the device blocks immediately."""
        loop = asyncio.get_running_loop()
        if faults.active():
            inj = faults.get_injector()
            if inj is not None:
                await inj.on_transfer()
        quant = self._tier_quant_passthrough()
        try:
            async with self._device_lock:
                if quant:
                    # int8-resident device pages spill VERBATIM into the
                    # int8 tiers (mantissas+scales, no recode) — onboard
                    # later returns the exact same bytes
                    data = await loop.run_in_executor(
                        None, self.runner.extract_blocks_quant, ids
                    )
                else:
                    data = await loop.run_in_executor(
                        None, self.runner.extract_blocks, ids
                    )
        except Exception:  # noqa: BLE001 — offload is best-effort
            logger.exception("block offload extract failed")
            return
        self._spawn_tracked(self._store_blocks_task(hashes, data, quant))

    def _tier_quant_passthrough(self) -> bool:
        """True when device pages and offload tiers share the int8 codec,
        so spills/onboards move mantissas+scales verbatim."""
        return (
            getattr(self.runner, "kv_quantized", False)
            and getattr(self.block_manager, "wire_codec", "raw") == "int8"
        )

    async def _store_blocks_task(self, hashes, data, quant=False) -> None:
        loop = asyncio.get_running_loop()
        try:
            if quant:
                stored = await loop.run_in_executor(
                    None,
                    lambda: self.block_manager.store_blocks_quant(
                        hashes, *data
                    ),
                )
            else:
                stored = await loop.run_in_executor(
                    None, self.block_manager.store_blocks,
                    hashes, data[0], data[1],
                )
            if self._offload_queue is not None:
                self._offload_queue.stats.offloaded += stored
        except Exception:  # noqa: BLE001 — offload is best-effort
            logger.exception("block offload store failed")
        finally:
            self._wake.set()

    async def _offload_task(
        self, owned_ids: list[int], pairs: list[tuple[int, int]]
    ) -> None:
        try:
            await self._copy_blocks_to_tier(
                [bid for _, bid in pairs], [h for h, _ in pairs]
            )
        finally:
            # the extract has completed (or failed) — device blocks are
            # recyclable now; the host-side store continues in background
            self.allocator.free(owned_ids)
            self._wake.set()

    def _offload_pairs(
        self, seq: _Sequence
    ) -> list[tuple[int, int]]:
        """(hash, chain-index) pairs of this sequence's offloadable blocks:
        KV-complete (see kv_written — when the final sampled token exactly
        completes a block, that block's last KV slot was never written and
        storing it would poison later onboards), device-resident, and not
        already in the host tier."""
        kv_complete = seq.kv_written // self.config.block_size
        return [
            (b.block_hash, i)
            for i, b in enumerate(seq.hash_seq.blocks)
            if i < min(len(seq.block_ids), kv_complete)
            and b.block_hash not in self.block_manager
        ]

    async def _drain_offload(self) -> None:
        """Copy a few queued mid-generation blocks to the host tier.

        Runs on the engine loop between scheduling phases, so candidate
        validity (checked in pop_valid) cannot change before the extract:
        preemption and sequence completion only happen on this same loop.
        Rate-limited to offload_per_step blocks per iteration."""
        q = self._offload_queue
        if q is None or not len(q):
            return
        cands = q.pop_valid(self.config.offload_per_step, self.block_manager)
        if not cands:
            return
        await self._copy_blocks_to_tier(
            [bid for _, _, bid in cands], [h for _, h, _ in cands]
        )

    def _key_row(self, seq: _Sequence) -> np.ndarray:
        """Raw threefry key row for this sequence's next sampled token:
        (stream, counter) = (per-request seed | engine-derived stream,
        num_generated) — same seed + same prompt ⇒ same output, regardless
        of batch composition or preemption."""
        from dynamo_tpu.ops.sampling import make_key_data

        stream = (
            seq.seed if seq.seed is not None
            else self._seed_base + seq.seq_id
        )
        # eos_drops rides the high counter bits so a dropped overflow-EOS
        # redraw uses a FRESH key (num_generated doesn't advance on a drop;
        # without this the redraw would deterministically re-sample the
        # same suppressed token). Generation counters stay < max_model_len
        # << 2^16, so the ranges can't collide.
        return make_key_data(
            stream, seq.num_generated + (seq.eos_drops << 16)
        )

    def _preempt_victim(self, exclude: _Sequence) -> bool:
        """Class-aware LIFO victim choice: lowest class first (bulk absorbs
        pressure before standard before interactive), youngest within a
        class — and never a victim whose class strictly outranks the
        preemptor's (bulk growth must not evict interactive work; the
        grower self-preempts instead, see _append_token)."""
        worst = max(qos.CLASS_RANK.values())
        for rank in range(worst, exclude.rank - 1, -1):
            for victim in reversed(self._admit_order):
                if (
                    victim is exclude
                    or victim.slot is None
                    or victim.pending_remote
                    or victim.rank != rank
                ):
                    continue
                if dprov.enabled():
                    dprov.record(
                        "engine", "preempt", victim.priority,
                        reason="class_rank",
                        ctx=victim.ctx,
                        proc=self.trace_proc,
                        alternatives=[
                            {
                                "request": c.ctx.id,
                                "class": c.priority,
                                "rank": c.rank,
                                "generated": c.num_generated,
                            }
                            for c in self._admit_order
                            if c is not exclude and c.slot is not None
                        ][:8],
                        grower=exclude.ctx.id,
                        grower_class=exclude.priority,
                    )
                self._preempt_seq(victim)
                return True
        return False

    def _preempt_seq(self, victim: _Sequence) -> None:
        """Preempt one admitted sequence, KV-preserving: spill completed
        blocks to the host tier before the device copies are recycled so
        re-admission onboards them instead of re-prefilling (reference
        offload.rs eviction-time offload). Guarded against preemption
        storms: past max_preemptions the sequence fails with a structured
        `preempted_too_often` error, and every re-queue waits out an
        exponential re-admission backoff."""
        victim.preemptions += 1
        by_class = self.stats.preemptions_by_class
        by_class[victim.priority] = by_class.get(victim.priority, 0) + 1
        # goodput ledger: every token whose device KV this preemption
        # discards must be recomputed on re-admission (the host-tier spill
        # below may onboard some back — counted as an upper bound)
        self.stats.goodput.record_waste(
            "preempt_replay",
            victim.prefill_pos if victim.prefilling else len(victim.token_ids),
        )
        if victim.preemptions > self.config.max_preemptions:
            self.stats.preempted_too_often += 1
            self._sp_event(victim, "preempted_too_often")
            self._finish_error(
                victim, "preemption",
                f"preempted {victim.preemptions} times under sustained "
                f"pressure (DYN_MAX_PREEMPTIONS="
                f"{self.config.max_preemptions}); giving up",
                "preempted_too_often",
            )
            return
        logger.debug(
            "preempting seq %d (%s, preemption #%d)",
            victim.seq_id, victim.priority, victim.preemptions,
        )
        self._spill_preempted(victim)
        self._free_seq(victim)
        victim.hash_seq = None
        victim.emitted_hashes = 0
        victim.offload_mark = 0
        if victim.spans:
            self._sp_event(victim, "preempted", count=victim.preemptions)
            self._sp_close_all(victim)
        if dtrace.enabled():
            # re-queued: its wait for re-admission is a fresh phase
            self._sp_begin(victim, "queue_wait", resumed=True)
        backoff_s = min(
            2.0,
            self.config.preempt_backoff_ms
            / 1e3
            * (1 << (victim.preemptions - 1)),
        )
        if dprov.enabled():
            dprov.record(
                "engine", "readmit", victim.priority,
                reason="backoff",
                ctx=victim.ctx,
                proc=self.trace_proc,
                backoff_ms=round(backoff_s * 1e3, 3),
                preemptions=victim.preemptions,
            )
        victim.requeue_after = time.monotonic() + backoff_s
        self._enqueue(victim)

    def _spill_preempted(self, victim: _Sequence) -> None:
        """Move ownership of the victim's not-yet-offloaded full blocks to
        an offload task; everything else (partial tail + already-offloaded
        blocks) frees immediately. At least one block is always freed now —
        the preemptor's allocation (the reason we preempt) must succeed
        without waiting for the host copies."""
        bm = self.block_manager
        if (
            bm is None
            or self._closed
            or victim.hash_seq is None
            or not victim.block_ids
        ):
            return
        pairs = self._offload_pairs(victim)
        if len(pairs) >= len(victim.block_ids):
            # every device block is a spill candidate: sacrifice the NEWEST
            # so the preemptor can allocate immediately — dropping the
            # oldest would break prefix contiguity and make the whole spill
            # unreachable (lookup_prefix only counts leading hits)
            pairs = pairs[:-1]
        if not pairs:
            return
        spill_positions = {i for _, i in pairs}
        owned = [victim.block_ids[i] for _, i in pairs]
        hash_block = [
            (h, victim.block_ids[i]) for h, i in pairs
        ]
        victim.block_ids = [
            bid
            for i, bid in enumerate(victim.block_ids)
            if i not in spill_positions
        ]
        self._spawn_tracked(self._offload_task(owned, hash_block))

    def _slot_kw(self, seqs: list[_Sequence]) -> dict:
        """`state_slots`, the keyword that tells a prefill call of the runner
        the lane slot of each sequence it holds, for a model that keeps a
        state there; nothing for any other (and for a runner that never
        heard of slots)."""
        if not self._recurrent_layers:
            return {}
        return {"state_slots": [s.slot for s in seqs]}

    def _give_back_window(self) -> None:
        """Give back the window blocks that have wholly left their window:
        those below the block that holds the oldest key any later query of
        the sequence can see (the next chunk's first query, or the token a
        decode step feeds next; the host's view, which a dispatch in flight
        is ahead of and never behind). A block the tables of a dispatch in
        flight still name goes back when that dispatch has landed. Counted
        on the host from the numbers the tables are built from."""
        w = self._window
        if w is None:
            return
        bs = self.config.block_size
        fl = self._flight
        for seq in self.slots:
            if seq is None or seq.pending_remote or not seq.block_ids:
                continue
            q_min = seq.prefill_pos if seq.prefilling else seq.pos - 1
            upto = min(max(0, q_min - w + 1) // bs, len(seq.block_ids))
            if upto <= seq.window_released:
                continue
            given = self.allocator.give_back(
                seq.block_ids[seq.window_released : upto]
            )
            seq.window_released = upto
            self.stats.goodput.record_pool(window_blocks_given_back=len(given))
            if fl is not None and fl.names(seq):
                fl.held_window.extend(given)
            else:
                self.allocator.free_window(given)

    def _pool_counts(self, steps: int) -> dict:
        """goodput.POOL_COUNTERS of one decode-family dispatch of `steps`
        steps, from what the lane arrays are built from: a decoding lane's
        context at step h is its tokens so far and h more."""
        w, alloc = self._window, self.allocator
        lanes = [
            s for s in self.slots
            if s is not None and not s.prefilling and not s.pending_remote
        ]
        ctx = (
            np.asarray([len(s.token_ids) for s in lanes], np.int64)[:, None]
            + np.arange(steps)[None, :]
        )
        past = [s for s in lanes if len(s.token_ids) > w]
        return {
            "decode_steps": steps,
            "lane_steps": len(lanes) * steps,
            "window_rows": int(np.minimum(ctx, w).sum()),
            "full_rows": int(ctx.sum()),
            "lanes_past_window": len(past) * steps,
            "window_blocks_past": steps * sum(
                len(s.block_ids) - s.window_released for s in past
            ),
            "window_in_use_steps": steps * alloc.window_in_use,
            "window_capacity_steps": steps * (alloc.window_blocks - 1),
            "full_in_use_steps": steps * alloc.in_use,
            "full_capacity_steps": steps * (alloc.num_blocks - 1),
        }

    def _room_for(self, seq: _Sequence) -> bool:
        """A free lane, and the sequence's blocks above the watermark."""
        return None in self.slots and (
            self.allocator.free_count
            >= seq.blocks_needed(self.config.block_size)
            + self.config.watermark_blocks
        )

    def _try_admit(self, seq: _Sequence) -> bool:
        """Allocate blocks + a slot and run prefill. False if no capacity."""
        if not self._room_for(seq):
            return False
        seq.block_ids = self.allocator.alloc(
            seq.blocks_needed(self.config.block_size)
        )
        seq.slot = self.slots.index(None)
        self.slots[seq.slot] = seq
        self._admit_order.append(seq)
        return True

    # ---------------------------------------------------------- main loop

    async def _engine_loop(self) -> None:
        loop = asyncio.get_running_loop()
        try:
            while not self._closed:
                # one pass; every part of it is a process-level phase
                # (telemetry/trace.py::phase), so its host time has a name
                # in `/debug/goodput` and, while a profile window is open,
                # on the device timeline
                with dtrace.phase("loop.iter"):
                    if await self._loop_pass(loop):
                        return
        finally:
            # closed, fenced or crashed with a dispatch in flight: nobody
            # will read it (its lanes have been failed or will be)
            self._drop_flight()

    async def _stats_and_yield(self, admitted: bool) -> None:
        with dtrace.phase("loop.stats"):
            self._update_stats()
        if not admitted:
            with dtrace.phase("loop.yield"):
                await asyncio.sleep(0)  # fairness for producers/consumers

    async def _loop_pass(self, loop) -> bool:
        """One pass of the engine loop; True when the loop must end."""
        with dtrace.phase("loop.reap"):
            self._reap_cancelled()
            self._give_back_window()
        if self._flight is not None:
            why = self._chain_break()
            if why is not None:
                # whatever else this pass does comes behind the dispatch in
                # flight: read and replay it first, then today's order
                await self._land_flight(why)
        self._process_landed()
        await self._drain_offload()
        # latch the QoS-degraded chunk size and per-step budget ONCE
        # per iteration: apply_brownout can land from another task
        # while a dispatch below is awaited, and a chunk_cap
        # transition must wait for the next step boundary instead of
        # re-slicing work already packed this iteration
        self._step_chunk_tokens = self._chunk_tokens()
        self._step_chunk_budget = self._chunk_budget()
        with dtrace.phase("loop.admit"):
            admitted = await self._admit_phase(loop)
        if self._prefilling:
            active = [
                s
                for s in self.slots
                if s is not None
                and not s.pending_remote
                and not s.prefilling
            ]
            if self._can_mix(active):
                # unified mixed step: every decode lane AND up to
                # _step_chunk_budget prefill tokens in ONE device
                # program — the alternating-phase bubble disappears.
                # Also with no lane decoding (an idle lane costs the step
                # its rows in the matmuls and nothing in attention): a long
                # prompt then runs the programs it runs on a busy server,
                # so which programs a server has compiled does not depend
                # on whether its first long prompt met a decoding lane
                await self._mixed_step_phase(loop, active)
                await self._stats_and_yield(admitted)
                return False
        # one chunk of at most one long prefill per iteration, so the
        # decode step below never waits longer than one chunk
        chunked = False
        if self._prefilling:
            await self._prefill_chunk_step(loop)
            chunked = True
        active = [
            s
            for s in self.slots
            if s is not None and not s.pending_remote and not s.prefilling
        ]
        if not active:
            if chunked:
                with dtrace.phase("loop.stats"):
                    self._update_stats()
                return False
            pending = any(
                s is not None and (s.pending_remote or s.prefilling)
                for s in self.slots
            )
            if not self.waiting and not pending:
                self._wake.clear()
                if self._closed:
                    return True
                # idle (no work anywhere): the gap to the next
                # dispatch is not a phase bubble
                self.stats.goodput.mark_idle()
                with dtrace.phase("loop.idle"):
                    await self._wake.wait()
            else:
                # remote prefills in flight (or unadmittable backlog):
                # yield without busy-spinning
                with dtrace.phase("loop.yield"):
                    await asyncio.sleep(0.001)
            return False
        await self._decode_phase(loop, active)
        await self._stats_and_yield(admitted)
        return False

    def _admission_due(self) -> bool:
        """Whether `_admit_phase` would take somebody off the queue now: its
        own test of the first sequence it would try, asked without taking
        anything."""
        now = time.monotonic()
        for seq in self.waiting:
            if not (seq.requeue_after and now < seq.requeue_after):
                return self._room_for(seq)
        return False

    def _chain_break(self) -> Optional[str]:
        """Why no `decode_multi` may be queued behind another one now (of
        goodput.CHAIN_BREAKS), read from the engine's own state; None while
        steady decode is all there is to do."""
        if self._closed or not any(s is not None for s in self.slots):
            return "other"
        if self._admission_due():
            return "arrival"
        if self._prefilling or any(
            s is not None and (s.pending_remote or s.prefilling)
            for s in self.slots
        ):
            return "prefilling"
        if (
            self._landed
            or self._remote_tasks
            or self._device_lock.locked()
            or (self._offload_queue is not None and len(self._offload_queue))
        ):
            # a landed remote prefill, block movement waiting for the device
            return "other"
        return None

    def _may_fly(self, label: str, penalties) -> bool:
        """Whether a plain `decode_multi` about to be launched may stay on
        the device's queue unread, for the next pass to chain on: the runner
        keeps the carry, the program is warm (a first dispatch is its
        compile, and is timed whole), nobody drafts, no lane carries
        penalties, and nothing else waits for the device."""
        return (
            penalties is None
            and self.drafter is None
            and label in self._dispatch_ema
            and getattr(self.runner, "chains_horizons", False)
            and self._chain_break() is None
        )

    async def _land_flight(self, why: str) -> None:
        """End the chain: read and replay the dispatch in flight. `why`
        (of goodput.CHAIN_BREAKS) is what the next unchained `decode_multi`
        counts."""
        fl = self._flight
        self._chain_why = why
        async with self._device_lock:
            packed = await self._dispatch(
                fl.label,
                lambda: self.runner.fetch_horizon(fl.packed),
                horizon=fl.H,
                lands=fl,
            )
        self._replay_horizon(fl.lanes, fl.H, packed)

    def _release_held(self, fl: _Flight) -> None:
        """What waited for the dispatch `fl` to land goes back to its pool."""
        if fl.held:
            self.allocator.free(fl.held)
            fl.held = []
        if fl.held_window:
            self.allocator.free_window(fl.held_window)
            fl.held_window = []

    def _drop_flight(self) -> None:
        fl, self._flight = self._flight, None
        while fl is not None:
            self._release_held(fl)
            fl = fl.prev
        self._dispatch_info = None

    def _reap_cancelled(self) -> None:
        for seq in list(self.waiting):
            if seq.ctx.is_killed() or seq.ctx.is_stopped():
                self.waiting.remove(seq)
                self._sp_close_all(seq)
                self._send_final(
                    seq, LLMEngineOutput.final(FinishReason.CANCELLED)
                )
            elif seq.ctx.expired() or seq.ctx.ttft_expired():
                # queued past its deadline (or past the point where its
                # first token could still arrive in budget): shed before it
                # wastes prefill compute
                self.waiting.remove(seq)
                self.stats.deadline_exceeded += 1
                seq.ctx.kill()
                self._sp_event(seq, "deadline_exceeded", phase="queue")
                self._sp_close_all(seq)
                self._send_final(
                    seq,
                    LLMEngineOutput.final_error(
                        seq.ctx.id, "queue",
                        "deadline exceeded while queued",
                        "deadline_exceeded",
                    ),
                )
        for seq in list(self._admit_order):
            # pending_remote seqs keep their blocks until the in-flight
            # inject lands — freeing now could hand the blocks to another
            # sequence and have the late inject corrupt its KV
            if seq.pending_remote:
                if seq.ctx.expired() and not seq.deadline_fired:
                    seq.deadline_fired = True
                    self.stats.deadline_exceeded += 1
                    seq.ctx.kill()  # cascade cancels the remote prefill
                    self._sp_event(
                        seq, "deadline_exceeded", phase="remote_prefill"
                    )
                    self._send_final(
                        seq,
                        LLMEngineOutput.final_error(
                            seq.ctx.id, "remote_prefill",
                            "deadline exceeded awaiting remote prefill",
                            "deadline_exceeded",
                        ),
                    )
                continue
            if seq.ctx.expired() or (
                seq.num_generated == 0 and seq.ctx.ttft_expired()
            ):
                self.stats.deadline_exceeded += 1
                seq.ctx.kill()  # cascade: frees child work, then the lane
                self._sp_event(seq, "deadline_exceeded", phase="decode")
                # partial output discarded: the consumer gets an error,
                # not the tokens this lane already generated
                self.stats.goodput.record_waste(
                    "deadline_partial", seq.num_generated
                )
                self._finish_error(
                    seq, "decode", "deadline exceeded mid-generation",
                    "deadline_exceeded",
                )
            elif seq.ctx.is_killed():
                # consumer disconnected (plain cancel or a hedge loser —
                # the engine cannot tell; the frontend hedger attributes
                # hedge_loser from its side)
                self.stats.goodput.record_waste(
                    "cancelled_partial", seq.num_generated
                )
                self._finish(seq, FinishReason.CANCELLED)

    async def _admit_phase(self, loop) -> bool:
        admitted = False
        to_pack: list[_Sequence] = []
        chunk_c = self._step_chunk_tokens
        can_pack = bool(chunk_c) and hasattr(
            self.runner, "prefill_packed_arrays"
        )
        idx = 0
        while idx < len(self.waiting):
            seq = self.waiting[idx]
            if seq.requeue_after and time.monotonic() < seq.requeue_after:
                # re-admission backoff after preemption: let same-or-lower
                # priority work behind it through instead of head-blocking
                idx += 1
                continue
            if not self._try_admit(seq):
                break
            self.waiting.pop(idx)
            admitted = True
            if seq.t_admitted is None:  # first admission (not a resume)
                seq.t_admitted = time.monotonic()
                waited = seq.t_admitted - seq.t_arrival
                self.stats.phase_histograms.observe("queue_wait", waited * 1e3)
                dtrace.observe_phase("queue_wait", int(waited * 1e9))
            if seq.spans:
                self._sp_finish(seq, "queue_wait")
            # multimodal sequences (vision embeddings in extra["mm"]):
            # token-hash prefix reuse would collide across DIFFERENT images
            # whose placeholder tokens are identical, so they skip the
            # block-manager/peer lookup, disagg shipping, chunking and
            # packing, and run the dedicated mm prefill program.
            mm = seq.request.extra.get("mm")
            if mm is not None:
                if dtrace.enabled():
                    self._sp_begin(seq, "prefill", path="mm")
                await self._run_mm_prefill(loop, seq, mm)
                continue
            hit_len = 0
            if self.block_manager is not None:
                seq.pending_chain = TokenBlockSequence(
                    list(seq.token_ids), self.config.block_size
                )
                chain = seq.pending_chain.blocks
                seq.prefix_hashes = [b.block_hash for b in chain]
                seq.cached_prefix_blocks = self.block_manager.lookup_prefix(
                    seq.prefix_hashes
                )
                plan = decisions_of(seq.ctx).pull_plan
                if plan and plan.get("freq"):
                    # fleet heat rides the pull plan (the radix tree's
                    # recent_uses counts): feed eviction scoring so a
                    # fleet-hot block out-survives a locally-idle one
                    note = getattr(
                        self.block_manager, "note_fleet_heat", None
                    )
                    if note is not None:
                        note(
                            [int(h) for h in plan.get("hashes", [])],
                            plan["freq"],
                        )
                if (
                    self.peer_block_client is not None
                    and seq.cached_prefix_blocks < len(seq.prefix_hashes)
                ):
                    # G4-lite: a peer may hold the rest of the prefix —
                    # directed by the router's plan when one is attached,
                    # opportunistic otherwise
                    with dtrace.span(
                        "peer_fetch", ctx=seq.ctx, proc=self.trace_proc,
                        blocks_missing=(
                            len(seq.prefix_hashes) - seq.cached_prefix_blocks
                        ),
                        planned=bool(plan),
                    ):
                        fetched = (
                            await self.peer_block_client.fetch_remote_prefix(
                                seq.prefix_hashes, plan=plan
                            )
                        )
                    if fetched:
                        seq.cached_prefix_blocks = (
                            self.block_manager.lookup_prefix(seq.prefix_hashes)
                        )
                hit_len = seq.cached_prefix_blocks * self.config.block_size
            use_remote = False
            if (
                self.disagg_router is not None
                and self.remote_prefill_client is not None
            ):
                refresh = getattr(self.disagg_router, "maybe_refresh", None)
                if refresh is not None:
                    await refresh()
                use_remote = self.disagg_router.prefill_remote(
                    len(seq.token_ids), hit_len
                )
            if use_remote:
                # ship the prefill out; the sequence holds its slot+blocks
                # and joins the decode batch when the KV lands
                seq.pending_remote = True
                if dtrace.enabled():
                    self._sp_begin(
                        seq, "remote_prefill",
                        tokens=len(seq.token_ids),
                        cached_blocks=seq.cached_prefix_blocks,
                    )
                self._spawn_tracked(self._remote_prefill_task(seq))
                continue
            if dtrace.enabled():
                self._sp_begin(
                    seq, "prefill",
                    tokens=len(seq.token_ids),
                    cached_blocks=seq.cached_prefix_blocks,
                )
            # re-admission after preemption replays generated tokens too
            replay = seq.token_ids
            bs = self.config.block_size
            # a prefix hit that skips >=1 full block routes through the
            # chunked path even for short prompts: prefill_chunk is the
            # only program that computes from an offset, so this is what
            # turns a host-tier hit into saved compute (onboard-into-
            # waiting-request, reference offload.rs onboarding)
            skippable = 0
            if self.block_manager is not None and seq.cached_prefix_blocks:
                skippable = min(
                    seq.cached_prefix_blocks, (len(replay) - 1) // bs
                )
            if chunk_c and (len(replay) > chunk_c or skippable > 0):
                # long prompt: prefill one chunk per loop iteration so the
                # in-flight decode batch never stalls more than one chunk
                seq.prefilling = True
                seq.prefill_pos = 0
                if self.block_manager is not None and seq.cached_prefix_blocks:
                    # local prefix onboarding (G2/G3/G4 -> G1): inject the
                    # cached leading blocks and start chunking after them;
                    # the final chunk always keeps >= 1 token so the first
                    # sample comes from real logits
                    onboarded = await self._onboard_prefix(seq, loop)
                    if onboarded:
                        skip = min(onboarded, (len(replay) - 1) // bs)
                        seq.prefill_pos = skip * bs
                self._prefilling.append(seq)
                continue
            if can_pack:
                # short prompt: batch with other waiting prompts into one
                # packed-prefill program (flushed below)
                to_pack.append(seq)
                continue
            key_row = self._key_row(seq)
            async with self._device_lock:
                sample = await self._dispatch(
                    "prefill",
                    lambda: self.runner.fetch_sample(
                        self.runner.prefill(
                            replay,
                            seq.block_ids,
                            seq.temperature,
                            seq.top_p,
                            seq.top_k,
                            rep_pen=seq.rep_pen,
                            key_data=key_row,
                            eos_ids=seq.eos_row,
                            eos_suppress=seq.needs_eos_suppress,
                            want_logprobs=seq.want_logprobs,
                            **self._slot_kw([seq]),
                        )
                    ),
                    tokens=len(replay),
                    state_resets=1,
                )
            with self._emitting():
                # the admission pass may have prebuilt the identical chain
                # for the prefix lookup — reuse instead of re-hashing
                seq.hash_seq = seq.pending_chain or TokenBlockSequence(
                    replay, self.config.block_size
                )
                self._emit_stored(seq)
                self._append_sample(seq, sample)
        # flush the packed batches: greedily fill the token budget, one
        # program launch per group (TTFT under many short prompts scales
        # with ceil(total_tokens / budget), not with request count)
        while to_pack:
            group, total = [], 0
            while (
                to_pack
                and total + len(to_pack[0].token_ids) <= chunk_c
                and len(group) < self.config.max_batch
            ):
                s = to_pack.pop(0)
                group.append(s)
                total += len(s.token_ids)
            await self._run_packed_prefill(loop, group)
        return admitted

    async def _run_mm_prefill(self, loop, seq: _Sequence, mm: dict) -> None:
        """Single-sequence multimodal prefill: vision embeddings spliced
        over the expanded placeholder span (runner.prefill_mm). No hash
        chain is built — the chain keys on token ids only, and two prompts
        with different images share identical placeholder tokens, so
        emitting Stored events would poison prefix routing."""
        with dtrace.phase("loop.pack"):
            embeds = mm["embeds"]
            if not hasattr(embeds, "devices"):  # host payload (wire path)
                embeds = np.asarray(embeds, np.float32)
            start = int(mm["start"])
            key_row = self._key_row(seq)
        async with self._device_lock:
            sample = await self._dispatch(
                "prefill_mm",
                lambda: self.runner.fetch_sample(
                    self.runner.prefill_mm(
                        list(seq.token_ids),
                        seq.block_ids,
                        embeds,
                        start,
                        seq.temperature,
                        seq.top_p,
                        seq.top_k,
                        rep_pen=seq.rep_pen,
                        key_data=key_row,
                        eos_ids=seq.eos_row,
                        eos_suppress=seq.needs_eos_suppress,
                        want_logprobs=seq.want_logprobs,
                    )
                ),
                tokens=len(seq.token_ids),
            )
        with self._emitting():
            self._append_sample(seq, sample)

    async def _run_packed_prefill(
        self, loop, group: list[_Sequence]
    ) -> None:
        with dtrace.phase("loop.pack"):
            specs = [
                (
                    list(s.token_ids), s.block_ids, s.temperature, s.top_p,
                    s.top_k, s.rep_pen, self._key_row(s), s.eos_row,
                    s.needs_eos_suppress,
                )
                for s in group
            ]
            packed = self.runner.pack_prefill(
                specs, want_logprobs=[s.want_logprobs for s in group],
                **self._slot_kw(group),
            )
        async with self._device_lock:
            sample = await self._dispatch(
                "prefill_packed",
                lambda: self.runner.fetch_sample(
                    self.runner.prefill_packed_arrays(**packed)
                ),
                tokens=sum(len(s.token_ids) for s in group),
                state_resets=len(group),
            )
        with self._emitting():
            toks, lps, tids, tlps = sample
            for i, seq in enumerate(group):
                if seq.slot is None:  # cancelled during the device call
                    continue
                seq.hash_seq = seq.pending_chain or TokenBlockSequence(
                    list(seq.token_ids), self.config.block_size
                )
                self._emit_stored(seq)
                self._append_token(
                    seq, int(toks[i]), lp=float(lps[i]),
                    top_ids=tids[i], top_lps=tlps[i],
                )

    async def _prefill_chunk_step(self, loop) -> None:
        """Run ONE chunk of the oldest in-progress chunked prefill."""
        seq = self._prefilling[0]
        if seq.slot is None:  # freed while queued
            if seq in self._prefilling:
                self._prefilling.remove(seq)
            return
        with dtrace.phase("loop.pack"):
            c = self._step_chunk_tokens
            start = seq.prefill_pos
            total = len(seq.token_ids)
            chunk = seq.token_ids[start : start + c]
            key_row = self._key_row(seq)
            final = start + c >= total
        async with self._device_lock:
            # only the FINAL chunk's sample is consumed; syncing the
            # fetch on intermediate chunks would leave the device idle for
            # one host round trip per chunk. Intermediate chunks dispatch
            # asynchronously; JAX orders them through the donated-cache
            # dataflow.
            def run_chunk():
                out = self.runner.prefill_chunk(
                    chunk, start, total, seq.block_ids,
                    seq.temperature, seq.top_p, seq.top_k,
                    rep_pen=seq.rep_pen, key_data=key_row,
                    eos_ids=seq.eos_row,
                    eos_suppress=seq.needs_eos_suppress,
                    want_logprobs=seq.want_logprobs,
                    **self._slot_kw([seq]),
                )
                return self.runner.fetch_sample(out) if final else None

            sample = await self._dispatch(
                "prefill_chunk", run_chunk, tokens=len(chunk),
                state_resets=int(start == 0),
            )
        with self._emitting():
            if seq.spans:
                sp = seq.spans.get("prefill")
                if sp is not None and len(sp.events) < 64:
                    sp.event("prefill_chunk", pos=start, tokens=len(chunk))
            if seq.slot is None:  # cancelled during the device call
                return
            seq.prefill_pos = min(start + c, total)
            if seq.prefill_pos >= total:
                self._prefilling.remove(seq)
                seq.prefilling = False
                seq.hash_seq = seq.pending_chain or TokenBlockSequence(
                    list(seq.token_ids), self.config.block_size
                )
                self._emit_stored(seq)
                self._append_sample(seq, sample)

    def _can_mix(self, active: list[_Sequence]) -> bool:
        """One mixed program can replace this iteration's prefill-chunk +
        decode pair. Gated off whenever the decode batch needs a program
        the mixed step doesn't carry: speculative verify (unless the
        brownout ladder paused drafting) and full-history penalty lanes.
        A decode horizon > 1 does not gate it: iterations with a prefill
        pending take the mixed step (one token per lane), the others the
        horizon program — a gate on the horizon made the mixed stepper
        unreachable on a TPU, whose default horizon is 4. The gate must
        stay read-only — e.g. never probe _collect_drafts here, it
        mutates drafter state."""
        if not self._mixed_enabled or not self._step_chunk_budget:
            return False
        if self.drafter is not None and not self._spec_paused:
            return False
        if any(s.has_penalties for s in active):
            return False
        return True

    async def _mixed_step_phase(
        self, loop, active: list[_Sequence]
    ) -> None:
        """ONE device program for the whole iteration: every active decode
        lane plus prefill chunks packed in priority order up to the
        latched per-step token budget (several chunks of one prompt, or
        chunks of several prompts, may share a step). A single
        fetch_sample round trip syncs the decode samples together with the
        samples of any chunk that finished its prompt."""
        with dtrace.phase("loop.pack"):
            C = self._step_chunk_tokens
            budget = self._step_chunk_budget
            # -- pack prefill chunks (decode lanes are already committed) ----
            chunks: list[tuple] = []
            packed: list[tuple[_Sequence, int, int]] = []  # (seq, start, n)
            plan: list[tuple[_Sequence, int]] = []  # per-seq total advance
            for seq in sorted(self._prefilling, key=self._queue_key):
                if seq.slot is None:  # freed while queued
                    self._prefilling.remove(seq)
                    continue
                if budget <= 0 or len(chunks) >= self._mixed_max_slots:
                    break
                total = len(seq.token_ids)
                pos = seq.prefill_pos
                advanced = 0
                key_row = self._key_row(seq)
                while (
                    pos < total
                    and budget > 0
                    and len(chunks) < self._mixed_max_slots
                ):
                    n = min(C, total - pos, budget)
                    chunks.append((
                        seq.token_ids[pos : pos + n], pos, total,
                        seq.block_ids, seq.temperature, seq.top_p, seq.top_k,
                        seq.rep_pen, key_row, seq.eos_row,
                        seq.needs_eos_suppress,
                    ))
                    packed.append((seq, pos, n))
                    pos += n
                    budget -= n
                    advanced += n
                if advanced:
                    plan.append((seq, advanced))
            if not chunks:
                # every in-flight prefill vanished under us; plain decode
                if active:
                    await self._decode_single_phase(loop, active)
                return
            # -- fill the decode lanes (single-step semantics; the eos-mask
            # variant always runs — neutral rows are a bitwise no-op) --------
            from dynamo_tpu.ops.sampling import MAX_EOS_IDS

            B = self.config.max_batch
            self._block_tables.fill(0)
            self._positions.fill(0)
            self._slot_indices.fill(0)  # null block slot 0
            self._idle_sampling()
            bs = self.config.block_size
            eos_ids = np.full((B, MAX_EOS_IDS), -1, np.int32)
            eos_sup = np.zeros(B, bool)
            for seq in active:
                pos = self._fill_lane(seq)
                self._slot_indices[seq.slot] = (
                    seq.block_ids[pos // bs] * bs + pos % bs
                )
                eos_ids[seq.slot] = seq.eos_row
                eos_sup[seq.slot] = seq.needs_eos_suppress
            # chunk slots whose sample is consumed (prompt finishes there)
            final_slots = [
                i for i, (seq, start, n) in enumerate(packed)
                if start + n >= len(seq.token_ids)
            ]
            k = len(chunks)
            tokens_packed = sum(n for _, _, n in packed)
            ctx_tokens = self._ctx_tokens(active)
        async with self._device_lock:

            def run_mixed():
                chunk_outs, d_out = self.runner.mixed_step(
                    chunks, self._tokens, self._positions,
                    self._block_tables, self._slot_indices, self._keys,
                    self._temps, self._top_ps, self._top_ks,
                    eos_ids=eos_ids, eos_suppress=eos_sup,
                    want_logprobs=self._want_lps,
                    chunk_want_logprobs=[p[0].want_logprobs for p in packed],
                    **self._slot_kw([p[0] for p in packed]),
                )
                fetch: list = []
                for i in final_slots:
                    fetch.extend(chunk_outs[i])
                fetch.extend(d_out)
                return self.runner.fetch_sample(tuple(fetch))

            out = await self._dispatch(
                f"mixed_step@c{k}", run_mixed,
                lanes=len(active), capacity=B, tokens=tokens_packed,
                ctx_tokens=ctx_tokens,
                state_resets=sum(start == 0 for _, start, _ in packed),
            )
        with self._emitting():
            final_samples = {
                slot: out[4 * j : 4 * j + 4]
                for j, slot in enumerate(final_slots)
            }
            d_sample = out[4 * len(final_slots) :]
            # -- prefill bookkeeping (chunk events, advance, finalize) -------
            for seq, start, n in packed:
                if seq.spans:
                    sp = seq.spans.get("prefill")
                    if sp is not None and len(sp.events) < 64:
                        sp.event("prefill_chunk", pos=start, tokens=n)
            for seq, advanced in plan:
                if seq.slot is None:  # cancelled during the device call
                    continue
                total = len(seq.token_ids)
                seq.prefill_pos = min(seq.prefill_pos + advanced, total)
                if seq.prefill_pos >= total:
                    self._prefilling.remove(seq)
                    seq.prefilling = False
                    seq.hash_seq = seq.pending_chain or TokenBlockSequence(
                        list(seq.token_ids), self.config.block_size
                    )
                    self._emit_stored(seq)
            for i, (seq, start, n) in enumerate(packed):
                if i in final_samples and seq.slot is not None:
                    self._append_sample(seq, final_samples[i])
            # -- decode bookkeeping ------------------------------------------
            toks, lps, tids, tlps = d_sample
            for seq in active:
                if seq.slot is None:
                    continue  # finished/cancelled concurrently
                i = seq.slot
                self._append_token(
                    seq, int(toks[i]), lp=float(lps[i]),
                    top_ids=tids[i], top_lps=tlps[i],
                )

    def _process_landed(self) -> None:
        """Complete landed remote prefills on the engine loop (serialized
        with decode, so preemption in _append_token can't race a step)."""
        landed, self._landed = self._landed, []
        for seq, sample, fail in landed:
            if seq.slot is None:  # reaped while queued
                continue
            seq.pending_remote = False
            if fail is not None or sample is None:
                if (fail or FinishReason.ERROR) is FinishReason.ERROR:
                    self._finish_error(
                        seq, "remote_prefill",
                        "landing remote prefill failed",
                        "remote_prefill_failed",
                    )
                else:
                    self._finish(seq, fail)
                continue
            token, lp, top = sample
            seq.hash_seq = seq.pending_chain or TokenBlockSequence(
                list(seq.token_ids), self.config.block_size
            )
            self._emit_stored(seq)
            top_ids = np.array([t for t, _ in top], np.int32) if top else None
            top_lps = np.array([l for _, l in top], np.float32) if top else None
            self._append_token(seq, token, lp=lp, top_ids=top_ids, top_lps=top_lps)
        self._flush_streams()

    def _kv_stream_enabled(self) -> bool:
        """Streaming KV data plane default-on (DYN_KV_STREAM=0 reverts to
        the monolithic single-response path)."""
        return os.environ.get("DYN_KV_STREAM", "1") not in (
            "0", "false", "no",
        )

    async def _inject_payload(
        self, ids: list[int], payload, loop
    ) -> None:
        """Land a KvBlockPayload into device blocks. Int8 payloads land
        VERBATIM on an int8-resident runner (mantissas+scales scatter
        straight in — no dequant/requant, no double quantization); every
        other combination goes through decode() + the quantize-on-inject
        (or plain) scatter."""
        n = len(ids)
        if (
            payload.codec == "int8"
            and getattr(self.runner, "kv_quantized", False)
        ):
            kq, ks, vq, vs = payload.quantized_arrays()
            async with self._device_lock:
                await loop.run_in_executor(
                    None, self.runner.inject_blocks_quant, ids,
                    kq[:, :, :n], ks[:, :, :n],
                    vq[:, :, :n], vs[:, :, :n],
                )
            return
        k, v = payload.decode()
        async with self._device_lock:
            await loop.run_in_executor(
                None, self.runner.inject_blocks, ids, k[:, :, :n],
                v[:, :, :n],
            )

    async def _land_stream_frame(
        self, seq: _Sequence, frame, loop, landed: Optional[set] = None
    ) -> None:
        """Onboard one in-flight KV frame through the sharding-aware jitted
        scatter while later prefill chunks still compute remotely. Frames
        are keyed by (request_id, first_block) and idempotent: redelivered
        frames overwrite the same blocks with identical content."""
        if seq.slot is None or seq.ctx.is_killed() or seq.ctx.is_stopped():
            return  # cancelled mid-stream: drop the frame on the floor
        n = frame.payload.num_blocks
        ids = seq.block_ids[frame.first_block : frame.first_block + n]
        if not ids:
            return
        await self._inject_payload(ids, frame.payload, loop)
        if landed is not None:
            landed.update(range(frame.first_block, frame.first_block + len(ids)))
        self.stats.kv_frames_rx += 1
        nbytes = frame.payload.wire_nbytes
        self.stats.kv_wire_bytes_rx += nbytes
        # landed while the remote prefill was still running: this
        # transfer was hidden behind compute
        self.stats.kv_bytes_overlapped += nbytes

    async def _remote_prefill_task(self, seq: _Sequence) -> None:
        """Await a remote prefill, land its KV, and enter the decode batch.

        Mirrors the decode-worker half of the reference's disagg flow
        (examples/llm/components/worker.py): enqueue -> prefill fleet runs ->
        computed blocks arrive -> request joins the in-flight decode batch.
        KV arrives as chunk-granular frames landed incrementally while the
        remote prefill computes (monolithic single-payload when either side
        can't stream). Falls back to local prefill on any remote error;
        a killed sequence tears the stream down on both sides instead.
        """
        from dynamo_tpu.disagg.transfer import PrefillStreamCancelled

        loop = asyncio.get_running_loop()
        cached = await self._onboard_prefix(seq, loop)
        stream = self._kv_stream_enabled()
        landed_blocks: set[int] = set()
        rsp = seq.spans.get("remote_prefill")

        async def on_frame(frame) -> None:
            with dtrace.span(
                "kv_land", parent=rsp, proc=self.trace_proc,
                seq=frame.seq, blocks=frame.payload.num_blocks,
                nbytes=frame.payload.wire_nbytes,
            ):
                await self._land_stream_frame(seq, frame, loop, landed_blocks)

        extra = None
        if rsp is not None:
            # the prefill worker parents its serving span under this one
            # (RemotePrefillRequest.extra["trace"]), so the assembled trace
            # shows prefill compute + frame wire time on the worker's track
            extra = {"trace": {"tid": rsp.trace_id, "sid": rsp.span_id}}
        try:
            resp = await self.remote_prefill_client.prefill(
                seq.token_ids,
                temperature=seq.temperature,
                top_p=seq.top_p,
                top_k=seq.top_k,
                cached_blocks=cached,
                rep_pen=seq.rep_pen,
                key_data=self._key_row(seq),
                eos_ids=seq.eos_row,
                eos_suppress=seq.needs_eos_suppress,
                stream=stream,
                on_frame=on_frame if stream else None,
                deadline=seq.ctx.deadline,
                ctx=seq.ctx,
                extra=extra,
            )
        except PrefillStreamCancelled:
            # requester cancelled (kill/deadline cascade): no local
            # fallback — finish the sequence and free its blocks
            self._landed.append((seq, None, FinishReason.CANCELLED))
            self._wake.set()
            return
        except asyncio.CancelledError:
            if self._closed:
                raise  # engine shutdown cancelled us: propagate
            # client-side cancellation (transport restart): fall back local
            logger.warning("remote prefill cancelled; falling back local")
            resp = None
        except Exception as e:  # noqa: BLE001 — any transport failure
            logger.warning("remote prefill failed (%s); falling back local", e)
            resp = None
        if resp is not None and resp.code == "deadline_exceeded":
            # the prefill fleet dropped it as expired; don't burn local
            # compute either — the reaper's structured error fires next tick
            seq.ctx.kill()
            self._landed.append((seq, None, FinishReason.CANCELLED))
            self._wake.set()
            return
        if seq.slot is None:  # cancelled/finished while in flight
            return
        if seq.ctx.is_killed() or seq.ctx.is_stopped():
            self._landed.append((seq, None, FinishReason.CANCELLED))
            self._wake.set()
            return
        if resp is not None and resp.error is None and resp.streamed_blocks:
            # the fabric's pub/sub is at-most-once: a frame lost in a
            # failover window would leave a silent KV hole. The final
            # frame declares the streamed span — verify coverage and fall
            # back to a local prefill rather than decode against garbage.
            missing = set(range(cached, resp.first_block)) - landed_blocks
            if missing:
                logger.warning(
                    "seq %d: stream lost %d frame block(s); falling back "
                    "to local prefill", seq.seq_id, len(missing),
                )
                resp = None
        if faults.active():
            inj = faults.get_injector()
            if inj is not None:
                await inj.on_transfer()
        if rsp is not None:
            rsp.set(
                blocks_landed=len(landed_blocks),
                fallback_local=resp is None,
            )
        try:
            sample = await self._land_prefill(seq, resp, loop)
            self._landed.append((seq, sample, None))
        except Exception:  # noqa: BLE001 — never strand the consumer
            logger.exception("landing prefill for seq %d failed", seq.seq_id)
            self._landed.append((seq, None, FinishReason.ERROR))
        self._wake.set()

    async def _onboard_prefix(self, seq: _Sequence, loop) -> int:
        """Inject cached prefix blocks (G2/G3 tiers) into this sequence's
        device blocks so the prefill worker needn't ship them back
        (reference: KVBM onboarding, offload.rs)."""
        cached = seq.cached_prefix_blocks
        if self.block_manager is None or not cached:
            return 0
        from dynamo_tpu.disagg.transfer import from_wire_array

        try:
            if self._tier_quant_passthrough():
                # int8 tier pages land verbatim in the int8-resident cache
                kq, ks, vq, vs = await loop.run_in_executor(
                    None,
                    self.block_manager.load_blocks_quant,
                    seq.prefix_hashes[:cached],
                )
                async with self._device_lock:
                    await loop.run_in_executor(
                        None,
                        self.runner.inject_blocks_quant,
                        seq.block_ids[:cached],
                        kq, ks, vq, vs,
                    )
                return cached
            kw, vw = await loop.run_in_executor(
                None, self.block_manager.load_blocks, seq.prefix_hashes[:cached]
            )
            dtype = self.block_manager.layout.dtype
            k = from_wire_array(kw, dtype)
            v = from_wire_array(vw, dtype)
            async with self._device_lock:
                await loop.run_in_executor(
                    None,
                    self.runner.inject_blocks,
                    seq.block_ids[:cached],
                    k,
                    v,
                )
            return cached
        except Exception:  # noqa: BLE001 — cache miss races are fine
            logger.exception("prefix onboard failed; full remote prefill")
            return 0

    async def _land_prefill(self, seq: _Sequence, resp, loop) -> tuple:
        """Device-side landing only: inject blocks / fallback prefill.
        Returns (first_token, logprob | None, top | None); scheduler-visible
        completion happens later in _process_landed on the engine loop."""
        if resp is not None and resp.error is None:
            if getattr(resp, "k_dev", None) is not None:
                # device-native payload (colocated P/D): blocks move
                # mesh-to-mesh via device_put inside inject_blocks_device —
                # no host hop, no msgpack
                ids = seq.block_ids[
                    resp.first_block : resp.first_block + resp.num_blocks
                ]
                if ids:
                    async with self._device_lock:
                        await loop.run_in_executor(
                            None,
                            self.runner.inject_blocks_device,
                            ids,
                            resp.k_dev,
                            resp.v_dev,
                        )
                return (resp.first_token, resp.first_logprob, resp.first_top)
            if resp.payload is not None:
                # payload may be absent when every shippable block was a
                # prefix hit already sitting in this worker's cache; on the
                # streaming path this is only the not-yet-streamed tail
                self.stats.kv_wire_bytes_rx += resp.payload.wire_nbytes
                ids = seq.block_ids[
                    resp.first_block
                    : resp.first_block + resp.payload.num_blocks
                ]
                if ids:
                    await self._inject_payload(ids, resp.payload, loop)
            return (resp.first_token, resp.first_logprob, resp.first_top)
        # local fallback (also covers error responses)
        key_row = self._key_row(seq)
        async with self._device_lock:
            sample = await loop.run_in_executor(
                None,
                lambda: self.runner.fetch_sample(
                    self.runner.prefill(
                        seq.token_ids,
                        seq.block_ids,
                        seq.temperature,
                        seq.top_p,
                        seq.top_k,
                        rep_pen=seq.rep_pen,
                        key_data=key_row,
                        eos_ids=seq.eos_row,
                        eos_suppress=seq.needs_eos_suppress,
                        want_logprobs=seq.want_logprobs,
                    )
                ),
            )
        tok, lp, tids, tlps = sample
        top = [[int(t), float(l)] for t, l in zip(tids, tlps)]
        return (int(tok), float(lp), top)

    async def prefill_only(self, req: Any) -> Any:
        """Serve one RemotePrefillRequest (the prefill-worker role).

        Recomputes the full prompt on scratch blocks, ships back blocks from
        `req.cached_blocks` on (prefix-hit blocks already sit in the decode
        worker's cache — bandwidth saved; compute is not, unlike the
        reference's NIXL read-back of prefix blocks, which ICI cannot
        replicate without the decode mesh's cooperation).
        """
        from dynamo_tpu.disagg.protocols import (
            KvBlockPayload,
            RemotePrefillResponse,
            wire_codec_from_env,
        )

        loop = asyncio.get_running_loop()
        bs = self.config.block_size
        T = len(req.token_ids)
        if T > self.config.max_model_len:
            return RemotePrefillResponse(
                request_id=req.request_id,
                first_token=-1,
                error=f"prompt {T} exceeds max_model_len",
            )
        need = (T + bs - 1) // bs
        block_ids = self.allocator.alloc(need)
        try:
            async with self._device_lock:
                sample = await loop.run_in_executor(
                    None,
                    lambda: self.runner.fetch_sample(
                        self.runner.prefill(
                            list(req.token_ids),
                            block_ids,
                            req.temperature,
                            req.top_p,
                            req.top_k,
                            rep_pen=getattr(req, "rep_pen", 1.0),
                            key_data=(
                                np.asarray(req.key_data, np.uint32)
                                if getattr(req, "key_data", None) is not None
                                else None
                            ),
                            eos_ids=(
                                np.asarray(req.eos_ids, np.int32)
                                if getattr(req, "eos_ids", None) is not None
                                else None
                            ),
                            eos_suppress=getattr(req, "eos_suppress", False),
                        )
                    ),
                )
                tok_arr, lp_arr, tids_arr, tlps_arr = sample
                ship = block_ids[req.cached_blocks :]
                quant = getattr(self.runner, "kv_quantized", False)
                if ship:
                    if quant:
                        # int8-resident: ship the device's mantissas+scales
                        # verbatim — no dequant/requant recode on the wire
                        kq, ks, vq, vs = await loop.run_in_executor(
                            None, self.runner.extract_blocks_quant, ship
                        )
                    else:
                        k, v = await loop.run_in_executor(
                            None, self.runner.extract_blocks, ship
                        )
            payload = None
            if ship:
                if quant:
                    payload = KvBlockPayload.from_quantized(kq, ks, vq, vs)
                else:
                    payload = KvBlockPayload.encode(
                        k, v, wire_codec_from_env()
                    )
                self.stats.kv_wire_bytes_tx += payload.wire_nbytes
            self.stats.generated_tokens += 1
            return RemotePrefillResponse(
                request_id=req.request_id,
                first_token=int(tok_arr),
                payload=payload,
                first_block=req.cached_blocks,
                first_logprob=float(lp_arr),
                first_top=[
                    [int(t), float(l)] for t, l in zip(tids_arr, tlps_arr)
                ],
            )
        finally:
            self.allocator.free(block_ids)

    async def prefill_only_stream(
        self, req: Any, emit, cancelled: Optional[Callable[[], bool]] = None
    ) -> Optional[Any]:
        """Streaming prefill-worker role: run the prompt through the
        chunked-prefill program and `emit` a KvStreamFrame of completed
        blocks after each chunk, while the NEXT chunk's dispatch is already
        queued on device — the publish (wire transfer) overlaps chunk
        compute, so by the time the final frame (first token + tail blocks)
        is published there is ~nothing left to transfer.

        `emit` may await (bounded-window backpressure upstream). A truthy
        `cancelled()` between chunks aborts the stream: scratch blocks are
        freed and None is returned (nothing published, caller just acks).
        Prompts that fit one chunk fall back to the monolithic
        prefill_only — same wire contract, no frame overhead."""
        from dynamo_tpu.disagg.protocols import (
            KvBlockPayload,
            KvStreamFrame,
            RemotePrefillResponse,
            wire_codec_from_env,
        )

        loop = asyncio.get_running_loop()
        bs = self.config.block_size
        T = len(req.token_ids)
        chunk_c = getattr(self.runner, "prefill_chunk_tokens", 0)
        if not chunk_c or T <= chunk_c:
            return await self.prefill_only(req)
        if T > self.config.max_model_len:
            return RemotePrefillResponse(
                request_id=req.request_id,
                first_token=-1,
                error=f"prompt {T} exceeds max_model_len",
            )
        codec = wire_codec_from_env()
        quant = getattr(self.runner, "kv_quantized", False)
        if quant:
            # int8-resident: every frame ships device mantissas+scales
            # verbatim (no recode); tight pow2 padding like the bf16 path
            def extract(ids):
                return self.runner.extract_blocks_quant(ids, tight=True)

            def build_payload(data):
                return KvBlockPayload.from_quantized(*data)
        else:
            extract = getattr(
                self.runner, "extract_blocks_tight",
                self.runner.extract_blocks,
            )

            def build_payload(data):
                return KvBlockPayload.encode(data[0], data[1], codec)
        key_data = (
            np.asarray(req.key_data, np.uint32)
            if getattr(req, "key_data", None) is not None
            else None
        )
        eos_ids = (
            np.asarray(req.eos_ids, np.int32)
            if getattr(req, "eos_ids", None) is not None
            else None
        )
        need = (T + bs - 1) // bs
        block_ids = self.allocator.alloc(need)
        # cached leading blocks already sit in the requester's cache and
        # are never shipped; `shipped` is the block cursor on the wire
        shipped = min(int(getattr(req, "cached_blocks", 0) or 0), need - 1)
        streamed = 0
        frame_seq = 0
        try:
            out = None
            pos = 0
            while pos < T:
                if cancelled is not None and cancelled():
                    return None
                chunk = req.token_ids[pos : pos + chunk_c]
                final = pos + len(chunk) >= T

                async with self._device_lock:
                    def run_chunk(chunk=chunk, start=pos):
                        return self.runner.prefill_chunk(
                            chunk, start, T, block_ids,
                            req.temperature, req.top_p, req.top_k,
                            rep_pen=getattr(req, "rep_pen", 1.0),
                            key_data=key_data,
                            eos_ids=eos_ids,
                            eos_suppress=getattr(req, "eos_suppress", False),
                        )

                    out = await self._dispatch(
                        "prefill_chunk", run_chunk, tokens=len(chunk)
                    )
                pos += len(chunk)
                # ship the blocks this chunk completed (the partial tail
                # stays for the final frame so the decode side has exactly
                # one landing point per block) — the publish runs in the
                # background while the next chunk computes
                upto = pos // bs
                if not final and upto > shipped:
                    ids = block_ids[shipped:upto]
                    async with self._device_lock:
                        data = await loop.run_in_executor(None, extract, ids)
                    payload = build_payload(data)
                    frame = KvStreamFrame(
                        request_id=req.request_id,
                        seq=frame_seq,
                        first_block=shipped,
                        payload=payload,
                    )
                    frame_seq += 1
                    streamed += len(ids)
                    self.stats.kv_frames_tx += 1
                    self.stats.kv_wire_bytes_tx += payload.wire_nbytes
                    await emit(frame)
                    shipped = upto
            if cancelled is not None and cancelled():
                return None
            # final frame: first token (+ logprob surface) and every block
            # not yet streamed — at minimum the partial tail block
            async with self._device_lock:
                sample = await loop.run_in_executor(
                    None, lambda: self.runner.fetch_sample(out)
                )
                ship = block_ids[shipped:]
                data = None
                if ship:
                    data = await loop.run_in_executor(None, extract, ship)
            tok_arr, lp_arr, tids_arr, tlps_arr = sample
            payload = None
            if ship:
                payload = build_payload(data)
                self.stats.kv_wire_bytes_tx += payload.wire_nbytes
            self.stats.generated_tokens += 1
            return RemotePrefillResponse(
                request_id=req.request_id,
                first_token=int(tok_arr),
                payload=payload,
                first_block=shipped,
                streamed_blocks=streamed,
                first_logprob=float(lp_arr),
                first_top=[
                    [int(t), float(l)] for t, l in zip(tids_arr, tlps_arr)
                ],
            )
        finally:
            self.allocator.free(block_ids)

    async def embed(self, token_ids: list[int]):
        """Pooled embedding for /v1/embeddings; serialized with the engine
        loop's device calls (embedding traffic shares the chip)."""
        loop = asyncio.get_running_loop()
        async with self._device_lock:
            return await loop.run_in_executor(
                None, self.runner.embed, list(token_ids)
            )

    async def prefill_only_device(self, req: Any) -> Any:
        """Colocated prefill-worker role: like prefill_only but the KV
        payload stays ON DEVICE (disagg/colocated.py). The caller's decode
        engine lands the blocks with inject_blocks_device — same process,
        mesh-to-mesh, zero host copies."""
        from dynamo_tpu.disagg.colocated import DevicePrefillResponse

        loop = asyncio.get_running_loop()
        bs = self.config.block_size
        T = len(req.token_ids)
        if T > self.config.max_model_len:
            return DevicePrefillResponse(
                request_id=req.request_id,
                first_token=-1,
                error=f"prompt {T} exceeds max_model_len",
            )
        need = (T + bs - 1) // bs
        block_ids = self.allocator.alloc(need)
        try:
            async with self._device_lock:
                sample = await loop.run_in_executor(
                    None,
                    lambda: self.runner.fetch_sample(
                        self.runner.prefill(
                            list(req.token_ids),
                            block_ids,
                            req.temperature,
                            req.top_p,
                            req.top_k,
                            rep_pen=getattr(req, "rep_pen", 1.0),
                            key_data=(
                                np.asarray(req.key_data, np.uint32)
                                if getattr(req, "key_data", None) is not None
                                else None
                            ),
                            eos_ids=(
                                np.asarray(req.eos_ids, np.int32)
                                if getattr(req, "eos_ids", None) is not None
                                else None
                            ),
                            eos_suppress=getattr(req, "eos_suppress", False),
                        )
                    ),
                )
                tok_arr, lp_arr, tids_arr, tlps_arr = sample
                ship = block_ids[req.cached_blocks :]
                k_dev = v_dev = None
                n_ship = 0
                if ship:
                    k_dev, v_dev, n_ship = await loop.run_in_executor(
                        None, self.runner.extract_blocks_device, ship
                    )
            self.stats.generated_tokens += 1
            return DevicePrefillResponse(
                request_id=req.request_id,
                first_token=int(tok_arr),
                k_dev=k_dev,
                v_dev=v_dev,
                num_blocks=n_ship,
                first_block=req.cached_blocks,
                first_logprob=float(lp_arr),
                first_top=[
                    [int(t), float(l)] for t, l in zip(tids_arr, tlps_arr)
                ],
            )
        finally:
            self.allocator.free(block_ids)

    @staticmethod
    def _ctx_tokens(active: list[_Sequence]) -> int:
        """Summed context of the live lanes: what a decode step's attention
        reads, carried by the `loop.dispatch` phase."""
        return sum(len(s.token_ids) for s in active)

    def _lane_remaining(self, seq: _Sequence) -> int:
        """Tokens this lane may still emit (max_new and model-length caps)."""
        return max(
            1,
            min(
                seq.max_new - seq.num_generated,
                self.config.max_model_len - len(seq.token_ids),
            ),
        )

    def _idle_sampling(self) -> None:
        """Every lane's sampling inputs as an idle lane's: greedy, nothing
        that restricts a draw, no log-probs asked for, so that only a live
        lane (`_fill_lane`) makes a step pay the pool or the surface."""
        self._temps.fill(0.0)
        self._top_ps.fill(1.0)
        self._top_ks.fill(0)
        self._want_lps.fill(False)

    def _fill_lane(self, seq: _Sequence) -> int:
        """Write one active lane's shared per-step inputs into the batch
        arrays (both decode phases use the identical eight); returns the
        fed token's position."""
        i = seq.slot
        pos = seq.pos - 1  # position of the token being fed
        self._tokens[i] = seq.token_ids[-1]
        self._positions[i] = pos
        self._block_tables[i, : len(seq.block_ids)] = seq.block_ids
        if self._window is not None:
            # the window group's table behind the full group's: each block's
            # companion; the blocks given back keep the null block the row
            # was cleared to
            w = self.runner.max_blocks_per_seq + seq.window_released
            kept = seq.block_ids[seq.window_released :]
            self._block_tables[i, w : w + len(kept)] = (
                self.allocator.window_of[kept]
            )
        self._temps[i] = seq.temperature
        self._top_ps[i] = seq.top_p
        self._top_ks[i] = seq.top_k
        self._want_lps[i] = seq.want_logprobs
        self._keys[i] = self._key_row(seq)
        return pos

    def _horizon_for(
        self, active: list[_Sequence], chained: bool = False
    ) -> int:
        """Pick this iteration's decode horizon. 1 = single-step path.
        `chained`: for a dispatch to be queued behind the one in flight,
        whose tokens the host has not replayed: the lanes are up to a
        horizon further than the host's arrays say, so everything here
        reaches two horizons from them; any answer but that dispatch's
        horizon means it must be landed first (`_decode_phase`).
        `self._horizon_why` says why the answer was 1."""
        self._horizon_why = "other"
        H = self.config.decode_horizon
        if H <= 1 or not hasattr(self.runner, "decode_multi"):
            return 1
        # penalties ride the horizon too: the program carries [B, V] count
        # tables on device, so a penalty lane no longer drags the whole
        # batch to single-stepping (VERDICT r4 weak #2)
        # overflow-EOS redraws (_append_token's eos_drops path) can't happen
        # mid-horizon: gate batches where the device mask can't hold the
        # full stop set of a min_tokens sequence
        from dynamo_tpu.ops.sampling import MAX_EOS_IDS

        if any(
            s.needs_eos_suppress and len(s.eos) > MAX_EOS_IDS for s in active
        ):
            return 1
        # everyone on their last token: single-step. Any other tail (2..H-1
        # tokens left on every lane) still runs the ONE horizon program —
        # lanes freeze on device at their own limit — because a program
        # per tail length (decode_multi@H3, @H2) is a cold compile of the
        # largest program family in the middle of serving. Chained: every
        # lane ends inside the dispatch in flight, and one behind it would
        # run frozen lanes only.
        remaining = [self._lane_remaining(s) for s in active]
        if max(remaining) <= (H if chained else 1):
            return 1
        # preallocate KV blocks to cover every horizon write — capped at
        # each lane's OWN remaining budget (a lane one token from its limit
        # must not grow past max_blocks_per_seq). On pressure, fall back to
        # single-step (its just-in-time alloc can preempt).
        bs = self.config.block_size
        reach = 2 * H if chained else H
        for seq, left in zip(active, remaining):
            last_write = (seq.pos - 1) + (min(reach, left) - 1)
            need = last_write // bs + 1 - len(seq.block_ids)
            if need > 0:
                try:
                    seq.block_ids.extend(self.allocator.alloc(need))
                except OutOfBlocks:
                    self._horizon_why = "blocks"
                    return 1
        return H

    async def _decode_phase(self, loop, active: list[_Sequence]) -> None:
        # brownout >= spec_off pauses drafting: the verify premium and
        # drafter host time go back to real tokens while the SLO burns
        fl = self._flight
        with dtrace.phase("loop.pack"):
            # drafting and the horizon's block preallocation are host work
            # of this dispatch, like the lane arrays built below
            drafts = None
            if self.drafter is not None and not self._spec_paused:
                drafts = self._collect_drafts(active)
            H = (
                self._horizon_for(active, chained=fl is not None)
                if drafts is None else 1
            )
        if fl is not None and H != fl.H:
            # nothing can be queued behind the dispatch in flight (the two
            # horizons' blocks are not there, or every lane ends in it):
            # land it, then today's order on what its replay left
            await self._land_flight(self._horizon_why)
            active = [s for s in active if s.slot is not None]
            if not active:
                return
            with dtrace.phase("loop.pack"):
                H = self._horizon_for(active)
        if drafts is not None:
            await self._spec_decode_phase(loop, active, drafts)
            return
        if H > 1:
            await self._decode_multi_phase(loop, active, H)
            return
        await self._decode_single_phase(loop, active)

    async def _decode_single_phase(
        self, loop, active: list[_Sequence]
    ) -> None:
        with dtrace.phase("loop.pack"):
            B = self.config.max_batch
            self._block_tables.fill(0)
            self._positions.fill(0)
            self._slot_indices.fill(0)  # null block slot 0
            self._idle_sampling()
            bs = self.config.block_size
            for seq in active:
                pos = self._fill_lane(seq)
                self._slot_indices[seq.slot] = (
                    seq.block_ids[pos // bs] * bs + pos % bs
                )
            penalties = None
            eos_mask = None
            any_pen = any(seq.has_penalties for seq in active)
            any_eos = any(seq.needs_eos_suppress for seq in active)
            if any_eos and not any_pen:
                # min_tokens-only batch: EOS masking needs no token history —
                # skip the [B, L] upload the penalty program pays every step
                from dynamo_tpu.ops.sampling import MAX_EOS_IDS

                eos_ids = np.full((B, MAX_EOS_IDS), -1, np.int32)
                eos_sup = np.zeros(B, bool)
                for seq in active:
                    eos_ids[seq.slot] = seq.eos_row
                    eos_sup[seq.slot] = seq.needs_eos_suppress
                eos_mask = (eos_ids, eos_sup)
            elif any_pen:
                # full-history penalties ride a separate (lazily compiled)
                # program; the plain path never pays the [B, L] input
                L = self.config.max_model_len
                hist = np.zeros((B, L), np.int32)
                hist_len = np.zeros(B, np.int32)
                prompt_len = np.zeros(B, np.int32)
                freq = np.zeros(B, np.float32)
                pres = np.zeros(B, np.float32)
                rep = np.ones(B, np.float32)
                from dynamo_tpu.ops.sampling import MAX_EOS_IDS

                eos_ids = np.full((B, MAX_EOS_IDS), -1, np.int32)
                eos_sup = np.zeros(B, bool)
                for seq in active:
                    i = seq.slot
                    n = min(len(seq.token_ids), L)
                    hist[i, :n] = seq.token_ids[:n]
                    hist_len[i] = n
                    prompt_len[i] = min(seq.num_prompt, n)
                    freq[i] = seq.freq_pen
                    pres[i] = seq.pres_pen
                    rep[i] = seq.rep_pen
                    eos_ids[i] = seq.eos_row
                    eos_sup[i] = seq.needs_eos_suppress
                penalties = (
                    hist, hist_len, prompt_len, freq, pres, rep, eos_ids, eos_sup
                )
        async with self._device_lock:
            sample = await self._dispatch(
                "decode",
                lambda: self.runner.fetch_sample(
                    self.runner.decode(
                        self._tokens,
                        self._positions,
                        self._block_tables,
                        self._slot_indices,
                        self._temps,
                        self._top_ps,
                        self._top_ks,
                        keys=self._keys,
                        penalties=penalties,
                        eos_mask=eos_mask,
                        want_logprobs=self._want_lps,
                    )
                ),
                lanes=len(active),
                capacity=self.config.max_batch,
                ctx_tokens=self._ctx_tokens(active),
                why="penalties" if any_pen else self._horizon_why,
            )
        with self._emitting():
            toks, lps, tids, tlps = sample
            for seq in active:
                if seq.slot is None:
                    continue  # finished/cancelled concurrently
                i = seq.slot
                self._append_token(
                    seq, int(toks[i]), lp=float(lps[i]),
                    top_ids=tids[i], top_lps=tlps[i],
                )

    def _collect_drafts(
        self, active: list[_Sequence]
    ) -> Optional[dict[int, list[int]]]:
        """Host drafting pass: seq_id -> proposed continuation tokens.

        None routes the batch to the plain decode paths — when no lane has
        a usable draft (the verify pass would be a plain decode step with
        extra logits columns) or when a min_tokens lane carries more stop
        ids than the device mask (the same overflow-EOS redraw hazard that
        gates the horizon; those redraws need per-token host control)."""
        from dynamo_tpu.ops.sampling import MAX_EOS_IDS

        if any(
            s.needs_eos_suppress and len(s.eos) > MAX_EOS_IDS for s in active
        ):
            return None
        out: dict[int, list[int]] = {}
        any_draft = False
        for seq in active:
            if seq.spec_backoff > 0:
                seq.spec_backoff -= 1
                out[seq.seq_id] = []
                continue
            # a lane may emit at most _lane_remaining tokens this dispatch,
            # and the verify pass always emits one bonus token past the
            # accepted drafts — cap drafts so writes stay inside the lane's
            # block budget (partial-block rollback is overwrite-based and
            # never needs blocks past max_model_len)
            cap = min(self.config.spec_k, self._lane_remaining(seq) - 1)
            d = self.drafter.draft(seq.token_ids, cap) if cap > 0 else []
            out[seq.seq_id] = d
            any_draft = any_draft or bool(d)
        if not any_draft:
            return None
        drafted = sum(1 for d in out.values() if d)
        need = max(1, int(np.ceil(self.config.spec_min_coverage * len(active))))
        if drafted < need:
            return None  # too sparse: plain decode is the better dispatch
        return out

    async def _spec_decode_phase(
        self, loop, active: list[_Sequence], drafts: dict[int, list[int]]
    ) -> None:
        """Speculative dispatch: one verify weight pass over each lane's
        draft window (+ the chained horizon continuation, device-side),
        then host-side accept: walk the packed per-position samples in
        order through the SAME _append_token flow as every other decode
        path and stop a lane at its first draft mismatch. All emitted
        tokens are the model's own samples, so streaming, stop handling,
        penalties, block growth and finish reasons are untouched — the
        draft only decides how many weight reads those tokens cost."""
        from dynamo_tpu.ops.sampling import MAX_EOS_IDS

        with dtrace.phase("loop.pack"):
            B = self.config.max_batch
            K = self.config.spec_k
            bs = self.config.block_size
            any_pen = any(s.has_penalties for s in active)
            # chained continuation after the verify pass (the RTT-amortizing
            # horizon): penalty batches run verify-only — the device count
            # tables can't subtract a rejected draft back out
            E = 0
            if self.config.decode_horizon > 1 and not any_pen:
                E = self.config.decode_horizon - 1
            # preallocate KV blocks for every potential write this dispatch
            # (same formula as _horizon_for: the last emitted token is never
            # fed, so writes cover lane_steps - 1 positions past pos-1)
            for seq in active:
                d = drafts.get(seq.seq_id) or []
                lane_steps = min(len(d) + 1 + E, self._lane_remaining(seq))
                last_write = (seq.pos - 1) + (lane_steps - 1)
                need = last_write // bs + 1 - len(seq.block_ids)
                if need > 0:
                    try:
                        seq.block_ids.extend(self.allocator.alloc(need))
                    except OutOfBlocks:
                        # block pressure: fall back to single-step (its
                        # just-in-time alloc can preempt)
                        await self._decode_single_phase(loop, active)
                        return
            self._block_tables.fill(0)
            self._positions.fill(0)
            self._idle_sampling()
            act = np.zeros(B, bool)
            limit_rem = np.ones(B, np.int32)
            min_rem = np.zeros(B, np.int32)
            eos_ids = np.full((B, MAX_EOS_IDS), -1, np.int32)
            draft_arr = np.full((B, K), -1, np.int32)
            draft_len = np.zeros(B, np.int32)
            for seq in active:
                i = seq.slot
                self._fill_lane(seq)
                act[i] = True
                limit_rem[i] = self._lane_remaining(seq)
                min_rem[i] = max(0, seq.min_tokens - seq.num_generated)
                eos_ids[i] = seq.eos_row
                d = drafts.get(seq.seq_id) or []
                draft_len[i] = len(d)
                if d:
                    draft_arr[i, : len(d)] = d
                    self.stats.num_drafts += 1
                    self.stats.num_draft_tokens += len(d)
            penalties = None
            if any_pen:
                # one [B, L] upload per dispatch, scattered to count tables on
                # device — identical contract to _decode_multi_phase
                L = self.config.max_model_len
                hist = np.zeros((B, L), np.int32)
                hist_len = np.zeros(B, np.int32)
                prompt_len = np.zeros(B, np.int32)
                freq = np.zeros(B, np.float32)
                pres = np.zeros(B, np.float32)
                rep = np.ones(B, np.float32)
                for seq in active:
                    i = seq.slot
                    n = min(len(seq.token_ids), L)
                    hist[i, :n] = seq.token_ids[:n]
                    hist_len[i] = n
                    prompt_len[i] = min(seq.num_prompt, n)
                    freq[i] = seq.freq_pen
                    pres[i] = seq.pres_pen
                    rep[i] = seq.rep_pen
                penalties = (hist, hist_len, prompt_len, freq, pres, rep)
        async with self._device_lock:
            packed = await self._dispatch(
                "spec_verify",
                lambda: self.runner.fetch_horizon(
                    self.runner.spec_verify(
                        K, E,
                        self._tokens, draft_arr, draft_len,
                        self._positions, self._block_tables,
                        self._temps, self._top_ps, self._top_ks,
                        self._keys, act, limit_rem, min_rem, eos_ids,
                        penalties=penalties, want_logprobs=self._want_lps,
                    )
                ),
                lanes=len(active),
                capacity=self.config.max_batch,
                ctx_tokens=self._ctx_tokens(active),
                horizon=1 + E,
            )
        with self._emitting():
            K2 = (packed.shape[-1] - 2) // 2
            # verify rows: accept the longest prefix of drafts matching the
            # model's own tokens, then the bonus token
            for seq in active:
                if seq.slot is None:
                    continue
                i = seq.slot
                d = drafts.get(seq.seq_id) or []
                lane_accepted = 0
                for h in range(len(d) + 1):
                    row = packed[h]
                    tok = int(row[i, 0])
                    if tok < 0:
                        break  # device marked the position invalid
                    accept = h < len(d) and d[h] == tok
                    self._append_token(
                        seq, tok,
                        lp=float(row[i, 1]),
                        top_ids=row[i, 2:2 + K2].astype(np.int32),
                        top_lps=row[i, 2 + K2:],
                    )
                    if accept:
                        lane_accepted += 1
                        self.stats.num_accepted_tokens += 1
                        if h < len(self.stats.accepted_per_pos):
                            self.stats.accepted_per_pos[h] += 1
                    if seq.slot is None or (h < len(d) and not accept):
                        break
                if d:
                    # verify premium paid for rejected draft positions: the
                    # device computed len(d)+1 positions but only
                    # lane_accepted drafts landed
                    self.stats.goodput.record_waste(
                        "spec_rejected", len(d) - lane_accepted
                    )
                    if lane_accepted:
                        seq.spec_fail = 0
                    else:
                        # whole draft rejected: history stopped predicting —
                        # exponentially back off this lane's drafting so the
                        # verify premium isn't paid dispatch after dispatch
                        # on low-repetition traffic
                        seq.spec_fail += 1
                        seq.spec_backoff = min(1 << seq.spec_fail, 32)
            # continuation rows: plain chained decode tokens from the accept
            # point (frozen lanes emit -1; a host-side finish above leaves
            # slot None and the lane skips its rows)
            for e in range(E):
                row = packed[K + 1 + e]
                for seq in active:
                    if seq.slot is None:
                        continue
                    i = seq.slot
                    tok = int(row[i, 0])
                    if tok < 0:
                        continue
                    self._append_token(
                        seq, tok,
                        lp=float(row[i, 1]),
                        top_ids=row[i, 2:2 + K2].astype(np.int32),
                        top_lps=row[i, 2 + K2:],
                    )

    async def _decode_multi_phase(
        self, loop, active: list[_Sequence], H: int
    ) -> None:
        """Horizon decode: H device-chained steps, one packed fetch.

        The device freezes a lane at EOS / its remaining-token budget and
        emits -1 for frozen steps; the host replays the packed [H, B, .]
        samples through the exact same _append_token flow as single-step,
        so streaming, stop handling, block growth (preallocated here) and
        finish reasons are identical — just H tokens per round trip."""
        from dynamo_tpu.ops.sampling import MAX_EOS_IDS

        with dtrace.phase("loop.pack"):
            B = self.config.max_batch
            self._block_tables.fill(0)
            self._positions.fill(0)
            self._idle_sampling()
            act = np.zeros(B, bool)
            limit_rem = np.ones(B, np.int32)
            min_rem = np.zeros(B, np.int32)
            eos_ids = np.full((B, MAX_EOS_IDS), -1, np.int32)
            for seq in active:
                i = seq.slot
                self._fill_lane(seq)
                act[i] = True
                limit_rem[i] = self._lane_remaining(seq)
                min_rem[i] = max(0, seq.min_tokens - seq.num_generated)
                eos_ids[i] = seq.eos_row
            penalties = None
            if any(seq.has_penalties for seq in active):
                # one [B, L] upload per HORIZON (not per step): the program
                # scatters it into count tables and maintains them on device;
                # plain lanes run freq=0/pres=0/rep=1 (exact pass-through)
                L = self.config.max_model_len
                hist = np.zeros((B, L), np.int32)
                hist_len = np.zeros(B, np.int32)
                prompt_len = np.zeros(B, np.int32)
                freq = np.zeros(B, np.float32)
                pres = np.zeros(B, np.float32)
                rep = np.ones(B, np.float32)
                for seq in active:
                    i = seq.slot
                    n = min(len(seq.token_ids), L)
                    hist[i, :n] = seq.token_ids[:n]
                    hist_len[i] = n
                    prompt_len[i] = min(seq.num_prompt, n)
                    freq[i] = seq.freq_pen
                    pres[i] = seq.pres_pen
                    rep[i] = seq.rep_pen
                penalties = (hist, hist_len, prompt_len, freq, pres, rep)
        label = f"decode_multi@H{H}B{self.config.max_batch}"
        # steady decode launches ahead: this dispatch goes on the device's
        # queue and stays there (`new`), and what the device does while the
        # host replays and serves the one before it (`fl`, not read yet) is
        # this. Behind one in flight its lanes go on from that one's carry
        # on the device: the arrays above are the host's state as of THAT
        # dispatch's start, which `decode_multi` is told with `chain`.
        # Otherwise one dispatch, whole: launched, read, replayed
        fl = self._flight
        new = None
        if fl is not None or self._may_fly(label, penalties):
            new = _Flight(label, list(active), H)
            extra = {"chain": act if fl is not None else None}
        else:
            extra = {"penalties": penalties}

        def call():
            packed = self.runner.decode_multi(
                H,
                self._tokens, self._positions, self._block_tables,
                self._temps, self._top_ps, self._top_ks,
                self._keys, act, limit_rem, min_rem, eos_ids,
                want_logprobs=self._want_lps, **extra,
            )
            if new is None:
                return self.runner.fetch_horizon(packed)
            new.packed = packed
            if fl is not None:
                return self.runner.fetch_horizon(fl.packed)

        async with self._device_lock:
            packed = await self._dispatch(
                # the label names the program that ran: a ledger that
                # shows only "decode" served at H=1
                label,
                call,
                lanes=len(active),
                capacity=self.config.max_batch,
                # a chained lane is up to a horizon further than the host's
                # arrays say
                ctx_tokens=self._ctx_tokens(active)
                + (H * len(active) if fl is not None else 0),
                horizon=H,
                why="penalties" if penalties is not None else self._chain_why,
                launches=new,
                lands=fl,
            )
        self._chain_why = "other"
        if new is None:
            self._replay_horizon(active, H, packed)
        elif fl is not None:
            self._replay_horizon(fl.lanes, H, packed)

    def _replay_horizon(
        self, lanes: list[_Sequence], H: int, packed: np.ndarray
    ) -> None:
        """Replay one fetched horizon through `_append_token`, step by
        step, over the lanes it was launched with. A lane that has finished
        since (in this horizon, or, under the launch ahead, in the one
        before while this one was on the device already) has no slot and is
        skipped: whatever the device still ran of it is dropped."""
        with self._emitting():
            counted = self.runner.step_stats(packed)
            if counted is not None:
                self.stats.goodput.record_moe(counted)
            K = (packed.shape[-1] - 2) // 2
            for h in range(H):
                step = packed[h]
                for seq in lanes:
                    if seq.slot is None:
                        continue  # finished earlier in this horizon
                    i = seq.slot
                    tok = int(step[i, 0])
                    if tok < 0:
                        continue  # lane was frozen on device
                    self._append_token(
                        seq,
                        tok,
                        lp=float(step[i, 1]),
                        top_ids=step[i, 2:2 + K].astype(np.int32),
                        top_lps=step[i, 2 + K:],
                    )

    @contextlib.contextmanager
    def _emitting(self):
        """The replay of one dispatch's results (`loop.emit`): whatever it
        appended leaves as one item a sequence when it ends, before the
        engine's task gives up the event loop."""
        with dtrace.phase("loop.emit"):
            try:
                yield
            finally:
                self._flush_streams()

    def _flush_streams(self) -> None:
        for seq in self._unflushed:
            self._flush_seq(seq)
        self._unflushed.clear()

    def _flush_seq(self, seq: _Sequence) -> None:
        out = seq.pending
        if out is not None:
            seq.pending = None
            self.stats.stream_items += 1
            self.stats.goodput.record_stream(len(out.token_ids))
            seq.out.put_nowait(out)

    def _send_final(self, seq: _Sequence, item: LLMEngineOutput) -> None:
        """Put a finish or an error on a sequence's stream, behind the
        tokens it still has pending."""
        self._flush_seq(seq)
        seq.out.put_nowait(item)

    def _append_sample(
        self, seq: _Sequence, sample: tuple[np.ndarray, ...]
    ) -> None:
        """Unpack a (tok, logprob, top_ids, top_lps) runner sample for a
        single sequence and append it."""
        tok, lp, tids, tlps = sample
        self._append_token(
            seq, int(tok), lp=float(lp), top_ids=tids, top_lps=tlps
        )

    def _append_token(
        self,
        seq: _Sequence,
        token: int,
        lp: Optional[float] = None,
        top_ids: Optional[np.ndarray] = None,
        top_lps: Optional[np.ndarray] = None,
    ) -> None:
        """Record a newly generated token: gather it into the sequence's
        pending item (one item a sequence and dispatch: `_flush_streams`
        puts it on the stream when the dispatch is replayed), grow blocks,
        stop. A finish or an error flushes first (`_send_final`), so a
        consumer sees tokens, then the finish, as its own item."""
        self.stats.generated_tokens += 1
        self.stats.goodput.record_decode_tokens()
        if seq.spans and "decode" not in seq.spans:
            # first token: the prefill phase (local or remote) is over
            self._sp_finish(seq, "prefill")
            self._sp_finish(seq, "remote_prefill")
            self._sp_begin(seq, "decode")
        if faults.active():
            inj = faults.get_injector()
            if inj is not None and inj.on_token():
                self._abort_all("injected engine fault (abort_after_tokens)")
                return
        if seq.ctx.is_stopped():
            self._finish(seq, FinishReason.CANCELLED)
            return
        if token in seq.eos:
            if seq.num_generated >= seq.min_tokens:
                self._finish(seq, FinishReason.EOS)  # eos token stays hidden
                return
            # min_tokens unmet but an EOS got sampled anyway: the device
            # mask covers only the first MAX_EOS_IDS sorted stop ids, so an
            # overflow id can slip through. Appending would leak the special
            # token into the stream AND stop the HTTP-layer decoder early —
            # drop it and resample next step (_key_row folds eos_drops into
            # the counter, so the redraw uses a fresh key). A greedy
            # sequence can still argmax the same id; after a few drops
            # finish anyway.
            seq.eos_drops += 1
            if seq.eos_drops > 4:
                self._finish(seq, FinishReason.EOS)
            return
        seq.token_ids.append(token)
        if seq.hash_seq is not None:
            seq.hash_seq.append(token)
            self._emit_stored(seq)
        out = seq.pending
        if out is None:
            out = seq.pending = LLMEngineOutput()
            self._unflushed.append(seq)
        out.token_ids.append(token)
        if seq.want_logprobs and lp is not None:
            # every token of a dispatch comes with its log-prob or none
            # does, so an item's lists stay aligned position for position
            if out.log_probs is None:
                out.log_probs = []
            out.log_probs.append(lp)
            k = seq.num_top_lp
            if k and top_ids is not None and top_lps is not None:
                if out.top_logprobs is None:
                    out.top_logprobs = []
                out.top_logprobs.append(
                    [
                        [int(t), float(l)]
                        for t, l in zip(top_ids[:k], top_lps[:k])
                    ]
                )
        if (
            seq.num_generated >= seq.max_new
            or len(seq.token_ids) >= self.config.max_model_len
        ):
            self._finish(seq, FinishReason.LENGTH)
            return
        # the NEXT decode step writes KV at index pos-1; allocate its block
        # just-in-time if the sequence crossed a block boundary
        if (seq.pos - 1) // self.config.block_size >= len(seq.block_ids):
            try:
                seq.block_ids.extend(self.allocator.alloc(1))
            except OutOfBlocks:
                if self._preempt_victim(exclude=seq):
                    seq.block_ids.extend(self.allocator.alloc(1))
                elif any(
                    v is not seq and v.slot is not None
                    and not v.pending_remote
                    for v in self._admit_order
                ):
                    # every other lane outranks this one (class-aware
                    # victim choice refused them all): the lower-class
                    # sequence yields ITSELF — KV spills to the host tier
                    # and it resumes via onboard when pressure clears
                    if dprov.enabled():
                        dprov.record(
                            "engine", "preempt", seq.priority,
                            reason="self_yield",
                            ctx=seq.ctx,
                            proc=self.trace_proc,
                            grower=seq.ctx.id,
                            grower_class=seq.priority,
                        )
                    self._preempt_seq(seq)
                else:
                    logger.error("seq %d: out of KV blocks", seq.seq_id)
                    self._finish_error(
                        seq, "decode", "out of KV blocks with no "
                        "preemptable sequence", "out_of_kv_blocks",
                    )

    def _abort_all(self, cause: str, code: str = "injected_fault") -> None:
        """In-process crash injection (faults.abort_after_tokens) and the
        self-fence path: fail every live sequence with a structured error,
        freeing slots + KV blocks, exactly as the engine-loop crash path
        does — but keep serving new requests (the chaos soak asserts
        conservation) unless the caller also closed the engine."""
        for seq in list(self.waiting):
            self.waiting.remove(seq)
            self._sp_close_all(seq)
            self._send_final(
                seq,
                LLMEngineOutput.final_error(
                    seq.ctx.id, "queue", cause, code
                ),
            )
        for seq in list(self._admit_order):
            if seq.pending_remote:
                seq.ctx.kill()
                self._send_final(
                    seq,
                    LLMEngineOutput.final_error(
                        seq.ctx.id, "remote_prefill", cause, code
                    ),
                )
            else:
                self._finish_error(seq, "decode", cause, code)

    def fence(self, reason: str) -> None:
        """Worker self-fence (DistributedRuntime.on_fence): the primary
        lease is gone, so the cluster has already declared this worker
        dead and is migrating its streams. Take effect BETWEEN dispatches:
        stop admitting, fail every lane with a structured `worker_fenced`
        error (consumers replay onto a live worker), and never decode
        another token — a partitioned zombie must not double-serve
        alongside its replacement for the rest of the lease TTL."""
        if self._fenced:
            return
        self._fenced = True
        self._closed = True  # loop exits after the in-flight dispatch
        logger.error("engine fenced: %s — failing all lanes", reason)
        dtrace.event("worker_fenced", reason=reason)
        self._abort_all(f"worker fenced: {reason}", code="worker_fenced")
        self._wake.set()

    def _update_stats(self) -> None:
        self.stats.active_slots = sum(1 for s in self.slots if s is not None)
        self.stats.waiting = len(self.waiting)
        self.stats.used_blocks = (
            self.config.num_blocks - 1 - self.allocator.free_count
        )
        outcomes = getattr(self.peer_block_client, "pull_outcomes", None)
        if outcomes:
            self.stats.kv_pull_outcomes = dict(outcomes)
