"""OpenAI-compatible HTTP service over aiohttp.

Role-equivalent of lib/llm/src/http/service/service_v2.rs (HttpService,
State{ModelManager, Metrics}) + openai.rs handlers (:133 completions, :287
chat, :677 models) with SSE streaming, client-disconnect kill (:725-811),
per-model execution chains, /health and Prometheus /metrics.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import json
import math
import os
import re
import time
from typing import Any, AsyncIterator, Callable, Optional

from aiohttp import web

from dynamo_tpu.backend import Backend, DetokenizeOperator
from dynamo_tpu.http.metrics import ServiceMetrics, TokenTimer
from dynamo_tpu.pipeline.nodes import ServiceBackend, ServiceFrontend
from dynamo_tpu.model_card import ModelDeploymentCard
from dynamo_tpu.pipeline.annotated import Annotated
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.preprocessor import (
    ChatDeltaGenerator,
    CompletionDeltaGenerator,
    OpenAIPreprocessor,
)
from dynamo_tpu.protocols.aggregator import ChatDeltaAggregator, CompletionAggregator
from dynamo_tpu.protocols.common import FinishReason, LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.protocols.openai import (
    ChatCompletionChunk,
    ChatCompletionRequest,
    CompletionRequest,
    CompletionResponse,
    ModelInfo,
    ModelList,
    usage_dict,
)
from dynamo_tpu.protocols.sse import encode_done, encode_json_event
from dynamo_tpu import qos
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.telemetry import brownout as dbrownout
from dynamo_tpu.telemetry import profile as dprofile
from dynamo_tpu.telemetry import provenance as dprov
from dynamo_tpu.telemetry import slo as dslo
from dynamo_tpu.telemetry import trace as dtrace

logger = get_logger("dynamo_tpu.http")

# client-supplied x-request-id: sanitized to a safe charset and bounded so
# it can serve as the Context id, a log field, and a trace filename
_RID_BAD = re.compile(r"[^A-Za-z0-9._:-]")
_RID_MAX = 128


def client_request_id(request: web.Request) -> Optional[str]:
    rid = request.headers.get("x-request-id")
    if not rid:
        return None
    rid = _RID_BAD.sub("-", rid.strip())[:_RID_MAX]
    return rid or None

# engine_fn(PreprocessedRequest, Context) -> AsyncIterator[LLMEngineOutput]
EngineFn = Callable[[PreprocessedRequest, Context], AsyncIterator[LLMEngineOutput]]


class EngineStreamError(Exception):
    """A structured engine failure (LLMEngineOutput.error) surfacing
    through the per-model chain; the HTTP layer renders it as a typed SSE
    `event: error` (streaming) or a mapped status code (unary)."""

    def __init__(self, payload: dict) -> None:
        super().__init__(payload.get("cause") or "engine error")
        self.payload = payload


# machine-readable error code -> HTTP status for unary responses
_CODE_STATUS = {
    "deadline_exceeded": 504,
    "worker_unavailable": 503,
    "overloaded": 429,
    "brownout_shed": 429,
    "preempted_too_often": 503,
    "prompt_too_long": 400,
}


def _error_payload(message: Optional[str]) -> dict:
    """Decode a stream error message: structured JSON payloads (request_id,
    phase, cause, code) pass through; anything else wraps as internal."""
    if message:
        try:
            d = json.loads(message)
            if isinstance(d, dict) and ("code" in d or "cause" in d):
                return d
        except (ValueError, TypeError):
            pass
    return {"cause": message or "engine error", "code": "internal_error"}


def _parse_class_fractions(raw: Optional[str]) -> dict[str, float]:
    """DYN_ADMISSION_CLASS_FRACTIONS: `class=frac,...` — the fraction of
    the model watermark at which that class starts shedding. Defaults give
    bulk half the queue, standard 80%, interactive the full watermark."""
    out = {"bulk": 0.5, "standard": 0.8, "interactive": 1.0}
    for entry in (raw or "").split(","):
        entry = entry.strip()
        if not entry or "=" not in entry:
            continue
        cls, _, frac = entry.partition("=")
        cls = qos.normalize_priority(cls)
        if cls is None:
            continue
        try:
            out[cls] = max(0.0, min(1.0, float(frac)))
        except ValueError:
            continue
    return out


def _usage_timing_block(ctx: Context) -> dict:
    """The `usage.timing` payload for a finished request: the per-phase
    trace breakdown plus (behind DYN_DECISIONS_USAGE=1) the request's
    decision timeline."""
    tb: dict = {}
    if dtrace.enabled():
        tb = dtrace.breakdown(dtrace.ctx_trace_id(ctx)) or {}
    if dprov.enabled() and dprov.usage_enabled():
        tb["decisions"] = dprov.timeline(ctx.id)
    return tb


def _prefix_sig(text: str) -> Optional[int]:
    """Cheap request-prefix signature for admission heat: a hash of the
    leading characters — the system-prompt/template region most likely to
    be a fleet-shared prefix. Process-local (str hashing is salted); the
    heat it keys is learned from the router's radix match, so the sig only
    needs to be stable within this frontend."""
    if not text:
        return None
    return hash(text[:256])


def _chat_prefix_sig(chat_req) -> Optional[int]:
    try:
        m = chat_req.messages[0]
        c = m.content
        if not isinstance(c, str):
            c = json.dumps(c, sort_keys=True, default=str)
        return _prefix_sig(f"{m.role}:{c}")
    except Exception:  # noqa: BLE001 — heat is advisory
        return None


def _completion_prefix_sig(comp_req) -> Optional[int]:
    try:
        p = comp_req.prompt
        if isinstance(p, list):
            p = ",".join(str(t) for t in p[:64])
        return _prefix_sig(str(p))
    except Exception:  # noqa: BLE001 — heat is advisory
        return None


class AdmissionController:
    """Frontend admission control and load shedding (reference: Dynamo's
    serving fabric owns graceful backpressure; Llumnix-style bounded
    queues). Per-model inflight is bounded by a high watermark derived
    from the aggregated worker slot count (`load_metrics` via a capacity
    fn) times DYN_ADMISSION_QUEUE_FACTOR, optionally capped by the static
    DYN_ADMISSION_MAX_INFLIGHT.

    Class-aware (ISSUE 7): each priority class sheds at its own fraction
    of the watermark (bulk first at 50%, standard at 80%, interactive only
    at the hard cap — DYN_ADMISSION_CLASS_FRACTIONS), and the brownout
    ladder can force whole classes shed regardless of load. The 429
    Retry-After hint is derived from the measured completion (drain) rate
    — how long the backlog above this class's threshold actually takes to
    clear — falling back to the DYN_ADMISSION_RETRY_AFTER_S constant when
    there is no drain signal yet."""

    def __init__(
        self,
        metrics: Optional[ServiceMetrics] = None,
        max_inflight: Optional[int] = None,
        queue_factor: Optional[float] = None,
    ) -> None:
        env = os.environ
        self.metrics = metrics
        if max_inflight is None:
            max_inflight = int(env.get("DYN_ADMISSION_MAX_INFLIGHT", "0")) or None
        self.max_inflight = max_inflight
        self.queue_factor = (
            queue_factor
            if queue_factor is not None
            else float(env.get("DYN_ADMISSION_QUEUE_FACTOR", "2.0"))
        )
        self.retry_after_s = float(env.get("DYN_ADMISSION_RETRY_AFTER_S", "1"))
        self.class_fractions = _parse_class_fractions(
            env.get("DYN_ADMISSION_CLASS_FRACTIONS")
        )
        # classes force-shed by the brownout ladder (set by the service's
        # BrownoutController on_change hook)
        self.brownout_shed: frozenset[str] = frozenset()
        self.drain = qos.DrainRateEstimator()
        self._inflight: dict[str, int] = {}
        # model -> zero-arg fn returning the fleet's total request slots
        # (None = unknown); installed by the model watcher / static wiring
        self._capacity_fns: dict[str, Callable[[], Optional[int]]] = {}
        self.shed_total = 0
        self.shed_by_class: dict[str, int] = {}
        # fleet prefix heat (cache-aware admission): EWMA of the router's
        # fleet-matched fraction per (model, request-prefix signature). A
        # KNOWN-cold bulk prefix sheds at a reduced watermark — cold-
        # prefix bulk gives way before hot-prefix traffic when the queue
        # fills. First-seen prefixes are never penalized (no heat entry).
        self.heat_max = max(
            1, int(env.get("DYN_ADMISSION_HEAT_MAX", "4096") or 4096)
        )
        self.cold_prefix_fraction = float(
            env.get("DYN_COLD_PREFIX_FRACTION", "0.6")
        )
        self.cold_prefix_heat = float(env.get("DYN_COLD_PREFIX_HEAT", "0.25"))
        self._prefix_heat: collections.OrderedDict = collections.OrderedDict()

    def set_capacity_fn(
        self, model: str, fn: Callable[[], Optional[int]]
    ) -> None:
        self._capacity_fns[model] = fn

    def remove_capacity_fn(self, model: str) -> None:
        self._capacity_fns.pop(model, None)

    def watermark(self, model: str) -> Optional[int]:
        slots: Optional[int] = None
        fn = self._capacity_fns.get(model)
        if fn is not None:
            try:
                slots = fn()
            except Exception:  # noqa: BLE001 — stale capacity is tolerable
                slots = None
        if slots:
            wm = max(1, int(math.ceil(slots * self.queue_factor)))
            if self.max_inflight:
                wm = min(wm, self.max_inflight)
            return wm
        return self.max_inflight

    def class_watermark(self, model: str, priority: str) -> Optional[int]:
        """The inflight count at which `priority`-class requests shed."""
        wm = self.watermark(model)
        if wm is None:
            return None
        frac = self.class_fractions.get(priority, 1.0)
        return max(1, int(math.ceil(wm * frac)))

    def _shed_one(
        self, model: str, priority: str, reason: str, excess: int
    ) -> float:
        self.shed_total += 1
        self.shed_by_class[priority] = self.shed_by_class.get(priority, 0) + 1
        if self.metrics is not None:
            self.metrics.requests_shed.labels(model).inc()
            self.metrics.class_shed.labels(model, priority, reason).inc()
        return self.drain.retry_after_s(max(1, excess), self.retry_after_s)

    def note_prefix_heat(
        self, model: str, prefix_sig: Optional[int], frac: float
    ) -> None:
        """Learn the router's fleet-matched fraction for this request's
        prefix signature (EWMA, LRU-capped table)."""
        if prefix_sig is None:
            return
        key = (model, prefix_sig)
        prev = self._prefix_heat.pop(key, None)
        heat = (
            float(frac) if prev is None else 0.5 * prev + 0.5 * float(frac)
        )
        self._prefix_heat[key] = heat
        while len(self._prefix_heat) > self.heat_max:
            self._prefix_heat.popitem(last=False)

    def prefix_heat(self, model: str, prefix_sig: Optional[int]) -> Optional[float]:
        if prefix_sig is None:
            return None
        return self._prefix_heat.get((model, prefix_sig))

    def _record_admission(
        self,
        kind: str,
        model: str,
        priority: str,
        reason: str,
        request_id: Optional[str],
        **attrs: Any,
    ) -> None:
        """Provenance: the watermark math behind one admit/shed verdict."""
        dprov.record(
            "admission",
            kind,
            priority,
            reason=reason,
            request_id=request_id,
            epoch=None if request_id else model,
            model=model,
            inflight=self._inflight.get(model, 0),
            class_fraction=self.class_fractions.get(priority, 1.0),
            **attrs,
        )

    def try_acquire(
        self,
        model: str,
        priority: str = qos.DEFAULT_CLASS,
        prefix_sig: Optional[int] = None,
        request_id: Optional[str] = None,
    ) -> Optional[float]:
        """None = admitted (caller must release()); else shed — the value
        is the Retry-After hint in seconds (drain-rate derived)."""
        priority = qos.normalize_priority(priority) or qos.DEFAULT_CLASS
        prov = dprov.enabled()
        if priority in self.brownout_shed:
            if prov:
                self._record_admission(
                    "shed", model, priority, "brownout", request_id,
                )
            return self._shed_one(model, priority, "brownout", 1)
        wm = self.class_watermark(model, priority)
        cur = self._inflight.get(model, 0)
        heat = None
        if wm is not None and priority == "bulk":
            heat = self.prefix_heat(model, prefix_sig)
            if heat is not None and heat < self.cold_prefix_heat:
                # KNOWN-cold bulk prefix: shed earlier than the class
                # fraction — it reuses no fleet KV, so under pressure it
                # costs full prefill compute that hot-prefix traffic skips
                cold_wm = max(
                    1, int(math.ceil(wm * self.cold_prefix_fraction))
                )
                if cur >= cold_wm:
                    if prov:
                        self._record_admission(
                            "shed", model, priority, "cold_prefix",
                            request_id, watermark=cold_wm,
                            heat=round(heat, 4),
                        )
                    return self._shed_one(
                        model, priority, "cold_prefix", cur - cold_wm + 1
                    )
        if wm is not None and cur >= wm:
            if prov:
                self._record_admission(
                    "shed", model, priority, "watermark", request_id,
                    watermark=wm,
                )
            return self._shed_one(
                model, priority, "watermark", cur - wm + 1
            )
        if prov:
            self._record_admission(
                "admit", model, priority,
                "under_watermark" if wm is not None else "unbounded",
                request_id,
                watermark=wm,
                heat=round(heat, 4) if heat is not None else None,
            )
        self._inflight[model] = cur + 1
        return None

    def release(self, model: str) -> None:
        self._inflight[model] = max(0, self._inflight.get(model, 1) - 1)
        # completion = one queue slot drained: feeds the Retry-After hint
        self.drain.note()

    def inflight(self, model: Optional[str] = None) -> int:
        if model is not None:
            return self._inflight.get(model, 0)
        return sum(self._inflight.values())


class ModelExecution:
    """Per-model chain: preprocess -> engine -> detokenize -> OpenAI chunks."""

    def __init__(
        self,
        mdc: ModelDeploymentCard,
        engine_fn: EngineFn,
        embed_fn: Optional[Callable] = None,
        clear_fn: Optional[Callable] = None,
    ) -> None:
        self.mdc = mdc
        self.engine_fn = engine_fn  # read through a closure by the pipeline
        # backend, so swapping it (tests, reconnect) takes effect
        # async (token_ids) -> pooled embedding vector, when the engine
        # supports it (ref http/service/openai.rs:222 /v1/embeddings)
        self.embed_fn = embed_fn
        # async () -> list of per-worker result dicts; flushes worker KV
        # caches (ref http/service/clear_kv_blocks.rs:40)
        self.clear_fn = clear_fn
        self.preprocessor = OpenAIPreprocessor(mdc)
        self.backend = Backend(self.preprocessor.tokenizer)
        # the per-model token pipeline as a composable node graph
        # (pipeline/nodes.py; reference watcher.rs:201-236 builds the same
        # frontend -> backend-operator -> router-backend ring). Chat/
        # completion-specific chunking stays at this HTTP layer; the chain
        # below is the protocol-independent token path.
        self.pipeline = (
            ServiceFrontend(name=mdc.name)
            .link(DetokenizeOperator(self.backend))
            .link(
                ServiceBackend.from_engine(
                    lambda req, ctx: self.engine_fn(req, ctx)
                )
            )
        )

    @property
    def supports_images(self) -> bool:
        """True when the backing worker understands image content parts
        (set by MultimodalEngine deployments via the model card — the flag
        must ride discovery so remote frontends see it too)."""
        return bool(self.mdc.extra.get("supports_images"))

    @staticmethod
    def _fanout(pre: PreprocessedRequest) -> list[PreprocessedRequest]:
        """n>1: n independent engine requests, one per choice index. A
        seeded request derives seed+i per choice so choices differ but the
        whole response stays reproducible (ref openai.rs n handling)."""
        import dataclasses

        n = max(1, pre.sampling.n or 1)
        if n == 1:
            return [pre]
        out = []
        for i in range(n):
            s = dataclasses.replace(pre.sampling, n=1)
            if s.seed is not None:
                s = dataclasses.replace(s, seed=s.seed + i)
            out.append(dataclasses.replace(pre, sampling=s))
        return out

    async def _merged_choices(
        self,
        choices: list[PreprocessedRequest],
        ctx: Context,
        timer: Optional[TokenTimer],
        emit_chunk,
        emit_finish,
        counters: dict,
    ) -> AsyncIterator[Any]:
        """Run every choice's engine stream concurrently; yield OpenAI
        chunks in arrival order (choice index rides inside each chunk)."""
        queue: asyncio.Queue = asyncio.Queue()

        async def run_choice(i: int, pre_i: PreprocessedRequest) -> None:
            finish: Optional[FinishReason] = None
            # per-choice CHILD context: engines kill their ctx when their
            # generator is torn down (the consumer-went-away signal), and
            # the pipeline now acloses deterministically below — a child
            # confines that kill to this choice, so a finished choice
            # can't cancel its siblings or suppress the request-level
            # finish/usage chunks (parent kill still cascades down)
            agen = self.pipeline.generate(pre_i, ctx.child())
            try:
                async for step in agen:
                    counters["completion"] += step.tokens_emitted
                    if step.text or step.logprobs:
                        if timer:
                            with dtrace.phase("frontend.sse"):
                                timer.on_token(max(step.tokens_emitted, 1))
                        with dtrace.phase("frontend.detokenize"):
                            chunks = emit_chunk(step, i)  # text_chunk objects
                        for chunk in chunks:
                            queue.put_nowait(("chunk", chunk))
                    if step.finish_reason is not None:
                        if step.finish_reason is FinishReason.ERROR:
                            raise EngineStreamError(
                                step.error
                                or {"cause": "engine error",
                                    "code": "internal_error"}
                            )
                        finish = step.finish_reason
                        break
                if not ctx.is_killed():
                    for chunk in emit_finish(finish or FinishReason.STOP, i):
                        queue.put_nowait(("chunk", chunk))
            except Exception as e:  # noqa: BLE001 — surface as SSE error
                queue.put_nowait(("error", e))
            finally:
                # close the pipeline chain NOW, not at GC: async-generator
                # finalization is deferred to the loop's asyncgen hooks, so
                # an abandoned chain would keep the worker stream open and
                # lose every span still inside a `with` (their exits only
                # run on aclose)
                with contextlib.suppress(Exception):
                    await agen.aclose()
                queue.put_nowait(("done", i))

        loop = asyncio.get_running_loop()
        tasks = [
            loop.create_task(run_choice(i, p)) for i, p in enumerate(choices)
        ]
        done = 0
        try:
            while done < len(tasks):
                kind, payload = await queue.get()
                if kind == "done":
                    done += 1
                elif kind == "error":
                    raise payload
                else:
                    yield payload
        finally:
            for t in tasks:
                t.cancel()

    async def chat_stream(
        self, request: ChatCompletionRequest, ctx: Context, timer: Optional[TokenTimer] = None
    ) -> AsyncIterator[Annotated]:
        with dtrace.phase("frontend.preprocess"):
            pre, prompt = self.preprocessor.preprocess_chat(request)
        pre.extra["echo_text"] = prompt  # feeds echo_full test engines
        qos.stamp_priority(pre, ctx)  # QoS class onto every wire hop
        for ann in self.preprocessor.requested_annotations(pre, prompt):
            yield ann
        gen = ChatDeltaGenerator(request.model)
        choices = self._fanout(pre)
        for i in range(len(choices)):
            yield Annotated.from_data(
                gen.role_chunk(i).model_dump(exclude_none=True)
            )
        counters = {"completion": 0}
        # tool calling: when the request declares tools, buffer each
        # choice's text and parse at end-of-stream — a successful parse
        # becomes tool_calls deltas + finish_reason "tool_calls"; anything
        # else is released as ordinary text (ref preprocessor/tools.rs:371)
        buffer_tools = bool(request.tools)
        buffers: dict[int, list] = {}

        def emit_chat(step, i):
            if buffer_tools:
                slot = buffers.setdefault(i, [[], []])
                if step.text:
                    slot[0].append(step.text)
                if step.logprobs:
                    slot[1].extend(step.logprobs)
                return []
            return [gen.text_chunk(step.text, index=i, logprobs=step.logprobs)]

        def finish_chat(reason, i):
            if not buffer_tools:
                return [gen.finish_chunk(reason, index=i)]
            from dynamo_tpu.tool_calling import parse_tool_calls

            texts, lps = buffers.get(i, [[], []])
            text = "".join(texts)
            calls = parse_tool_calls(text) if text else None
            if calls:
                return [
                    gen.tool_calls_chunk(
                        [c.to_openai(j) for j, c in enumerate(calls)], index=i
                    ),
                    gen.finish_chunk(reason, index=i, literal="tool_calls"),
                ]
            out = []
            if text or lps:
                out.append(gen.text_chunk(text, index=i, logprobs=lps or None))
            out.append(gen.finish_chunk(reason, index=i))
            return out

        try:
            async for chunk in self._merged_choices(
                choices,
                ctx,
                timer,
                emit_chat,
                finish_chat,
                counters,
            ):
                with dtrace.phase("frontend.sse"):
                    frame = chunk.model_dump(exclude_none=True)
                yield Annotated.from_data(frame)
        except EngineStreamError as e:
            yield Annotated.from_error(json.dumps(e.payload))
            return
        except Exception as e:  # noqa: BLE001
            yield Annotated.from_error(f"engine error: {e}")
            return
        if ctx.is_killed():
            return
        if request.stream_options and request.stream_options.get("include_usage"):
            chunk = gen.usage_chunk(
                len(pre.token_ids), counters["completion"]
            ).model_dump(exclude_none=True)
            # final SSE chunk carries the per-request phase breakdown and
            # decision timeline (worker records arrived on the final frame)
            tb = _usage_timing_block(ctx)
            if tb and chunk.get("usage") is not None:
                chunk["usage"]["timing"] = tb
            yield Annotated.from_data(chunk)

    async def completion_stream(
        self, request: CompletionRequest, ctx: Context, timer: Optional[TokenTimer] = None
    ) -> AsyncIterator[Annotated]:
        with dtrace.phase("frontend.preprocess"):
            pre, prompt = self.preprocessor.preprocess_completion(request)
        pre.extra["echo_text"] = prompt
        qos.stamp_priority(pre, ctx)  # QoS class onto every wire hop
        gen = CompletionDeltaGenerator(request.model)
        choices = self._fanout(pre)
        if request.echo and prompt:
            for i in range(len(choices)):
                gen.note_echo(prompt, index=i)
                yield Annotated.from_data(
                    gen.text_chunk(prompt, index=i).model_dump(exclude_none=True)
                )
        counters = {"completion": 0}
        try:
            async for chunk in self._merged_choices(
                choices,
                ctx,
                timer,
                lambda step, i: [
                    gen.text_chunk(step.text, index=i, logprobs=step.logprobs)
                ],
                lambda reason, i: [gen.finish_chunk(reason, index=i)],
                counters,
            ):
                with dtrace.phase("frontend.sse"):
                    frame = chunk.model_dump(exclude_none=True)
                yield Annotated.from_data(frame)
        except EngineStreamError as e:
            yield Annotated.from_error(json.dumps(e.payload))
            return
        except Exception as e:  # noqa: BLE001
            yield Annotated.from_error(f"engine error: {e}")
            return
        if ctx.is_killed():
            return
        if request.stream_options and request.stream_options.get("include_usage"):
            chunk = gen.usage_chunk(
                len(pre.token_ids), counters["completion"]
            ).model_dump(exclude_none=True)
            tb = _usage_timing_block(ctx)
            if tb and chunk.get("usage") is not None:
                chunk["usage"]["timing"] = tb
            yield Annotated.from_data(chunk)


class ModelManager:
    """Registry of live models (reference discovery/model_manager.rs)."""

    def __init__(self) -> None:
        self._models: dict[str, dict[str, Any]] = {}

    def add_model(
        self, name: str, execution: ModelExecution, ref: str = "local"
    ) -> None:
        entry = self._models.get(name)
        if entry is None:
            self._models[name] = {"execution": execution, "refs": {ref}}
            logger.info("model added: %s", name)
        else:
            entry["refs"].add(ref)

    def remove_ref(self, name: str, ref: str) -> bool:
        """Drop one worker ref; removes the model when the last ref dies.
        Returns True if the model was fully removed."""
        entry = self._models.get(name)
        if entry is None:
            return False
        entry["refs"].discard(ref)
        if not entry["refs"]:
            del self._models[name]
            logger.info("model removed: %s", name)
            return True
        return False

    def get(self, name: str) -> Optional[ModelExecution]:
        entry = self._models.get(name)
        return entry["execution"] if entry else None

    def list_models(self) -> list[str]:
        return sorted(self._models.keys())


class HttpService:
    def __init__(
        self,
        manager: Optional[ModelManager] = None,
        host: str = "0.0.0.0",
        port: int = 8080,
        metrics: Optional[ServiceMetrics] = None,
        template: Optional[Any] = None,  # request_template.RequestTemplate
        admission: Optional[AdmissionController] = None,
    ) -> None:
        self.manager = manager or ModelManager()
        self.host = host
        self.port = port
        self.metrics = metrics or ServiceMetrics()
        # integrity/fence counters (process-wide): a frontend's share is
        # chiefly dispatch-plane fenced rejects from zombie workers
        from dynamo_tpu.integrity import COUNTERS as _icounters

        self.metrics.attach_integrity(_icounters)
        self.template = template
        self.admission = admission or AdmissionController(self.metrics)
        self._draining = False
        self.app = web.Application(client_max_size=64 * 1024 * 1024)
        self.app.add_routes(
            [
                web.post("/v1/chat/completions", self._chat),
                web.post("/v1/completions", self._completions),
                web.post("/v1/embeddings", self._embeddings),
                web.post("/v1/responses", self._responses),
                web.post("/clear_kv_blocks", self._clear_kv_blocks),
                web.get("/v1/models", self._models),
                web.get("/health", self._health),
                web.get("/live", self._health),
                web.get("/metrics", self._metrics),
                web.get("/debug/slo", self._debug_slo),
                web.get("/debug/goodput", self._debug_goodput),
                web.get("/debug/traces", self._debug_traces_list),
                web.get("/debug/traces/{request_id}", self._debug_trace),
                web.get("/debug/decisions/{request_id}", self._debug_decisions),
                web.get("/debug/fleet", self._debug_fleet),
                web.get("/debug/profile", self._debug_profile),
            ]
        )
        self._runner: Optional[web.AppRunner] = None
        # SLO plane (telemetry/slo.py): one engine per model, fed from
        # this frontend's own phase observations. State transitions
        # publish a `slo-status` fabric event via slo_publisher (wired by
        # run_http; None = log only).
        self._slo_engines: dict[str, dslo.SloEngine] = {}
        self._slo_task: Optional[asyncio.Task] = None
        self._slo_tick_s = float(os.environ.get("DYN_SLO_TICK_S", "1.0"))
        self.slo_publisher: Optional[Callable[[dict], None]] = None
        # Brownout ladder (telemetry/brownout.py): fed by this frontend's
        # own SLO evaluation AND remote `slo-status` events (wired by
        # run_http via note_remote_slo). Rungs 1/4 force-shed whole classes
        # at this AdmissionController; transitions publish on the
        # `brownout-status` subject via brownout_publisher.
        self.brownout = dbrownout.BrownoutController(
            scope="frontend", on_change=self._on_brownout_change
        )
        self.brownout_publisher: Optional[Callable[[dict], None]] = None
        self._local_slo_state = "ok"
        self._remote_slo_state = "ok"
        self.metrics.attach_brownout(self.brownout)
        # auxiliary background tasks (event subscriptions etc.) cancelled
        # on close; registered by the entrypoint wiring
        self._aux_tasks: list[asyncio.Task] = []
        # pluggable fleet-state feeds for the merged /debug/fleet snapshot:
        # label -> zero-arg fn returning a JSON-able blob (the entrypoint
        # wiring registers health / planner-status / upgrade-status reads)
        self.fleet_sources: dict[str, Callable[[], Any]] = {}

    # ---------------------------------------------------------- lifecycle

    async def start(self) -> None:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        if self.port == 0:
            self.port = site._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]
        if dslo.SloConfig.from_env().enabled and self._slo_task is None:
            self._slo_task = asyncio.get_running_loop().create_task(
                self._slo_loop()
            )
        logger.info("openai http service on %s:%d", self.host, self.port)

    async def close(self) -> None:
        if self._slo_task is not None:
            self._slo_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._slo_task
            self._slo_task = None
        for t in self._aux_tasks:
            t.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await t
        self._aux_tasks.clear()
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    def add_background_task(self, task: asyncio.Task) -> None:
        """Track an auxiliary task (event subscription loop) for close()."""
        self._aux_tasks.append(task)

    def begin_drain(self) -> None:
        """Stop admitting: every new request is answered 503 + Retry-After.
        In-flight requests keep streaming until done (or drain timeout)."""
        self._draining = True

    async def drain(self, timeout_s: float = 10.0) -> None:
        """Graceful drain for SIGTERM: stop admission, wait (bounded) for
        in-flight requests to finish, then close the server."""
        self.begin_drain()
        deadline = time.monotonic() + timeout_s
        while self.admission.inflight() > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        left = self.admission.inflight()
        if left:
            logger.warning(
                "drain timeout (%.1fs): %d request(s) still in flight",
                timeout_s, left,
            )
        await self.close()

    # ----------------------------------------------------------- helpers

    @staticmethod
    def _error(status: int, message: str, typ: str = "invalid_request_error"):
        return web.json_response(
            {"error": {"message": message, "type": typ}}, status=status
        )

    def _structured_error(
        self, model: str, message: Optional[str], ctx: Optional[Context] = None
    ):
        """Unary rendering of a structured engine error: the payload's
        machine-readable code picks the HTTP status."""
        payload = _error_payload(message)
        code = payload.get("code", "internal_error")
        if ctx is not None:
            payload.setdefault("request_id", ctx.id)
            # DYN_TRACE=auto retention: an errored request keeps its trace
            ctx.metadata["error_code"] = code
        if code == "deadline_exceeded":
            self.metrics.deadline_exceeded.labels(model).inc()
        status = _CODE_STATUS.get(code, 500)
        resp = web.json_response(
            {"error": {"message": payload.get("cause") or "engine error",
                       "type": code, **{k: v for k, v in payload.items()
                                        if k in ("request_id", "phase")}}},
            status=status,
            headers=self._resp_headers(ctx) if ctx is not None else None,
        )
        if status == 429:
            resp.headers["Retry-After"] = "1"
        return resp

    # ---------------------------------------------------------- telemetry

    def _request_ctx(self, request: web.Request) -> Context:
        """Context honoring a client-supplied x-request-id (sanitized and
        bounded) so client logs, our logs, and traces share one id."""
        rid = client_request_id(request)
        return Context(id=rid) if rid else Context()

    def _trace_root(self, request: web.Request, ctx: Context, endpoint: str):
        """Open the request's trace root, honoring an inbound W3C
        `traceparent` (minting a fresh trace id otherwise)."""
        if not dtrace.enabled():
            return dtrace.NULL_CM
        tid = sid = None
        tp = request.headers.get("traceparent")
        if tp:
            tid, sid = dtrace.parse_traceparent(tp)
        return dtrace.root_span(
            "http_request", ctx, trace_id=tid, parent_id=sid,
            proc="frontend", endpoint=endpoint, request_id=ctx.id,
        )

    def _resp_headers(self, ctx: Context) -> dict[str, str]:
        h = {"x-request-id": ctx.id}
        tid = dtrace.ctx_trace_id(ctx)
        if tid:
            h["x-dyn-trace-id"] = tid
        return h

    @staticmethod
    def _resolve_priority_recorded(
        request: web.Request, api_req: Any, model: str, ctx: Context
    ) -> str:
        """Resolve the QoS class at the edge and record which precedence
        rung won (header > ext > env default) in the decision ledger."""
        header = request.headers.get("x-dyn-priority")
        ext = getattr(api_req, "ext", None)
        ext_value = getattr(ext, "priority", None) if ext else None
        prio = qos.resolve_priority(header, ext_value, model)
        if dprov.enabled():
            dprov.record(
                "qos",
                "priority",
                prio,
                reason=qos.priority_source(header, ext_value),
                request_id=ctx.id,
                model=model,
            )
        return prio

    @staticmethod
    def _attach_timing(d: dict, ctx: Context) -> None:
        """Per-request timing breakdown onto a unary response's usage."""
        tb = _usage_timing_block(ctx)
        if tb:
            usage = d.get("usage") or {}
            usage["timing"] = tb
            d["usage"] = usage

    # ------------------------------------------------------------- slo

    def _slo_engine(self, model: str) -> dslo.SloEngine:
        eng = self._slo_engines.get(model)
        if eng is None:
            def on_transition(old: str, new: str, status: dict) -> None:
                logger.warning(
                    "SLO state for %s: %s -> %s", model, old, new
                )
                payload = {"old": old, "new": new, **status}
                if self.slo_publisher is not None:
                    self.slo_publisher(payload)

            eng = dslo.SloEngine(
                dslo.SloConfig.from_env(model),
                model=model,
                on_transition=on_transition,
            )
            self._slo_engines[model] = eng
        return eng

    def _slo_observe_all(self) -> dict[str, dict]:
        out = {}
        for model in self.manager.list_models():
            eng = self._slo_engine(model)
            out[model] = eng.observe(self.metrics.phase_hist_for(model))
        worst = "ok"
        for status in out.values():
            s = status.get("state", "ok")
            if dslo._SEVERITY.get(s, 0) > dslo._SEVERITY.get(worst, 0):
                worst = s
        self._local_slo_state = worst
        return out

    async def _slo_loop(self) -> None:
        while True:
            try:
                self._slo_observe_all()
                self._observe_brownout()
            except Exception:  # noqa: BLE001 — telemetry must not crash us
                logger.exception("slo evaluation failed")
            await asyncio.sleep(self._slo_tick_s)

    # -------------------------------------------------------------- brownout

    def note_remote_slo(self, state: Optional[str]) -> None:
        """Feed a fleet `slo-status` transition (metrics component / other
        frontends) into the brownout ladder. Events fire on transitions
        only, so the last remote state stays authoritative until the next
        event flips it back."""
        if state in dslo._SEVERITY:
            self._remote_slo_state = state
            self._observe_brownout()

    def _observe_brownout(self) -> None:
        """Reduce local + remote SLO states to the WORST and step the
        ladder (the controller's dwell timers assume one coherent feed)."""
        local, remote = self._local_slo_state, self._remote_slo_state
        worst = (
            local
            if dslo._SEVERITY.get(local, 0) >= dslo._SEVERITY.get(remote, 0)
            else remote
        )
        self.brownout.observe(worst)

    def _on_brownout_change(self, old: int, new: int, rung: str) -> None:
        self.admission.brownout_shed = dbrownout.shed_classes_for(new)
        if self.brownout_publisher is not None:
            self.brownout_publisher(
                {
                    "scope": "frontend",
                    "old_level": old,
                    "level": new,
                    "rung": rung,
                    **self.brownout.actions(),
                }
            )

    @staticmethod
    def _trace_migrated(trace_id: Optional[str]) -> bool:
        """Did any span of this trace record a migration event? (In auto
        mode spans exist for every request, so this is reliable.)"""
        if not trace_id:
            return False
        for s in dtrace.spans_for_trace(trace_id):
            for ev in s.events:
                if ev.get("name") == "migration":
                    return True
        return False

    def _finish_trace(
        self,
        ctx: Context,
        model: str = "",
        timer: Optional[TokenTimer] = None,
    ) -> None:
        """Request-completion trace hook. DYN_TRACE=1: write the trace
        when DYN_TRACE_DIR is set (pre-existing behavior). DYN_TRACE=auto:
        flight-recorder retention — keep the trace only when the request
        breached its SLO, errored / was deadline-killed, migrated across a
        worker death, or hit the 1-in-N sample (DYN_TRACE_SAMPLE)."""
        self._finish_decisions(ctx, model=model, timer=timer)
        if not dtrace.enabled():
            return
        tid = dtrace.ctx_trace_id(ctx)
        if not tid:
            return
        if not dtrace.auto():
            dtrace.maybe_write_trace(tid, ctx.id)
            return
        reason = dslo.retention_reason(
            dslo.SloConfig.from_env(model) if model else None,
            error_code=ctx.metadata.get("error_code"),
            ttft_ms=getattr(timer, "ttft_ms", None),
            max_itl_ms=getattr(timer, "max_itl_ms", None),
            migrated=self._trace_migrated(tid),
        )
        rec = dslo.recorder()
        if reason is not None:
            rec.retain(tid, ctx.id, reason)
        else:
            rec.note_dropped()

    def _finish_decisions(
        self,
        ctx: Context,
        model: str = "",
        timer: Optional[TokenTimer] = None,
    ) -> None:
        """DYN_DECISIONS=auto retention: keep a completed request's
        decision records only under the flight-recorder rules (same
        `dslo.retention_reason` verdict the trace plane uses)."""
        if not (dprov.enabled() and dprov.auto()):
            return
        migrated = any(
            r.actor == "remote" and r.kind == "migrate"
            for r in dprov.records_for_request(ctx.id)
        )
        reason = dslo.retention_reason(
            dslo.SloConfig.from_env(model) if model else None,
            error_code=ctx.metadata.get("error_code"),
            ttft_ms=getattr(timer, "ttft_ms", None),
            max_itl_ms=getattr(timer, "max_itl_ms", None),
            migrated=migrated,
        )
        dprov.maybe_retain(ctx.id, reason)

    def _shed(self, model: str, retry_after_s: float) -> web.Response:
        resp = self._error(
            429,
            "server overloaded: admission watermark reached, retry later",
            "overloaded",
        )
        resp.headers["Retry-After"] = str(max(1, int(math.ceil(retry_after_s))))
        return resp

    def _draining_resp(self) -> web.Response:
        resp = self._error(503, "server is draining", "unavailable")
        resp.headers["Retry-After"] = "2"
        return resp

    @staticmethod
    def _arm_deadline(ctx: Context, request: Any) -> None:
        """Arm the request/TTFT budgets from the ext block, falling back
        to DYN_DEFAULT_DEADLINE_MS for the overall deadline."""
        ext = getattr(request, "ext", None)
        timeout_ms = getattr(ext, "timeout_ms", None) if ext else None
        ttft_ms = getattr(ext, "ttft_timeout_ms", None) if ext else None
        if timeout_ms is None:
            default = os.environ.get("DYN_DEFAULT_DEADLINE_MS")
            if default:
                timeout_ms = float(default)
        ctx.set_deadline_ms(timeout_ms, ttft_ms)

    async def _stream_sse(
        self,
        request: web.Request,
        ctx: Context,
        annotated_stream: AsyncIterator[Annotated],
        model: str = "",
    ) -> web.StreamResponse:
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                **self._resp_headers(ctx),
            },
        )
        with dtrace.phase("frontend.sse"):
            await resp.prepare(request)  # status line and headers
        try:
            async for item in annotated_stream:
                if item.is_error():
                    # typed SSE error event: structured payloads (request
                    # id, phase, cause, code) ride through verbatim
                    err = _error_payload(item.error_message())
                    err.setdefault("request_id", ctx.id)
                    # DYN_TRACE=auto retention: errored streams keep traces
                    ctx.metadata["error_code"] = err.get(
                        "code", "internal_error"
                    )
                    if err.get("code") == "deadline_exceeded" and model:
                        self.metrics.deadline_exceeded.labels(model).inc()
                    payload = {
                        "error": {
                            "message": err.get("cause")
                            or err.get("message")
                            or "engine error",
                            "type": err.get("code", "internal_error"),
                            **{
                                k: v
                                for k, v in err.items()
                                if k in ("request_id", "phase")
                            },
                        }
                    }
                    await resp.write(
                        encode_json_event(payload, event="error").encode()
                    )
                    break
                if item.event is not None:
                    await resp.write(
                        encode_json_event(
                            item.annotation_value(), event=item.event
                        ).encode()
                    )
                elif item.data is not None:
                    # the frame's bytes and the socket write; the write
                    # awaits only when the transport pushes back
                    with dtrace.phase("frontend.sse"):
                        await resp.write(
                            encode_json_event(item.data).encode()
                        )
            await resp.write(encode_done().encode())
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: kill generation (reference openai.rs:725-811)
            ctx.kill()
            raise
        return resp

    # ---------------------------------------------------------- handlers

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        if self._draining:
            return self._draining_resp()
        try:
            body = await request.json()
            with dtrace.phase("frontend.parse"):
                if self.template is not None:
                    body = self.template.apply_chat(body)
                chat_req = ChatCompletionRequest.model_validate(body)
        except Exception as e:  # noqa: BLE001
            return self._error(400, f"invalid request: {e}")
        execution = self.manager.get(chat_req.model)
        if execution is None:
            return self._error(404, f"model {chat_req.model!r} not found", "not_found_error")
        has_images = any(
            isinstance(m.content, list)
            and any(
                p.get("type") in ("image_url", "video_url")
                for p in m.content
            )
            for m in chat_req.messages
        )
        if has_images and not execution.supports_images:
            # fail loudly instead of silently answering text-only (the
            # preprocessor strips image parts for the template either way)
            return self._error(
                501, "this model does not accept image input",
                "not_implemented",
            )
        ctx = self._request_ctx(request)
        prio = self._resolve_priority_recorded(
            request, chat_req, chat_req.model, ctx
        )
        sig = _chat_prefix_sig(chat_req)
        retry_after = self.admission.try_acquire(
            chat_req.model, prio, prefix_sig=sig, request_id=ctx.id
        )
        if retry_after is not None:
            return self._shed(chat_req.model, retry_after)
        ctx.decisions().priority = prio
        try:
            self._arm_deadline(ctx, chat_req)
            timer = TokenTimer(self.metrics, chat_req.model)
            with self.metrics.track(chat_req.model, "chat_completions"), \
                    self._trace_root(request, ctx, "chat_completions") as root:
                root.set(
                    model=chat_req.model, stream=bool(chat_req.stream),
                    priority=prio,
                )
                self.metrics.prompt_tokens.labels(chat_req.model)  # touch label
                stream = execution.chat_stream(chat_req, ctx, timer)
                if chat_req.stream:
                    return await self._stream_sse(
                        request, ctx, stream, model=chat_req.model
                    )
                agg = ChatDeltaAggregator()
                async for item in stream:
                    if item.is_error():
                        return self._structured_error(
                            chat_req.model, item.error_message(), ctx
                        )
                    if item.data is not None:
                        agg.add(ChatCompletionChunk.model_validate(item.data))
                d = agg.finish().model_dump(exclude_none=True)
                self._attach_timing(d, ctx)
                return web.json_response(d, headers=self._resp_headers(ctx))
        finally:
            frac = ctx.decisions().kv_fleet_frac
            if frac is not None:
                self.admission.note_prefix_heat(chat_req.model, sig, frac)
            self.admission.release(chat_req.model)
            self._finish_trace(ctx, model=chat_req.model, timer=timer)

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        if self._draining:
            return self._draining_resp()
        try:
            body = await request.json()
            with dtrace.phase("frontend.parse"):
                if self.template is not None:
                    body = self.template.apply_completion(body)
                comp_req = CompletionRequest.model_validate(body)
        except Exception as e:  # noqa: BLE001
            return self._error(400, f"invalid request: {e}")
        execution = self.manager.get(comp_req.model)
        if execution is None:
            return self._error(404, f"model {comp_req.model!r} not found", "not_found_error")
        ctx = self._request_ctx(request)
        prio = self._resolve_priority_recorded(
            request, comp_req, comp_req.model, ctx
        )
        sig = _completion_prefix_sig(comp_req)
        retry_after = self.admission.try_acquire(
            comp_req.model, prio, prefix_sig=sig, request_id=ctx.id
        )
        if retry_after is not None:
            return self._shed(comp_req.model, retry_after)
        ctx.decisions().priority = prio
        try:
            self._arm_deadline(ctx, comp_req)
            timer = TokenTimer(self.metrics, comp_req.model)
            with self.metrics.track(comp_req.model, "completions"), \
                    self._trace_root(request, ctx, "completions") as root:
                root.set(model=comp_req.model, stream=bool(comp_req.stream))
                stream = execution.completion_stream(comp_req, ctx, timer)
                if comp_req.stream:
                    return await self._stream_sse(
                        request, ctx, stream, model=comp_req.model
                    )
                agg = CompletionAggregator()
                async for item in stream:
                    if item.is_error():
                        return self._structured_error(
                            comp_req.model, item.error_message(), ctx
                        )
                    if item.data is not None:
                        agg.add(CompletionResponse.model_validate(item.data))
                d = agg.finish().model_dump(exclude_none=True)
                self._attach_timing(d, ctx)
                return web.json_response(d, headers=self._resp_headers(ctx))
        finally:
            frac = ctx.decisions().kv_fleet_frac
            if frac is not None:
                self.admission.note_prefix_heat(comp_req.model, sig, frac)
            self.admission.release(comp_req.model)
            self._finish_trace(ctx, model=comp_req.model, timer=timer)

    async def _embeddings(self, request: web.Request) -> web.Response:
        from dynamo_tpu.protocols.openai import EmbeddingRequest

        try:
            body = await request.json()
            emb_req = EmbeddingRequest.model_validate(body)
        except Exception as e:  # noqa: BLE001
            return self._error(400, f"invalid request: {e}")
        execution = self.manager.get(emb_req.model)
        if execution is None:
            return self._error(
                404, f"model {emb_req.model!r} not found", "not_found_error"
            )
        if execution.embed_fn is None:
            return self._error(
                501, "this model does not serve embeddings", "not_implemented"
            )
        inputs = emb_req.input
        if isinstance(inputs, str):
            inputs = [inputs]
        elif inputs and isinstance(inputs[0], int):
            inputs = [inputs]
        tokenizer = execution.preprocessor.tokenizer
        data = []
        prompt_tokens = 0
        with self.metrics.track(emb_req.model, "embeddings"):
            for i, item in enumerate(inputs):
                token_ids = (
                    list(item)
                    if isinstance(item, list)
                    else tokenizer.encode(str(item)).ids
                )
                prompt_tokens += len(token_ids)
                vec = await execution.embed_fn(token_ids)
                data.append(
                    {
                        "object": "embedding",
                        "index": i,
                        "embedding": [float(x) for x in vec],
                    }
                )
        return web.json_response(
            {
                "object": "list",
                "data": data,
                "model": emb_req.model,
                "usage": {
                    "prompt_tokens": prompt_tokens,
                    "total_tokens": prompt_tokens,
                },
            }
        )

    async def _responses(self, request: web.Request) -> web.Response:
        """OpenAI Responses API, unary (ref http/service/openai.rs:443 —
        the reference also serves it unary-only). A responses body is
        converted to a chat request (responses.rs:152-191 TryFrom), run
        through the chat chain, and the aggregate is reshaped into a
        Response object (responses.rs:198-253)."""
        import uuid

        if self._draining:
            return self._draining_resp()
        try:
            body = await request.json()
        except Exception as e:  # noqa: BLE001
            return self._error(400, f"invalid request: {e}")
        if not isinstance(body, dict):
            return self._error(400, "request body must be a JSON object")
        if self.template is not None:
            body = self.template.apply_responses(body)
        inp = body.get("input")
        if not isinstance(inp, str):
            # ref validate_response_input_is_text_only: items input is 501
            return self._error(
                501, "only text input is supported", "not_implemented"
            )
        for field in ("tools", "tool_choice", "previous_response_id"):
            if body.get(field):
                return self._error(
                    501, f"`{field}` is not supported", "not_implemented"
                )
        chat_body = {
            "model": body.get("model", ""),
            "messages": [{"role": "user", "content": inp}],
            "stream": False,
        }
        for src, dst in (
            ("temperature", "temperature"),
            ("top_p", "top_p"),
            ("max_output_tokens", "max_completion_tokens"),
        ):
            if body.get(src) is not None:
                chat_body[dst] = body[src]
        if body.get("top_logprobs") is not None:
            chat_body["logprobs"] = True
            chat_body["top_logprobs"] = min(int(body["top_logprobs"]), 20)
        try:
            chat_req = ChatCompletionRequest.model_validate(chat_body)
        except Exception as e:  # noqa: BLE001
            return self._error(400, f"invalid request: {e}")
        execution = self.manager.get(chat_req.model)
        if execution is None:
            return self._error(
                404, f"model {chat_req.model!r} not found", "not_found_error"
            )
        ctx = self._request_ctx(request)
        prio = self._resolve_priority_recorded(
            request, chat_req, chat_req.model, ctx
        )
        sig = _chat_prefix_sig(chat_req)
        retry_after = self.admission.try_acquire(
            chat_req.model, prio, prefix_sig=sig, request_id=ctx.id
        )
        if retry_after is not None:
            return self._shed(chat_req.model, retry_after)
        ctx.decisions().priority = prio
        try:
            self._arm_deadline(ctx, chat_req)
            timer = TokenTimer(self.metrics, chat_req.model)
            with self.metrics.track(chat_req.model, "responses"), \
                    self._trace_root(request, ctx, "responses"):
                agg = ChatDeltaAggregator()
                async for item in execution.chat_stream(chat_req, ctx, timer):
                    if item.is_error():
                        return self._structured_error(
                            chat_req.model, item.error_message(), ctx
                        )
                    if item.data is not None:
                        agg.add(ChatCompletionChunk.model_validate(item.data))
                chat_resp = agg.finish()
        finally:
            frac = ctx.decisions().kv_fleet_frac
            if frac is not None:
                self.admission.note_prefix_heat(chat_req.model, sig, frac)
            self.admission.release(chat_req.model)
            self._finish_trace(ctx, model=chat_req.model, timer=timer)
        content = ""
        if chat_resp.choices:
            content = chat_resp.choices[0].message.content or ""
        return web.json_response(
            headers=self._resp_headers(ctx),
            data={
                "id": f"resp_{uuid.uuid4().hex}",
                "object": "response",
                "created_at": int(time.time()),
                "model": chat_req.model,
                "status": "completed",
                "output": [
                    {
                        "type": "message",
                        "id": f"msg_{uuid.uuid4().hex}",
                        "role": "assistant",
                        "status": "completed",
                        "content": [
                            {
                                "type": "output_text",
                                "text": content,
                                "annotations": [],
                            }
                        ],
                    }
                ],
            }
        )

    async def _clear_kv_blocks(self, request: web.Request) -> web.Response:
        """Admin route: flush every worker's reusable KV cache state (ref
        http/service/clear_kv_blocks.rs:40-110 — per-worker-group results
        under cleared/failed lists)."""
        models = self.manager.list_models()
        if not models:
            return web.json_response(
                {"message": "No active worker groups found"}
            )
        cleared, failed = [], []
        for name in models:
            execution = self.manager.get(name)
            if execution is None or execution.clear_fn is None:
                failed.append(
                    {
                        "name": name,
                        "status": "worker group doesn't support "
                        "clear_kv_blocks",
                    }
                )
                continue
            try:
                results = await execution.clear_fn()
                cleared.append(
                    {"name": name, "status": "cleared", "workers": results}
                )
            except Exception as e:  # noqa: BLE001
                failed.append(
                    {"name": name, "status": "error", "error": str(e)}
                )
        return web.json_response(
            {"cleared_worker_groups": cleared, "failed_worker_groups": failed}
        )

    async def _debug_slo(self, request: web.Request) -> web.Response:
        """Frontend SLO status: per-model burn rates, window percentiles,
        and the ok/burning/breached state (evaluated on demand from this
        frontend's own phase observations)."""
        cfg = dslo.SloConfig.from_env()
        if not cfg.enabled:
            return web.json_response(
                {
                    "enabled": False,
                    "hint": "set DYN_SLO_TTFT_MS / DYN_SLO_ITL_MS "
                    "or DYN_SLO_CONFIG",
                    # brownout can still step off remote slo-status events
                    "brownout": self.brownout.status(),
                }
            )
        return web.json_response(
            {
                "enabled": True,
                "scope": "frontend",
                "models": self._slo_observe_all(),
                "brownout": self.brownout.status(),
            }
        )

    async def _debug_goodput(self, request: web.Request) -> web.Response:
        """Colocated-engine goodput ledger (ISSUE 14): per-label step
        distributions, occupancy, phase bubbles, the token-waste taxonomy
        (with the frontend hedger's hedge_loser overlay), and recompile
        forensics. The fleet-merged view lives on the metrics component's
        /debug/goodput."""
        from dynamo_tpu.telemetry import goodput as dgoodput

        read = getattr(self.metrics, "_goodput_read", None)
        hedger = getattr(self.metrics, "_goodput_hedger", None)
        gp = read() if read is not None else None
        summary = gp.summary() if gp is not None else None
        hedge_tokens = (
            int(hedger.wasted_tokens) if hedger is not None else 0
        )
        if summary is not None and hedge_tokens:
            summary["tokens_wasted"]["hedge_loser"] += hedge_tokens
            summary["tokens_wasted_total"] += hedge_tokens
        if summary is not None:
            # this process's phase table (telemetry/trace.py::phase): the
            # engine loop's and the frontend's host time by name, and the
            # request phases queue_wait and prefill_wait
            summary["phases"] = dtrace.phase_summary()
        body: dict[str, Any] = {
            "scope": "frontend",
            "enabled": dgoodput.enabled_from_env(),
            "goodput": summary,
            "hedge_loser_tokens": hedge_tokens,
        }
        if summary is None:
            body["hint"] = (
                "no colocated engine ledger on this frontend; the "
                "fleet-merged view is GET /debug/goodput on the metrics "
                "component"
            )
        return web.json_response(body)

    async def _debug_traces_list(self, request: web.Request) -> web.Response:
        """List retained trace exemplars (DYN_TRACE=auto flight recorder)
        with their breach reasons, newest last."""
        if not dtrace.enabled():
            return self._error(
                404, "tracing is disabled (set DYN_TRACE=1 or auto)",
                "not_found_error",
            )
        rec = dslo.recorder()
        return web.json_response(
            {
                "mode": "auto" if dtrace.auto() else "always",
                "stats": rec.stats(),
                "traces": rec.entries(),
            }
        )

    @staticmethod
    async def _wait_assembled(probe: Callable[[], Any]) -> Any:
        """Wait-bounded assembly (DYN_TRACE_ASSEMBLE_MS, default 250 ms):
        spans/records that arrive only via the `trace-export` fallback
        race the ModelWatcher's async ingest — re-poll `probe` until it
        yields something or the budget lapses, instead of 404ing a
        request whose evidence is milliseconds away."""
        try:
            budget_ms = float(
                os.environ.get("DYN_TRACE_ASSEMBLE_MS", "250") or 250
            )
        except ValueError:
            budget_ms = 250.0
        deadline = time.monotonic() + max(0.0, budget_ms) / 1e3
        out = probe()
        while not out and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
            out = probe()
        return out

    async def _debug_trace(self, request: web.Request) -> web.Response:
        """Serve one request's assembled cross-process trace as Chrome
        trace-event JSON (load in Perfetto / chrome://tracing). Accepts
        the request id (x-request-id / Context id) or a raw trace id."""
        if not dtrace.enabled():
            return self._error(
                404, "tracing is disabled (set DYN_TRACE=1)", "not_found_error"
            )
        rid = request.match_info["request_id"]
        spans = await self._wait_assembled(
            lambda: dtrace.spans_for_trace(dtrace.trace_for_request(rid) or rid)
        )
        tid = dtrace.trace_for_request(rid) or rid
        if not spans:
            if dtrace.trace_for_request(rid) is None:
                return self._error(
                    404, f"no trace for request {rid!r}", "not_found_error"
                )
            # the request is known (root was opened here) but its spans
            # haven't landed within the assembly budget: partial, not 404
            return web.json_response(
                {
                    "traceEvents": [],
                    "displayTimeUnit": "ms",
                    "otherData": {
                        "trace_id": tid,
                        "request_id": rid,
                        "partial": True,
                    },
                }
            )
        doc = dtrace.chrome_trace(tid)
        doc["otherData"]["request_id"] = rid
        doc["otherData"]["breakdown"] = dtrace.breakdown(tid)
        doc["otherData"]["partial"] = False
        return web.json_response(doc)

    async def _debug_decisions(self, request: web.Request) -> web.Response:
        """One request's cross-process decision timeline: every control-
        plane choice (admission, QoS, routing, preemption, hedging,
        migration, pulls) in causal order, assembled from local records
        plus the worker records that rode the final frame / trace-export
        fallback. Same wait-bounded path as /debug/traces."""
        if not dprov.enabled():
            return self._error(
                404,
                "decision ledger is disabled (set DYN_DECISIONS=1)",
                "not_found_error",
            )
        rid = request.match_info["request_id"]
        recs = await self._wait_assembled(
            lambda: dprov.records_for_request(rid)
        )
        if not recs:
            if dtrace.trace_for_request(rid) is None:
                return self._error(
                    404, f"no decisions for request {rid!r}", "not_found_error"
                )
            return web.json_response(
                {"request_id": rid, "partial": True, "decisions": []}
            )
        return web.json_response(
            {
                "request_id": rid,
                "partial": False,
                "count": len(recs),
                "procs": sorted({r.proc for r in recs}),
                "decisions": dprov.timeline(rid),
            }
        )

    async def _debug_fleet(self, request: web.Request) -> web.Response:
        """One-stop fleet snapshot: models, admission state + prefix heat,
        brownout rung, degraded/fence counters, recent fleet-scoped
        decisions, and whatever fleet feeds the wiring registered
        (health scores, planner intent/freeze, upgrade phase) — the
        merged view that used to take five debug endpoints."""
        from dynamo_tpu.integrity import COUNTERS as _icounters

        adm = self.admission
        models = self.manager.list_models()
        heat = list(adm._prefix_heat.values())
        body: dict[str, Any] = {
            "models": models,
            "admission": {
                "inflight": {m: adm.inflight(m) for m in models},
                "watermarks": {m: adm.watermark(m) for m in models},
                "class_fractions": adm.class_fractions,
                "shed_total": adm.shed_total,
                "shed_by_class": dict(adm.shed_by_class),
                "brownout_shed": sorted(adm.brownout_shed),
                "prefix_heat": {
                    "entries": len(heat),
                    "mean": round(sum(heat) / len(heat), 4) if heat else None,
                    "cold_threshold": adm.cold_prefix_heat,
                },
            },
            "brownout": self.brownout.status(),
            "slo": {
                "local": self._local_slo_state,
                "remote": self._remote_slo_state,
            },
            "integrity": _icounters.snapshot(),
            "decisions": {
                "enabled": dprov.enabled(),
                "counts": {
                    f"{a}/{k}": n for (a, k), n in sorted(
                        dprov.counts().items()
                    )
                },
                "ring_dropped": dprov.dropped_total(),
                "fleet_recent": dprov.fleet_summary(limit=16),
            },
        }
        for label, fn in self.fleet_sources.items():
            try:
                body[label] = fn()
            except Exception as e:  # noqa: BLE001 — one stale feed must
                # not take down the whole snapshot
                body[label] = {"error": str(e)}
        return web.json_response(body)

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """Open an on-demand device profile window:
        GET /debug/profile?seconds=N[&dir=PATH]. The window auto-closes;
        artifacts land under DYN_PROFILE_DIR (TensorBoard/Perfetto)."""
        try:
            seconds = float(request.query.get("seconds", "5"))
        except ValueError:
            return self._error(400, "seconds must be a number")
        info = dprofile.start(seconds, request.query.get("dir") or None)
        status = 200
        if "error" in info:
            status = 409 if "already" in info["error"] else 501
        return web.json_response(info, status=status)

    async def _models(self, request: web.Request) -> web.Response:
        listing = ModelList(
            data=[ModelInfo(id=name) for name in self.manager.list_models()]
        )
        return web.json_response(listing.model_dump())

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"status": "healthy", "models": self.manager.list_models()}
        )

    async def _metrics(self, request: web.Request) -> web.Response:
        return web.Response(
            body=self.metrics.render(), content_type="text/plain"
        )
