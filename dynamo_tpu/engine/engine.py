"""TpuEngine: the AsyncEngine facade over the continuous-batching scheduler.

This is what a dynamo-tpu worker serves (the role vLLM's ``AsyncLLM`` plays
for the reference's vllm adapter, components/backends/vllm handlers.py).

Request wire shape (PreprocessedRequest, ref: protocols/common):
``{"token_ids": [...], "sampling_options": {...}, "stop_conditions": {...}}``
Response frames (LLMEngineOutput): ``{"token_ids": [t], "finish_reason": ...,
"index": 0}`` — detokenization happens upstream in the Backend operator,
never in the engine.

Single-task ownership: only the engine's step-loop task mutates the
scheduler; ``generate``/``abort`` stage work through event-loop-local lists,
and the blocking device step runs via ``asyncio.to_thread`` so serving IO
never stalls.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Callable, List, Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.compile_cache import BUILD_LOG, enable_compile_cache, in_one_chunk
from dynamo_tpu.engine.config import ModelConfig, get_config
from dynamo_tpu.engine.kv_cache import KvEvent
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import (
    ForwardPassMetrics,
    Scheduler,
    SchedulerConfig,
    Sequence,
    StepOutput,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.tracing import StepLog, get_tracer

logger = get_logger(__name__)


@dataclass
class EngineArgs:
    model: str = "tiny"
    model_config: Optional[ModelConfig] = None
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    dtype: str = "bfloat16"
    seed: int = 0
    eos_token_ids: List[int] = field(default_factory=list)
    checkpoint_path: Optional[str] = None
    # KVBM tiers (0 / None = disabled): host-DRAM and disk offload pools.
    kvbm_host_blocks: int = 0
    kvbm_disk_dir: Optional[str] = None
    kvbm_disk_blocks: int = 0
    # Sharded serving: a ParallelConfig (engine/sharding.py) with total > 1
    # builds a device mesh and shards params + KV cache over it.
    parallel: Optional[Any] = None
    # Speculative decoding: a draft model preset/config proposing spec_gamma
    # tokens per round (greedy batches only; ref SpecDecodeStats surface).
    draft_model: Optional[str] = None
    draft_checkpoint_path: Optional[str] = None
    spec_gamma: int = 4
    # KV cache storage dtype override ("auto" | "int8") — config.py.
    kv_cache_dtype: str = "auto"
    # Weight storage dtype override ("auto" | "int8") — config.py weight_dtype.
    weight_dtype: str = "auto"
    # Precompile serving-hot executables for contexts up to this many tokens
    # before taking traffic (scheduler.warmup; 0 = skip). Without it, every
    # new (batch bucket × table width) shape compiles mid-request — measured
    # as the dominant serving-plane latency on fresh processes.
    warmup_ctx: int = 0
    # Guided decoding (structured outputs) needs the SERVED tokenizer to
    # lift grammars to token-level FSMs (llm/guided). Attached before
    # warmup so the masked-sampling executables precompile; without it,
    # guided requests are rejected engine-side.
    tokenizer: Optional[Any] = None
    # Incident autopsy plane (runtime/incidents.py): anomaly-triggered
    # black-box bundles land here (None falls back to DYN_INCIDENT_DIR;
    # unset = detect + count but never write). The detector itself is
    # always armed — it is host-side work on the stats-scrape cadence.
    incident_dir: Optional[str] = None
    incident_keep: int = 16
    # Attach a short jax.profiler device capture to each bundle (TPU
    # diagnosis: was the spike device time or host time?).
    profile_on_incident: bool = False
    # Continuous device-truth sampler (runtime/profiling.ContinuousProfiler):
    # short programmatic profiler windows at a bounded duty cycle, parsed
    # into measured MFU / per-kernel top-N siblings of the modeled gauges.
    # On by default — its defaults are a <1% duty cycle and the first
    # window only opens a full interval after startup.
    continuous_profiling: bool = True
    profile_window_s: float = 0.25
    profile_interval_s: float = 30.0
    # Artifact root for ALL capture paths (falls back to DYN_PROFILE_DIR).
    profile_dir: Optional[str] = None


def _log_built(b: dict, warmup_ctx: int) -> None:
    """The start-up line: what ``debug_state()["build"]`` holds in full."""
    logger.info(
        "engine.build %.1f s (params %.1f, scheduler %.1f, warm-up at ctx %d %.1f): %d keys warmed, "
        "%d executables (%d in one stack chunk, %d eager, %d from the cache, %d of their programs from the store and %d written to it)"
        " = trace %.1f + lower %.1f + backend %.1f + other %.1f s",
        b["engine_build"]["span_s"], *(b["phase_s"].get(p, 0.0) for p in ("build.params", "build.scheduler")), warmup_ctx,
        b["phase_s"].get("build.warmup", 0.0), b["keys"], b["executables"], b["in_one_chunk"], b["eager"], b["cache_hits"], b["store_hits"], b["store_misses"],
        *(b["engine_build"][p] for p in ("trace_s", "lower_s", "backend_s", "other_s")),
    )


class TpuEngine:
    def __init__(
        self,
        scheduler: Scheduler,
        *,
        kv_event_sink: Optional[Callable[[KvEvent], None]] = None,
    ):
        self.scheduler = scheduler
        self._staged_adds: List[tuple] = []
        self._staged_aborts: List[str] = []
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._closed = False
        self._kv_event_sink = kv_event_sink
        # Stall watchdog: work queued but no step completing for
        # stall_after_s marks the engine stalled (counter + log + unhealthy
        # /health). Evaluated lazily on every stats scrape / health probe —
        # no background task, deterministic under a monkeypatched clock.
        from dynamo_tpu.runtime.telemetry import StallWatchdog

        self.watchdog = StallWatchdog(
            probe=lambda: (scheduler.has_work(), scheduler.flight.last_step_ts),
            stall_after_s=scheduler.sc.stall_after_s,
        )
        # Incident autopsy plane: the anomaly detector rides every stats
        # scrape (same lazy cadence as the watchdog) and, when a signal
        # fires, the recorder snapshots a self-contained black-box bundle.
        # build() replaces this default (capture-disabled) plane with one
        # pointed at EngineArgs.incident_dir.
        from dynamo_tpu.runtime.incidents import IncidentConfig, IncidentPlane

        self.incidents = IncidentPlane(
            IncidentConfig(),
            state_probe=self.debug_state,
            flight_probe=scheduler.flight.ring_snapshot,
            config_probe=scheduler.config_snapshot,
        )
        # Tenant ledger snapshot rides every incident bundle (autopsy --tenant
        # reads it); process-global like the router's decision ring — a
        # rebuilt engine replaces its predecessor's probe.
        from dynamo_tpu.runtime.incidents import register_evidence_probe

        register_evidence_probe("tenant_ledger", scheduler.ledger.snapshot)
        # Device-truth profiling plane: ONE DeviceProfiler per engine — the
        # serialization point every capture path (health server POST,
        # incident captures, continuous sampler) must share — and the
        # optional background sampler build() arms. Engines constructed
        # directly (tests) get the profiler but no sampler thread.
        from dynamo_tpu.runtime.profiling import DeviceProfiler

        self.profiler = DeviceProfiler()
        self.continuous_profiler = None

    # --- construction -------------------------------------------------------
    @classmethod
    def build(
        cls,
        args: EngineArgs,
        *,
        params=None,
        draft_params=None,
        kv_event_sink: Optional[Callable[[KvEvent], None]] = None,
    ) -> "TpuEngine":
        """The engine, warmed. The whole of it is one ``engine.build`` span on
        the engine's step log, with children ``build.params`` (weights: loading
        or making them, quantizing them), ``build.scheduler`` (the pool, the
        slots, the ``jax.jit`` objects) and ``build.warmup``
        (``Scheduler.warmup``); every executable JAX builds inside is an entry
        of the build log (engine/compile_cache.py) under its phase and key, and
        says whether its program came from the program store
        (engine/program_store.py: where the persistent cache is on, a key's
        lowered program is read from disk, not traced and lowered again).
        All of it runs below ``in_one_chunk``'s frame, this one call: what is
        traced and lowered here pays no system call at a stack chunk's edge,
        whatever stands above this function or inside it (PERF.md §6, PR 42)."""
        return in_one_chunk(cls._build, args, params, draft_params, kv_event_sink)

    @classmethod
    def _build(cls, args: EngineArgs, params, draft_params, kv_event_sink) -> "TpuEngine":
        enable_compile_cache()
        log = StepLog()
        with BUILD_LOG.scope(log, "engine.build"):
            mc = args.model_config or get_config(args.model)
            if args.kv_cache_dtype != "auto":
                mc = mc.replace(kv_cache_dtype=args.kv_cache_dtype)
            if args.weight_dtype != "auto":
                mc = mc.replace(weight_dtype=args.weight_dtype)
            dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
            with BUILD_LOG.scope(log, "build.params"):
                if params is None:
                    if args.checkpoint_path:
                        from dynamo_tpu.engine.weights import load_checkpoint

                        params = load_checkpoint(args.checkpoint_path, mc, dtype=dtype)
                    else:
                        from dynamo_tpu.engine.models import get_module

                        logger.warning("no checkpoint: initializing random weights for %s", mc.name)
                        params = get_module(mc).init_params(mc, jax.random.PRNGKey(args.seed), dtype=dtype)
                if mc.weight_dtype == "int8":
                    from dynamo_tpu.engine.quant import params_quantized, quantize_params

                    if not params_quantized(params):
                        params = quantize_params(params)
                        logger.info("int8 weight-only quantization applied (layer matmul weights)")
            mesh = None
            if args.parallel is not None and args.parallel.total > 1:
                from dynamo_tpu.engine.sharding import build_mesh

                mesh = build_mesh(args.parallel)
            with BUILD_LOG.scope(log, "build.scheduler"):
                scheduler = Scheduler(
                    mc,
                    params,
                    args.scheduler,
                    dtype=dtype,
                    eos_token_ids=args.eos_token_ids,
                    on_kv_event=lambda ev: engine._on_kv_event(ev),
                    rng_seed=args.seed,
                    mesh=mesh,
                    parallel=args.parallel,
                    step_log=log,
                )
                engine = cls(scheduler, kv_event_sink=kv_event_sink)
            if args.draft_model:
                from dynamo_tpu.engine.models import get_module

                dc = get_config(args.draft_model)
                if draft_params is None:
                    if args.draft_checkpoint_path:
                        from dynamo_tpu.engine.weights import load_checkpoint

                        draft_params = load_checkpoint(args.draft_checkpoint_path, dc, dtype=dtype)
                    else:
                        logger.warning("no draft checkpoint: random weights for %s", dc.name)
                        draft_params = get_module(dc).init_params(
                            dc, jax.random.PRNGKey(args.seed + 1), dtype=dtype
                        )
                engine.scheduler.attach_draft(dc, draft_params, gamma=args.spec_gamma)
            if args.tokenizer is not None:
                engine.scheduler.attach_guided(args.tokenizer)
            if args.warmup_ctx > 0:
                with BUILD_LOG.scope(log, "build.warmup", ctx=args.warmup_ctx):
                    engine.scheduler.warmup(args.warmup_ctx)
            # From here on, compiles are mid-traffic: the flight recorder counts
            # them (and alerts when a warmup pass was supposed to cover them).
            engine.scheduler.flight.mark_warmup_done(warmed=args.warmup_ctx > 0)
            # Incident capture: point the plane at the bundle directory (CLI /
            # env); the detector is armed either way — counters flow to the
            # scrape even when no bundles are written.
            import os as _os

            from dynamo_tpu.runtime.incidents import INCIDENT_DIR_ENV, IncidentConfig, IncidentPlane

            incident_dir = args.incident_dir or _os.environ.get(INCIDENT_DIR_ENV) or None
            # One shared DeviceProfiler for every capture path — incident
            # captures, the health server's POST /debug/profile, and the
            # continuous sampler all serialize through its capture lock.
            if args.profile_dir:
                engine.profiler.out_dir = args.profile_dir
            elif args.profile_on_incident and incident_dir:
                engine.profiler.out_dir = _os.path.join(incident_dir, "profiles")
            engine.incidents = IncidentPlane(
                IncidentConfig(
                    dir=incident_dir,
                    keep=args.incident_keep,
                    profile_on_incident=args.profile_on_incident,
                ),
                state_probe=engine.debug_state,
                flight_probe=engine.scheduler.flight.ring_snapshot,
                config_probe=engine.scheduler.config_snapshot,
                profiler=engine.profiler,
            )
            if args.continuous_profiling:
                from dynamo_tpu.runtime.profiling import (
                    ContinuousProfileConfig,
                    ContinuousProfiler,
                )

                flight = engine.scheduler.flight
                engine.continuous_profiler = ContinuousProfiler(
                    engine.profiler,
                    ContinuousProfileConfig(
                        window_s=args.profile_window_s,
                        interval_s=args.profile_interval_s,
                    ),
                    cost_probe=flight.roofline_totals,
                    sink=flight.record_measured_window,
                )
                engine.continuous_profiler.start()
            if args.kvbm_host_blocks > 0:
                from dynamo_tpu.llm.block_manager import KvBlockManager

                engine.kvbm = KvBlockManager(
                    engine.scheduler.cache,
                    engine.scheduler.allocator,
                    host_blocks=args.kvbm_host_blocks,
                    disk_dir=args.kvbm_disk_dir,
                    disk_blocks=args.kvbm_disk_blocks,
                )
                engine.scheduler.attach_kvbm(engine.kvbm)
        flight = engine.scheduler.flight
        flight.since_ns = log.last("engine.build")[1]
        _log_built(flight.builds.summary(flight.since_ns), args.warmup_ctx)
        return engine

    def _on_kv_event(self, ev: KvEvent) -> None:
        if self._kv_event_sink is not None:
            self._kv_event_sink(ev)

    # --- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if self._loop_task is None:
            self._loop_task = asyncio.get_running_loop().create_task(self._loop(), name="engine-step-loop")

    async def stop(self) -> None:
        self._closed = True
        self._wake.set()
        if self.continuous_profiler is not None:
            await asyncio.to_thread(self.continuous_profiler.stop)
        if self._loop_task is not None:
            await self._loop_task
            self._loop_task = None
        kvbm = getattr(self.scheduler, "kvbm", None)
        if kvbm is not None:
            # Queued offload snapshots must reach the host/disk tiers —
            # a persistent G3 dir is supposed to survive restarts.
            await asyncio.to_thread(kvbm.flush_pending)

    async def _loop(self) -> None:
        try:
            while not self._closed:
                n = self.scheduler.expire_exports()
                if n:
                    logger.warning("reclaimed %d unpulled KV exports past TTL", n)
                if not (self._staged_adds or self._staged_aborts or self.scheduler.has_work()):
                    self._wake.clear()
                    # Wake periodically while exports await pulling so the
                    # TTL guard runs even when the engine is otherwise idle.
                    if self.scheduler._pending_exports:
                        try:
                            await asyncio.wait_for(self._wake.wait(), timeout=1.0)
                        except asyncio.TimeoutError:
                            pass
                    else:
                        await self._wake.wait()
                    continue
                # One iteration with work: ``engine.loop`` is the parent of the
                # staging, the scheduler's step (on the step thread) and the
                # delivery; all carry the step's sequence number.
                log = self.scheduler.flight.log
                step = log.step + 1
                with log.span("engine.loop", step=step):
                    if self._staged_adds or self._staged_aborts:
                        with log.span("engine.stage", step=step, adds=len(self._staged_adds),
                                      aborts=len(self._staged_aborts)):
                            self._stage()
                    outputs = await asyncio.to_thread(self.scheduler.step)
                    with log.span("engine.deliver", step=step, outputs=len(outputs)):
                        for seq, out in outputs:
                            seq.out_queue.put_nowait(out)
        except Exception:
            logger.exception("engine step loop crashed")
            # Engine death: fail all in-flight requests so streams end and the
            # migration operator can replay them elsewhere (ref: engine
            # monitor EngineDeadError flow, vllm handlers.py:88-92).
            for seq in list(self.scheduler.by_id.values()):
                seq.out_queue.put_nowait(StepOutput(token_id=-1, finished=True, finish_reason="error:engine_dead"))
            raise

    def _stage(self) -> None:
        """Hand the requests and aborts that arrived while the last step ran
        to the scheduler (only this task mutates it)."""
        for rid, tokens, sampling, stop, queue, extras in self._staged_adds:
            try:
                seq = self.scheduler.add_request(rid, tokens, sampling, stop, **extras)
                seq.out_queue = queue
            except ValueError as e:
                queue.put_nowait(StepOutput(token_id=-1, finished=True, finish_reason=f"error:{e}"))
        self._staged_adds.clear()
        for rid in self._staged_aborts:
            self.scheduler.abort(rid)
        self._staged_aborts.clear()

    # --- AsyncEngine --------------------------------------------------------
    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        enqueued_ts = time.monotonic()  # the staged wait (until add_request) starts here
        self.start()
        rid = context.id
        sampling_d = request.get("sampling_options") or {}
        temp = sampling_d.get("temperature")
        seed = sampling_d.get("seed")
        tlp = int(sampling_d.get("top_logprobs") or 0)
        sampling = SamplingParams(
            temperature=1.0 if temp is None else float(temp),  # null ≡ unset ≡ default
            top_k=int(sampling_d.get("top_k") or 0),
            top_p=float(sampling_d.get("top_p") or 1.0),
            seed=int(seed) if seed is not None else None,
            logprobs=bool(sampling_d.get("logprobs")) or tlp > 0,
            top_logprobs=tlp,
            frequency_penalty=float(sampling_d.get("frequency_penalty") or 0.0),
            presence_penalty=float(sampling_d.get("presence_penalty") or 0.0),
        )
        logit_bias = sampling_d.get("logit_bias")
        if logit_bias:
            from dynamo_tpu.logits_processing import LogitBiasProcessor

            # Applied pre-sampling via the per-request processor chain (the
            # host path — logit_bias rows skip the batched fast paths).
            sampling.logits_processors = [LogitBiasProcessor(logit_bias)]
        stop = StopConditions.from_dict(request.get("stop_conditions"))
        disagg = request.get("disagg_params") or {}
        # keep_blocks: prefill role (decode worker will pull the KV);
        # _prefilled: decode role (KV already pulled, injected locally).
        extras = {
            "keep_blocks_on_finish": bool(disagg.get("do_remote_decode")),
            "prefilled": request.get("_prefilled"),
            # Capacity-ledger attribution (runtime/ledger.py): resolved by
            # the frontend, billed by the scheduler.
            "tenant": request.get("tenant") or "anon",
            "enqueued_ts": enqueued_ts,
        }
        guided = request.get("guided_decoding")
        if guided is not None:
            # Grammar-constrained decoding (llm/guided): the scheduler
            # compiles/caches the token FSM and masks sampling device-side.
            extras["guided"] = guided
        mm = request.get("multimodal")
        if mm is not None:
            from dynamo_tpu.llm.multimodal import features_from_wire

            extras["mm_features"] = (
                mm if hasattr(mm, "shape") else features_from_wire(mm)
            )
        # Request tracing: hand the scheduler the (trace_id, parent_span)
        # pair only for traces that should record — head-sampled (the
        # deterministic decision matches the frontend's, so one request is
        # one trace) or, in tail mode, every trace: unsampled records stay
        # in the in-memory ring for SLO-violation promotion and incident
        # bundles instead of exporting.
        tracer = get_tracer()
        tp = context.traceparent
        if tracer.enabled and tp is not None and tracer.record_allowed(tp.trace_id):
            extras["trace"] = (tp.trace_id, tp.parent_id)
        queue: "asyncio.Queue[StepOutput]" = asyncio.Queue()
        self._staged_adds.append((rid, list(request["token_ids"]), sampling, stop, queue, extras))
        self._wake.set()

        finished = False
        stop_task = asyncio.create_task(context.stopped())
        try:
            while True:
                # Fast path: drain whatever the last scheduler dispatch
                # already queued — a multi-step window lands up to
                # num_scheduler_steps tokens at once, and pushing them as
                # ONE frame collapses the per-token asyncio/detok/SSE hops
                # that dominated the serving plane (measured: the plane,
                # not the device, capped HTTP throughput at ~6 req/s).
                outs = []
                try:
                    while True:
                        outs.append(queue.get_nowait())
                        if outs[-1].finished:
                            break
                except asyncio.QueueEmpty:
                    pass
                if not outs:
                    if context.is_stopped():
                        self.abort(rid)
                        out = await queue.get()
                        while not out.finished:
                            out = await queue.get()
                        finished = True
                        return
                    get_task = asyncio.create_task(queue.get())
                    done, _ = await asyncio.wait(
                        {get_task, stop_task}, return_when=asyncio.FIRST_COMPLETED
                    )
                    if stop_task in done and get_task not in done:
                        get_task.cancel()
                        self.abort(rid)
                        out = await queue.get()
                        while not out.finished:
                            out = await queue.get()
                        finished = True
                        return
                    outs.append(get_task.result())

                frame = {"token_ids": [], "finish_reason": None, "index": 0}
                logprobs = []
                top_logprobs = []
                for out in outs:
                    if out.finish_reason and out.finish_reason.startswith("error:"):
                        if frame["token_ids"]:
                            if logprobs:
                                frame["logprobs"] = logprobs
                            if top_logprobs:
                                frame["top_logprobs"] = top_logprobs
                            yield frame  # tokens decoded before the error
                        finished = True
                        raise RuntimeError(out.finish_reason[6:])
                    if out.token_id >= 0:
                        frame["token_ids"].append(out.token_id)
                    if out.logprob is not None:
                        logprobs.append(out.logprob)
                    if out.top_logprobs is not None:
                        # Per emitted token: [[alt_token_id, logprob], ...] —
                        # parallel to frame["logprobs"] (top_logprobs implies
                        # logprobs, so the lists stay index-aligned).
                        top_logprobs.append([[t, lp] for t, lp in out.top_logprobs])
                    if out.queue_s is not None and "queue_s" not in frame:
                        frame["queue_s"] = out.queue_s
                    if out.cached_tokens is not None and "cached_tokens" not in frame:
                        # Prefix-cache reuse (first frame): prompt tokens
                        # served from resident KV — flows to OpenAI
                        # usage.prompt_tokens_details and router accounting.
                        frame["cached_tokens"] = out.cached_tokens
                    if out.finished:
                        frame["finish_reason"] = out.finish_reason
                if logprobs:
                    frame["logprobs"] = logprobs
                if top_logprobs:
                    frame["top_logprobs"] = top_logprobs
                yield frame
                if frame["finish_reason"]:
                    finished = True
                    return
        finally:
            stop_task.cancel()
            # Abandoned stream (GeneratorExit / disconnect without kill):
            # stop decoding a request nobody is reading.
            if not finished:
                self.abort(rid)

    def abort(self, request_id: str) -> None:
        self._staged_aborts.append(request_id)
        self._wake.set()

    # --- disaggregation -----------------------------------------------------
    async def take_export(self, request_id: str):
        """Pull a finished prefill-role request's KV blocks (device→host) and
        release them. Returns (blocks, hashes, prompt_len) or None."""
        return await asyncio.to_thread(self.scheduler.take_export, request_id)

    async def take_export_device(self, request_id: str):
        """Device-native export: stacked device arrays, no host round-trip.
        Returns ((k_stack, v_stack), hashes, prompt_len) or None."""
        return await asyncio.to_thread(self.scheduler.take_export_device, request_id)

    # --- elastic capacity dial ---------------------------------------------
    def set_capacity_dial(self, prefill_fraction: float) -> dict:
        """Re-split this worker's budget between prefill and decode, live.

        Thread-safe (scheduler takes _aux_lock); reachable remotely via the
        ``set_dial`` control op on the worker's control subject.
        """
        return self.scheduler.set_capacity_dial(prefill_fraction)

    # --- introspection ------------------------------------------------------
    def metrics(self) -> ForwardPassMetrics:
        return self.scheduler.metrics()

    def stats_handler(self) -> dict:
        m = self.scheduler.metrics()
        stats = {
            "kv_usage": m.kv_usage,
            "kv_total_blocks": m.kv_total_blocks,
            "kv_active_blocks": m.kv_active_blocks,
            "num_running": m.num_running,
            "num_waiting": m.num_waiting,
            "preemptions_total": self.scheduler.preempt_total,
            # Failure-lifecycle counters: deadline evictions (finish_reason
            # "timeout" + KV freed) — the chaos suite's recovery signal.
            "request_timeouts_total": self.scheduler.timeouts_total,
            # Mixed-step composition (scrape-visible so the planner and
            # dashboards can see how much prefill rides the decode wave —
            # runtime/metrics.py documents the derived counters).
            "mixed_steps_total": m.mixed_steps_total,
            "mixed_prefill_tokens_total": m.mixed_prefill_tokens_total,
            "mixed_decode_tokens_total": m.mixed_decode_tokens_total,
            # Automatic prefix caching: skipped prompt tokens + the block
            # hit/miss/evict/onboard account (Grafana "Prefix cache" rows).
            "cached_tokens_total": m.cached_tokens_total,
            "prefix_hit_blocks_total": m.prefix_hit_blocks_total,
            "prefix_miss_blocks_total": m.prefix_miss_blocks_total,
            "prefix_evicted_blocks_total": m.prefix_evicted_blocks_total,
            "prefix_onboard_total": m.prefix_onboard_total,
            # First-token latency decomposition: queue (arrival→admission)
            # and prefill (admission→first token) sums — with the flight
            # recorder's step histograms these give the bench http sweep
            # its queue/prefill/decode breakdown.
            "queue_wait_seconds_total": round(self.scheduler.queue_wait_s_total, 6),
            "prefill_wait_seconds_total": round(self.scheduler.prefill_wait_s_total, 6),
            "first_tokens_total": self.scheduler.first_tokens_total,
            # Elastic capacity dial: the live prefill:decode budget split
            # (set_capacity_dial) so the planner's ratio actuator and the
            # Grafana "Elastic" row can see each worker's current shape.
            "elastic_prefill_fraction": m.elastic_prefill_fraction,
            "elastic_prefill_budget": m.elastic_prefill_budget,
            "elastic_decode_slots": m.elastic_decode_slots,
            "elastic_dial_changes_total": m.elastic_dial_changes_total,
        }
        # Flight recorder: per-phase step/token counters + the XLA compile
        # tracker (compiles_after_warmup_total > 0 in steady state is the
        # alert that shapes are compiling mid-traffic — PR 1's silent killer)
        # + the measured device-truth siblings once profile windows landed.
        stats.update(self.scheduler.flight.to_stats())
        # Continuous device-truth sampler: window/skip/error counters and
        # the live duty-cycle gauge (pure dict assembly — no device work).
        if self.continuous_profiler is not None:
            stats.update(self.continuous_profiler.to_stats())
        # KV-pool utilization gauges (free/cached depth, fragmentation,
        # prefix hit rate) + the SLO/goodput account + stall-watchdog state.
        stats.update(self.scheduler.kv_gauges())
        stats.update(self.scheduler.slo.to_stats())
        stats.update(self.watchdog.to_stats())
        # Mergeable latency digests (ttft/tpot/itl/queue_wait + per-phase
        # step durations): the aggregator merges these across workers into
        # true fleet-wide quantiles — averaging per-worker p99s does not.
        stats["digests"] = self.scheduler.telemetry.to_wire()
        # Tenant capacity ledger: flat billed totals on the worker plane +
        # the nested sketch wire the aggregator merges into fleet-true
        # per-tenant top-K families (runtime/ledger.py).
        stats.update(self.scheduler.ledger.to_stats())
        stats["tenant_ledger"] = self.scheduler.ledger.to_wire()
        # Guided decoding: request + grammar-compile counters (scrape-
        # visible so dashboards can watch structured-output traffic).
        if self.scheduler.guided is not None:
            stats.update(self.scheduler.guided.stats())
        # Chaos plane: injected-fault counters when an injector is armed
        # (runtime/faults.py; {} otherwise — the keys only exist on
        # chaos-armed workers).
        from dynamo_tpu.runtime import faults as _faults

        stats.update(_faults.stats())
        # Incident autopsy plane: the detector checks THIS snapshot (the
        # scrape is the poll cadence, exactly like the watchdog above) and
        # may write a black-box bundle; its counters ride the same scrape.
        self.incidents.observe(stats)
        stats.update(self.incidents.to_stats())
        return stats

    def debug_state(self) -> dict:
        """Live engine introspection for the health server's /debug/state."""
        state = self.scheduler.debug_state()
        state["watchdog"] = self.watchdog.to_stats()
        state["watchdog"]["stall_after_s"] = self.watchdog.stall_after_s
        state["incidents"] = self.incidents.debug_info()
        return state

    def attach_guided_tokenizer(self, tokenizer) -> None:
        """Enable guided decoding post-build (pipeline assembly attaches the
        serving tokenizer here when EngineArgs.tokenizer wasn't set)."""
        self.scheduler.attach_guided(tokenizer)
