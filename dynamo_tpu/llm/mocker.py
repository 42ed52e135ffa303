"""Mocker engine: full engine emulation with no TPU.

Ref: lib/llm/src/mocker/* (3,226 LoC) — ``MockVllmEngine`` (engine.rs:48)
simulates a batched scheduler with prefill/decode timing, KV block
allocation with prefix caching, watermark-driven preemption, and KV events,
all compressed by ``speedup_ratio``; the reference's distributed test suite
runs whole router/frontend topologies against fleets of these (SURVEY.md §4
— the single highest-leverage test asset).

This mocker mirrors the real engine's architecture (scheduler.py) rather
than simulating per-request in isolation:

- ONE batched simulation loop steps all running sequences together; each
  step's duration comes from a load-dependent timing model —
  ``decode_ms(batch, active_kv_tokens)`` (bandwidth-bound decode: a base
  weights-streaming floor plus per-sequence and per-cached-token terms) and
  ``prefill_ms(chunk_tokens)`` for the chunked prefill admitted alongside —
  so routers and the planner observe the queueing effects the reference
  mocker models (mocker/scheduler.rs:240): ITL rises with batch size and
  with active context length.
- The *real* ``BlockAllocator`` + chained hashing provide prefix caching
  and block-granular KV events, bit-identical to the real engine's.
- Watermark preemption: when block allocation fails mid-decode, the newest
  running sequence is preempted (blocks released → removed events) and
  requeued for recompute — the real scheduler's policy.
- ``speedup_ratio`` compresses simulated time uniformly.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass
from typing import Any, AsyncIterator, Callable, List, Optional

from dynamo_tpu.engine.kv_cache import BlockAllocator, KvEvent, OutOfBlocksError
from dynamo_tpu.engine.scheduler import ForwardPassMetrics
from dynamo_tpu.llm.tokens import compute_block_hashes
from dynamo_tpu.runtime import faults
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.telemetry import SloConfig, SloJudge, Telemetry

logger = get_logger(__name__)

# Queue sentinel for an injected engine crash: ``generate`` turns it into an
# abrupt ConnectionResetError (the stream dies without a final frame).
_CRASH = object()


@dataclass
class MockEngineArgs:
    """Ref: mocker/protocols.rs:67 MockEngineArgs."""

    block_size: int = 16
    num_blocks: int = 512
    max_batch: int = 32
    speedup_ratio: float = 1.0
    # Fraction of blocks kept free: allocations that would dip below the
    # watermark trigger preemption (ref mocker's eviction policy).
    watermark: float = 0.01
    # Timing model — decode is bandwidth-bound (weights floor + per-seq +
    # per-active-KV-token), prefill is compute-bound (per-token).
    itl_base_ms: float = 3.0
    itl_per_seq_ms: float = 0.05
    itl_per_kv_token_us: float = 0.05
    prefill_base_ms: float = 0.5
    prefill_per_token_us: float = 40.0
    max_prefill_chunk: int = 2048
    # SLA telemetry: same knobs as SchedulerConfig — the mocker judges its
    # (wall-clock) TTFT/TPOT against these and exports the same digest/SLO
    # stats keys, so planner tests and traffic harnesses run engine-free.
    slo_ttft_ms: Optional[float] = None
    slo_tpot_ms: Optional[float] = None
    # Tenant ledger (runtime/ledger.py): heavy-hitter sketch width, same
    # knob as SchedulerConfig.ledger_top_k.
    ledger_top_k: int = 16
    # Output-token rule: "cycle" repeats the prompt (default), "position"
    # emits token = sequence position — position streams continue bit-
    # identically across a migration replay (prompt + emitted tokens fold
    # into the replay prompt), which is what the chaos suite's zero-loss /
    # zero-duplication assertions pin.
    token_rule: str = "cycle"
    # Back-compat aliases used by older callers/flags.
    prefill_time_per_token_ms: Optional[float] = None
    decode_time_per_token_ms: Optional[float] = None

    def __post_init__(self):
        if self.prefill_time_per_token_ms is not None:
            self.prefill_per_token_us = self.prefill_time_per_token_ms * 1000.0
        if self.decode_time_per_token_ms is not None:
            self.itl_base_ms = self.decode_time_per_token_ms

    def decode_ms(self, batch: int, active_kv_tokens: int) -> float:
        return (
            self.itl_base_ms
            + batch * self.itl_per_seq_ms
            + active_kv_tokens * self.itl_per_kv_token_us / 1000.0
        )

    def prefill_ms(self, chunk_tokens: int) -> float:
        return self.prefill_base_ms + chunk_tokens * self.prefill_per_token_us / 1000.0


class _Seq:
    def __init__(
        self,
        request_id: str,
        tokens: List[int],
        max_tokens: int,
        context: Context,
        forced: Optional[List[int]] = None,
        deadline_ms: Optional[float] = None,
        prefill_done: bool = False,
        prefill_len: Optional[int] = None,
        tenant: str = "anon",
    ):
        self.request_id = request_id
        self.tenant = tenant
        self.tokens = tokens
        self.max_tokens = max_tokens
        self.context = context
        # Disaggregated decode leg: the prompt's KV "arrived by transfer"
        # (the real scheduler's disagg_inject) — blocks are allocated but
        # no prefill compute is simulated and no prefix is matched or
        # registered (transferred KV is not reuse). prefill_len < prompt
        # length marks a token-boundary SPLIT leg: only the first
        # prefill_len tokens transferred; the rest prefills locally.
        self.prefill_done = prefill_done
        self.prefill_len = len(tokens) if prefill_len is None else prefill_len
        self.arrival_ts = time.monotonic()
        self.deadline_ts = (
            self.arrival_ts + deadline_ms / 1000.0 if deadline_ms else None
        )
        self.admitted_ts: Optional[float] = None
        self.first_token_ts: Optional[float] = None
        # Guided decoding: the exact token stream to emit (a grammar-valid
        # rendering of the request's constraint) instead of prompt cycling.
        self.forced = forced
        self.out: asyncio.Queue = asyncio.Queue()
        self.block_ids: List[int] = []
        self.hashes = []
        self.computed = 0  # tokens (re)computed toward prefill_span
        self.cached_tokens = 0
        self.generated = 0
        self.recompute = 0  # generated tokens whose KV must be recomputed (preemption)
        self.preemptions = 0
        self.done = False
        # Tenant capacity bill (runtime/ledger.py) — same accrual discipline
        # as the real scheduler's Sequence: simulated device-seconds per
        # phase, lazy KV block-second clock, billed-once guard.
        self.bill_prefill_s = 0.0
        self.bill_decode_s = 0.0
        self.bill_kv_block_s = 0.0
        self.kv_ts: Optional[float] = None
        self.billed = False

    @property
    def total_len(self) -> int:
        return len(self.tokens) + self.generated

    @property
    def prefill_span(self) -> int:
        """Tokens the (re)prefill must cover: the prompt, plus — after a
        preemption — the generated tokens whose KV was dropped (the real
        scheduler's recompute-preemption cost)."""
        return len(self.tokens) + self.recompute

    @property
    def in_decode(self) -> bool:
        return self.computed >= self.prefill_span


class MockTpuEngine:
    """AsyncEngine-shaped engine emulator with a batched scheduler core."""

    def __init__(
        self,
        args: Optional[MockEngineArgs] = None,
        *,
        kv_event_sink: Optional[Callable[[KvEvent], None]] = None,
        tokenizer=None,
    ):
        self.args = args or MockEngineArgs()
        self._sink = kv_event_sink
        # Guided requests render their grammar's accepted string through
        # this tokenizer (default: the byte tokenizer the mocker stacks
        # serve with), so the full wire path yields schema-valid output.
        self.tokenizer = tokenizer
        self.guided_total = 0
        self.allocator = BlockAllocator(self.args.num_blocks, on_event=self._on_event)
        self.waiting: List[_Seq] = []
        self.running: List[_Seq] = []
        self.request_total = 0
        self.prefill_tokens_done = 0
        self.preempt_total = 0
        self.cached_tokens_total = 0  # prefix-cache hit tokens (hit-rate telemetry)
        self.timeouts_total = 0  # deadline evictions (finish_reason "timeout")
        # Traffic-shape counters: the planner's observer derives request
        # rate and avg ISL/OSL from these when no frontend is in the path
        # (pure mocker fleets under the traffic harness).
        self.input_tokens_total = 0
        self.output_tokens_total = 0
        self.disagg_prefill_done_total = 0  # decode legs admitted with transferred KV
        # Per-phase step accounting, same families as the flight recorder's
        # step_{phase}_* counters: the observer derives MEASURED per-worker
        # tok/s from Δtokens/Δtime of these, so the ProfiledCapacityModel
        # closes its loop on engine-free mocker fleets too. Time is wall
        # clock (speedup applied) — the same clock MockerCapacityModel's
        # declared rates are in.
        self.step_prefill_steps_total = 0
        self.step_prefill_tokens_total = 0
        self.step_prefill_time_s = 0.0
        self.step_decode_steps_total = 0
        self.step_decode_tokens_total = 0
        self.step_decode_time_s = 0.0
        # Elastic capacity dial: same semantics as Scheduler.set_capacity_dial
        # (budget split re-derived around the configured bases), so planner
        # stacks and the traffic harness exercise ratio shifts engine-free.
        self._base_prefill_chunk = self.args.max_prefill_chunk
        self._base_max_batch = self.args.max_batch
        self._elastic_fraction = 0.5
        self.elastic_dial_changes_total = 0
        # Degradation-ladder counters (same families as the disagg handler's
        # scrape): the handler — or a harness standing in for it — reports
        # mode transitions here so mocker fleets emit the engine's keys.
        self.degrade_disagg_to_colocated_total = 0
        self.degrade_colocated_to_disagg_total = 0
        self._step_n = 0  # chaos-plane step counter (worker.step site passes)
        self.last_step_ms = 0.0  # most recent simulated step duration
        self.last_step_ts: Optional[float] = None  # stall-watchdog reference
        # Same telemetry surface as the real engine (runtime/telemetry.py):
        # wall-clock ttft/tpot/itl/queue_wait digests + SLO/goodput account,
        # exported under the same stats keys so planner and traffic-harness
        # stacks observe a mocker fleet exactly like an engine fleet.
        self.telemetry = Telemetry()
        self.slo = SloJudge(SloConfig(ttft_ms=self.args.slo_ttft_ms,
                                      tpot_ms=self.args.slo_tpot_ms))
        # Tenant capacity ledger: same sketch/digest/stats surface as the
        # real scheduler's, fed from the simulated timing model, so fleet
        # merge and Grafana's Tenants row run engine-free.
        from dynamo_tpu.runtime.ledger import TenantLedger

        self.ledger = TenantLedger(
            top_k=self.args.ledger_top_k,
            slo=SloConfig(ttft_ms=self.args.slo_ttft_ms, tpot_ms=self.args.slo_tpot_ms),
        )
        # Incident autopsy plane (runtime/incidents.py): the mocker runs the
        # REAL detector over its own simulated stats and emits the same
        # incidents_*/gauge keys as TpuEngine, so planner/autoscaler stacks
        # observe identical metric families from an engine-free fleet.
        from dynamo_tpu.runtime.incidents import IncidentConfig, IncidentPlane

        self.incidents = IncidentPlane(
            IncidentConfig(),
            config_probe=lambda: {"engine": "mocker", "args": vars(self.args)},
        )
        self._loop_task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()

    def _on_event(self, ev: KvEvent) -> None:
        if self._sink is not None:
            self._sink(ev)

    def set_kv_event_sink(self, sink: Callable[[KvEvent], None]) -> None:
        self._sink = sink

    # --- elastic capacity dial ---------------------------------------------
    def set_capacity_dial(self, prefill_fraction: float) -> dict:
        """Re-split the simulated budget between prefill and decode, live —
        the mocker mirror of Scheduler.set_capacity_dial (same clamps, same
        f=0.5 ⇒ configured-identity), reachable via the same ``set_dial``
        control op when served behind an endpoint."""
        f = min(1.0, max(0.0, float(prefill_fraction)))
        bs = self.args.block_size
        raw = int(round(2.0 * f * self._base_prefill_chunk))
        budget = max(bs, min(raw, self._base_prefill_chunk))
        slots = int(round(2.0 * (1.0 - f) * self._base_max_batch))
        slots = max(1, min(self._base_max_batch, slots))
        self._elastic_fraction = f
        self.args.max_prefill_chunk = budget
        self.args.max_batch = slots
        self.elastic_dial_changes_total += 1
        logger.info("mocker capacity dial: prefill_fraction=%.3f → prefill_chunk=%d decode_slots=%d",
                    f, budget, slots)
        return {"prefill_fraction": f, "mixed_prefill_budget": budget, "decode_slots": slots}

    def note_degrade(self, direction: str) -> None:
        """Record a degradation-ladder transition on this worker's scrape
        (the disagg handler owns the decision; mocker fleets without one
        let the harness call this so the degrade_* families still flow)."""
        if direction == "disagg_to_colocated":
            self.degrade_disagg_to_colocated_total += 1
        elif direction == "colocated_to_disagg":
            self.degrade_colocated_to_disagg_total += 1
        else:
            raise ValueError(f"unknown degrade direction: {direction}")

    # --- AsyncEngine --------------------------------------------------------
    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        tokens: List[int] = list(request.get("token_ids") or [])
        stop = request.get("stop_conditions") or {}
        max_tokens = int(stop.get("max_tokens") or 16)
        deadline_ms = stop.get("deadline_ms")
        self.request_total += 1
        # Disagg decode legs are marked "_prefilled" on the engine-plane
        # wire (disagg.py); the traffic harness's synthetic requests use the
        # legacy "prefill_done" flag. Honor both so the mocker behaves like
        # the real engine when it stands in for one behind the disagg
        # handler ("prefill_done" itself is baselined in dtlint_baseline).
        pref = request.get("_prefilled") or request.get("prefill_done")
        prefilled = bool(pref)
        # Token-boundary split legs: a dict _prefilled may carry
        # "prefill_len" = N (< prompt length) — the first N tokens arrived
        # as transferred KV; the remainder prefills locally, exactly the
        # real scheduler's partial-inject path.
        prefill_len = len(tokens)
        if isinstance(pref, dict) and pref.get("prefill_len") is not None:
            prefill_len = min(int(pref["prefill_len"]), len(tokens))
        if not prefilled:
            # Disagg decode legs carry the prompt for context accounting but
            # prefill none of it — counting their input tokens would double
            # the observer's prefill-demand estimate (rate × ISL).
            self.input_tokens_total += len(tokens)
        elif prefill_len < len(tokens):
            self.input_tokens_total += len(tokens) - prefill_len  # the local remainder
        forced = self._guided_tokens(request.get("guided_decoding"))
        seq = _Seq(
            f"mock-{self.request_total}", tokens, max_tokens, context,
            forced=forced, deadline_ms=float(deadline_ms) if deadline_ms else None,
            prefill_done=prefilled, prefill_len=prefill_len,
            tenant=request.get("tenant") or "anon",
        )
        self.waiting.append(seq)
        self._ensure_loop()
        self._wake.set()
        try:
            while True:
                frame = await seq.out.get()
                if frame is None:
                    return
                if frame is _CRASH:
                    # An injected engine crash: die like a process death —
                    # the worker ingress drops the call-home socket and the
                    # client observes a genuine StreamDisconnect.
                    raise ConnectionResetError("injected worker crash")
                yield frame
                if frame.get("finish_reason"):
                    return
        finally:
            seq.done = True

    def _guided_tokens(self, spec) -> Optional[List[int]]:
        """Honor a guided-decoding spec: compile its grammar and emit the
        (deterministic) shortest accepted string as the output token stream,
        so router/frontend stacks exercise the full structured-output wire
        path — response_format in, schema-valid JSON out — with no model."""
        if not spec:
            return None
        from dynamo_tpu.llm.guided.grammar import GrammarError, spec_to_dfa

        try:
            text = spec_to_dfa(spec).shortest_accepting()
        except GrammarError as e:
            logger.warning("mocker ignoring uncompilable guided spec: %s", e)
            return None
        self.guided_total += 1
        if self.tokenizer is not None:
            return list(self.tokenizer.encode(text))
        from dynamo_tpu.llm.tokenizer import ByteTokenizer

        return list(ByteTokenizer().encode(text))

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(self._sim_loop())

    # --- batched simulation core -------------------------------------------
    async def _sim_loop(self) -> None:
        args = self.args
        while self.waiting or self.running:
            self._reap_stopped()
            step_ms = 0.0
            slow_factor = 1.0

            # Chaos plane (runtime/faults.py): the per-step site. ``crash``
            # kills the engine loop and severs every live stream abruptly
            # (process-death semantics); ``hang`` wedges the loop inside
            # afire; ``slow`` stretches this step's simulated duration.
            if faults.armed():
                self._step_n += 1
                try:
                    spec = await faults.afire("worker.step", step=self._step_n)
                except faults.InjectedFault:
                    self._crash_all()
                    return
                if spec is not None and spec.kind == "slow":
                    slow_factor = max(spec.factor, 1.0)

            # Admission: a WAVE of prefill chunks per step, bounded by a
            # max_prefill_chunk token budget — mirroring the real
            # scheduler's wave admission + mixed-step prefill budget (a
            # burst of short/cache-hit prompts admits together instead of
            # serializing one per step, which queued concentrated KV-routed
            # traffic behind an artificial one-admission rule). Prefer
            # mid-chunk sequences (they already hold blocks — leaving one
            # parked while the head can't allocate is a head-of-line
            # deadlock); otherwise take the head.
            wave_tokens = 0
            wave_bill: List[tuple] = []  # (seq, chunk) — per-seq prefill attribution
            while (
                self.waiting
                and len(self.running) < args.max_batch
                and wave_tokens < args.max_prefill_chunk
            ):
                seq = next((s for s in self.waiting if s.block_ids), self.waiting[0])
                chunk = self._admit_chunk(seq, args.max_prefill_chunk - wave_tokens)
                wave_tokens += chunk
                self.prefill_tokens_done += chunk
                if chunk:
                    wave_bill.append((seq, chunk))
                if seq.in_decode:
                    # remove() not pop(0): _admit_chunk's allocation may have
                    # preempted a victim INTO waiting[0] just now.
                    self.waiting.remove(seq)
                    self.running.append(seq)
                else:
                    break  # blocked on KV blocks, or budget consumed mid-prompt
            pre_ms = args.prefill_ms(wave_tokens) if wave_tokens else 0.0
            step_ms += pre_ms

            # Batched decode step: every running sequence produces one token;
            # latency depends on batch width and total active KV.
            decoding = [s for s in self.running if s.in_decode]
            dec_ms = 0.0
            if decoding:
                active_kv = sum(s.total_len for s in decoding)
                dec_ms = args.decode_ms(len(decoding), active_kv)
                step_ms += dec_ms

            if step_ms == 0.0:
                # Nothing admissible (block pressure): idle-wait a tick.
                step_ms = args.itl_base_ms

            step_ms *= slow_factor
            self.last_step_ms = step_ms
            await asyncio.sleep(step_ms / 1000.0 / args.speedup_ratio)
            self.last_step_ts = time.monotonic()
            # Per-phase step accounting: each phase is charged its own
            # simulated wall time (slow-factor included, so chaos slowdowns
            # show up as genuinely reduced measured capacity).
            scale = slow_factor / 1000.0 / args.speedup_ratio
            if wave_tokens:
                self.step_prefill_steps_total += 1
                self.step_prefill_tokens_total += wave_tokens
                self.step_prefill_time_s += pre_ms * scale
                # Tenant billing: the wave's simulated prefill time splits
                # pro-rata by chunk tokens — shares sum to the step exactly.
                for s, chunk in wave_bill:
                    s.bill_prefill_s += pre_ms * scale * (chunk / wave_tokens)
            if decoding:
                self.step_decode_steps_total += 1
                self.step_decode_tokens_total += len(decoding)
                self.step_decode_time_s += dec_ms * scale
                # Decode billing: each row's marginal term of the timing
                # model (per-seq + per-KV-token), normalized so the shared
                # weights-streaming floor is carried pro-rata too.
                dweights = [
                    args.itl_per_seq_ms + s.total_len * args.itl_per_kv_token_us / 1000.0
                    for s in decoding
                ]
                dsum = sum(dweights) or 1.0
                for s, w in zip(decoding, dweights):
                    s.bill_decode_s += dec_ms * scale * w / dsum
            # KV block-second accrual for every current holder (lazy clock,
            # same discipline as the real scheduler's _accrue_kv).
            kv_now = time.monotonic()
            for s in self.running + self.waiting:
                if s.block_ids or s.kv_ts is not None:
                    self._accrue_kv(s, kv_now)
            if decoding:
                # Wall-clock step time = the ITL the wire observes.
                self.telemetry.observe("itl", step_ms / 1000.0 / args.speedup_ratio)
                self.telemetry.observe("decode_step", step_ms / 1000.0 / args.speedup_ratio)

            for s in list(decoding):
                if s not in self.running:
                    continue  # preempted mid-step by another row's allocation
                if s.context.is_stopped():
                    continue  # reaped next iteration
                if not self._grow_blocks(s):
                    continue  # preempted (itself) — no token this step
                if s.forced is not None and not s.forced:
                    # Grammar accepts the empty string: finish immediately.
                    s.out.put_nowait({"token_ids": [], "finish_reason": "stop", "index": 0})
                    self._finish(s, "stop")
                    continue
                s.generated += 1
                self.output_tokens_total += 1
                if s.forced is not None:
                    # Guided: emit the grammar-valid stream; "stop" on the
                    # final token (the FSM accepted), "length" if max_tokens
                    # cuts the rendering short.
                    token = s.forced[s.generated - 1]
                    finish = "stop" if s.generated >= len(s.forced) else None
                    if finish is None and s.generated >= s.max_tokens:
                        finish = "length"
                elif args.token_rule == "position":
                    # token = 0-based sequence position: a migrated replay
                    # (prompt + already-emitted tokens) continues exactly
                    # where the dead worker stopped.
                    token = s.total_len - 1
                    finish = "length" if s.generated >= s.max_tokens else None
                else:
                    token = s.tokens[s.generated % len(s.tokens)] if s.tokens else s.generated
                    finish = "length" if s.generated >= s.max_tokens else None
                frame = {"token_ids": [token], "finish_reason": finish, "index": 0}
                if s.generated == 1:
                    s.first_token_ts = time.monotonic()
                    self.telemetry.observe(
                        "ttft", max(0.0, s.first_token_ts - s.arrival_ts)
                    )
                    # First frame carries the real engine's reuse report:
                    # prompt tokens whose simulated prefill was skipped by
                    # the prefix cache (the wire shape router/frontend
                    # accounting reads).
                    frame["cached_tokens"] = s.cached_tokens
                s.out.put_nowait(frame)
                if finish:
                    # Natural finish: judge SLA (cancelled requests aren't
                    # latency violations) and fold TPOT into the digests.
                    ttft_s = tpot_s = None
                    if s.first_token_ts is not None:
                        now = time.monotonic()
                        ttft_s = max(0.0, s.first_token_ts - s.arrival_ts)
                        if s.generated > 1:
                            tpot_s = max(0.0, now - s.first_token_ts) / (s.generated - 1)
                            self.telemetry.observe("tpot", tpot_s)
                        self.slo.judge(ttft_s, tpot_s, s.generated)
                    self._finish(s, finish, ttft_s=ttft_s, tpot_s=tpot_s)
            if not (self.waiting or self.running):
                # Wait briefly for new arrivals before exiting the loop task.
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=0.2)
                except asyncio.TimeoutError:
                    # A request may have landed in the shutdown window (its
                    # _wake.set() can race the cancelled waiter): only exit
                    # when there is truly no work.
                    if not (self.waiting or self.running):
                        return

    def _reap_stopped(self) -> None:
        now = time.monotonic()

        def verdict(s: _Seq) -> Optional[str]:
            if s.context.is_stopped() or s.done:
                return "cancelled"
            if s.deadline_ts is not None and now >= s.deadline_ts:
                # Deadline eviction, same semantics as the real scheduler:
                # finish_reason "timeout", blocks freed right here.
                self.timeouts_total += 1
                return "timeout"
            return None

        for s in list(self.running):
            reason = verdict(s)
            if reason is not None:
                if not s.done:
                    s.out.put_nowait({"token_ids": [], "finish_reason": reason, "index": 0})
                self._finish(s, reason)
        for s in list(self.waiting):
            reason = verdict(s)
            if reason is not None:
                self.waiting.remove(s)
                # Never-admitted requests still bill their queue time (and
                # any mid-prefill KV hold) — timeout storms in the queue are
                # exactly what tenant attribution must see.
                self._emit_bill(s, reason)
                self.allocator.release(s.block_ids)
                s.block_ids = []
                s.kv_ts = None
                if not s.done:
                    s.out.put_nowait({"token_ids": [], "finish_reason": reason, "index": 0})

    def _admit_chunk(self, seq: _Seq, budget: Optional[int] = None) -> int:
        """Advance one prefill chunk; returns simulated chunk tokens (0 when
        blocked on KV blocks). First touch matches the prefix cache —
        cached tokens shorten the simulated prefill (the chunk covers only
        the uncached remainder, the real engine's skipped-FLOPs behavior).
        ``budget`` caps the chunk (wave admission shares one per-step
        token budget across admitted sequences)."""
        args = self.args
        bs = args.block_size
        if seq.computed == 0 and not seq.block_ids and seq.prefill_done and seq.recompute == 0:
            # Disagg decode leg: KV for (the first prefill_len tokens of)
            # the prompt was transferred in. Allocate the blocks the full
            # sequence occupies, skip the prefill simulation for the
            # transferred span, and leave the prefix cache untouched
            # (transferred blocks are private — counting them as cache hits
            # would poison the router's warmth accounting). A SPLIT leg
            # (prefill_len < prompt) falls through to chunked prefill for
            # the remainder. After a preemption the transferred KV is gone
            # and the normal recompute path runs.
            needed = (seq.total_len + 1 + bs - 1) // bs
            if not self._allocate(seq, needed, preempt=False):
                return 0
            n_pref = min(seq.prefill_len, len(seq.tokens))
            full = n_pref >= len(seq.tokens)
            seq.computed = seq.prefill_span if full else n_pref
            self.disagg_prefill_done_total += 1
            if seq.admitted_ts is None:
                seq.admitted_ts = time.monotonic()
                self.telemetry.observe(
                    "queue_wait", max(0.0, seq.admitted_ts - seq.arrival_ts)
                )
            if full:
                return 0
        if seq.computed == 0 and not seq.block_ids:
            seq.hashes = compute_block_hashes(seq.tokens, bs)
            matched = self.allocator.match_prefix(seq.hashes)
            if matched and len(matched) * bs >= len(seq.tokens):
                self.allocator.release([matched[-1]])
                matched = matched[:-1]
            seq.block_ids = list(matched)
            seq.cached_tokens = len(matched) * bs
            seq.computed = min(seq.cached_tokens, seq.prefill_span)
            # Cover the full current length (prompt + any generated tokens
            # being recomputed after preemption) plus the next write slot.
            # Admission never preempts — it backpressures (the real
            # scheduler's _admit policy): preempting a decode to admit a
            # newcomer just trades one recompute for another, and under
            # wave admission it livelocks (victims re-match their own
            # still-registered prefix and thrash).
            needed = (seq.total_len + 1 + bs - 1) // bs - len(seq.block_ids)
            if needed > 0 and not self._allocate(seq, needed, preempt=False):
                # Roll back the first touch entirely; retried next step.
                self.allocator.release(seq.block_ids)
                seq.block_ids = []
                seq.computed = 0
                seq.cached_tokens = 0
                return 0
            # Count hits only on a COMMITTED first touch — a rolled-back
            # admission retries and would double-count (which inflated a
            # thrash-prone policy's hit rate).
            self.cached_tokens_total += seq.cached_tokens
            if seq.admitted_ts is None:
                seq.admitted_ts = time.monotonic()
                self.telemetry.observe(
                    "queue_wait", max(0.0, seq.admitted_ts - seq.arrival_ts)
                )
        remaining = seq.prefill_span - seq.computed
        chunk = min(remaining, args.max_prefill_chunk)
        if budget is not None:
            chunk = min(chunk, budget)
        seq.computed += chunk
        # Register every completed block as chunks land (the real
        # scheduler's per-chunk registration): concurrent same-prefix
        # requests share KV mid-prefill.
        n_done = min(seq.computed, len(seq.tokens)) // bs
        n_done = min(n_done, len(seq.hashes), len(seq.block_ids))
        if n_done:
            self.allocator.register_hashes(seq.block_ids[:n_done], seq.hashes[:n_done])
        return chunk

    def _allocate(self, seq: _Seq, n: int, preempt: bool = True) -> bool:
        """Allocate n blocks, preempting the newest running sequence when the
        pool dips below the watermark (ref mocker's eviction policy).
        ``preempt=False`` (admission path) backpressures instead."""
        args = self.args
        floor = int(args.num_blocks * args.watermark)
        while True:
            if self.allocator.num_blocks - self.allocator.num_active - n >= floor:
                try:
                    seq.block_ids.extend(self.allocator.allocate(n))
                    return True
                except OutOfBlocksError:
                    pass
            if not preempt or not self._preempt_newest(exclude=seq):
                return False

    def _grow_blocks(self, seq: _Seq) -> bool:
        bs = self.args.block_size
        while seq.total_len + 1 > len(seq.block_ids) * bs:
            if not self._allocate(seq, 1):
                # Could not grow even after preempting others: preempt SELF.
                self._preempt(seq)
                return False
        return True

    def _preempt_newest(self, exclude: Optional[_Seq] = None) -> bool:
        candidates = [s for s in self.running if s is not exclude and s.in_decode]
        if not candidates:
            return False
        self._preempt(candidates[-1])
        return True

    def _preempt(self, seq: _Seq) -> None:
        if seq in self.running:
            self.running.remove(seq)
        # Close the KV clock at the true release point (recompute holds none).
        self._accrue_kv(seq)
        seq.kv_ts = None
        self.allocator.release(seq.block_ids)
        seq.block_ids = []
        seq.hashes = []
        seq.computed = 0
        seq.cached_tokens = 0
        seq.recompute = seq.generated  # dropped KV must be recomputed
        seq.preemptions += 1
        self.preempt_total += 1
        self.waiting.insert(0, seq)

    def _finish(self, seq: _Seq, reason: str = "cancelled",
                ttft_s: Optional[float] = None, tpot_s: Optional[float] = None) -> None:
        if seq in self.running:
            self.running.remove(seq)
        if seq in self.waiting:
            self.waiting.remove(seq)
        # Bill while blocks are still held so the KV accrual closes at the
        # true release point — same choke-point discipline as the scheduler.
        self._emit_bill(seq, reason, ttft_s=ttft_s, tpot_s=tpot_s)
        self.allocator.release(seq.block_ids)
        seq.block_ids = []
        seq.kv_ts = None

    def _accrue_kv(self, seq: _Seq, now: Optional[float] = None) -> None:
        """Lazy KV block-second accrual (real scheduler's _accrue_kv)."""
        if now is None:
            now = time.monotonic()
        if seq.kv_ts is not None:
            seq.bill_kv_block_s += len(seq.block_ids) * (now - seq.kv_ts)
        seq.kv_ts = now if seq.block_ids else None

    def _emit_bill(self, seq: _Seq, reason: str,
                   ttft_s: Optional[float] = None,
                   tpot_s: Optional[float] = None) -> None:
        if seq.billed:
            return
        seq.billed = True
        from dynamo_tpu.runtime.ledger import RequestBill

        self._accrue_kv(seq)
        queue_end = seq.admitted_ts if seq.admitted_ts is not None else time.monotonic()
        self.ledger.record(RequestBill(
            tenant=seq.tenant,
            request_id=seq.request_id,
            queue_s=max(0.0, queue_end - seq.arrival_ts),
            prefill_device_s=seq.bill_prefill_s,
            decode_device_s=seq.bill_decode_s,
            flops=0.0,  # the mocker has no cost model — device time is the truth
            output_tokens=seq.generated,
            kv_block_s=seq.bill_kv_block_s,
            finish_reason=reason,
            ttft_s=ttft_s,
            tpot_s=tpot_s,
        ))

    def _crash_all(self) -> None:
        """Injected engine death: sever every live stream without a final
        frame (clients observe StreamDisconnect and migrate) and free the
        pool — the next request restarts the sim loop, i.e. the worker
        'process' comes back empty, exactly like a restart."""
        logger.warning("mocker crash injected: dropping %d stream(s)",
                       len(self.running) + len(self.waiting))
        for s in self.running + self.waiting:
            self.allocator.release(s.block_ids)
            s.block_ids = []
            s.kv_ts = None  # process death: in-flight consumption bills nowhere
            s.out.put_nowait(_CRASH)
        self.running.clear()
        self.waiting.clear()

    # --- stats --------------------------------------------------------------
    def metrics(self) -> ForwardPassMetrics:
        return ForwardPassMetrics(
            num_running=len(self.running),
            num_waiting=len(self.waiting),
            kv_usage=self.allocator.usage(),
            kv_total_blocks=self.allocator.num_blocks,
            kv_active_blocks=self.allocator.num_active,
            prefill_tokens_in_flight=sum(len(s.tokens) - s.computed for s in self.waiting),
            request_total=self.request_total,
            cached_tokens_total=self.cached_tokens_total,
            prefix_hit_blocks_total=self.allocator.hit_blocks_total,
            prefix_miss_blocks_total=self.allocator.miss_blocks_total,
            prefix_evicted_blocks_total=self.allocator.evicted_blocks_total,
            elastic_prefill_fraction=self._elastic_fraction,
            elastic_prefill_budget=self.args.max_prefill_chunk,
            elastic_decode_slots=self.args.max_batch,
            elastic_dial_changes_total=self.elastic_dial_changes_total,
        )

    def stats_handler(self) -> dict:
        m = self.metrics()
        a = self.allocator
        hits, misses = a.hit_blocks_total, a.miss_blocks_total
        stats = {
            "kv_usage": m.kv_usage,
            "num_running": m.num_running,
            "num_waiting": m.num_waiting,
            # Prefix-cache hit accounting over the scrape path, same keys as
            # the real engine's stats_handler (aggregator counters).
            "cached_tokens_total": m.cached_tokens_total,
            "prefix_hit_blocks_total": m.prefix_hit_blocks_total,
            "prefix_miss_blocks_total": m.prefix_miss_blocks_total,
            "prefix_evicted_blocks_total": m.prefix_evicted_blocks_total,
            # Utilization gauges, same keys as Scheduler.kv_gauges().
            "kv_free_blocks": len(a._free),
            "kv_cached_blocks": a.num_cached,
            "prefix_hit_rate": round(hits / (hits + misses), 6) if (hits + misses) else 0.0,
            # KV warmth: fraction of the pool holding registered (reusable)
            # prefix KV — the engine-side half of the planner's
            # coldest-worker scale-down signal.
            "kv_warmth": round(a.num_cached / a.num_blocks, 6) if a.num_blocks else 0.0,
            "preemptions_total": self.preempt_total,
            "request_total": self.request_total,
            "request_timeouts_total": self.timeouts_total,
            # Traffic shape for the observer (rate = Δrequest_total/Δt,
            # ISL/OSL = token deltas per request delta) on frontend-less
            # mocker fleets.
            "input_tokens_total": self.input_tokens_total,
            "output_tokens_total": self.output_tokens_total,
            "disagg_prefill_done_total": self.disagg_prefill_done_total,
            # Elastic capacity dial + degradation ladder: same key families
            # as the engine scrape (stats_handler) and the disagg handler's,
            # so planner stacks exercise ratio shifts engine-free.
            "elastic_prefill_fraction": self._elastic_fraction,
            "elastic_prefill_budget": self.args.max_prefill_chunk,
            "elastic_decode_slots": self.args.max_batch,
            "elastic_dial_changes_total": self.elastic_dial_changes_total,
            "degrade_disagg_to_colocated_total": self.degrade_disagg_to_colocated_total,
            "degrade_colocated_to_disagg_total": self.degrade_colocated_to_disagg_total,
            # Per-phase step families (flight-recorder key parity): the
            # observer's measured tok/s derivation reads Δtokens/Δseconds.
            "step_prefill_steps_total": self.step_prefill_steps_total,
            "step_prefill_tokens_total": self.step_prefill_tokens_total,
            "step_prefill_time_seconds_total": round(self.step_prefill_time_s, 6),
            "step_decode_steps_total": self.step_decode_steps_total,
            "step_decode_tokens_total": self.step_decode_tokens_total,
            "step_decode_time_seconds_total": round(self.step_decode_time_s, 6),
        }
        # Device-truth parity: plausible synthetic measured siblings so the
        # aggregator/Grafana/planner stack runs engine-free. The mocker's
        # simulated clock IS its device, so the synthetic sampler reports
        # one 250ms window per 30s of simulated busy time, 85% device-busy
        # and a perfectly calibrated cost model.
        sim_busy_s = self.step_prefill_time_s + self.step_decode_time_s
        windows = int(sim_busy_s / 30.0) + (1 if sim_busy_s > 0 else 0)
        stats.update({
            "device_profile_windows_total": windows,
            "device_profile_window_seconds_total": round(windows * 0.25, 6),
            "device_profile_skipped_busy_total": 0,
            "device_profile_errors_total": 0,
            "device_profile_duty_cycle": round(0.25 / 30.0, 6),
            "cost_model_calibrated": 1.0,
        })
        if windows:
            stats.update({
                "measured_windows_total": windows,
                "measured_device_seconds_total": round(windows * 0.25 * 0.85, 6),
                "measured_wall_seconds_total": round(windows * 0.25, 6),
                "measured_mfu": 0.45,
                "measured_hbm_frac": 0.6,
                "measured_device_frac": 0.85,
                "measured_modeled_mfu_ratio": 1.0,
                "measured_top_kernel_share": 0.55,
            })
        # Chaos plane: injected-fault counters, same keys as the engine's
        # scrape (only present on chaos-armed workers).
        stats.update(faults.stats())
        # SLO/goodput account + latency digests: identical keys/shape to
        # TpuEngine.stats_handler, so the aggregator/planner/observer stack
        # can run against pure mocker fleets.
        stats.update(self.slo.to_stats())
        stats["digests"] = self.telemetry.to_wire()
        # Tenant ledger: identical flat tenant_* keys + sketch wire as the
        # real engine's scrape, so the aggregator's fleet merge and the
        # Grafana Tenants row run against mocker fleets unchanged.
        stats.update(self.ledger.to_stats())
        stats["tenant_ledger"] = self.ledger.to_wire()
        # Incident plane: same detector, same incidents_*/profiler keys as
        # the real engine's scrape (engine-free planner stacks included).
        self.incidents.observe(stats)
        stats.update(self.incidents.to_stats())
        return stats
