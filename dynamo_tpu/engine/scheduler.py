"""Continuous batching scheduler: the engine's step loop.

The reference outsources this to vLLM/SGLang/TRT-LLM schedulers; the mocker
(lib/llm/src/mocker/scheduler.rs:240) emulates exactly this machinery —
prefill admission, decode batching, KV block accounting, eviction. Here it is
implemented for real against XLA's static-shape world:

- **Bucketed compilation**: prefill lengths and decode batch sizes round up
  to power-of-two buckets; XLA compiles one executable per bucket and reuses
  it (SURVEY.md §7 hard part (b)).
- **Chunked prefill**: prompts longer than the largest bucket run as chunks,
  interleaving with decode so long prompts don't starve running sequences.
- **Prefix caching**: prompt block hashes are matched against the allocator's
  registry; matched blocks skip prefill entirely (the engine-side half of the
  KV-aware routing story, §3D).
- **Mixed prefill+decode steps**: with sequences decoding AND prefill work
  waiting, each iteration dispatches ONE ragged batch — the full decode
  batch plus up to ``mixed_prefill_budget`` chunk tokens (llama.mixed_step;
  DynaServe arXiv:2504.09285 / TPU ragged paged attention arXiv:2604.15464
  show the same unification). A long prefill no longer stalls the decode
  wave, and admission no longer waits for an empty one.
- **Priority**: decode-first each iteration (keeps ITL low), one prefill
  chunk per iteration (bounds TTFT).

The step loop runs in a worker thread (`asyncio.to_thread`) so device-blocked
steps never stall the process's asyncio IO (the serving plane).
"""

from __future__ import annotations

import asyncio
import enum
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.flight_recorder import FlightRecorder, StepCostModel
from dynamo_tpu.engine.kv_cache import (
    BlockAllocator, KvCacheArrays, KvEvent, OutOfBlocksError, SlotAllocator, cache_rows, pool_of,
)
from dynamo_tpu.runtime.ledger import RequestBill, TenantLedger
from dynamo_tpu.runtime.telemetry import SloConfig, SloJudge, Telemetry
from dynamo_tpu.engine.program_store import StoredJit, open_store
from dynamo_tpu.engine.sampling import SamplingParams, guided_sample_batch, make_row_keys, sample_batch
from dynamo_tpu.llm.tokens import extend_block_hashes
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.tracing import StepLog, StepSpan, get_tracer

logger = get_logger(__name__)


def next_bucket(n: int, buckets: List[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def width_rungs(max_w: int, start: int = 4) -> List[int]:
    """Block-table width rungs up to and including the bucket of ``max_w``:
    pow2 and 1.5·pow2 (4, 6, 8, 12, 16, 24, ...)."""
    rungs: List[int] = []
    w = start
    while True:
        rungs.append(w)
        if w >= max_w:
            return rungs
        nxt = w + w // 2 if w & (w - 1) == 0 else (w // 3) * 4
        w = nxt


def width_bucket(n: int, cap: int) -> int:
    """Smallest pow2-or-1.5·pow2 rung ≥ n, clamped to ``cap``: the width a
    block table is padded to, so that table widths are few."""
    return min(width_rungs(max(n, 1))[-1], cap)


def pack_operands(*parts) -> np.ndarray:
    """A dispatch's small operands as ONE int32 vector, so that they cost one
    upload: ints and bools as they are, floats bit-cast from float32
    (``_f32`` undoes it inside the program)."""
    out = []
    for part in parts:
        a = np.asarray(part).reshape(-1)
        out.append(a.astype(np.float32).view(np.int32) if a.dtype.kind == "f" else a.astype(np.int32))
    return np.concatenate(out)


def _f32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _unpack_rows(rows: jax.Array):
    """(tokens, positions, active) of a decode batch out of the first three
    of the lanes ``Scheduler._pack_rows`` laid down."""
    return rows[0], rows[1], rows[2].astype(bool)


def _greedy(logits: jax.Array) -> jax.Array:
    """What a step program samples: each row's argmax. (A row that draws is
    the host path's, Scheduler._needs_host: the sampler in every executable
    of every key is set-up time and device memory, PERF.md section 6, PR 35.)"""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


_M32 = 0xFFFFFFFF
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def fold_key(key: tuple, data: int) -> tuple:
    """``jax.random.fold_in(key, data)`` of a raw threefry key, on the host:
    the two words of Threefry-2x32 over the block ``[0, data]``, in Python
    integers. A window's key rides its packed operands as two lanes and the
    host path's goes up as it is, so no step program holds a fold (250
    operations to lower again in every executable that has one, 30 ms of
    set-up a key), no program folds eagerly on the step thread, and the seed
    is data, never a constant of an HLO."""
    k0, k1 = key
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = ks[0], (data + ks[1]) & _M32
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = (x0 + x1) & _M32
            x1 = ((x1 << r) | (x1 >> (32 - r))) & _M32
            x1 ^= x0
        x0 = (x0 + ks[(i + 1) % 3]) & _M32
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & _M32
    return x0, x1


@dataclass
class StopConditions:
    max_tokens: int = 256
    min_tokens: int = 0
    stop_token_ids: List[int] = field(default_factory=list)
    ignore_eos: bool = False
    # Remaining deadline budget in ms at arrival (wire: the frontend's
    # --request-timeout-ms / client ``timeout``, minus time already spent).
    # Past-deadline rows are evicted with finish_reason "timeout" and their
    # KV freed — a hung or saturated engine cannot hold a request forever.
    deadline_ms: Optional[float] = None

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "StopConditions":
        d = d or {}
        dl = d.get("deadline_ms")
        return cls(
            max_tokens=d.get("max_tokens") or 256,
            min_tokens=d.get("min_tokens") or 0,
            stop_token_ids=list(d.get("stop_token_ids") or []),
            ignore_eos=bool(d.get("ignore_eos", False)),
            deadline_ms=float(dl) if dl else None,
        )


class SeqState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"  # mid chunked-prefill
    RUNNING = "running"
    FINISHED = "finished"


@dataclass
class StepOutput:
    token_id: int
    finished: bool = False
    finish_reason: Optional[str] = None
    logprob: Optional[float] = None
    # Set on the first token only: seconds the request waited between
    # arrival and engine admission (the saturation signal the SLA planner
    # inverts; ref: http_queue_guard, http/service/metrics.rs).
    queue_s: Optional[float] = None
    # Set on the first token only: prompt tokens whose KV came from the
    # prefix cache instead of prefill compute — the engine's ground truth
    # behind OpenAI ``usage.prompt_tokens_details.cached_tokens`` and the
    # KV router's reuse accounting.
    cached_tokens: Optional[int] = None
    # OpenAI ``top_logprobs``: [(token_id, logprob), ...] for the k most
    # likely tokens at this position (k = sampling.top_logprobs), computed
    # in the same fused sampling dispatch as ``logprob``.
    top_logprobs: Optional[list] = None


@dataclass
class Sequence:
    request_id: str
    prompt: List[int]
    sampling: SamplingParams
    stop: StopConditions
    eos_token_ids: List[int] = field(default_factory=list)
    # runtime state
    state: SeqState = SeqState.WAITING
    output_ids: List[int] = field(default_factory=list)
    block_ids: List[int] = field(default_factory=list)
    num_computed: int = 0  # prompt tokens whose KV is in cache
    # attention_kind "eva": completed windows whose rows are summaries by now
    # (Scheduler._roll); the table is then [summary rows ; current window].
    rolls: int = 0
    # ModelConfig.layer_types: the slot of recurrent state this sequence holds
    # from admission to finish or preemption (0 = none: the scratch slot).
    state_slot: int = 0
    block_hashes: List[int] = field(default_factory=list)
    num_cached_blocks: int = 0  # prefix blocks reused from cache
    cached_tokens: int = 0  # prompt tokens skipped by the prefix cache
    out_queue: "asyncio.Queue[Optional[StepOutput]]" = field(default_factory=asyncio.Queue)
    # When TpuEngine.generate took the request (time.monotonic); it then sits
    # staged until the running dispatch returns and add_request stamps
    # arrival_ts. None for requests added to a bare Scheduler.
    enqueued_ts: Optional[float] = None
    arrival_ts: float = field(default_factory=time.monotonic)
    admitted_ts: Optional[float] = None  # first engine work (queue-time end)
    first_token_ts: Optional[float] = None
    # Request record (step log): dispatches that carried prompt tokens, and the
    # scheduler iteration of the first engine work.
    prefill_chunks: int = 0
    first_step: Optional[int] = None
    aborted: bool = False
    abort_reason: str = "cancelled"
    # Absolute eviction deadline (arrival + stop.deadline_ms); None = no
    # deadline. Swept every step in _reap_aborted.
    deadline_ts: Optional[float] = None
    # Disaggregation: prefill-role sequences keep their blocks at finish for
    # export to the decode worker (ref: vllm do_remote_decode flow, §3C).
    keep_blocks_on_finish: bool = False
    # Decode-role sequences start from remotely prefilled KV.
    prefilled: Optional[dict] = None
    # Multimodal: feature rows injected at positions [0, F) during prefill
    # (the prompt's first F ids are placeholders). Disables prefix caching
    # for the sequence (placeholder ids don't identify image content).
    mm_features: Optional[np.ndarray] = None
    # Preemption resume: tokens whose KV must be recomputed (all generated
    # tokens fold in; the final token re-enters via decode, so no sampling
    # happens at the end of a resume prefill).
    resume_tokens: Optional[List[int]] = None
    preemptions: int = 0
    # Speculative decoding: positions coherently materialized in the DRAFT
    # cache (the draft mirrors the target's block tables; see _decode_spec).
    d_n: int = 0
    # Chosen-token logprob computed by the single-row sampler, consumed by
    # the next _append_token (sampling.logprobs requests).
    _pending_logprob: Optional[float] = None
    # Top-k alternatives for the same token (sampling.top_logprobs > 0),
    # consumed alongside _pending_logprob.
    _pending_top_logprobs: Optional[list] = None
    # Request tracing: (trace_id, parent_span_id) when this request's trace
    # is sampled; None keeps the scheduler's trace path one branch.
    trace: Optional[tuple] = None
    # Guided decoding: per-sequence token-FSM cursor (llm/guided
    # GuidedState). The scheduler advances it host-side from each sampled
    # token and masks logits device-side via the shared mask pool.
    guided: Optional[object] = None
    # Capacity-ledger attribution (runtime/ledger.py): the tenant this
    # request bills to, plus the running bill accumulators. Device-seconds
    # accrue per step in _bill_step; KV block-seconds accrue lazily from
    # ``kv_ts`` (the clock starts when blocks are first held — COW-shared
    # prefix blocks are in block_ids, so holders are charged too).
    tenant: str = "anon"
    bill_prefill_s: float = 0.0
    bill_decode_s: float = 0.0
    bill_flops: float = 0.0
    bill_kv_block_s: float = 0.0
    kv_ts: Optional[float] = None
    billed: bool = False

    @property
    def all_ids(self) -> List[int]:
        return self.prompt + self.output_ids

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.output_ids)


@dataclass
class SchedulerConfig:
    num_blocks: int = 512
    # Decode slots. Default 16→32 (r6): the bench http sweep's first-token
    # breakdown at concurrency 64 put 292 ms of the 393 ms TTFT p50 in the
    # ADMISSION QUEUE with 16 slots (prefill wait was 20 ms) — the knee was
    # queueing, not compute; 32 slots measured +53% req/s and halved p50,
    # with OutOfBlocks backpressure still guarding memory. Size num_blocks
    # to the expected context × slots as before.
    max_running: int = 32
    prefill_buckets: List[int] = field(default_factory=lambda: [32, 64, 128, 256, 512, 1024, 2048])
    decode_buckets: List[int] = field(default_factory=lambda: [1, 2, 4, 8, 16, 32])
    max_prefill_chunk: int = 2048
    enable_prefix_caching: bool = True
    # Disagg prefill role: how long finished-prefill KV blocks may await the
    # decode worker's pull before being reclaimed (orphan guard — e.g. the
    # decode worker timed out or died between prefill and pull).
    export_ttl_s: float = 120.0
    # Multi-step decode: run N autoregressive steps + sampling on device per
    # dispatch (vLLM --num-scheduler-steps role). Amortizes host dispatch —
    # the dominant cost on high-latency links. Tradeoffs: tokens stream out
    # in bursts of N, stop conditions trim after the window (up to N-1
    # wasted steps per finished sequence), and admission waits for the
    # window (only used when no request is waiting). Default 32: measured
    # on v5e at 1B (gather + hoisted window), window 16→32 takes b8 from
    # 7.6→4.9 ms/step and b32 from 11.6→8.0 — the hoisted prefix gather,
    # dispatch, and frame all amortize across the window; 64 gains nothing
    # further and doubles the burst.
    num_scheduler_steps: int = 32
    # While requests wait for admission, cap decode windows at this rung
    # (None = keep full windows). Each window pays one host round-trip
    # (its cost is not measured on a directly attached chip), so full
    # windows favour throughput. Default 8: a newly arrived request must
    # never wait a full 32-step window for admission
    # — mixed batching largely subsumes this (prefill rides the decode
    # step), but the cap still bounds the window on the fallback paths
    # (spec decode, non-llama, mixed disabled). None restores full windows.
    window_waiting_cap: Optional[int] = 8
    # Mixed prefill+decode steps: when sequences are decoding AND prefill
    # work is waiting, each engine step carries the full decode batch plus
    # up to ``mixed_prefill_budget`` prefill tokens from the head of the
    # queue in ONE dispatch (llama.mixed_step) — a long prefill no longer
    # stalls the decode wave, and admission no longer waits for an empty
    # one. The budget bounds the chunk riding each step (the per-step
    # decode stall is one chunk's compute, not a whole prompt's); an
    # itl_budget_ms cap composes on top via _chunk_budget.
    enable_mixed_batching: bool = True
    mixed_prefill_budget: int = 512
    # ITL protection: while sequences are decoding, cap each prefill chunk so
    # its estimated device time stays under this budget (the prefill token
    # rate is learned online from measured chunks). None ⇒ chunks use
    # max_prefill_chunk regardless of running decodes. This bounds the
    # decode stall a long prompt can inject — the role chunked-prefill
    # interleaving plays in the reference's engines (mocker/scheduler.rs:240).
    itl_budget_ms: Optional[float] = None
    # On OutOfBlocks mid-decode, preempt the newest running sequence (free
    # its blocks, re-prefill it later) instead of finishing the starved
    # sequence with "length" (ref: vLLM recompute preemption).
    enable_preemption: bool = True
    # Guided decoding: initial device mask-pool capacity in FSM-state rows.
    # The masked-sampling executable's shape is (decode_bucket, pool_rows);
    # warmup() precompiles it at this capacity, so as long as the total
    # states of live grammars fit, guided rows add no post-warmup compiles.
    # Overflow doubles the pool (pow2 buckets, one recompile, logged).
    guided_pool_rows: int = 1024
    # SLA telemetry: per-request latency targets (None = phase unjudged).
    # Every finished request's TTFT/TPOT is judged against these, feeding
    # the slo_*_total counters and the goodput account the planner reads.
    slo_ttft_ms: Optional[float] = None
    slo_tpot_ms: Optional[float] = None
    # Rolling window for the quantile-gauge snapshots (digest totals stay
    # cumulative for the aggregator's Prometheus histogram re-export).
    telemetry_window_s: float = 60.0
    # Stall watchdog: the step loop not completing a step for this long
    # while work is queued marks the engine stalled (unhealthy /health,
    # engine_stalled counter). Sized well past any legitimate cold compile.
    stall_after_s: float = 120.0
    # Tenant capacity ledger: SpaceSaving sketch size — per-tenant digests
    # and SLO counters exist only for the top-K set, so this bounds the
    # ledger's memory regardless of tenant cardinality.
    ledger_top_k: int = 16


@dataclass
class ForwardPassMetrics:
    """Worker load snapshot published to the router
    (ref: _core.pyi:354-427 ForwardPassMetrics{WorkerStats, KvStats})."""

    num_running: int = 0
    num_waiting: int = 0
    kv_usage: float = 0.0
    kv_total_blocks: int = 0
    kv_active_blocks: int = 0
    prefill_tokens_in_flight: int = 0
    request_total: int = 0
    # Speculative decoding acceptance accounting (SpecDecodeStats.to_dict(),
    # None when no draft model is attached) — ref: _core.pyi:354-427.
    spec_decode: Optional[dict] = None
    # Wide-EP capacity-dispatch pressure: (token, expert) assignments dropped
    # by capacity limits / total routed assignments (capacity MoE only).
    moe_dropped_total: int = 0
    moe_assignments_total: int = 0
    # Mixed-step composition: how many engine steps fused a prefill chunk
    # into the decode dispatch, and the token split they carried. The ratio
    # prefill_tokens/steps is the average chunk riding each decode step —
    # the saturation signal for mixed_prefill_budget tuning.
    mixed_steps_total: int = 0
    mixed_prefill_tokens_total: int = 0
    mixed_decode_tokens_total: int = 0
    # Automatic prefix caching: prompt tokens served from resident KV
    # instead of prefill compute, and the block-granular hit/miss/evict/
    # onboard account behind them. hit/(hit+miss) is the block hit rate;
    # onboard counts DRAM/disk-tier blocks copied back into HBM on a hit.
    cached_tokens_total: int = 0
    prefix_hit_blocks_total: int = 0
    prefix_miss_blocks_total: int = 0
    prefix_evicted_blocks_total: int = 0
    prefix_onboard_total: int = 0
    # Elastic capacity dial (set_capacity_dial): the live prefill:decode
    # split. fraction 0.5 = the configured budget/slots; the budget/slots
    # gauges carry the APPLIED values so the router's cost model and the
    # planner's ratio actuator see the dial, not just its setting.
    elastic_prefill_fraction: float = 0.5
    elastic_prefill_budget: int = 0
    elastic_decode_slots: int = 0
    elastic_dial_changes_total: int = 0

    def to_wire(self) -> dict:
        return self.__dict__.copy()


class Scheduler:
    """Owns the device cache + compiled steps + the running/waiting sets.

    Synchronous core (stepped from a thread by TpuEngine); asyncio-facing
    methods only touch queues/events.
    """

    def __init__(
        self,
        model_config: ModelConfig,
        params,
        scheduler_config: Optional[SchedulerConfig] = None,
        *,
        dtype=jnp.bfloat16,
        on_kv_event: Optional[Callable[[KvEvent], None]] = None,
        eos_token_ids: Optional[List[int]] = None,
        rng_seed: int = 0,
        mesh=None,
        parallel=None,
        step_log: Optional[StepLog] = None,
    ):
        from dynamo_tpu.engine.config import resolve_moe_dispatch

        self.mc = model_config
        if model_config.is_eva and mesh is not None:
            self._refuse_eva("sharded serving (a mesh)")
        if mesh is not None:
            model_config.refuse_for_layer_types("sharded serving (a mesh)")
        ep = parallel.ep if parallel is not None else (mesh.shape.get("ep", 1) if mesh else 1)
        model_config = resolve_moe_dispatch(model_config, ep)
        self.mc = model_config
        self.sc = scheduler_config or SchedulerConfig()
        self.mesh = mesh
        self.parallel = parallel
        # attention_kind "eva": a position and its cache row differ
        # (kv_cache.cache_rows), completed windows are rolled into summaries
        # (_roll_windows), and what equates the two is refused (_refuse_eva).
        self._eva = model_config.is_eva
        # layer_types: every running sequence holds one slot of recurrent state
        # beside its blocks (self.slots, _open_slot / _drop_slot); what equates
        # a sequence with its block table alone is refused (_refuse_unbuilt).
        self._hybrid = model_config.is_hybrid
        if (self._eva or self._hybrid) and self.sc.enable_prefix_caching:
            logger.info("model %r: prefix-block reuse is not built for it, prefix caching is off", model_config.name)
            self.sc.enable_prefix_caching = False
        # What a slot holds names its gauges and step-log counts: recurrent state
        # ("ssm"), for a stack of cca layers the convolutions' columns ("cca"), for
        # a stack of latent layers the window layers' rings ("window").
        self._slot_kind = "window" if model_config.is_latent else "cca" if model_config.num_cca_layers else "ssm"
        self.moe_skipped_rows_total = 0  # rows x layers that drew the ZAYA router's skip choice
        self.slots: Optional[SlotAllocator] = None
        if self._hybrid:
            # One slot a running sequence and the scratch slot: a sequence is
            # admitted only while len(running) < max_running, so no more are held.
            self.slots = SlotAllocator(self.sc.max_running + 1)
        self.ssm_preempt_recomputes_total = 0
        self.allocator = BlockAllocator(self.sc.num_blocks, on_event=on_kv_event)
        # Reserve block 0 as the scratch sink for padded scatter positions.
        self.allocator._free.remove(0)
        if mesh is not None:
            # Sharded serving: place params + cache with the real partition
            # specs; GSPMD propagates shardings through the jitted steps and
            # inserts the tp all-reduces / dp batch splits over ICI.
            from jax.sharding import NamedSharding

            from dynamo_tpu.engine.sharding import kv_cache_spec, shard_params

            tp = parallel.tp if parallel is not None else mesh.shape.get("tp", 1)
            params = shard_params(params, mesh, model_config.tie_word_embeddings, model_config.num_experts)
            cache_sharding = NamedSharding(mesh, kv_cache_spec(model_config.num_kv_heads, tp))
            self.cache = KvCacheArrays.create(model_config, self.sc.num_blocks, dtype=dtype, sharding=cache_sharding)
        else:
            self.cache = KvCacheArrays.create(
                model_config, self.sc.num_blocks, dtype=dtype, num_slots=self.slots.num_slots if self.slots else 0
            )
        self.params = params
        # Widest table a sequence can hold: every position's row, or for eva the
        # summaries of every completed window and one whole window.
        most_rows = model_config.max_seq_len
        if self._eva:
            W = model_config.window_size
            last_window = (most_rows - 1) // W * W  # first position of the last window a sequence can open
            most_rows = cache_rows(model_config, last_window) + min(W, most_rows)
        self.max_blocks_per_seq = (most_rows + model_config.block_size - 1) // model_config.block_size

        # Optional tiered block manager (KVBM) — set via attach_kvbm().
        self.kvbm = None
        # Finished prefill-role sequences awaiting KV export (disagg).
        self._pending_exports: Dict[str, Sequence] = {}
        self._export_deadline: Dict[str, float] = {}
        self.waiting: List[Sequence] = []
        self.running: List[Sequence] = []
        self.by_id: Dict[str, Sequence] = {}
        self.request_total = 0
        self.preempt_total = 0
        # Deadline eviction: requests whose deadline_ms budget lapsed before
        # they finished (finish_reason "timeout", KV freed at eviction).
        self.timeouts_total = 0
        self._has_deadlines = False  # skip the per-step sweep until one arrives
        # Online prefill-rate estimate (tokens/s) for ITL-budgeted chunking.
        self._prefill_tok_s: Optional[float] = None
        self._eos = eos_token_ids or []
        self._rng = jax.random.PRNGKey(rng_seed)
        self._rng_words = tuple(int(w) for w in np.asarray(self._rng))  # the engine's key on the host, for fold_key
        self._step_counter = 0
        # Host-to-device transfers of the iteration in progress (_up), and
        # who sampled each dispatch so far (_note_sampled).
        self._uploads = 0
        self._built0 = (0, 0)  # the build log's counts when the iteration in progress began (step)
        self.sampled_in_program_total = 0
        self.sampled_on_host_total = 0
        # What the decode rows' attention launches walked, and what their
        # bucket and table width spanned (_note_step).
        self.attn_items_total = 0
        self.attn_pages_total = 0
        self.attn_slots_total = 0
        self._rows_step_pages: Dict[int, int] = {}
        # SLA telemetry: mergeable latency digests (ttft/tpot/itl/queue_wait
        # + per-phase step durations via the flight recorder) and the SLO
        # judge behind the goodput account. All host-side — no dispatches.
        self.telemetry = Telemetry(window_s=self.sc.telemetry_window_s)
        self.slo = SloJudge(SloConfig(ttft_ms=self.sc.slo_ttft_ms, tpot_ms=self.sc.slo_tpot_ms))
        # Tenant capacity ledger: per-request bills (queue/device/KV-hold
        # time, FLOPs, tokens) roll into bounded top-K heavy-hitter
        # sketches + per-tenant SLO telemetry (runtime/ledger.py).
        self.ledger = TenantLedger(
            top_k=self.sc.ledger_top_k,
            slo=SloConfig(ttft_ms=self.sc.slo_ttft_ms, tpot_ms=self.sc.slo_tpot_ms),
            window_s=self.sc.telemetry_window_s,
        )
        # Flight recorder: per-phase step histograms + XLA compile tracker
        # (every dispatch registers its shape key; keys first seen after
        # warmup are counted/logged). Tracer: per-request lifecycle events
        # for sequences whose trace is sampled.
        self.flight = FlightRecorder(telemetry=self.telemetry, log=step_log)
        self.tracer = get_tracer()
        # Per-step FLOPs+bytes roofline model from the REAL params/cache
        # byte widths (int8 weights/KV are modeled as stored): BENCH
        # roofline numbers become the live mfu_*/hbm_frac_* gauges.
        p_leaves = jax.tree_util.tree_leaves(params)
        param_count = sum(int(x.size) for x in p_leaves)
        param_bytes = sum(int(x.size) * x.dtype.itemsize for x in p_leaves)
        # (The paged pool alone: a hybrid model's slot arrays ride beside it and are counted apart.)
        kv_leaves = jax.tree_util.tree_leaves((pool_of(self.cache.k), pool_of(self.cache.v)))
        kv_bytes = sum(int(x.size) * x.dtype.itemsize for x in kv_leaves)
        kv_per_token = kv_bytes / max(self.sc.num_blocks * model_config.block_size, 1)
        # KV-read traffic factor per attention path: the XLA gather's
        # read + packed-copy write + attend re-read moves 3× the true
        # prefix bytes; the paged Pallas paths (r5 kernel, megakernel)
        # stream each page once. Without this the hbm_frac_decode gauge
        # can't reflect the megakernel's actual roofline position.
        # The model module as this engine traces it: under a mesh its calls
        # see it (sharding.bind_mesh), so the Pallas attention kernels
        # partition over tp instead of being refused by the compiler.
        from dynamo_tpu.engine.models import get_module
        from dynamo_tpu.engine.sharding import bind_mesh

        self._model = model = bind_mesh(get_module(model_config), mesh)
        self._attn_impl = "gather"
        self._chunk_attn_paths: Dict[int, str] = {}  # chunk bucket -> llama.chunk_attn_path, for the step log
        if model_config.architecture == "llama":
            model.warn_attention_impl_degrade(model_config, pool_of(self.cache.k))
            self._attn_impl = model.resolve_attention_impl(model_config, pool_of(self.cache.k))
        kv_read_factor = 1.0 if self._attn_impl in ("paged", "megakernel") else 3.0
        self.flight.set_cost_model(
            StepCostModel(param_count, param_bytes, kv_per_token,
                          kv_read_factor=kv_read_factor)
        )
        # Read by benchmark/run.py and chip_smoke.py (their "serve" reports).
        self._param_bytes = param_bytes
        self._kv_cache_bytes = kv_bytes

        # Trim buckets to the model's max length.
        self.sc.prefill_buckets = [b for b in self.sc.prefill_buckets if b <= model_config.max_seq_len] or [
            model_config.max_seq_len
        ]

        # Prefill impl: flash = Pallas kernel chunk attention (auto ⇒ TPU
        # only; the interpreted kernel is far too slow for CPU serving).
        self._use_flash_prefill = (
            model_config.architecture == "llama"
            and model.resolve_prefill_impl(model_config) == "flash"
        )
        # ``has_prefix`` is a STATIC argument of the prefill and mixed-step
        # programs only where it changes them: on the flash path's own
        # chunk attention, which skips the prefix piece of a fresh chunk.
        # That path is taken only where no kernel serves the pool
        # (llama.chunk_walks_tiles): under "megakernel" and "paged" a chunk
        # walks tiles, the step programs never read it, and a static
        # argument would key two byte-identical executables — the second one
        # re-traced, lowered and fetched from the persistent cache in the
        # middle of traffic (0.05-0.6 s of a stalled step thread per key,
        # PERF.md §6 PR 26).
        self._hp_static = self._use_flash_prefill and not model.chunk_walks_tiles(model_config, pool_of(self.cache.k))
        # Capacity-dispatch MoE exports drop counters (wide-EP observability;
        # ref: SURVEY.md §2e / trtllm_utils.py:37-39 wide-EP surface).
        self._moe_stats = (
            model_config.architecture == "llama"
            and model_config.num_experts > 0
            and model_config.moe_dispatch == "capacity"
        )
        self._moe_dropped_total = 0  # guarded-by: _aux_lock
        self._moe_assignments_total = 0  # guarded-by: _aux_lock
        # Elastic capacity dial (set_capacity_dial): bases capture the
        # CONFIGURED split — the dial scales mixed_prefill_budget and the
        # admission slot cap around them, fraction 0.5 = identity. Written
        # from the event loop (control op / planner actuator) while the
        # step thread reads the live sc knobs and the stats scrape reads
        # the gauges, so the grouped update rides _aux_lock.
        self._base_mixed_prefill_budget = self.sc.mixed_prefill_budget or self.sc.max_prefill_chunk
        self._base_max_running = self.sc.max_running
        self._elastic_fraction = 0.5  # guarded-by: _aux_lock
        self.elastic_dial_changes_total = 0  # guarded-by: _aux_lock
        self._pending_aux: list = []
        # layer_types: the step programs' last result, the expert layer's counts
        # of the dispatch (device scalars until _note_aux reads them back).
        self._step_aux = None
        # _drain_aux runs on the step thread (overflow drain in
        # _consume_aux) AND the event loop (metrics()/moe_* properties via
        # the stats scrape): the swap-and-accumulate must not interleave.
        self._aux_lock = threading.Lock()
        # llama-only kwargs (MLA's forward has its own signature).
        stats_kw = {"moe_stats": True} if self._moe_stats else {}
        # The program store (engine/program_store.py), or None where the step
        # programs take today's path (the persistent cache off, a mesh): what
        # every program of this engine reads beside its arguments and its key.
        self._store = open_store(
            repr((self.mc, self.sc, jnp.dtype(dtype).name, self._attn_impl, self._use_flash_prefill, self._hp_static,
                  os.environ.get("DYNAMO_TPU_HOIST_GATHER_MAX_BYTES"))),
            mesh,
        )
        # Every step program is a NAMED function: the profiler's "XLA
        # Modules" line then reads jit_<kind>(...) (the flight recorder's
        # kind, with the window rung where there is one), so a program's
        # device time is found by name, without host marks.
        #
        # A dispatch is one upload, one program, one read-back: the small
        # operands ride ONE packed int32 vector (pack_operands) that the
        # program splits, and the program takes every row's argmax beside the
        # logits it returns, which stay on the device unless a row needs the
        # host between its logits and its token (_needs_host): then the host
        # path samples from them and the program's tokens are left unread.
        def prefill_body(p, k, v, buf, bt, **kw):  # buf: [chunk tokens | its length, its start]
            res = model.prefill(p, self.mc, k, v, buf[:-2], buf[-2], buf[-1], bt, **kw, **stats_kw)
            logits = res[0][None]  # [1, V]: the row the samplers take
            return (_greedy(logits), logits) + tuple(res[1:])

        if self._hp_static:

            def prefill(p, k, v, buf, bt, hp):
                return prefill_body(p, k, v, buf, bt, use_flash=True, has_prefix=hp)

            self._prefill_jit = self._jit(prefill, donate_argnums=(1, 2), static_argnums=(5,))
        else:
            # No ``hp`` here: the XLA path's masks and the tile walk's ragged
            # rows cover prefix and fresh prefills alike (a static argument
            # would compile two byte-identical executables per bucket, a
            # traced one would be an upload of its own).
            def prefill(p, k, v, buf, bt):
                return prefill_body(p, k, v, buf, bt)

            self._prefill_jit = self._jit(prefill, donate_argnums=(1, 2))

        def decode(p, k, v, buf, bt):  # buf: the rows' 3 x B lanes
            t, pos, act = _unpack_rows(buf.reshape(3, -1))
            res = model.decode(p, self.mc, k, v, t, pos, bt, act, **stats_kw)
            return (_greedy(res[0]),) + tuple(res)

        self._decode_jit = self._jit(decode, donate_argnums=(1, 2))
        self._sample_jit = self._jit(sample_batch)
        self._row_keys_jit = self._jit(make_row_keys)
        # Logprobs folded into the sampling dispatch (one executable, one
        # readback) — the separate compute_logprobs op cost an extra device
        # round-trip per step for any batch with a logprobs row.
        from dynamo_tpu.engine.sampling import (
            guided_sample_batch_logprobs,
            guided_sample_batch_top_logprobs,
            sample_batch_logprobs,
            sample_batch_top_logprobs,
        )

        self._sample_lp_jit = self._jit(sample_batch_logprobs)
        # The host path's first-token logprobs, warmed like the samplers
        # (nothing eager on the step thread).
        from dynamo_tpu.engine.sampling import compute_logprobs, compute_topk_logprobs

        self._lp_jit = self._jit(compute_logprobs)
        self._tlp_jit = self._jit(compute_topk_logprobs)
        self._guided_sample_lp_jit = self._jit(guided_sample_batch_logprobs)
        # Top-k variants (OpenAI top_logprobs): chosen logprob + the static
        # candidate cap's (ids, logprobs) in the same dispatch.
        self._sample_tlp_jit = self._jit(sample_batch_top_logprobs)
        self._guided_sample_tlp_jit = self._jit(guided_sample_batch_top_logprobs)
        # The last decode block-table upload (_decode_tables): tables cross
        # the wire only when a table actually changes.
        self._tables_cache: Optional[tuple] = None
        # eva: the summarising program of a roll (one window of one sequence),
        # and what the rolls have done so far.
        self.eva_rolls_total = 0
        self.eva_released_blocks_total = 0
        if self._hybrid:
            self._open_slot_jit = self._jit(model.open_slot, donate_argnums=(0, 1))
        if self._eva:

            def eva_roll(p, k, v, table, row0):
                return model.eva_roll(p, self.mc, k, v, table, row0)

            self._roll_jit = self._jit(eva_roll, donate_argnums=(1, 2))
            self._roll_blocks = model.eva_roll_blocks(model_config)
        # Step-phase spans (runtime/tracing.py): the iteration in progress
        # and its open plan phase, which crosses from step() into whichever
        # dispatch path forms the batch.
        self._step_span: Optional[StepSpan] = None
        self._plan_span: Optional[StepSpan] = None
        # Prefix-cache copy-on-write: duplicate one block's contents into a
        # private block (full-cover hits recompute only the LAST prompt
        # token, whose KV write would otherwise land in a block other
        # sequences still reference). Donated in-place scatter, one
        # executable for every (src, dst) pair; warmed against scratch.
        from dynamo_tpu.engine.kv_cache import QuantKv

        def _copy_block_arr(c, src, dst):
            if isinstance(c, QuantKv):
                return QuantKv(c.q.at[:, dst].set(c.q[:, src]), c.scale.at[:, dst].set(c.scale[:, src]))
            return c.at[:, dst].set(c[:, src])

        def kv_block_copy(k, v, s, d):
            return _copy_block_arr(k, s, d), _copy_block_arr(v, s, d)

        self._kv_copy_jit = self._jit(kv_block_copy, donate_argnums=(0, 1))
        # Prefix-cache accounting: reuse is only "automatic" if it is
        # visible — cached_tokens flows request-level (StepOutput → usage)
        # and these totals flow through stats → aggregator → Grafana.
        self.cached_tokens_total = 0
        self.cow_blocks_total = 0
        self.prefix_onboard_total = 0
        # First-token latency decomposition (bench http-sweep breakdown):
        # queue (arrival→admission) and prefill (admission→first token)
        # sums over finished first tokens.
        self.queue_wait_s_total = 0.0
        self.prefill_wait_s_total = 0.0
        self.first_tokens_total = 0
        # Guided decoding (attach_guided): grammar compiler + device mask
        # pool. One fused mask+sample executable serves every guided batch.
        self.guided = None
        self._guided_sample_jit = self._jit(guided_sample_batch)
        self.dtype = dtype
        self._mm_jit = None  # lazy: multimodal prefill variant
        # Speculative decoding (attach_draft): draft model + stats.
        self.draft_params = None
        self.draft_cfg = None
        self.draft_cache = None
        self.spec_gamma = 0
        self.spec_stats = None
        self._supports_multi_step = hasattr(model, "decode_multi")
        # Batched admission (chunk_decode waves) — llama-family only, and not
        # eva (a wave's chunk may straddle a window boundary).
        self._supports_chunk_admit = hasattr(model, "chunk_decode") and not self._eva and not self._hybrid
        self._admit_jits: Dict = {}
        # Mixed prefill+decode steps (llama.mixed_step) — llama-family only.
        self._supports_mixed = hasattr(model, "mixed_step")
        self._mixed_jits: Dict = {}
        self.mixed_steps_total = 0
        self.mixed_prefill_tokens_total = 0
        self.mixed_decode_tokens_total = 0
        if self._supports_multi_step:
            # One executable per window rung: short requests must not pay a
            # full num_scheduler_steps window (a 16-token request under a
            # 32-step window wastes half the dispatch). _decode_multi picks
            # the smallest rung covering the batch's remaining budget.
            def mk_multi(steps: int):
                def decode_multi(p, k, v, buf, bt):  # buf: [the rows' 6 x B lanes | the window's key]
                    rows = buf[:-2].reshape(6, -1)
                    t, pos, act = _unpack_rows(rows)
                    return model.decode_multi(
                        p, self.mc, k, v, t, pos, bt, act, _f32(rows[3]), rows[4], _f32(rows[5]),
                        jax.lax.bitcast_convert_type(buf[-2:], jnp.uint32), steps, **stats_kw,
                    )

                decode_multi.__name__ = f"decode_multi_w{steps}"
                return self._jit(decode_multi, donate_argnums=(1, 2))  # (the rung is in its name)

            self._window_rungs = sorted(
                {w for w in (8, 16, self.sc.num_scheduler_steps) if w <= self.sc.num_scheduler_steps}
            )
            self._decode_multi_jits = {w: mk_multi(w) for w in self._window_rungs}

    def attach_draft(self, draft_config: ModelConfig, draft_params, *, gamma: int = 4) -> None:
        """Enable batched speculative decoding: the draft model proposes γ
        tokens per round and the target verifies them in one chunk pass
        (llama.chunk_decode). The draft's paged cache mirrors the target's
        block tables, so allocation/preemption/prefix logic is shared.
        Ref: the reference surfaces engine speculation via SpecDecodeStats
        (_core.pyi:354-427); here the machinery is native."""
        from dynamo_tpu.engine.spec_decode import SpecDecodeStats

        if self._eva or draft_config.is_eva:
            self._refuse_eva("speculative decoding")
        self.mc.refuse_for_layer_types("speculative decoding (verification and rollback)")
        draft_config.refuse_for_layer_types("speculative decoding (verification and rollback)")
        if draft_config.block_size != self.mc.block_size:
            raise ValueError("draft and target must share block_size")
        if draft_config.vocab_size != self.mc.vocab_size:
            raise ValueError("draft and target must share the vocabulary")
        if draft_config.architecture != "llama" or self.mc.architecture != "llama":
            raise ValueError("spec decode needs llama-family draft AND target for now")
        if self.mesh is not None:
            # Sharded serving: the draft rides the target's mesh — same
            # partition specs, so GSPMD propagates the tp all-reduces / dp
            # splits through the draft's jitted steps too.
            from jax.sharding import NamedSharding

            from dynamo_tpu.engine.sharding import kv_cache_spec, shard_params

            tp = self.parallel.tp if self.parallel is not None else self.mesh.shape.get("tp", 1)
            if tp > 1 and draft_config.num_kv_heads % tp:
                raise ValueError(
                    f"draft kv_heads {draft_config.num_kv_heads} not divisible by tp={tp}"
                )
            draft_params = shard_params(
                draft_params, self.mesh, draft_config.tie_word_embeddings, draft_config.num_experts
            )
            d_sharding = NamedSharding(self.mesh, kv_cache_spec(draft_config.num_kv_heads, tp))
            self.draft_cache = KvCacheArrays.create(
                draft_config, self.sc.num_blocks, dtype=self.dtype, sharding=d_sharding
            )
        else:
            self.draft_cache = KvCacheArrays.create(draft_config, self.sc.num_blocks, dtype=self.dtype)
        self.draft_cfg = draft_config
        self.draft_params = draft_params
        self.spec_gamma = gamma
        self.spec_stats = SpecDecodeStats()
        dc = draft_config
        model = self._model  # llama-family (checked above), under this engine's mesh
        def draft_prefill(p, k, v, t, vl, cl, bt):
            return model.prefill(p, dc, k, v, t, vl, cl, bt)

        self._d_prefill_jit = self._jit(draft_prefill, donate_argnums=(1, 2), closure=dc)

        def spec_draft_chunk(p, k, v, t, pos, val, bt, te, tk, tp, key):
            # Draft catch-up chunk + FIRST proposal sampled from the row's
            # last valid position with its own sampling params (greedy rows
            # reduce to argmax). Returns the dist too — spec_verify needs it.
            lg, k, v = model.chunk_decode(p, dc, k, v, t, pos, val, bt, all_logits=True)
            last = jnp.take_along_axis(
                lg, jnp.maximum(val - 1, 0)[:, None, None], axis=1
            )[:, 0]  # [B, V]
            tok = sample_batch(last, te, tk, tp, key)
            return tok.astype(jnp.int32), last, k, v

        self._d_chunk_sample_jit = self._jit(spec_draft_chunk, donate_argnums=(1, 2), closure=dc)
        t_stats_kw = {"moe_stats": True} if self._moe_stats else {}

        def spec_target_chunk(p, k, v, t, pos, val, bt):
            return model.chunk_decode(
                p, self.mc, k, v, t, pos, val, bt, all_logits=True, **t_stats_kw
            )

        self._t_chunk_jit = self._jit(spec_target_chunk, donate_argnums=(1, 2))
        from dynamo_tpu.engine.spec_decode import spec_verify

        self._spec_verify_jit = self._jit(spec_verify)
        if gamma > 1:
            # On-device window for proposals 2..γ: one dispatch + one sync
            # instead of γ-1 round-trips; samples with the rows' REAL
            # params and returns per-step logits for rejection sampling.
            def spec_draft_multi(p, k, v, t, pos, bt, act, te, tk, tp, key):
                return model.decode_multi(
                    p, dc, k, v, t, pos, bt, act, te, tk, tp, key, gamma - 1,
                    return_logits=True,
                )

            spec_draft_multi.__name__ = f"spec_draft_multi_w{gamma - 1}"
            self._d_multi_jit = self._jit(spec_draft_multi, donate_argnums=(1, 2), closure=(dc, gamma))

    def attach_guided(self, tokenizer) -> None:
        """Enable grammar-constrained decoding: grammars lift to token FSMs
        against this tokenizer's vocabulary (llm/guided). Attach BEFORE
        warmup() so the masked-sampling executables precompile at the
        initial pool bucket."""
        from dynamo_tpu.llm.guided.processor import GuidedDecoder

        self.guided = GuidedDecoder(
            tokenizer,
            eos_ids=self._eos,
            vocab_size=self.mc.vocab_size,
            pool_rows=self.sc.guided_pool_rows,
        )

    # --- public API (called from event loop) --------------------------------
    def add_request(
        self,
        request_id: str,
        token_ids: List[int],
        sampling: SamplingParams,
        stop: StopConditions,
        *,
        keep_blocks_on_finish: bool = False,
        prefilled: Optional[dict] = None,
        mm_features: Optional[np.ndarray] = None,
        trace: Optional[tuple] = None,
        guided: Optional[dict] = None,
        tenant: str = "anon",
        enqueued_ts: Optional[float] = None,
    ) -> Sequence:
        if not token_ids:
            raise ValueError("empty prompt")
        if guided is not None and self.guided is None:
            raise ValueError(
                "guided decoding requested but no tokenizer is attached "
                "(Scheduler.attach_guided / EngineArgs.tokenizer)"
            )
        if len(token_ids) >= self.mc.max_seq_len:
            raise ValueError(f"prompt length {len(token_ids)} >= max_seq_len {self.mc.max_seq_len}")
        if keep_blocks_on_finish or prefilled is not None:
            self._refuse_unbuilt("KV export and injection (disaggregated prefill)")
        if mm_features is not None:
            self._refuse_unbuilt("multimodal feature rows")
        if mm_features is not None:
            if self.mc.architecture != "llama":
                raise ValueError("multimodal features require the llama prefill path")
            if mm_features.shape[0] > len(token_ids):
                raise ValueError("more multimodal feature rows than prompt tokens")
        seq = Sequence(
            request_id=request_id,
            prompt=list(token_ids),
            sampling=sampling,
            stop=stop,
            eos_token_ids=self._eos,
            keep_blocks_on_finish=keep_blocks_on_finish,
            prefilled=prefilled,
            mm_features=mm_features,
            trace=trace,
            tenant=tenant or "anon",
            enqueued_ts=enqueued_ts,
        )
        if guided is not None:
            seq.guided = self.guided.open(guided)  # ValueError on a bad spec
        if stop.deadline_ms is not None:
            seq.deadline_ts = seq.arrival_ts + stop.deadline_ms / 1000.0
            self._has_deadlines = True
        self.waiting.append(seq)
        self.by_id[request_id] = seq
        self.request_total += 1
        self._trace_event(seq, "queued", prompt_tokens=len(token_ids))
        if seq.guided is not None:
            self._trace_event(
                seq, "guided_mask",
                states=seq.guided.fsm.num_states,
                compile_s=round(seq.guided.fsm.compile_s, 6),
                cached=seq.guided.from_cache,
            )
        return seq

    def abort(self, request_id: str) -> None:
        seq = self.by_id.get(request_id)
        if seq is not None and seq.state != SeqState.FINISHED:
            seq.aborted = True

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def moe_dropped_total(self) -> int:
        """Capacity-MoE drop counter, drained-on-read: jitted steps stage
        their aux scalars in ``_pending_aux`` (forcing them per step would
        add a host sync — see _consume_aux), so a direct read must drain
        first or it sees counters up to 256 steps stale."""
        self._drain_aux()
        return self._moe_dropped_total

    @property
    def moe_assignments_total(self) -> int:
        self._drain_aux()
        return self._moe_assignments_total

    def metrics(self) -> ForwardPassMetrics:
        a = self.allocator
        self._drain_aux()
        return ForwardPassMetrics(
            num_running=len(self.running),
            num_waiting=len(self.waiting),
            kv_usage=a.usage(),
            kv_total_blocks=a.num_blocks,
            kv_active_blocks=a.num_active,
            prefill_tokens_in_flight=sum(len(s.prompt) - s.num_computed for s in self.waiting),
            request_total=self.request_total,
            spec_decode=self.spec_stats.to_dict() if self.spec_stats else None,
            moe_dropped_total=self._moe_dropped_total,
            moe_assignments_total=self._moe_assignments_total,
            mixed_steps_total=self.mixed_steps_total,
            mixed_prefill_tokens_total=self.mixed_prefill_tokens_total,
            mixed_decode_tokens_total=self.mixed_decode_tokens_total,
            cached_tokens_total=self.cached_tokens_total,
            prefix_hit_blocks_total=a.hit_blocks_total,
            prefix_miss_blocks_total=a.miss_blocks_total,
            prefix_evicted_blocks_total=a.evicted_blocks_total,
            prefix_onboard_total=self.prefix_onboard_total,
            elastic_prefill_fraction=self._elastic_fraction,
            elastic_prefill_budget=self.sc.mixed_prefill_budget or 0,
            elastic_decode_slots=self.sc.max_running,
            elastic_dial_changes_total=self.elastic_dial_changes_total,
        )

    def kv_gauges(self) -> dict:
        """Block-pool utilization for the stats scrape: free/cached depth,
        internal fragmentation (allocated-but-unwritten slots across live
        sequences — the padding cost of block-granular allocation), and the
        prefix-cache hit rate."""
        a = self.allocator
        bs = self.mc.block_size
        allocated = 0
        used = 0
        for s in list(self.running) + list(self.waiting):
            nb = len(s.block_ids)
            if not nb:
                continue
            allocated += nb * bs
            used += min(self._rows_for(s, s.total_len), nb * bs)
        hits, misses = a.hit_blocks_total, a.miss_blocks_total
        out = {
            "kv_free_blocks": len(a._free),
            "kv_cached_blocks": a.num_cached,
            "kv_fragmentation": round(1.0 - used / allocated, 6) if allocated else 0.0,
            "prefix_hit_rate": round(hits / (hits + misses), 6) if (hits + misses) else 0.0,
        }
        if self._hybrid:
            kind = self._slot_kind
            out.update({
                f"{kind}_slots_total": self.slots.num_slots - 1,
                f"{kind}_slots_in_use": self.slots.in_use,
                f"{kind}_slot_allocs_total": self.slots.allocs_total,
                f"{kind}_preempt_recomputes_total": self.ssm_preempt_recomputes_total,
            })
            if self.mc.moe_skip_choice:
                out["moe_skipped_rows_total"] = self.moe_skipped_rows_total
        if self._eva:
            # Blocks that hold summaries of rolled windows (the last of them
            # may also hold the current window's first rows), the rest of the
            # live tables, and what the rolls gave back.
            live = [s for s in list(self.running) + list(self.waiting) if s.block_ids]
            summary = sum(-(-self.mc.summaries_per_window * s.rolls // bs) for s in live)
            out.update(
                eva_rolls_total=self.eva_rolls_total,
                eva_released_blocks_total=self.eva_released_blocks_total,
                eva_summary_blocks=summary,
                eva_window_blocks=sum(len(s.block_ids) for s in live) - summary,
            )
        return out

    def debug_state(self) -> dict:
        """Live introspection snapshot for /debug/state: every sequence with
        its age/progress, the block pool, digest percentiles, and the recent
        step timeline. Read from the event loop while the step thread
        mutates — last-write-wins races are fine for a debug dump."""
        now = time.monotonic()

        def seq_info(s: Sequence) -> dict:
            return {
                "request_id": s.request_id,
                "state": s.state.value,
                "age_s": round(now - s.arrival_ts, 3),
                "prompt_tokens": len(s.prompt),
                "output_tokens": len(s.output_ids),
                "computed": s.num_computed,
                "cached_tokens": s.cached_tokens,
                "blocks": len(s.block_ids),
                "preemptions": s.preemptions,
                **({"rolls": s.rolls, "cache_rows": self._rows_for(s, s.total_len)} if self._eva else {}),
                **({"state_slot": s.state_slot} if self._hybrid else {}),
            }

        a = self.allocator
        f = self.flight
        return {
            "running": [seq_info(s) for s in list(self.running)],
            "waiting": [seq_info(s) for s in list(self.waiting)],
            "block_pool": {
                "total": a.num_blocks,
                "free": len(a._free),
                "cached": a.num_cached,
                "active": a.num_active,
                "usage": round(a.usage(), 6),
                **{k: v for k, v in self.kv_gauges().items() if k == "kv_fragmentation" or k.startswith(("eva_", "ssm_", "cca_", "window_", "moe_skipped_"))},
            },
            "digests": self.telemetry.summary(),
            "slo": self.slo.to_stats(),
            # Dispatches whose tokens the step program sampled, and those the host
            # path did (a row that needs the host between logits and token; a wave).
            "sampled_in_program_total": self.sampled_in_program_total,
            "sampled_on_host_total": self.sampled_on_host_total,
            # Steps the decode rows' attention launches took a layer (one a group of
            # pages_per_step live pages), the pages under them (pages / items: what a step
            # carried), and the bucket x (table width + 1) they would span.
            "attn_items_total": self.attn_items_total,
            "attn_pages_total": self.attn_pages_total,
            "attn_slots_total": self.attn_slots_total,
            # What JAX built for this engine (engine/compile_cache.py): seconds by
            # phase, by kind, the costliest keys, eager executables, entries since warm-up.
            "build": f.builds.summary(f.since_ns),
            "flight": {
                "last_step_phase": f.last_step_phase,
                "last_step_s": round(f.last_step_s, 6),
                "last_step_age_s": (
                    round(now - f.last_step_ts, 3) if f.last_step_ts is not None else None
                ),
                "compiles_total": f.compiles_total,
                "compiles_after_warmup_total": f.compiles_after_warmup_total,
                "post_warmup_keys": [str(k) for k in f.post_warmup_keys[-8:]],
                "recent_steps": f.recent_steps(),
                "utilization": {
                    ph: {"mfu": round(m, 6), "hbm_frac": round(h, 6)}
                    for ph, (m, h) in f.utilization().items()
                },
            },
        }

    def config_snapshot(self) -> dict:
        """Deployment configuration for incident bundles: the scheduler
        knobs and the model/attention identity that reproduce the serving
        behavior under diagnosis (a bundle without its config is a mystery
        six months later)."""
        return {
            "scheduler": {
                k: v for k, v in vars(self.sc).items() if not k.startswith("_")
            },
            "model": {
                "name": self.mc.name,
                "architecture": self.mc.architecture,
                "attention_kind": self.mc.attention_kind,
                "max_seq_len": self.mc.max_seq_len,
                "block_size": self.mc.block_size,
                "kv_cache_dtype": getattr(self.mc, "kv_cache_dtype", None),
                "weight_dtype": getattr(self.mc, "weight_dtype", None),
                "attention_impl": self._attn_impl,
            },
            "parallel": str(self.parallel) if self.parallel is not None else None,
        }

    # --- elastic capacity dial ----------------------------------------------
    def set_capacity_dial(self, prefill_fraction: float) -> dict:
        """Live prefill:decode capacity split — the worker half of elastic
        prefill/decode (ROADMAP item 2; DynaServe arXiv:2504.09285 argues
        the same continuous-ratio pool). ``prefill_fraction`` ∈ [0, 1]:

        - 0.5 — the configured identity (mixed_prefill_budget / max_running
          exactly as constructed);
        - → 1.0 — prefill-heavy: the mixed-step chunk budget scales up to
          2× (clamped to max_prefill_chunk) while decode admission slots
          shrink toward 1;
        - → 0.0 — decode-heavy: admission slots stay at the configured cap
          while the chunk budget shrinks toward one block.

        Slots never exceed the configured max_running (the allocator and
        decode buckets are sized for it), and already-admitted rows past a
        shrunken cap drain naturally (_decode_step slices by decode bucket,
        not max_running). Thread-safe: called from the event loop (control
        op / planner actuator) while the step thread reads the knobs — the
        grouped update rides _aux_lock so a stats scrape never observes a
        half-applied dial. Returns the applied values."""
        f = min(1.0, max(0.0, float(prefill_fraction)))
        raw = int(round(2.0 * f * self._base_mixed_prefill_budget))
        budget = max(self.mc.block_size, min(raw, self.sc.max_prefill_chunk))
        slots = int(round(2.0 * (1.0 - f) * self._base_max_running))
        slots = max(1, min(self._base_max_running, slots))
        with self._aux_lock:
            self._elastic_fraction = f
            self.sc.mixed_prefill_budget = budget
            self.sc.max_running = slots
            self.elastic_dial_changes_total += 1
        logger.info(
            "capacity dial: prefill_fraction=%.3f → mixed_prefill_budget=%d decode_slots=%d",
            f, budget, slots,
        )
        return {
            "prefill_fraction": f,
            "mixed_prefill_budget": budget,
            "decode_slots": slots,
        }

    def _mixed_warm_buckets(self) -> List[int]:
        """Prefill-chunk buckets a mixed step can ride across the capacity
        dial's whole range: raw budgets span [block_size, min(2·base,
        max_prefill_chunk)] and chunks bucket UP (next_bucket), so warmup
        must cover every bucket between those bounds — a ratio shift must
        never compile mid-traffic (WARM001 / flight-recorder gate)."""
        eligible = [b for b in self.sc.prefill_buckets if b <= self.sc.max_prefill_chunk]
        if not eligible:
            eligible = [self.sc.prefill_buckets[0]]
        lo = next_bucket(max(self.mc.block_size, 1), eligible)
        hi = next_bucket(
            min(2 * self._base_mixed_prefill_budget, self.sc.max_prefill_chunk), eligible
        )
        return [b for b in eligible if lo <= b <= hi] or [eligible[0]]

    # --- step loop core (runs in worker thread) -----------------------------
    def step(self) -> List[tuple]:
        """One scheduler iteration. Returns [(seq, StepOutput), ...].

        With sequences decoding AND prefill work at the head of the queue,
        the iteration is a MIXED step: one dispatch carries the decode
        batch plus up to mixed_prefill_budget prefill tokens, so neither
        phase stalls the other. Otherwise the phase-separated order runs:
        decode first (ITL), then admit one prefill (TTFT).

        The iteration is one ``sched.step`` span whose phases (plan, upload,
        launch, sync, sample, emit, account — runtime/tracing.py) partition
        it: ``sched.plan`` opens here and each dispatch path closes it where
        its uploads begin, then reopens it after its accounting."""
        outputs: List[tuple] = []
        log = self.flight.log
        log.step += 1
        self._built0 = self.flight.builds.total, self.flight.builds.total_ns
        with log.span("sched.step") as span:
            self._step_span = span
            self._uploads = 0
            self._begin_plan()
            try:
                self._step(outputs)
            finally:
                self._end_plan()
                if self._uploads:
                    span.set(uploads=self._uploads)  # host-to-device transfers of the iteration (_up)
                if self.flight.builds.total != self._built0[0]:
                    self._note_built(span)
                self._step_span = None
        return outputs

    def _note_built(self, span: StepSpan) -> None:
        """JAX built executables during this iteration (engine/compile_cache.py
        has each with its key): how many, and their trace, lowering and
        backend seconds, on its ``sched.step`` entry."""
        total, total_ns = self._built0
        builds = self.flight.builds
        span.set(built=builds.total - total, build_s=(builds.total_ns - total_ns) / 1e9)

    def _step(self, outputs: List[tuple]) -> None:
        self._sweep_deadlines()
        self._reap_aborted(outputs)
        if self._eva:
            self._roll_windows()
        cand = self._mixed_candidate()
        if cand is not None and not self._wave_preferred() and self._mixed_step(cand, outputs):
            return
        if self.running:
            outputs.extend(self._decode_step())
        self._admit(outputs)

    # --- step-phase spans (runtime/tracing.py) -------------------------------
    def _span(self, name: str, **attrs) -> StepSpan:
        return self.flight.log.span(name, **attrs)

    def _begin_plan(self) -> None:
        self._plan_span = self.flight.log.span("sched.plan").begin()

    def _end_plan(self) -> None:
        if self._plan_span is not None:
            self._plan_span.end()
            self._plan_span = None

    def _note_step(self, kind: str, key: tuple, batch=(), prefill: int = 0, decode: int = 0) -> None:
        """What the iteration dispatched, on the open ``sched.step``: kind
        and shape key (as registered with ``flight.record_exec``), rows and
        context tokens of the batch, prefill and decode tokens. A second
        dispatch of one iteration (decode, then a prefill) only counts
        itself."""
        span = self._step_span
        if span is None:
            return
        attrs = span.attrs or {}
        if kind == "eva_roll":
            # A roll goes before the iteration's step program: it is counted
            # beside it, and the entry keeps that program's kind and shape.
            span.set(rolls=attrs.get("rolls", 0) + 1)
        elif "kind" not in attrs:
            span.set(kind=kind, key=key, rows=len(batch), ctx=sum(s.total_len for s in batch),
                     prefill=prefill, decode=decode)
            if self._eva:
                # Cache rows the batch's contexts hold (what a decode step
                # attends), beside their lengths in bytes under ``ctx``.
                span.set(attended=sum(self._rows_for(s, s.total_len) for s in batch))
            if self._hybrid:
                # Slots the dispatch advances (its decode rows' and its chunk's), and slots held.
                span.set(**{f"{self._slot_kind}_rows": len(batch) + (1 if kind == "mixed" else 0),
                            f"{self._slot_kind}_slots": self.slots.in_use})
                if self.mc.is_latent:
                    # Blocks live sequences hold: the full layers' rows alone, the rings hold none.
                    span.set(pool_blocks=self.sc.num_blocks - 1 - self.allocator.num_free)
            if kind in ("decode", "decode_multi", "mixed") and self._attn_impl == "megakernel":
                # The decode rows' attention launch (megakernel.build_work): the pages under
                # the rows' current tokens, the steps it takes a layer (one a group of
                # pages_per_step of a row's pages), beside its batch bucket x (table width + 1).
                bs, (bucket, width) = self.mc.block_size, key[-2:]
                per_step = self._rows_pages_per_step(width)
                held = [max(min(-(-self._rows_for(s, s.total_len - 1) // bs), width), 1) for s in batch]
                pages, items = sum(held), sum(-(-h // per_step) for h in held)
                span.set(attn_items=items, attn_pages=pages, attn_slots=bucket * (width + 1))
                self.attn_items_total += items
                self.attn_pages_total += pages
                self.attn_slots_total += bucket * (width + 1)
        else:
            span.set(dispatches=attrs.get("dispatches", 1) + 1)
        if kind in ("mixed", "prefill", "prefill_mm"):
            # The chunk's attention path, also where the chunk is the
            # iteration's second dispatch (a bare chunk after a window).
            span.set(chunk_attn=self._chunk_attn(key[0]))

    def _rows_pages_per_step(self, width: int) -> int:
        """Pages a step of the decode rows' attention launch takes under a
        table ``width`` slots wide, as this engine traced it
        (megakernel.pages_per_step over this engine's pool)."""
        if width not in self._rows_step_pages:  # once a table width, not once a dispatch
            self._rows_step_pages[width] = self._model.rows_pages_per_step(self.mc, pool_of(self.cache.k), width)
        return self._rows_step_pages[width]

    def _chunk_attn(self, bucket: int) -> str:
        """How a chunk of ``bucket`` queries meets its keys in ``prefill`` and
        ``mixed_step`` as this engine traced them: ``tile<TQ>`` (the ragged
        megakernel's walk by tiles of TQ queries, wherever a kernel serves
        the pool: llama.chunk_walks_tiles) or ``gather``."""
        if self._attn_impl == "gather":
            return "gather"
        if bucket not in self._chunk_attn_paths:  # 0.8 ms to work out: once a bucket, not once a dispatch
            self._chunk_attn_paths[bucket] = self._model.chunk_attn_path(self.mc, pool_of(self.cache.k), bucket, self.dtype)
        return self._chunk_attn_paths[bucket]

    # --- what a dispatch costs the host: uploads, the packed operands, who samples ---
    def _up(self, host_array) -> jax.Array:
        """One host-to-device transfer, counted (``uploads`` of the iteration's ``sched.step`` entry)."""
        self._uploads += 1
        return jax.device_put(host_array)

    def _pack_rows(self, batch: List[Sequence], bucket: int, sampler: bool = False) -> np.ndarray:
        """A decode batch's int32 lanes (``_unpack_rows`` in the program):
        token, write position, active; with ``sampler`` (a window samples in
        its program) also temperature, top-k, top-p, the float ones bit-cast.
        Pad lanes are inactive and greedy."""
        rows = np.zeros((6 if sampler else 3, bucket), dtype=np.int32)
        for i, seq in enumerate(batch):
            rows[0, i] = seq.all_ids[-1]
            rows[1, i] = seq.total_len - 1  # write slot of the current token
            rows[2, i] = 1
        if sampler:
            from dynamo_tpu.engine.sampling import pack_param_rows

            temps, top_ks, top_ps = pack_param_rows([s.sampling for s in batch], bucket)
            rows[3], rows[4], rows[5] = temps.view(np.int32), top_ks, top_ps.view(np.int32)
        return rows

    @staticmethod
    def _host_between_tokens(seq: Sequence) -> bool:
        """Does ``seq`` need the host between two of its tokens? Penalties
        (history mutates from token to token), logits processors, logprobs /
        top_logprobs, a guided row (the FSM advances on the host; proposal
        sampling ignores its mask) and a seeded sampled row (neither a spec
        round nor decode_multi threads per-row keys; a greedy row's seed is a
        no-op). One such row takes its whole batch to single steps."""
        s = seq.sampling
        return bool(
            s.logits_processors
            or s.logprobs
            or s.top_logprobs
            or s.has_penalties
            or seq.guided is not None
            or (s.seed is not None and s.temperature > 0)
        )

    @classmethod
    def _needs_host(cls, seq: Sequence) -> bool:
        """Does ``seq`` need the host between its logits and its token in a
        single step (``decode``, ``mixed``, ``prefill``)? What needs it
        between tokens, and every row that draws: the single-step programs
        take the argmax and hold no sampler (a window's program does, as
        before). One such row takes its whole dispatch to the host path: the
        program's tokens are left unread and the warmed samplers run on the
        logits it returned."""
        return seq.sampling.temperature > 0 or cls._host_between_tokens(seq)

    def _note_sampled(self, how: str) -> None:
        """Who sampled the dispatch just synced, onto the open ``sched.step``:
        ``"program"`` or ``"host"`` (which stays, where an iteration makes two
        dispatches and one of them is the host's)."""
        if how == "program":
            self.sampled_in_program_total += 1
        else:
            self.sampled_on_host_total += 1
        span = self._step_span
        if span is not None and (how == "host" or "sampled" not in (span.attrs or {})):
            span.set(sampled=how)

    def _launch(self, kind: str, decode: bool = False) -> StepSpan:
        """The ``sched.launch`` span of one program. A decode-family launch
        first books the decode host gap (see _record_host_gap). While the span
        is open it is the build log's scope on this thread: what JAX builds
        inside is of ``kind`` and of the key ``record_exec`` was just handed."""
        if decode:
            self._record_host_gap()
            span = self.flight.log.span("sched.launch", kind=kind, decode=True)
        else:
            span = self.flight.log.span("sched.launch", kind=kind)
        self.flight.builds.launching(span, self.flight.last_exec)
        return span

    def _jit(self, fun: Callable, closure=(), **jit_kw):
        """``jax.jit(fun, **jit_kw)``, its programs read from and written to
        the program store where this engine has one. ``closure``: what ``fun``
        closes over that neither its arguments nor the engine's configuration
        name (a mixed step's widths, the draft's configuration)."""
        if self._store is None:
            return jax.jit(fun, **jit_kw)
        return StoredJit(self._store, fun, closure=closure, **jit_kw)

    def _building(self, kind: Optional[str] = None, key: tuple = ()):
        """A ``build.key`` scope (engine/compile_cache.py): what JAX builds
        inside belongs to the executable key ``(kind, *key)``; with no
        argument, to the key ``record_exec`` was just handed (as a launch's
        does in serving)."""
        if kind is None:
            kind, key = self.flight.last_exec[0], self.flight.last_exec[1:]
        return self.flight.builds.scope(self.flight.log, "build.key", kind=kind, key=key)

    def _since(self, span: StepSpan) -> float:
        """Seconds since ``span`` began: a dispatch's timed part runs from
        the start of its ``sched.upload`` to the end of its ``sched.emit``
        (what the flight recorder, the bills and the itl digest are fed)."""
        return (time.monotonic_ns() - span.t0) / 1e9

    # --- attention_kind "eva": rows, rolls, refusals --------------------------
    def _refuse_eva(self, what: str) -> None:
        raise NotImplementedError(
            f"{what} is not built for attention_kind='eva' (model {self.mc.name!r}): "
            "its block tables hold summaries of rolled windows, not one row a token"
        )

    def _refuse_unbuilt(self, what: str) -> None:
        """``what`` equates a sequence with one row a token in its block table:
        not built where the table holds summaries (eva) or where a slot of
        recurrent state rides beside it (layer_types)."""
        if self._eva:
            self._refuse_eva(what)
        self.mc.refuse_for_layer_types(what)

    # --- layer_types: slots of recurrent state beside the block pool -----------
    def _open_slot(self, seq: Sequence) -> None:
        """``seq`` (its blocks just allocated) takes a slot: zeroed on the
        device in every state-space layer and tied to the table's first block,
        by which the step programs find it. OutOfBlocksError when none is free."""
        self._end_plan()
        try:
            with self._span("sched.slots", request_id=seq.request_id) as span:
                seq.state_slot = self.slots.allocate()
                span.set(slot=seq.state_slot)
                self.flight.record_exec("open_slot", ())
                self.cache.k, self.cache.v = self._open_slot_jit(
                    self.cache.k, self.cache.v, self._up(np.int32(seq.block_ids[0])), self._up(np.int32(seq.state_slot))
                )
        finally:
            self._begin_plan()

    def _drop_slot(self, seq: Sequence) -> None:
        """``seq`` gives its slot back (finish, abort, preemption): the next
        sequence to take it zeroes it."""
        if seq.state_slot:
            self.slots.release(seq.state_slot)
            seq.state_slot = 0

    def _read(self, tokens: jax.Array) -> np.ndarray:
        """The dispatch's one blocking read-back: what the program sampled
        and, for layer_types, the expert layer's counts of the same dispatch,
        which ride the same transfer onto the open ``sched.step``."""
        aux, self._step_aux = self._step_aux, None
        if aux is None or self._step_span is None:
            return jax.device_get(tokens)
        sampled, aux = jax.device_get((tokens, aux))
        self._count_aux(aux)
        return sampled

    def _count_aux(self, aux: dict) -> None:
        """The expert layer's counts of one dispatch (read back already), added onto the open ``sched.step``."""
        attrs = self._step_span.attrs or {}
        self._step_span.set(**{name: attrs.get(name, 0) + int(n) for name, n in aux.items()})
        self.moe_skipped_rows_total += int(aux.get("skipped_rows", 0))

    def _read_step(self, tokens: jax.Array) -> np.ndarray:
        """``_read`` of a single step's tokens, inside its ``sched.sample`` and ``sched.sync``."""
        with self._span("sched.sample"), self._span("sched.sync"):
            return self._read(tokens)

    def _note_aux(self) -> None:
        """The expert layer's counts of the dispatch just synced, onto the open
        ``sched.step``: read after the step's blocking read-back, so the two
        scalars are there already and the read waits for nothing."""
        aux, self._step_aux = self._step_aux, None
        if aux is not None and self._step_span is not None:
            self._count_aux(jax.device_get(aux))

    def _rows_for(self, seq: Sequence, n_tokens: int) -> int:
        """Table rows ``seq`` needs to hold its first ``n_tokens`` positions.
        Causal: one row a token. Eva: the summaries of its rolled windows and
        the positions of the current window, which ends at its boundary (what
        lies past it is written after the roll, over the rows it frees)."""
        if not self._eva:
            return n_tokens
        W = self.mc.window_size
        first = seq.rolls * W  # the current window's first position, at the row after the summaries
        return cache_rows(self.mc, first) + max(0, min(n_tokens - first, W))

    def _grow_table(self, seq: Sequence, n_tokens: int) -> None:
        """Blocks for ``seq``'s first ``n_tokens`` positions, or OutOfBlocksError."""
        bs = self.mc.block_size
        need = (self._rows_for(seq, n_tokens) + bs - 1) // bs - len(seq.block_ids)
        if need > 0:
            seq.block_ids.extend(self.allocator.allocate(need))

    def _window_room(self, position: int) -> int:
        """Positions from ``position`` to the end of its window: how far one
        dispatch may write (a prefill chunk is cut there, a decode window's
        row stops there), the roll coming before the next."""
        return self.mc.window_size - position % self.mc.window_size

    def _roll_windows(self) -> None:
        """Roll every live sequence whose next write opens a window while the
        one before is still exact rows: a phase of the iteration, before the
        batch is formed."""
        W = self.mc.window_size
        for seq in list(self.running) + [s for s in self.waiting if s.state == SeqState.PREFILL]:
            nxt = seq.total_len - 1 if seq.state == SeqState.RUNNING else seq.num_computed
            while seq.block_ids and nxt // W > seq.rolls:
                self._roll(seq)

    def _roll(self, seq: Sequence) -> None:
        """One roll: the summarising program over the completed window's
        blocks, in place (the summaries land on the window's first rows, so
        nothing is allocated), then the blocks past them go back to the
        allocator and the table is [summary rows ; one row of the new window]."""
        bs, M = self.mc.block_size, self.mc.summaries_per_window
        self._end_plan()
        with self._span("sched.roll", request_id=seq.request_id, window=seq.rolls) as roll:
            row0 = M * seq.rolls
            table = np.zeros((self._roll_blocks,), dtype=np.int32)
            mine = seq.block_ids[row0 // bs : row0 // bs + self._roll_blocks]
            table[: len(mine)] = mine
            self.flight.record_exec("eva_roll", ())
            self._note_step("eva_roll", (), (seq,))
            with self._launch("eva_roll"):
                self.cache.k, self.cache.v = self._roll_jit(
                    self.params, self.cache.k, self.cache.v, self._up(table), self._up(np.int32(row0))
                )
            with self._span("sched.sync"):
                # The program is short and rare; waiting for it here keeps its
                # device time out of the step program's that follows.
                jax.block_until_ready(self.cache.k)
            self._accrue_kv(seq)  # the window's blocks were held until now
            seq.rolls += 1
            keep = (M * seq.rolls + 1 + bs - 1) // bs
            released, seq.block_ids = seq.block_ids[keep:], seq.block_ids[:keep]
            self.allocator.release(released)
            self.eva_rolls_total += 1
            self.eva_released_blocks_total += len(released)
            roll.set(released=len(released), blocks=len(seq.block_ids))
        self.flight.record_step(
            "roll", self._since(roll), M, kv_read_tokens=self.mc.window_size, param_passes=0.0
        )
        self._trace_event(seq, "eva_roll", window=seq.rolls - 1, released=len(released))
        self._begin_plan()

    def _mixed_candidate(self) -> Optional[Sequence]:
        """Head-of-queue sequence eligible to ride a mixed step, or None.
        Only the head is considered (FIFO — jumping an ineligible head
        would starve it); ineligible heads (remote-prefilled injection,
        multimodal, non-llama, draft-attached engines) fall back to the
        phase-separated path, as does a full decode set when the head has
        not been admitted yet."""
        if not (
            self.sc.enable_mixed_batching
            and self._supports_mixed
            and self.draft_params is None
            and self.running
            and self.waiting
        ):
            return None
        head = self.waiting[0]
        if head.aborted or head.prefilled is not None or head.mm_features is not None:
            return None
        if head.state == SeqState.WAITING and len(self.running) >= self.sc.max_running:
            return None
        return head

    def _wave_preferred(self) -> bool:
        """Prefer batched wave admission over a mixed step when ≥2 short
        wave-eligible prompts wait AND the head's chunk fits the mixed
        budget anyway — the wave admits them all in one dispatch with a
        stall no worse than the chunk a mixed step would carry. Long-prompt
        heads always take the mixed path: a wave would dispatch the whole
        prompt in one stall, which is exactly the regression mixed steps
        exist to kill."""
        if not self._supports_chunk_admit or self.draft_params is not None:
            return False
        if self.sc.itl_budget_ms and self.running:
            return False  # _admit_wave refuses under an ITL budget too
        cap = min(self._wave_s_cap(), self.sc.mixed_prefill_budget or self._wave_s_cap())
        room = self.sc.max_running - len(self.running)
        if room < 2:
            return False
        head = self.waiting[0]
        if not (self._wave_eligible(head) and len(head.prompt) <= cap):
            return False
        n = sum(
            1 for seq in self.waiting[: self.sc.decode_buckets[-1]]
            if self._wave_eligible(seq) and len(seq.prompt) <= cap
        )
        return n >= 2

    def _get_mixed_jit(self, key):
        """Mixed-step executable for (s_bucket, p_width, d_bucket, d_width)
        — shared by _mixed_step and warmup so both compile the same thing.
        ``hp`` follows the prefill convention (``_hp_static``): static on
        the flash path's own chunk attention (the kernel skips the prefix
        piece), not an argument on XLA and under the tile walk."""
        if key not in self._mixed_jits:
            model = self._model
            stats_kw = {"moe_stats": True} if self._moe_stats else {}
            S, Wp = key[0], key[1]

            def mixed_body(p, k, v, buf, dtab, **kw):
                # [chunk tokens S | its length, its start | the rows' 3 x B lanes | the chunk's table Wp]
                dt, dpos, dact = _unpack_rows(buf[S + 2 : -Wp].reshape(3, -1))
                res = model.mixed_step(
                    p, self.mc, k, v, buf[:S], buf[S], buf[S + 1], buf[-Wp:], dt, dpos, dtab, dact, **kw, **stats_kw
                )
                logits = res[0]  # [chunk's last row ; decode rows]
                return (_greedy(logits), logits[:1], logits[1:]) + tuple(res[1:])

            if self._hp_static:

                def mixed_step(p, k, v, buf, dtab, hp):
                    return mixed_body(p, k, v, buf, dtab, use_flash=True, has_prefix=hp)

                self._mixed_jits[key] = self._jit(mixed_step, donate_argnums=(1, 2), static_argnums=(5,), closure=key)
            else:

                def mixed_step(p, k, v, buf, dtab):
                    return mixed_body(p, k, v, buf, dtab)

                self._mixed_jits[key] = self._jit(mixed_step, donate_argnums=(1, 2), closure=key)
        return self._mixed_jits[key]

    def _mixed_step(self, seq: Sequence, outputs: List[tuple]) -> bool:
        """One mixed iteration: the full decode batch plus ``seq``'s next
        prefill chunk in ONE dispatch. Returns False (caller falls back to
        the phase-separated path) when the chunk's blocks can't be
        allocated. Preemption resumes ride too — their chunk recomputes KV
        and samples nothing at the end."""
        resuming = seq.resume_tokens is not None
        pf_tokens = seq.resume_tokens if resuming else seq.prompt
        if seq.state == SeqState.WAITING:
            total_tokens = (seq.total_len if resuming else len(seq.prompt)) + 1
            try:
                self._first_touch(seq, pf_tokens, total_tokens)
            except OutOfBlocksError:
                return False
        if seq.num_computed >= len(pf_tokens):
            # Prefix-cache hit covered the whole chunkable range already —
            # nothing to compute this step; let _prefill_one finish it.
            return False

        remaining = len(pf_tokens) - seq.num_computed
        budget = self._chunk_budget()
        if self.sc.mixed_prefill_budget:
            budget = min(budget, self.sc.mixed_prefill_budget)
        chunk = min(remaining, budget)
        s_bucket = next_bucket(chunk, self.sc.prefill_buckets)
        chunk = min(chunk, s_bucket)
        if self._eva:
            chunk = min(chunk, self._window_room(seq.num_computed))
            try:
                self._grow_table(seq, seq.num_computed + chunk + 1)
            except OutOfBlocksError:
                return False
        chunk_tokens = pf_tokens[seq.num_computed : seq.num_computed + chunk]
        p_tok = np.zeros((s_bucket,), dtype=np.int32)
        p_tok[: len(chunk_tokens)] = chunk_tokens
        has_prefix = seq.num_computed > 0
        # Does this chunk end the prompt, so that its last row's token is the
        # request's first? (A resume's final token re-enters through decode.)
        samples = not resuming and seq.num_computed + len(chunk_tokens) >= len(pf_tokens)

        # Decode batch formation — identical to _decode_step (see there for
        # why max_running is NOT a term: dial shrinks must not strand rows).
        n = min(len(self.running), self.sc.decode_buckets[-1])
        batch = self.running[:n]
        d_bucket = next_bucket(n, self.sc.decode_buckets)
        width = self._width_bucket(max(len(s.block_ids) for s in batch))
        on_host = any(self._needs_host(s) for s in batch) or (samples and self._needs_host(seq))
        p_table = self._prefill_table(seq)
        buf = pack_operands(p_tok, len(chunk_tokens), seq.num_computed, self._pack_rows(batch, d_bucket), p_table)

        self._end_plan()
        with self._span("sched.upload") as upload:
            tables = self._decode_tables(batch, d_bucket, width)
            buf_d = self._up(buf)
            mixed_key = (s_bucket, int(p_table.shape[0]), d_bucket, width)
            exec_key = mixed_key + ((has_prefix,) if self._hp_static else ())
            self.flight.record_exec("mixed", exec_key)
            self._note_step("mixed", exec_key, batch, prefill=len(chunk_tokens), decode=n)
        with self._launch("mixed"):
            res = self._get_mixed_jit(mixed_key)(
                self.params, self.cache.k, self.cache.v, buf_d, tables,
                *((has_prefix,) if self._hp_static else ()),
            )
            toks, chunk_logits, decode_logits, self.cache.k, self.cache.v = self._consume_aux(res)
        self.mixed_steps_total += 1
        self.mixed_prefill_tokens_total += len(chunk_tokens)
        self.mixed_decode_tokens_total += n
        seq.prefill_chunks += 1

        # Decode rows first (output-order parity with the phase-separated
        # decode-then-admit iteration), then the chunk's progress.
        if on_host:
            self._finish_decode_rows(batch, d_bucket, decode_logits, outputs)
        else:
            sampled = self._read_step(toks)  # the dispatch's one read-back: [chunk's last row ; decode rows]
            self._step_counter += 2 if samples else 1  # (as the host path counts: a later draw finds the key it would have)
            self._emit_decode_rows(batch, sampled[1:], outputs)
        self._note_sampled("host" if on_host else "program")
        dur = self._since(upload)
        with self._span("sched.account"):
            # Mixed-step roofline split: the chunk's FLOPs/bytes land in the
            # PREFILL bucket and the decode rows' in DECODE, so mfu_prefill /
            # hbm_frac_decode stay truthful when one fused launch serves both
            # phases (the step histogram itself stays under "mixed").
            self.flight.record_mixed_step(
                dur, len(chunk_tokens), n,
                kv_read_prefill=self._rows_for(seq, seq.num_computed),
                kv_read_decode=sum(self._rows_for(s, s.total_len) for s in batch),
            )
            self._bill_step(
                dur,
                [(seq, "prefill", len(chunk_tokens), self._rows_for(seq, seq.num_computed))]
                + [(s, "decode", 1, self._rows_for(s, s.total_len)) for s in batch],
            )
            self.telemetry.observe("itl", dur)
            self._trace_event(
                seq, "mixed_ride", chunk_tokens=len(chunk_tokens), decode_rows=n,
                dur_s=round(dur, 6),
            )

        with self._span("sched.emit"):
            seq.num_computed += len(chunk_tokens)
            self._register_full_blocks(seq)  # chunk's completed blocks go live
            done = seq.num_computed >= len(pf_tokens)
            if done:
                self.waiting.remove(seq)
                seq.state = SeqState.RUNNING
                self.running.append(seq)
                self._register_full_blocks(seq)
        if not done:
            self._begin_plan()
            return True  # more chunks ride later steps
        if resuming:
            # KV restored through the last generated token; the final token
            # re-enters via decode — nothing to sample or emit.
            seq.resume_tokens = None
        else:
            if on_host:
                with self._span("sched.sample"):
                    token = self._sample_one(seq, chunk_logits)
            else:
                token = int(sampled[0])
            with self._span("sched.emit"):
                seq.first_token_ts = time.monotonic()
                self._append_token(seq, token, outputs)
        self._begin_plan()
        return True

    def _reap_aborted(self, outputs: List[tuple]) -> None:
        for seq in list(self.running):
            if seq.aborted:
                self._finish(seq, seq.abort_reason, outputs)
        for seq in list(self.waiting):
            if seq.aborted:
                self.waiting.remove(seq)
                seq.state = SeqState.FINISHED
                # Never-admitted requests still bill their queue time (and
                # any mid-prefill KV hold) — a timeout storm in the queue is
                # exactly what tenant attribution must see.
                self._emit_bill(seq, seq.abort_reason)
                self._log_request(seq, seq.abort_reason)
                # Mid-prefill cancellations already hold blocks — release them.
                self.allocator.release(seq.block_ids)
                seq.block_ids = []
                self._drop_slot(seq)
                self.by_id.pop(seq.request_id, None)
                outputs.append((seq, StepOutput(token_id=-1, finished=True, finish_reason=seq.abort_reason)))

    def _sweep_deadlines(self) -> None:
        """Mark past-deadline rows aborted with reason "timeout"; the
        regular reap then frees their KV and emits the final frame. Runs at
        the head of every step (host-side, O(live rows)) but only once any
        deadline-carrying request has been admitted."""
        if not self._has_deadlines:
            return
        now = time.monotonic()
        for seq in self.running + self.waiting:
            if (
                seq.deadline_ts is not None
                and not seq.aborted
                and now >= seq.deadline_ts
            ):
                seq.aborted = True
                seq.abort_reason = "timeout"
                self.timeouts_total += 1
                self._trace_event(
                    seq, "deadline_evict",
                    overrun_ms=round((now - seq.deadline_ts) * 1000.0, 3),
                    output_tokens=len(seq.output_ids),
                )

    def _admit(self, outputs: List[tuple]) -> None:
        """Admit waiting sequences: a batched WAVE when several short
        prompts wait (one dispatch + one readback for all of them — on
        dispatch-latency-heavy links per-request prefills serialized
        admission at one ~100 ms round-trip each), else one chunked
        prefill."""
        if not self.waiting or len(self.running) >= self.sc.max_running:
            return
        # FIFO fairness: waves only form when the HEAD of the queue joins
        # them — otherwise an ineligible head (long prompt, seeded/logprobs
        # request) would starve behind an endless stream of wave-admitted
        # shorts. The head must ALSO fit the wave's chunk cap: a long-prompt
        # head is exactly the starvation case.
        head = self.waiting[0]
        if (
            self._wave_eligible(head)
            and len(head.prompt) <= self._wave_s_cap()
            and self._admit_wave(outputs)
        ):
            return
        seq = self.waiting[0]
        try:
            done = self._prefill_one(seq, outputs)
        except OutOfBlocksError:
            # Not enough KV blocks — leave in queue; decode progress will
            # free/evict blocks. (The reference's engines preempt here; we
            # backpressure instead.)
            return
        if done:
            self.waiting.pop(0)

    def _wave_s_cap(self) -> int:
        """Longest prompt a wave admission will take in one chunk."""
        return min(self.sc.max_prefill_chunk, self.sc.prefill_buckets[-1])

    def _get_admit_jit(self, key):
        """Wave-admission executable for (b_bucket, s_bucket, width) —
        shared by _admit_wave and warmup so both compile the same thing."""
        if key not in self._admit_jits:
            model = self._model
            stats_kw = {"moe_stats": True} if self._moe_stats else {}

            def admit_wave(p, k, v, t, p0, vl, bt):
                return model.chunk_decode(
                    p, self.mc, k, v, t, p0, vl, bt, last_logits=True, **stats_kw
                )

            self._admit_jits[key] = self._jit(admit_wave, donate_argnums=(1, 2))
        return self._admit_jits[key]

    def _wave_eligible(self, seq: Sequence) -> bool:
        s = seq.sampling
        return (
            seq.state == SeqState.WAITING
            and seq.prefilled is None
            and seq.resume_tokens is None
            and seq.mm_features is None
            and seq.guided is None  # wave samples on device, unmasked
            and not s.logprobs
            and not s.top_logprobs
            and not s.logits_processors
            and not (s.seed is not None and s.temperature > 0)
        )

    def _admit_wave(self, outputs: List[tuple]) -> bool:
        """Prefill a wave of short waiting prompts in ONE ``chunk_decode``
        dispatch: KV for every row's whole prompt is written batched, the
        last-valid logits feed the on-device sampler, and the host reads
        back one [B] token array. Returns True when a wave was admitted.

        Falls through to the single-sequence path for prompts longer than
        one chunk, non-llama architectures, draft-attached engines (the
        draft catch-up is per-sequence), and requests needing per-token
        logprobs/processors/seeded sampling."""
        if not self._supports_chunk_admit or self.draft_params is not None:
            return False
        if self.sc.itl_budget_ms and self.running:
            # A wave dispatches B×S prompt tokens in one device call —
            # incompatible with an ITL budget while decodes run; the
            # single-prefill path enforces the budgeted chunk size.
            return False
        s_cap = self._wave_s_cap()
        room = self.sc.max_running - len(self.running)
        wave: List[Sequence] = []
        for seq in self.waiting:
            if len(wave) >= min(room, self.sc.decode_buckets[-1]):
                break
            if not self._wave_eligible(seq):
                continue
            if len(seq.prompt) > s_cap:
                continue
            wave.append(seq)
        if len(wave) < 2:
            return False

        # First touch per seq: prefix match + all-or-nothing allocation
        # (shared with _prefill_one; a seq that can't allocate ends the wave).
        admitted: List[Sequence] = []
        for seq in wave:
            try:
                self._first_touch(seq, seq.prompt, len(seq.prompt) + 1)
            except OutOfBlocksError:
                break
            admitted.append(seq)
        if len(admitted) < 2:
            # 0 or 1 allocated: hand everything back to the single-seq path
            # untouched (it re-runs first-touch matching, so blocks/refs
            # acquired here must be returned first).
            for seq in admitted:
                self.allocator.release(seq.block_ids)
                self.cached_tokens_total -= seq.cached_tokens
                seq.block_ids = []
                seq.num_cached_blocks = 0
                seq.num_computed = 0
                seq.cached_tokens = 0
                seq.kv_ts = None  # clock started at first touch; nothing held now
                seq.state = SeqState.WAITING
            return False

        s_max = max(len(seq.prompt) - seq.num_computed for seq in admitted)
        s_bucket = next_bucket(s_max, self.sc.prefill_buckets)
        b_bucket = next_bucket(len(admitted), self.sc.decode_buckets)
        width = self._width_bucket(max(len(seq.block_ids) for seq in admitted))

        from dynamo_tpu.engine.sampling import pack_param_rows

        tokens = np.zeros((b_bucket, s_bucket), dtype=np.int32)
        pos0 = np.zeros((b_bucket,), dtype=np.int32)
        valid = np.zeros((b_bucket,), dtype=np.int32)
        tables = np.zeros((b_bucket, width), dtype=np.int32)
        temps, top_ks, top_ps = pack_param_rows([s.sampling for s in admitted], b_bucket)
        for i, seq in enumerate(admitted):
            chunk = seq.prompt[seq.num_computed:]
            tokens[i, : len(chunk)] = chunk
            pos0[i] = seq.num_computed
            valid[i] = len(chunk)
            tables[i, : len(seq.block_ids)] = seq.block_ids

        self._end_plan()
        with self._span("sched.upload") as upload:
            tokens_d, pos0_d, valid_d, tables_d = (
                self._up(tokens), self._up(pos0), self._up(valid), self._up(tables)
            )
            wave_key = (b_bucket, s_bucket, width)
            self.flight.record_exec("admit", wave_key)
            self._note_step("admit", wave_key, admitted, prefill=int(valid.sum()))
        with self._launch("admit"):
            res = self._get_admit_jit(wave_key)(
                self.params, self.cache.k, self.cache.v,
                tokens_d, pos0_d, valid_d, tables_d,
            )
            lg, self.cache.k, self.cache.v = self._consume_aux(res)
        with self._span("sched.sample"):
            self._step_counter += 1
            skey = self._key()
            res = self._sample_jit(
                lg, self._up(temps), self._up(top_ks), self._up(top_ps), skey, None
            )
            with self._span("sched.sync"):
                sampled = np.asarray(res)  # the wave's ONE host sync
        self._note_sampled("host")  # a wave keeps its sampler as a program of its own
        with self._span("sched.emit"):
            for i, seq in enumerate(admitted):
                self.waiting.remove(seq)
                seq.num_computed = len(seq.prompt)
                seq.prefill_chunks += 1
                seq.first_token_ts = time.monotonic()
                seq.state = SeqState.RUNNING
                self.running.append(seq)
                self._register_full_blocks(seq)
                self._append_token(seq, int(sampled[i]), outputs)
        dur = self._since(upload)
        with self._span("sched.account"):
            self.flight.record_step(
                "wave", dur, int(valid.sum()) + len(admitted),
                kv_read_tokens=int(pos0.sum()),
            )
            self._bill_step(
                dur,
                [(seq, "prefill", int(valid[i]) + 1, int(pos0[i])) for i, seq in enumerate(admitted)],
            )
        self._begin_plan()
        return True

    def _first_touch(self, seq: Sequence, pf_tokens: List[int], total_tokens: int) -> None:
        """First admission: prefix-cache match + full block allocation,
        all-or-nothing — a partial failure re-runs next step, so any
        acquired refs/blocks are returned before OutOfBlocksError
        propagates. Shared by single prefills and wave admission."""
        bs = self.mc.block_size
        try:
            if self.sc.enable_prefix_caching and seq.mm_features is None:
                seq.block_hashes = extend_block_hashes([], pf_tokens, bs)
                matched = self._match_prefix_tiers(seq)
                # At least one token must prefill so logits exist. A FULL
                # cover keeps every matched block and recomputes only the
                # last token — but its KV write lands inside the final
                # matched block, which other sequences may still reference:
                # copy-on-write it into a private block. A sole-held block
                # (refcount 1 = just us) is written in place instead — the
                # recomputed row is bit-identical, so no copy is needed.
                if matched and len(matched) * bs >= len(pf_tokens):
                    last = matched[-1]
                    if self.allocator.ref_count(last) > 1:
                        try:
                            (cow,) = self.allocator.allocate(1)
                        except OutOfBlocksError:
                            # No room for the private copy: degrade to
                            # recomputing the whole last block (still an
                            # n-1 block hit).
                            self.allocator.release([last])
                            matched = matched[:-1]
                        else:
                            self._copy_block(last, cow)
                            self.allocator.release([last])
                            matched[-1] = cow
                            self.cow_blocks_total += 1
                seq.block_ids = list(matched)
                seq.num_cached_blocks = len(matched)
                seq.num_computed = min(len(matched) * bs, len(pf_tokens) - 1)
                seq.cached_tokens = seq.num_computed
                self.cached_tokens_total += seq.cached_tokens
            # (eva: the first window's rows; later windows grow chunk by chunk.)
            self._grow_table(seq, total_tokens)
            if self._hybrid:
                self._open_slot(seq)
        except OutOfBlocksError:
            self.allocator.release(seq.block_ids)
            self.cached_tokens_total -= seq.cached_tokens
            seq.block_ids = []
            seq.num_cached_blocks = 0
            seq.num_computed = 0
            seq.cached_tokens = 0
            seq.kv_ts = None
            raise
        # Block-seconds clock starts at first hold — prefix-cache matched
        # (COW-shared) blocks included, since the tenant pins their refcount.
        self._accrue_kv(seq)
        seq.state = SeqState.PREFILL
        if seq.admitted_ts is None:
            seq.admitted_ts = time.monotonic()
            seq.first_step = self.flight.log.step
            self._trace_event(
                seq, "admitted",
                queue_s=round(seq.admitted_ts - seq.arrival_ts, 6),
                cached_blocks=seq.num_cached_blocks,
            )

    def _prefill_one(self, seq: Sequence, outputs: List[tuple]) -> bool:
        """Run one prefill chunk for ``seq``. Returns True when the prompt is
        fully computed (sequence moved to running). Preempted sequences
        resume here: ``resume_tokens`` (prompt + generated so far, minus the
        last token) recompute their KV, then decode continues — no sampling
        at the end of a resume."""
        bs = self.mc.block_size
        # Inject only on first admission: a preempted decode-role sequence
        # (resume_tokens set) must recompute, not re-inject — re-injection
        # would duplicate first_token and leave generated-token KV absent.
        if seq.state == SeqState.WAITING and seq.prefilled is not None and seq.resume_tokens is None:
            return self._inject_prefilled(seq, outputs)
        resuming = seq.resume_tokens is not None
        pf_tokens = seq.resume_tokens if resuming else seq.prompt
        if seq.state == SeqState.WAITING:
            total_tokens = (seq.total_len if resuming else len(seq.prompt)) + 1
            self._first_touch(seq, pf_tokens, total_tokens)

        remaining = len(pf_tokens) - seq.num_computed
        chunk = min(remaining, self._chunk_budget())
        bucket = next_bucket(chunk, self.sc.prefill_buckets)
        chunk = min(chunk, bucket)
        if self._eva:
            chunk = min(chunk, self._window_room(seq.num_computed))
            self._grow_table(seq, seq.num_computed + chunk + 1)  # OutOfBlocksError: _admit retries later

        tokens = pf_tokens[seq.num_computed : seq.num_computed + chunk]
        padded = np.zeros((bucket,), dtype=np.int32)
        padded[: len(tokens)] = tokens
        has_prefix = seq.num_computed > 0
        if seq.mm_features is not None:
            feats = seq.mm_features
            fb = 16
            while fb < feats.shape[0]:
                fb *= 2
            padded_f = np.zeros((fb, feats.shape[1]), dtype=np.float32)
            padded_f[: feats.shape[0]] = feats

        t0 = time.monotonic() if self.sc.itl_budget_ms else None
        # Does the chunk end the prompt (its last row's token is the request's first), and who samples it?
        samples = not resuming and seq.num_computed + len(tokens) >= len(pf_tokens)
        on_host = seq.mm_features is not None or (samples and self._needs_host(seq))  # (multimodal: no sampler in its program)
        self._end_plan()
        with self._span("sched.upload") as upload:
            table = self._up(self._prefill_table(seq))
            if seq.mm_features is not None:
                # The multimodal variant keeps its own operands (lazily built, off the text path).
                args = (self._up(padded), self._up(np.int32(len(tokens))), self._up(np.int32(seq.num_computed)),
                        table, has_prefix, self._up(padded_f), self._up(np.int32(feats.shape[0])))
                kind, fn = "prefill_mm", self._prefill_mm_jit()
                key = (bucket, int(table.shape[0]), fb, has_prefix)
                self.flight.record_exec("prefill_mm", key)
            else:
                buf = pack_operands(padded, len(tokens), seq.num_computed)
                args = (self._up(buf), table) + ((has_prefix,) if self._hp_static else ())
                # Shape key mirrors warmup(): has_prefix keys an executable only
                # where it is static (_hp_static).
                kind, fn = "prefill", self._prefill_jit
                key = (bucket, int(table.shape[0]), has_prefix if self._hp_static else False)
                self.flight.record_exec("prefill", key)
            self._note_step(kind, key, (seq,), prefill=len(tokens))
        with self._launch(kind):
            res = fn(self.params, self.cache.k, self.cache.v, *args)
            if seq.mm_features is not None:
                logits, self.cache.k, self.cache.v = self._consume_aux(res)
                logits = logits[None, :]
            else:
                tok, logits, self.cache.k, self.cache.v = self._consume_aux(res)
        dur = self._since(upload)
        seq.prefill_chunks += 1
        with self._span("sched.account"):
            self.flight.record_step(
                "prefill", dur, len(tokens), kv_read_tokens=self._rows_for(seq, seq.num_computed)
            )
            self._bill_step(dur, [(seq, "prefill", len(tokens), self._rows_for(seq, seq.num_computed))])
            self._trace_event(
                seq, "prefill_chunk", tokens=len(tokens), bucket=bucket,
                computed=seq.num_computed + len(tokens), dur_s=round(dur, 6),
                resume=resuming,
            )
        if t0 is not None:
            # Sync to learn the chunk rate (feeds _chunk_budget's EMA).
            with self._span("sched.sync"):
                logits.block_until_ready()
            dt = max(time.monotonic() - t0, 1e-6)
            rate = len(tokens) / dt
            self._prefill_tok_s = rate if self._prefill_tok_s is None else (
                0.7 * self._prefill_tok_s + 0.3 * rate
            )
        with self._span("sched.emit"):
            seq.num_computed += len(tokens)
            self._register_full_blocks(seq)  # chunk's completed blocks go live
            self._draft_catchup_prefill(seq, pf_tokens)
            done = seq.num_computed >= len(pf_tokens)
            if done and resuming:
                # KV restored through the last generated token; the final token
                # re-enters via the decode step — nothing to sample or emit.
                seq.resume_tokens = None
                seq.state = SeqState.RUNNING
                self.running.append(seq)
                self._register_full_blocks(seq)
                self._trace_event(seq, "resume", total_len=seq.total_len)
        if not done or resuming:
            self._note_sampled("host" if on_host else "program")  # (nothing to read: the program's lane is left)
            self._begin_plan()
            return done  # False: more chunks to go

        # Prompt fully computed: its first token, sampled by the program or, for
        # a row that needs the host between logits and token, by _sample_one.
        if on_host:
            with self._span("sched.sample"):
                token = self._sample_one(seq, logits)
                self._note_aux()
        else:
            token = int(self._read_step(tok)[0])
            self._step_counter += 1
        self._note_sampled("host" if on_host else "program")
        with self._span("sched.emit"):
            seq.first_token_ts = time.monotonic()
            seq.state = SeqState.RUNNING
            self.running.append(seq)
            self._register_full_blocks(seq)
            self._append_token(seq, token, outputs)
        self._begin_plan()
        return True

    def _chunk_budget(self) -> int:
        """Max prefill-chunk tokens for this iteration. With an ITL budget
        and live decodes, cap the chunk so its estimated device time stays
        within budget (never below the smallest bucket — progress must be
        made)."""
        cap = self.sc.max_prefill_chunk
        if not self.sc.itl_budget_ms or not self.running or self._prefill_tok_s is None:
            return cap
        budget_tokens = int(self.sc.itl_budget_ms / 1000.0 * self._prefill_tok_s)
        return max(min(cap, budget_tokens), self.sc.prefill_buckets[0])

    def _width_bucket(self, max_used: int) -> int:
        """Block-table width buckets at pow2 AND 1.5·pow2 rungs
        (4, 6, 8, 12, 16, 24, ...). Pure pow2 pays up to 2× gather padding
        right past a boundary — at 256-token pages a 1025-token context
        would gather 2048 tokens; the 1.5 rungs cap the waste at 33% for
        2·log2(max_blocks) executable variants, still few enough for
        warmup() to precompile. (History: multiples of 16 produced
        max_seq/256 variants that compiled mid-traffic — the then-dominant
        serving-plane cost.)"""
        return width_bucket(max_used, self.max_blocks_per_seq)

    def _calibrate_cost_model(self, bucket: int, width: int) -> None:
        """Replace the cost model's hand-rolled 2·params FLOPs/token with
        XLA's own count of the decode executable
        (``jax.stages.Compiled.cost_analysis``) where the backend provides
        one. Lowering happens BEFORE the warmup dispatch of the same shape —
        ``lower()`` only records donation, it does not invalidate the live
        cache buffers — and the compile lands in the same compilation cache
        the warmup call hits. Failures degrade to the analytical model."""
        cm = self.flight.cost_model
        if cm is None:
            return
        try:
            buf = jnp.zeros((3 * bucket,), jnp.int32)
            tables = jnp.zeros((bucket, width), jnp.int32)
            # (The decode executable of this shape is built here, under the kind
            # "calibrate": the warm-up call of the same key then finds it built.)
            with self._building("calibrate", (bucket, width)):
                cost = self._decode_jit.lower(self.params, self.cache.k, self.cache.v, buf, tables).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            flops = float(cost.get("flops", 0.0) or 0.0)
            if flops > 0 and cm.calibrate(flops / max(bucket, 1)):
                logger.info(
                    "cost model calibrated from XLA cost_analysis: "
                    "%.4g flops/token (analytical %.4g)",
                    cm.flops_per_token, 2.0 * cm.param_count,
                )
        except Exception as e:  # noqa: BLE001 — calibration is best-effort
            logger.debug("cost_analysis calibration unavailable: %s", e)

    def _sampler_args(self, rows: int, key) -> tuple:
        """What warm-up hands the host path's samplers at ``rows`` rows: logits, temperature, top-k, top-p, key."""
        return (
            jnp.zeros((rows, self.mc.vocab_size), jnp.float32),
            jnp.zeros((rows,), jnp.float32), jnp.zeros((rows,), jnp.int32),
            jnp.ones((rows,), jnp.float32), key, None,
        )

    def warmup(self, ctx_tokens: int = 2048) -> int:
        """Precompile the serving-hot executables so traffic never waits on
        XLA (the reference's engines warm up at startup for the same reason;
        vLLM role: --enforce-eager off + warmup passes). Covers: decode
        (every batch bucket × table widths up to ``ctx_tokens``), the
        multi-step window variant when enabled, fresh-prefill chunks per
        bucket, and the sampler per bucket. Dispatches run with all rows
        inactive, so writes land in the reserved scratch block 0 and cache
        contents are untouched. Returns the number of executable KEYS warmed
        (the calls made); what JAX built for each is in the build log
        (engine/compile_cache.py), whose ``build.key`` scopes these calls open:
        a key may be several executables, or none that was not built already.
        Array arguments are made before a key's scope opens, so that their
        fills stay the eager executables they are. What these calls trace and
        lower pays no system call at an edge of CPython's stack chunks where
        the caller stands below ``compile_cache.in_one_chunk``'s frame, as
        ``TpuEngine.build`` does: the size of this frame, and what stands
        between it and its jitted calls, no longer move a set-up's seconds."""
        bs = self.mc.block_size
        max_w = self._width_bucket((ctx_tokens + bs - 1) // bs)
        widths = sorted(set(min(r, self.max_blocks_per_seq) for r in width_rungs(max_w)))
        count = 0
        key = self._rng
        # Ask XLA for the decode executable's own FLOPs count before the
        # first dispatch of the same shape compiles it for real.
        self._calibrate_cost_model(self.sc.decode_buckets[0], widths[0])
        for bucket in self.sc.decode_buckets:
            for width in widths:
                tables = jnp.zeros((bucket, width), jnp.int32)
                self.flight.record_exec("decode", (bucket, width))
                with self._building():
                    _, _, self.cache.k, self.cache.v = self._consume_aux(  # (all rows inactive)
                        self._decode_jit(
                            self.params, self.cache.k, self.cache.v, jax.device_put(pack_operands(self._pack_rows([], bucket))), tables
                        )
                    )
                count += 1
                if self.sc.num_scheduler_steps > 1 and self._supports_multi_step:
                    # (All rows inactive and greedy, a key of zeros.)
                    buf = jax.device_put(pack_operands(self._pack_rows([], bucket, sampler=True), (0, 0)))
                    for w, mjit in self._decode_multi_jits.items():
                        self.flight.record_exec("decode_multi", (w, bucket, width))
                        with self._building():
                            _, self.cache.k, self.cache.v = self._consume_aux(
                                mjit(self.params, self.cache.k, self.cache.v, buf, tables)
                            )
                        count += 1
            # The sampler, its fused logprobs variant (a logprobs row joining a
            # warmed batch must not compile the sampler mid-traffic) and the
            # top-k variant (OpenAI top_logprobs; static candidate cap, so one
            # warm covers every requested k).
            args = self._sampler_args(bucket, key)
            with self._building("sampler", (bucket,)):
                self._sample_jit(*args)
                self._sample_lp_jit(*args)
                self._sample_tlp_jit(*args)
            # ... and the keys of a batch that holds a seeded sampled row.
            args = (key, jnp.zeros((bucket,), jnp.int32), jnp.zeros((bucket,), jnp.int32), jnp.zeros((bucket,), bool))
            with self._building("sampler", ("row_keys", bucket)):
                self._row_keys_jit(*args)
            count += 4
        # The host path's first-token logprobs (its sampler at one row is warmed below).
        args = (jnp.zeros((1, self.mc.vocab_size), jnp.float32), jnp.zeros((1,), jnp.int32))
        with self._building("sampler", ("first_token_logprobs",)):
            self._lp_jit(*args)
            self._tlp_jit(*args)
        count += 2
        if self._hybrid:
            # Taking a slot: one executable, warmed on the scratch slot and block.
            self.flight.record_exec("open_slot", ())
            with self._building():
                self.cache.k, self.cache.v = self._open_slot_jit(self.cache.k, self.cache.v, jnp.int32(0), jnp.int32(0))
            count += 1
        if self._eva:
            # The roll program: one executable, warmed against the scratch
            # block (a table of zeros reads and writes block 0).
            self.flight.record_exec("eva_roll", ())
            tables = jnp.zeros((self._roll_blocks,), jnp.int32)
            with self._building():
                self.cache.k, self.cache.v = self._roll_jit(self.params, self.cache.k, self.cache.v, tables, jnp.int32(0))
            count += 1
        # Prefix-cache copy-on-write block copy: one executable, warmed
        # against the scratch block so a full-cover hit under traffic never
        # compiles (0-post-warmup invariant with prefix caching enabled).
        if self.sc.enable_prefix_caching:
            self.flight.record_exec("kv_block_copy", ())
            with self._building():
                self.cache.k, self.cache.v = self._kv_copy_jit(self.cache.k, self.cache.v, jnp.int32(0), jnp.int32(0))
            count += 1
        # Guided masked-sampling executables: one per decode bucket (plus
        # the bucket-1 prefill-tail sampler) at the current pool capacity —
        # guided rows joining a warmed batch then compile nothing.
        if self.guided is not None:
            pool = self.guided.pool.device()
            P = int(pool.shape[0])
            for bucket in sorted(set(self.sc.decode_buckets) | {1}):
                self.flight.record_exec("guided_sample", (bucket, P))
                args = (
                    jnp.zeros((bucket, self.mc.vocab_size), jnp.float32), pool,
                    jnp.zeros((2, bucket), jnp.int32),
                    jnp.zeros((bucket,), jnp.float32),
                    jnp.ones((bucket,), jnp.float32), key, None,
                )
                with self._building():
                    self._guided_sample_jit(*args)
                    self._guided_sample_lp_jit(*args)
                    self._guided_sample_tlp_jit(*args)
                count += 3
        prev_bucket = 0
        for bucket in self.sc.prefill_buckets:
            if bucket > self.sc.max_prefill_chunk:
                continue
            # Smallest table width serving can pair with this chunk bucket:
            # the shortest prompt that maps here (prev_bucket+1 tokens),
            # bucketed by _prefill_table's rung rule (16 floor).
            min_w = self._prompt_width((prev_bucket + 1 + bs - 1) // bs)
            # Wave-admission width floor for this chunk bucket: _admit_wave
            # buckets by the wave's longest block table (rung floor 4, NOT
            # _prefill_table's 16) — the shortest fresh prompt chunking
            # here plus its next-token slot.
            wave_lo = width_bucket((prev_bucket + 2 + bs - 1) // bs, self.max_blocks_per_seq)
            prev_bucket = bucket
            # Serving's _prefill_table buckets by the sequence's TOTAL block
            # count, not the chunk: a long prompt prefilled in small chunks
            # uses a wide table from chunk 0, and prefix-hit continuations
            # inherit the full-prompt width. Warm every rung width from the
            # bucket's minimum up to the ctx budget so neither compiles
            # mid-traffic.
            p_widths = sorted(set(
                min(r, self.max_blocks_per_seq)
                for r in width_rungs(max(max_w, min_w))
                if r >= min_w
            ))
            for width in p_widths:
                # Where has_prefix is static (_hp_static) both variants: fresh
                # prefills AND chunked / prefix-hit continuations.
                tables = jnp.zeros((width,), jnp.int32)
                for hp in (False, True) if self._hp_static else (False,):
                    self.flight.record_exec("prefill", (bucket, width, hp))
                    with self._building():
                        _, _, self.cache.k, self.cache.v = self._consume_aux(
                            self._prefill_jit(
                                self.params, self.cache.k, self.cache.v,
                                jax.device_put(pack_operands(np.zeros((bucket,), np.int32), 1, 0)),  # one valid token at position 0
                                tables, *((hp,) if self._hp_static else ()),
                            )
                        )
                    count += 1
                if self.draft_params is not None:
                    args = (jnp.zeros((bucket,), jnp.int32), jnp.int32(1), jnp.int32(0), tables)
                    with self._building("draft_prefill", (bucket, width)):
                        _, self.draft_cache.k, self.draft_cache.v = self._d_prefill_jit(
                            self.draft_params, self.draft_cache.k, self.draft_cache.v, *args
                        )
                    count += 1
            args = self._sampler_args(1, key)
            with self._building("sampler", (1,)):
                self._sample_jit(*args)
            count += 1
            # Wave-admission executables for this chunk bucket: every batch
            # rung a wave can form (≥2 admitted) × the table-width rungs
            # wave traffic actually produces — from the shortest fresh
            # prompt chunking here up to the longest wave-eligible prompt
            # (prefix-hit waves pair SMALL chunk buckets with the FULL
            # prompt's table width), clamped to the ctx budget. The round-5
            # advisor flagged these non-default (b, s, w) keys compiling
            # mid-traffic: only (top_bucket, s, 16-floor width) was warmed,
            # while real waves bucket width from their block tables (rung
            # floor 4).
            if self._supports_chunk_admit and self.draft_params is None:
                wave_hi = min(
                    max(max_w, wave_lo),
                    width_bucket((self._wave_s_cap() + 1 + bs - 1) // bs, self.max_blocks_per_seq),
                )
                wave_ws = sorted(
                    w for w in set(
                        min(r, self.max_blocks_per_seq) for r in width_rungs(wave_hi)
                    )
                    if wave_lo <= w <= wave_hi
                )
                for b_b in (b for b in self.sc.decode_buckets if b >= 2):
                    for w in wave_ws:
                        self.flight.record_exec("admit", (b_b, bucket, w))
                        args = (jnp.zeros((b_b, bucket), jnp.int32), jnp.zeros((b_b,), jnp.int32),
                                jnp.zeros((b_b,), jnp.int32), jnp.zeros((b_b, w), jnp.int32))
                        with self._building():
                            _, self.cache.k, self.cache.v = self._consume_aux(
                                self._get_admit_jit((b_b, bucket, w))(self.params, self.cache.k, self.cache.v, *args)
                            )
                        count += 1
        # Mixed prefill+decode executables: every budget-sized chunk bucket
        # the capacity dial can produce (_mixed_warm_buckets — a ratio
        # shift between dial settings must not compile mid-traffic) at
        # every decode bucket × width, with the minimum prefill-table
        # width. Bucket rungs keep the key space bounded; rarer (s, Wp)
        # keys compile lazily.
        if (
            self._supports_mixed
            and self.sc.enable_mixed_batching
            and self.draft_params is None
        ):
            # (An eva table shrinks at every roll and regrows, so a prompt's
            # table takes every width on its way: warm them all.)
            p_ws = [self._prompt_width(1)]
            if self._eva:
                p_ws = sorted({max(16, w) for w in widths} | set(p_ws))
            # Where has_prefix changes the program (_hp_static) a prompt's
            # first chunk and its later ones are two executables: warm both.
            hps = (False, True) if self._hp_static else (False,)
            for s_b, p_w in ((s, w) for s in self._mixed_warm_buckets() for w in p_ws):
                for bucket in self.sc.decode_buckets:
                    for width, hp in ((w, hp) for w in widths for hp in hps):
                        self.flight.record_exec(
                            "mixed",
                            (s_b, p_w, bucket, width)
                            + ((hp,) if self._hp_static else ()),
                        )
                        tables = jnp.zeros((bucket, width), jnp.int32)
                        buf = jax.device_put(pack_operands(
                            np.zeros((s_b,), np.int32), 1, 0, self._pack_rows([], bucket), np.zeros((p_w,), np.int32)
                        ))
                        with self._building():
                            res = self._get_mixed_jit((s_b, p_w, bucket, width))(
                                self.params, self.cache.k, self.cache.v, buf, tables,
                                *((hp,) if self._hp_static else ()),
                            )
                            _, _, _, self.cache.k, self.cache.v = self._consume_aux(res)
                        count += 1
        # Speculative-round executables (draft chunk+sample, γ-1 proposal
        # window, target chunk scoring, rejection verify): _decode_spec keys
        # them by (γ, decode bucket, table width), so with a draft attached
        # the first spec round after warmup would otherwise compile four
        # executables mid-traffic. All rows inactive/zero-valid, tables
        # zero → writes land in the reserved scratch block 0, same as the
        # decode warmup above.
        if self.draft_params is not None:
            gamma = self.spec_gamma
            S = gamma + 1
            for bucket in self.sc.decode_buckets:
                for width in widths:
                    self.flight.record_exec("spec", (gamma, bucket, width))
                    tables = jnp.zeros((bucket, width), jnp.int32)
                    temps = jnp.zeros((bucket,), jnp.float32)
                    tks = jnp.zeros((bucket,), jnp.int32)
                    tps = jnp.ones((bucket,), jnp.float32)
                    toks = jnp.zeros((bucket, S), jnp.int32)
                    pos0 = jnp.zeros((bucket,), jnp.int32)
                    valid = jnp.zeros((bucket,), jnp.int32)
                    with self._building():  # its four executables, and the glue between them
                        tok1, lg1, self.draft_cache.k, self.draft_cache.v = (
                            self._d_chunk_sample_jit(
                                self.draft_params, self.draft_cache.k, self.draft_cache.v,
                                toks, pos0, valid, tables, temps, tks, tps, key,
                            )
                        )
                        count += 1
                        if gamma > 1:
                            _, lg_steps, self.draft_cache.k, self.draft_cache.v = (
                                self._d_multi_jit(
                                    self.draft_params, self.draft_cache.k, self.draft_cache.v,
                                    tok1, pos0, tables, jnp.zeros((bucket,), bool),
                                    temps, tks, tps, key,
                                )
                            )
                            draft_logits = jnp.concatenate(
                                [lg1[:, None], jnp.transpose(lg_steps, (1, 0, 2))], axis=1
                            )
                            count += 1
                        else:
                            draft_logits = lg1[:, None]
                        t_logits, self.cache.k, self.cache.v = self._consume_aux(
                            self._t_chunk_jit(
                                self.params, self.cache.k, self.cache.v,
                                toks, pos0, valid, tables,
                            )
                        )
                        self._spec_verify_jit(
                            draft_logits, t_logits,
                            jnp.zeros((bucket, gamma), jnp.int32),
                            temps, tks, tps, key,
                        )
                    count += 2
        return count

    def _draft_catchup(self, seq: Sequence, tokens: List[int], upto: int) -> None:
        """Materialize draft KV for positions seq.d_n..upto-1 (prefill-style
        chunks over ``tokens``). Used to mirror prompt prefill, to absorb
        remotely-prefilled prompts, and to re-sync rows whose draft lag
        outgrew the spec chunk width (e.g. after stretches of non-spec
        decode in mixed batches)."""
        if self.draft_params is None or seq.mm_features is not None:
            return  # no vision path in the draft — mm rows decode unspeculated
        while seq.d_n < upto:
            start = seq.d_n
            chunk = min(upto - start, self.sc.max_prefill_chunk)
            bucket = next_bucket(chunk, self.sc.prefill_buckets)
            chunk = min(chunk, bucket)
            toks = tokens[start : start + chunk]
            padded = np.zeros((bucket,), dtype=np.int32)
            padded[: len(toks)] = toks
            with self._launch("draft_prefill"):
                _, self.draft_cache.k, self.draft_cache.v = self._d_prefill_jit(
                    self.draft_params, self.draft_cache.k, self.draft_cache.v,
                    jnp.asarray(padded), jnp.int32(len(toks)), jnp.int32(start),
                    self._up(self._prefill_table(seq)),
                )
            seq.d_n += len(toks)

    def _draft_catchup_prefill(self, seq: Sequence, pf_tokens: List[int]) -> None:
        """Mirror prefill into the draft cache (spec decode). The draft
        always computes the FULL prompt — target-side prefix-cache hits
        don't populate draft KV — so it runs from seq.d_n regardless of
        where the target's chunks started."""
        self._draft_catchup(seq, pf_tokens, seq.num_computed)

    # --- decode ---------------------------------------------------------------
    def _decode_tables(self, batch: List[Sequence], bucket: int, width: int) -> jnp.ndarray:
        """Decode block tables as a device array, re-uploaded ONLY when a
        table actually changed. Block tables are append-only between
        composition changes, so steady-state decode re-transferred an
        identical [bucket, width] i32 array every step; one cached entry
        (keyed on composition + exact block ids) eliminates that."""
        key = (bucket, width, tuple(s.request_id for s in batch))
        blocks = tuple(tuple(s.block_ids) for s in batch)
        if self._tables_cache is not None:
            ckey, cblocks, dev = self._tables_cache
            if ckey == key and cblocks == blocks:
                return dev
        tables = np.zeros((bucket, width), dtype=np.int32)
        for i, s in enumerate(batch):
            tables[i, : len(s.block_ids)] = s.block_ids
        dev = self._up(tables)
        self._tables_cache = (key, blocks, dev)
        return dev

    def _record_host_gap(self) -> None:
        """Host-gap accounting, called right BEFORE a decode-family dispatch:
        the interval since the previous decode dispatch RETURNED (the end of
        its ``sched.launch`` span in the step log) is the bubble the device
        spent waiting on Python. Any other program launched in between (a
        mixed step, a prefill, a wave, a block copy) is the newest launch in
        the log instead, so that interval is not a decode host gap."""
        prev = self.flight.log.last("sched.launch")
        if prev is not None and prev[4].get("decode"):
            self.flight.record_host_gap((time.monotonic_ns() - prev[2]) / 1e9)

    def _decode_step(self) -> List[tuple]:
        outputs: List[tuple] = []
        # Batch size caps at the largest decode bucket — NOT max_running:
        # admission keeps len(running) ≤ max_running in steady state, but a
        # capacity-dial shrink can leave more rows running than the new
        # cap, and slicing to max_running would decode the same head rows
        # every step while the tail starved forever. Over-cap rows drain.
        n = min(len(self.running), self.sc.decode_buckets[-1])
        batch = self.running[:n]
        bucket = next_bucket(n, self.sc.decode_buckets)

        # One row that needs the host between its tokens takes its whole batch
        # to single steps: neither a spec round nor decode_multi has it there.
        host_between_tokens = any(self._host_between_tokens(seq) for seq in batch)
        # Each falls through to the next when blocks/limits don't allow it.
        if (
            self.draft_params is not None
            and not host_between_tokens
            and not any(seq.mm_features is not None for seq in batch)
            and self._decode_spec(batch, bucket, outputs)
        ):
            return outputs
        if (
            self.sc.num_scheduler_steps > 1
            and self._supports_multi_step
            and not host_between_tokens
            and self._decode_multi(batch, bucket, outputs)
        ):
            return outputs

        # Bucket the block-table width by the longest sequence in the batch:
        # the attention gather is O(table_width), so short contexts must not
        # pay for max_seq_len. Power-of-two widths (see _width_bucket) bound
        # the executable count at log2(max_blocks) so warmup() precompiles
        # them all.
        width = self._width_bucket(max(len(seq.block_ids) for seq in batch))

        buf = pack_operands(self._pack_rows(batch, bucket))
        on_host = any(self._needs_host(seq) for seq in batch)

        self._end_plan()
        with self._span("sched.upload") as upload:
            tables = self._decode_tables(batch, bucket, width)
            buf_d = self._up(buf)
            exec_key = (bucket, width)
            self.flight.record_exec("decode", exec_key)
            self._note_step("decode", exec_key, batch, decode=len(batch))
        with self._launch("decode", decode=True):
            res = self._decode_jit(self.params, self.cache.k, self.cache.v, buf_d, tables)
            toks, logits, self.cache.k, self.cache.v = self._consume_aux(res)
        if on_host:
            self._finish_decode_rows(batch, bucket, logits, outputs)
        else:
            self._step_counter += 1
            self._emit_decode_rows(batch, self._read_step(toks), outputs)  # the step's one read-back
        self._note_sampled("host" if on_host else "program")
        dur = self._since(upload)
        with self._span("sched.account"):
            self.flight.record_step(
                "decode", dur, len(outputs),
                kv_read_tokens=sum(self._rows_for(s, s.total_len) for s in batch),
            )
            self._bill_step(dur, [(s, "decode", 1, self._rows_for(s, s.total_len)) for s in batch])
            self.telemetry.observe("itl", dur)
        self._begin_plan()
        return outputs

    def _finish_decode_rows(
        self, batch: List[Sequence], bucket: int, logits: jax.Array, outputs: List[tuple]
    ) -> None:
        """The HOST path of a single decode step, for a batch that holds a row
        which needs the host between its logits and its token (_needs_host):
        penalties, logits processors, sampling (with per-request seeds),
        logprobs, and token append/stop handling, on the logits the step
        program returned beside its own (then unread) tokens. Shared by
        _decode_step and _mixed_step — the decode rows of a mixed dispatch
        carry the same per-row [B, V] logits a plain decode step produces."""
        from dynamo_tpu.engine.sampling import pack_param_rows

        with self._span("sched.sample"):
            # Frequency/presence penalties: one batched device op for the whole
            # step (per-row output-token counts via scatter-add — sampling.py).
            # Penalty-free batches skip it entirely.
            if any(seq.sampling.has_penalties for seq in batch):
                logits = self._apply_penalties(batch, bucket, logits)
            # Per-request logits processors (dynamo_tpu.logits_processing): the
            # host path — ONLY the rows that carry processors cross to host
            # (device gather → [n_proc, V] transfer → device scatter), so one
            # logit_bias row no longer drags the whole batch's [B, V] logits
            # over the wire, and processor-free batches stay on the fast path.
            if any(seq.sampling.logits_processors for seq in batch):
                from dynamo_tpu.logits_processing import apply_chain

                proc_rows = [i for i, seq in enumerate(batch) if seq.sampling.logits_processors]
                sel = self._up(np.asarray(proc_rows, dtype=np.int32))
                sub = np.array(logits[sel])  # [n_proc, V] writable host copy
                for j, i in enumerate(proc_rows):
                    sub[j] = np.asarray(
                        apply_chain(batch[i].sampling.logits_processors, batch[i].output_ids, jnp.asarray(sub[j]))
                    )
                logits = logits.at[sel].set(self._up(sub))
            self._step_counter += 1
            key = self._key()
            row_keys = None
            if any(seq.sampling.seed is not None for seq in batch):
                seeds = np.zeros((bucket,), dtype=np.int32)
                poss_out = np.zeros((bucket,), dtype=np.int32)
                has_seed = np.zeros((bucket,), dtype=bool)
                for i, seq in enumerate(batch):
                    if seq.sampling.seed is not None:
                        seeds[i] = seq.sampling.seed
                        poss_out[i] = len(seq.output_ids)
                        has_seed[i] = True
                row_keys = self._row_keys_jit(
                    key, self._up(seeds), self._up(poss_out), self._up(has_seed)
                )
            temps, top_ks, top_ps = pack_param_rows([s.sampling for s in batch], bucket)
            # Logprobs fold into the SAME sampling dispatch when any row wants
            # them (sampling.sample_batch_logprobs): one executable, one
            # readback — previously a separate compute_logprobs device op plus
            # its own sync per step. A top_logprobs row widens the dispatch to
            # the top-k variant (static candidate cap — one executable for any
            # requested k); the chosen-token logprob rides along either way.
            want_tlp = any(seq.sampling.top_logprobs for seq in batch)
            want_lp = want_tlp or any(seq.sampling.logprobs for seq in batch)
            logprobs_np = None
            top_ids_np = top_lps_np = None
            if any(seq.guided is not None for seq in batch):
                # Guided rows: gather each row's FSM-state mask from the shared
                # device pool inside the fused mask+sample dispatch. Unguided
                # rows point at the reserved allow-all row 0, so the mixed batch
                # shares one executable.
                pool = self.guided.pool.device()
                k_rows = np.zeros((2, bucket), dtype=np.int32)
                k_rows[0] = top_ks
                for i, seq in enumerate(batch):
                    if seq.guided is not None:
                        k_rows[1, i] = seq.guided.row_id
                self.flight.record_exec("guided_sample", (bucket, int(pool.shape[0])))
                guided_jit = (
                    self._guided_sample_tlp_jit if want_tlp
                    else self._guided_sample_lp_jit if want_lp
                    else self._guided_sample_jit
                )
                res = guided_jit(
                    logits, pool, self._up(k_rows),
                    self._up(temps), self._up(top_ps), key, row_keys,
                )
            else:
                sample_jit = (
                    self._sample_tlp_jit if want_tlp
                    else self._sample_lp_jit if want_lp
                    else self._sample_jit
                )
                res = sample_jit(
                    logits, self._up(temps), self._up(top_ks), self._up(top_ps), key, row_keys
                )
            # The step's blocking read-back: the device runs the step program and
            # the sampler while the host waits here.
            with self._span("sched.sync"):
                if want_tlp:
                    sampled, logprobs_np, top_ids_np, top_lps_np = jax.device_get(res)
                elif want_lp:
                    sampled, logprobs_np = jax.device_get(res)
                else:
                    sampled = np.asarray(res)
                self._note_aux()

        self._emit_decode_rows(batch, sampled, outputs, logprobs_np, top_ids_np, top_lps_np)

    def _emit_decode_rows(
        self, batch: List[Sequence], sampled, outputs: List[tuple], logprobs_np=None, top_ids_np=None, top_lps_np=None
    ) -> None:
        """Append each live row's token of a single decode step (read back by
        the caller), growing its table for the next one."""
        with self._span("sched.emit"):
            for i, seq in enumerate(batch):
                if seq.state != SeqState.RUNNING:
                    continue  # preempted while growing an earlier row this step
                self._ensure_block_capacity(seq)
                if seq.state != SeqState.RUNNING:
                    continue  # itself preempted (no candidate to evict)
                lp = (
                    float(logprobs_np[i])
                    if logprobs_np is not None
                    and (seq.sampling.logprobs or seq.sampling.top_logprobs)
                    else None
                )
                tlp = None
                if top_ids_np is not None and seq.sampling.top_logprobs:
                    k = min(seq.sampling.top_logprobs, top_ids_np.shape[1])
                    tlp = [
                        (int(top_ids_np[i, j]), float(top_lps_np[i, j])) for j in range(k)
                    ]
                self._append_token(seq, int(sampled[i]), outputs, logprob=lp, top_logprobs=tlp)

    def _decode_multi(self, batch: List[Sequence], bucket: int, outputs: List[tuple]) -> bool:
        """Multi-step decode window: N steps in one dispatch, one host sync.
        Returns False (caller falls back to single-step) when KV blocks for
        the whole window can't be reserved."""
        # Smallest window rung covering the batch's remaining token budget —
        # a request needing 5 more tokens dispatches an 8-step window, not
        # the full num_scheduler_steps. Windows keep running at full size
        # while requests wait (disabling them under load serialized every
        # token on the wire — measured 4% of the raw decode rate on a
        # dispatch-latency-heavy link); deployments that want bounded
        # admission delay opt in via window_waiting_cap, which caps the
        # window at the first rung ≥ the configured value.
        rem = max(
            max(1, seq.stop.max_tokens - len(seq.output_ids)) for seq in batch
        )
        steps = next((w for w in self._window_rungs if w >= rem), self._window_rungs[-1])
        if self.sc.window_waiting_cap:
            cap_rung = next(
                (w for w in self._window_rungs if w >= self.sc.window_waiting_cap),
                self._window_rungs[-1],
            )
            if self.waiting:
                steps = min(steps, cap_rung)
            # ``rem`` is the MAX remaining across the batch, so one long
            # request would drag short-remaining batchmates through an
            # oversized window — every step past a batchmate's stop is
            # computed then trimmed. When any batchmate is within a rung of
            # finishing, clamp to the same cap rung: the short row wastes at
            # most cap_rung-1 trimmed steps instead of the full window.
            rem_min = min(
                max(1, seq.stop.max_tokens - len(seq.output_ids)) for seq in batch
            )
            if rem_min <= cap_rung:
                steps = min(steps, cap_rung)
        bs = self.mc.block_size
        # Reserve blocks for the whole window up front (+1 for the next
        # iteration's write slot, matching _ensure_block_capacity).
        for seq in batch:
            if seq.total_len + steps > self.mc.max_seq_len:
                # Window would run past max_seq_len (and past the per-seq
                # block-table capacity): let single-step finish it off.
                return False
            try:
                self._grow_table(seq, seq.total_len + steps)
            except OutOfBlocksError:
                return False

        width = self._width_bucket(max(len(seq.block_ids) for seq in batch))
        # Tokens each row takes from the window: all of them, or for eva those
        # before its window boundary (the program writes nothing past it, the
        # roll comes first).
        taken = [min(steps, self._window_room(seq.total_len - 1)) if self._eva else steps for seq in batch]

        exec_key = (steps, bucket, width)
        self.flight.record_exec("decode_multi", exec_key)
        self._note_step("decode_multi", exec_key, batch, decode=sum(taken))
        kv_rows = [self._rows_for(s, s.total_len) for s in batch]  # at the window's first step
        if self._eva and self._step_span is not None:
            # What the window needs of the device: the steps until its last
            # row stops, and the cache rows those steps attend, a row growing
            # by one a step until its boundary (``attended`` is the first step's).
            self._step_span.set(live_steps=max(taken),
                                attended_sum=sum(t * r + t * (t - 1) // 2 for t, r in zip(taken, kv_rows)))
        n0 = len(outputs)
        self._step_counter += 1
        buf = pack_operands(self._pack_rows(batch, bucket, sampler=True), fold_key(self._rng_words, self._step_counter))
        self._end_plan()
        with self._span("sched.upload") as upload:
            tables = self._decode_tables(batch, bucket, width)
            buf_d = self._up(buf)
        with self._launch("decode_multi", decode=True):
            res = self._decode_multi_jits[steps](self.params, self.cache.k, self.cache.v, buf_d, tables)
            toks_out, self.cache.k, self.cache.v = self._consume_aux(res)
        with self._span("sched.sync"):
            sampled = self._read(toks_out)  # [steps, bucket] — the one host sync
        self._note_sampled("program")
        with self._span("sched.emit"):
            for i, seq in enumerate(batch):
                for s in range(taken[i]):
                    if seq.state != SeqState.RUNNING:
                        break  # stopped mid-window; later tokens are trimmed
                    self._append_token(seq, int(sampled[s, i]), outputs)
        dur = self._since(upload)
        with self._span("sched.account"):
            self.flight.record_step(
                "decode", dur, len(outputs) - n0,
                kv_read_tokens=steps * sum(kv_rows),
                # The fori_loop window re-streams the parameter set every step.
                param_passes=float(steps),
            )
            self._bill_step(dur, [(s, "decode", steps, steps * r) for s, r in zip(batch, kv_rows)])
            self.telemetry.observe("itl", dur / max(steps, 1))
        self._begin_plan()
        return True

    def _decode_spec(self, batch: List[Sequence], bucket: int, outputs: List[tuple]) -> bool:
        """One speculative round for the whole batch: the draft catches up on
        any unconsumed confirmed tokens and proposes γ SAMPLED tokens (one
        chunk pass + a γ-1 window), the target scores [last ; proposals] in
        ONE chunk pass, and rejection sampling (spec_decode.spec_verify)
        accepts a prefix + a correction/bonus token per row — the output
        distribution equals sampling the target directly; greedy rows reduce
        to exact argmax agreement. Returns False to fall back to normal
        decode when blocks/limits don't allow a full window."""
        gamma = self.spec_gamma
        S = gamma + 1
        bs = self.mc.block_size
        for seq in batch:
            if seq.total_len + S + 1 > self.mc.max_seq_len:
                return False
            need = (seq.total_len + S + 1 + bs - 1) // bs - len(seq.block_ids)
            if need > 0:
                try:
                    seq.block_ids.extend(self.allocator.allocate(need))
                except OutOfBlocksError:
                    return False
            if seq.total_len - seq.d_n > S:
                # Oversized lag (stretches of non-spec decode in mixed
                # batches, fallback rounds): absorb it with prefill-style
                # chunks so the row rejoins speculation instead of latching
                # the whole batch off spec forever.
                self._draft_catchup(seq, seq.all_ids, seq.total_len - 1)

        from dynamo_tpu.engine.sampling import pack_param_rows

        B = bucket
        width = self._width_bucket(max(len(seq.block_ids) for seq in batch))
        exec_key = (gamma, B, width)
        self.flight.record_exec("spec", exec_key)
        self._note_step("spec", exec_key, batch, decode=S * len(batch))
        n0 = len(outputs)
        t_round = time.perf_counter()
        tables = np.zeros((B, width), dtype=np.int32)
        d_toks = np.zeros((B, S), dtype=np.int32)
        d_pos0 = np.zeros((B,), dtype=np.int32)
        d_valid = np.zeros((B,), dtype=np.int32)
        temps, top_ks, top_ps = pack_param_rows([s.sampling for s in batch], B)
        for i, seq in enumerate(batch):
            lag = seq.total_len - seq.d_n  # ≥ 1: the last token is never materialized
            d_toks[i, :lag] = seq.all_ids[seq.d_n :]
            d_pos0[i] = seq.d_n
            d_valid[i] = lag
            tables[i, : len(seq.block_ids)] = seq.block_ids
        tables_j = jnp.asarray(tables)
        temps_j, tks_j, tps_j = jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps)

        # Draft: catch-up chunk + SAMPLED first proposal (+ its dist), then
        # γ-1 sampled window steps with per-step logits.
        self._step_counter += 1
        key = self._key()
        with self._launch("spec_draft_chunk"):
            tok1, lg1, self.draft_cache.k, self.draft_cache.v = self._d_chunk_sample_jit(
                self.draft_params, self.draft_cache.k, self.draft_cache.v,
                jnp.asarray(d_toks), jnp.asarray(d_pos0), jnp.asarray(d_valid), tables_j,
                temps_j, tks_j, tps_j, key,
            )
        with self._span("sched.sync"):
            tok1_h = np.asarray(tok1)
        proposals = np.zeros((B, gamma), dtype=np.int32)
        poss = np.zeros((B,), dtype=np.int32)
        act = np.zeros((B,), dtype=bool)
        for i, seq in enumerate(batch):
            proposals[i, 0] = tok1_h[i]
            poss[i] = seq.total_len
            act[i] = True
        if gamma > 1:
            self._step_counter += 1
            key2 = self._key()
            with self._launch("spec_draft_multi"):
                toks_out, lg_steps, self.draft_cache.k, self.draft_cache.v = self._d_multi_jit(
                    self.draft_params, self.draft_cache.k, self.draft_cache.v,
                    tok1, jnp.asarray(poss), tables_j, jnp.asarray(act),
                    temps_j, tks_j, tps_j, key2,
                )
            with self._span("sched.sync"):
                proposals[:, 1:] = np.asarray(toks_out).T
            draft_logits = jnp.concatenate(
                [lg1[:, None], jnp.transpose(lg_steps, (1, 0, 2))], axis=1
            )  # [B, γ, V]
        else:
            draft_logits = lg1[:, None]

        # Target: score [last_confirmed ; proposals] in one chunk pass.
        t_toks = np.zeros((B, S), dtype=np.int32)
        t_pos0 = np.zeros((B,), dtype=np.int32)
        t_valid = np.zeros((B,), dtype=np.int32)
        for i, seq in enumerate(batch):
            t_toks[i, 0] = seq.all_ids[-1]
            t_toks[i, 1:] = proposals[i]
            t_pos0[i] = seq.total_len - 1
            t_valid[i] = S
        with self._launch("spec_target_chunk"):
            t_logits, self.cache.k, self.cache.v = self._consume_aux(
                self._t_chunk_jit(
                    self.params, self.cache.k, self.cache.v,
                    jnp.asarray(t_toks), jnp.asarray(t_pos0), jnp.asarray(t_valid), tables_j,
                )
            )

        # Rejection-sampling verification (greedy rows: exact argmax check).
        self._step_counter += 1
        vkey = self._key()
        with self._launch("spec_verify"):
            accepted, next_tok = self._spec_verify_jit(
                draft_logits, t_logits, jnp.asarray(proposals), temps_j, tks_j, tps_j, vkey
            )
        with self._span("sched.sync"):
            accepted_h = np.asarray(accepted)
            next_h = np.asarray(next_tok)

        st = self.spec_stats
        st.num_rounds += 1
        for i, seq in enumerate(batch):
            if seq.state != SeqState.RUNNING:
                continue
            k = int(accepted_h[i])
            st.record_round(k, gamma)
            old_total = seq.total_len
            for t in list(proposals[i, :k]) + [int(next_h[i])]:
                if seq.state != SeqState.RUNNING:
                    break  # stop hit mid-chunk; stale KV rows are position-masked
                self._append_token(seq, int(t), outputs)
            # Draft-coherent prefix: catch-up reached old_total-1; proposal
            # inputs covered positions old_total..old_total+γ-2, of which the
            # first min(k, γ-1) carry accepted (confirmed) tokens.
            seq.d_n = old_total + min(k, gamma - 1)
        dur_round = time.perf_counter() - t_round
        self.flight.record_step(
            "spec", dur_round, len(outputs) - n0,
            kv_read_tokens=2 * sum(s.total_len for s in batch),
        )
        self._bill_step(dur_round, [(s, "decode", S, 2 * s.total_len) for s in batch])
        return True

    # --- disaggregation support ---------------------------------------------
    def _inject_prefilled(self, seq: Sequence, outputs: List[tuple]) -> bool:
        """Decode-role admission: KV arrived from a prefill worker — scatter
        it into fresh blocks and enter decode directly (no prefill compute).
        ``prefilled["blocks"]`` carries host numpy block pairs (wire path);
        ``prefilled["device_blocks"]`` carries stacked device arrays (the
        device-native path: in-process handoff or transfer-server pull)."""
        from dynamo_tpu.llm.block_manager.transfer import scatter_blocks, scatter_blocks_device

        self._refuse_unbuilt("KV injection (disaggregated prefill)")
        bs = self.mc.block_size
        data = seq.prefilled
        # Token-boundary splits (elastic disagg): ``prefill_len`` marks how
        # many prompt tokens the transferred KV covers. Absent or >= the
        # prompt, this is the classic full-prefill handoff.
        n_pref = min(int(data.get("prefill_len") or len(seq.prompt)), len(seq.prompt))
        full = n_pref >= len(seq.prompt)
        n_blocks = (len(seq.prompt) + 1 + bs - 1) // bs
        seq.block_ids = self.allocator.allocate(n_blocks)  # raises → retried next step
        self._accrue_kv(seq)  # decode leg's block-seconds clock starts at injection
        if "device_blocks" in data:
            k_stack, v_stack = data["device_blocks"]
            scatter_blocks_device(self.cache, seq.block_ids[: k_stack.shape[1]], k_stack, v_stack)
        else:
            for bid, (k_np, v_np) in zip(seq.block_ids, data["blocks"]):
                scatter_blocks(self.cache, bid, k_np, v_np)
        seq.num_computed = n_pref
        if seq.admitted_ts is None:
            seq.admitted_ts = time.monotonic()
            seq.first_step = self.flight.log.step
        # Spec decode: the draft cache has nothing for remotely-prefilled KV —
        # compute the draft's own prompt KV before the row joins spec rounds.
        self._draft_catchup_prefill(seq, seq.prompt)
        if self.sc.enable_prefix_caching:
            seq.block_hashes = extend_block_hashes([], seq.prompt, bs)
            self._register_full_blocks(seq)
        if not full:
            # Partial injection: the split request continues as a normal
            # chunked prefill from position n_pref — the REAL first token
            # is sampled at prompt completion (the prefill leg's capped
            # first_token is a placeholder and is discarded), so the
            # output stream is bit-identical to single-worker serving.
            seq.state = SeqState.PREFILL
            seq.prefilled = None
            self._trace_event(
                seq, "disagg_inject", blocks=len(seq.block_ids),
                device_native="device_blocks" in data,
                partial=True, prefill_len=n_pref,
            )
            return False
        seq.state = SeqState.RUNNING
        seq.first_token_ts = time.monotonic()
        self.running.append(seq)
        self._trace_event(
            seq, "disagg_inject", blocks=len(seq.block_ids),
            device_native="device_blocks" in data,
        )
        self._append_token(seq, int(data["first_token"]), outputs)
        seq.prefilled = None  # consumed — a later preemption resumes via recompute
        return True

    def take_export(self, request_id: str):
        """Prefill-role export: hand over the finished sequence's blocks
        (k/v numpy per block) and release them. Returns (blocks, hashes,
        prompt_len) or None."""
        from dynamo_tpu.llm.block_manager.transfer import gather_blocks

        self._refuse_unbuilt("KV export (disaggregated prefill)")
        seq = self._pending_exports.pop(request_id, None)
        self._export_deadline.pop(request_id, None)
        if seq is None:
            return None
        data = [gather_blocks(self.cache, bid) for bid in seq.block_ids]
        self.allocator.release(seq.block_ids)
        seq.block_ids = []
        return data, seq.block_hashes, len(seq.prompt)

    def take_export_device(self, request_id: str):
        """Device-native export: stack the sequence's blocks into fresh
        device arrays (one fused gather, no host round-trip) and release
        them. Returns ((k_stack [L,n,BS,KVH,HD], v_stack|None), hashes,
        prompt_len) or None. The stack is independent of the cache, so it
        can await a remote pull while the blocks are reused."""
        from dynamo_tpu.llm.block_manager.transfer import gather_blocks_device

        self._refuse_unbuilt("KV export (disaggregated prefill)")
        seq = self._pending_exports.pop(request_id, None)
        self._export_deadline.pop(request_id, None)
        if seq is None:
            return None
        k_stack, v_stack = gather_blocks_device(self.cache, seq.block_ids)
        self.allocator.release(seq.block_ids)
        seq.block_ids = []
        return (k_stack, v_stack), seq.block_hashes, len(seq.prompt)

    def expire_exports(self, now: Optional[float] = None) -> int:
        """Reclaim exports nobody pulled within export_ttl_s. Returns count."""
        now = time.monotonic() if now is None else now
        expired = [rid for rid, dl in self._export_deadline.items() if dl < now]
        for rid in expired:
            seq = self._pending_exports.pop(rid, None)
            self._export_deadline.pop(rid, None)
            if seq is not None:
                self.allocator.release(seq.block_ids)
                seq.block_ids = []
        return len(expired)

    # --- helpers ------------------------------------------------------------
    def _trace_event(self, seq: Sequence, name: str, **attrs) -> None:
        """Lifecycle event on the request's trace (no-op when unsampled —
        ``seq.trace`` is only set for sampled requests, so the hot path
        pays one None check)."""
        if seq.trace is None:
            return
        self.tracer.event(
            name, seq.trace[0], parent_id=seq.trace[1], service="scheduler",
            request_id=seq.request_id, **attrs,
        )

    def attach_kvbm(self, kvbm) -> None:
        """Enable tiered offload/onboard (KVBM G2/G3) for this scheduler."""
        self._refuse_unbuilt("KVBM offload tiers")
        self.kvbm = kvbm

    def _copy_block(self, src: int, dst: int) -> None:
        """Device-side block duplication (the COW copy). One warmed
        executable; src/dst ride as traced scalars."""
        self.flight.record_exec("kv_block_copy", ())
        with self._launch("kv_block_copy"):
            self.cache.k, self.cache.v = self._kv_copy_jit(
                self.cache.k, self.cache.v, jnp.int32(src), jnp.int32(dst)
            )

    def _match_prefix_tiers(self, seq: Sequence) -> List[int]:
        """G1 match, extended through G2/G3 onboarding when KVBM is attached.
        Onboarded blocks count as hits (reuse, not recompute) — the
        allocator's G1 walk saw them as misses, so the counters are
        re-attributed here; ``prefix_onboard_total`` tracks the subset that
        crossed a tier boundary back into HBM."""
        self._refuse_unbuilt("prefix-block matching")
        if self.kvbm is None:
            return self.allocator.match_prefix(seq.block_hashes)
        match = self.kvbm.match_prefix(seq.block_hashes)
        blocks = self.kvbm.onboard(match, seq.block_hashes)
        onboarded = len(blocks) - len(match.g1_blocks)
        if onboarded > 0:
            self.prefix_onboard_total += onboarded
            self.allocator.hit_blocks_total += onboarded
            self.allocator.miss_blocks_total -= onboarded
        return blocks

    def _consume_aux(self, res):
        """Strip the moe-stats aux (when enabled) from a jitted step's result
        tuple. The aux scalars stay on device — forcing them here would add a
        host sync per step on a path that otherwise syncs once; metrics()
        drains them in a batch."""
        if self._hybrid:
            *main, self._step_aux = res
            return tuple(main)
        if not self._moe_stats:
            return res
        *main, aux = res
        self._pending_aux.append((aux["moe_dropped"], aux["moe_assignments"]))
        if len(self._pending_aux) >= 256:
            self._drain_aux()
        return tuple(main)

    def _drain_aux(self) -> None:
        if not self._pending_aux:
            return
        with self._aux_lock:
            pend, self._pending_aux = self._pending_aux, []
            vals = jax.device_get(pend)  # one transfer for the whole batch
            self._moe_dropped_total += int(sum(int(d) for d, _ in vals))
            self._moe_assignments_total += int(sum(int(a) for _, a in vals))

    def _prefill_mm_jit(self):
        """Lazy jit of the multimodal prefill variant (feature injection)."""
        if self._mm_jit is None:
            model = self._model
            uf = self._use_flash_prefill


            def prefill_mm(p, k, v, t, vl, cl, bt, hp, mf, ml):
                return model.prefill(
                    p, self.mc, k, v, t, vl, cl, bt,
                    use_flash=uf, has_prefix=hp, mm_feats=mf, mm_len=ml,
                    moe_stats=self._moe_stats,
                )

            self._mm_jit = self._jit(prefill_mm, donate_argnums=(1, 2), static_argnums=(7,))
        return self._mm_jit

    def _prefill_table(self, seq: Sequence) -> np.ndarray:
        """Prefill block table bucketed to a power-of-two width covering the
        sequence's blocks — NOT padded to max_blocks_per_seq. The prefill
        prefix gather/mask is O(width·block_size), so a 2K prompt must not
        pay for a 128K max_seq_len (measured: the dominant prefill cost at
        1B on v5e before this). Rung widths (see width_rungs) bound the
        executable count at 2·log2(max_blocks) variants per prefill
        bucket. A host array: a mixed step packs it with its other operands."""
        w = self._prompt_width(len(seq.block_ids))
        table = np.zeros((w,), dtype=np.int32)
        table[: len(seq.block_ids)] = seq.block_ids
        return table

    def _prompt_width(self, blocks: int) -> int:
        """Width of the table a prompt of ``blocks`` blocks is prefilled under:
        its rung, 16 at least. A stack of latent layers walks a chunk's pages
        up to its last row (``latent.full_chunk``: a loop with a traced bound),
        so a wider table costs it nothing and every prompt takes the widest:
        one executable a chunk bucket, none built for a rare long prompt."""
        if self.mc.is_latent:
            return self.max_blocks_per_seq
        return max(16, width_bucket(blocks, self.max_blocks_per_seq))

    def _ensure_block_capacity(self, seq: Sequence) -> None:
        """Grow the block table if the *next* token would overflow it.
        On OutOfBlocks, preempt the newest other running sequence (recompute
        preemption) and retry; only when no victim exists does the sequence
        finish with "length"."""
        bs = self.mc.block_size
        while self._rows_for(seq, seq.total_len + 1) > len(seq.block_ids) * bs:
            try:
                self._grow_table(seq, seq.total_len + 1)
                return
            except OutOfBlocksError:
                if self.sc.enable_preemption and self._preempt_for(seq):
                    continue  # victim freed blocks — retry
                # Out of memory, nobody to evict: finish with "length".
                seq.aborted = True
                seq.abort_reason = "length"
                logger.warning("seq %s out of KV blocks at len %d", seq.request_id, seq.total_len)
                return

    def _preempt_for(self, needy: Sequence) -> bool:
        """Evict the newest other running sequence: release its blocks and
        send it back to the waiting queue for recompute (ref: vLLM recompute
        preemption). Returns True if a victim was preempted."""
        candidates = [s for s in self.running if s is not needy and s.state == SeqState.RUNNING]
        if not candidates:
            return False
        victim = max(candidates, key=lambda s: s.arrival_ts)
        self.running.remove(victim)
        # Close the victim's KV accrual at the true release point: it holds
        # no blocks while waiting for recompute, so its clock stops here.
        self._accrue_kv(victim)
        victim.kv_ts = None
        self.allocator.release(victim.block_ids)
        if victim.state_slot:
            # The state goes with the blocks: the recompute starts from a zeroed slot.
            self._drop_slot(victim)
            self.ssm_preempt_recomputes_total += 1
        victim.block_ids = []
        victim.block_hashes = []
        victim.num_cached_blocks = 0
        victim.num_computed = 0
        victim.rolls = 0  # summaries are gone with the blocks: the recompute rolls again
        victim.d_n = 0  # draft cache rows are gone with the blocks
        # Recompute everything up to (not including) the last token; the
        # last token re-enters through the decode step on resume.
        victim.resume_tokens = list(victim.all_ids[:-1])
        victim.state = SeqState.WAITING
        victim.preemptions += 1
        self.preempt_total += 1
        self.waiting.insert(0, victim)
        self._trace_event(
            victim, "preempted", total_len=victim.total_len, for_request=needy.request_id
        )
        logger.info("preempted %s (len %d) to free blocks", victim.request_id, victim.total_len)
        return True

    def _apply_penalties(self, batch: List[Sequence], bucket: int, logits: jax.Array) -> jax.Array:
        """Apply frequency/presence penalties for the rows that request them
        (sampling.apply_penalties). History width buckets to powers of two so
        the executable count stays bounded as outputs grow."""
        from dynamo_tpu.engine.sampling import apply_penalties

        H = 16
        longest = max(
            (len(s.output_ids) for s in batch if s.sampling.has_penalties), default=0
        )
        while H < longest:
            H *= 2
        hist = np.zeros((bucket, H), dtype=np.int32)
        hist_len = np.zeros((bucket,), dtype=np.int32)
        freq = np.zeros((bucket,), dtype=np.float32)
        pres = np.zeros((bucket,), dtype=np.float32)
        for i, seq in enumerate(batch):
            if not seq.sampling.has_penalties or not seq.output_ids:
                continue
            n = len(seq.output_ids)
            hist[i, :n] = seq.output_ids
            hist_len[i] = n
            freq[i] = seq.sampling.frequency_penalty
            pres[i] = seq.sampling.presence_penalty
        return apply_penalties(
            logits, self._up(hist), self._up(hist_len), self._up(freq), self._up(pres)
        )

    def _key(self, seq: Optional[Sequence] = None) -> jax.Array:
        """The key of the step just counted (the engine's key folded with
        ``_step_counter``) or, for a seeded ``seq``, its own: its seed folded
        with its output position (same seed + prompt ⇒ same samples, whatever
        the batch around them). Folded on the host (fold_key) and sent up: an
        eager ``fold_in`` builds its programs on the step thread."""
        if seq is not None and seq.sampling.seed is not None:
            words = fold_key((0, seq.sampling.seed & _M32), len(seq.output_ids))  # PRNGKey(seed) is [0, seed]
        else:
            words = fold_key(self._rng_words, self._step_counter)
        return self._up(np.asarray(words, dtype=np.uint32))

    def _sample_one(self, seq: Sequence, logits: jax.Array) -> int:
        """The host path's first token of ``seq`` from its prompt's last
        logits ``[1, V]`` (a row that needs the host between logits and token:
        _needs_host; or the multimodal prefill)."""
        self._step_counter += 1
        s = seq.sampling
        if s.logits_processors:
            from dynamo_tpu.logits_processing import apply_chain

            # (User code on the host: its programs are built when first met.)
            logits = apply_chain(s.logits_processors, seq.output_ids, logits[0])[None, :]
        temp_d, top_p_d = self._up(np.asarray([s.temperature], np.float32)), self._up(np.asarray([s.top_p], np.float32))
        if seq.guided is not None:
            # First token after prefill: same fused mask+sample executable
            # as the batched path at bucket 1.
            pool = self.guided.pool.device()
            self.flight.record_exec("guided_sample", (1, int(pool.shape[0])))
            tok = self._guided_sample_jit(
                logits, pool, self._up(np.asarray([[s.top_k], [seq.guided.row_id]], np.int32)),
                temp_d, top_p_d, self._key(seq), None,  # (row_keys spelled out, as warmup spells it: the same executable)
            )
        else:
            tok = self._sample_jit(
                logits, temp_d, self._up(np.asarray([s.top_k], np.int32)), top_p_d, self._key(seq), None,
            )
        with self._span("sched.sync"):
            token = int(jax.device_get(tok)[0])
        if s.top_logprobs:
            # First token's alternatives: same op group as the batched
            # top-k path (guided rows already applied their mask above via
            # the fused sampler; these logprobs are of the raw logits the
            # single-row sampler saw).
            chosen, ids, lps = jax.device_get(self._tlp_jit(logits, tok))
            seq._pending_logprob = float(chosen[0])
            k = min(s.top_logprobs, ids.shape[1])
            seq._pending_top_logprobs = [
                (int(ids[0, j]), float(lps[0, j])) for j in range(k)
            ]
        elif s.logprobs:
            seq._pending_logprob = float(jax.device_get(self._lp_jit(logits, tok))[0])
        return token

    def _append_token(
        self,
        seq: Sequence,
        token: int,
        outputs: List[tuple],
        logprob: Optional[float] = None,
        top_logprobs: Optional[list] = None,
    ) -> None:
        if logprob is None:
            logprob = getattr(seq, "_pending_logprob", None)
            seq._pending_logprob = None
        if top_logprobs is None:
            top_logprobs = getattr(seq, "_pending_top_logprobs", None)
            seq._pending_top_logprobs = None
        seq.output_ids.append(token)
        if seq.guided is not None:
            # Host-side FSM advance: one next-state table lookup on the
            # token the step already read back — no extra device sync.
            seq.guided.advance(token)
        # First token carries the request's queue time (arrival → admission)
        # and its prefix-cache reuse (skipped prompt tokens).
        queue_s = None
        cached = None
        if len(seq.output_ids) == 1:
            if seq.admitted_ts is not None:
                queue_s = max(0.0, seq.admitted_ts - seq.arrival_ts)
                self.queue_wait_s_total += queue_s
                self.telemetry.observe("queue_wait", queue_s)
                if seq.first_token_ts is not None:
                    self.prefill_wait_s_total += max(0.0, seq.first_token_ts - seq.admitted_ts)
            self.first_tokens_total += 1
            cached = seq.cached_tokens
            ttft_s = max(0.0, (seq.first_token_ts or time.monotonic()) - seq.arrival_ts)
            self.telemetry.observe("ttft", ttft_s)
            self._trace_event(
                seq, "first_token",
                ttft_s=round(time.monotonic() - seq.arrival_ts, 6),
                cached_tokens=seq.cached_tokens,
            )
        reason = self._check_stop(seq, token)
        if reason is not None:
            # Token that triggered 'stop' is still emitted (backend strips).
            outputs.append(
                (seq, StepOutput(token_id=token, finished=True, finish_reason=reason,
                                 logprob=logprob, queue_s=queue_s, cached_tokens=cached,
                                 top_logprobs=top_logprobs))
            )
            self._finish(seq, reason, outputs, emit=False)
        else:
            outputs.append(
                (seq, StepOutput(token_id=token, logprob=logprob, queue_s=queue_s,
                                 cached_tokens=cached, top_logprobs=top_logprobs))
            )

    def _check_stop(self, seq: Sequence, token: int) -> Optional[str]:
        if seq.guided is not None and seq.guided.exhausted:
            # The FSM accepts and only EOS remains (or the cursor is done):
            # force-finish instead of burning a step to sample the EOS.
            return "stop"
        n_out = len(seq.output_ids)
        if n_out >= seq.stop.min_tokens:
            if not seq.stop.ignore_eos and token in seq.eos_token_ids:
                return "stop"
            if token in seq.stop.stop_token_ids:
                return "stop"
        if n_out >= seq.stop.max_tokens:
            return "length"
        if seq.total_len >= self.mc.max_seq_len:
            return "length"
        return None

    def _register_full_blocks(self, seq: Sequence) -> None:
        """Publish completed prompt blocks for prefix reuse. Called after
        EVERY prefill chunk, not just at prompt completion: a burst of
        same-prefix requests then shares KV mid-prefill — the second
        request's first touch matches the chunks the first has already
        computed instead of recomputing the whole prompt in parallel."""
        if not self.sc.enable_prefix_caching or not seq.block_hashes:
            return
        self._refuse_unbuilt("prefix-block registration")
        bs = self.mc.block_size
        n_full = min(seq.num_computed, len(seq.prompt)) // bs
        n_full = min(n_full, len(seq.block_hashes), len(seq.block_ids))
        if n_full > seq.num_cached_blocks:
            self.allocator.register_hashes(seq.block_ids[:n_full], seq.block_hashes[:n_full])

    # --- tenant capacity billing (runtime/ledger.py) ------------------------

    def _measured_mult(self) -> float:
        """Wall→device-seconds multiplier from the continuous profiler:
        ``measured_modeled_mfu_ratio`` is modeled/measured (= step_s /
        device_s), so device-seconds per wall second is its inverse.
        Clamped to a sane band so one noisy window can't distort bills;
        1.0 until a measured window lands."""
        snap = self.flight.measured_snapshot()
        if not snap:
            return 1.0
        r = float(snap.get("measured_modeled_mfu_ratio") or 0.0)
        if r <= 0.0:
            return 1.0
        return min(4.0, max(0.25, 1.0 / r))

    def _bill_step(self, dur_s: float, rows: List[tuple]) -> None:
        """Charge one step's wall time to its rows' bills. ``rows`` is
        [(seq, phase, tokens, kv_read_tokens)]; each row's share is its
        MARGINAL roofline weight from the step cost model (its flops +
        its KV traffic; the parameter read is batch-shared, so it's
        excluded from attribution), normalized so shares sum to dur_s
        exactly — per-step conservation — then scaled to device-seconds
        by the measured/modeled ratio when the continuous profiler has a
        live window. Also the per-step KV block-second accrual point."""
        if dur_s <= 0.0 or not rows:
            return
        cm = self.flight.cost_model
        weights: List[float] = []
        flops_rows: List[float] = []
        for _seq, _phase, tokens, kv_read in rows:
            if cm is not None:
                fl = cm.flops_per_token * tokens
                by = (kv_read * cm.kv_read_factor + tokens) * cm.kv_bytes_per_token
                w = max(fl / cm.peak_flops, by / cm.peak_bw)
            else:
                fl = 0.0
                w = float(max(tokens, 1))
            weights.append(max(w, 1e-12))
            flops_rows.append(fl)
        scale = dur_s * self._measured_mult() / sum(weights)
        now = time.monotonic()
        for (seq, phase, _tokens, _kv), w, fl in zip(rows, weights, flops_rows):
            if phase == "prefill":
                seq.bill_prefill_s += w * scale
            else:
                seq.bill_decode_s += w * scale
            seq.bill_flops += fl
            self._accrue_kv(seq, now)

    def _accrue_kv(self, seq: Sequence, now: Optional[float] = None) -> None:
        """Lazy KV block-second accrual: charge the blocks held since the
        last accrual point (step billing, preemption, finish). COW-shared
        prefix blocks sit in ``block_ids`` like any other, so every holder
        pays for the blocks it pins. Block-count growth mid-interval is
        charged at the new count for ≤ one step — negligible and cheap."""
        if now is None:
            now = time.monotonic()
        if seq.kv_ts is not None:
            seq.bill_kv_block_s += len(seq.block_ids) * (now - seq.kv_ts)
        seq.kv_ts = now if seq.block_ids else None

    def _emit_bill(self, seq: Sequence, reason: str,
                   ttft_s: Optional[float] = None,
                   tpot_s: Optional[float] = None) -> None:
        """Emit the request's RequestBill into the tenant ledger — the ONE
        choke point (finish, timeout eviction, abort reap), guarded so a
        request can never bill twice on one worker. A migrated or disagg
        request's other leg bills on ITS worker's ledger, so legs sum
        across the fleet without double-billing. Must run while the
        sequence still holds its blocks (the final KV accrual)."""
        if seq.billed:
            return
        seq.billed = True
        self._accrue_kv(seq)
        queue_end = seq.admitted_ts if seq.admitted_ts is not None else time.monotonic()
        self.ledger.record(RequestBill(
            tenant=seq.tenant,
            request_id=seq.request_id,
            queue_s=max(0.0, queue_end - seq.arrival_ts),
            prefill_device_s=seq.bill_prefill_s,
            decode_device_s=seq.bill_decode_s,
            flops=seq.bill_flops,
            output_tokens=len(seq.output_ids),
            kv_block_s=seq.bill_kv_block_s,
            finish_reason=reason,
            ttft_s=ttft_s,
            tpot_s=tpot_s,
        ))

    def _log_request(self, seq: Sequence, reason: str) -> None:
        """One record per finished request in the step log, always on (PR 2's
        sampled trace events are beside it): the stamps of its life on
        ``time.monotonic()`` — ``enqueued`` (TpuEngine.generate took it),
        ``arrival`` (add_request, after the staged wait), ``admitted``,
        ``first_token``, ``finished`` — its token counts, and the scheduler
        iterations (``sched.step`` numbers) of its first and last dispatch."""
        log = self.flight.log
        log.requests.append({
            "request_id": seq.request_id,
            "reason": reason,
            "enqueued": seq.enqueued_ts,
            "arrival": seq.arrival_ts,
            "admitted": seq.admitted_ts,
            "first_token": seq.first_token_ts,
            "finished": time.monotonic(),
            "prompt_tokens": len(seq.prompt),
            "cached_tokens": seq.cached_tokens,
            "output_tokens": len(seq.output_ids),
            "prefill_chunks": seq.prefill_chunks,
            "preemptions": seq.preemptions,
            "first_step": seq.first_step,
            "last_step": log.step,
        })

    def _finish(self, seq: Sequence, reason: str, outputs: List[tuple], emit: bool = True) -> None:
        if seq in self.running:
            self.running.remove(seq)
        seq.state = SeqState.FINISHED
        # Request-level telemetry + the SLO/goodput verdict. Cancelled and
        # errored requests are not judged (the client walked away; counting
        # them as violations would let an abort storm fake an SLO breach).
        ttft_s = tpot_s = None
        if seq.first_token_ts is not None and reason in ("stop", "length"):
            now = time.monotonic()
            ttft_s = max(0.0, seq.first_token_ts - seq.arrival_ts)
            n_out = len(seq.output_ids)
            if n_out > 1:
                tpot_s = max(0.0, now - seq.first_token_ts) / (n_out - 1)
                self.telemetry.observe("tpot", tpot_s)
            self.slo.judge(ttft_s, tpot_s, n_out)
        # Tenant ledger: the request's capacity bill, emitted while blocks
        # are still held so the KV accrual closes at the true release point.
        self._emit_bill(seq, reason, ttft_s=ttft_s, tpot_s=tpot_s)
        self._log_request(seq, reason)
        self._trace_event(
            seq, "finish", reason=reason, output_tokens=len(seq.output_ids),
            preemptions=seq.preemptions,
        )
        # Extend hashes over generated tokens so completed output blocks are
        # reusable too (multi-turn: next request's prompt includes them).
        # mm sequences never register: placeholder ids don't hash the image.
        if self.sc.enable_prefix_caching and reason != "cancelled" and seq.mm_features is None:
            bs = self.mc.block_size
            seq.block_hashes = extend_block_hashes(seq.block_hashes, seq.all_ids, bs)
            n_full = len(seq.all_ids) // bs
            self.allocator.register_hashes(seq.block_ids[:n_full], seq.block_hashes[:n_full])
        if seq.keep_blocks_on_finish and reason not in ("cancelled", "timeout"):
            # Disagg prefill role: hold blocks until the decode worker pulls
            # them (take_export); refs stay live so eviction can't touch them.
            self._pending_exports[seq.request_id] = seq
            self._export_deadline[seq.request_id] = time.monotonic() + self.sc.export_ttl_s
        else:
            self.allocator.release(seq.block_ids)
            seq.block_ids = []
        self._drop_slot(seq)
        if emit:
            outputs.append((seq, StepOutput(token_id=-1, finished=True, finish_reason=reason)))
        self.by_id.pop(seq.request_id, None)
