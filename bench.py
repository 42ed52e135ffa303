"""Benchmark suite: decode sweep, prefill/TTFT, and HTTP end-to-end.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.
The primary metric is decode tok/s/user at the flagship config (best sweep
point); ``vs_baseline`` is the **achieved fraction of this chip's HBM
roofline** for that decode step (weights+KV bytes / step time ÷ peak HBM
bandwidth) — a like-for-like bound, unlike cross-hardware comparisons (the
reference's published numbers are for 8B/70B on H100 clusters; BASELINE.md).

Failure discipline:
- No chip, no number: the measurement child exits non-zero when JAX finds
  no accelerator, and so does the orchestrator. There is no CPU fallback.
- The orchestrator (default entry) never imports jax in-process — one
  process holds the chip — and runs the measurement child under the
  wall-clock budget.
- The child emits each section's result as a ``BENCH_PARTIAL`` line the
  moment it completes, so a later hang/crash loses only later sections.

Ref anchors (BASELINE.md): decode ITL 4.83 ms (51.22 tok/s/user) for
DS-Distill-Llama-8B TP4 on H100; prefill TTFT 48.37 ms @ 3k ISL.
Ref standard for always-producing profiling flows:
docs/benchmarks/pre_deployment_profiling.md:54-84.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

PARTIAL_TAG = "BENCH_PARTIAL "


def chip_peaks(device_kind: str):
    """(peak HBM GB/s, peak bf16 TFLOP/s) of the chip JAX reports as
    ``device_kind`` — the flight recorder's table; an accelerator that is
    not in it raises."""
    from dynamo_tpu.engine.flight_recorder import peaks_for

    flops, bw = peaks_for("tpu", device_kind)
    return bw / 1e9, flops / 1e12


def param_bytes_of(params):
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


# --------------------------------------------------------------------------
# measurement sections (run inside the child)
# --------------------------------------------------------------------------

def bench_decode(cfg, params, batch, ctx_len, steps, window):
    """Multi-step-window decode (the production num_scheduler_steps path).
    Returns seconds per decode step."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import llama

    num_blocks = batch * (ctx_len // cfg.block_size + 4) + 8
    cache = KvCacheArrays.create(cfg, num_blocks=num_blocks, dtype=jnp.bfloat16)

    # Production table width: the scheduler's rung bucketing (pow2 and
    # 1.5·pow2) for a sequence ending at ctx_len + steps tokens — the
    # driver's decode number reflects what serving actually gathers.
    from dynamo_tpu.engine.scheduler import width_bucket

    needed = (ctx_len + steps + 1 + cfg.block_size - 1) // cfg.block_size
    max_blocks = width_bucket(needed, cfg.max_seq_len // cfg.block_size)
    tables = jnp.tile(jnp.arange(1, max_blocks + 1, dtype=jnp.int32)[None, :], (batch, 1))
    tables = (tables + jnp.arange(batch, dtype=jnp.int32)[:, None] * (ctx_len // cfg.block_size)) % (num_blocks - 1) + 1
    active = jnp.ones((batch,), dtype=bool)
    greedy = jnp.zeros((batch,), jnp.float32)
    top_k = jnp.zeros((batch,), jnp.int32)
    top_p = jnp.ones((batch,), jnp.float32)

    decode_window = jax.jit(
        lambda p, k, v, t, pos, key: llama.decode_multi(
            p, cfg, k, v, t, pos, tables, active, greedy, top_k, top_p, key, window
        ),
        donate_argnums=(1, 2),
    )

    import numpy as _np

    toks = jnp.zeros((batch,), dtype=jnp.int32)
    pos = jnp.full((batch,), ctx_len, dtype=jnp.int32)
    k, v = cache.k, cache.v

    # Warm until steady state: a few executions beyond the compile (how
    # many a directly attached chip needs is not measured). np.asarray is
    # the host sync: the readback waits for the device.
    for i in range(3):
        out, k, v = decode_window(params, k, v, toks, pos, jax.random.PRNGKey(0))
        _np.asarray(out)

    # Best of two timed passes (run-to-run spread is not measured on a
    # directly attached chip; ROADMAP S1 replaces this with medians).
    n_windows = max(1, steps // window)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for i in range(n_windows):
            out, k, v = decode_window(params, k, v, toks, pos + i * window, jax.random.PRNGKey(i))
        _np.asarray(out)
        best = min(best, (time.perf_counter() - t0) / (n_windows * window))
    return best


def _pallas_dispatch_overhead_ms(n: int = 32) -> float:
    """Per-``pallas_call`` dispatch overhead: a jitted chain of ``n``
    dependent no-op kernels, best-of-3, divided by ``n``. This is the tax
    the megakernel amortizes (not measured on a directly attached chip)
    — folded in from tools/profile_decode.py so it is tracked every BENCH
    round."""
    import jax
    import jax.numpy as jnp
    import numpy as _np
    from jax.experimental import pallas as pl

    def nop(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    call = pl.pallas_call(
        nop, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=jax.default_backend() != "tpu",
    )

    @jax.jit
    def chain(x):
        for _ in range(n):
            x = call(x) + 0.0  # dependency: launches serialize
        return x

    x = jnp.zeros((8, 128), jnp.float32)
    _np.asarray(chain(x))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _np.asarray(chain(x))
        best = min(best, time.perf_counter() - t0)
    return best / n * 1000.0


def _decode_attention_cpu_parity() -> dict:
    """CPU half of the decode_attention section (interpreter-mode Pallas):
    megakernel vs gather GREEDY TOKEN PARITY through the real scheduler —
    the structural guarantee CI gates on where no HBM roofline exists."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import get_config
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions

    cfg = get_config("tiny")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    def run(impl: str):
        sched = Scheduler(cfg.replace(attention_impl=impl), params, SchedulerConfig(
            num_blocks=128, max_running=4,
            prefill_buckets=[32], decode_buckets=[1, 2, 4],
            num_scheduler_steps=8, enable_prefix_caching=False,
            enable_mixed_batching=False,
        ), dtype=jnp.float32)
        toks: dict = {}
        t0 = time.perf_counter()
        for i in range(3):
            sched.add_request(f"r{i}", list(range(1 + i, 25 + i)),
                              SamplingParams(temperature=0.0),
                              StopConditions(max_tokens=16, ignore_eos=True))
        for _ in range(200):
            if not sched.has_work():
                break
            for s, o in sched.step():
                if o.token_id >= 0:
                    toks.setdefault(s.request_id, []).append(o.token_id)
        wall = time.perf_counter() - t0
        n = sum(len(v) for v in toks.values())
        return sched, toks, round(n / max(wall, 1e-9), 1)

    s_m, t_m, rate_m = run("megakernel")
    s_g, t_g, rate_g = run("gather")
    parity = t_m == t_g
    assert parity, "megakernel/gather greedy token streams diverged"
    return {
        "cpu_parity_mode": True,
        "token_parity": parity,
        "tok_s_megakernel_interp": rate_m,
        "tok_s_gather": rate_g,
        "note": "CPU: interpreter-mode Pallas — a structural assert (token "
                "parity), not speed. TPU rounds report "
                "tok/s + pct_hbm_roofline per impl.",
    }


def bench_decode_attention(cfg=None, params=None, ctx_len=1024, hbm_gbps=None):
    """Decode-attention backend tracking: gather vs megakernel at b∈{8,32}
    — tok/s, achieved HBM GB/s, pct_hbm_roofline, and the per-launch
    dispatch overhead both kernels pay. Folds tools/{ablate_decode,
    bench_decode_impl,profile_decode,profile_decode_split}.py into a
    standing BENCH_r* section so the roofline fraction is tracked every
    round instead of living in one-off tool runs. On CPU it degrades to
    the parity assert (CI)."""
    import jax

    if jax.default_backend() != "tpu":
        out = _decode_attention_cpu_parity()
        out["pallas_dispatch_ms_per_launch"] = round(_pallas_dispatch_overhead_ms(8), 3)
        return out

    if cfg is None or params is None:
        # Standalone mode (BENCH_DECODE_ATTN_ONLY) builds its own model.
        import jax.numpy as jnp

        from dynamo_tpu.engine.config import get_config
        from dynamo_tpu.engine.models import llama

        cfg = get_config(os.environ.get("BENCH_MODEL", "llama-3.2-1b")).replace(
            max_seq_len=max(4096, ctx_len + 512)
        )
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    if hbm_gbps is None:
        hbm_gbps, _ = chip_peaks(jax.devices()[0].device_kind)

    points = []
    for batch in (8, 32):
        row = {"batch": batch, "ctx": ctx_len}
        for impl in ("gather", "megakernel"):
            cfg_i = cfg.replace(attention_impl=impl)
            step_s = bench_decode(cfg_i, params, batch, ctx_len, 128, 32)
            pbytes = param_bytes_of(params)
            kv_bytes = 2 * cfg.num_layers * ctx_len * cfg.num_kv_heads * cfg.head_dim * 2 * batch
            gbps = (pbytes + kv_bytes) / step_s / 1e9
            row[impl] = {
                "step_ms": round(step_s * 1000, 3),
                "tok_s_per_chip": round(batch / step_s, 1),
                "achieved_hbm_gbps": round(gbps, 1),
                "pct_hbm_roofline": round(100 * gbps / hbm_gbps, 1) if hbm_gbps else None,
            }
        row["speedup"] = round(
            row["gather"]["step_ms"] / max(row["megakernel"]["step_ms"], 1e-9), 3
        )
        points.append(row)
    return {
        "points": points,
        "pallas_dispatch_ms_per_launch": round(_pallas_dispatch_overhead_ms(), 3),
        "note": "dispatch overhead is per pallas_call on THIS runtime — the "
                "megakernel pays it once per layer, the r4 design paid it "
                "per piece.",
    }


def bench_prefill(cfg, params, prompt_len):
    """One full prefill dispatch at the bucketed length → TTFT proxy."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import llama

    num_blocks = prompt_len // cfg.block_size + 8
    cache = KvCacheArrays.create(cfg, num_blocks=num_blocks, dtype=jnp.bfloat16)
    # Power-of-two table width — what Scheduler._prefill_table passes.
    w = 16
    while w < num_blocks - 1:
        w *= 2
    import numpy as _np

    table = jnp.asarray(_np.pad(_np.arange(1, num_blocks, dtype=_np.int32), (0, w - num_blocks + 1)))

    # Same impl choice the Scheduler makes: flash kernel on TPU, XLA else.
    use_flash = jax.default_backend() == "tpu" and cfg.prefill_impl in ("auto", "flash")
    prefill = jax.jit(
        lambda p, k, v, t: llama.prefill(
            p, cfg, k, v, t, jnp.int32(prompt_len), jnp.int32(0), table,
            use_flash=use_flash, has_prefix=False,
        ),
        donate_argnums=(1, 2),
    )
    import numpy as _np

    toks = jnp.arange(prompt_len, dtype=jnp.int32) % 1000
    logits, k, v = prefill(params, cache.k, cache.v, toks)
    _np.asarray(logits[:4])  # real host sync (see bench_decode)

    iters = 8
    t0 = time.perf_counter()
    for _ in range(iters):
        logits, k, v = prefill(params, k, v, toks)
    _np.asarray(logits[:4])
    return (time.perf_counter() - t0) / iters


def bench_tpu_http(n_requests=64, concurrency=32, tokens_out=32, isl=96):
    """Full serving stack with the FLAGSHIP model on the real chip: HTTP →
    preprocess → scheduler (TPU decode windows) → detokenize → SSE. The r4
    artifact measured the engine on TPU and the serving plane on CPU, never
    both — this section carries the combined number (served tok/s vs the
    raw decode rate at the same batch). Shapes are pinned (one prefill
    bucket, one decode bucket) and warmed by live requests so the section
    compiles a handful of executables, not a full warmup grid."""
    import asyncio

    async def run():
        import aiohttp

        from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
        from dynamo_tpu.engine.scheduler import SchedulerConfig
        from dynamo_tpu.llm.discovery import ModelManager
        from dynamo_tpu.llm.entrypoint import build_local_pipeline
        from dynamo_tpu.llm.http.service import HttpService
        from dynamo_tpu.llm.tokenizer import ByteTokenizer

        model = os.environ.get("BENCH_MODEL", "llama-3.2-1b")
        engine = TpuEngine.build(
            EngineArgs(
                model=model,
                scheduler=SchedulerConfig(
                    num_blocks=1024,
                    max_running=concurrency,
                    prefill_buckets=[256],
                    max_prefill_chunk=256,
                    decode_buckets=[concurrency],
                ),
            )
        )
        manager = ModelManager()
        manager.add_model("chat", "bench-1b", build_local_pipeline(ByteTokenizer(), engine))
        svc = HttpService(manager, host="127.0.0.1", port=0)
        await svc.start()
        url = f"http://127.0.0.1:{svc.port}/v1/chat/completions"
        prompt = "x" * isl

        async def one(session, i):
            body = {
                "model": "bench-1b",
                "messages": [{"role": "user", "content": prompt}],
                "max_tokens": tokens_out,
                "stream": True,
            }
            t0 = time.perf_counter()
            ttft = None
            t_last = None
            nchars = 0
            async with session.post(url, json=body) as resp:
                async for line in resp.content:
                    if not line.startswith(b"data:"):
                        continue
                    idx = line.find(b'"content": "')
                    if idx >= 0 and not line.startswith(b'"', idx + 12):
                        now = time.perf_counter()
                        if ttft is None:
                            ttft = now - t0
                        t_last = now
            itl = None
            if ttft is not None and t_last is not None and tokens_out > 1:
                # Approximate per-token latency assuming the request ran to
                # max_tokens (greedy random-weight models essentially never
                # emit EOS early); counting chars breaks on JSON-escaped
                # bytes, so the budget is the honest denominator.
                itl = (t_last - (t0 + ttft)) / (tokens_out - 1)
            return ttft, itl

        async with aiohttp.ClientSession(connector=aiohttp.TCPConnector(limit=0)) as session:
            # Live-request warmup: compiles prefill(256) + the window rungs
            # and single-step decode at this batch bucket (first pass is
            # XLA compile, second is executable steady-state).
            for _ in range(2):
                await asyncio.gather(*[one(session, -i) for i in range(concurrency)])
            sem = asyncio.Semaphore(concurrency)

            async def guarded(i):
                async with sem:
                    return await one(session, i)

            t0 = time.perf_counter()
            results = await asyncio.gather(*[guarded(i) for i in range(n_requests)])
            wall = time.perf_counter() - t0
        await svc.stop()
        await engine.stop()
        ttfts = sorted(t for t, _ in results if t is not None)
        itls = sorted(i for _, i in results if i is not None)
        return {
            "model": model,
            "req_s": round(n_requests / wall, 2),
            "tok_s": round(n_requests * tokens_out / wall, 1),
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1000, 1) if ttfts else None,
            "itl_p50_ms": round(itls[len(itls) // 2] * 1000, 2) if itls else None,
            "concurrency": concurrency,
            "tokens_out": tokens_out,
            "isl": isl,
        }

    return asyncio.run(run())


def bench_http_e2e(n_requests=48, concurrency=12, tokens_out=16):
    """End-to-end serving stack: real HTTP frontend → preprocessor →
    scheduler → detokenize → SSE, tiny model (measures the serving plane,
    not the TPU). Ref: benchmarks/llm/perf.sh genai-perf concurrency sweep."""
    import asyncio

    async def run():
        import aiohttp

        from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
        from dynamo_tpu.engine.scheduler import SchedulerConfig
        from dynamo_tpu.llm.discovery import ModelManager
        from dynamo_tpu.llm.entrypoint import build_local_pipeline
        from dynamo_tpu.llm.http.service import HttpService
        from dynamo_tpu.llm.tokenizer import ByteTokenizer

        engine = TpuEngine.build(
            EngineArgs(
                model="tiny",
                scheduler=SchedulerConfig(num_blocks=1024, max_running=64,
                                          prefill_buckets=[32, 64, 128],
                                          # max_running/top bucket cover the
                                          # sweep's top concurrency: with 32
                                          # slots the conc-64 level queued
                                          # half its requests a full request
                                          # duration (r05: TTFT p50 242 ms);
                                          # mixed steps + wave admission keep
                                          # the wider batch fed without
                                          # prefill stalls.
                                          decode_buckets=[1, 2, 4, 8, 16, 32, 64],
                                          # Single-step: windows amortize
                                          # DISPATCH cost, which a local CPU
                                          # engine doesn't pay — a 32-step
                                          # window just overshoots 16-token
                                          # requests and serializes the batch
                                          # (measured: 6.1 -> 5.4 req/s).
                                          num_scheduler_steps=1),
                # Precompile: the serving measurement must not time XLA.
                # 160 covers the sweep's real contexts (~70-token templated
                # prompt + 16 out → width rung 6): at 64 the width-6 decode
                # executables compiled mid-traffic and the first high-
                # concurrency level timed XLA, not serving (measured: first
                # b64 level p50 252 ms, second 90 ms).
                warmup_ctx=160,
            )
        )
        manager = ModelManager()
        manager.add_model("chat", "bench-tiny", build_local_pipeline(ByteTokenizer(), engine))
        svc = HttpService(manager, host="127.0.0.1", port=0)
        await svc.start()
        url = f"http://127.0.0.1:{svc.port}/v1/chat/completions"

        async def one(session, i):
            body = {
                "model": "bench-tiny",
                "messages": [{"role": "user", "content": f"benchmark request {i} padding padding"}],
                "max_tokens": tokens_out,
                "stream": True,
            }
            t0 = time.perf_counter()
            ttft = None
            async with session.post(url, json=body) as resp:
                async for line in resp.content:
                    # Client parsing shares the single core with the server
                    # under test — a json.loads per SSE line throttled the
                    # SERVER to ~6 req/s (measured: 6 -> 34 req/s from the
                    # client fix alone). TTFT = first chunk carrying content
                    # (the stream opens with a content-less role chunk);
                    # detect it with a byte scan, parse nothing.
                    if ttft is None and line.startswith(b"data:"):
                        idx = line.find(b'"content": "')
                        # match a NON-EMPTY content delta (the stream opens
                        # with a role chunk whose content is "")
                        if idx >= 0 and not line.startswith(b'"', idx + 12):
                            ttft = time.perf_counter() - t0
            return ttft

        async def level(session, conc, n):
            sem = asyncio.Semaphore(conc)

            async def guarded(i):
                async with sem:
                    return await one(session, i)

            # First-token latency decomposition from the engine's own
            # accounting: queue (arrival→admission) + prefill (admission→
            # first token) sums, and the decode-phase step time from the
            # flight recorder — where a level's TTFT actually goes.
            sched = engine.scheduler
            q0, p0, f0 = sched.queue_wait_s_total, sched.prefill_wait_s_total, sched.first_tokens_total
            dh = sched.flight._hists["decode"]
            d_t0, d_n0 = dh.sum_s, dh.total
            t0 = time.perf_counter()
            ttfts = await asyncio.gather(*[guarded(i) for i in range(n)])
            wall = time.perf_counter() - t0
            firsts = max(sched.first_tokens_total - f0, 1)
            breakdown = {
                "queue_ms_mean": round(1000 * (sched.queue_wait_s_total - q0) / firsts, 2),
                "prefill_ms_mean": round(1000 * (sched.prefill_wait_s_total - p0) / firsts, 2),
                "decode_step_ms_mean": round(
                    1000 * (dh.sum_s - d_t0) / max(dh.total - d_n0, 1), 3
                ),
            }
            ttfts = sorted(t for t in ttfts if t is not None)
            p50 = ttfts[len(ttfts) // 2] if ttfts else None
            return {
                "concurrency": conc,
                "req_s": round(n / wall, 2),
                "tok_s": round(n * tokens_out / wall, 1),
                "ttft_p50_ms": round(p50 * 1000, 1) if p50 else None,
                "breakdown": breakdown,
            }

        # genai-perf-style concurrency sweep (ref: benchmarks/llm/perf.sh):
        # throughput vs concurrency exposes the serving plane's knee.
        async with aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0)
        ) as session:
            # Warmup: compiles + first-execution costs across the batch
            # buckets the sweep will hit (cold executables polluted the
            # first level by ~6x when warmed with a single request).
            await asyncio.gather(*[one(session, -i) for i in range(1, 17)])
            sweep = []
            for conc in (concurrency, 64):
                if sweep and sweep[-1]["concurrency"] >= conc:
                    continue
                sweep.append(await level(session, conc, max(n_requests, 3 * conc)))

        sched = engine.scheduler
        mixed = {
            "steps": sched.mixed_steps_total,
            "prefill_tokens": sched.mixed_prefill_tokens_total,
            "decode_tokens": sched.mixed_decode_tokens_total,
        }
        await svc.stop()
        await engine.stop()
        best = max(sweep, key=lambda p: p["req_s"])
        return {
            **best, "sweep": sweep, "mixed": mixed,
            "admission_tuning": {
                "note": "per-level breakdown (queue/prefill/decode) drove the "
                        "max_running default 16→32: at conc 64 with 16 slots "
                        "the queue term was 292 ms of a 393 ms TTFT p50 "
                        "(prefill 20 ms); 32 slots measured +53% req/s and "
                        "halved p50; 64 zeroes queueing but shifts 60 ms "
                        "into batched prefill waves — the sweep here runs "
                        "max_running=concurrency for the knee itself",
            },
        }

    return asyncio.run(run())


def bench_mixed_admission():
    """Mixed prefill+decode steps, measured at the scheduler (no HTTP): a
    long prompt arrives while a decode wave runs. Phase-separated
    scheduling dispatches the whole prompt as one stall between decode
    steps; mixed steps carry mixed_prefill_budget-token chunks inside the
    decode dispatch. Reports the decode wave's worst inter-token gap and
    the newcomers' TTFT, mixed on vs off, plus the per-step composition
    counters the scheduler now exports."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import get_config
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions

    cfg = get_config("tiny").replace(max_seq_len=4096)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    def run(mixed: bool) -> dict:
        sched = Scheduler(cfg, params, SchedulerConfig(
            num_blocks=768, max_running=16,
            prefill_buckets=[32, 64, 128, 256, 512, 1024],
            decode_buckets=[1, 2, 4, 8, 16],
            num_scheduler_steps=1, enable_prefix_caching=False,
            enable_mixed_batching=mixed,
        ), dtype=jnp.float32)
        for i in range(8):
            sched.add_request(f"d{i}", list(range(1, 33)),
                              SamplingParams(temperature=0.0), StopConditions(max_tokens=400))
        for _ in range(12):  # decode wave warm + executables compiled
            sched.step()
        # Warm the long-prompt shapes too so the gap measures scheduling,
        # not XLA compiles, for both modes.
        sched.add_request("warm", list(range(3, 1027)),
                          SamplingParams(temperature=0.0), StopConditions(max_tokens=2))
        for _ in range(40):
            sched.step()

        t0 = time.perf_counter()
        sched.add_request("long", list(range(5, 1029)),
                          SamplingParams(temperature=0.0), StopConditions(max_tokens=4))
        sched.add_request("short", list(range(7, 39)),
                          SamplingParams(temperature=0.0), StopConditions(max_tokens=4))
        long_ttft = short_ttft = None
        last_decode = t0
        max_gap = 0.0
        for _ in range(400):
            outs = sched.step()
            now = time.perf_counter()
            if any(s.request_id.startswith("d") and o.token_id >= 0 for s, o in outs):
                max_gap = max(max_gap, now - last_decode)
                last_decode = now
            for s, o in outs:
                if o.token_id >= 0 and s.request_id == "long" and long_ttft is None:
                    long_ttft = now - t0
                if o.token_id >= 0 and s.request_id == "short" and short_ttft is None:
                    short_ttft = now - t0
            if long_ttft is not None and short_ttft is not None:
                break
        return {
            "enable_mixed_batching": mixed,
            "long_ttft_ms": round(long_ttft * 1000, 2) if long_ttft else None,
            "short_ttft_ms": round(short_ttft * 1000, 2) if short_ttft else None,
            "decode_max_gap_ms": round(max_gap * 1000, 2),
            "mixed_steps": sched.mixed_steps_total,
            "mixed_prefill_tokens": sched.mixed_prefill_tokens_total,
            "mixed_decode_tokens": sched.mixed_decode_tokens_total,
        }

    on = run(True)
    off = run(False)
    return {
        "mixed_on": on,
        "mixed_off": off,
        "isl": 1024,
        "decode_stall_ratio": round(off["decode_max_gap_ms"] / max(on["decode_max_gap_ms"], 1e-3), 2),
        "note": "tiny model on CPU — scheduling structure, not device speed; "
                "decode_max_gap is the worst stall a 1K prefill injects into "
                "an active 8-wide decode wave",
    }


def bench_prefix_reuse():
    """Automatic prefix caching, measured at the REAL engine: KV-aware
    routing vs round-robin over two live Schedulers (tiny model). Groups of
    requests share the leading 0.9 of their prompts under cache pressure
    (one worker's pool holds ~half the group prefixes). KV-aware routing
    pins each group to its home worker, where the engine's prefix cache
    turns the hint into SKIPPED prefill FLOPs — the suffix chunk is all
    that computes; round-robin cycles groups across workers, evicting and
    re-prefilling. Reports mean TTFT per policy, the engine-reported
    cached_tokens (asserted equal to the blocks the allocator actually
    served from cache × block_size), and the post-warmup compile count
    (the 0-compile invariant must hold with prefix caching enabled)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import get_config
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions
    from dynamo_tpu.llm.kv_router.indexer import KvIndexer
    from dynamo_tpu.llm.kv_router.scheduler import KvScheduler
    from dynamo_tpu.llm.kv_router.sequence import ActiveSequencesMultiWorker
    from dynamo_tpu.llm.tokens import compute_block_hashes

    cfg = get_config("tiny").replace(max_seq_len=4096)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    bs = cfg.block_size
    ISL, RATIO, GROUPS, WORKERS, OSL = 1024, 0.9, 4, 2, 2
    # Pool sizing: one worker holds ~2 of the 4 group prefixes (+ working
    # set); all 4 never fit — round-robin's cycling must actually evict.
    num_blocks = 192

    import random as _random

    rng = _random.Random(7)
    shared = [[rng.randrange(1, 30000) for _ in range(int(ISL * RATIO))] for _ in range(GROUPS)]

    def make_prompt(g):
        return shared[g] + [rng.randrange(1, 30000) for _ in range(ISL - len(shared[g]))]

    def run(policy: str) -> dict:
        workers = []
        indexer = KvIndexer(block_size=bs)
        for w in range(WORKERS):
            sched = Scheduler(
                cfg, params,
                SchedulerConfig(
                    # Sequential single-request serving: decode bucket 1
                    # only, mixed steps off — keeps the warmup grid
                    # (2 workers × every shape) CPU-affordable while the
                    # serving-hot prefill buckets stay real.
                    num_blocks=num_blocks, max_running=8,
                    prefill_buckets=[128, 256, 512, 1024],
                    decode_buckets=[1], num_scheduler_steps=1,
                    enable_mixed_batching=False,
                ),
                dtype=jnp.float32,
                on_kv_event=lambda ev, w=w: indexer.apply_event(w, ev.to_wire()),
            )
            sched.warmup(ISL + 64)
            sched.flight.mark_warmup_done(warmed=True)
            workers.append(sched)
        router = KvScheduler(ActiveSequencesMultiWorker(block_size=bs))

        order = [i % GROUPS for i in range(GROUPS * 6)]
        rng2 = _random.Random(11)
        rng2.shuffle(order)
        ttfts = []
        cached_total = 0
        accounting_exact = True
        for i, g in enumerate(order):
            prompt = make_prompt(g)
            if policy == "kv":
                hashes = compute_block_hashes(prompt, bs)
                decision = router.select_worker(
                    list(range(WORKERS)), (len(prompt) + bs - 1) // bs,
                    indexer.find_matches(hashes),
                )
                w = decision.worker
            else:
                w = i % WORKERS
            sched = workers[w]
            rid = f"{policy}-{i}"
            hits_before = sched.allocator.hit_blocks_total
            sched.add_request(
                rid, prompt, SamplingParams(temperature=0.0),
                StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            t0 = time.perf_counter()
            ttft = None
            cached = 0
            while sched.has_work():
                for s, o in sched.step():
                    if s.request_id == rid and o.token_id >= 0 and ttft is None:
                        ttft = time.perf_counter() - t0
                        cached = o.cached_tokens or 0
            ttfts.append(ttft)
            cached_total += cached
            # Engine-reported cached_tokens must equal the blocks the
            # allocator actually served from cache (full-cover hits report
            # n·bs − 1: one token recomputes to produce logits).
            matched = sched.allocator.hit_blocks_total - hits_before
            if cached not in (matched * bs, max(0, matched * bs - 1)):
                accounting_exact = False
        # Each group's first occurrence is cold establishment (identical per
        # policy); drop them from the mean.
        seen: set = set()
        warm_ttfts = []
        for g, t in zip(order, ttfts):
            if g in seen:
                warm_ttfts.append(t)
            seen.add(g)
        return {
            "ttft_mean_ms": round(1000 * sum(warm_ttfts) / max(len(warm_ttfts), 1), 2),
            "cached_tokens": cached_total,
            "cached_matches_blocks": accounting_exact,
            "compiles_after_warmup": sum(
                s.flight.compiles_after_warmup_total for s in workers
            ),
        }

    kv = run("kv")
    rr = run("rr")
    return {
        "isl": ISL, "prefix_ratio": RATIO, "groups": GROUPS, "workers": WORKERS,
        "worker_blocks": num_blocks,
        "kv": kv, "rr": rr,
        "speedup": round(rr["ttft_mean_ms"] / max(kv["ttft_mean_ms"], 1e-9), 2),
        "note": "tiny model on CPU, sequential requests (no queueing): the "
                "ratio is skipped prefill FLOPs — the engine-level win the "
                "KV router's hint now buys. Real-chip prefill is faster in "
                "absolute terms; the skipped fraction is the same.",
    }


def bench_observability_overhead():
    """Tracing + flight-recorder + telemetry + INCIDENT-PLANE cost at the
    scheduler (no HTTP): steady decode throughput with tracing disabled vs
    fully sampled (sample=1.0, JSONL export live, trace ring + tail keep
    armed). The digests, SLO judge, FLOPs/bytes roofline model, stall
    watchdog, anomaly detector (polled at the production scrape cadence),
    the host stack sampler, and the tenant capacity ledger (every request
    billed to a tenant) are LIVE in both phases — they are always-on in
    production — so the section proves the whole diagnosis plane rides
    inside the budget. The acceptance bar is ≤2%
    token-throughput cost at the bench knee with 0 post-warmup compiles."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import get_config
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions
    from dynamo_tpu.runtime.incidents import IncidentConfig, IncidentPlane
    from dynamo_tpu.runtime.profiling import (
        ContinuousProfileConfig,
        ContinuousProfiler,
        DeviceProfiler,
        HostStackSampler,
    )
    from dynamo_tpu.runtime.telemetry import StallWatchdog
    from dynamo_tpu.runtime.tracing import configure_tracing, get_tracer

    cfg = get_config("tiny").replace(max_seq_len=4096)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rounds = 3

    # One JSONL-exporting tracer for the whole section; the "off" scheduler
    # simply has no per-sequence trace tuples (the production off-path: one
    # None check per event site).
    trace_path = tempfile.mktemp(prefix="bench_trace_", suffix=".jsonl")
    # Incident bundles land in the CI artifact dir when set (failures ship
    # their own black box), else a scratch dir.
    incident_dir = os.environ.get("DYN_INCIDENT_DIR") or tempfile.mkdtemp(
        prefix="bench_incidents_"
    )

    phase_counter = [0]

    def measure(sched, traced: bool) -> float:
        # Each measurement is a FULL identical batch (admission → decode →
        # finish) on the same long-lived scheduler: the per-request trace
        # tuple is the production on/off switch, and reusing one scheduler
        # removes instance-to-instance confounders (allocation layout,
        # build order) while the fixed batch shape removes context-growth
        # drift between phases.
        phase_counter[0] += 1
        p = phase_counter[0]
        tokens = 0
        t0 = time.perf_counter()
        for i in range(8):
            sched.add_request(
                f"p{p}r{i}", list(range(1 + (p + i) % 8, 33 + (p + i) % 8)),
                SamplingParams(temperature=0.0), StopConditions(max_tokens=80),
                trace=(f"{p:016x}{i:016x}", f"{i:016x}") if traced else None,
                # Tenant ledger armed in BOTH phases (it is always-on in
                # production): every request bills to one of two tenants.
                tenant=f"bench-t{i % 2}",
            )
        while sched.has_work():
            tokens += sum(1 for _, o in sched.step() if o.token_id >= 0)
        return tokens / (time.perf_counter() - t0)

    from dynamo_tpu.runtime import faults as _faults

    try:
        # Full plane armed: ring black box + tail keep on top of the live
        # JSONL export (tail is the worst case — every record also lands
        # in the ring).
        configure_tracing(path=trace_path, sample=1.0, service="bench",
                          ring_size=256, tail=True)
        # Chaos plane armed-but-idle: the injector is live (the production
        # posture during a drill window) with a spec that can never match,
        # so every planted site pays its armed-path cost while zero faults
        # fire. The budget + 0-compile assertions below hold regardless.
        _faults.arm(_faults.FaultInjector(
            [{"site": "worker.frame", "kind": "stream_drop",
              "match": {"request_id": "bench-never-matches"}}], seed=0,
        ))
        # SLO targets set so the per-finish judge actually runs; digests +
        # roofline model are unconditionally live in the scheduler.
        sched = Scheduler(cfg, params, SchedulerConfig(
            num_blocks=768, max_running=8,
            prefill_buckets=[32, 64, 128], decode_buckets=[1, 2, 4, 8],
            num_scheduler_steps=1, enable_prefix_caching=False,
            slo_ttft_ms=1000.0, slo_tpot_ms=100.0,
        ), dtype=jnp.float32)
        watchdog = StallWatchdog(
            probe=lambda: (sched.has_work(), sched.flight.last_step_ts),
            stall_after_s=120.0,
        )
        # Incident autopsy plane over the scheduler's own stats surface —
        # detector + recorder polled at the production scrape cadence.
        plane = IncidentPlane(
            IncidentConfig(dir=incident_dir),
            state_probe=sched.debug_state,
            flight_probe=sched.flight.ring_snapshot,
            config_probe=sched.config_snapshot,
        )

        def sched_stats() -> dict:
            s = dict(sched.flight.to_stats())
            s.update(sched.slo.to_stats())
            s["digests"] = sched.telemetry.to_wire()
            return s

        # Host stack sampler armed for the whole measured section at its
        # production period.
        sampler = HostStackSampler(interval_s=0.005)
        sampler.start()
        # Continuous device-truth sampler armed at the DEFAULT duty cycle
        # (0.25 s window / 30 s interval): the production posture. At this
        # cadence it idles through the section — the point is that an armed
        # sampler thread + its due()-polling loop ride inside the same ≤2%
        # budget with zero errors, not that a window fires mid-bench.
        cont = ContinuousProfiler(
            DeviceProfiler(out_dir=tempfile.mkdtemp(prefix="bench_prof_")),
            ContinuousProfileConfig(),
            cost_probe=sched.flight.roofline_totals,
            sink=sched.flight.record_measured_window,
        )
        cont.start()
        measure(sched, False)  # admission-wave + decode executable warmup
        # The warmup measurement compiled every serving shape this section
        # touches: from here, compiles are the 0-post-warmup invariant.
        sched.flight.mark_warmup_done(warmed=True)
        # Round-interleaved best-of-N: warm-up drift hits both modes equally.
        best_off = best_on = 0.0
        for _ in range(rounds):
            best_off = max(best_off, measure(sched, False))
            best_on = max(best_on, measure(sched, True))
            watchdog.check()  # the production poll cadence rides along
            plane.observe(sched_stats())  # detector check per scrape
        sampler_armed = cont.armed
        cont.stop()
        cont_stats = cont.to_stats()
        assert sampler_armed, "continuous profiler thread died mid-section"
        assert cont_stats["device_profile_errors_total"] == 0, (
            f"continuous profiler errored during the bench: {cont_stats}"
        )
        assert cont_stats["device_profile_duty_cycle"] <= 0.02, (
            f"default duty cycle above the 2% clamp: {cont_stats}"
        )
        sampler.stop()
        sampler_report = sampler.report(top=5)
        plane_stats = plane.to_stats()
        tracer = get_tracer()
        ring_records = len(tracer.ring_records())
        tracer.flush()
        off = {"traced": False, "tok_s": round(best_off, 1),
               "rounds": rounds, "trace_records": 0}
        on = {"traced": True, "tok_s": round(best_on, 1),
              "rounds": rounds, "trace_records": tracer.events_written}
        digest_counts = {
            name: sched.telemetry.digest(name).total.count
            for name in sched.telemetry.names()
        }
        compiles_after_warmup = sched.flight.compiles_after_warmup_total
        slo_judged = sched.slo.requests_total
        faults_injected = _faults.get_injector().injected_total
        assert faults_injected == 0, (
            f"armed-but-idle fault injector fired {faults_injected} times"
        )
        # Tenant ledger armed throughout: every request billed, both
        # tenants tracked, and the charged device-seconds conserve (Σ
        # tracked + other = exact total — nothing leaks the sketch).
        ledger_wire = sched.ledger.to_wire()
        assert ledger_wire["bills"] == phase_counter[0] * 8, (
            f"ledger billed {ledger_wire['bills']} of {phase_counter[0] * 8} requests"
        )
        from dynamo_tpu.runtime.ledger import SpaceSaving as _SpaceSaving

        _tracked = {t for t, _, _ in _SpaceSaving.from_wire(
            ledger_wire["sketches"]["device_seconds"]).items()}
        assert _tracked == {"bench-t0", "bench-t1"}, _tracked
        assert ledger_wire["totals"]["device_seconds"] > 0.0
        assert plane.to_stats()["incidents_total"] == 0, (
            "calm bench traffic fired a false incident"
        )
    finally:
        _faults.disarm()
        configure_tracing(path=None, sample=0.0)  # leave the process clean
    overhead_pct = round(100.0 * (off["tok_s"] - on["tok_s"]) / max(off["tok_s"], 1e-9), 2)

    # Static cross-check with the dtlint SYNC001 allowlist: the telemetry/
    # stats plane (metrics, kv_gauges, debug_state — what this section
    # exercises alongside traffic) must declare ZERO sanctioned blocking
    # syncs. A sync sneaking into a stats path shows up twice: dtlint
    # fails statically, and this section's overhead budget pays for it
    # dynamically. The one deliberate exception (the batched MoE aux
    # drain) lives in dtlint_baseline.json, not the allowlist.
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tools", "dtlint", "sync_allowlist.json")) as f:
        _allow = json.load(f)
    stats_funcs = {"Scheduler.metrics", "Scheduler.kv_gauges", "Scheduler.debug_state"}
    stats_path_syncs = [e for e in _allow["allowed_syncs"] if e["func"] in stats_funcs]
    assert stats_path_syncs == [], (
        f"sync_allowlist sanctions blocking syncs in stats paths: {stats_path_syncs}"
    )
    hot = _allow["hot_paths"].get("dynamo_tpu/engine/scheduler.py", [])
    assert stats_funcs <= set(hot), (
        "scheduler stats paths fell out of the SYNC001 hot-path scope"
    )

    # Static cross-check with dtlint WARM001: the executable keys that
    # compiled during this section (all pre-mark_warmup_done, per the
    # 0-compile assert above) must be inside the statically enumerated
    # warmup key space — the recorder's dynamic view and the linter's
    # static view of "what warmup must cover" stay pinned to each other.
    from tools.dtlint.rules_warmup import static_warmup_report

    _static = static_warmup_report(os.path.dirname(os.path.abspath(__file__)))
    _dynamic = sched.flight.exec_key_summary()
    for _kind, _arities in _dynamic.items():
        assert _kind in _static["warmed"], (
            f"recorder compiled kind '{_kind}' missing from WARM001's "
            f"static warmup enumeration"
        )
        _sar = set(_static["warmed"][_kind])
        assert not _sar or set(_arities) <= _sar, (
            f"kind '{_kind}' compiled at arities {_arities}; static warmup "
            f"registers {sorted(_sar)}"
        )

    return {
        "tracing_off": off,
        "tracing_on": on,
        "overhead_pct": overhead_pct,
        "budget_pct": 2.0,
        "within_budget": overhead_pct <= 2.0,
        # Telemetry-plane proof points: the digests/SLO judge observed real
        # traffic in BOTH phases, the watchdog polled, and none of it
        # dispatched to the device (0 compiles after warmup).
        "digest_counts": digest_counts,
        "slo_judged_requests": slo_judged,
        "compiles_after_warmup": compiles_after_warmup,
        "stats_path_allowed_syncs": 0,
        "warmup_views": {
            "static_warmed_kinds": sorted(_static["warmed"]),
            "dynamic_exec_kinds": sorted(_dynamic),
            "agree": True,
        },
        # Chaos plane armed for the whole measured section with a
        # never-matching scenario: the armed-path site cost rides inside
        # the same ≤2% budget, and zero injections fired (asserted).
        "faults_armed_idle": {"armed": True, "injected": faults_injected},
        # Continuous device-truth sampler armed at the default duty cycle
        # for the whole measured section (asserted above: thread alive,
        # zero errors, duty ≤ 2%).
        "continuous_profiler": {"armed": True, **cont_stats},
        # Tenant capacity ledger armed in both phases: every request billed
        # to one of two tenants, charges conserved, zero extra compiles —
        # attribution is pure host arithmetic riding the same ≤2% budget.
        "tenant_ledger": {
            "armed": True,
            "bills": ledger_wire["bills"],
            "tenants_tracked": sorted(_tracked),
            "device_seconds": round(ledger_wire["totals"]["device_seconds"], 4),
            "kv_block_seconds": round(ledger_wire["totals"]["kv_block_seconds"], 4),
        },
        # Incident autopsy plane armed for the whole section: detector
        # polled per round, trace ring + tail keep live, host stack
        # sampler running at its production period. Calm traffic must not
        # fire (a false positive here is a detector bug worth failing on).
        "incident_plane": {
            "detector_checks": plane.detector.checks_total,
            "incidents": plane_stats["incidents_total"],
            "trace_ring_records": ring_records,
            "host_sampler_samples": sampler_report["samples"],
            "host_sampler_scheduler_share": sampler_report["scheduler_share"],
            "incident_dir": incident_dir,
        },
        "note": "tiny model on CPU, sample=1.0 with live JSONL export, trace "
                "ring + tail keep + anomaly detector + host stack sampler all "
                "armed — the worst case; production sampling (e.g. 0.1) costs "
                "proportionally less. Digests + SLO judge + roofline model "
                "+ watchdog are live in both phases.",
    }


def bench_guided_overhead():
    """Guided decoding cost at the scheduler: steady greedy decode
    throughput with every row unmasked vs every row grammar-masked
    (the fused mask-gather+sample dispatch + the host-side FSM advance).
    Interleaved best-of-N on one long-lived scheduler, same discipline as
    observability_overhead. Budget: ≤5% per-step decode overhead. Also
    reports grammar→token-FSM compile latency for a realistic tool schema
    (the per-first-request cost the LRU cache amortizes away)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import get_config
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions
    from dynamo_tpu.llm.guided.processor import GuidedDecoder
    from dynamo_tpu.llm.tokenizer import ByteTokenizer

    cfg = get_config("tiny").replace(max_seq_len=4096)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rounds = 3
    # Never-accepting within the run (500+ chars required, 80 emitted), so
    # masked rows decode the full budget — pure steady-state mask cost.
    pattern = "[ab]{500,}"
    spec = {"kind": "regex", "pattern": pattern}

    sched = Scheduler(cfg, params, SchedulerConfig(
        num_blocks=768, max_running=8,
        prefill_buckets=[32, 64, 128], decode_buckets=[1, 2, 4, 8],
        num_scheduler_steps=1, enable_prefix_caching=False,
        guided_pool_rows=1024,
    ), dtype=jnp.float32)
    sched.attach_guided(ByteTokenizer())

    phase_counter = [0]

    def measure(guided: bool) -> float:
        """Steady-state decode-step throughput from the flight recorder's
        decode-phase histogram: admit all 8 rows first, then measure only
        full-batch decode steps. The subject is the per-STEP cost of the
        fused mask-gather+sample dispatch plus the host FSM advance —
        admission structure (guided rows are wave-ineligible by design)
        and batch ramp-down tails are excluded from both phases alike."""
        phase_counter[0] += 1
        p = phase_counter[0]
        for i in range(8):
            sched.add_request(
                f"p{p}r{i}", list(range(1 + (p + i) % 8, 33 + (p + i) % 8)),
                SamplingParams(temperature=0.0), StopConditions(max_tokens=200),
                guided=spec if guided else None,
            )
        while sched.waiting:
            sched.step()
        h = sched.flight._hists["decode"]
        t_before, n_before = h.sum_s, h.tokens
        while len(sched.running) == 8 and sched.has_work():
            sched.step()
        tok_s = (h.tokens - n_before) / max(h.sum_s - t_before, 1e-9)
        while sched.has_work():  # drain the tail unmeasured
            sched.step()
        return tok_s

    measure(False)  # executable warmup (admission wave + decode)
    measure(True)   # guided-sampler + grammar warmup
    best_off = best_on = 0.0
    for _ in range(rounds):
        best_off = max(best_off, measure(False))
        best_on = max(best_on, measure(True))

    # Grammar→token-FSM compile latency for a realistic tool schema (fresh
    # decoder: no LRU hit), plus the cached re-open cost.
    tool_schema = {
        "type": "object",
        "properties": {
            "location": {"type": "string", "maxLength": 64},
            "unit": {"enum": ["celsius", "fahrenheit"]},
            "days": {"type": "integer"},
            "include_hourly": {"type": "boolean"},
        },
    }
    from dynamo_tpu.llm.guided.grammar import schema_to_regex

    tool_spec = {"kind": "regex", "pattern": schema_to_regex(tool_schema)}
    dec = GuidedDecoder(ByteTokenizer(), eos_ids=[0], vocab_size=cfg.vocab_size)
    t0 = time.perf_counter()
    st = dec.open(tool_spec)
    compile_ms = (time.perf_counter() - t0) * 1000.0
    t0 = time.perf_counter()
    dec.open(tool_spec)
    cached_ms = (time.perf_counter() - t0) * 1000.0

    overhead_pct = round(100.0 * (best_off - best_on) / max(best_off, 1e-9), 2)
    return {
        "unguided": {"tok_s": round(best_off, 1), "rounds": rounds},
        "guided": {"tok_s": round(best_on, 1), "rounds": rounds,
                   "fsm_states": sched.guided.pool._used - 1},
        "overhead_pct": overhead_pct,
        "budget_pct": 5.0,
        "within_budget": overhead_pct <= 5.0,
        "grammar_compile": {
            "tool_schema_ms": round(compile_ms, 2),
            "cached_open_ms": round(cached_ms, 3),
            "fsm_states": st.fsm.num_states,
        },
        "note": "tiny model on CPU, byte tokenizer, every row masked — the "
                "worst case; real batches mix guided/unguided rows through "
                "the same executable",
    }


def bench_device_truth():
    """Measured vs modeled roofline agreement (device-truth plane).

    Runs real decode traffic through a scheduler to accumulate the modeled
    roofline account (FLOPs/bytes/step-seconds), then replays that exact
    span through the trace parser on a synthesized Chrome-trace fixture
    whose device-busy time equals the modeled step seconds — the CPU-CI
    path where the answer is known. Asserts the round trip: the parser's
    per-lane interval union recovers the busy time, the flight recorder's
    ``measured_mfu`` lands on the modeled MFU, ``measured_modeled_mfu_ratio``
    sits at 1.0 within tolerance, and the parser counts the attention
    kernel's launches from TRACE events. A live
    ``jax.profiler`` window against real device work rides along
    best-effort (real traces vary by backend; reported, not asserted)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import get_config
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions
    from dynamo_tpu.runtime.profiling import (
        ContinuousProfileConfig,
        ContinuousProfiler,
        DeviceProfiler,
        parse_trace_events,
    )

    cfg = get_config("tiny").replace(max_seq_len=4096)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_blocks=512, max_running=8,
        prefill_buckets=[32, 64], decode_buckets=[1, 2, 4, 8],
        num_scheduler_steps=1, enable_prefix_caching=False,
    ), dtype=jnp.float32)

    def drive(tag: str, n: int = 6, max_tokens: int = 48) -> None:
        for i in range(n):
            sched.add_request(
                f"{tag}{i}", list(range(1 + i % 8, 33 + i % 8)),
                SamplingParams(temperature=0.0), StopConditions(max_tokens=max_tokens),
            )
        while sched.has_work():
            sched.step()

    # XLA-truth FLOPs: the same cost_analysis calibration warmup() runs,
    # so the modeled side of the comparison is the calibrated model.
    sched._calibrate_cost_model(sched.sc.decode_buckets[0], 1)
    drive("warm")  # compiles every shape this section touches
    sched.flight.mark_warmup_done(warmed=True)
    drive("run")

    flight = sched.flight
    flops, bytes_moved, secs = flight.roofline_totals()
    assert secs > 0 and flops > 0, "no modeled roofline accumulated"
    modeled_stats = flight.to_stats()
    peak_flops = flight.cost_model.peak_flops
    peak_bw = flight.cost_model.peak_bw
    modeled_mfu = flops / secs / peak_flops
    modeled_hbm = bytes_moved / secs / peak_bw

    # --- fixture path: a synthetic trace whose device lane is busy for
    # exactly the modeled step seconds, most of it in a few launches of the
    # attention kernel. The parser must recover all of it.
    busy_us = secs * 1e6
    attn_n = 4
    attn_us = busy_us * 0.6 / attn_n
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0 (fixture)"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 99, "name": "process_name",
         "args": {"name": "python host"}},
        # Host-lane noise the union must EXCLUDE.
        {"ph": "X", "pid": 99, "tid": 1, "name": "host_busywork",
         "ts": 0.0, "dur": busy_us * 10},
    ]
    t = 0.0
    for _ in range(attn_n):
        events.append({"ph": "X", "pid": 7, "tid": 1,
                       "name": "ragged_paged_attention(layer)",
                       "ts": t, "dur": attn_us})
        t += attn_us + 3.0  # gaps: the union must not bridge them
    other_us = busy_us - attn_us * attn_n
    events.append({"ph": "X", "pid": 7, "tid": 1, "name": "fusion.sample_rows",
                   "ts": t, "dur": other_us})
    summary = parse_trace_events(events)
    assert summary.device_lane_found, "fixture device lane not recognized"
    assert abs(summary.device_time_us - busy_us) <= max(1.0, busy_us * 1e-6), (
        f"interval union lost time: {summary.device_time_us} vs {busy_us}"
    )
    launches = summary.launch_count("ragged_paged_attention")
    assert launches == attn_n, f"launch count {launches} != {attn_n}"

    record = {
        "status": "ok",
        "wall_s": secs * 1.25,  # device busy 80% of the trace wall window
        "device_time_s": summary.device_time_us / 1e6,
        "flops": flops, "bytes": bytes_moved, "step_seconds": secs,
        "kernel_events": summary.kernel_events,
        "device_lanes": summary.device_lanes,
        "device_lane_found": summary.device_lane_found,
        "truncated": summary.truncated,
        "top_kernels": summary.top(4),
        "top_kernel_share": summary.top_share(),
    }
    flight.record_measured_window(record)
    stats = flight.to_stats()

    # --- the acceptance asserts: measured siblings agree with the model on
    # the span where agreement is the ground truth.
    ratio = stats["measured_modeled_mfu_ratio"]
    measured_mfu = stats["measured_mfu"]
    mfu_rel_err = abs(measured_mfu - modeled_mfu) / max(modeled_mfu, 1e-12)
    assert abs(ratio - 1.0) <= 0.02, (
        f"measured/modeled time ratio {ratio} off the fixture identity"
    )
    assert mfu_rel_err <= 0.05, (
        f"measured_mfu {measured_mfu} vs modeled {modeled_mfu}: {mfu_rel_err:.3%}"
    )
    assert stats["measured_windows_total"] == 1

    # --- live capture (best effort): a real jax.profiler window over real
    # device work, through the same sample_once path the production sampler
    # runs. Reported, not asserted — trace shape varies by backend.
    import threading as _threading

    import tempfile as _tempfile
    stop = _threading.Event()

    def churn() -> None:
        x = jnp.ones((128, 128), jnp.float32)
        while not stop.is_set():
            x = jnp.tanh(x @ x.T / 128.0)
            x.block_until_ready()

    cont = ContinuousProfiler(
        DeviceProfiler(out_dir=_tempfile.mkdtemp(prefix="bench_truth_")),
        ContinuousProfileConfig(window_s=0.1),
        cost_probe=flight.roofline_totals,
        sink=None,  # keep the fixture-path measured stats as the asserted view
    )
    worker = _threading.Thread(target=churn, daemon=True)
    worker.start()
    try:
        live = cont.sample_once(force=True)
    finally:
        stop.set()
        worker.join(timeout=2.0)
    live_report = {
        "status": live.get("status"),
        "kernel_events": live.get("kernel_events"),
        "device_lanes": live.get("device_lanes"),
        "device_lane_found": live.get("device_lane_found"),
        "device_time_ms": round(float(live.get("device_time_s") or 0.0) * 1e3, 3),
        "top_kernels": (live.get("top_kernels") or [])[:3],
    }

    return {
        "modeled": {
            "mfu_overall": round(modeled_mfu, 6),
            "hbm_frac_overall": round(modeled_hbm, 6),
            "mfu_decode": modeled_stats.get("mfu_decode"),
            "hbm_frac_decode": modeled_stats.get("hbm_frac_decode"),
            "step_seconds": round(secs, 6),
            "cost_model_calibrated": stats.get("cost_model_calibrated"),
        },
        "measured": {
            "measured_mfu": measured_mfu,
            "measured_hbm_frac": stats["measured_hbm_frac"],
            "measured_device_frac": stats["measured_device_frac"],
            "measured_top_kernel_share": stats["measured_top_kernel_share"],
            "device_seconds": round(summary.device_time_us / 1e6, 6),
        },
        "agreement": {
            "measured_modeled_mfu_ratio": ratio,
            "mfu_rel_err": round(mfu_rel_err, 6),
            "ratio_tolerance": 0.02,
            "mfu_tolerance": 0.05,
            "ok": True,
        },
        "fixture": {
            "kernel_events": summary.kernel_events,
            "device_lanes": summary.device_lanes,
            "attention_launches": launches,
        },
        "live_capture": live_report,
        "note": "fixture path is the asserted ground truth (CPU CI); the "
                "live jax.profiler window is reported best-effort. On TPU "
                "the continuous sampler feeds the same record shape from "
                "real traces.",
    }


def bench_autoscale():
    """Closed-loop SLA autoscaling under the million-user traffic harness
    (tools/traffic_harness.py): a seeded diurnal ramp with drifting ISL
    drives a real in-process plane — mocker pools → metrics aggregator
    (multi-endpoint scrape) → Prometheus observer → AutoscaleController →
    fleet launches/drains — with a chaos crash armed the moment the first
    scale event lands. Reports the SLO-attainment + goodput curves across
    the ramp, the scale timeline, and convergence vs the capacity oracle.
    CI asserts: converged (final pools within ±1 of the oracle), SLO
    attainment above the floor, chaos fired, zero token loss."""
    import asyncio

    from tools.traffic_harness import (
        AutoscaleBenchConfig,
        TrafficPattern,
        run_autoscale_bench,
    )

    cfg = AutoscaleBenchConfig(
        pattern=TrafficPattern(
            kind="diurnal", duration_s=float(os.environ.get("BENCH_AUTOSCALE_S", "20")),
            base_rate=1.5, peak_rate=8.0, isl=96, isl_end=144, osl=16,
            prefix_ratio=0.5, seed=0,
        ),
        adjustment_interval_s=1.5,
        scale_cooldown_s=3.0,
        settle_s=5.0,
    )
    report = asyncio.run(run_autoscale_bench(cfg))
    planner = report["planner"]
    report["summary"] = {
        "converged": report["final"]["converged"],
        "final_pools": {"prefill": report["final"]["prefill"],
                        "decode": report["final"]["decode"]},
        "oracle_pools": {"prefill": report["final"]["oracle_prefill"],
                         "decode": report["final"]["oracle_decode"]},
        "slo_attainment": report["slo_attainment"],
        "slo_floor": 0.7,
        "token_loss": report["totals"]["token_loss"],
        "errors": report["totals"]["errors"],
        "chaos_injections": report["chaos"]["injections"],
        "scale_ups": planner["planner_scale_up_total"],
        "scale_downs": planner["planner_scale_down_total"],
    }
    return report


def bench_elastic():
    """Elastic prefill/decode: degrade-vs-queue TTFT/goodput curves under a
    shifting ISL/OSL mix (tools/traffic_harness.py run_elastic_bench). Three
    fleets of identical hardware — pure disagg (static split, queues on
    saturation), pure co-located (mixed everywhere, constant interference),
    elastic (disagg + capacity dial + degradation ladder) — offered the same
    seeded mix flip. CI asserts the elastic fleet strictly dominates both
    static extremes on SLO attainment AND goodput, with zero token loss and
    both degrade directions exercised."""
    import asyncio

    from tools.traffic_harness import ElasticBenchConfig, run_elastic_bench

    cfg = ElasticBenchConfig()
    cfg.pattern.duration_s = float(os.environ.get("BENCH_ELASTIC_S", "16"))
    return asyncio.run(run_elastic_bench(cfg))


# --------------------------------------------------------------------------
# child: run sections against the already-chosen backend, emit partials
# --------------------------------------------------------------------------

def _emit_partial(section: str, payload) -> None:
    print(PARTIAL_TAG + json.dumps({"section": section, "data": payload}), flush=True)


def _run_cpu_subprocess(argv, key, timeout_s, extra_env=None):
    """Run a CPU-pinned helper process and scan stdout for the JSON object
    carrying ``key``. Returns (obj_or_None, error_or_None)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("BENCH_CHILD", None)
    # Helpers under tools/ put THEIR dir on sys.path, not the repo root —
    # make dynamo_tpu importable even without a pip install.
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    out = subprocess.run(argv, env=env, capture_output=True, text=True, timeout=timeout_s)
    for line in out.stdout.splitlines():
        try:
            obj = json.loads(line)
            if isinstance(obj, dict) and key in obj:
                return obj, None
        except ValueError:
            pass
    return None, f"no result (rc={out.returncode}): {out.stderr.strip()[-200:]}"


def child_main() -> None:
    """Measurement process. Emits BENCH_PARTIAL lines per section and a full
    JSON line at the end; every section is individually fenced so one
    failure cannot empty the round."""
    deadline = float(os.environ["BENCH_DEADLINE"])  # absolute time.time()
    errors: list = []

    def remaining() -> float:
        return deadline - time.time()

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import get_config
    from dynamo_tpu.engine.models import llama

    dev0 = jax.devices()[0]
    if dev0.platform == "cpu":
        sys.exit("bench: JAX found no accelerator — no chip, no number")
    from dynamo_tpu.engine.compile_cache import enable_compile_cache

    enable_compile_cache()
    model = os.environ.get("BENCH_MODEL", "llama-3.2-1b")
    batches = [int(b) for b in os.environ.get("BENCH_BATCHES", "8,16,32").split(",")]
    steps = int(os.environ.get("BENCH_STEPS", "256"))
    window = int(os.environ.get("BENCH_WINDOW", "32"))
    ctx_len = int(os.environ.get("BENCH_CTX", "1024"))
    prompt_len = int(os.environ.get("BENCH_PREFILL", "2048"))
    attn = os.environ.get("BENCH_ATTN", "auto")
    skip_http = os.environ.get("BENCH_SKIP_HTTP", "") == "1"

    device = dev0.device_kind
    hbm_gbps, tflops = chip_peaks(device)
    _emit_partial("device", {"device": device})

    cfg = get_config(model).replace(max_seq_len=max(4096, ctx_len + 512), attention_impl=attn)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    pbytes = param_bytes_of(params)

    # --- decode sweep (primary) — smallest batch first so SOME decode
    # number lands before any budget/compile trouble at larger batches.
    decode_points = []
    for batch in batches:
        if decode_points and remaining() < 60:
            errors.append(f"decode sweep truncated before b{batch}: {remaining():.0f}s left")
            break
        try:
            step_s = bench_decode(cfg, params, batch, ctx_len, steps, window)
            kv_bytes = 2 * cfg.num_layers * ctx_len * cfg.num_kv_heads * cfg.head_dim * 2 * batch
            gbps = (pbytes + kv_bytes) / step_s / 1e9
            point = {
                "batch": batch,
                "ctx": ctx_len,
                "step_ms": round(step_s * 1000, 3),
                "tok_s_per_user": round(1.0 / step_s, 2),
                "tok_s_per_chip": round(batch / step_s, 1),
                "achieved_hbm_gbps": round(gbps, 1),
                "pct_hbm_roofline": round(100 * gbps / hbm_gbps, 1) if hbm_gbps else None,
            }
            decode_points.append(point)
            _emit_partial("decode_point", point)
        except Exception as e:  # noqa: BLE001 — a failed point must not kill the sweep
            errors.append(f"decode b{batch}: {type(e).__name__}: {e}")

    # --- int8 KV point (capacity ×2; ON by default, BENCH_INT8=0 opts out —
    # decode latency is at best at parity on current XLA:TPU, the point
    # records the capacity configuration; see models/llama.py:_gather_kv) ---
    if os.environ.get("BENCH_INT8", "1") == "1" and decode_points and remaining() > 90:
        try:
            b8 = batches[0]
            cfg8 = cfg.replace(kv_cache_dtype="int8", attention_impl="gather")
            step_s = bench_decode(cfg8, params, b8, ctx_len, max(64, steps // 4), window)
            kv_bytes = cfg.num_layers * ctx_len * cfg.num_kv_heads * cfg.head_dim * 2 * b8  # int8 k+v
            gbps = (pbytes + kv_bytes) / step_s / 1e9
            point = {
                "batch": b8, "ctx": ctx_len, "kv_dtype": "int8",
                "step_ms": round(step_s * 1000, 3),
                "tok_s_per_user": round(1.0 / step_s, 2),
                "tok_s_per_chip": round(b8 / step_s, 1),
                "achieved_hbm_gbps": round(gbps, 1),
                "pct_hbm_roofline": round(100 * gbps / hbm_gbps, 1) if hbm_gbps else None,
            }
            decode_points.append(point)
            _emit_partial("decode_point", point)
        except Exception as e:  # noqa: BLE001
            errors.append(f"decode int8: {type(e).__name__}: {e}")

    # --- int8-WEIGHT point (weight-only quant speeds decode outright:
    # layer weights stream at half the bytes and XLA fuses the dequant into
    # the matmul reads — measured, see engine/quant.py) ----------------------
    if os.environ.get("BENCH_INT8W", "1") == "1" and decode_points and remaining() > 90:
        params_q = None
        try:
            from dynamo_tpu.engine.quant import quantize_params

            b8 = batches[0]
            # quantize_params mutates in place — hand it a copied layers
            # dict so the bf16 tree stays intact for the prefill section.
            params_q = quantize_params({**params, "layers": dict(params["layers"])})
            step_s = bench_decode(cfg, params_q, b8, ctx_len, max(64, steps // 4), window)
            qbytes = param_bytes_of(params_q)
            kv_bytes = 2 * cfg.num_layers * ctx_len * cfg.num_kv_heads * cfg.head_dim * 2 * b8
            gbps = (qbytes + kv_bytes) / step_s / 1e9
            point = {
                "batch": b8, "ctx": ctx_len, "weight_dtype": "int8",
                "step_ms": round(step_s * 1000, 3),
                "tok_s_per_user": round(1.0 / step_s, 2),
                "tok_s_per_chip": round(b8 / step_s, 1),
                "achieved_hbm_gbps": round(gbps, 1),
                "pct_hbm_roofline": round(100 * gbps / hbm_gbps, 1) if hbm_gbps else None,
            }
            decode_points.append(point)
            _emit_partial("decode_point", point)
        except Exception as e:  # noqa: BLE001
            errors.append(f"decode int8w: {type(e).__name__}: {e}")
        finally:
            # Free on every path: leaked int8 copies push the 8B section
            # over HBM (its own failure-mode comment).
            del params_q

    # --- decode attention backends (gather vs megakernel + dispatch tax) ----
    decode_attention = None
    if remaining() > 90:
        try:
            decode_attention = bench_decode_attention(
                cfg=cfg, params=params,
                ctx_len=ctx_len, hbm_gbps=hbm_gbps,
            )
            _emit_partial("decode_attention", decode_attention)
        except Exception as e:  # noqa: BLE001
            errors.append(f"decode_attention: {type(e).__name__}: {e}")
    else:
        errors.append("decode_attention skipped: budget")

    # --- prefill ------------------------------------------------------------
    prefill_detail = None
    if remaining() > 45:
        try:
            prefill_s = bench_prefill(cfg, params, prompt_len)
            dense_params = pbytes / 2  # bf16
            mfu = (2 * dense_params * prompt_len / prefill_s / 1e12 / tflops) if tflops else None
            prefill_detail = {
                "prompt_len": prompt_len,
                "ttft_ms": round(prefill_s * 1000, 2),
                "tok_s": round(prompt_len / prefill_s, 1),
                "mfu_pct": round(100 * mfu, 1) if mfu else None,
            }
            _emit_partial("prefill", prefill_detail)
        except Exception as e:  # noqa: BLE001
            errors.append(f"prefill: {type(e).__name__}: {e}")
    else:
        errors.append("prefill skipped: budget")

    # --- TPU + HTTP combined (flagship model through the full stack) --------
    tpu_http = None
    if not skip_http and remaining() > 120:
        try:
            tpu_http = bench_tpu_http()
            # Served fraction of the raw engine decode rate at the same
            # batch — the serving-plane tax on TPU throughput.
            raw = next((p for p in decode_points if p["batch"] == tpu_http["concurrency"]), None)
            if raw:
                tpu_http["pct_of_raw_decode"] = round(
                    100.0 * tpu_http["tok_s"] / raw["tok_s_per_chip"], 1
                )
            _emit_partial("tpu_http_e2e", tpu_http)
        except Exception as e:  # noqa: BLE001
            errors.append(f"tpu_http_e2e: {type(e).__name__}: {e}")
    elif not skip_http:
        errors.append("tpu_http_e2e skipped: budget")

    # Free the 1B artifacts before the 8B section: the 8.5 GiB int8 model
    # plus resident 1B params/engines exceeds HBM (measured: RESOURCE_
    # EXHAUSTED poisoning every later section).
    try:
        import gc

        del params
        gc.collect()
    except NameError:
        pass

    # --- 8B-class point (int8 weights fit where bf16 cannot) ---------------
    large_detail = None
    if os.environ.get("BENCH_SKIP_8B") != "1" and remaining() > 150:
        try:
            import gc

            from dynamo_tpu.engine.quant import QuantW

            cfg8 = get_config("llama-3-8b").replace(max_seq_len=4096)
            key8 = jax.random.PRNGKey(7)

            def synth_qw(shape):
                nonlocal key8
                key8, k1, k2 = jax.random.split(key8, 3)
                q = jax.random.randint(k1, (cfg8.num_layers,) + shape, -127, 128, jnp.int8)
                s = jax.random.uniform(k2, (cfg8.num_layers, 1, shape[-1]), jnp.float32, 1e-3, 2e-3)
                jnp.asarray(s)[0, 0, 0].block_until_ready()
                return QuantW(q, s)

            def synth_dense(shape, scale=0.02):
                nonlocal key8
                key8, k1 = jax.random.split(key8)
                return jax.random.normal(k1, shape, jnp.bfloat16) * scale

            D8, H8, KVH8, HD8, I8, V8 = (cfg8.hidden_size, cfg8.num_heads, cfg8.num_kv_heads,
                                          cfg8.head_dim, cfg8.intermediate_size, cfg8.vocab_size)
            params8 = {
                "embed": synth_dense((V8, D8)),
                "final_norm": synth_dense((D8,), 1.0),
                "lm_head": synth_dense((D8, V8)),
                "layers": {
                    "wq": synth_qw((D8, H8 * HD8)), "wk": synth_qw((D8, KVH8 * HD8)),
                    "wv": synth_qw((D8, KVH8 * HD8)), "wo": synth_qw((H8 * HD8, D8)),
                    "w_gate": synth_qw((D8, I8)), "w_up": synth_qw((D8, I8)),
                    "w_down": synth_qw((I8, D8)),
                    "attn_norm": synth_dense((cfg8.num_layers, D8), 1.0),
                    "mlp_norm": synth_dense((cfg8.num_layers, D8), 1.0),
                },
            }
            pts = []
            for b8b in (8,):
                if remaining() < 60:
                    errors.append(f"8B point b{b8b} skipped: budget")
                    break
                step_s = bench_decode(cfg8, params8, b8b, ctx_len, 128, 32)
                w_bytes = param_bytes_of(params8)
                kv_b = 2 * cfg8.num_layers * ctx_len * cfg8.num_kv_heads * cfg8.head_dim * 2 * b8b
                gbps = (w_bytes + kv_b) / step_s / 1e9
                pts.append({
                    "batch": b8b, "ctx": ctx_len,
                    "step_ms": round(step_s * 1000, 3),
                    "tok_s_per_user": round(1.0 / step_s, 2),
                    "tok_s_per_chip": round(b8b / step_s, 1),
                    "pct_hbm_roofline": round(100 * gbps / hbm_gbps, 1) if hbm_gbps else None,
                })
            large_detail = {
                "model": "llama-3-8b", "weight_dtype": "int8",
                "note": "bf16 weights are 15.0 GiB and OOM this 16 GiB chip before "
                        "the first decode step (measured); int8 layer weights "
                        "(engine/quant.py) fit with KV headroom. Synthetic codes — "
                        "perf-only; real checkpoints quantize host-side at load.",
                "points": pts,
                "ref_anchor_tok_s_user_8b_tp4_h100": 51.22,
            }
            del params8
            gc.collect()
            _emit_partial("large_model", large_detail)
        except Exception as e:  # noqa: BLE001
            errors.append(f"8B section: {type(e).__name__}: {e}")
    elif os.environ.get("BENCH_SKIP_8B") != "1":
        errors.append("8B section skipped: budget")

    # --- router benefit (mocker fleet, CPU subprocess) ----------------------
    router_prefix = None
    if not skip_http and remaining() > 60:
        try:
            router_prefix, err = _run_cpu_subprocess(
                [sys.executable, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                              "tools", "bench_router_prefix.py"), "--quick"],
                "sweep", max(60, remaining() - 10),
            )
            if router_prefix is not None:
                _emit_partial("router_prefix", router_prefix)
            else:
                errors.append(f"router_prefix: {err}")
        except subprocess.TimeoutExpired:
            errors.append("router_prefix: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"router_prefix: {type(e).__name__}: {e}")
    elif not skip_http:
        errors.append("router_prefix skipped: budget")

    # --- HTTP e2e (serving stack, tiny model) -------------------------------
    # Runs in a CPU subprocess: the section measures the serving plane
    # (HTTP/preprocess/scheduler-loop/detok overhead), not the device.
    http = None
    if not skip_http and remaining() > 60:
        try:
            http, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "tok_s",
                max(60, remaining() - 10), extra_env={"BENCH_HTTP_ONLY": "1"},
            )
            if http is None:
                errors.append(f"http_e2e: {err}")
            else:
                _emit_partial("http_e2e", http)
        except subprocess.TimeoutExpired:
            errors.append("http_e2e: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"http_e2e: {type(e).__name__}: {e}")
    elif not skip_http:
        errors.append("http_e2e skipped: budget")


    # --- mixed prefill+decode admission (scheduler-level, CPU subprocess) ---
    mixed_admission = None
    if remaining() > 60:
        try:
            mixed_admission, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "mixed_on",
                max(60, remaining() - 10), extra_env={"BENCH_MIXED_ONLY": "1"},
            )
            if mixed_admission is None:
                errors.append(f"mixed_admission: {err}")
            else:
                _emit_partial("mixed_admission", mixed_admission)
        except subprocess.TimeoutExpired:
            errors.append("mixed_admission: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"mixed_admission: {type(e).__name__}: {e}")
    else:
        errors.append("mixed_admission skipped: budget")

    # --- engine-level prefix reuse (real schedulers, CPU subprocess) --------
    prefix_reuse = None
    if remaining() > 60:
        try:
            prefix_reuse, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "speedup",
                max(60, remaining() - 10), extra_env={"BENCH_PREFIX_ONLY": "1"},
            )
            if prefix_reuse is None:
                errors.append(f"prefix_reuse: {err}")
            else:
                _emit_partial("prefix_reuse", prefix_reuse)
        except subprocess.TimeoutExpired:
            errors.append("prefix_reuse: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"prefix_reuse: {type(e).__name__}: {e}")
    else:
        errors.append("prefix_reuse skipped: budget")

    # --- observability overhead (tracing on vs off, CPU subprocess) ---------
    observability = None
    if remaining() > 45:
        try:
            observability, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "overhead_pct",
                max(45, remaining() - 10), extra_env={"BENCH_OBS_ONLY": "1"},
            )
            if observability is None:
                errors.append(f"observability: {err}")
            else:
                _emit_partial("observability", observability)
        except subprocess.TimeoutExpired:
            errors.append("observability: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"observability: {type(e).__name__}: {e}")
    else:
        errors.append("observability skipped: budget")

    # --- device truth: measured vs modeled roofline (CPU subprocess) --------
    device_truth = None
    if remaining() > 45:
        try:
            device_truth, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "agreement",
                max(45, remaining() - 10), extra_env={"BENCH_DEVICE_TRUTH_ONLY": "1"},
            )
            if device_truth is None:
                errors.append(f"device_truth: {err}")
            else:
                _emit_partial("device_truth", device_truth)
        except subprocess.TimeoutExpired:
            errors.append("device_truth: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"device_truth: {type(e).__name__}: {e}")
    else:
        errors.append("device_truth skipped: budget")

    # --- guided decoding overhead (masked vs unmasked, CPU subprocess) ------
    guided_overhead = None
    if remaining() > 45:
        try:
            guided_overhead, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "overhead_pct",
                max(45, remaining() - 10), extra_env={"BENCH_GUIDED_ONLY": "1"},
            )
            if guided_overhead is None:
                errors.append(f"guided_overhead: {err}")
            else:
                _emit_partial("guided_overhead", guided_overhead)
        except subprocess.TimeoutExpired:
            errors.append("guided_overhead: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"guided_overhead: {type(e).__name__}: {e}")
    else:
        errors.append("guided_overhead skipped: budget")

    # --- closed-loop autoscaling (traffic harness, CPU subprocess) ----------
    autoscale = None
    if remaining() > 60:
        try:
            autoscale, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "summary",
                max(60, remaining() - 10), extra_env={"BENCH_AUTOSCALE_ONLY": "1"},
            )
            if autoscale is None:
                errors.append(f"autoscale: {err}")
            else:
                _emit_partial("autoscale", autoscale)
        except subprocess.TimeoutExpired:
            errors.append("autoscale: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"autoscale: {type(e).__name__}: {e}")
    else:
        errors.append("autoscale skipped: budget")

    # --- elastic prefill/decode (degrade-vs-queue, CPU subprocess) ----------
    elastic = None
    if remaining() > 60:
        try:
            elastic, err = _run_cpu_subprocess(
                [sys.executable, os.path.abspath(__file__)], "summary",
                max(60, remaining() - 10), extra_env={"BENCH_ELASTIC_ONLY": "1"},
            )
            if elastic is None:
                errors.append(f"elastic: {err}")
            else:
                _emit_partial("elastic", elastic)
        except subprocess.TimeoutExpired:
            errors.append("elastic: subprocess timed out")
        except Exception as e:  # noqa: BLE001
            errors.append(f"elastic: {type(e).__name__}: {e}")
    else:
        errors.append("elastic skipped: budget")

    print(json.dumps(assemble(decode_points, prefill_detail, http, device, model,
                              errors, tpu_http=tpu_http,
                              router_prefix=router_prefix, large_model=large_detail,
                              mixed_admission=mixed_admission,
                              observability=observability,
                              guided_overhead=guided_overhead,
                              prefix_reuse=prefix_reuse,
                              decode_attention=decode_attention,
                              autoscale=autoscale, elastic=elastic,
                              device_truth=device_truth)), flush=True)


def assemble(decode_points, prefill_detail, http, device, model, errors, tpu_http=None, router_prefix=None, large_model=None, mixed_admission=None, observability=None, guided_overhead=None, prefix_reuse=None, decode_attention=None, autoscale=None, elastic=None, device_truth=None) -> dict:
    """Build the final JSON object from whatever sections completed."""
    hbm_gbps, _ = chip_peaks(device)
    best = max(decode_points, key=lambda p: p.get("achieved_hbm_gbps") or 0.0) if decode_points else None
    frac = None
    if best and hbm_gbps:
        frac = round(best["achieved_hbm_gbps"] / hbm_gbps, 3)
    return {
        "metric": (
            f"decode_tok_s_per_user_{model}_b{best['batch']}_ctx{best['ctx']}"
            if best else f"decode_tok_s_per_user_{model}"
        ),
        "value": best["tok_s_per_user"] if best else None,
        "unit": "tok/s/user",
        # Honest like-for-like: fraction of THIS chip's HBM roofline achieved
        # by the best decode point (1.0 = bandwidth-bound optimum).
        "vs_baseline": frac,
        "detail": {
            "decode_sweep": decode_points,
            "decode_attention": decode_attention,
            "prefill": prefill_detail,
            "tpu_http_e2e": tpu_http,
            "http_e2e": http,
            "router_prefix": router_prefix,
            "prefix_reuse": prefix_reuse,
            "large_model": large_model,
            "mixed_admission": mixed_admission,
            "observability": observability,
            "device_truth": device_truth,
            "guided_overhead": guided_overhead,
            "autoscale": autoscale,
            "elastic": elastic,
            "device": device,
            "errors": errors,
            "ref_anchor": {
                "decode_tok_s_user_8b_tp4_h100": 51.22,
                "prefill_ttft_ms_3k_tp4_h100": 48.37,
                "note": "different model+hardware class; anchors only",
            },
            "attention_impls": {
                "prefill": "pallas flash kernel (attention/prefill.py): 40.8 TF/s causal "
                           "at 1B shapes on v5e; 149.8->40.8 ms at 2K ISL (17.1%->63.0% MFU)",
                "decode": "auto = ragged paged-attention megakernel on TPU "
                          "(attention/megakernel.py): one pallas launch per layer "
                          "serves the whole mixed step's ragged batch (chunk rows + "
                          "length-1 decode rows, GQA fold, scalar-prefetched tables, "
                          "pl.when-skipped dead slots, int8 dequant-in-VMEM). "
                          "Off-TPU: XLA "
                          "width-bucketed gather (pow2 + 1.5*pow2 rungs, two-piece "
                          "online-softmax, once-per-window hoist; r5: b32 28.5% -> "
                          "~54% HBM roofline — the 3x gather traffic the megakernel "
                          "removes). The r4/r5 per-piece paged kernel remains "
                          "explicit opt-in; it lost to per-pallas-call dispatch "
                          "overhead, which the decode_attention section now tracks "
                          "per round. Full record: ModelConfig.attention_impl "
                          "docstring.",
            },
        },
    }


# --------------------------------------------------------------------------
# orchestrator: run child under budget → print the one JSON line, or exit
# non-zero when no chip produced a number
# --------------------------------------------------------------------------

def main() -> None:
    t_start = time.time()
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "500"))
    errors: list = []

    env = dict(os.environ)
    env["BENCH_CHILD"] = "1"
    child_budget = budget_s - 5
    env["BENCH_DEADLINE"] = str(time.time() + child_budget)

    partials: dict = {"decode_point": []}
    final = None
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=sys.stderr, text=True,
        )
        try:
            out, _ = proc.communicate(timeout=child_budget + 30)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            errors.append(f"bench child exceeded {child_budget:.0f}s budget; partial results only")
        for line in (out or "").splitlines():
            if line.startswith(PARTIAL_TAG):
                rec = json.loads(line[len(PARTIAL_TAG):])
                if rec["section"] == "decode_point":
                    partials["decode_point"].append(rec["data"])
                else:
                    partials[rec["section"]] = rec["data"]
            else:
                try:
                    obj = json.loads(line)
                    if isinstance(obj, dict) and "metric" in obj:
                        final = obj
                except ValueError:
                    pass
        if final is None and proc.returncode not in (0, None):
            errors.append(f"bench child rc={proc.returncode}")
    except Exception as e:  # noqa: BLE001 — the orchestrator must always emit
        errors.append(f"orchestrator: {type(e).__name__}: {e}")

    if final is None:
        if "device" not in partials:
            # The child never reached a chip: no chip, no number.
            sys.exit("bench: " + "; ".join(errors or ["no result from the measurement child"]))
        final = assemble(
            partials["decode_point"], partials.get("prefill"), partials.get("http_e2e"),
            partials["device"]["device"],
            os.environ.get("BENCH_MODEL", "llama-3.2-1b"),
            [], tpu_http=partials.get("tpu_http_e2e"),
            router_prefix=partials.get("router_prefix"),
            large_model=partials.get("large_model"),
            mixed_admission=partials.get("mixed_admission"),
            observability=partials.get("observability"),
            device_truth=partials.get("device_truth"),
            guided_overhead=partials.get("guided_overhead"),
            prefix_reuse=partials.get("prefix_reuse"),
            decode_attention=partials.get("decode_attention"),
            autoscale=partials.get("autoscale"),
        )
    final["detail"]["errors"] = errors + final["detail"].get("errors", [])
    final["detail"]["wall_s"] = round(time.time() - t_start, 1)
    print(json.dumps(final), flush=True)


if __name__ == "__main__":
    if os.environ.get("BENCH_DECODE_ATTN_ONLY") == "1":
        # Standalone decode_attention section (CI uses this on CPU: the token
        # parity assert; on TPU it reports the gather vs megakernel roofline
        # sweep).
        print(json.dumps(bench_decode_attention()), flush=True)
    elif os.environ.get("BENCH_PREFIX_ONLY") == "1":
        # CPU-pinned: the subject is skipped prefill FLOPs vs recompute in
        # the real scheduler, not device speed.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_prefix_reuse()), flush=True)
    elif os.environ.get("BENCH_MIXED_ONLY") == "1":
        # CPU-pinned like the http section: the subject is scheduler
        # structure (mixed vs phase-separated steps), not the device.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_mixed_admission()), flush=True)
    elif os.environ.get("BENCH_GUIDED_ONLY") == "1":
        # CPU-pinned: measures the mask-gather + FSM-advance cost in the
        # scheduler step loop, not the device.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_guided_overhead()), flush=True)
    elif os.environ.get("BENCH_AUTOSCALE_ONLY") == "1":
        # CPU-pinned: the subject is the closed planner loop over mocker
        # fleets (scheduler/aggregator/controller structure), not a device.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_autoscale()), flush=True)
    elif os.environ.get("BENCH_ELASTIC_ONLY") == "1":
        # CPU-pinned: the subject is topology policy (dial + degradation
        # ladder vs static extremes) over mocker fleets, not a device.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_elastic()), flush=True)
    elif os.environ.get("BENCH_DEVICE_TRUTH_ONLY") == "1":
        # CPU-pinned: the asserted path is the trace parser + flight
        # recorder round trip on a known fixture, not device speed.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_device_truth()), flush=True)
    elif os.environ.get("BENCH_OBS_ONLY") == "1":
        # CPU-pinned: measures the tracing layer's host-side cost, not the
        # device.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_observability_overhead()), flush=True)
    elif os.environ.get("BENCH_HTTP_ONLY") == "1":
        # CPU-pinned: this section measures the serving plane, not the
        # device.
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(bench_http_e2e()), flush=True)
    elif os.environ.get("BENCH_CHILD") == "1":
        child_main()
    else:
        main()
