"""Router-benefit benchmark: KV-aware routing vs round-robin over a mocker
fleet, swept by shared-prefix ratio.

This is the measurement behind the reference's headline routing claim
(ref: benchmarks/router/prefix_ratio_benchmark.py — ~3x TTFT from KV
routing at high prefix share): timed mocker workers with REAL prefix
caches serve interleaved requests from G prefix GROUPS — each group
shares the leading fraction ``p`` of its tokens — under cache pressure
(the aggregate group prefixes exceed one worker's blocks). KV routing
partitions groups across workers so each prefix stays warm on its home
worker; round-robin cycles every group through every worker, evicting
and re-prefilling constantly. The win grows with ``p``.

The wire-path sweep here measures the whole distributed stack (router
index, KV events, pub/sub + TCP, mocker timing model) — its single-core
asyncio queueing noise floors the measurable ratio. The ENGINE-side
speedup the routing hint buys — real Schedulers skipping real prefill
FLOPs — is measured by ``bench.py``'s ``prefix_reuse`` section: 4.4×
mean TTFT at 0.9 prefix ratio, with engine-reported ``cached_tokens``
asserted equal to the blocks actually served from cache and 0 XLA
compiles after warmup.

Prints ONE JSON line:
  {"isl": ..., "workers": N, "sweep": [{"prefix_ratio": p,
    "ttft_kv_ms": ..., "ttft_rr_ms": ..., "speedup": ...,
    "cached_tokens_kv": ..., "cached_tokens_rr": ...}, ...]}

TTFTs are in emulated-model milliseconds scaled by the mocker speedup —
absolute values track the timing model; the kv/rr RATIO is the result.

Usage: python tools/bench_router_prefix.py [--quick]
"""

import asyncio
import json
import os
import random
import sys
import time

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.llm.kv_router import (
    KvEventPublisher,
    KvPushRouter,
    KvRouterConfig,
    WorkerMetricsPublisher,
)
from dynamo_tpu.llm.mocker import MockEngineArgs, MockTpuEngine
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.push_router import PushRouter, RouterMode

WORKERS = 4
GROUPS = 8
ISL = 1024  # prefill compute must dominate the wire/tick overhead (~3 ms)
OSL = 4
# Real time == emulated time: speedup 2 halved every simulated duration
# while the REAL wire/tick overhead (~3 ms) stayed put, so the reported
# (emulated-scaled) TTFTs carried a doubled overhead floor that diluted
# the hit-side advantage — the routing win is measured, don't compress it.
SPEEDUP = 1.0
NUM_BLOCKS = 256  # per worker: ~3 group prefixes fit, all 8 never do
CHUNK = 512  # mocker prefill chunk — the engine's mixed_prefill_budget, so a
# cold miss stalls batchmates one chunk at a time, not a whole prompt


async def spawn_fleet(drt, ns):
    ep = drt.namespace(ns).component("mocker").endpoint("generate")
    fleet = []
    for _ in range(WORKERS):
        engine = MockTpuEngine(
            MockEngineArgs(
                speedup_ratio=SPEEDUP, num_blocks=NUM_BLOCKS, max_batch=8,
                max_prefill_chunk=CHUNK,
            )
        )
        handle = await ep.serve_endpoint(engine.generate, stats_handler=engine.stats_handler)
        wid = handle.instance.instance_id
        pub = KvEventPublisher(drt, ep.namespace, ep.component, wid)
        pub.start()
        engine.set_kv_event_sink(lambda ev, p=pub: p.publish(ev))
        mpub = WorkerMetricsPublisher(
            drt, ep.namespace, ep.component, wid, engine.metrics, interval_s=0.05
        )
        mpub.start()
        drt.local_engines.pop(wid)  # force the wire path
        fleet.append((engine, handle, pub, mpub))
    client = await ep.client()
    await client.wait_for_instances(WORKERS, timeout=10)
    return ep, client, fleet


def make_requests(n, prefix_ratio, seed):
    """(warmup, measured): one request per group in group order (EVERY
    group's prefix gets established somewhere before measurement — a
    shuffled warmup sample left some groups cold, so the measured phase
    timed cold establishment instead of routing quality), then n measured
    requests interleaved across the GROUPS prefix groups (shuffled —
    aligned striding would hand round-robin a perfect group partition by
    accident since GROUPS % WORKERS == 0; real traffic is unordered)."""
    rng = random.Random(seed)
    shared = [
        [rng.randrange(1, 30000) for _ in range(int(ISL * prefix_ratio))]
        for _ in range(GROUPS)
    ]

    def req(g):
        suffix = [rng.randrange(1, 30000) for _ in range(ISL - len(shared[g]))]
        return shared[g] + suffix

    warmup = [req(g) for g in range(GROUPS)]
    order = [i % GROUPS for i in range(n)]
    rng.shuffle(order)
    return warmup, [req(g) for g in order]


async def run_policy(policy, warmup, prompts, drt, ns):
    """Serve all prompts through the given policy; return (mean ttft ms,
    total mocker-cached tokens)."""
    ep, client, fleet = await spawn_fleet(drt, ns)
    router = None
    rr = None
    if policy == "kv":
        router = await KvPushRouter.create(client, KvRouterConfig(block_size=16))
    else:
        rr = PushRouter(client, RouterMode.ROUND_ROBIN)

    async def one(tokens):
        req = {
            "token_ids": tokens,
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": OSL},
        }
        t0 = time.perf_counter()
        ttft = None
        if router is not None:
            stream = router.generate(req, Context())
        else:
            stream = rr.generate(req)
        async for item in stream:
            data = getattr(item, "data", item)
            if data and ttft is None:
                ttft = time.perf_counter() - t0
        return ttft if ttft is not None else time.perf_counter() - t0

    # Warm every group's prefix sequentially (both policies get the same
    # warmup), then measure with bounded concurrency (the realistic
    # arrival pattern).
    ttfts = []
    for tokens in warmup:
        await one(tokens)
    await asyncio.sleep(0.3)  # KV events reach the indexer
    sem = asyncio.Semaphore(4)

    async def guarded(tokens):
        async with sem:
            ttfts.append(await one(tokens))

    await asyncio.gather(*[guarded(t) for t in prompts])
    cached = sum(e.cached_tokens_total for e, *_ in fleet)
    if router is not None:
        await router.close()
    for e, handle, pub, mpub in fleet:
        await handle.stop()
        await pub.stop()
        await mpub.stop()
    mean_ms = 1000.0 * sum(ttfts) / max(len(ttfts), 1)
    return mean_ms * SPEEDUP, cached  # report emulated-model time


async def main():
    quick = "--quick" in sys.argv
    ratios = [0.0, 0.5, 0.9] if quick else [0.0, 0.25, 0.5, 0.75, 0.9]
    n = 32 if quick else 56
    drt = await DistributedRuntime.detached()
    sweep = []
    for i, p in enumerate(ratios):
        warmup, prompts = make_requests(n, p, seed=1234 + i)
        kv_ms, kv_cached = await run_policy("kv", warmup, prompts, drt, f"rpx_kv_{i}")
        rr_ms, rr_cached = await run_policy("rr", warmup, prompts, drt, f"rpx_rr_{i}")
        sweep.append(
            {
                "prefix_ratio": p,
                "ttft_kv_ms": round(kv_ms, 2),
                "ttft_rr_ms": round(rr_ms, 2),
                "speedup": round(rr_ms / max(kv_ms, 1e-9), 2),
                "cached_tokens_kv": kv_cached,
                "cached_tokens_rr": rr_cached,
            }
        )
    await drt.shutdown()
    print(json.dumps({
        "isl": ISL, "workers": WORKERS, "groups": GROUPS, "osl": OSL,
        "worker_blocks": NUM_BLOCKS, "sweep": sweep,
    }))


if __name__ == "__main__":
    asyncio.run(main())
