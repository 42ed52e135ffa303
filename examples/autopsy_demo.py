"""Incident autopsy demo: inject a synthetic latency spike through the demo
stack and watch the diagnosis plane catch it.

What happens:

1. A tiny engine serves behind the OpenAI frontend (frontend → router →
   worker wire path → scheduler) with the incident plane pointed at a
   scratch directory and ring-only tracing armed (no trace file anywhere —
   the in-memory black box is the only trace sink).
2. Calm sequential traffic builds the anomaly detector's trailing
   baselines over the real stats-scrape wire.
3. A concurrency burst against two decode slots injects a queue-wait
   spike; the next scrape fires the detector, which writes ONE debounced
   incident bundle (debug state, step ring, trace ring, digests, thread
   stacks, config, the triggering signal + baseline).
4. ``tools/autopsy.py`` reads the bundle back and attributes the spike —
   queue wait, not prefill/decode/compile — with the signal ratios as
   evidence, then drills into one spiked request from the trace ring.

Run: python examples/autopsy_demo.py
"""

import asyncio
import glob
import json
import os
import sys
import tempfile

import aiohttp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Run as a file from a bare checkout: the package and tools/ sit under REPO.
sys.path[:0] = [REPO, os.path.join(REPO, "tools")]


async def main():
    import autopsy  # tools/autopsy.py

    from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.entrypoint import build_routed_pipeline, register_llm
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.tokenizer import ByteTokenizer
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.incidents import DetectorConfig
    from dynamo_tpu.runtime.push_router import PushRouter
    from dynamo_tpu.runtime.tracing import configure_tracing

    incident_dir = tempfile.mkdtemp(prefix="autopsy_demo_")
    configure_tracing(path=None, sample=1.0, ring_size=1024, service="demo")
    drt = await DistributedRuntime.detached()

    print("building engine (2 decode slots — easy to saturate) ...")
    engine = TpuEngine.build(
        EngineArgs(
            model="tiny", dtype="float32", eos_token_ids=[0],
            scheduler=SchedulerConfig(
                num_blocks=128, max_running=2,
                prefill_buckets=[16, 32, 64], decode_buckets=[1, 2, 4],
                enable_mixed_batching=False,
            ),
            warmup_ctx=128,
            incident_dir=incident_dir,
        )
    )
    # Demo-friendly thresholds: fire on a 50 ms / 3x excursion, one bundle.
    engine.incidents.detector.config = DetectorConfig(
        jump_factor=3.0, min_abs_s=0.05, min_window_count=6, baseline_checks=3,
        debounce_s=600.0,
    )

    ep = drt.namespace("demo").component("backend").endpoint("generate")
    card = ModelDeploymentCard(name="tiny-demo", model_type="chat")
    handle, _ = await register_llm(drt, ep, engine, card,
                                   stats_handler=engine.stats_handler)
    drt.local_engines.pop(handle.instance.instance_id)  # full wire path
    client = await ep.client()
    await client.wait_for_instances(1, timeout=5)
    manager = ModelManager()
    manager.add_model(
        "chat", "tiny-demo",
        build_routed_pipeline(ByteTokenizer(), PushRouter(client), card),
    )
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()

    async def post(session, i, tokens):
        body = {"model": "tiny-demo",
                "messages": [{"role": "user", "content": f"request {i}"}],
                "max_tokens": tokens, "temperature": 0}
        async with session.post(
            f"http://127.0.0.1:{service.port}/v1/chat/completions", json=body
        ) as r:
            r.raise_for_status()
            await r.json()

    try:
        async with aiohttp.ClientSession() as session:
            print("calm traffic: 8 sequential requests (baseline builds per scrape)")
            for i in range(8):
                await post(session, i, 4)
                await client.scrape_stats()  # detector check rides the scrape

            print("spike: 24-way burst against 2 decode slots ...")
            await asyncio.gather(*(post(session, 100 + i, 32) for i in range(24)))
            stats = await client.scrape_stats()  # this scrape fires the detector
            w = next(iter(stats.values()))
            print(f"incidents_total={w['incidents_total']} "
                  f"incident_last_age_s={w['incident_last_age_s']}")
    finally:
        await service.stop()
        await engine.stop()
        await drt.shutdown()
        configure_tracing(path=None, sample=0.0, ring_size=0)

    bundles = sorted(glob.glob(os.path.join(incident_dir, "incident_*.json")))
    print(f"\nbundle: {bundles[0] if bundles else '(none — try a slower machine?)'}")
    if not bundles:
        return
    bundle = autopsy.load_bundle(bundles[0])
    report = autopsy.incident_report(bundle)
    print("\n--- incident autopsy ---")
    autopsy.render(report)

    # Drill into the most-queued request from the bundle's trace ring.
    admitted = [r for r in bundle["trace_ring"] if r.get("name") == "admitted"]
    if admitted:
        worst = max(admitted, key=lambda r: (r.get("attrs") or {}).get("queue_s", 0))
        print("\n--- worst request in the black box ---")
        autopsy.render(
            autopsy.request_report(bundle["trace_ring"], worst["trace_id"], bundle=bundle)
        )
    print(f"\nexplore further:\n  python tools/trace_view.py {bundles[0]}\n"
          f"  python tools/autopsy.py {bundles[0]} --json")


if __name__ == "__main__":
    asyncio.run(main())
