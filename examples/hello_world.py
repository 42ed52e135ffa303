"""Hello-world: serve an endpoint, discover it, route a request, stream the
response — the dynamo-tpu equivalent of the reference's
examples/runtime/hello_world (SURVEY.md §3B worker registration flow).

Run: python examples/hello_world.py
"""

import asyncio
import os
import sys

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.runtime import DistributedRuntime, PushRouter
from dynamo_tpu.runtime.health import SystemHealth, SystemStatusServer, HEALTHY


async def generate(request, context):
    """A toy engine: yields each word of the prompt, uppercased."""
    for word in request["prompt"].split():
        yield {"token": word.upper()}


async def main():
    drt = await DistributedRuntime.detached()

    # Worker side: register + serve.
    endpoint = drt.namespace("hello").component("backend").endpoint("generate")
    handle = await endpoint.serve_endpoint(generate, stats_handler=lambda: {"kv_usage": 0.1})

    # Force the full wire path (pub/sub push + TCP call-home) instead of the
    # in-process fast path, to demonstrate the data plane.
    drt.local_engines.pop(handle.instance.instance_id)

    # Client side: discover + route + stream.
    client = await endpoint.client()
    instances = await client.wait_for_instances(1)
    print(f"discovered instances: {[f'{i.instance_id:x}' for i in instances]}")

    router = PushRouter(client)
    print("response:", end=" ")
    async for item in router.generate({"prompt": "hello distributed tpu world"}):
        print(item.data["token"], end=" ", flush=True)
    print()

    stats = await client.scrape_stats()
    print(f"stats: {stats}")

    # System status server over real HTTP.
    health = SystemHealth()
    health.set_system_ready()
    health.set_endpoint_health(endpoint.path, HEALTHY)
    server = SystemStatusServer(health)
    await server.start()
    import aiohttp

    async with aiohttp.ClientSession() as session:
        async with session.get(f"http://127.0.0.1:{server.port}/health") as resp:
            print(f"GET /health -> {resp.status}: {await resp.text()}")
    await server.stop()
    await drt.shutdown()
    print("clean shutdown")


if __name__ == "__main__":
    asyncio.run(main())
