"""Structured outputs (guided decoding) against an in-process serving stack.

Runs the full pipeline — OpenAI HTTP frontend → preprocessor → TpuEngine
(tiny model, byte tokenizer) → backend — and exercises the three guided
surfaces: response_format json_schema, a forced tool call, and a choice
list. No checkpoint needed: the token-FSM guarantees grammar-valid output
whatever the (random) weights emit.

    python examples/structured_outputs.py
"""

import asyncio
import json
import os
import sys

import aiohttp

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.llm.discovery import ModelManager
from dynamo_tpu.llm.entrypoint import build_local_pipeline
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.llm.tokenizer import ByteTokenizer

MODEL = "tiny-chat"

WEATHER_SCHEMA = {
    "type": "object",
    "properties": {
        "city": {"enum": ["SF", "NY", "Tokyo"]},
        "unit": {"enum": ["celsius", "fahrenheit"]},
        "days": {"type": "integer"},
    },
}


async def main() -> None:
    tokenizer = ByteTokenizer()
    engine = TpuEngine.build(
        EngineArgs(
            model="tiny",
            dtype="float32",
            eos_token_ids=[0],
            tokenizer=tokenizer,  # guided decoding lifts grammars against it
            scheduler=SchedulerConfig(num_blocks=64, guided_pool_rows=512),
        )
    )
    manager = ModelManager()
    manager.add_model("chat", MODEL, build_local_pipeline(tokenizer, engine))
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    base = f"http://127.0.0.1:{service.port}/v1"

    async with aiohttp.ClientSession() as s:
        # 1) response_format: json_schema — the output IS valid JSON.
        body = {
            "model": MODEL,
            "messages": [{"role": "user", "content": "weather in SF?"}],
            "max_tokens": 64,
            "temperature": 0,
            "response_format": {
                "type": "json_schema",
                "json_schema": {"name": "weather", "schema": WEATHER_SCHEMA},
            },
        }
        async with s.post(f"{base}/chat/completions", json=body) as r:
            data = await r.json()
        content = data["choices"][0]["message"]["content"]
        print("json_schema  ->", content, "| parsed:", json.loads(content))

        # 2) forced tool call — parseable tool_calls, finish 'tool_calls'.
        body = {
            "model": MODEL,
            "messages": [{"role": "user", "content": "look it up"}],
            "max_tokens": 96,
            "temperature": 0,
            "tools": [{"type": "function", "function": {"name": "get_weather", "parameters": WEATHER_SCHEMA}}],
            "tool_choice": {"type": "function", "function": {"name": "get_weather"}},
        }
        async with s.post(f"{base}/chat/completions", json=body) as r:
            data = await r.json()
        call = data["choices"][0]["message"]["tool_calls"][0]["function"]
        print("tool_choice  ->", call["name"], json.loads(call["arguments"]))

        # 3) choice list (nvext extension) on completions.
        body = {
            "model": MODEL,
            "prompt": "pick a color:",
            "max_tokens": 16,
            "temperature": 0,
            "nvext": {"guided_choice": ["red", "green", "blue"]},
        }
        async with s.post(f"{base}/completions", json=body) as r:
            data = await r.json()
        print("guided_choice ->", data["choices"][0]["text"])

    await service.stop()
    await engine.stop()


if __name__ == "__main__":
    asyncio.run(main())
