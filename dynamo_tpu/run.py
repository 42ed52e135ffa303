"""dynamo-run equivalent: single-command launcher.

Ref: launch/dynamo-run (SURVEY.md §3E) — ``dynamo-run in=X out=Y``:
- in:  http | text | batch:<prompts.jsonl>
- out: <model-preset> | mocker | dyn://<ns>.<component>.<endpoint>

Examples:
  python -m dynamo_tpu.run in=http out=tiny
  python -m dynamo_tpu.run in=text out=tiny
  python -m dynamo_tpu.run in=batch:prompts.jsonl out=tiny --output results.jsonl
  python -m dynamo_tpu.run in=http out=dyn://dynamo.backend.generate
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.llm.discovery import ModelManager
from dynamo_tpu.llm.entrypoint import RouterEngine, build_local_pipeline
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.llm.mocker import MockEngineArgs, MockTpuEngine
from dynamo_tpu.llm.tokenizer import load_tokenizer
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.logging import get_logger, init_logging
from dynamo_tpu.runtime.push_router import PushRouter

logger = get_logger(__name__)


async def make_engine(out_spec: str, args, drt):
    """Resolve out= to (engine, needs_drt)."""
    if out_spec == "mocker":
        return MockTpuEngine(MockEngineArgs()), None
    if out_spec.startswith("dyn://"):
        path = out_spec[6:]
        ns, comp, ep_name = path.split(".")
        ep = drt.namespace(ns).component(comp).endpoint(ep_name)
        client = await ep.client()
        await client.wait_for_instances(1, timeout=args.timeout)
        return RouterEngine(PushRouter(client)), None
    engine = TpuEngine.build(
        EngineArgs(
            model=out_spec,
            dtype=args.dtype,
            checkpoint_path=args.checkpoint,
            seed=args.seed,
            scheduler=SchedulerConfig(num_blocks=args.num_blocks),
        )
    )
    return engine, None


async def serve_http(engine, tokenizer, pipeline, model_name: str, *, host: str, port: int) -> HttpService:
    """The ``in=http`` frontend over ``engine``, started: chat completions,
    plus embeddings when the engine is local. The caller stops it."""
    manager = ModelManager()
    manager.add_model("chat", model_name, pipeline)
    if isinstance(engine, TpuEngine):
        from dynamo_tpu.engine.embeddings import EmbeddingEngine
        from dynamo_tpu.llm.entrypoint import build_embeddings_pipeline

        sched = engine.scheduler
        manager.add_model(
            "embeddings",
            model_name,
            build_embeddings_pipeline(tokenizer, EmbeddingEngine(sched.mc, sched.params)),
        )
    service = HttpService(manager, host=host, port=port)
    await service.start()
    return service


async def amain(args) -> None:
    drt = await DistributedRuntime.from_settings()
    engine, _ = await make_engine(args.out, args, drt)
    tokenizer = load_tokenizer(args.tokenizer)
    pipeline = build_local_pipeline(tokenizer, engine)
    model_name = args.model_name or args.out

    if args.mode == "http":
        service = await serve_http(
            engine, tokenizer, pipeline, model_name, host="0.0.0.0", port=args.http_port
        )
        print(f"serving {model_name} on :{service.port} (POST /v1/chat/completions)", flush=True)
        drt.runtime.install_signal_handlers()
        await drt.runtime.cancellation.cancelled()
        await service.stop()
    elif args.mode == "text":
        print(f"interactive chat with {model_name}; ctrl-d to exit")
        loop = asyncio.get_running_loop()
        while True:
            try:
                line = await loop.run_in_executor(None, lambda: input("> "))
            except (EOFError, KeyboardInterrupt):
                break
            body = {
                "model": model_name,
                "messages": [{"role": "user", "content": line}],
                "max_tokens": args.max_tokens,
            }
            async for item in pipeline.generate(body, Context()):
                data = item.data if hasattr(item, "data") else item
                if data and data.get("text"):
                    print(data["text"], end="", flush=True)
            print()
    elif args.mode.startswith("batch"):
        path = args.mode.split(":", 1)[1]
        out_path = args.output or "results.jsonl"
        with open(path) as f, open(out_path, "w") as out_f:
            for line in f:
                if not line.strip():
                    continue
                rec = json.loads(line)
                body = {
                    "model": model_name,
                    "prompt": rec.get("prompt") or rec.get("text", ""),
                    "max_tokens": rec.get("max_tokens", args.max_tokens),
                }
                text_parts = []
                async for item in pipeline.generate(body, Context()):
                    data = item.data if hasattr(item, "data") else item
                    if data and data.get("text"):
                        text_parts.append(data["text"])
                out_f.write(json.dumps({"prompt": body["prompt"], "output": "".join(text_parts)}) + "\n")
        print(f"batch results written to {out_path}")
    if hasattr(engine, "stop"):
        await engine.stop()
    await drt.shutdown()


def main() -> None:
    init_logging()
    p = argparse.ArgumentParser(description="dynamo-run for TPU", allow_abbrev=False)
    p.add_argument("io", nargs=2, help="in=http|text|batch:<file> out=<model>|mocker|dyn://ns.comp.ep")
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--model-name", default=None)
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--num-blocks", type=int, default=512)
    p.add_argument("--max-tokens", type=int, default=128)
    p.add_argument("--output", default=None)
    p.add_argument("--timeout", type=float, default=30.0)
    p.set_defaults(seed=0)  # EngineArgs.seed (random-weight init); no flag — chip_smoke.py sets it
    args = p.parse_args()
    spec = {}
    for part in args.io:
        key, _, value = part.partition("=")
        spec[key] = value
    if "in" not in spec or "out" not in spec:
        p.error("expected in=... out=...")
    args.mode = spec["in"]
    args.out = spec["out"]
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
