#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip — what the driver runs
    python chip_smoke.py --chips 4    # the tp=4 path and what it is compared with, nothing else

One process holds the chip: server, aiohttp client and the parity checks all
run here, in one event loop. Run from a bare checkout (no install). Without
an accelerator the script fails: there is no CPU branch. ``--rehearse`` is the
explicit, never-default rehearsal of the same control flow at the ``tiny``
preset on whatever backend JAX has (CPU, virtual devices for ``--chips 4``).

One chip, in order:
1. *serve* — Llama-3.2-1B at full published width and depth (16 layers,
   random bf16 weights from ``--seed``, ByteTokenizer) through the construction
   ``python -m dynamo_tpu.run in=http out=llama-3.2-1b`` uses, on a loopback
   port; ``POST /v1/chat/completions``: one non-streamed, a few concurrent
   streams whose prompts take the 512-token bucket, 96 new tokens each, one arriving mid-decode
   (mixed step), one repeated prompt (prefix cache). The traffic mix is
   repeated on fresh prompts until a pass dispatches no new shape; that pass
   must compile nothing.
2. *parity* — the same weights through ``attention_impl="megakernel"`` +
   flash prefill, through ``"gather"`` + the flash kernel, and through
   ``"gather"`` + XLA prefill: one 512-token prefill, a second shorter one, and
   8 teacher-forced decode steps; logits compared within bf16 tolerance.
   Generated tokens are never compared: with random weights the top-2 margins
   sit below bf16 noise.

Every line of standard output is one JSON object; the last one is the verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``. Any
phase that fails makes the exit code non-zero and the verdict ``"ok": false``.
No rates and no utilisation: this is not the benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import time
import traceback

MODEL = "llama-3.2-1b"
REHEARSAL_MODEL = "tiny"
# Max-abs logit difference allowed between two attention paths on the same
# weights and cache contents, relative to the reference's largest |logit|:
# bf16 keeps 8 bits of mantissa (0.4% per rounding) and the paths differ in
# where the attention probabilities round and in accumulation order.
LOGIT_REL_TOL = 0.05
# Request interleaving decides batch buckets, so the traffic mix is repeated
# until a pass dispatches no new shape (4 passes on the first chip runs; a
# pass of warm shapes takes seconds).
MAX_PASSES = 8

_phase = "start"


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileMeter:
    """Counts what JAX really builds: every executable (compiled or fetched
    from the persistent cache) and the seconds it took."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.executables = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.executables += 1
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self) -> dict:
        return {
            "executables": self.executables,
            "compile_seconds": round(self.seconds, 2),
            "cache_hits": self.cache_hits,
            "cache_writes": self.cache_writes,
        }


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --- the device, the cache, what "auto" resolved to ---------------------------


def open_device(args):
    """Touch JAX (this process now holds the chip) and refuse anything that
    is not the accelerator the run was asked for."""
    global _phase
    _phase = "device"
    if args.rehearse and args.chips > 1:
        flag = f"--xla_force_host_platform_device_count={args.chips}"
        if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax

    from dynamo_tpu import native
    from dynamo_tpu.engine.compile_cache import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if not args.rehearse:
        check(device["platform"] == "tpu", f"JAX found no TPU: {device}")
    check(len(devs) >= args.chips, f"asked for {args.chips} chip(s), JAX sees {len(devs)}")
    cache_dir = enable_compile_cache()
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    emit({
        "phase": "device", "device": device, "rehearsal": args.rehearse, "seed": args.seed,
        "jax": jax.__version__, "compile_cache_dir": cache_dir, "compile_cache_warm": warm,
        "native_hash_extension": native.available(),
    })
    return device


# --- phase: serve over HTTP ----------------------------------------------------


def _words(rng: random.Random, n_bytes: int) -> str:
    out = []
    size = 0
    while size < n_bytes:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz") for _ in range(rng.randint(2, 9)))
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes]


def _chat_body(model: str, prompt: str, max_tokens: int, stream: bool) -> dict:
    return {
        "model": model,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
        "stream": stream,
        "nvext": {"ignore_eos": True},
    }


async def _post_json(session, url: str, body: dict) -> dict:
    async with session.post(url, json=body) as resp:
        text = await resp.text()
        check(resp.status == 200, f"HTTP {resp.status}: {text[:300]}")
        return json.loads(text)


async def _post_stream(session, url: str, body: dict, started: asyncio.Event | None = None) -> dict:
    """One SSE request; every frame must be ``data: <json>`` and the stream
    must end with ``data: [DONE]``. Returns the final frame's usage."""
    usage, finish, chunks, done, line = None, None, 0, False, ""
    async with session.post(url, json=body) as resp:
        if resp.status != 200:
            raise AssertionError(f"HTTP {resp.status}: {(await resp.text())[:300]}")
        check(resp.headers.get("Content-Type", "").startswith("text/event-stream"), "not an SSE response")
        async for raw in resp.content:
            line = raw.decode("utf-8").rstrip("\r\n")
            if not line:
                continue
            check(not done, f"SSE frame after [DONE]: {line[:80]}")
            check(line.startswith("data: "), f"malformed SSE line: {line[:80]!r}")
            payload = line[len("data: "):]
            if payload == "[DONE]":
                done = True
                continue
            frame = json.loads(payload)
            choice = frame["choices"][0]
            if choice.get("delta", {}).get("content"):
                chunks += 1
                if started is not None:
                    started.set()  # this stream is decoding
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
                usage = frame.get("usage")
    if started is not None:
        started.set()  # finished without a text delta (random bytes need not be UTF-8)
    check(done, f"SSE stream ended without [DONE]; last line: {line[:300]!r}")
    check(usage is not None, "final SSE frame carried no usage")
    return {"usage": usage, "finish_reason": finish, "content_chunks": chunks}


async def _traffic_pass(session, url: str, model: str, rng: random.Random, sizes: dict) -> dict:
    """The traffic mix once, on fresh prompts. Raises on any wrong answer."""
    new, short_new = sizes["max_tokens"], sizes["short_max_tokens"]

    def want(usage: dict, n: int, what: str) -> None:
        check(usage["completion_tokens"] == n, f"{what}: asked {n} tokens, usage says {usage}")

    # 1. one non-streamed request, alone.
    short = await _post_json(
        session, url, _chat_body(model, _words(rng, sizes["short_prompt"]), short_new, False)
    )
    check(isinstance(short["choices"][0]["message"]["content"], str), "no message content")
    want(short["usage"], short_new, "non-streamed")

    # 2. concurrent streams with long prompts; the last one is sent once the
    # first is decoding, so its prefill rides a mixed prefill+decode step.
    # Tokens reach the client a decode window (32 steps) at a time and the
    # next window is already in flight by then: max_tokens spans three
    # windows so that the late prompt finds the others still decoding.
    prompts = [_words(rng, sizes["long_prompt"]) for _ in range(sizes["streams"])]
    decoding = asyncio.Event()
    tasks = [
        asyncio.create_task(_post_stream(
            session, url, _chat_body(model, p, new, True), started=decoding if i == 0 else None
        ))
        for i, p in enumerate(prompts[:-1])
    ]
    await asyncio.wait([asyncio.ensure_future(decoding.wait()), tasks[0]], return_when=asyncio.FIRST_COMPLETED)
    tasks.append(asyncio.create_task(_post_stream(session, url, _chat_body(model, prompts[-1], new, True))))
    streams = await asyncio.gather(*tasks)
    for s in streams:
        want(s["usage"], new, "stream")
        check(s["usage"]["prompt_tokens"] >= sizes["long_prompt"], f"prompt too short: {s['usage']}")
        check(s["finish_reason"] == "length", f"finish_reason {s['finish_reason']!r}")

    # 3. the first long prompt again: answered through the prefix cache.
    again = await _post_stream(session, url, _chat_body(model, prompts[0], new, True))
    want(again["usage"], new, "repeat")
    cached = (again["usage"].get("prompt_tokens_details") or {}).get("cached_tokens", 0)
    check(cached > 0, f"repeated prompt was not served from the prefix cache: {again['usage']}")
    return {
        "requests": 2 + len(streams),
        "prompt_tokens": [s["usage"]["prompt_tokens"] for s in streams],
        "completion_tokens": [short_new] + [new] * (len(streams) + 1),
        "repeat_cached_tokens": cached,
    }


async def serve_phase(args, meter: CompileMeter):
    """Build the engine as ``dynamo_tpu.run`` does and drive it over HTTP.
    Returns ``(engine, drt)`` (the parity phase reuses the engine's weights)."""
    global _phase
    _phase = "serve"
    import aiohttp
    import jax

    from dynamo_tpu import run as dynamo_run
    from dynamo_tpu.llm.entrypoint import build_local_pipeline
    from dynamo_tpu.llm.tokenizer import load_tokenizer
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    model = REHEARSAL_MODEL if args.rehearse else MODEL
    sizes = (
        dict(short_prompt=24, long_prompt=100, max_tokens=40, short_max_tokens=6, streams=3)
        if args.rehearse
        else dict(short_prompt=48, long_prompt=480, max_tokens=96, short_max_tokens=16, streams=4)
    )
    t0 = time.time()
    run_args = argparse.Namespace(
        dtype="bfloat16", checkpoint=None, num_blocks=512, timeout=30.0, seed=args.seed
    )
    drt = await DistributedRuntime.from_settings()
    engine, _ = await dynamo_run.make_engine(model, run_args, drt)
    tokenizer = load_tokenizer(None)
    pipeline = build_local_pipeline(tokenizer, engine)
    service = await dynamo_run.serve_http(
        engine, tokenizer, pipeline, model, host="127.0.0.1", port=0
    )
    sched = engine.scheduler
    mc = sched.mc
    impl = {"attention_impl": sched._attn_impl, "prefill_impl": "flash" if sched._use_flash_prefill else "xla"}
    emit({
        "phase": "serve", "step": "built", "model": mc.name, "layers": mc.num_layers,
        "hidden": mc.hidden_size, "heads": [mc.num_heads, mc.num_kv_heads], "head_dim": mc.head_dim,
        "vocab": mc.vocab_size, "dtype": mc.dtype, "param_bytes": sched._param_bytes,
        "kv_cache_bytes": sched._kv_cache_bytes, "port": service.port, **impl,
        "seconds": round(time.time() - t0, 2),
    })
    try:
        if not args.rehearse:
            check(
                impl == {"attention_impl": "megakernel", "prefill_impl": "flash"},
                f"on a TPU 'auto' must resolve to the Pallas paths, got {impl}",
            )
            check(mc.num_layers == 16 and mc.hidden_size == 2048 and mc.vocab_size == 128256, f"not the full 1B: {mc}")
        url = f"http://127.0.0.1:{service.port}/v1/chat/completions"
        rng = random.Random(args.seed)
        timeout = aiohttp.ClientTimeout(total=1000)
        async with aiohttp.ClientSession(timeout=timeout) as session:
            # Repeat the mix on fresh prompts until a pass dispatches no new
            # shape (request interleaving decides batch buckets, so the first
            # pass need not see them all). That pass must compile nothing.
            # Whether the late prompt finds the others still decoding is a
            # race too (at the rehearsal's size a whole answer is a few
            # milliseconds of decoding, and a loaded host sends late), so a
            # pass without a mixed step is repeated as well.
            for n in range(1, MAX_PASSES + 1):
                t1 = time.time()
                shapes0, execs0 = sched.flight.compiles_total, meter.executables
                cached0 = sched.cached_tokens_total
                result = await _traffic_pass(session, url, model, rng, sizes)
                new_shapes = sched.flight.compiles_total - shapes0
                built = meter.executables - execs0
                check(sched.cached_tokens_total > cached0, "scheduler counted no cached prompt tokens")
                emit({
                    "phase": "serve", "step": f"pass{n}", **result,
                    "scheduler_cached_tokens_total": sched.cached_tokens_total,
                    "new_shape_keys": new_shapes, "executables_built": built,
                    "mixed_steps_total": sched.mixed_steps_total,
                    "seconds": round(time.time() - t1, 2),
                })
                if new_shapes == 0 and sched.mixed_steps_total > 0:
                    break
            check(new_shapes == 0, f"the traffic mix still dispatched new shapes in pass {MAX_PASSES}")
            check(built == 0, f"{built} executables were built in a pass of warm shapes")
        keys = sorted(sched.flight._exec_keys, key=str)
        kinds = {k[0] for k in keys}
        check(sched.mixed_steps_total > 0, "no mixed prefill+decode step was dispatched")
        check("decode_multi" in kinds, f"no decode window was dispatched: {sorted(kinds)}")
        if not args.rehearse:
            check(any(k[0] in ("prefill", "admit", "mixed") and max(k[1:3]) >= 512 for k in keys),
                  f"no prefill took a >=512-token bucket: {keys}")
        emit({
            "phase": "serve", "step": "done", "shape_keys": [list(map(str, k)) for k in keys],
            "compiles_after_warm_shapes": 0,
            "peak_bytes_in_use": peak_bytes(jax.devices()[0]), **meter.snapshot(),
            "seconds": round(time.time() - t0, 2),
        })
    finally:
        await service.stop()
    return engine, drt


# --- phase: kernel-vs-XLA parity at full width ---------------------------------


def _compare(name: str, got, ref) -> dict:
    """Max-abs logit difference against ``ref`` and argmax agreement on the
    rows whose top-2 margin exceeds that difference's bound."""
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} vs {ref.shape}")
    check(bool(np.isfinite(got).all()) and bool(np.isfinite(ref).all()), f"{name}: non-finite logits")
    ref_absmax = float(np.abs(ref).max())
    tol = LOGIT_REL_TOL * ref_absmax
    diff = float(np.abs(got - ref).max())
    top2 = np.sort(ref, axis=-1)[..., -2:]
    decided = (top2[..., 1] - top2[..., 0]) > 2 * tol  # margin a ±tol shift cannot flip
    agree = np.argmax(got, axis=-1) == np.argmax(ref, axis=-1)
    out = {
        "compare": name, "max_abs_diff": diff, "ref_absmax": ref_absmax, "tolerance": tol,
        "rows": int(agree.size), "argmax_agree": int(agree.sum()),
        "rows_with_decisive_margin": int(decided.sum()),
        "decisive_rows_agree": int((agree & decided).sum()),
    }
    check(diff <= tol, f"{name}: max-abs logit difference {diff} exceeds {tol}")
    check(bool((agree | ~decided).all()), f"{name}: argmax differs where the margin exceeds the tolerance: {out}")
    return out


def parity_phase(args, engine) -> None:
    global _phase
    _phase = "parity"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import llama

    t0 = time.time()
    sched = engine.scheduler
    base, params = sched.mc, sched.params
    T = 64 if args.rehearse else 512
    B, W, steps = 8, (T + 16 * 8) // base.block_size, 8
    rs = np.random.RandomState(args.seed)
    lens = [T, (T * 5) // 8]  # a full bucket and a ragged one
    prompts = [rs.randint(1, base.vocab_size, size=T).astype(np.int32) for _ in lens]
    forced = rs.randint(1, base.vocab_size, size=(steps, B)).astype(np.int32)  # teacher-forced decode inputs
    tables = np.zeros((B, W), np.int32)
    for row in range(len(lens)):
        tables[row] = 1 + row * W + np.arange(W)
    active = np.zeros((B,), np.int32)
    active[: len(lens)] = 1

    def run_path(attention_impl: str, use_flash: bool):
        cfg = base.replace(attention_impl=attention_impl)
        cache = KvCacheArrays.create(cfg, 1 + len(lens) * W, dtype=jnp.bfloat16)
        prefill = jax.jit(
            lambda p, k, v, t, vl, bt: llama.prefill(
                p, cfg, k, v, t, vl, jnp.int32(0), bt, use_flash=use_flash, has_prefix=False
            ),
            donate_argnums=(1, 2),
        )
        decode = jax.jit(
            lambda p, k, v, t, pos, bt, act: llama.decode(p, cfg, k, v, t, pos, bt, act),
            donate_argnums=(1, 2),
        )
        k, v = cache.k, cache.v
        logits = []
        for row, n in enumerate(lens):
            lg, k, v = prefill(params, k, v, jnp.asarray(prompts[row]), jnp.int32(n), jnp.asarray(tables[row]))
            logits.append(np.asarray(lg)[None])
        pos = np.zeros((B,), np.int32)
        pos[: len(lens)] = lens
        dec = []
        for s in range(steps):
            lg, k, v = decode(
                params, k, v, jnp.asarray(forced[s]), jnp.asarray(pos + s * active),
                jnp.asarray(tables), jnp.asarray(active.astype(bool)),
            )
            dec.append(np.asarray(lg)[: len(lens)])
        return np.concatenate(logits), np.stack(dec)  # [2, V], [steps, 2, V]

    ref_p, ref_d = run_path("gather", use_flash=False)
    for name, attention_impl in (("megakernel+flash", "megakernel"), ("gather+flash-kernel", "gather")):
        got_p, got_d = run_path(attention_impl, use_flash=True)
        emit({"phase": "parity", "prefill_tokens": lens, "decode_steps": steps,
              **_compare(f"{name} vs gather+xla: prefill", got_p, ref_p)})
        emit({"phase": "parity", **_compare(f"{name} vs gather+xla: decode", got_d, ref_d)})
    emit({"phase": "parity", "step": "done", "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
          "seconds": round(time.time() - t0, 2)})


# --- --chips 4: the tp=4 engine against a one-device engine ---------------------


async def tp4_phase(args, meter: CompileMeter) -> None:
    global _phase
    _phase = "tp4"
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.engine.sharding import ParallelConfig
    from dynamo_tpu.llm.entrypoint import build_local_pipeline
    from dynamo_tpu.llm.tokenizer import load_tokenizer
    from dynamo_tpu.runtime.engine import Context

    t0 = time.time()
    model = REHEARSAL_MODEL if args.rehearse else MODEL
    tp = args.chips
    if args.rehearse:
        tp = 2  # tiny has 2 KV heads: the widest tp its kernels partition over

    def build(parallel):
        return TpuEngine.build(EngineArgs(
            model=model, seed=args.seed, parallel=parallel,
            scheduler=SchedulerConfig(num_blocks=512),
        ))

    sharded = build(ParallelConfig(tp=tp))
    gc.collect()
    s_tp = sharded.scheduler
    devs = jax.devices()[:tp]
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devs]
    leaves = jax.tree_util.tree_leaves((s_tp.params, s_tp.cache.k, s_tp.cache.v))
    n_devs = sorted({len(x.sharding.device_set) for x in leaves})
    impl = {"attention_impl": s_tp._attn_impl, "prefill_impl": "flash" if s_tp._use_flash_prefill else "xla"}
    emit({
        "phase": "tp4", "step": "built", "model": s_tp.mc.name, "layers": s_tp.mc.num_layers, "tp": tp,
        "mesh": {ax: int(n) for ax, n in s_tp.mesh.shape.items()}, **impl,
        "sharding_device_set_sizes": n_devs, "bytes_in_use_per_device": in_use,
        "seconds": round(time.time() - t0, 2),
    })
    check(n_devs == [tp], f"params/cache leaves live on {n_devs} devices, want all on {tp}")
    if not args.rehearse:
        check(impl == {"attention_impl": "megakernel", "prefill_impl": "flash"},
              f"under the tp mesh the Pallas paths must partition, got {impl}")
        check(min(in_use) > 0, f"a device holds nothing: {in_use}")
        check(max(in_use) <= 1.25 * min(in_use), f"bytes piled unevenly across devices: {in_use}")

    single = build(None)
    s_1 = single.scheduler
    check(s_1.mesh is None and s_1._attn_impl == s_tp._attn_impl, "the one-device engine took another path")

    # The same prompts through each engine's own prefill and decode
    # executables (the programs serving dispatches), logits compared.
    T = 64 if args.rehearse else 512
    W, B = (T + 128) // s_1.mc.block_size, 8
    rs = np.random.RandomState(args.seed)
    prompt = rs.randint(1, s_1.mc.vocab_size, size=T).astype(np.int32)
    table = (1 + np.arange(W)).astype(np.int32)
    # The packed operands of a dispatch (scheduler.pack_operands): the chunk's tokens, then its length and its start; a
    # batch's [3, B] lanes (token, position, active).
    from dynamo_tpu.engine.scheduler import pack_operands

    chunk = pack_operands(prompt, T, 0)
    rows = np.zeros((3, B), np.int32)
    rows[:, 0] = (int(rs.randint(1, s_1.mc.vocab_size)), T, 1)
    tables = np.zeros((B, W), np.int32)
    tables[0] = table

    def logits_of(s):
        hp = (False,) if s._hp_static else ()
        res = s._prefill_jit(s.params, s.cache.k, s.cache.v, jnp.asarray(chunk), jnp.asarray(table), *hp)
        _, pre, s.cache.k, s.cache.v = res[:4]
        res = s._decode_jit(s.params, s.cache.k, s.cache.v, jnp.asarray(pack_operands(rows)), jnp.asarray(tables))
        _, dec, s.cache.k, s.cache.v = res[:4]
        return np.asarray(pre), np.asarray(dec)[:1]

    got_p, got_d = logits_of(s_tp)
    ref_p, ref_d = logits_of(s_1)
    emit({"phase": "tp4", "prefill_tokens": T, **_compare(f"tp={tp} vs one device: prefill", got_p, ref_p)})
    emit({"phase": "tp4", **_compare(f"tp={tp} vs one device: first decode step", got_d, ref_d)})

    # And the serving loop itself under the mesh: one request end to end.
    pipeline = build_local_pipeline(load_tokenizer(None), sharded)
    body = {
        "model": model, "messages": [{"role": "user", "content": "four chips, one engine"}],
        "max_tokens": 8, "temperature": 0.0, "nvext": {"ignore_eos": True},
    }
    n_out = 0
    async for item in pipeline.generate(body, Context()):
        data = item.data if hasattr(item, "data") else item
        n_out += len((data or {}).get("token_ids") or [])
    check(n_out == 8, f"the tp={tp} engine generated {n_out} tokens, asked for 8")
    emit({
        "phase": "tp4", "step": "done", "served_tokens": n_out, **impl,
        "peak_bytes_in_use_per_device": [peak_bytes(d) for d in devs], **meter.snapshot(),
        "seconds": round(time.time() - t0, 2),
    })
    await sharded.stop()
    await single.stop()


# --- entry ----------------------------------------------------------------------


async def amain(args, meter: CompileMeter) -> None:
    if args.chips > 1:
        await tp4_phase(args, meter)
        return
    engine, drt = await serve_phase(args, meter)
    try:
        parity_phase(args, engine)
    finally:
        await engine.stop()
        await drt.shutdown()


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0], allow_abbrev=False)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the tp=4 engine and the one-device engine it is compared with")
    p.add_argument("--seed", type=int, default=0, help="weights, prompts and teacher-forced tokens")
    p.add_argument("--rehearse", action="store_true",
                   help="control-flow rehearsal at the tiny preset on any backend; never the default")
    args = p.parse_args()
    t0 = time.time()
    device = None
    try:
        device = open_device(args)
        meter = CompileMeter()
        asyncio.run(amain(args, meter))
        emit({"phase": "total", **meter.snapshot(), "seconds": round(time.time() - t0, 2)})
    except BaseException as e:  # noqa: BLE001 — every failure, whatever it is, fails the smoke
        traceback.print_exc()
        emit({"ok": False, "phase": _phase, "error": f"{type(e).__name__}: {e}"[:600], "device": device})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # No interpreter finalization: a daemon thread of the program (profiler capture, trace writer) that
    # returns from native code while Python tears down is unwound by force and aborts the process with
    # "FATAL: exception not rethrown" after the result line (seen once in six-worker test runs).
    os._exit(code)
