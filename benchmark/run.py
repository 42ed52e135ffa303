#!/usr/bin/env python3
"""The benchmark's one runner.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``benchmark/configs/<config>.json``) under a traffic mix
(``benchmark/traffic/<mix>.json``). Its metrics are files under
``benchmark/metrics/``; which cells report a metric is the manifest's to say.
The configuration names its family, and everything that depends on the
architecture comes from ``benchmark/families/<family>.py``. Nothing in this
file names a cell, a configuration, a mix, a metric or a model.

One process holds the chip: it makes the weights from ``--seed`` on the
device, checks the program's logits against the plain reference, builds the
program's engine and serves it over HTTP exactly as ``python -m dynamo_tpu.run
in=http`` does, and starts the load generator as a child process that never
imports JAX. Order of a run:

1. *set-up*: weights, output check, engine, HTTP service; then the warm-up
   stream (the cell's own generator at the cell's rate on a fixed seed of its
   own) until JAX has built nothing for a while; the stream drains.
2. *ramp*: the measured schedule's requests due before the window: served,
   not counted.
3. *window*: ``--seconds`` long. Requests due inside it are judged, each
   timed from when it was due; they drain after it.

Without an accelerator the runner exits non-zero and prints no result.
``--rehearse`` is the explicit, never-default rehearsal of the same control
flow at the configuration's tiny ``rehearsal`` sizes on whatever backend JAX
has; it prints counts only, never a time under a metric's name.

Every line of standard output is one JSON object; the last is the result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
STATE = os.path.join(ROOT, ".bench_state")  # schedules, results, tokenizers, traces (git-ignored)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def resolve_cell(workload: str, rehearse: bool):
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r} (have {sorted(cells)})")
    cell = cells[workload]
    cfg = load_json(HERE, "configs", f"{cell['config']}.json")
    mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    if rehearse:
        cfg, mix = overlay(cfg, cfg.get("rehearsal", {})), overlay(mix, mix.get("rehearsal", {}))
    return manifest, cell, cfg, mix


def open_device(chips: int, rehearse: bool) -> dict:
    """Touch JAX (this process now holds the chip), point its persistent
    cache at the checkout, and refuse anything but the accelerator asked for."""
    import jax

    from dynamo_tpu.engine.compile_cache import enable_compile_cache

    # Every executable an earlier run of this checkout reached is a cache hit:
    # by default JAX keeps only those that took a second or more to compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if not rehearse and device["platform"] != "tpu":
        raise SystemExit(f"JAX found no TPU: {device}")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), JAX sees {len(devs)}")
    device["count"] = chips
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    emit({"phase": "device", "device": device, "jax": jax.__version__, "compile_cache_dir": cache_dir,
          "compile_cache_warm": warm})
    return device


def peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Run:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# --- the load generator's child process ------------------------------------------


async def start_loadgen(url: str, reqs, model: str, mix: dict, t0: float, tag: str, max_inflight: int = 0):
    from benchmark import traffic

    os.makedirs(STATE, exist_ok=True)
    sched_path = os.path.join(STATE, f"schedule.{tag}.jsonl")
    out_path = os.path.join(STATE, f"client.{tag}.json")
    if os.path.exists(out_path):
        os.remove(out_path)
    traffic.write_schedule(sched_path, reqs, model, mix)
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    proc = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "loadgen.py"), "--url", url, "--schedule", sched_path,
        "--t0", repr(t0), "--out", out_path, "--max-inflight", str(max_inflight),
        stdin=asyncio.subprocess.PIPE, env=env,
    )
    return proc, out_path


async def finish_loadgen(proc, out_path: str) -> dict:
    rc = await proc.wait()
    if rc != 0 or not os.path.exists(out_path):
        raise RuntimeError(f"the load generator exited with {rc}")
    return load_json(out_path)


async def stop_issuing(proc) -> None:
    try:
        proc.stdin.write(b"stop\n")
        await proc.stdin.drain()
    except (BrokenPipeError, ConnectionResetError):
        pass


# --- phases -----------------------------------------------------------------------


def engine_alive(engine) -> None:
    task = engine._loop_task
    if task is not None and task.done():
        raise RuntimeError(f"the engine's step loop ended: {task.exception()!r}")


async def warmup(url, model, mix, vocab, meter, engine) -> dict:
    """The cell's own generator at the cell's rate, on the mix's warm-up seed,
    until JAX has built nothing for ``quiet_s`` seconds (and compiled nothing
    that missed the persistent cache for ``quiet_after_compile_s``)."""
    from benchmark import traffic

    w = mix["warmup"]
    reqs = traffic.schedule(mix, w["seed"], w["max_s"], vocab, ramp=False)
    for r in reqs:
        r.counted = False
    t0 = time.monotonic() + 1.0
    e0, m0 = meter.executables, len(meter.misses)
    proc, out = await start_loadgen(url, reqs, model, mix, t0, "warmup", max_inflight=int(w.get("max_inflight", 96)))
    while True:
        await asyncio.sleep(0.25)
        engine_alive(engine)
        now = time.monotonic()
        el = now - t0
        quiet = now - max(meter.last_build(), t0) >= w["quiet_s"]
        if len(meter.misses) > m0:  # something was compiled, not loaded: look longer
            quiet = quiet and now - meter.last_miss() >= w["quiet_after_compile_s"]
        if (el >= w["min_s"] and quiet) or el >= w["max_s"] or proc.returncode is not None:
            break
    await stop_issuing(proc)
    res = await finish_loadgen(proc, out)
    sent = [r for r in res["requests"] if r["sent"] is not None]
    from benchmark.readers import request_ok

    failed = [r for r in sent if r["error"] != "shed" and not request_ok(r)]
    if failed:
        raise RuntimeError(f"{len(failed)} of {len(sent)} warm-up requests failed, e.g. {failed[0]['error']!r}")
    return {"stream_s": time.monotonic() - t0, "issued_for_s": el, "requests": len(sent),
            "shed": sum(r["error"] == "shed" for r in sent),
            "executables_built": meter.executables - e0, "cache_misses": len(meter.misses) - m0}


async def capture_trace(hooks, trace_dir: str, t_start: float, seconds: float) -> None:
    """A slice of the window under ``jax.profiler``; the benchmark's marks
    bound it so that the reductions use exactly this span."""
    import jax

    await asyncio.sleep(max(0.0, t_start - time.monotonic()))
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host marks come from TraceAnnotation; Python frames only slow the host
    options.host_tracer_level = 2
    await asyncio.to_thread(lambda: jax.profiler.start_trace(trace_dir, profiler_options=options))
    hooks.annotate = True
    await asyncio.sleep(0.05)
    hooks.point("window_open")
    await asyncio.sleep(seconds)
    hooks.point("window_close")
    hooks.annotate = False
    await asyncio.to_thread(jax.profiler.stop_trace)


async def measured_pass(url, model, mix, vocab, seed, seconds, hooks, trace_dir, rate_scale=1.0, tag="measured"):
    from benchmark import traffic

    reqs = traffic.schedule(mix, seed, seconds, vocab, rate_scale=rate_scale)
    t_open = time.monotonic() + float(mix.get("ramp_s", 0.0)) + 1.0
    proc, out = await start_loadgen(url, reqs, model, mix, t_open, tag)
    tracer = None
    if trace_dir:
        tc = mix["trace"]
        tracer = asyncio.ensure_future(capture_trace(hooks, trace_dir, t_open + tc["start_s"], tc["seconds"]))
    while proc.returncode is None:
        engine_alive(hooks.engine)
        try:
            await asyncio.wait_for(asyncio.shield(proc.wait()), 1.0)
        except asyncio.TimeoutError:
            pass
    client = await finish_loadgen(proc, out)
    if tracer is not None:
        await tracer
    return reqs, t_open, client


async def serve(args, cell, cfg, mix, device, meter, params, family, mc, parts):
    from dynamo_tpu import run as dynamo_run
    from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.llm.entrypoint import build_local_pipeline

    from benchmark import spans, tokenizer, traffic

    t = time.monotonic()
    eng = cfg["engine"]
    engine = TpuEngine.build(
        EngineArgs(
            model=cell["config"], model_config=mc, dtype=eng.get("dtype", "bfloat16"), seed=args.seed & 0x7FFFFFFF,
            scheduler=SchedulerConfig(**{k: v for k, v in cfg["scheduler"].items() if not k.endswith("_why")}),  # *_why keys are prose
            continuous_profiling=bool(eng.get("continuous_profiling", False)),
            warmup_ctx=int(eng.get("warmup_ctx", 0)),
        ),
        params=params,
    )
    tok = tokenizer.load(mc.vocab_size, os.path.join(STATE, "tokenizers", cell["config"] + (".rehearsal" if args.rehearse else "")))
    hooks = spans.Hooks(engine, annotate=False)
    pipeline = build_local_pipeline(tok, engine)
    service = await dynamo_run.serve_http(engine, tok, pipeline, cell["config"], host="127.0.0.1", port=0)
    url = f"http://127.0.0.1:{service.port}/v1/chat/completions"
    sched = engine.scheduler
    parts["engine_build_s"] = time.monotonic() - t
    emit({"phase": "engine", "model": mc.name, "layers": mc.num_layers, "hidden": mc.hidden_size,
          "vocab": mc.vocab_size, "weight_dtype": mc.weight_dtype, "attention_impl": sched._attn_impl,
          "prefill_impl": "flash" if sched._use_flash_prefill else "xla", "kv_blocks": sched.sc.num_blocks,
          "param_bytes": sched._param_bytes, "kv_cache_bytes": sched._kv_cache_bytes,
          "tokenizer": type(tok).__name__, "continuous_profiling": engine.continuous_profiler is not None,
          "seconds": parts["engine_build_s"]})
    try:
        t = time.monotonic()
        w = await warmup(url, cell["config"], mix, mc.vocab_size, meter, engine)
        parts["warmup_s"] = time.monotonic() - t
        emit({"phase": "warmup", **w, **meter.snapshot()})
        if args.sweep:
            await sweep(args, url, cell, mix, mc, hooks, meter)
            return None
        trace_dir = os.path.join(STATE, "trace") if args.trace else None
        reqs, t_open, client = await measured_pass(
            url, cell["config"], mix, mc.vocab_size, args.seed, args.seconds, hooks, trace_dir)
        run = Run(client=client, window=(t_open, t_open + args.seconds), setup_s=t_open - T_START,
                  hooks=hooks, meter=meter, cfg=cfg, mix=mix, family=family, weight_dtype=mc.weight_dtype, device=device,
                  prompt_keys={r.rid: spans.prompt_key([0] + r.word_ids) for r in reqs},
                  trace_steps=[], trace_busy=None, trace_rows=None, offered=traffic.offered(reqs))
        return run
    finally:
        await service.stop()
        await engine.stop()


async def sweep(args, url, cell, mix, mc, hooks, meter) -> None:
    """Find the knee once: one process, one pass per offered rate."""
    from benchmark.readers import quantile, request_ok

    for i, rate in enumerate(float(x) for x in args.sweep.split(",")):
        scale = rate / float(mix["rate_rps"])
        e0 = meter.executables
        reqs, t_open, client = await measured_pass(
            url, cell["config"], mix, mc.vocab_size, args.seed + i, args.seconds, hooks, None, rate_scale=scale,
            tag=f"sweep{i}")
        t0, t1 = t_open, t_open + args.seconds
        rs = client["requests"]
        counted = [r for r in rs if r["counted"]]
        ok = [r for r in counted if request_ok(r)]

        def inflight(t):
            return sum(1 for r in rs if r["sent"] is not None and r["sent"] <= t
                       and (not r["frames"] or r["frames"][-1][0] > t or not r["done"]))

        done_in = sum(1 for r in rs if request_ok(r) and t0 <= r["frames"][-1][0] <= t1)
        toks = sum(f[1] for r in rs for f in r["frames"] if t0 <= f[0] <= t1)
        ttft = [r["frames"][0][0] - r["due"] for r in ok]
        tpot = [(r["frames"][-1][0] - r["frames"][0][0]) / (sum(f[1] for f in r["frames"]) - 1) for r in ok
                if sum(f[1] for f in r["frames"]) > 1]
        emit({"phase": "sweep", "offered_rps": rate, "seconds": args.seconds, "requests": len(counted),
              "failed": len(counted) - len(ok), "completed_rps": done_in / args.seconds,
              "out_tok_s": toks / args.seconds,
              "inflight_at": {"open": inflight(t0), "mid": inflight((t0 + t1) / 2), "close": inflight(t1)},
              "ttft_p50_ms": 1e3 * (quantile(ttft, 0.5) or 0), "ttft_p90_ms": 1e3 * (quantile(ttft, 0.9) or 0),
              "tpot_p50_ms": 1e3 * (quantile(tpot, 0.5) or 0), "executables_built": meter.executables - e0,
              "drain_s": client["finished"] - t1})


STUDY_CONTROL_SEEDS = 3  # of a study's seeds, how many also run the controls


def emit_check(check: dict) -> None:
    """Each number compared, beside its limit."""
    what = "logits of prefill, mixed steps and decode windows vs float32 reference"
    emit({"phase": "correct", "compared": what + ", all positions", "number": "rel_err", "value": check["rel_err"],
          "limit": check["limit_rel_err"], "positions": check["positions"]})
    emit({"phase": "correct", "compared": what + ", the worst group of positions", "number": "group_rel_err",
          "value": check["group_rel_err"], "limit": check["limit_group_rel_err"], "worst_group": check["worst_group"],
          "groups": check["groups"]})
    emit({"phase": "correct", "compared": "ids a decode window sampled vs the argmax of its own logits",
          "number": "mismatched_windows", "value": 0 if check["sampled_is_argmax"] else 1, "limit": 0})


def finish(result: dict, compared: dict) -> None:
    """The result line, what was compared last on it, and the same as the last lines of standard error."""
    emit(dict(result, compared={k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}))
    for k, (v, lim) in compared.items():
        print(f"compared {k} {v!r} limit {lim!r}", file=sys.stderr)


def parity_study(args, cell, cfg, family, mc) -> int:
    """The study behind the output check's limits: ``--parity-study N`` seeds
    of the program against the reference and, on the first few of them, the
    configuration's controls and the faulty program. One process, no engine."""
    from benchmark import parity

    spec = cfg["parity"]
    rows = []
    for i in range(args.parity_study):
        seed = args.seed + i * 7919
        t = time.monotonic()
        params = family.make_params(mc, seed)
        ctl = i < STUDY_CONTROL_SEEDS
        r = parity.check(family, params, mc, seed, spec, controls=spec["controls"] if ctl else (), fault=ctl,
                         per_position=True)
        del params
        row = {"phase": "parity_study", "seed": seed, **{k: r.get(k) for k in (
            "rel_err", "group_rel_err", "worst_group", "groups", "sampled_is_argmax", "controls", "fault_control",
            "per_position")}, "seconds": time.monotonic() - t}
        rows.append(row)
        emit(row)
    with_ctl = [r for r in rows if r["controls"]]
    emit({"phase": "parity_study", "workload": cell["name"], "seeds": len(rows), "quantile": spec.get("quantile", 0.5),
          "sound_rel_err": [min(r["rel_err"] for r in rows), max(r["rel_err"] for r in rows)],
          "sound_group_rel_err": [min(r["group_rel_err"] for r in rows), max(r["group_rel_err"] for r in rows)],
          "control_seeds": len(with_ctl),
          "control_smallest_rel_err": {n: min(r["controls"][n]["rel_err"] for r in with_ctl) for n in spec["controls"]},
          "control_smallest_group": {n: min(r["controls"][n]["smallest_group"] for r in with_ctl) for n in spec["controls"]},
          "fault_control_smallest_group_rel_err": min(r["fault_control"]["group_rel_err"] for r in with_ctl),
          "all_sampled_is_argmax": all(r["sampled_is_argmax"] for r in rows), "peak_bytes": peak_bytes()})
    return 0


def reduce_trace(run, trace_dir: str) -> None:
    from benchmark import trace as tr

    path = tr.find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    rows = tr.events_from_xplane(path)
    run.trace_rows = rows
    run.trace_busy = tr.busy(rows)
    run.trace_steps = tr.steps(rows)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0], allow_abbrev=False)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="control-flow rehearsal at the configuration's tiny sizes on any backend; prints counts only")
    p.add_argument("--sweep", default="", help="comma-separated offered rates: find the knee (one process)")
    p.add_argument("--parity-study", type=int, default=0, help="N seeds of program vs reference, no engine")
    args = p.parse_args()

    manifest, cell, cfg, mix = resolve_cell(args.workload, args.rehearse)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    device = open_device(int(cell["chips"]), args.rehearse)

    from benchmark import families, parity, readers, spans

    meter = spans.CompileMeter()
    family = families.load(cfg["family"])
    mc = family.model_config(cfg, cell["config"])
    if args.parity_study:
        return parity_study(args, cell, cfg, family, mc)

    parts = {}
    t = time.monotonic()
    params = family.make_params(mc, args.seed)
    import jax

    jax.block_until_ready(params)
    parts["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    check = parity.check(family, params, mc, args.seed, cfg["parity"])
    parts["parity_s"] = time.monotonic() - t
    emit_check(check)

    run = asyncio.run(serve(args, cell, cfg, mix, device, meter, params, family, mc, parts))
    if run is None:
        return 0
    run.cell = cell["name"]
    counted = [r for r in run.client["requests"] if r["counted"]]
    failed = [r for r in counted if not readers.request_ok(r)]
    for r in failed[:5]:
        emit({"phase": "failed_request", "rid": r["rid"], "error": r["error"], "finish_reason": r["finish_reason"],
              "usage": r["usage"], "tokens_received": sum(f[1] for f in r["frames"]), "max_tokens": r["max_tokens"]})
    emit({"phase": "correct", "compared": "requests due in the window: SSE framing, finish_reason, token counts",
          "number": "failed", "value": len(failed), "limit": 0})
    # Each number compared beside its limit: last on the result line, and as the last lines of standard error.
    compared = {"rel_err": [check["rel_err"], check["limit_rel_err"]],
                "group_rel_err": [check["group_rel_err"], check["limit_group_rel_err"]],
                "mismatched_windows": [0 if check["sampled_is_argmax"] else 1, 0], "failed": [len(failed), 0]}
    if args.trace:
        t = time.monotonic()
        reduce_trace(run, os.path.join(STATE, "trace"))
        parts["trace_reduce_s"] = time.monotonic() - t

    kind = "per_layer" if args.trace else "end_to_end"
    reports = lambda m: cell["name"] in m.get("workloads", [cell["name"]])  # noqa: E731 - no key: every cell
    wanted = [m for m in manifest[kind] if reports(m)]
    metrics = {}
    for m in wanted:
        value = readers.read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # Beside the cell's own metrics: every other metric the manifest gives this cell that needs no trace, as
    # candidates (the driver ignores other keys; the spread study reads them). A metric file without a manifest
    # entry is a candidate of no cell.
    extra = {}
    if not args.trace:
        for m in sorted(manifest["end_to_end"] + manifest["per_layer"], key=lambda m: m["name"]):
            if reports(m) and m["source"] != "device_trace" and m["name"] not in metrics:
                value = readers.read_metric(m["name"], run)
                if value is not None:
                    extra[m["name"]] = value
    in_win = meter.in_window(*run.window)
    hooks_keys = run.hooks.engine.scheduler.flight.post_warmup_keys
    emit({"phase": "setup", "setup_s": run.setup_s, **parts, "ramp_s": mix.get("ramp_s", 0.0), **meter.snapshot(),
          "in_window": in_win, "offered": run.offered,
          "post_warmup_shape_keys": [list(map(str, k)) for k in hooks_keys][-12:]})
    dev = dict(device, memory_peak_bytes=peak_bytes())
    result = {"correct": bool(check["ok"] and not failed), "attempted": len(counted), "failed": len(failed)}
    if args.rehearse:
        # A rehearsal's times are the CPU's: counts only, never under a metric's name.
        result.update(metrics={}, device=dev, rehearsal=True, metric_names=sorted(metrics),
                      counts={"compiles_in_window": in_win["builds"], "trace_steps": len(run.trace_steps),
                              "tokens_received": sum(f[1] for r in run.client["requests"] for f in r["frames"])})
        finish(result, compared)
        return 0
    if args.trace:
        from benchmark import trace as tr

        dev.update(busy_s=run.trace_busy["busy_s"], window_s=run.trace_busy["window_s"])
        if not dev["busy_s"]:
            raise RuntimeError("the traced slice holds no device operation")
        result["breakdown"] = {"device_ops": tr.top_ops(run.trace_rows), "idle_gaps": tr.idle_gaps(run.trace_rows)}
    result.update(metrics=metrics, device=dev)
    if extra:
        result["candidates"] = extra
    finish(result, compared)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 - any failure: no result line, non-zero exit
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # no thread of the program may keep the process (and the chip) alive
