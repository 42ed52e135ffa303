"""Profile the serving plane: where does the time per request go?

Runs the bench_http_e2e stack (tiny model, CPU ok) with instrumentation:
- scheduler.step() wall time, split prefill/decode, + counts
- engine loop iterations and to_thread overhead
- HTTP-level req/s + tok/s

Usage: python tools/profile_serving.py [n_requests] [concurrency]
"""

import asyncio
import cProfile
import io
import os
import pstats
import sys
import time

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def main():
    n_requests = int(sys.argv[1]) if len(sys.argv) > 1 else 48
    concurrency = int(sys.argv[2]) if len(sys.argv) > 2 else 12

    import dynamo_tpu.engine.scheduler as sched_mod

    stats = {"step_calls": 0, "step_s": 0.0, "prefill_calls": 0, "prefill_s": 0.0,
             "decode_calls": 0, "decode_s": 0.0, "sample_one_calls": 0, "sample_one_s": 0.0}

    orig_step = sched_mod.Scheduler.step
    orig_prefill = sched_mod.Scheduler._prefill_one
    orig_decode = sched_mod.Scheduler._decode_step
    orig_sample1 = sched_mod.Scheduler._sample_one

    def timed(name, orig):
        def wrap(self, *a, **kw):
            t0 = time.perf_counter()
            try:
                return orig(self, *a, **kw)
            finally:
                stats[f"{name}_calls"] += 1
                stats[f"{name}_s"] += time.perf_counter() - t0
        return wrap

    sched_mod.Scheduler.step = timed("step", orig_step)
    sched_mod.Scheduler._prefill_one = timed("prefill", orig_prefill)
    sched_mod.Scheduler._decode_step = timed("decode", orig_decode)
    sched_mod.Scheduler._sample_one = timed("sample_one", orig_sample1)

    t0 = time.perf_counter()
    res = bench.bench_http_e2e(n_requests=n_requests, concurrency=concurrency)
    wall = time.perf_counter() - t0
    print("http_e2e:", res)
    print(f"wall {wall:.1f}s")
    for k in ("step", "prefill", "decode", "sample_one"):
        calls, secs = stats[f"{k}_calls"], stats[f"{k}_s"]
        if calls:
            print(f"{k:12s}: {calls:5d} calls, {secs:7.2f}s total, {secs/calls*1e3:7.2f} ms/call")
    other = stats["step_s"] - stats["prefill_s"] - stats["decode_s"]
    print(f"{'step other':12s}: {other:7.2f}s (reap/admit bookkeeping)")
    print(f"{'outside step':12s}: {wall - stats['step_s']:7.2f}s (HTTP, detok, asyncio, idle)")


if __name__ == "__main__":
    main()
