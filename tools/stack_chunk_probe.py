#!/usr/bin/env python3
"""Where CPython's data-stack chunks end, and what a call across such an end costs
(PERF.md section 6, PR 39: why a warm set-up's lowering seconds move with the
Python frames under the jitted call).

    python3 tools/stack_chunk_probe.py [--depths 400] [--calls 300000] [--anchored] [--enter 0]

CPython 3.11+ keeps a thread's Python frames in chunks of 16 KiB. A call whose
frame does not fit the current chunk maps a new one, and the return that empties
it gives it back: a loop whose callee's frame is the FIRST of a chunk pays an
``mmap`` and a ``munmap`` every iteration. The probe runs one small loop under
0..N extra frames and prints the depths at which it is several times slower
than at its best, and by how much. Needs no JAX and no device: a host's
property, read on the host it runs on.

``--anchored`` makes the same sweep a second time below
``dynamo_tpu.engine.compile_cache.in_one_chunk`` (the one large frame
``TpuEngine.build`` runs under: PERF.md section 6, PR 42), entered ``--enter``
frames deep, and prints it beside the plain one: below that frame no depth
should be slow until its chunk's free room is full.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def leaf(a, b, c, d):
    return a


def hot(calls: int) -> float:
    t = time.perf_counter()
    s = 0
    for i in range(calls):
        s += leaf(i, 1, 2, 3)
    return time.perf_counter() - t


def under(frames: int, calls: int) -> float:
    return hot(calls) if frames == 0 else under(frames - 1, calls)


def sweep(depths: int, calls: int) -> list:
    return [under(k, calls) for k in range(depths)]


def anchored(enter: int, depths: int, calls: int) -> list:
    """The sweep below ``in_one_chunk``'s frame, which is entered ``enter`` frames down."""
    from dynamo_tpu.engine.compile_cache import in_one_chunk

    return in_one_chunk(sweep, depths, calls) if enter == 0 else anchored(enter - 1, depths, calls)


def digest(seconds: list, calls: int) -> dict:
    best = min(seconds)
    slow = [(k, round(s / best, 1)) for k, s in enumerate(seconds) if s > 3 * best]
    return {"best_s": round(best, 4), "us_a_call_at_best": round(1e6 * best / calls, 3), "worst_over_best": round(max(seconds) / best, 1),
            "depths_over_3x": slow, "us_a_call_there": [round(1e6 * seconds[k] / calls, 2) for k, _ in slow]}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--depths", type=int, default=400)
    p.add_argument("--calls", type=int, default=300_000)
    p.add_argument("--anchored", action="store_true", help="the sweep again below compile_cache.in_one_chunk")
    p.add_argument("--enter", type=int, default=0, help="frames above in_one_chunk's own in the anchored sweep")
    args = p.parse_args()
    sys.setrecursionlimit(max(sys.getrecursionlimit(), args.depths + args.enter + 100))
    out = {"python": sys.version.split()[0], "calls": args.calls, **digest(sweep(args.depths, args.calls), args.calls)}
    if args.anchored:
        out["anchored"] = {"enter": args.enter, **digest(anchored(args.enter, args.depths, args.calls), args.calls)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
