#!/usr/bin/env python3
"""Where CPython's data-stack chunks end, and what a call across such an end costs
(PERF.md section 6, PR 39: why a warm set-up's lowering seconds move with the
Python frames under the jitted call).

    python3 tools/stack_chunk_probe.py [--depths 400] [--calls 300000]

CPython 3.11+ keeps a thread's Python frames in chunks of 16 KiB. A call whose
frame does not fit the current chunk maps a new one, and the return that empties
it gives it back: a loop whose callee's frame is the FIRST of a chunk pays an
``mmap`` and a ``munmap`` every iteration. The probe runs one small loop under
0..N extra frames and prints the depths at which it is several times slower
than at its best, and by how much. Needs no JAX and no device: a host's
property, read on the host it runs on.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


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


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--depths", type=int, default=400)
    p.add_argument("--calls", type=int, default=300_000)
    args = p.parse_args()
    sys.setrecursionlimit(max(sys.getrecursionlimit(), args.depths + 100))
    seconds = [under(k, args.calls) for k in range(args.depths)]
    best = min(seconds)
    slow = [(k, round(s / best, 1)) for k, s in enumerate(seconds) if s > 3 * best]
    print(json.dumps({"python": sys.version.split()[0], "calls": args.calls, "best_s": round(best, 4),
                      "us_a_call_at_best": round(1e6 * best / args.calls, 3),
                      "depths_over_3x": slow, "us_a_call_there": [round(1e6 * seconds[k] / args.calls, 2) for k, _ in slow]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
