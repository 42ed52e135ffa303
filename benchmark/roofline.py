"""The table of peaks, and the least time a count of FLOPs and bytes could take.

What a decode step needs is counted by the configuration's family
(``benchmark/families/<f>.py::decode_step_cost``): the work the algorithm
needs, not what a program happens to move. An unknown device is an error,
never a default.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} (have {sorted(table)})")
    return table[device_kind]


def _bytes_of(dtype: str) -> float:
    return {"bfloat16": 2.0, "float16": 2.0, "float32": 4.0, "int8": 1.0}[dtype]


def min_seconds(cost: dict, device_kind: str) -> dict:
    """The least time the chip could take for ``cost`` and which peak bounds it."""
    p = peaks(device_kind)
    t_c, t_m = cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c > t_m else "memory"}
