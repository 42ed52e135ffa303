"""The float32 references against the program at the tiny presets, and the
control that must come out as not correct."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import parity, weights  # noqa: E402
from dynamo_tpu.engine.config import get_config  # noqa: E402

# At the tiny presets on the CPU the program reads 0.010-0.025 (every group), the precision controls'
# smallest group 0.06 or more and the faulty program's worst group 0.5 or more (this file's own runs);
# the limits sit between, as on the chip.
SPEC = {"prompt_lens": [24, 44, 50, 40], "chunk": 32, "window": 4, "windows": 2, "decode_bucket": 4,
        "limit_rel_err": 0.035, "limit_group_rel_err": 0.04}


@pytest.mark.parametrize("preset,weight_dtype,lower", [
    ("tiny", "int8", "int4"),      # mistral-7b-w8's weights and their control
    ("tiny", "int8", "fp8_act"),   # mistral-7b-w8's bfloat16 activations and their control
    ("tiny-moe", "auto", "fp8"),   # mixtral-8x7b-d3's precision and its control
    ("tiny", "auto", "fp8"),
])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_served_programs_agree_with_reference_and_controls_fail(preset, weight_dtype, lower, seed):
    mc = get_config(preset).replace(weight_dtype=weight_dtype)
    params = weights.make_params(mc, seed)
    r = parity.check(params, mc, seed, SPEC, controls=(lower,), fault=True)
    assert r["ok"] and r["sampled_is_argmax"], r
    # every program path and every sequence's window rows were compared
    assert set(r["groups"]) == {"prefill", "chunk_fresh", "chunk_prefix", "mixed_decode",
                                "window_s0", "window_s1", "window_s2", "window_s3"}
    assert r["positions"] == 24 + 3 + 3 + (2 * 1 + 2 * 2 + 2 * 3) + 4 * 8
    ctl = r["controls"][lower]
    assert ctl["fails"], r
    assert ctl["rel_err"] > 3 * r["rel_err"], (r["rel_err"], ctl)
    # a chunk that reads another sequence's blocks as its prefix: one path's fault, caught by its group
    bad = r["fault_control"]
    assert bad["fails"] and bad["group_rel_err"] > 3 * r["group_rel_err"], (r["group_rel_err"], bad)
    assert bad["worst_group"] != "prefill" and bad["groups"]["prefill"] <= SPEC["limit_group_rel_err"]


def test_weights_are_the_seed_and_nothing_else():
    import jax
    import numpy as np

    mc = get_config("tiny").replace(weight_dtype="int8")
    a, b, c = (weights.make_params(mc, s) for s in (5, 5, 6))
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert a["layers"]["wq"].q.dtype == np.int8 and a["layers"]["wq"].scale.dtype == np.float32
    assert a["embed"].dtype == jax.numpy.bfloat16
