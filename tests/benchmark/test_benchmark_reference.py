"""The float32 references against the program at the tiny presets, the
control that must come out as not correct, and the parent's numbers, which
moving the code into `benchmark/families/` had to keep."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families, parity  # noqa: E402
from dynamo_tpu.engine.config import get_config  # noqa: E402

LLAMA = families.load("llama")
# Numbers of the parent commit (428f41a, PR 26), taken by running its own `run.model_config`, `weights.make_params`
# and `parity.check` on the CPU before anything moved: see the file's "from".
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "frozen_parent.json")) as _f:
    FROZEN = json.load(_f)

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
    params = LLAMA.make_params(mc, seed)
    r = parity.check(LLAMA, params, mc, seed, SPEC, controls=(lower,), fault=True)
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
    a, b, c = (LLAMA.make_params(mc, s) for s in (5, 5, 6))
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert a["layers"]["wq"].q.dtype == np.int8 and a["layers"]["wq"].scale.dtype == np.float32
    assert a["embed"].dtype == jax.numpy.bfloat16


def rehearsal_config(name):
    from benchmark.run import load_json, overlay

    cfg = load_json(ROOT, "benchmark", "configs", f"{name}.json")
    cfg = overlay(cfg, cfg["rehearsal"])
    family = families.load(cfg["family"])
    return cfg, family, family.model_config(cfg, name)


@pytest.mark.parametrize("seed", FROZEN["seeds"])
@pytest.mark.parametrize("name", ["mistral-7b-w8", "mixtral-8x7b-d3"])
def test_the_family_draws_the_parents_weights(name, seed):
    """Same seed, same tree: the RNG streams and the order of `split`/`fold_in`
    did not change when `make_params` moved (every leaf's sum and sum of
    magnitudes, as the parent commit drew them)."""
    import jax
    import numpy as np

    _, family, mc = rehearsal_config(name)
    leaves = jax.tree_util.tree_flatten_with_path(family.make_params(mc, seed))[0]
    frozen = FROZEN["params"][f"{name}/{seed}"]
    assert [jax.tree_util.keystr(p) for p, _ in leaves] == list(frozen)
    for path, leaf in leaves:
        x = np.asarray(np.asarray(leaf).astype(np.float32), np.float64)
        total, mag = frozen[jax.tree_util.keystr(path)]
        assert x.sum() == pytest.approx(total, abs=1e-9 * mag) and np.abs(x).sum() == pytest.approx(mag, rel=1e-9), path


@pytest.mark.parametrize("seed", FROZEN["seeds"])
@pytest.mark.parametrize("name", ["mistral-7b-w8", "mixtral-8x7b-d3"])
def test_the_output_check_reads_what_the_parent_read(name, seed):
    """`rel_err` and `group_rel_err` of the parent commit to 1e-6: weights,
    step programs and reference arithmetic are the same code in another file."""
    cfg, family, mc = rehearsal_config(name)
    r = parity.check(family, family.make_params(mc, seed), mc, seed, cfg["parity"])
    frozen = FROZEN["parity"][f"{name}/{seed}"]
    assert r["rel_err"] == pytest.approx(frozen["rel_err"], abs=1e-6)
    assert r["group_rel_err"] == pytest.approx(frozen["group_rel_err"], abs=1e-6)
    assert (r["worst_group"], r["positions"], r["ok"], r["sampled_is_argmax"]) == (
        frozen["worst_group"], frozen["positions"], frozen["ok"], frozen["sampled_is_argmax"])
