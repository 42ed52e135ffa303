"""The ``evabyte`` family's counts on a grid (attended rows, with and without
a roll), its configuration against what the program is told, and a CPU
rehearsal of its cell end to end at the tiny sizes, with the tracer on.

Two rehearsals in one checkout share ``.bench_state/``: this one takes the
lock file of ``conftest.py``'s fixture (``benchmark_rehearsal.lock`` in the
directory all workers share), runs once a session and keeps its result beside
it."""

import fcntl
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families, readers, roofline  # noqa: E402

EVA = families.load("evabyte")
CELL = "evabyte-d16.doc-bytes"
with open(os.path.join(ROOT, "benchmark", "configs", "evabyte-d16.json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

LAYER = 4 * 4096 * 4096 + 3 * 4096 * 11008  # matmul parameters of one layer
ROW = 2 * 32 * 128 * 2  # K and V of one cache row in one layer, bf16


@pytest.mark.parametrize("rows,attended,rolls", [(1, 1, 0), (10, 17000, 0), (16, 16 * 2432, 0), (10, 17000, 1), (4, 600, 2)])
def test_decode_step_cost_on_a_grid(rows, attended, rolls):
    """By hand: 16 layers of bf16 weights and two norms, the final norm, the
    next-byte head (320 of the 2560 columns), the attended rows read and one
    row a sequence written, in every layer; a roll reads a window's 2048 rows
    and writes 128, in every layer."""
    weights = 16 * (LAYER * 2 + 2 * 4096 * 2) + 4096 * 320 * 2 + 4096 * 2
    kv = 16 * ROW * (attended + rows)
    io = rows * (4096 * 2 + 320 * 4)
    roll_bytes = 16 * ROW * (2048 + 128)
    c = EVA.decode_step_cost(CFG, "auto", rows, attended, rolls=rolls)
    assert c["weight_bytes"] == weights and c["kv_bytes"] == kv
    assert c["bytes"] == weights + kv + io + rolls * roll_bytes
    flops = rows * (16 * 2 * LAYER + 2 * 4096 * 320) + 16 * 4 * 32 * 128 * attended
    assert c["flops"] == flops + rolls * 16 * 2048 * (2 * 32 * 128) * 4
    assert EVA.decode_step_cost(CFG, "auto", rows, attended) == EVA.decode_step_cost(CFG, "auto", rows, attended, rolls=0.0)
    least = roofline.min_seconds(c, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(c["bytes"] / 819e9)


def test_counts_by_hand_at_the_published_widths():
    assert EVA.layer_params(CFG) == LAYER == 202_375_168 and EVA.kv_row_bytes(CFG) == ROW == 16384
    roll = EVA.roll_cost(CFG)
    assert roll["bytes"] == 16 * 16384 * 2176 == 570_425_344  # 0.57 GB: 0.70 ms at the chip's 819 GB/s
    assert 0.69e-3 < roofline.min_seconds(roll, "TPU v5 lite")["seconds"] < 0.70e-3
    assert 6.4e9 < EVA.decode_step_cost(CFG, "auto", 1, 0)["weight_bytes"] < 6.5e9
    with pytest.raises(ValueError):
        EVA.decode_step_cost(CFG, "int8", 1, 1)


def test_configuration_is_the_published_one_cut_in_depth_and_context_only():
    published = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 32, "intermediate_size": 11008,
                 "vocab_size": 320, "window_size": 2048, "chunk_size": 16, "rope_theta": 100000, "num_pred_heads": 8,
                 "rms_norm_eps": 1e-05, "attention_class": "eva", "norm_add_unit_offset": True, "fp32_skip_add": True}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "max_position_embeddings"] and CFG["deployment"]
    mc = EVA.model_config(CFG, "evabyte-d16")
    assert (mc.num_layers, mc.max_seq_len, mc.head_dim, mc.block_size) == (16, 10240, 128, 128)
    assert mc.is_eva and mc.summaries_per_window == mc.block_size and mc.norm_unit_offset and mc.residual_fp32
    assert mc.dtype == "bfloat16" and mc.weight_dtype == "auto" and mc.kv_cache_dtype == "auto"
    longest = max(CFG["parity"]["prompt_lens"]) + 64
    assert longest < mc.max_seq_len and CFG["scheduler"]["enable_prefix_caching"] is False
    with pytest.raises(ValueError):
        EVA.model_config(dict(CFG, attention_class="softmax"), "x")


def test_the_cell_reports_the_new_metrics_and_each_has_a_reader():
    mine = [m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])]
    new = ["step_roofline_pct", "attention_share_pct", "roll_program_ms", "roll_roofline_pct", "rolls_in_window",
           "sched_roll_host_ms", "attended_rows_per_ctx_byte"]
    assert {n + ".doc-bytes" for n in new} <= set(mine) and "compile_s" in mine and len(mine) == 26
    for n in new:  # a reader of its own beside its file, and nothing to read on an empty run gives None, not an error
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", n + ".doc-bytes.py"))
        empty = type("Run", (), {"trace_rows": None, "trace_busy": None, "hooks": None, "family": object(),
                                 "window": (0.0, 1.0), "cfg": CFG, "device": {"kind": "TPU v5 lite"}})()
        assert readers.read_metric(n + ".doc-bytes", empty) is None


@pytest.fixture(scope="module")
def rehearsed_eva(tmp_path_factory):
    shared = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        shared = shared.parent  # a worker's base is <session>/popen-gwN
    kept = shared / "benchmark_rehearsal_evabyte.json"
    with open(shared / "benchmark_rehearsal.lock", "w") as lock:  # the lock of conftest.py's rehearsal
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not kept.exists():
            env = dict(os.environ, JAX_PLATFORMS="cpu", DYN_LOG="ERROR", BENCH_RUN="7")
            env.pop("XLA_FLAGS", None)
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
                 "--seed", str(2**31 + 28), "--seconds", "4", "--trace", "1", "--rehearse"],
                cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
            kept.write_text(json.dumps({"returncode": p.returncode, "stdout": p.stdout, "stderr": p.stderr}))
        return json.loads(kept.read_text())


def test_rehearsal_serves_the_cell_over_http_across_rolls(rehearsed_eva):
    assert rehearsed_eva["returncode"] == 0, rehearsed_eva["stderr"][-3000:]
    lines = [json.loads(line) for line in rehearsed_eva["stdout"].splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] == 16
    assert last["metrics"] == {} and last["rehearsal"] is True
    # (the rehearsal runs without the program's warm-up, so what its 4 s window builds is the host's timing: counted, not held to 0)
    assert last["counts"]["compiles_in_window"] >= 0
    assert {"rolls_in_window.doc-bytes", "sched_roll_host_ms.doc-bytes", "attended_rows_per_ctx_byte.doc-bytes",
            "sched_host_ms.eva.doc-bytes", "queue_wait_p50_ms.doc-bytes", "frontend_ttft_gap_ms.doc-bytes", "compile_s"} <= set(
        last["metric_names"])
    engine = next(l for l in lines if l.get("phase") == "engine")
    assert engine["model"] == "evabyte-d16" and engine["vocab"] == 320 and engine["layers"] == 2
    groups = next(l for l in lines if l.get("number") == "group_rel_err")["groups"]
    assert {"chunk_summaries", "decode_rolled", "mixed_decode", "window_s3"} <= set(groups)
    setup = next(l for l in lines if l.get("phase") == "setup")
    assert ["eva_roll"] in setup["post_warmup_shape_keys"] or setup["executables"] > 0
