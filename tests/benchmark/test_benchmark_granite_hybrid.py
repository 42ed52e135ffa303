"""The ``granite_hybrid`` family's counts on hand-made sizes, its configuration
against the published one and against what the program is told, the cell's
manifest entries, and a CPU rehearsal of its cell end to end at the tiny sizes,
with the tracer on.

Rehearsals in one checkout share ``.bench_state/``: this one takes the lock
file of ``conftest.py``'s fixture (``benchmark_rehearsal.lock`` in the
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

FAM = families.load("granite_hybrid")
CONFIG = "granite-4.0-h-small-d10-e36"
CELL = CONFIG + ".chat-many"
with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "traffic", "chat-many.json")) as _f:
    MIX = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# Parameters by hand (ISSUE 32's sizing), at the published widths.
MAMBA = 4096 * 16768 + 8192 * 4096  # in_proj [4096, 8192 + 8448 + 128], out_proj
MAMBA_SMALL = 4 * 8448 + 8448 + 3 * 128 + 8192 + 4096  # conv taps and bias, dt_bias / A_log / D, the two norms
ATTN = 2 * 4096 * 4096 + 2 * 4096 * 1024
EXPERT, SHARED, ROUTER = 3 * 4096 * 768, 3 * 4096 * 1536, 4096 * 72
STATE_ROW = 128 * 64 * 128 * 4 + 3 * 8448 * 2  # one sequence, one state-space layer: float32 state, bf16 columns


def test_counts_by_hand_at_the_published_widths():
    assert MAMBA == 102_236_160 and MAMBA + MAMBA_SMALL == 102_286_976 + 4096  # (ISSUE 32 counts the mixer without its pre-norm)
    assert ATTN == 41_943_040 and EXPERT == 9_437_184 and SHARED == 18_874_368 and ROUTER == 294_912
    assert FAM.state_row_bytes(CFG) == STATE_ROW == 4_194_304 + 50_688
    s = FAM._sizes(CFG)
    assert (s["L"], s["La"], s["Lm"], s["di"], s["cd"]) == (10, 1, 9, 8192, 8448)
    assert s["mamba_params"] == MAMBA and s["mamba_small"] == MAMBA_SMALL and s["expert_params"] == EXPERT
    slots = 65 * 9 * STATE_ROW
    assert 2.48e9 < slots < 2.49e9  # the slot arrays of the configuration's 65 slots
    assert FAM.experts_reached(CFG, 0) == 0 and 35.9 < FAM.experts_reached(CFG, 64) <= 36
    assert 0.89 * 36 < FAM.experts_reached(CFG, 16) < 0.92 * 36  # "at 16 rows and more, >= 89% of the held experts"
    with pytest.raises(ValueError):
        FAM.decode_step_cost(CFG, "int8", 1, 1)


@pytest.mark.parametrize("rows,ctx,visited", [(1, 1, 5), (32, 9000, 330), (64, 40000, 360), (16, 3000, None)])
def test_decode_step_cost_on_hand_made_sizes(rows, ctx, visited):
    """By hand: bf16 weights of nine Mamba mixers and one attention mixer, ten
    shared experts, routers and norms, the experts VISITED (not all held), the
    tied head and the final norm; each row's state and columns read and
    written in nine layers; the one attention layer's attended rows read and
    one row a sequence written; embedding rows in, float32 logits out."""
    c = FAM.decode_step_cost(CFG, "auto", rows, ctx, experts_visited=visited)
    n_visited = visited if visited is not None else 10 * FAM.experts_reached(CFG, rows)
    mixers = (9 * (MAMBA + MAMBA_SMALL) + ATTN + 4096) * 2
    ffn = 10 * (SHARED + ROUTER + 4096) * 2
    head = (4096 * 100352 + 4096) * 2
    experts = n_visited * EXPERT * 2
    state = 2 * rows * 9 * STATE_ROW
    kv = 2 * 1024 * 2 * (ctx + rows)
    io = rows * (4096 * 2 + 100352 * 4)
    assert c["expert_bytes"] == pytest.approx(experts) and c["state_bytes"] == state and c["kv_bytes"] == kv
    assert c["weight_bytes"] == pytest.approx(mixers + ffn + head + experts)
    assert c["bytes"] == pytest.approx(mixers + ffn + head + experts + state + kv + io)
    per_row = 2 * (9 * MAMBA + ATTN + 10 * (SHARED + ROUTER) + 10 * 10 * 0.5 * EXPERT + 4096 * 100352)
    assert c["flops"] == pytest.approx(rows * per_row + rows * 9 * 6 * 128 * 64 * 128 + 4 * 4096 * ctx)
    least = roofline.min_seconds(c, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(c["bytes"] / 819e9)
    if visited is not None:  # all held experts visited is the most a step can need
        assert c["bytes"] <= FAM.decode_step_cost(CFG, "auto", rows, ctx, experts_visited=360)["bytes"]


def test_a_step_at_32_rows_is_the_issues_12_gigabytes():
    c = FAM.decode_step_cost(CFG, "auto", 32, 32 * 400)
    assert 11.5e9 < c["bytes"] < 12.6e9 and 2.4e9 < c["state_bytes"] < 2.5e9 and 6.4e9 < c["expert_bytes"] < 6.8e9


@pytest.mark.parametrize("rows", [0, 1, 32, 64])
def test_ssm_update_cost_on_hand_made_sizes(rows):
    """One launch of the state kernel, one layer: each row's [128, 64, 128] float32 state read and written, x and y
    (8192 each), B and C (128 each) and the step (128), float32; six operations a state element."""
    c = FAM.ssm_update_cost(CFG, rows)
    state = 128 * 64 * 128
    assert c["bytes"] == rows * (2 * state + 2 * 8192 + 2 * 128 + 128) * 4 and c["flops"] == rows * 6 * state
    if rows:
        assert roofline.min_seconds(c, "TPU v5 lite")["bound"] == "memory"


def test_the_kernels_roofline_reader_on_hand_made_rows():
    """A window of 2 steps at 32 rows whose 18 launches of 100 us each lie in its span, a bare chunk's idle launches
    (time, no bytes), and a span the slice cut (one launch of its nine)."""
    dev, ops = "/device:TPU:0", "XLA Ops"
    kernel = "%ssm_update_rows.7 = (f32[585,64,128,128]{3,2,1,0}, f32[32,64,128]{2,1,0}) custom-call(...)"
    rows = [[dev, ops, kernel, 1_000 + 1_000_000 * i, 100_000] for i in range(18)]
    rows += [[dev, ops, kernel, 30_000_000 + 1_000_000 * i, 10_000] for i in range(9)]
    rows += [[dev, ops, kernel, 50_000_000, 100_000], [dev, ops, "%fusion.3 = f32[8]{0} fusion(...)", 50_200_000, 5_000]]
    step = lambda t0, dur, **stats: ["/host:CPU", "t", "dyn:sched.step", t0, dur, stats]  # noqa: E731
    dyn = [step(0, 20_000_000, kind="decode_multi", key="(2, 32, 4)", rows=32, ssm_rows=32),
           step(30_000_000, 10_000_000, kind="prefill", key="(256,)", rows=0, ssm_rows=0),
           step(50_000_000, 1_000_000, kind="decode", key="(32, 4)", rows=32, ssm_rows=32),
           step(60_000_000, 1_000_000, kind="decode", key="(32, 4)", rows=32)]  # another program's entry: no ssm_rows
    run = type("Run", (), {"trace_rows": rows, "_dyn_rows": dyn, "family": FAM, "cfg": CFG, "device": {"kind": "TPU v5 lite"}})()
    one = FAM.ssm_update_cost(CFG, 32)["bytes"] / 819e9
    want = 100.0 * 19 * one / (18 * 100e-6 + 9 * 10e-6 + 100e-6)
    assert readers.read_metric("ssm_update_rows_roofline_pct.chat-many", run) == pytest.approx(want)
    run.family = object()  # a family without the cost function: nothing
    assert readers.read_metric("ssm_update_rows_roofline_pct.chat-many", run) is None


PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "mamba_proj_bias": False, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10, "num_key_value_heads": 8, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True, "vocab_size": 100352,
}


def test_configuration_is_the_published_one_cut_in_depth_experts_held_and_context_only():
    assert {k: CFG[k] for k in PUBLISHED} == PUBLISHED
    pattern = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert CFG["layer_types"] == pattern * 4  # kept whole, as published: the family reads the first ten
    assert CFG["reduced"] == ["num_hidden_layers", "num_local_experts", "max_position_embeddings"]
    assert (CFG["num_hidden_layers"], CFG["num_local_experts"], CFG["max_position_embeddings"]) == (10, 36, 2048)
    assert set(CFG["reduced_why"]) == set(CFG["reduced"]) and CFG["deployment"] and CFG["assumed"] and CFG["memory"]
    assert CFG["deployment_experts"] == {"routed": 72, "first_held": 0, "held_here": "num_local_experts"}
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] and entry["source"] == CFG["source"] and entry["file"].endswith(CONFIG + ".json")
    assert len(entry["why"]) <= 200
    mc = FAM.model_config(CFG, CONFIG)
    assert mc.is_hybrid and mc.layer_groups == (("mamba", 5), ("attention", 1), ("mamba", 4))
    assert (mc.num_experts, mc.num_experts_per_tok, mc.experts_held, mc.first_expert_held) == (72, 10, 36, 0)
    assert (mc.intermediate_size, mc.shared_intermediate_size, mc.head_dim, mc.block_size, mc.max_seq_len) == (768, 1536, 128, 128, 2048)
    assert (mc.mamba_d_inner, mc.mamba_conv_dim, mc.mamba_chunk_size) == (8192, 8448, 256)
    assert not mc.use_rope and mc.attention_scale == 1 / 128 and mc.tie_word_embeddings
    assert (mc.embedding_multiplier, mc.residual_multiplier, mc.logits_scaling) == (12.0, 0.22, 16.0)
    sc = CFG["scheduler"]
    assert sc["max_running"] == 64 and "num_state_slots" not in sc and sc["enable_prefix_caching"] is False
    assert sc["max_prefill_chunk"] == sc["mixed_prefill_budget"] == CFG["mamba_chunk_size"] and sc["num_scheduler_steps"] == 8
    assert len(sc["decode_buckets"]) == 2 and sc["decode_buckets"][-1] == sc["max_running"]
    assert max(CFG["parity"]["prompt_lens"]) + 9 + 16 < mc.max_seq_len
    assert all(CFG["parity"][k] == sc[k] for k in ("num_blocks", "max_running"))  # the check's cache is the engine's
    assert CFG["parity"]["decode_bucket"] in sc["decode_buckets"]
    assert set(CFG["parity"]["controls"]) == set(FAM.CONTROLS) >= {"stale_state", "no_conv_carry", "all_experts", "bf16_state", "fp8_act"}
    for bad in (dict(position_embedding_type="rope"), dict(model_type="granitemoe"), dict(mamba_proj_bias=True)):
        with pytest.raises(ValueError):
            FAM.model_config(dict(CFG, **bad), "x")


def test_the_traffic_is_the_chat_lengths_on_this_prs_draw():
    with open(os.path.join(ROOT, "benchmark", "traffic", "chat.json")) as f:
        chat = json.load(f)
    for key in ("dist", "median", "sigma", "min", "max"):
        assert MIX["prompt_tokens"][key] == chat["prompt_tokens"][key] and MIX["output_tokens"][key] == chat["output_tokens"][key]
    assert MIX["order"] == "rotate" and MIX["base_seed"] == 32 and MIX["arrival"] == {"dist": "gamma", "cv": 1.0}
    assert MIX["loop"] == "open" and MIX["ramp_s"] == 16.0 and MIX["trace"] == {"start_s": 5.0, "seconds": 4.0}
    assert MIX["rate_rps"] > 0 and "sweep" in MIX["rate_from"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "chat-many", "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200


NEW = ["step_roofline_pct", "ssm_update_rows_roofline_pct", "ssm_share_pct", "moe_share_pct", "expert_rows_mean",
       "experts_visited_pct", "ssm_slots_in_use_mean", "sched_slots_host_ms"]
NINE = ["idle_pre_launch_pct", "idle_post_sync_pct", "idle_loop_pct", "sched_host_ms", "staged_wait_p50_ms",
        "programs_per_dispatch", "decode_program_ms", "mixed_program_ms", "frontend_busy_pct"]


def test_the_cell_reports_the_new_metrics_and_each_has_a_reader():
    mine = {m["name"]: m for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])}
    assert {n + ".chat-many" for n in NEW} | {n + ".ssm.chat-many" for n in NINE} | {"compile_s"} <= set(mine)
    # Since PR 44 the cell's end-to-end metric is `out_tok_s`: its `tpot_p50_ms` moved with the seed's weights by more
    # than the largest bound allows (PERF.md sections 2 and 6) and is the per-layer `client_tpot_p50_ms.chat-many`.
    assert len(mine) == 28 and all(m["moves"] in ("out_tok_s", "setup_s") for m in mine.values())
    assert "client_tpot_p50_ms.chat-many" in mine
    assert CELL in next(m for m in MANIFEST["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    assert CELL not in next(m for m in MANIFEST["end_to_end"] if m["name"] == "tpot_p50_ms")["workloads"]
    for name, m in mine.items():
        if name != "compile_s":
            assert m["workloads"] == [CELL]
        spec = readers.load_metric(name)
        assert (spec["unit"], spec["better"], spec["source"], spec["layer"], spec["moves"]) == (
            m["unit"], m["better"], m["source"], m["layer"], m["moves"])
    for n in NEW:  # a reader of its own beside its file, and nothing to read on an empty run gives None, not an error
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", n + ".chat-many.py"))
        empty = type("Run", (), {"trace_rows": None, "trace_busy": None, "hooks": None, "family": object(),
                                 "window": (0.0, 1.0), "cfg": CFG, "device": {"kind": "TPU v5 lite"}})()
        assert readers.read_metric(n + ".chat-many", empty) is None


def test_counter_readers_on_hand_made_step_entries():
    """A step log of three entries: a window of 8 steps at 20 rows, a mixed
    step, and an entry of another program's (no counts)."""
    spans = [("sched.step", 10, 20, 1, {"kind": "decode_multi", "key": "(8, 32, 4)", "rows": 20, "held_assignments": 800,
                                       "experts_visited": 2400, "ssm_slots": 21}),
             ("sched.step", 30, 40, 2, {"kind": "mixed", "key": "(256, 16, 32, 4)", "rows": 20, "held_assignments": 1400,
                                       "experts_visited": 350, "ssm_slots": 23}),
             ("sched.slots", 41, 45, 3, {"slot": 4}), ("sched.slots", 46, 52, 3, {"slot": 5}),
             ("sched.step", 60, 70, 3, {"kind": "decode_multi", "key": "(8, 32, 4)", "rows": 3}),
             ("sched.step", 2_000_000_000, 2_000_000_010, 4, {"kind": "decode", "rows": 1, "ssm_slots": 60})]  # after the window
    log = type("Log", (), {"spans": spans})()
    engine = type("E", (), {"scheduler": type("S", (), {"flight": type("F", (), {"log": log})()})()})()
    run = type("Run", (), {"hooks": type("H", (), {"engine": engine})(), "window": (0.0, 1.0), "cfg": CFG})()
    assert readers.read_metric("expert_rows_mean.chat-many", run) == pytest.approx(800 / 2400)
    assert readers.read_metric("experts_visited_pct.chat-many", run) == pytest.approx(100 * 2400 / (8 * 360))
    assert readers.read_metric("ssm_slots_in_use_mean.chat-many", run) == pytest.approx(22.0)
    assert readers.read_metric("sched_slots_host_ms.chat-many", run) == pytest.approx(5e-6)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    shared = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        shared = shared.parent  # a worker's base is <session>/popen-gwN
    kept = shared / "benchmark_rehearsal_granite_hybrid.json"
    with open(shared / "benchmark_rehearsal.lock", "w") as lock:  # the lock of conftest.py's rehearsal
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not kept.exists():
            env = dict(os.environ, JAX_PLATFORMS="cpu", DYN_LOG="ERROR", BENCH_RUN="7")
            env.pop("XLA_FLAGS", None)
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
                 "--seed", str(2**31 + 32), "--seconds", "4", "--trace", "1", "--rehearse"],
                cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
            kept.write_text(json.dumps({"returncode": p.returncode, "stdout": p.stdout, "stderr": p.stderr[-20000:]}))
        return json.loads(kept.read_text())


def test_rehearsal_serves_the_cell_over_http_on_slots(rehearsed):
    assert rehearsed["returncode"] == 0, rehearsed["stderr"][-3000:]
    lines = [json.loads(line) for line in rehearsed["stdout"].splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] == 16
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert {"expert_rows_mean.chat-many", "experts_visited_pct.chat-many", "ssm_slots_in_use_mean.chat-many",
            "sched_slots_host_ms.chat-many", "sched_host_ms.ssm.chat-many", "queue_wait_p50_ms.chat-many",
            "frontend_ttft_gap_ms.chat-many", "compile_s"} <= set(last["metric_names"])
    engine = next(l for l in lines if l.get("phase") == "engine")
    assert engine["model"] == CONFIG and engine["layers"] == 7 and engine["vocab"] == 512
    groups = next(l for l in lines if l.get("number") == "group_rel_err")["groups"]
    assert {"prefill", "chunk_first", "chunk_carried", "mixed_decode", "window_s4"} <= set(groups)
    setup = next(l for l in lines if l.get("phase") == "setup")
    assert ["open_slot"] in setup["post_warmup_shape_keys"] or setup["executables"] > 0
