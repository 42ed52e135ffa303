"""The ``zaya`` family's seam and counts on hand-made sizes, its configuration
against the catalog's and against what the program is told, the cell's
manifest entries and traffic, each new reader on hand-made rows and on an
empty run, and a CPU rehearsal of its cell end to end at the tiny sizes, with
the tracer on.

Rehearsals in one checkout share ``.bench_state/``: this one takes the lock
file of ``conftest.py``'s fixture (``benchmark_rehearsal.lock`` in the
directory all workers share), runs once a session and keeps its result beside
it."""

import fcntl
import inspect
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families, readers, roofline  # noqa: E402

FAM = families.load("zaya")
CONFIG = "zaya1-8b-d20"
CELL = CONFIG + ".reason"
with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "traffic", "reason.json")) as _f:
    MIX = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# Parameters by hand (ISSUE 41's sizing), at the published widths.
W_IN, W_O, CONVS = 2048 * 1536, 1024 * 2048, 2 * 1280 + 1280 + 10 * 256 * 128 + 1280
ATTN = W_IN + W_O + CONVS + 4 * 2048 + 2048  # with the sublayer's merge and norm
ROUTER_BF16 = 2048 * 256 + 4 * 2048 + 2048  # the down-projection, the expert sublayer's merge and norm
ROUTER_F32 = 3 * 256 + 2 * (256 * 256 + 256) + 256 * 17 + 17 + 2  # b_d, gamma, norm, the MLP, beta; the layer's two temperatures
EXPERT = 3 * 2048 * 2048
SLOT_ROW = (2 * 1280 + 128) * 2  # one sequence, one layer: bf16 columns


def test_the_family_exposes_the_seam_and_its_reference_takes_nothing_of_the_program():
    assert sorted(FAM.__all__) == sorted(families.SEAM)
    assert inspect.signature(FAM.decode_step_cost).parameters["experts_visited"].default is None
    assert inspect.signature(FAM.decode_step_cost).parameters["skipped_rows"].default is None
    src = inspect.getsource(sys.modules[FAM.reference_forward.__module__])
    assert "dynamo_tpu" not in src and 'default_matmul_precision("highest")' in src and src.count("# ASSUMED") >= 10
    assert set(FAM.CONTROLS) >= {"stale_slot", "no_conv_carry", "no_value_shift", "no_router_carry", "skip_computed",
                                 "bf16_router", "fp8_act"}


def test_counts_by_hand_at_the_published_widths():
    assert W_IN == 3_145_728 and W_O == 2_097_152 and CONVS == 332_800 and EXPERT * 16 == 201_326_592
    s = FAM._sizes(CFG)
    assert (s["L"], s["q"], s["kv"], s["C"], s["slot_lanes"]) == (20, 1024, 256, 1280, 2688)
    assert s["attn_params"] == ATTN and s["router_bf16"] == ROUTER_BF16 and s["router_f32"] == ROUTER_F32 and s["expert_params"] == EXPERT
    layer = ATTN + ROUTER_BF16 + ROUTER_F32 + 16 * EXPERT
    assert layer == 207_583_763 and 20 * layer + 262_272 * 2048 + 2048 == 4_688_810_364  # the configuration's `memory`
    assert FAM.slot_row_bytes(CFG) == SLOT_ROW == 5_376 and 65 * 20 * SLOT_ROW == 6_988_800
    assert FAM.experts_reached(CFG, 0) == 0 and 9.9 < FAM.experts_reached(CFG, 16) < 10.1 and 13.6 < FAM.experts_reached(CFG, 32) < 13.8
    with pytest.raises(ValueError):
        FAM.decode_step_cost(CFG, "int8", 1, 1)


@pytest.mark.parametrize("rows,ctx,visited,skipped", [(1, 1, 17, 3), (32, 32000, 276, 40), (64, 90000, 318, 70), (16, 3000, None, None)])
def test_decode_step_cost_on_hand_made_sizes(rows, ctx, visited, skipped):
    """By hand: the weights of twenty attention sublayers and routers (the
    router's MLP float32), the experts VISITED, the tied head and the final
    norm; each row's columns read and written in twenty layers; every attended
    row of twenty layers' 256-lane keys and values read and one a sequence
    written; embedding rows in, float32 logits out."""
    c = FAM.decode_step_cost(CFG, "auto", rows, ctx, experts_visited=visited, skipped_rows=skipped)
    n_visited = visited if visited is not None else 20 * FAM.experts_reached(CFG, rows)
    n_skipped = skipped if skipped is not None else 20 * rows / 17
    weights = 20 * (ATTN * 2 + ROUTER_BF16 * 2 + ROUTER_F32 * 4) + (2048 * 262_272 + 2048) * 2 + n_visited * EXPERT * 2
    slots, kv = 2 * rows * 20 * SLOT_ROW, 20 * 2 * 256 * 2 * (ctx + rows)
    io = rows * (2048 * 2 + 262_272 * 4)
    assert c["expert_bytes"] == pytest.approx(n_visited * EXPERT * 2) and c["slot_bytes"] == slots and c["kv_bytes"] == kv
    assert c["weight_bytes"] == pytest.approx(weights) and c["bytes"] == pytest.approx(weights + slots + kv + io)
    per_row = 20 * (2 * (W_IN + W_O + 1280 * 4 * 128) + 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 17)) + 2 * 2048 * 262_272
    assert c["flops"] == pytest.approx(rows * per_row + (20 * rows - n_skipped) * 2 * EXPERT + 20 * 4 * 1024 * ctx)
    least = roofline.min_seconds(c, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(c["bytes"] / 819e9)
    assert c["bytes"] <= FAM.decode_step_cost(CFG, "auto", rows, ctx, experts_visited=320)["bytes"]  # every expert: the most


def test_a_step_at_32_rows_is_the_issues_nine_gigabytes():
    c = FAM.decode_step_cost(CFG, "auto", 32, 32 * 1000)
    assert 8.4e9 < c["bytes"] < 9.3e9 and 6.7e9 < c["expert_bytes"] < 7.1e9 and 0.6e9 < c["kv_bytes"] < 0.7e9
    assert 10.2e-3 < roofline.min_seconds(c, "TPU v5 lite")["seconds"] < 11.4e-3


PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "lm_head_bias": False, "model_type": "zaya", "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts": 16,
    "num_experts_per_tok": 1, "num_key_value_heads": 2, "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True, "vocab_size": 262272,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
                        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
                        "rope_type": "default"},
}


def test_configuration_is_the_published_one_cut_in_depth_and_context_only():
    assert {k: CFG[k] for k in PUBLISHED} == PUBLISHED
    assert CFG["layer_types"] == ["hybrid"] * 40  # kept whole, as published: the family reads the first twenty
    assert CFG["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert (CFG["num_hidden_layers"], CFG["max_position_embeddings"]) == (20, 3072)
    assert set(CFG["reduced_why"]) == set(CFG["reduced"]) and CFG["deployment"] and CFG["memory"] and CFG["family"] == "zaya"
    assumed = " ".join(CFG["assumed"])
    for item in ("convolutions", "q-k mean", "value shift", "unit L2 norm", "temperature", "GELU", "RMSNorm", "gamma", "skip choice",
                 "balancing bias", "residual merge", "float32", "seeded random", "synthetic"):
        assert item in assumed, item
    assert "2510.04476" in assumed and "2511.17127" in assumed
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] and entry["source"] == CFG["source"] and entry["file"].endswith(CONFIG + ".json")
    assert len(entry["why"]) <= 200
    mc = FAM.model_config(CFG, CONFIG)
    assert mc.is_hybrid and mc.layer_groups == (("cca", 20),) and mc.num_attention_layers == 20
    assert (mc.num_experts, mc.num_experts_per_tok, mc.experts_held, mc.router_choices, mc.router_hidden_size) == (16, 1, 16, 17, 256)
    assert (mc.hidden_size, mc.intermediate_size, mc.q_size, mc.kv_size, mc.head_dim, mc.vocab_size) == (2048, 2048, 1024, 256, 128, 262272)
    assert (mc.cca_channels, mc.cca_slot_lanes, mc.block_size, mc.max_seq_len) == (1280, 2688, 128, 3072)
    assert (mc.rope_theta, mc.rope_fraction, mc.router_kind, mc.moe_skip_choice, mc.residual_merge) == (5e6, 0.5, "zaya", True, True)
    sc = CFG["scheduler"]
    assert (sc["num_blocks"], sc["max_running"], sc["decode_buckets"], sc["num_scheduler_steps"]) == (1025, 64, [32, 64], 8)
    assert sc["max_prefill_chunk"] == sc["mixed_prefill_budget"] == 256 == 2 * mc.block_size  # the reference's no_conv_carry zeroes at two blocks
    assert sc["enable_prefix_caching"] is False and CFG["engine"]["max_seq_len"] == 3072 == 24 * mc.block_size
    assert max(CFG["parity"]["prompt_lens"]) + 9 + 16 < mc.max_seq_len
    assert all(CFG["parity"][k] == sc[k] for k in ("num_blocks", "max_running"))  # the check's cache is the engine's
    assert set(CFG["parity"]["controls"]) == set(FAM.CONTROLS) - {"bf16_router"}  # (that one cannot fail: PERF.md section 6, PR 41)
    assert sum(n > 2 * CFG["parity"]["chunk"] for n in CFG["parity"]["prompt_lens"]) >= 2  # prompts that cross two chunk boundaries
    re = CFG["rehearsal"]
    assert re["scheduler"]["max_prefill_chunk"] == 2 * re["engine"]["block_size"] == re["parity"]["chunk"]
    for bad in (dict(model_type="zaya1_vl"), dict(sliding_window=4096), dict(attention_bias=True), dict(tie_word_embeddings=False)):
        with pytest.raises(ValueError):
            FAM.model_config(dict(CFG, **bad), "x")


def test_the_traffic_is_the_issues_letter_for_letter():
    assert MIX["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32, "max": 960,
                                    "max_why": MIX["prompt_tokens"]["max_why"]}
    assert MIX["output_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256, "max": 2048}
    assert MIX["order"] == "rotate" and MIX["base_seed"] == 41 and MIX["arrival"] == {"dist": "gamma", "cv": 1.0}
    assert MIX["loop"] == "open" and MIX["ramp_s"] == 30.0 and MIX["trace"]["seconds"] == 4.0
    assert MIX["stream"] is True and MIX["temperature"] == 0.0 and MIX["ignore_eos"] is True and MIX["shared_prefix_share"] == 0.0
    assert MIX["rate_rps"] > 0 and "sweep" in MIX["rate_from"]
    assert MIX["prompt_tokens"]["max"] + MIX["output_tokens"]["max"] + CFG["scheduler"]["num_scheduler_steps"] <= CFG["engine"]["max_seq_len"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "reason", "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert "4/5 of the knee" in cell["why"]


NEW = ["step_roofline_pct", "cca_share_pct", "router_share_pct", "moe_share_pct", "experts_visited_pct", "expert_rows_mean",
       "skipped_rows_pct", "cca_slots_in_use_mean", "sched_slots_host_ms"]
NINE = ["idle_pre_launch_pct", "idle_post_sync_pct", "idle_loop_pct", "sched_host_ms", "staged_wait_p50_ms",
        "programs_per_dispatch", "decode_program_ms", "mixed_program_ms", "frontend_busy_pct"]
ACCEPTED = ["client_ttft_p50_ms", "client_ttft_p90_ms", "compiles_in_window", "device_idle_pct", "prefill_tok_s",
            "loadgen_late_p99_ms", "queue_wait_p50_ms", "frontend_ttft_gap_ms", "decode_step_ms"]


def test_the_cell_is_in_the_manifest_and_its_metric_files_stand_ready():
    """The cell reports `tpot_p50_ms`, `setup_s` and, per layer, `compile_s` and
    the 27 metrics whose files end in `.reason.json`: each entry's fields are
    its file's, and the 27 stand at the end of the list, after PR 39's
    `build_eager_executables`, in this file's order (`NEW`, `ACCEPTED`, the
    nine `.cca.` twins). PR 41 brought the files, PR 44 listed them (PERF.md
    section 6)."""
    mine = {m["name"]: m for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])}
    ready = [n + ".reason" for n in NEW + ACCEPTED] + [n + ".cca.reason" for n in NINE]
    assert set(mine) == set(ready) | {"compile_s"} and mine["compile_s"]["moves"] == "setup_s"
    assert CELL in next(m for m in MANIFEST["end_to_end"] if m["name"] == "tpot_p50_ms")["workloads"]
    assert CELL not in next(m for m in MANIFEST["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    on_disk = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "metrics")) if f.endswith(".reason.json"))
    assert on_disk == sorted(ready) and len(ready) == 27
    listed = [m["name"] for m in MANIFEST["per_layer"]]
    assert sorted(ready, key=listed.index) == ready and listed.index(ready[0]) > listed.index("build_eager_executables")
    for name in ready:
        spec, entry = readers.load_metric(name), mine[name]
        assert entry == {**{k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")}, "workloads": [CELL]}
        assert spec["moves"] == "tpot_p50_ms" and "workloads" not in spec and len(spec["unit"]) <= 16
        assert spec["reader"] in readers.READERS or os.path.exists(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))


@pytest.mark.parametrize("name", [n + ".reason" for n in NEW] + [n + ".cca.reason" for n in NINE])
def test_a_reader_finds_nothing_on_an_empty_run_and_does_not_raise(name):
    """The parent has none of this PR's spans and counters, and an untraced run no rows: None, never an error."""
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    flight = type("F", (), {})()  # a program without the step log
    hooks = type("H", (), {"engine": type("E", (), {"scheduler": type("S", (), {"flight": flight})()})()})()
    for rows in (None, []):
        empty = type("Run", (), {"trace_rows": rows, "trace_busy": None, "hooks": hooks, "family": FAM, "_dyn_rows": [],
                                 "window": (0.0, 1.0), "cfg": CFG, "weight_dtype": "auto", "device": {"kind": "TPU v5 lite"}})()
        assert readers.read_metric(name, empty) is None
    # The parent's entries carry none of the counts: a step log without them reads as nothing too.
    log = type("Log", (), {"spans": [("sched.step", 10, 20, 1, {"kind": "decode_multi", "key": "(8, 32, 4)", "rows": 20})], "requests": []})()
    bare = type("H", (), {"engine": type("E", (), {"scheduler": type("S", (), {"flight": type("F", (), {"log": log})()})()})()})()
    run = type("Run", (), {"trace_rows": None, "trace_busy": None, "hooks": bare, "family": FAM, "_dyn_rows": [],
                           "window": (0.0, 1.0), "cfg": CFG, "weight_dtype": "auto", "device": {"kind": "TPU v5 lite"}})()
    if name.rsplit(".", 2)[0] not in ("sched_host_ms", "staged_wait_p50_ms"):  # (those two read any step entry)
        assert readers.read_metric(name, run) is None


def test_counter_readers_on_hand_made_step_entries():
    """A step log of four entries: two windows of 8 steps, a mixed step, and
    an entry of another program's (no counts)."""
    spans = [("sched.step", 10, 20, 1, {"kind": "decode_multi", "key": "(8, 32, 4)", "rows": 20, "held_assignments": 3000,
                                       "experts_visited": 2000, "skipped_rows": 200, "cca_slots": 21}),
             ("sched.step", 22, 28, 2, {"kind": "decode_multi", "key": "(8, 64, 8)", "rows": 40, "held_assignments": 6100,
                                       "experts_visited": 2400, "skipped_rows": 300, "cca_slots": 40}),
             ("sched.step", 30, 40, 2, {"kind": "mixed", "key": "(256, 16, 32, 4)", "rows": 20, "held_assignments": 1400,
                                       "experts_visited": 300, "skipped_rows": 90, "cca_slots": 23}),
             ("sched.slots", 41, 45, 3, {"slot": 4}), ("sched.slots", 46, 52, 3, {"slot": 5}),
             ("sched.step", 60, 70, 3, {"kind": "decode_multi", "key": "(8, 32, 4)", "rows": 3}),
             ("sched.step", 2_000_000_000, 2_000_000_010, 4, {"kind": "decode", "rows": 1, "cca_slots": 60})]  # after the window
    log = type("Log", (), {"spans": spans})()
    engine = type("E", (), {"scheduler": type("S", (), {"flight": type("F", (), {"log": log})()})()})()
    run = type("Run", (), {"hooks": type("H", (), {"engine": engine})(), "window": (0.0, 1.0), "cfg": CFG})()
    assert readers.read_metric("expert_rows_mean.reason", run) == pytest.approx(9100 / 4400)
    assert readers.read_metric("experts_visited_pct.reason", run) == pytest.approx(100 * 4400 / (16 * 320))
    assert readers.read_metric("skipped_rows_pct.reason", run) == pytest.approx(100 * 500 / (8 * 20 * 20 + 8 * 20 * 40))
    assert readers.read_metric("cca_slots_in_use_mean.reason", run) == pytest.approx(28.0)
    assert readers.read_metric("sched_slots_host_ms.reason", run) == pytest.approx(5e-6)


def test_device_readers_on_hand_made_rows():
    """A slice of 10 ms: one window of 2 steps at 32 rows whose program runs 3 ms, and device operations told by
    their HLO lines: the attention kernel and a 1280-lane fusion (cca), a 256-lane and a 17-wide one (router), gmm."""
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    mark = lambda text, t: ["/host:CPU", "python3", "bench:" + text, t, 0]  # noqa: E731
    from benchmark import trace as tr

    rows = [[ "/host:CPU", "python3", tr.MARK + "window_open", 0, 0], ["/host:CPU", "python3", tr.MARK + "window_close", 10_000_000, 0],
            [dev, mods, "jit_decode_multi_w2(7)", 1_000_000, 3_000_000],
            [dev, ops, "%ragged_paged_attention.3 = bf16[64,8,128]{2,1,0} custom-call(...)", 1_000_000, 400_000],
            [dev, ops, "%fusion.9 = bf16[64,1280]{1,0} fusion(...)", 1_400_000, 100_000],
            [dev, ops, "%fusion.10 = f32[64,256]{1,0} fusion(...)", 1_500_000, 200_000],
            [dev, ops, "%fusion.11 = f32[64,17]{1,0} fusion(...)", 1_700_000, 100_000],
            [dev, ops, "%scatter.2 = bf16[20500,128,256]{2,1,0} scatter(...)", 1_800_000, 200_000],
            [dev, ops, "%gmm.5 = bf16[64,2048]{1,0} custom-call(...)", 2_000_000, 1_500_000],
            [dev, ops, "%fusion.12 = bf16[64,2048]{1,0} fusion(...)", 3_500_000, 500_000]]
    del mark
    step = ["/host:CPU", "t", "dyn:sched.step", 500_000, 4_000_000,
            {"kind": "decode_multi", "key": "(2, 32, 8)", "rows": 32, "ctx": 32_000, "experts_visited": 540, "skipped_rows": 80, "step": 1}]
    run = type("Run", (), {"trace_rows": rows, "trace_busy": {"busy_s": 3e-3, "window_s": 10e-3}, "_dyn_rows": [step], "family": FAM,
                           "cfg": CFG, "weight_dtype": "auto", "device": {"kind": "TPU v5 lite"}})()
    assert readers.read_metric("cca_share_pct.reason", run) == pytest.approx(100 * (0.4 + 0.1 + 0.2) / 3.0)
    assert readers.read_metric("router_share_pct.reason", run) == pytest.approx(100 * (0.2 + 0.1) / 3.0)  # the pool's 256-lane pages are not the router's
    assert readers.read_metric("moe_share_pct.reason", run) == pytest.approx(100 * 1.5 / 3.0)
    cost = FAM.decode_step_cost(CFG, "auto", 32.0, 32_000 + 32 * 0.5, experts_visited=270.0, skipped_rows=40.0)
    want = 100.0 * 2 * roofline.min_seconds(cost, "TPU v5 lite")["seconds"] / 3e-3
    assert readers.read_metric("step_roofline_pct.reason", run) == pytest.approx(want)


def _rehearsal_sizes():
    from benchmark.run import overlay

    cfg = overlay(CFG, CFG["rehearsal"])
    return cfg, FAM.model_config(cfg, CONFIG)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    shared = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        shared = shared.parent  # a worker's base is <session>/popen-gwN
    kept = shared / "benchmark_rehearsal_zaya.json"
    with open(shared / "benchmark_rehearsal.lock", "w") as lock:  # the lock of conftest.py's rehearsal
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not kept.exists():
            env = dict(os.environ, JAX_PLATFORMS="cpu", DYN_LOG="ERROR", BENCH_RUN="7")
            env.pop("XLA_FLAGS", None)
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
                 "--seed", str(2**31 + 41), "--seconds", "4", "--trace", "1", "--rehearse"],
                cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
            kept.write_text(json.dumps({"returncode": p.returncode, "stdout": p.stdout, "stderr": p.stderr[-20000:]}))
        return json.loads(kept.read_text())


def test_rehearsal_serves_the_cell_over_http_on_pool_and_slots(rehearsed):
    """What is asserted is what the schedule and the program decide, never what
    four seconds of a loaded machine's clock happen to hold: the first form of
    this test asked for a marked step inside the traced slice and failed in the
    driver's run of the suite, on a host six workers kept busy, with every
    request served (PR 41's review). The step entries' counts are read in the
    test above, with no clock; the traced slice was captured and reduced or the
    run would have raised."""
    assert rehearsed["returncode"] == 0, rehearsed["stderr"][-3000:]
    lines = [json.loads(line) for line in rehearsed["stdout"].splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] == 16
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert "compile_s" in last["metric_names"] and last["counts"]["trace_steps"] >= 0
    setup = next(l for l in lines if l.get("phase") == "setup")
    assert last["counts"]["tokens_received"] >= setup["offered"]["output_tokens"] > 0  # ignore_eos: every answer whole
    engine = next(l for l in lines if l.get("phase") == "engine")
    assert engine["model"] == CONFIG and engine["layers"] == 4 and engine["vocab"] == 512
    groups = next(l for l in lines if l.get("number") == "group_rel_err")["groups"]
    assert set(groups) == {"slot_head", "chunk_head", "body", "rows", "windows"}


def test_the_counter_readers_read_a_served_schedulers_own_step_log():
    """The readers on the entries a real scheduler of this kind writes, at the
    rehearsal's sizes and with no clock in it: three requests served to their
    end in this process, and the whole of the log read. (The rehearsal below
    serves the cell over HTTP inside a window of four seconds of the host's
    clock; what that window holds depends on the machine's load, so nothing is
    asserted of it.)"""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions

    cfg, mc = _rehearsal_sizes()
    sc = SchedulerConfig(**{k: v for k, v in cfg["scheduler"].items() if not k.endswith("_why")})
    s = Scheduler(mc, FAM.make_params(mc, 41), sc, dtype=jnp.float32)
    rng = np.random.default_rng(41)
    for rid, (n, m) in {"a": (37, 20), "b": (9, 12), "c": (20, 17)}.items():
        s.add_request(rid, rng.integers(1, mc.vocab_size, size=n).tolist(), SamplingParams(temperature=0.0),
                      StopConditions(max_tokens=m, ignore_eos=True))
    tokens, iterations = 0, 0
    while s.has_work():
        tokens += sum(o.token_id >= 0 for _, o in s.step())
        iterations += 1
        assert iterations < 200
    assert tokens == 20 + 12 + 17
    hooks = type("H", (), {"engine": type("E", (), {"scheduler": s})()})()
    run = type("Run", (), {"hooks": hooks, "window": (0.0, 1e12), "cfg": cfg})()
    L, E = mc.num_layers, mc.num_experts
    read = {n: readers.read_metric(n + ".reason", run) for n in ("expert_rows_mean", "experts_visited_pct", "skipped_rows_pct",
                                                                 "cca_slots_in_use_mean", "sched_slots_host_ms")}
    assert all(v is not None for v in read.values()), read
    assert read["expert_rows_mean"] >= 1.0 and 0 < read["experts_visited_pct"] <= 100 and 0 < read["skipped_rows_pct"] < 100
    assert 1.0 <= read["cca_slots_in_use_mean"] <= 3.0 and read["sched_slots_host_ms"] > 0
    steps = [a for n, _, _, _, a in s.flight.log.spans if n == "sched.step" and a and a.get("kind") in ("decode", "decode_multi")]
    assert steps and all({"held_assignments", "experts_visited", "skipped_rows", "cca_slots"} <= set(a) for a in steps)
    assert all(a["experts_visited"] <= L * E for a in steps if a["kind"] == "decode")
    assert readers.read_metric("sched_host_ms.cca.reason", run) > 0  # (the other eight of the nine need the device trace or the frontend)


@pytest.mark.parametrize("seed", [1, 2**31 + 41])
def test_the_hole_on_record_a_bfloat16_router_passes_whatever_a_bfloat16_program_passes(seed):
    """``bf16_router`` is in the family's CONTROLS and NOT among the judged ones
    (``parity.controls``): the configuration states the router in float32, and a
    program that computed it in bfloat16 would still report ``correct``. Here,
    at the rehearsal's widths on a bfloat16 stream: the reference with only its
    router in bfloat16 reads BELOW the sound bfloat16 program on both compared
    numbers, so no limit that the program passes can fail it, while stepping
    the activations down (``fp8_act``) reads several times above. On the chip
    at the published widths: p90 0.335-0.366 against the program's 0.344-0.434
    (PERF.md section 6, PR 41). Until a ``benchmark`` PR compares the routing
    choices apart (PERF.md section 7, PR 41 (2)), a PR that lowers the router's
    precision has to be refused on this ground by hand. When this test fails
    because the control has come to stand clear of the program, judge it."""
    from benchmark import parity

    cfg, _ = _rehearsal_sizes()
    mc = FAM.model_config(dict(cfg, engine=dict(cfg["engine"], dtype="bfloat16")), CONFIG)
    assert "bf16_router" in FAM.CONTROLS and "bf16_router" not in CFG["parity"]["controls"]
    r = parity.check(FAM, FAM.make_params(mc, seed), mc, seed, cfg["parity"], controls=("bf16_router", "fp8_act"))
    hole, sharp = r["controls"]["bf16_router"], r["controls"]["fp8_act"]
    assert hole["rel_err"] < r["rel_err"] and hole["group_rel_err"] < r["group_rel_err"], (r["rel_err"], r["group_rel_err"], hole)
    assert sharp["rel_err"] > 3 * r["rel_err"] and sharp["group_rel_err"] > 2 * r["group_rel_err"], (r["rel_err"], sharp)
