"""The ``dots3`` family's seam and counts on hand-made sizes, its configuration
against the catalog's and against what the program is told, the cell's
manifest entries and traffic, each new reader on hand-made rows and on an
empty run, and a CPU rehearsal of its cell end to end at the tiny sizes, with
the tracer on (the lock and the kept result as ``test_benchmark_zaya.py``)."""

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

FAM = families.load("dots3")
CONFIG = "dots3-note-prev-d5-e32"
CELL = CONFIG + ".long-notes"
with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "traffic", "long-notes.json")) as _f:
    MIX = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# Parameters by hand (ISSUE 50's sizing), at the published widths.
D = 5120
FULL = {"q": D * 1024 + 1024 + 1024 * 128 * 192, "kv": D * 576 + 512 + 512 * 128 * 256, "o": 128 * 128 * D, "gate": D * 128,
        "indexer": 1024 * 64 * 128 + D * 128 + 256 + D * 64, "norm": D}
SWA = {"q": D * 1024 + 1024 + 1024 * 64 * 256, "kv": D * 1088 + 1024 + 1024 * 64 * 320, "o": 64 * 128 * D, "gate": D * 64, "norm": D}
DENSE, EXPERT, ROUTER = 3 * D * 13824 + D, 3 * D * 1536, D * 256 + D
EXPERT_LAYER = 32 * EXPERT + EXPERT + ROUTER + 256  # held experts, the shared one, router and norm, the correction bias
ROW, RING_ROW = 576, 1088


def test_the_family_exposes_the_seam_and_its_reference_takes_nothing_of_the_program():
    assert sorted(FAM.__all__) == sorted(families.SEAM)
    for name in ("experts_visited", "held_assignments", "indexed_rows", "index_ctx"):
        assert inspect.signature(FAM.decode_step_cost).parameters[name].default is None
    src = inspect.getsource(sys.modules[FAM.reference_forward.__module__])
    assert "dynamo_tpu" not in src and 'default_matmul_precision("highest")' in src and src.count("# ASSUMED") >= 7
    assert set(FAM.CONTROLS) == {"fp8_act", "attend_all", "no_window", "topk_less_1", "window_less_1"}


def test_counts_by_hand_at_the_published_widths_are_the_issues():
    """ISSUE 50: full sublayer 144.05 M (q 30.41, kv 19.73, o 83.89, gate 0.66, indexer 9.37), sliding 90.83 M, dense FFN
    212.34 M, an expert layer's held share 779.88 M; 4,087 M parameters = 8.17 GB."""
    rnd = lambda n: round(n / 1e6, 2)  # noqa: E731
    assert [rnd(FULL[k]) for k in ("q", "kv", "o", "gate", "indexer")] == [30.41, 19.73, 83.89, 0.66, 9.37]
    assert [rnd(SWA[k]) for k in ("q", "kv", "o", "gate")] == [22.02, 26.54, 41.94, 0.33]
    assert rnd(sum(FULL.values())) == 144.06 and rnd(sum(SWA.values())) == 90.84 and rnd(DENSE) == 212.34 and rnd(EXPERT_LAYER) == 779.88
    s = FAM._sizes(CFG)
    assert (s["L"], s["full"], s["window"], s["dense"], s["expert_layers"]) == (5, 2, 3, 1, 4)
    assert s["full_params"] == sum(FULL.values()) and s["window_params"] == sum(SWA.values()) and s["dense_params"] == DENSE
    assert (s["expert_params"], s["shared_params"], s["router_params"], s["row_full"], s["row_window"]) == (EXPERT, EXPERT, ROUTER, ROW, RING_ROW)
    total = 2 * sum(FULL.values()) + 3 * sum(SWA.values()) + DENSE + 4 * EXPERT_LAYER + 2 * 19008 * D + D
    assert FAM.parameter_count(CFG) == total == 4_087_154_176 and round(2 * total / 1e9, 2) == 8.17
    assert FAM.experts_reached(CFG, 0) == 0 and 12.6 < FAM.experts_reached(CFG, 16) < 12.9
    with pytest.raises(ValueError):
        FAM.decode_step_cost(CFG, "int8", 1, 1)


@pytest.mark.parametrize("rows,ctx,visited,held,indexed,scored", [
    (1, 5000, 3, 4, 2 * 2048, 2 * 5001), (16, 16 * 9000, 50, 64, 16 * 2 * 2048, 2 * 16 * 9001), (32, 32 * 6000, None, None, None, None)])
def test_decode_step_cost_on_hand_made_sizes(rows, ctx, visited, held, indexed, scored):
    """By hand: the weights of every sublayer but the experts, the experts
    VISITED, head, embedding row and final norm; for each row and full layer
    every index key scored and the rows chosen, a row and a key written; for
    each row and sliding layer the ring of 513 read and a row written."""
    c = FAM.decode_step_cost(CFG, "auto", rows, ctx, experts_visited=visited, held_assignments=held, indexed_rows=indexed, index_ctx=scored)
    n_visited = visited if visited is not None else 4 * FAM.experts_reached(CFG, rows)
    n_held = held if held is not None else 4 * rows * 8 * 32 / 256
    n_scored = scored if scored is not None else 2 * (ctx + rows)
    n_indexed = indexed if indexed is not None else 2 * rows * 2048
    rest = (2 * sum(FULL.values()) + 3 * sum(SWA.values()) + DENSE + 4 * (EXPERT + ROUTER) + D * 19008 + D) * 2 + 4 * 256 * 4
    index_bytes = n_scored * 128 * 2 + 2 * rows * 128 * 2
    chosen_bytes = n_indexed * ROW * 2 + 2 * rows * ROW * 2
    ring_bytes = 3 * rows * 514 * RING_ROW * 2
    assert c["expert_bytes"] == pytest.approx(n_visited * EXPERT * 2) and c["weight_bytes"] == pytest.approx(rest + n_visited * EXPERT * 2)
    assert (c["index_bytes"], c["chosen_bytes"], c["ring_bytes"]) == pytest.approx((index_bytes, chosen_bytes, ring_bytes))
    assert c["bytes"] == pytest.approx(c["weight_bytes"] + index_bytes + chosen_bytes + ring_bytes + rows * (D * 2 + 19008 * 4))
    proj_full = (sum(FULL.values()) - D - 1024 - 512 - 256) - 512 * 128 * 256 + 128 * 128 * 512 + 128 * 512 * 128
    proj_swa = (sum(SWA.values()) - D - 2048) - 1024 * 64 * 320 + 64 * 192 * 1024 + 64 * 1024 * 128
    per_row = 2 * (2 * proj_full + 3 * proj_swa + (DENSE - D) + 4 * (EXPERT + D * 256) + D * 19008)
    attn = 2 * (n_indexed * 128 * (2 * 512 + 64) + 3 * rows * 513 * 64 * (2 * 1024 + 64)) + 2 * n_scored * 64 * 128
    assert c["flops"] == pytest.approx(rows * per_row + n_held * 2 * EXPERT + attn)
    least = roofline.min_seconds(c, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(c["bytes"] / 819e9)


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return next((json.loads(line) for line in f if json.loads(line)["name"] == "dots3-note-prev"), None)


def test_configuration_is_the_published_one_cut_in_depth_experts_vocabulary_and_context():
    cat = _catalog()
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    if cat is not None:  # every key of the catalog's config but the four reduced ones, letter for letter
        assert CFG["source"] == cat["source_url"]
        assert {k: CFG[k] for k in cat["config"] if k not in CFG["reduced"]} == {k: v for k, v in cat["config"].items() if k not in CFG["reduced"]}
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"], CFG["vocab_size"], CFG["max_position_embeddings"]) == (5, 32, 19008, 18432)
    assert len(CFG["layer_types"]) == 46 and CFG["layer_types"][:6] == ["full_attention"] * 2 + ["sliding_attention"] * 3 + ["full_attention"]
    dep = CFG["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["experts_published"], dep["first_expert_held"], dep["vocab_published"],
            dep["layers_published"]) == (8, 256, 0, 152064, 46) and 8 * 19008 == 152064 and 8 * 32 == 256
    assert set(CFG["reduced_why"]) == set(CFG["reduced"]) and CFG["memory"] and CFG["family"] == "dots3"
    assumed = " ".join(CFG["assumed"])
    for item in ("apply_mla_qkv_lora_rescale", "headwise", "(2i, 2i + 1)", "counts the token itself", "DeepSeek-V3.2-Exp", "Hadamard",
                 "LayerNorm", "noaux_tc", "one group", "float32", "seeded random", "vision tower", "synthetic"):
        assert item in assumed, item
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] and entry["source"] == CFG["source"] and entry["file"].endswith(CONFIG + ".json")
    assert len(entry["why"]) <= 200
    mc = FAM.model_config(CFG, CONFIG)
    assert mc.is_latent and mc.latent_groups == (("mla_full", True, 1), ("mla_full", False, 1), ("mla_window", False, 3))
    assert tuple(mc.latent_sizes("mla_full")) == (128, 128, 64, 128, 1024, 512, 8e7)
    assert tuple(mc.latent_sizes("mla_window")) == (64, 192, 64, 128, 1024, 1024, 5e4)
    assert (mc.index_n_heads, mc.index_head_dim, mc.index_topk, mc.sliding_window) == (64, 128, 2048, 513)
    assert (mc.num_experts, mc.num_experts_per_tok, mc.experts_held, mc.first_expert_held, mc.router_kind) == (256, 8, 32, 0, "sigmoid")
    assert (mc.hidden_size, mc.intermediate_size, mc.dense_intermediate_size, mc.shared_intermediate_size, mc.vocab_size) == (
        5120, 1536, 13824, 1536, 19008)
    assert (mc.first_k_dense, mc.norm_topk_prob, mc.routed_scaling_factor, mc.attention_gate, mc.mla_lora_rescale) == (1, True, 1.0, True, True)
    assert (mc.block_size, mc.max_seq_len, mc.tie_word_embeddings) == (1024, 18432, False) and mc.max_seq_len == 18 * mc.block_size
    sc = CFG["scheduler"]
    assert sc["max_prefill_chunk"] == sc["mixed_prefill_budget"] == sc["prefill_buckets"][0] == CFG["parity"]["chunk"]
    assert sc["enable_prefix_caching"] is False and sc["num_scheduler_steps"] == 8 == CFG["parity"]["window"]
    assert all(CFG["parity"][k] == sc[k] for k in ("num_blocks", "max_running"))  # the check's cache is the engine's
    assert max(CFG["parity"]["prompt_lens"]) > mc.index_topk + mc.sliding_window  # the indexer chooses and a ring wraps
    assert set(CFG["parity"]["controls"]) <= set(FAM.CONTROLS)
    for bad in (dict(model_type="dots3_vl"), dict(vision_config={"depth": 42}), dict(audio_config={"layers": 1}), dict(attention_bias=True),
                dict(scoring_func="softmax"), dict(tie_word_embeddings=True), dict(attention_gate_type="none")):
        with pytest.raises(ValueError):
            FAM.model_config(dict(CFG, **bad), "x")


def test_the_traffic_is_the_issues_letter_for_letter():
    assert MIX["prompt_tokens"] == {"dist": "lognormal", "median": 8192, "sigma": 0.4, "min": 4096, "max": 16384,
                                    "max_why": MIX["prompt_tokens"]["max_why"]}
    assert MIX["output_tokens"]["dist"] == "lognormal" and MIX["output_tokens"]["sigma"] == 0.4
    assert 512 <= MIX["output_tokens"]["median"] <= 768 and (MIX["output_tokens"]["min"], MIX["output_tokens"]["max"]) == (256, 1024)
    assert MIX["order"] == "rotate" and MIX["base_seed"] == 50 and MIX["arrival"] == {"dist": "gamma", "cv": 1.0}
    assert MIX["loop"] == "open" and MIX["ramp_s"] >= 20.0 and MIX["trace"]["seconds"] >= 8.0
    assert MIX["stream"] is True and MIX["temperature"] == 0.0 and MIX["ignore_eos"] is True and MIX["shared_prefix_share"] == 0.0
    assert MIX["rate_rps"] > 0 and "sweep" in MIX["rate_from"]
    assert MIX["prompt_tokens"]["max"] + MIX["output_tokens"]["max"] + CFG["scheduler"]["num_scheduler_steps"] <= CFG["engine"]["max_seq_len"]
    assert MIX["prompt_tokens"]["min"] > CFG["index_topk"]  # every context is past the top-k: every full layer's query chooses
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "long-notes", "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert "of the knee" in cell["why"] and MANIFEST["workloads"][-1] == cell and MANIFEST["configs"][-1]["name"] == CONFIG


# Twelve of ISSUE 50's eighteen: the manifest holds 128 per-layer entries at most and had 116. Left out, files and all: the twins
# `prefill_tok_s`, `client_ttft_p50_ms`, `compiles_in_window`, `programs_per_dispatch`, `frontend_busy_pct`, and `held_pairs_pct`.
TWINS = ["device_idle_pct", "sched_host_ms", "decode_program_ms", "mixed_program_ms"]
NEW = ["step_mfu_pct", "latent_share_pct", "indexer_share_pct", "moe_share_pct", "indexed_rows_per_ctx_row", "window_slots_in_use_mean",
       "experts_visited_pct", "pool_fill_pct"]
WITH_FILES = [n for n in TWINS if n != "device_idle_pct"] + NEW


def test_the_cell_is_in_the_manifest_and_its_metric_files_stand_at_the_end():
    mine = {m["name"]: m for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])}
    ready = [n + ".long-notes" for n in TWINS + NEW]
    assert set(mine) == set(ready) | {"compile_s"} and mine["compile_s"]["moves"] == "setup_s"
    assert len(MANIFEST["per_layer"]) <= 128  # the manifest's own limit: what the driver refuses before any run
    assert next(m for m in MANIFEST["end_to_end"] if m["name"] == "tpot_p50_ms")["workloads"][-1] == CELL
    assert CELL not in next(m for m in MANIFEST["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    on_disk = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "metrics")) if f.endswith(".long-notes.json"))
    assert on_disk == sorted(ready)
    assert [m["name"] for m in MANIFEST["per_layer"]][-len(ready):] == ready  # appended, in this file's order
    for name in ready:
        spec, entry = readers.load_metric(name), mine[name]
        assert entry == {**{k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")}, "workloads": [CELL]}
        assert spec["moves"] == "tpot_p50_ms" and "workloads" not in spec and len(spec["unit"]) <= 16
        assert spec["reader"] in readers.READERS or os.path.exists(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    assert "mfu" in "step_mfu_pct.long-notes" and mine["step_mfu_pct.long-notes"]["unit"] == "%"


def _run(**kw):
    base = {"trace_rows": None, "trace_busy": None, "family": FAM, "_dyn_rows": [], "window": (0.0, 1.0), "cfg": CFG,
            "weight_dtype": "auto", "device": {"kind": "TPU v5 lite"}}
    return type("Run", (), {**base, **kw})()


def _hooks(log=None):
    flight = type("F", (), {} if log is None else {"log": log})()
    return type("H", (), {"engine": type("E", (), {"scheduler": type("S", (), {"flight": flight})()})()})()


@pytest.mark.parametrize("name", [n + ".long-notes" for n in TWINS + NEW])
def test_a_reader_finds_nothing_on_an_empty_run_and_does_not_raise(name):
    """The parent has none of this PR's counters, and an untraced run no rows: None, never an error."""
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", name + ".py")) == (name.rsplit(".", 1)[0] in WITH_FILES)
    for rows in (None, []):
        assert readers.read_metric(name, _run(trace_rows=rows, hooks=_hooks())) is None
    # The parent's entries carry none of the counts: a step log without them reads as nothing too.
    log = type("Log", (), {"spans": [("sched.step", 10, 20, 1, {"kind": "decode_multi", "key": "(8, 32, 4)", "rows": 20})], "requests": []})()
    if name.rsplit(".", 1)[0] != "sched_host_ms":  # (that one reads any step entry)
        assert readers.read_metric(name, _run(hooks=_hooks(log))) is None


def test_counter_readers_on_hand_made_step_entries():
    """A step log of two windows of 8 steps, a mixed step, an entry of another program's (no counts) and one after the window."""
    counts = lambda **kw: dict(kw)  # noqa: E731
    spans = [("sched.step", 10, 20, 1, counts(kind="decode_multi", key="(8, 16, 24)", rows=10, held_assignments=2600, experts_visited=800,
                                              indexed_rows=10 * 8 * 2 * 2048, index_ctx=10 * 8 * 2 * 8192, window_slots=11, pool_blocks=200)),
             ("sched.step", 22, 28, 2, counts(kind="decode_multi", key="(8, 32, 35)", rows=20, held_assignments=5000, experts_visited=900,
                                              indexed_rows=20 * 8 * 2 * 2048, index_ctx=20 * 8 * 2 * 4096, window_slots=21, pool_blocks=392)),
             ("sched.step", 30, 40, 2, counts(kind="mixed", key="(512, 16, 32, 24)", rows=20, held_assignments=9000, experts_visited=128,
                                              indexed_rows=81920, index_ctx=163840, window_slots=22, pool_blocks=400)),
             ("sched.step", 60, 70, 3, counts(kind="decode_multi", key="(8, 32, 4)", rows=3)),
             ("sched.step", 2_000_000_000, 2_000_000_010, 4, counts(kind="decode", rows=1, window_slots=30, pool_blocks=1000))]
    run = _run(hooks=_hooks(type("Log", (), {"spans": spans})()))
    assert readers.read_metric("indexed_rows_per_ctx_row.long-notes", run) == pytest.approx((10 + 20) * 2048 / (10 * 8192 + 20 * 4096))
    assert readers.read_metric("window_slots_in_use_mean.long-notes", run) == pytest.approx(18.0)
    assert readers.read_metric("experts_visited_pct.long-notes", run) == pytest.approx(100 * 1700 / (16 * 4 * 32))
    assert readers.read_metric("pool_fill_pct.long-notes", run) == pytest.approx(100 * (200 + 392 + 400) / 3 / (CFG["scheduler"]["num_blocks"] - 1))


def test_device_readers_on_hand_made_rows():
    """A slice of 30 ms: one window of 2 steps at 16 rows whose program runs 20 ms, and device operations told by their
    HLO lines: scores and gathered rows (latent), index scores and the sort (indexer), gmm."""
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    from benchmark import trace as tr

    rows = [["/host:CPU", "python3", tr.MARK + "window_open", 0, 0], ["/host:CPU", "python3", tr.MARK + "window_close", 30_000_000, 0],
            [dev, mods, "jit_decode_multi_w2(7)", 1_000_000, 20_000_000],
            [dev, ops, "%fusion.3 = f32[16,128,2048]{2,1,0} fusion(...)", 1_000_000, 400_000],
            [dev, ops, "%gather.9 = bf16[16,2048,576]{2,1,0} gather(...)", 1_400_000, 300_000],
            [dev, ops, "%fusion.10 = bf16[16,64,1024]{2,1,0} fusion(...)", 1_700_000, 100_000],
            [dev, ops, "%fusion.11 = f32[16,64,12288]{2,1,0} fusion(...)", 1_800_000, 200_000],
            [dev, ops, "%sort.2 = (f32[16,12288]{1,0}, s32[16,12288]{1,0}) sort(...)", 2_000_000, 500_000],
            [dev, ops, "%gmm.5 = bf16[128,1536]{1,0} custom-call(...)", 2_500_000, 1_500_000],
            [dev, ops, "%fusion.12 = bf16[16,5120]{1,0} fusion(...)", 4_000_000, 1_000_000]]
    step = ["/host:CPU", "t", "dyn:sched.step", 500_000, 25_000_000,
            {"kind": "decode_multi", "key": "(2, 16, 24)", "rows": 16, "ctx": 16 * 9000, "experts_visited": 100, "held_assignments": 128,
             "indexed_rows": 2 * 16 * 2 * 2048, "index_ctx": 2 * 16 * 2 * 9001, "step": 1}]
    run = _run(trace_rows=rows, trace_busy={"busy_s": 4e-3, "window_s": 30e-3}, _dyn_rows=[step])
    assert readers.read_metric("latent_share_pct.long-notes", run) == pytest.approx(100 * (0.4 + 0.3 + 0.1) / 4.0)
    assert readers.read_metric("indexer_share_pct.long-notes", run) == pytest.approx(100 * (0.2 + 0.5) / 4.0)
    assert readers.read_metric("moe_share_pct.long-notes", run) == pytest.approx(100 * 1.5 / 4.0)
    cost = FAM.decode_step_cost(CFG, "auto", 16.0, 16 * 9000 + 16 * 0.5, experts_visited=50.0, held_assignments=64.0,
                                indexed_rows=16 * 2 * 2048.0, index_ctx=16 * 2 * 9001.0)
    want = 100.0 * 2 * roofline.min_seconds(cost, "TPU v5 lite")["seconds"] / 20e-3
    got = readers.read_metric("step_mfu_pct.long-notes", run)
    assert got == pytest.approx(want) and 0 < got < 100


def _rehearsal_sizes():
    from benchmark.run import overlay

    cfg = overlay(CFG, CFG["rehearsal"])
    return cfg, FAM.model_config(cfg, CONFIG)


def test_the_rehearsal_is_the_tiny_preset_of_the_same_kinds():
    from dynamo_tpu.engine.config import get_config

    cfg, mc = _rehearsal_sizes()
    tiny = get_config("tiny-dots3")
    assert mc.latent_groups == tiny.latent_groups and mc.layer_types == tiny.layer_types
    assert tuple(mc.latent_sizes("mla_full"))[:6] == tuple(tiny.latent_sizes("mla_full"))[:6]
    assert tuple(mc.latent_sizes("mla_window"))[:6] == tuple(tiny.latent_sizes("mla_window"))[:6]
    assert (mc.index_topk, mc.sliding_window, mc.num_experts, mc.experts_held, mc.num_experts_per_tok) == (8, 5, 16, 4, 2)
    assert max(cfg["parity"]["prompt_lens"]) > 24 > mc.index_topk + mc.sliding_window  # past the top-k and a ring's wrap


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    shared = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        shared = shared.parent  # a worker's base is <session>/popen-gwN
    kept = shared / "benchmark_rehearsal_dots3.json"
    with open(shared / "benchmark_rehearsal.lock", "w") as lock:  # the lock of conftest.py's rehearsal
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not kept.exists():
            env = dict(os.environ, JAX_PLATFORMS="cpu", DYN_LOG="ERROR", BENCH_RUN="7")
            env.pop("XLA_FLAGS", None)
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
                 "--seed", str(2**31 + 50), "--seconds", "4", "--trace", "1", "--rehearse"],
                cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
            kept.write_text(json.dumps({"returncode": p.returncode, "stdout": p.stdout, "stderr": p.stderr[-20000:]}))
        return json.loads(kept.read_text())


def test_rehearsal_serves_the_cell_over_http_on_pool_and_rings(rehearsed):
    """What is asserted is what the schedule and the program decide, never what
    four seconds of a loaded machine's clock happen to hold (``test_benchmark_zaya.py``)."""
    assert rehearsed["returncode"] == 0, rehearsed["stderr"][-3000:]
    lines = [json.loads(line) for line in rehearsed["stdout"].splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] == 16
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert "compile_s" in last["metric_names"] and last["counts"]["trace_steps"] >= 0
    setup = next(l for l in lines if l.get("phase") == "setup")
    assert last["counts"]["tokens_received"] >= setup["offered"]["output_tokens"] > 0  # ignore_eos: every answer whole
    engine = next(l for l in lines if l.get("phase") == "engine")
    assert engine["model"] == CONFIG and engine["layers"] == 5 and engine["vocab"] == 512 and engine["attention_impl"] == "gather"
    groups = next(l for l in lines if l.get("number") == "group_rel_err")["groups"]
    assert set(groups) == {"prefill", "body", "chosen", "rows", "windows"}


def test_the_counter_readers_read_a_served_schedulers_own_step_log():
    """The readers on the entries a real scheduler of these kinds writes, at the
    rehearsal's sizes and with no clock in it: three requests served to their
    end in this process, and the whole of the log read."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions

    cfg, mc = _rehearsal_sizes()
    sc = SchedulerConfig(**{k: v for k, v in cfg["scheduler"].items() if not k.endswith("_why")})
    s = Scheduler(mc, FAM.make_params(mc, 50), sc, dtype=jnp.float32)
    rng = np.random.default_rng(50)
    for rid, (n, m) in {"a": (37, 20), "b": (19, 12), "c": (28, 17)}.items():
        s.add_request(rid, rng.integers(1, mc.vocab_size, size=n).tolist(), SamplingParams(temperature=0.0),
                      StopConditions(max_tokens=m, ignore_eos=True))
    iterations = 0
    while s.has_work():
        s.step()
        iterations += 1
        assert iterations < 200
    run = _run(hooks=type("H", (), {"engine": type("E", (), {"scheduler": s})()})(), window=(0.0, 1e9), cfg=cfg)
    share = readers.read_metric("indexed_rows_per_ctx_row.long-notes", run)
    assert 8 / 57 < share < 8 / 19  # index_topk 8 over contexts of 19 to 57 rows
    assert 0 < readers.read_metric("window_slots_in_use_mean.long-notes", run) <= 3
    assert 0 < readers.read_metric("experts_visited_pct.long-notes", run) <= 100
    assert 0 < readers.read_metric("pool_fill_pct.long-notes", run) < 100
