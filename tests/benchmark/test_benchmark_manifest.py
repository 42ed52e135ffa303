"""`BENCHMARK.json` against the files it names: a cell, a configuration, a mix
and a metric are data, found by name."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families, readers, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|expand|experts_per_tok")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


M = manifest()
METRICS = M["end_to_end"] + M["per_layer"]


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200  # fits with the full 24 cells
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(M["workloads"]) // 4)
    for path in M["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_configuration_file_is_the_source_as_run(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"} and NAME.match(cfg["name"])
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"] and body["reduced"] == cfg["reduced"]
    assert not any(WIDTH.search(k) for k in cfg["reduced"])  # no width is ever cut
    assert body["engine"]["continuous_profiling"] is False
    assert body["assumed"] and set(body["reduced_why"]) == set(cfg["reduced"])
    family = families.load(body["family"])  # the configuration names its family; the family names its controls
    assert all(hasattr(family, name) for name in families.SEAM)
    controls = body["parity"]["controls"]
    assert controls and set(controls) <= set(family.CONTROLS)
    assert 0 < body["parity"]["limit_rel_err"] <= body["parity"]["limit_group_rel_err"] < 0.5 and body["parity"]["chunk"] == body["scheduler"]["prefill_buckets"][-1]
    assert body["parity"]["window"] == body["scheduler"]["num_scheduler_steps"]  # the check runs the served shapes
    assert body["parity"]["decode_bucket"] in body["scheduler"]["decode_buckets"]
    assert any(w["config"] == cfg["name"] for w in M["workloads"])
    assert cfg["file"].startswith("benchmark/configs/") and cfg["file"].endswith(cfg["name"] + ".json")


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_names_files_that_exist_and_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"} and len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in M["configs"]}
    mix = traffic.load_mix(cell["traffic"])
    assert mix["loop"] == "open" and mix["rate_rps"] > 0 and mix["warmup"]["seed"] > 0
    mine = lambda ms: [m for m in ms if cell["name"] in m.get("workloads", [cell["name"]])]  # noqa: E731
    e2e = mine(M["end_to_end"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2 and len(mine(M["per_layer"])) >= 1


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_a_file_with_a_reader(metric):
    spec = readers.load_metric(metric["name"])
    for key in ("name", "unit", "better", "source"):
        assert spec.get(key) == metric.get(key), key
    assert "workloads" not in spec  # which cells report a metric is the manifest's to say: a new cell edits no metric file
    own = os.path.join(ROOT, "benchmark", "metrics", metric["name"] + ".py")
    assert spec["reader"] in readers.READERS or os.path.exists(own)
    assert NAME.match(metric["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in M["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    assert ("workloads" in metric) == (metric["name"] != "setup_s")  # every cell reports its set-up
    if metric in M["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1 and metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert spec["layer"] == metric["layer"] and spec["moves"] == metric["moves"]
        moved = next(m for m in M["end_to_end"] if m["name"] == metric["moves"])
        assert set(metric["workloads"]) <= set(moved.get("workloads", cells))  # each cell reports what it moves
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
