"""Every metric file is read by some cell, or this file says why not (PR 44).

A metric is `benchmark/metrics/<name>.json`, and a cell reports it only once
`BENCHMARK.json` lists an entry of that name. PR 41 brought 27 files that no
entry named, so the driver never ran their readers and the ledger showed one
per-layer number for the cell. `test_benchmark_manifest.py` goes from the
manifest to the files; this goes from the files to the manifest, a case a
file, so that a file can no longer stand ready and unread without a test
saying so.

A later PR appends its entries at the END of the manifest's lists (no test
holds a place or a total there) and names a new cell's metrics
`<metric>.<traffic>`."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers  # noqa: E402

METRICS = os.path.join(ROOT, "benchmark", "metrics")
FILES = sorted(f[:-5] for f in os.listdir(METRICS) if f.endswith(".json"))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# A file that no entry lists, with the reason: a reader that PR 44's traced seeds on the chip showed to find nothing
# in some slice. Empty: all 27 of `zaya1-8b-d20.reason` read a number on every seed (PERF.md section 6, PR 44).
UNLISTED: dict = {}


@pytest.mark.parametrize("name", FILES)
def test_a_metric_file_is_listed_under_its_name_with_its_fields(name):
    spec = readers.load_metric(name)  # (it holds the file's own `name` to the file's name)
    if name in UNLISTED:
        assert len(UNLISTED[name]) > 20 and "layer" in spec  # a reason, not a word; only a per-layer metric may wait
        assert all(m["name"] != name for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"])
        return
    kind, keys = ("per_layer", ("unit", "better", "source", "layer", "moves")) if "layer" in spec else ("end_to_end", ("unit", "better", "source"))
    entries = [m for m in MANIFEST[kind] if m["name"] == name]
    assert len(entries) == 1, f"{name}.json is in no entry of {kind}: list it at the end, or name it in UNLISTED with its reason"
    assert {k: entries[0][k] for k in keys} == {k: spec[k] for k in keys}


def test_the_unlisted_are_files_on_disk():
    assert set(UNLISTED) <= set(FILES)
