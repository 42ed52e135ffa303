"""The probes behind PERF.md's kernel choices still run: ``tools/moe_gemm_bench.py``,
``tools/ssm_step_bench.py`` and ``tools/attn_chunk_bench.py`` import the step programs'
internals, so an attention or MoE change can break them unnoticed and the next builder
finds out on the chip's clock. Each ``--tiny`` invocation is control flow only, on the
CPU: it exits 0, prints one JSON object a line, names every form it was asked for, and
the forms of one study agree (they compute the same thing). No time it prints is read."""

import json
import math
import os

import pytest

from tests.test_bring_up import REPO, _run

CHUNK_FORMS = {"walk", "rows", "paged", "pagedrows", "tile16", "chunk16", "tile32", "chunk32", "tile16fold"}
# invocation -> the forms its lines name
PROBES = {
    "tools/moe_gemm_bench.py --tiny": {"scan", "stack", "gmm", "unroll"},
    "tools/ssm_step_bench.py --tiny --parts update": {"gather_scatter", "inplace_loop", "pallas_t8"},
    "tools/ssm_step_bench.py --tiny --parts chunk": {"ssd_chunk", "stepwise"},
    "tools/ssm_step_bench.py --tiny --parts moe": {"ragged_dot", "gmm"},
    "tools/ssm_step_bench.py --tiny --parts moe --shipped-only": {"ragged_dot", "gmm"},
    "tools/attn_chunk_bench.py --tiny": CHUNK_FORMS,
    "tools/attn_chunk_bench.py --tiny --widths 6": CHUNK_FORMS,
    "tools/attn_chunk_bench.py --tiny --study rows": {"rows", "rows_walk"},
    "tools/attn_chunk_bench.py --tiny --study rows --heads 8 --pages-per-step 1 2 4": {"rows", "rows_walk"},
}


@pytest.mark.parametrize("invocation", list(PROBES))
def test_a_tiny_probe_runs_and_its_forms_agree(invocation, tmp_path):
    script, *args = invocation.split()
    # from tmp_path: a probe appends its lines to chiprun_out/ under its cwd
    proc = _run([os.path.join(REPO, script), *args], cwd=tmp_path, timeout=180)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert lines and all(isinstance(line, dict) for line in lines)
    assert [line["error"] for line in lines if "error" in line] == []
    assert {line.get("form") for line in lines} - {None} == PROBES[invocation]
    diffs = [v for line in lines for k, v in line.items() if k.startswith("max_") and "diff" in k]
    assert len(diffs) >= len(lines) - 1  # every line but the heading compares itself with the first form
    assert all(math.isfinite(d) and d < 1e-2 for d in diffs), max(diffs)
