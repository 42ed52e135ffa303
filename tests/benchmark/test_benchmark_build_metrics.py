"""The four set-up metrics that read the program's build log (PR 39):
``build_trace_s``, ``build_lower_s``, ``build_other_s``,
``build_eager_executables`` (``benchmark/build_log.py``). On a hand-made log
they give the hand-computed numbers; on a program without a log they read as
nothing and never raise; none needs a device, so the session's rehearsal lists
all four."""

import json
import os
import types

import pytest

from benchmark import build_log, readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ("build_trace_s", "build_lower_s", "build_other_s", "build_eager_executables")
S = 1_000_000_000  # ns


def entry(t_s, kind, trace_s, lower_s, backend_s):
    return types.SimpleNamespace(t_ns=int(t_s * S), kind=kind, trace_s=trace_s, lower_s=lower_s, backend_s=backend_s)


def run_of(flight, window=(130.0, 180.0)):
    return types.SimpleNamespace(hooks=types.SimpleNamespace(engine=types.SimpleNamespace(
        scheduler=types.SimpleNamespace(flight=flight))), window=window)


def hand_made():
    """``engine.build`` from 100 s to 120 s on the log's clock, the window opening at 130 s."""
    entries = [
        entry(95.0, "eager", 1.0, 1.0, 1.0),  # the harness's output check, before engine.build: left out
        entry(101.0, "eager", 0.01, 0.02, 0.03),  # the pool's zeros
        entry(105.0, "decode", 0.25, 0.30, 0.05),
        entry(110.0, "mixed", 0.50, 0.40, 0.10),
        entry(119.0, "eager", 0.02, 0.04, 0.06),
        entry(125.0, "prefill", 0.125, 0.25, 0.5),  # met by the warm-up stream: set-up, outside engine.build
        entry(126.0, "eager", 0.03, 0.01, 0.01),
        entry(150.0, "mixed", 5.0, 5.0, 5.0),  # inside the window: compiles_in_window's, not set-up's
    ]
    scopes = [("build.key", "decode", 104 * S, 106 * S), ("build.warmup", None, 103 * S, 120 * S),
              ("engine.build", None, 90 * S, 91 * S),  # another engine's, earlier in the process
              ("engine.build", None, 100 * S, 120 * S)]
    return types.SimpleNamespace(builds=types.SimpleNamespace(entries=entries, scopes=scopes), since_ns=100 * S)


def test_the_readers_give_the_hand_computed_numbers():
    run = run_of(hand_made())
    got = {name: readers.read_metric(name, run) for name in NAMES}
    assert got["build_trace_s"] == pytest.approx(0.01 + 0.25 + 0.50 + 0.02 + 0.125 + 0.03)
    assert got["build_lower_s"] == pytest.approx(0.02 + 0.30 + 0.40 + 0.04 + 0.25 + 0.01)
    # The span of 20 s less the seconds of the four entries inside it.
    assert got["build_other_s"] == pytest.approx(20.0 - (0.06 + 0.60 + 1.00 + 0.12))
    assert got["build_eager_executables"] == 3.0
    entries, span = build_log.set_up(run)
    assert [e.t_ns // S for e in entries] == [101, 105, 110, 119, 125, 126] and span == (100 * S, 120 * S)
    # A window that opens earlier takes less of what the stream met.
    assert readers.read_metric("build_eager_executables", run_of(hand_made(), window=(125.5, 175.5))) == 2.0


@pytest.mark.parametrize("flight", [
    types.SimpleNamespace(),  # a program before PR 39: no log
    types.SimpleNamespace(builds=types.SimpleNamespace(entries=[], scopes=[]), since_ns=7),  # a log, no engine.build scope
    types.SimpleNamespace(builds=hand_made().builds),  # no start to read from
], ids=["no-log", "no-engine-build", "no-since"])
def test_a_program_without_the_log_reads_as_nothing_and_never_raises(flight):
    for run in (run_of(flight), types.SimpleNamespace(window=(0.0, 1.0)), types.SimpleNamespace(hooks=None, window=(0.0, 1.0))):
        for name in NAMES:
            assert readers.read_metric(name, run) is None, name


def test_the_manifest_gives_the_four_to_the_cells_whose_tests_do_not_count_their_metrics():
    """Every cell builds an engine and the readers read any cell's log; the
    manifest lists the two ``llama`` cells alone, because the accepted tests of
    the other two count the ``per_layer`` entries that name their cell
    (``test_benchmark_evabyte.py``: 26; ``test_benchmark_granite_hybrid.py``:
    27, each but ``compile_s`` naming that cell only), and an entry without a
    ``workloads`` key is refused by ``test_benchmark_manifest.py``."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    listed = [m["name"] for m in manifest["per_layer"]]
    # Found by name, never by place: later PRs append their entries after these (PR 44). Their own order stands.
    assert listed.count(NAMES[0]) == 1 and sorted(NAMES, key=listed.index) == list(NAMES)
    for name in NAMES:
        m, spec = per_layer[name], readers.load_metric(name)
        assert (m["layer"], m["moves"], m["better"]) == ("compile", "setup_s", "lower")
        assert m["workloads"] == ["mistral-7b-w8.chat", "mixtral-8x7b-d3.chat-sat"]
        assert {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")} == {k: m[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert spec["reader"] == f"metrics/{name}.py" and os.path.exists(os.path.join(ROOT, "benchmark", spec["reader"]))
    assert per_layer["build_eager_executables"]["source"] == "program_counter"
    assert {per_layer[n]["source"] for n in NAMES[:3]} == {"program_span"}


def test_the_rehearsal_lists_all_four(rehearsed):
    """No device is needed: the log is the program's, on the host's clock."""
    assert rehearsed["returncode"] == 0, rehearsed["stderr"][-3000:]
    last = json.loads(rehearsed["stdout"].splitlines()[-1])
    assert last["rehearsal"] is True and set(NAMES) <= set(last["metric_names"]), last["metric_names"]
