"""A CPU rehearsal of one cell end to end at the configuration's tiny sizes:
the result line's keys, and no time of a CPU run under a metric's name."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cell(*extra, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", DYN_LOG="ERROR", BENCH_RUN="7")
    env.pop("XLA_FLAGS", None)  # one CPU device, as a one-chip cell sees one chip
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "mistral-7b-w8.chat",
         "--seed", str(2**31 + 11), "--seconds", "4", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_without_a_chip_the_runner_fails_and_prints_no_result():
    p = run_cell("--trace", "0", timeout=120)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert "metrics" not in json.loads(line)


def test_rehearsal_runs_the_cell_end_to_end_with_the_tracer_on(rehearsed):
    assert rehearsed["returncode"] == 0, rehearsed["stderr"][-3000:]
    lines = [json.loads(line) for line in rehearsed["stdout"].splitlines()]  # every line is one JSON object
    last = lines[-1]
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] == 16
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    # A CPU run's times never appear under a metric's name, nor as the device's busy time.
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert last["counts"]["tokens_received"] > 0 and last["counts"]["trace_steps"] > 0
    compared = [l for l in lines if l.get("phase") == "correct"]
    assert len(compared) == 4 and all("value" in l and "limit" in l for l in compared)
    engine = next(l for l in lines if l.get("phase") == "engine")
    assert engine["continuous_profiling"] is False and engine["tokenizer"] == "HFTokenizer"
    setup = next(l for l in lines if l.get("phase") == "setup")
    assert {"weights_s", "parity_s", "engine_build_s", "warmup_s", "cache_hits", "cache_writes"} <= set(setup)
