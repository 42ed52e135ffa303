"""One CPU rehearsal of a traced cell, shared by every test that reads it.

A run of the harness owns the ``.bench_state/`` of its checkout: the tokenizer
file, ``schedule.measured.jsonl``, ``client.measured.json`` and the profiler's
``trace/`` directory, which it deletes before it traces. Two rehearsals in the
repository's checkout at once (two test files, on two of the driver's six
workers) take these from under each other, and in a fresh checkout one fails.
So the rehearsal runs once a session: the first worker to ask takes a file
lock in the directory all workers share, runs it, and leaves the result beside
the lock for the others.
"""

import fcntl
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSED_CELL = "mistral-7b-w8.chat"


@pytest.fixture(scope="session")
def rehearsed(tmp_path_factory):
    """``{"returncode", "stdout", "stderr"}`` of the one ``--trace 1`` rehearsal of ``REHEARSED_CELL``."""
    shared = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        shared = shared.parent  # a worker's base is <session>/popen-gwN
    kept = shared / "benchmark_rehearsal.json"
    with open(shared / "benchmark_rehearsal.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not kept.exists():
            env = dict(os.environ, JAX_PLATFORMS="cpu", DYN_LOG="ERROR", BENCH_RUN="7")
            env.pop("XLA_FLAGS", None)  # one CPU device, as a one-chip cell sees one chip
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", REHEARSED_CELL,
                 "--seed", str(2**31 + 11), "--seconds", "4", "--trace", "1", "--rehearse"],
                cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
            kept.write_text(json.dumps({"returncode": p.returncode, "stdout": p.stdout, "stderr": p.stderr}))
        return json.loads(kept.read_text())
