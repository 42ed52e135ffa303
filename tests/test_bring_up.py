"""Bring-up guards: nothing on the chip path quietly falls back to the CPU,
the compile cache has one rule, and ``chip_smoke.py`` fails without an
accelerator. The smoke's control flow is rehearsed at the ``tiny``
preset (``--rehearse``) so a later PR cannot break it unnoticed; the real run
needs the chip and is the builder's / driver's."""

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from dynamo_tpu.engine import compile_cache, flight_recorder
from dynamo_tpu.engine.models import llama

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd=REPO, timeout=600, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    full.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices are not the child's business
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, env=full, capture_output=True, text=True, timeout=timeout
    )


def _verdict(proc) -> dict:
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


# --- no fallback that hides the device ---------------------------------------


def test_on_tpu_lets_a_backend_failure_raise(monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        llama._on_tpu()


@pytest.mark.parametrize(
    "platform,kind,want",
    [
        ("tpu", "TPU v5 lite", (197e12, 819e9)),  # what a v5e reports
        ("tpu", "TPU v5e", (197e12, 819e9)),
        ("tpu", "TPU v4", (275e12, 1228e9)),
        ("cpu", "cpu", flight_recorder._CPU_PEAKS),  # nominal, so tests run
    ],
)
def test_peaks_are_keyed_by_reported_device_kind(platform, kind, want):
    assert flight_recorder.peaks_for(platform, kind) == want


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 ultra"), ("gpu", "NVIDIA H100"), ("tpu", "v5e")])
def test_unknown_accelerator_is_an_error_not_a_default(platform, kind):
    with pytest.raises(ValueError, match="no peak"):
        flight_recorder.peaks_for(platform, kind)


# --- compile cache: placed from outside, or one fixed path --------------------


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # idempotent
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert not jax.config.jax_enable_compilation_cache  # conftest keeps it off for tests


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


# --- chip_smoke.py -----------------------------------------------------------


def test_chip_smoke_fails_without_an_accelerator():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    verdict = _verdict(proc)
    assert verdict["ok"] is False and verdict["phase"] == "device"


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal(chips):
    """The explicit, never-default rehearsal: tiny preset on the CPU (virtual
    devices for the tp path). Same phases and checks as on the chip."""
    proc = _run(["chip_smoke.py", "--rehearse", "--chips", str(chips)])
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    assert _verdict(proc) == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": chips}}
    phases = [json.loads(line) for line in proc.stdout.splitlines()]
    if chips == 1:
        done = next(p for p in phases if p.get("phase") == "serve" and p.get("step") == "done")
        assert done["compiles_after_warm_shapes"] == 0
        assert {k[0] for k in done["shape_keys"]} >= {"prefill", "decode_multi", "mixed"}
        assert sum(p.get("phase") == "parity" and "compare" in p for p in phases) == 4
    else:
        assert sum(p.get("phase") == "tp4" and "compare" in p for p in phases) == 2


# --- the README names what the checkout holds ----------------------------------


def test_readme_names_files_that_exist():
    """Every back-quoted path of README.md under a directory of the checkout, or a ``*.py`` /
    ``*.json`` / ``*.md`` at its root, is there (``::name`` and ``:line`` cut off; globs and
    ``<...>`` are patterns, not paths)."""
    with open(os.path.join(REPO, "README.md")) as f:
        quoted = re.findall(r"`([^`\n]+)`", f.read())
    dirs = ("tools/", "dynamo_tpu/", "benchmark/", "tests/", "deploy/", "examples/")
    paths = {re.split(r"::|:\d", q.split()[0])[0] for q in quoted if q.strip()}
    paths = {
        p for p in paths
        if not re.search(r"[*<>{}$]", p) and (p.startswith(dirs) or re.fullmatch(r"[\w.-]+\.(py|json|md)", p))
    }
    assert len(paths) > 30, sorted(paths)  # the pattern still finds the README's paths
    assert [p for p in sorted(paths) if not os.path.exists(os.path.join(REPO, p))] == []
