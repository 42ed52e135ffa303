"""The seam a configuration of another architecture comes in by: the runner
knows a model only through `benchmark/families/<family>.py`, and the manifest
alone says which cells report a metric. Proved by adding a second family and
its cell to a copy of the benchmark as new files and manifest entries (the
files are `tests/benchmark/seam/`), and, in-process, by what the seam is made of."""

import filecmp
import importlib
import inspect
import io
import json
import os
import shutil
import subprocess
import sys
import tokenize

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families  # noqa: E402

SEAM = os.path.join(os.path.dirname(os.path.abspath(__file__)), "seam")
MODEL_WORDS = ("llama", "num_local_experts", "num_key_value_heads", "intermediate_size", "KvCacheArrays")
SHARED = ("run.py", "parity.py", "readers.py", "weights.py", "reference.py", "roofline.py")
SIGNATURES = {
    "model_config": ["cfg", "name"],
    "make_params": ["mc", "seed"],
    "program_logits": ["params", "mc", "spec", "lens", "prompts", "forced", "fault"],
    "reference_forward": ["params", "mc", "seqs", "positions", "lower"],
    "decode_step_cost": ["cfg", "weight_dtype", "rows", "ctx_tokens"],
}


def code_of(readline) -> str:
    """Python source without its comments."""
    return " ".join(tok.string for tok in tokenize.generate_tokens(readline) if tok.type != tokenize.COMMENT)


def configurations():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        for c in json.load(f)["configs"]:
            with open(os.path.join(ROOT, c["file"])) as g:
                yield c["name"], json.load(g)


CONFIGS = dict(configurations())
FAMILIES = sorted({body["family"] for body in CONFIGS.values()})


@pytest.mark.parametrize("name", SHARED)
def test_shared_files_name_no_model(name):
    """The grep of ISSUE 27's "Done means": outside comments, none of the files
    every family shares names a model module, a Hugging Face key of one
    architecture, or the cache of one."""
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        code = code_of(f.readline)
    assert not [w for w in MODEL_WORDS if w in code]


def test_the_grep_finds_a_model_word_where_there_is_one():
    code = code_of(io.StringIO("from dynamo_tpu.engine.models import llama  # not KvCacheArrays\n").readline)
    assert [w for w in MODEL_WORDS if w in code] == ["llama"]


@pytest.mark.parametrize("family", FAMILIES)
def test_family_exposes_the_seam_and_nothing_else(family):
    mod = families.load(family)
    assert sorted(mod.__all__) == sorted(families.SEAM) == sorted([*SIGNATURES, "CONTROLS"])
    for name, args in SIGNATURES.items():
        params = inspect.signature(getattr(mod, name)).parameters
        assert list(params)[:len(args)] == args, name
        assert all(p.default is not inspect.Parameter.empty for p in list(params.values())[len(args):]), name
    assert inspect.signature(mod.program_logits).parameters["fault"].default is False
    assert inspect.signature(mod.reference_forward).parameters["lower"].default is None
    assert mod.CONTROLS and all(isinstance(c, str) for c in mod.CONTROLS)
    # The reference takes nothing of the program: the module that holds it imports none of it.
    ref = inspect.getsource(importlib.import_module(mod.reference_forward.__module__))
    assert "dynamo_tpu" not in ref


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_configuration_names_a_family_that_has_its_controls(name):
    body = CONFIGS[name]
    assert set(body["parity"]["controls"]) <= set(families.load(body["family"]).CONTROLS)


def test_no_metric_file_says_which_cells_report_it():
    metrics = os.path.join(ROOT, "benchmark", "metrics")
    for f in sorted(os.listdir(metrics)):
        if f.endswith(".json"):
            with open(os.path.join(metrics, f)) as g:
                assert "workloads" not in json.load(g), f


# --- a second family, in a copy -------------------------------------------------------


@pytest.fixture(scope="module")
def copy_with_a_second_family(tmp_path_factory):
    """`benchmark/`, `BENCHMARK.json` and the manifest's test in a directory of
    their own, with the files of `tests/benchmark/seam/` added and the
    manifest's entries appended. No copied file is changed but the manifest."""
    root = tmp_path_factory.mktemp("seam")
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark", ignore=ignore)
    os.makedirs(root / "tests" / "benchmark")
    shutil.copy(os.path.join(ROOT, "tests", "benchmark", "test_benchmark_manifest.py"), root / "tests" / "benchmark")
    for sub in ("families", "configs", "traffic", "metrics"):
        for f in sorted(os.listdir(os.path.join(SEAM, sub))):
            if f == "__pycache__":
                continue
            assert not (root / "benchmark" / sub / f).exists(), f  # new files only
            shutil.copy(os.path.join(SEAM, sub, f), root / "benchmark" / sub / f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(SEAM, "manifest_entries.json")) as f:
        entries = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        manifest[key] += entries[key]
    for metric, cell in entries["append_workload_to"].items():
        next(m for m in manifest["end_to_end"] if m["name"] == metric)["workloads"].append(cell)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f, indent=1)
    return root, entries["workloads"][0]["name"]


def test_a_second_family_is_new_files_and_manifest_entries(copy_with_a_second_family):
    root, cell = copy_with_a_second_family
    same = filecmp.dircmp(os.path.join(ROOT, "benchmark"), root / "benchmark", ignore=["__pycache__"])
    assert not same.diff_files and not same.left_only  # every file the benchmark has is there, unchanged
    assert sorted(same.subdirs["families"].right_only) == ["renamed.py", "renamed_reference.py"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", DYN_LOG="ERROR", PYTHONPATH=ROOT)  # the program comes from the repo
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload", cell, "--seed", str(2**31 + 47),
         "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(line) for line in p.stdout.splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert {"out_tok_s", "setup_s"} <= set(last["metric_names"])
    assert next(l for l in lines if l.get("phase") == "engine")["model"] == "tiny-renamed"
    assert os.path.isdir(root / ".bench_state") and not os.path.exists(os.path.join(ROOT, ".bench_state", "tokenizers", "tiny-renamed.rehearsal"))


def test_the_manifests_own_tests_pass_on_the_copy(copy_with_a_second_family):
    root, cell = copy_with_a_second_family
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("PYTEST_XDIST_WORKER", None)
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark/test_benchmark_manifest.py", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    passed = [line for line in p.stdout.splitlines() if "PASSED" in line]
    for case in (f"[{cell}]", "[tiny-renamed]", "[compiles_in_window.smoke]", "[out_tok_s]"):  # the new entries were tested
        assert any(case in line for line in passed), case
