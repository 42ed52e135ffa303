"""Test config: force JAX onto a virtual 8-device CPU mesh so sharding tests
run anywhere (SURVEY.md §4 — the reference runs distributed tests against
mockers + local etcd/NATS; we run against in-memory control plane + CPU mesh).

Must set env before jax initializes a backend.
"""

import os

# Force CPU even if the session env points at a real TPU: tests must be
# hermetic, and a chip belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DYN_LOG", "WARNING")

import jax

# Tests keep the persistent compilation cache off (engine/compile_cache.py
# would otherwise point every TpuEngine.build at <checkout>/.jax_cache).
jax.config.update("jax_enable_compilation_cache", False)
assert jax.default_backend() == "cpu", "tests must run on the CPU backend"
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"

import asyncio
import functools

import pytest


def pytest_collection_modifyitems(config, items):
    """Run coroutine test functions via asyncio.run (no pytest-asyncio here)."""
    for item in items:
        if asyncio.iscoroutinefunction(getattr(item, "function", None)):
            item.obj = _sync(item.function)


def _sync(fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return asyncio.run(fn(*args, **kwargs))

    return wrapper
