"""One stack chunk for a set-up (PR 42, ``engine/compile_cache.py::in_one_chunk``):
the frame ``TpuEngine.build`` runs below is too large for one of CPython's
16 KiB stack chunks, so CPython maps one large chunk for it and every frame
of the set-up lives in what it leaves free: no call below it maps and unmaps
a chunk at an edge (``tools/stack_chunk_probe.py``).

(a) the helper as a call: result, arguments, an exception with its traceback,
the frame among a deep callee's ancestors, its size, the plain call on an
interpreter without chunks; (b) what it is for, on this host, with a wide
margin: the depths at which the probe's loop is slow are not slow below it;
(c) what the build log says of it, on hand-made events (the built engine's
entries are in ``tests/test_build_log.py``)."""

import importlib.util
import os
import sys
import threading
import traceback
import types

import pytest

from dynamo_tpu.engine import compile_cache
from dynamo_tpu.engine.compile_cache import BACKEND_EVENT, ANCHOR_SLOTS, BuildLog, in_one_chunk

CHUNK = 16 * 1024  # CPython's DATA_STACK_CHUNK_SIZE


def probe():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools", "stack_chunk_probe.py")
    spec = importlib.util.spec_from_file_location("stack_chunk_probe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ancestors(frame):
    while frame is not None:
        yield frame.f_code
        frame = frame.f_back


def down(n):
    return sys._getframe() if n == 0 else down(n - 1)


# --- (a) the helper as a call ---------------------------------------------------------------------------------------


@pytest.mark.parametrize("args, kwargs", [((), {}), ((1, 2), {}), ((), {"c": 3}), ((1,), {"b": 5, "c": 7})])
def test_it_returns_what_the_function_returns_from_the_arguments_and_keywords_it_was_given(args, kwargs):
    def fn(a=0, b=0, *, c=0):
        return ("got", a, b, c)

    assert in_one_chunk(fn, *args, **kwargs) == fn(*args, **kwargs)


def test_an_exception_comes_through_with_its_traceback():
    def fails(what):
        raise KeyError(what)

    with pytest.raises(KeyError, match="this") as caught:
        in_one_chunk(fails, "this")
    names = [f.name for f in traceback.extract_tb(caught.value.__traceback__)]
    assert names[-3:] == ["in_one_chunk", "anchor", "fails"]
    assert not compile_cache._stack.anchored  # cleared on the way out


def test_a_frame_300_calls_below_has_the_large_frame_among_its_ancestors_and_one_beside_it_has_not():
    anchor = compile_cache._anchor.__code__
    assert anchor in set(ancestors(in_one_chunk(down, 300)))
    assert anchor not in set(ancestors(down(300)))


def test_the_frame_cannot_fit_a_chunk_so_cpython_maps_one_for_it_with_a_megabyte_to_spare():
    code = compile_cache._anchor.__code__
    assert code.co_stacksize == ANCHOR_SLOTS and 8 * code.co_stacksize > CHUNK
    # push_chunk: 8 × (slots + 1000) bytes rounded up to a power of two; what the frame leaves is the set-up's.
    slots = code.co_stacksize + code.co_nlocals + 9
    mapped = CHUNK
    while mapped < 8 * (slots + 1000):
        mapped *= 2
    assert mapped == 2 << 20 and mapped - 8 * slots > 1_000_000
    # The code is the plain trampoline's but for its stack size: nothing else was rewritten.
    assert code.co_varnames == ("fn", "args", "kwargs") and code.co_nlocals == 3


@pytest.mark.parametrize("name, version", [("pypy", (3, 12, 0)), ("cpython", (3, 10, 14))])
def test_on_an_interpreter_that_keeps_no_frames_in_chunks_it_is_a_plain_call(monkeypatch, name, version):
    monkeypatch.setattr(compile_cache, "sys", types.SimpleNamespace(implementation=types.SimpleNamespace(name=name), version_info=version))
    assert compile_cache._make_anchor() is None
    monkeypatch.setattr(compile_cache, "_anchor", compile_cache._make_anchor())

    def fn(a, *, b):
        return sys._getframe(1).f_code, compile_cache._stack.anchored, a + b

    caller, anchored, total = in_one_chunk(fn, 2, b=3)
    assert caller is in_one_chunk.__code__ and not anchored and total == 5


def test_on_this_interpreter_there_is_a_frame():
    assert (sys.implementation.name, sys.version_info >= (3, 11)) == ("cpython", True)  # the installation's: CPython 3.12
    assert compile_cache._make_anchor() is not None


# --- (b) what it is for -----------------------------------------------------------------------------------------------


def test_the_depths_at_which_a_loop_thrashes_on_this_host_do_not_thrash_below_the_frame():
    p = probe()
    depths, calls = 300, 20_000

    def best_of_three(sweep):
        return [min(three) for three in zip(*(sweep() for _ in range(3)))]

    plain = best_of_three(lambda: p.sweep(depths, calls))
    best = min(plain)
    slow = [k for k, s in enumerate(plain) if s > 20 * best]
    if not slow:
        pytest.skip(f"no depth of {depths} is 20x slower than the best here: this host's chunks cost a call nothing (worst {max(plain) / best:.1f}x)")
    # (The best of seven: a loaded host can stretch a millisecond's loop, it cannot shorten one.)
    below = {k: min(in_one_chunk(p.under, k, calls) for _ in range(7)) for k in slow}
    assert all(below[k] < 5 * best for k in slow), [(k, round(plain[k] / best, 1), round(below[k] / best, 1)) for k in slow]


# --- (c) what the build log says ------------------------------------------------------------------------------------


def test_an_entry_says_whether_it_was_built_below_the_frame_on_its_own_thread():
    log = BuildLog()

    def build(name):
        log.on_duration(BACKEND_EVENT, 0.01, fun_name=name)
        return compile_cache._stack.anchored

    assert build("jit(outside)") is False
    assert in_one_chunk(build, "jit(inside)") is True
    assert in_one_chunk(in_one_chunk, build, "jit(nested)") is True and not compile_cache._stack.anchored
    # Another thread's stack is its own: what it builds while this one is below the frame is not.
    other = threading.Thread(target=build, args=("jit(other_thread)",))
    in_one_chunk(lambda: (other.start(), other.join(timeout=10)))
    assert not other.is_alive()
    assert [(e.fun_name, e.in_one_chunk) for e in log.entries] == [
        ("jit(outside)", False), ("jit(inside)", True), ("jit(nested)", True), ("jit(other_thread)", False)]
    assert [e.brief()["in_one_chunk"] for e in log.entries] == [False, True, True, False]
    s = log.summary()
    assert (s["executables"], s["in_one_chunk"]) == (4, 2)
