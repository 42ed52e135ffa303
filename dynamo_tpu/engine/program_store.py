"""The program store: a set-up's step programs as exported modules on disk,
so that a warm set-up neither traces nor lowers what this checkout has
lowered before.

JAX's persistent cache (``compile_cache.py``) holds executables by the hash of
their HLO: to find one, a process first traces and lowers the program in
Python, 0.1-0.7 s a step program and 10-16% of a warm set-up. The store
holds the lowered program itself (``jax.export``: StableHLO and the calling
convention, 20-100 KB a program) under a digest of everything the tracing
would have read:

- the build key (kind and key tuple of the open ``build.key`` scope or
  ``sched.launch``), the function's name and what it closes over beside the
  configuration (``closure``: a mixed step's chunk and table widths, a
  window's rung, the draft model's configuration);
- the abstract values and shardings of its arguments, their tree, the donated
  positions and the static arguments' values;
- the engine's ``context`` (the fields of ``ModelConfig`` and
  ``SchedulerConfig``, the compute dtype, the resolved attention paths);
- the versions of ``jax``, ``jaxlib`` and the backend (``platform_version``),
  the device kind, and the bytes of every ``.py`` file of this package: one
  changed source line anywhere is another *generation* of the store, a
  directory of its own. A stale program is a wrong answer; a missed one costs
  what a set-up cost before there was a store.

``StoredJit`` stands where a ``jax.jit`` object stood. The first call at an
argument signature resolves a program: on a hit it deserialises the module; on
a miss it traces, lowers and exports as ``jax.jit`` would have, writes the
bytes (a temporary file beside the target and ``os.replace``) and deserialises
those same bytes. Either way the callable it keeps is ``jax.jit(call, donate_argnums=...)``
around ``Exported.call`` under the function's own name: its trace is one
primitive, its lowering embeds the stored module, so a checkout has ONE HLO a
key, cold or warm, and the warm run's load from the persistent cache hits.
Every later call is that ``jax.jit`` object's (the C++ dispatch path) behind
one dictionary lookup.

Today's path stays for what cannot be exported, decided by what the code
sees and never by a setting: no store is opened where the persistent cache
is off or the mesh holds more than one device (``open_store``), and a key
whose export or serialisation raises keeps the plain ``jax.jit`` object.
A file that is truncated or not ours fails its checksum and is a miss,
rewritten. The build log's entries say which it was (``store``).

What a stored program fixes that tracing would have decided afresh: nothing
the scheduler keeps on the host is learned at trace time (``record_exec``
and ``_note_step`` work from the key), and the one reading of the device
inside a traced body, ``llama._hoist_gather_budget`` (free memory, on the
gather path's windows), is the first run's: the same configuration, pool
and weights leave the same memory free at warm-up. Its environment override
is part of the context.
"""

from __future__ import annotations

import functools
import hashlib
import logging
import os
import shutil
from typing import Any, Callable, Optional, Sequence

import jax

from dynamo_tpu.engine.compile_cache import BUILD_LOG, program_store_dir
from dynamo_tpu.engine.kv_cache import QuantKv, SlotKv
from dynamo_tpu.engine.quant import QuantW

logger = logging.getLogger(__name__)

MAGIC = b"dynamo-tpu program 1\n"  # then the payload's sha256 (32 bytes), then the payload
SUFFIX = ".jaxexport"
# Generations kept beside the newest: a parent and a change alternated in one
# checkout path each keep theirs; older sources' programs go when a new
# generation is first written.
GENERATIONS = 4

# The NamedTuples that ride the step programs' arguments and results: an
# exported module's trees are serialised by these names.
for _node in (QuantKv, SlotKv, QuantW):
    jax.export.register_namedtuple_serialization(_node, serialized_name=f"dynamo_tpu.{_node.__name__}")


@functools.cache
def source_digest() -> str:
    """sha256 over every ``.py`` file of the ``dynamo_tpu`` package (relative
    path and bytes, in sorted order): read once a process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for folder, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                h.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as f:
                    h.update(f.read() + b"\0")
    return h.hexdigest()


def _hex(*parts: Any) -> str:
    return hashlib.sha256("\x1f".join(map(str, parts)).encode()).hexdigest()


def open_store(context: str, mesh=None) -> Optional["ProgramStore"]:
    """The store of an engine whose programs read ``context``, or None where
    they take today's path: the persistent cache is off, or the mesh holds
    more than one device (an exported module names one device's layout)."""
    root = program_store_dir()
    if root is None or (mesh is not None and mesh.size > 1):
        return None
    return ProgramStore(root, context)


class ProgramStore:
    """One generation's directory under ``root`` and the context its digests
    carry. ``source`` stands in for ``source_digest()`` in tests."""

    def __init__(self, root: str, context: str, *, source: Optional[str] = None) -> None:
        import jaxlib

        device = jax.devices()[0]
        self.root = root
        self.context = context
        self.generation = _hex(
            source or source_digest(), jax.__version__, jaxlib.__version__, device.platform, device.client.platform_version,
            device.device_kind, jax.config.jax_enable_x64, jax.config.jax_default_matmul_precision,
            jax.config.jax_default_prng_impl, jax.config.jax_threefry_partitionable,
        )
        self.dir = os.path.join(root, self.generation[:16])

    def path(self, kind: str, name: str, digest: str) -> str:
        return os.path.join(self.dir, f"{kind}-{name}-{digest[:32]}{SUFFIX}")

    def read(self, path: str) -> Optional[jax.export.Exported]:
        """The module at ``path``; None where there is none, or the bytes are
        not what ``write`` wrote (truncated, garbage, another format)."""
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        head = len(MAGIC) + hashlib.sha256().digest_size
        payload = blob[head:]
        if blob[:len(MAGIC)] != MAGIC or blob[len(MAGIC):head] != hashlib.sha256(payload).digest():
            return None
        return jax.export.deserialize(bytearray(payload))

    def write(self, path: str, payload: bytes) -> None:
        if not os.path.isdir(self.dir):
            os.makedirs(self.dir, exist_ok=True)
            self._drop_old_generations()
        tmp = f"{path}.{os.getpid()}.tmp"  # two processes of one checkout write the same bytes, each through a file of its own
        with open(tmp, "wb") as f:
            f.write(MAGIC + hashlib.sha256(payload).digest() + payload)
        os.replace(tmp, path)

    def _drop_old_generations(self) -> None:
        """Keep this generation and the ``GENERATIONS`` most recently written
        others: nothing reads an older source's programs again."""
        others = [e for e in os.scandir(self.root) if e.is_dir() and e.path != self.dir]
        others.sort(key=lambda e: e.stat().st_mtime, reverse=True)
        for old in others[GENERATIONS:]:
            shutil.rmtree(old.path, ignore_errors=True)


def _aval(x: Any) -> str:
    return f"{jax.typeof(x)}@{getattr(x, 'sharding', None)}"


class StoredJit:
    """``jax.jit(fun, donate_argnums=..., static_argnums=...)`` whose programs
    come from ``store``: called, or lowered, with positional arguments."""

    def __init__(self, store: ProgramStore, fun: Callable, *, donate_argnums: Sequence[int] = (),
                 static_argnums: Sequence[int] = (), closure: Any = ()) -> None:
        self.store = store
        self.jit = jax.jit(fun, donate_argnums=donate_argnums, static_argnums=static_argnums)  # today's path
        self.__name__ = fun.__name__
        self._donate, self._static, self._closure = tuple(donate_argnums), tuple(static_argnums), closure
        self._programs: dict = {}  # an argument signature -> the jax.jit object that serves it

    def __call__(self, *args: Any) -> Any:
        # What tells one program of this function from another: its static
        # values and the shapes and dtypes of its array arguments (a tree of
        # arrays, the weights or a slotted pool, is the same at every call).
        static = self._static
        sig = tuple([a if i in static else ((a.shape, a.dtype) if hasattr(a, "dtype") else None) for i, a in enumerate(args)])
        program = self._programs.get(sig)
        if program is None:
            program = self._programs[sig] = self._resolve(args)
        return program(*args)

    def lower(self, *args: Any) -> jax.stages.Lowered:
        """The lowering a call would compile (``Scheduler._calibrate_cost_model``
        reads its cost). Not kept: the call that follows resolves again, so
        that its entry of the build log says where its module came from."""
        return self._resolve(args).lower(*args)

    def _resolve(self, args: tuple) -> Callable:
        kind, key, _ = BUILD_LOG.where()
        try:
            dynamic = [i for i in range(len(args)) if i not in self._static]
            leaves, tree = jax.tree.flatten([args[i] for i in dynamic])
            digest = _hex(
                self.store.generation, self.store.context, kind, key, self.__name__, self._closure, self._donate,
                [(i, args[i]) for i in self._static], tree, *map(_aval, leaves),
            )
            path = self.store.path(kind, self.__name__, digest)
            exported, store = self.store.read(path), "hit"
            if exported is None:
                store = "miss"
                payload = bytes(jax.export.export(self.jit)(*args).serialize())
                BUILD_LOG.exported(f"jit({self.__name__})")
                self.store.write(path, payload)
                exported = jax.export.deserialize(bytearray(payload))  # the bytes a warm run will read: one module a key, cold or warm
        except Exception as e:  # noqa: BLE001: whatever jax.export refuses takes today's path
            logger.warning("program store: %s %s of %s is not stored (%s: %s)", kind, key, self.__name__, type(e).__name__, e)
            BUILD_LOG.stored(None)
            return self.jit

        def call(*a):
            return exported.call(*[a[i] for i in dynamic])

        call.__name__ = call.__qualname__ = self.__name__  # the executable's name, and so the profiler's: jit_<name>
        BUILD_LOG.stored(store)
        return jax.jit(call, donate_argnums=self._donate, static_argnums=self._static)
