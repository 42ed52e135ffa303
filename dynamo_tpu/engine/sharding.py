"""Device meshes + partition specs for the engine.

The reference delegates TP/PP/EP to engine-internal NCCL (SURVEY.md §2e);
here parallelism is native: a ``jax.sharding.Mesh`` with named axes and
GSPMD-propagated shardings. XLA inserts the collectives (all-reduce after
row-parallel matmuls, etc.) over ICI — no hand-written comm code in the
model.

Axis convention:
- ``dp``   — data parallel (batch) across chips within one engine instance.
- ``pp``   — pipeline parallel: stacked layer axis split into stages
             (microbatched ppermute pipeline; pipeline_parallel.py).
- ``tp``   — tensor parallel: attention heads + MLP hidden dim.
- ``ep``   — expert parallel (MoE models).
- ``sp``   — sequence/context parallel (ring attention, long prefill).

Weight layout (megatron-style column→row pairs so each layer needs exactly
one all-reduce per block):
- wq/wk/wv, w_gate/w_up: shard output dim over tp (column-parallel).
- wo, w_down:            shard input dim over tp (row-parallel).
- KV cache:              shard kv_heads (the merged KVH*HD axis) over tp.
- embed/lm_head:         shard vocab over tp.

The one thing GSPMD cannot partition is a Pallas (Mosaic) kernel: the
attention kernels run under ``jax.shard_map`` over ``tp`` (``over_tp``) —
heads are independent, each shard sees its local KV heads, no collective is
added. The engine's mesh reaches the model's trace through ``bind_mesh``.
"""

from __future__ import annotations

import contextvars
import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ParallelConfig:
    tp: int = 1
    dp: int = 1
    ep: int = 1
    sp: int = 1
    pp: int = 1

    @property
    def total(self) -> int:
        return self.tp * self.dp * self.ep * self.sp * self.pp


def build_mesh(parallel: ParallelConfig, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    n = parallel.total
    if len(devices) < n:
        raise ValueError(f"need {n} devices for {parallel}, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(
        parallel.dp, parallel.pp, parallel.sp, parallel.ep, parallel.tp
    )
    return Mesh(arr, axis_names=("dp", "pp", "sp", "ep", "tp"))


def param_specs(tie_word_embeddings: bool, num_experts: int = 0, pp: bool = False) -> dict:
    """PartitionSpec pytree matching llama.init_params structure (one stack of
    identical layers: ``shard_params`` refuses a tree with mixer stacks).

    MoE: experts shard over ``ep`` and the FFN hidden dim over ``tp`` —
    the wide-EP layout (each chip holds E/ep experts, each split tp-ways).
    With ``pp=True`` the stacked layer axis additionally shards over ``pp``
    (each pipeline stage holds L/pp contiguous layers)."""
    lax_ = "pp" if pp else None  # leading (stacked-layer) axis
    specs = {
        "embed": P("tp", None),
        "final_norm": P(None),
        "layers": {
            "attn_norm": P(lax_, None),
            "mlp_norm": P(lax_, None),
            "wq": P(lax_, None, "tp"),
            "wk": P(lax_, None, "tp"),
            "wv": P(lax_, None, "tp"),
            "wo": P(lax_, "tp", None),
        },
    }
    if num_experts == 0:
        specs["layers"].update(
            w_gate=P(lax_, None, "tp"),
            w_up=P(lax_, None, "tp"),
            w_down=P(lax_, "tp", None),
        )
    else:
        specs["layers"].update(
            router=P(lax_, None, None),
            w_gate=P(lax_, "ep", None, "tp"),
            w_up=P(lax_, "ep", None, "tp"),
            w_down=P(lax_, "ep", "tp", None),
        )
    if not tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def kv_cache_spec(num_kv_heads: int = 0, tp_size: int = 1, pp: bool = False) -> P:
    """[L, N, BS, KVH*HD] (a QuantKv's scales: [L, N, BS, KVH]) — shard the
    merged head axis over tp when the kv heads divide: a contiguous 1/tp
    slice of it is KVH/tp whole heads. When tp > kv_heads (e.g. 70B
    kv_heads=8 on tp=16) the cache replicates and the duplicated-KV-head
    handling lives in the attention partitioning. With ``pp=True`` the layer
    axis shards over pp alongside the layer stack."""
    lax_ = "pp" if pp else None
    if tp_size > 1 and num_kv_heads % tp_size == 0:
        return P(lax_, None, None, "tp")
    return P(lax_, None, None, None)


def shard_params(params, mesh: Mesh, tie_word_embeddings: bool, num_experts: int = 0, pp: bool = False):
    if "mamba" in params:
        raise NotImplementedError("sharded parameters (a mesh) are not built for layer_types: no spec for the mixer stacks")
    specs = param_specs(tie_word_embeddings, num_experts, pp=pp)

    def _put(x, s):
        from dynamo_tpu.engine.quant import QuantW

        if isinstance(x, QuantW):
            # int8 codes take the weight's spec; the per-output-channel
            # scale [..., 1, out] keeps only the spec's LAST axis (its
            # other axes are size-1 or layer-stacked and must not shard a
            # unit dimension).
            s_scale = P(*([None] * (len(s) - 1) + [s[-1]])) if len(s) else s
            return QuantW(
                jax.device_put(x.q, NamedSharding(mesh, s)),
                jax.device_put(x.scale, NamedSharding(mesh, s_scale)),
            )
        return jax.device_put(x, NamedSharding(mesh, s))

    from dynamo_tpu.engine.quant import QuantW

    return jax.tree.map(
        _put, params, specs,
        is_leaf=lambda x: isinstance(x, (jax.Array, QuantW)),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp"))


# --- the engine's mesh, visible to the model while a step is traced ---------

_STEP_MESH: contextvars.ContextVar = contextvars.ContextVar("dynamo_tpu_step_mesh", default=None)


def step_mesh() -> Optional[Mesh]:
    """The mesh of the engine whose model step is being traced (None for a
    one-device engine or a direct model call)."""
    return _STEP_MESH.get()


class _MeshBound:
    """A model module whose functions run — i.e. are traced, inside the
    scheduler's jits — with ``mesh`` as the step mesh."""

    def __init__(self, module, mesh: Mesh):
        self._module = module
        self._mesh = mesh

    def __getattr__(self, name):
        attr = getattr(self._module, name)
        if not callable(attr):
            return attr

        @functools.wraps(attr)
        def call(*args, **kwargs):
            token = _STEP_MESH.set(self._mesh)
            try:
                return attr(*args, **kwargs)
            finally:
                _STEP_MESH.reset(token)

        return call


def bind_mesh(module, mesh: Optional[Mesh]):
    """``module`` itself without a mesh; with one, a view of it whose calls
    see ``step_mesh() == mesh``. Two engines in one process (a tp=4 engine
    beside a one-device one) each trace under their own."""
    return module if mesh is None else _MeshBound(module, mesh)


def tp_size(mesh: Optional[Mesh]) -> int:
    return 1 if mesh is None else int(mesh.shape.get("tp", 1))


def kernel_shards(num_kv_heads: int) -> int:
    """How many ``tp`` shards a per-head Pallas kernel of the step being
    traced splits into: 1 without a tp mesh, ``tp`` when the KV heads divide
    by it, and 0 when they do not — the cache is then replicated
    (``kv_cache_spec``), the kernels cannot partition, and the callers take
    their XLA paths."""
    tp = tp_size(step_mesh())
    if tp == 1:
        return 1
    return tp if num_kv_heads % tp == 0 else 0


# Specs of the attention kernels' operands under ``over_tp``.
HEADS = P(None, "tp", None)  # [rows, heads, HD] — q/k/v rows, outputs, (m, l)
PAGES = P(None, None, "tp")  # [pages, BS, KVH*HD] — and a QuantKv's scales [pages, BS, KVH]


def over_tp(kernel, num_kv_heads: int, in_specs, out_specs, **static):
    """``kernel(*arrays, num_kv_heads=<local count>, **static)`` — a Pallas
    attention call over per-head arrays — partitioned by hand over the step
    mesh's ``tp`` axis: heads are independent, so each shard runs the kernel
    on its local heads and no collective is added. Without a tp mesh it is
    the plain kernel. Axes the specs do not name are replicated."""
    tp = kernel_shards(num_kv_heads)
    if tp == 0:
        raise ValueError(
            f"{num_kv_heads} KV heads do not divide by tp={tp_size(step_mesh())}: "
            "resolve_attention_impl / resolve_prefill_impl choose the XLA paths there"
        )
    fn = functools.partial(kernel, num_kv_heads=num_kv_heads // tp, **static)
    if tp == 1:
        return fn
    return jax.shard_map(
        fn, mesh=step_mesh(), in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
