"""Where the expert GEMMs read their weights (PERF.md §6, PR 29), held on the
step programs' jaxprs: for a MoE FFN under the grouped-GEMM dispatch the
``rhs`` of every grouped GEMM is the whole stored stack viewed ``[L*E, D, F]``
(``llama._split_expert_stacks``), with ``L*E`` group sizes. No layer scan
takes an ``[L, E, ...]`` stack as ``xs`` and no equation produces one layer's
``[E, D, F]``: XLA:TPU fuses neither a dynamic nor a static slice of the stack
into ``ragged-dot`` — either is a copy of 0.94 GB a matrix at Mixtral's widths
(12.8 ms a layer against 4.4: ``tools/moe_gemm_bench.py``). The CPU compiles
other code, so this guards the operands; ``tests/test_tpu_compile.py`` guards
the temporaries of the compiled programs and a chip trace guards the time."""

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import llama
from tests.test_kv_layout import _sub_jaxprs

# Three layers and F != D: no other array of a step has a stack's shapes.
MOE = get_config("tiny-moe").replace(num_layers=3, intermediate_size=96)
L, E, D, F = MOE.num_layers, MOE.num_experts, MOE.hidden_size, MOE.intermediate_size
ONE_LAYER = {(E, D, F), (E, F, D), (1, E, D, F), (1, E, F, D)}
i32 = jnp.int32

TAB = jnp.array([1, 2, 0, 0], i32)
TABS = jnp.array([[3, 4, 0, 0], [5, 0, 0, 0], [0, 0, 0, 0]], i32)
D_TOK, D_POS, D_ACT = jnp.array([5, 6, 0], i32), jnp.array([20, 7, 0], i32), jnp.array([True, True, False])
CHUNK = jnp.arange(1, 17, dtype=i32)

PROGRAMS = {
    "prefill": lambda p, c, k, v: llama.prefill(p, c, k, v, CHUNK, i32(12), i32(16), TAB),
    "mixed_step": lambda p, c, k, v: llama.mixed_step(p, c, k, v, CHUNK, i32(12), i32(16), TAB, D_TOK, D_POS, TABS, D_ACT),
    "decode_multi": lambda p, c, k, v: llama.decode_multi(
        p, c, k, v, D_TOK, D_POS, TABS, D_ACT, jnp.zeros((3,)), jnp.zeros((3,), i32), jnp.ones((3,)), jax.random.PRNGKey(0), 4
    ),
    "chunk_decode": lambda p, c, k, v: llama.chunk_decode(p, c, k, v, jnp.ones((3, 4), i32), D_POS, jnp.array([4, 2, 0], i32), TABS),
    "decode": lambda p, c, k, v: llama.decode(p, c, k, v, D_TOK, D_POS, TABS, D_ACT),
}


def expert_reads(jaxpr):
    """``(grouped GEMMs, faults)`` under ``jaxpr`` (scan and while bodies,
    branches, nested jits, kernel bodies): a fault is a grouped GEMM whose
    rhs or group sizes are not the whole stack's, a scan that takes an
    ``[L, E, ...]`` stack as ``xs``, or any equation whose result is one
    layer's experts."""
    gemms, faults = 0, []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if "ragged_dot" in name:
            gemms += 1
            rhs, sizes = eqn.invars[1].aval.shape, eqn.invars[2].aval.shape
            if rhs[0] != L * E or sizes != (L * E,):
                faults.append(f"{name}: rhs {rhs}, group sizes {sizes}")
        if name == "scan":
            first_xs = eqn.params["num_consts"] + eqn.params["num_carry"]
            for var in eqn.invars[first_xs:]:
                if var.aval.shape[:2] == (L, E) and var.aval.ndim == 4:
                    faults.append(f"scan xs {var.aval.shape}")
        for var in eqn.outvars:
            if getattr(var.aval, "shape", None) in ONE_LAYER:
                faults.append(f"{name} -> {var.aval.shape}")
        for inner in _sub_jaxprs(eqn):
            g, f = expert_reads(inner)
            gemms, faults = gemms + g, faults + f
    return gemms, faults


def _trace(cfg, program):
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = KvCacheArrays.create(cfg, 23, dtype=jnp.float32)
    return jax.make_jaxpr(lambda p, k, v: PROGRAMS[program](p, cfg, k, v))(params, cache.k, cache.v)


@pytest.mark.parametrize("impl", ["megakernel", "gather"])
@pytest.mark.parametrize("program", PROGRAMS)
def test_expert_gemms_read_the_whole_stack(program, impl):
    jaxpr = _trace(MOE.replace(attention_impl=impl), program)
    gemms, faults = expert_reads(jaxpr.jaxpr)
    assert gemms == 3, "gate, up and down of the one compiled layer body"
    assert faults == []
    # The parameter tree is the stored one: [L, E, D, F] arguments, merged by a reshape.
    shapes = [v.aval.shape for v in jaxpr.jaxpr.invars]
    assert shapes.count((L, E, D, F)) == 2 and shapes.count((L, E, F, D)) == 1


@pytest.mark.parametrize("mode", ["dense", "capacity"])
def test_other_dispatches_keep_the_per_layer_slice(mode):
    """``_moe_dense`` and ``_moe_capacity`` (no cell: debugging and ``ep > 1``)
    still take their layer's experts from the scan, and the walk says so."""
    gemms, faults = expert_reads(_trace(MOE.replace(moe_dispatch=mode), "decode").jaxpr)
    assert gemms == 0 and any(f.startswith("scan xs") for f in faults)


def _stacks():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    wg, wu = (jax.random.normal(k, (L, E, D, F)) for k in ks[:2])
    return wg, wu, jax.random.normal(ks[2], (L, E, F, D)), jnp.ones((8, D)), jnp.array([2, 2, 2, 2], i32)


def _scan_sliced(wg, wu, wd, x, sizes):
    """The form before PR 29: the stacks ride the scan, ``ragged_dot`` on the slice."""

    def body(h, ws):
        g, u, d = ws
        return lax.ragged_dot(lax.ragged_dot(h, g, sizes) * lax.ragged_dot(h, u, sizes), d, sizes), None

    return lax.scan(body, x, (wg, wu, wd))[0]


def _dynamic_sliced(wg, wu, wd, x, sizes):
    def body(h, l):
        return lax.ragged_dot(h, lax.dynamic_index_in_dim(wg, l, keepdims=False), sizes) @ wd[0, 0], None

    return lax.scan(body, x, jnp.arange(L))[0]


def _unrolled(wg, wu, wd, x, sizes):
    for l in range(L):
        x = lax.ragged_dot(lax.ragged_dot(x, wg[l], sizes), wd[l], sizes)
    return x


@pytest.mark.parametrize("form", [_scan_sliced, _dynamic_sliced, _unrolled], ids=lambda f: f.__name__.strip("_"))
def test_the_walk_finds_a_per_layer_copy(form):
    _, faults = expert_reads(jax.make_jaxpr(form)(*_stacks()).jaxpr)
    assert faults
