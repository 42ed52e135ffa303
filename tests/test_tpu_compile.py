"""AOT compiles for a described (not attached) v5e: the rehearsal that costs no
chip time. The TPU compiler installed with JAX compiles the main path's
kernels and whole step programs at Llama-3.2-1B widths for a ``v5e:2x2``
topology — what it refuses here it would refuse on the chip (unaligned
slices, VMEM, a Mosaic kernel that cannot be partitioned). Nothing runs: a
pass here is not a chip run and is never reported as one.

Rules this file keeps (``on-chip-measurement`` guide, section 2): the
topology is described inside a module-scoped fixture — never at import, never
in conftest, not autouse — because only one process may load libtpu; every
compile happens in the test's own process; all such tests live in this one
file. ``llama._on_tpu`` is steered by monkeypatch here, not by an option of
the program.
"""

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import QuantKv
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.quant import QUANT_KEYS, QuantW
from dynamo_tpu.engine.sharding import bind_mesh, kv_cache_spec, param_specs

CFG = get_config("llama-3.2-1b")  # 16 layers, hidden 2048, 32/8 heads, HD 64, vocab 128256
H, KVH, HD, BS = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim, CFG.block_size
NUM_BLOCKS = 512  # run.py's default --num-blocks
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described device's executable is written to the persistent cache but
    # cannot be read back without a chip: keep the cache off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    import numpy as np

    return Mesh(np.array(topo.devices).reshape(1, 1, 1, 1, 4), ("dp", "pp", "sp", "ep", "tp"))


@pytest.fixture
def on_tpu(monkeypatch):
    """The program's one backend question answers "tpu": kernels compile
    (never interpret) and "auto" resolves as it would on the chip."""
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)


def _cell_file(cell):
    """``benchmark/configs/<cell>.json`` as the harness reads it."""
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs", cell + ".json")) as f:
        return json.load(f)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pages(sh, quant=False, n=16 * NUM_BLOCKS, kvh=KVH, hd=HD, bs=BS):
    if quant:
        return QuantKv(_sds((n, bs, kvh * hd), jnp.int8, sh), _sds((n, bs, kvh), jnp.float32, sh))
    return _sds((n, bs, kvh * hd), BF16, sh)


def _model_args(param_sh, cache_sh):
    """(params, k_cache, v_cache) as shapes: ``param_sh`` maps a param's path
    spec to a sharding, the cache takes ``cache_sh``."""
    shapes = jax.eval_shape(lambda: llama.init_params(CFG, jax.random.PRNGKey(0), dtype=BF16))
    params = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh), shapes, param_sh(shapes))
    cache = _sds((CFG.num_layers, NUM_BLOCKS, BS, KVH * HD), BF16, cache_sh)
    return params, cache, cache


def _one_chip_args(one_chip):
    return _model_args(lambda shapes: jax.tree.map(lambda _: one_chip, shapes), one_chip)


# --- kernels ----------------------------------------------------------------


WIDTHS = {  # H, KVH, HD, BS
    "1b": (H, KVH, HD, BS),
    "cell": (32, 8, 128, 128),  # the benchmark's llama cells: Mistral-7B's and Mixtral's heads, pages of 128
    "zaya": (8, 2, 128, 128),  # ZAYA1's compressed latent: pages of 128 tokens by 256 lanes, 4 of them a rows step
}


@pytest.mark.parametrize(
    "widths,nq,ck,rows,width,quant,chunk",
    [("1b", 8, 8, 8, 64, False, False), ("1b", 520, 520, 9, 64, False, False), ("1b", 8, 8, 8, 64, True, False),
     ("1b", 512, 512, 1, 32, False, True),
     # What a mixed step of the cells launches a layer: the chunk by tiles over its one row of 16 slots,
     # the 32 decode rows a query a grid row (33 rows x 16 slots, 288 queries in one launch before PR 31).
     ("cell", 256, 256, 1, 16, False, True), ("cell", 256, 256, 1, 16, True, True),
     ("cell", 32, 32, 32, 16, False, False), ("cell", 32, 32, 32, 16, True, False),
     ("cell", 288, 288, 33, 16, False, False), ("cell", 2048, 2048, 1, 16, False, True),
     # zaya1-8b-d20.reason's rows: a bucket of 64 under its widest table, a window's 9 fresh keys a row.
     ("zaya", 64, 64, 64, 24, False, False), ("zaya", 64, 576, 64, 24, False, False), ("zaya", 64, 64, 64, 24, True, False)],
    ids=["decode-b8", "mixed-chunk512+b8", "decode-b8-int8kv", "chunk512-tiles",
         "cell-chunk256-tiles", "cell-chunk256-tiles-int8kv", "cell-rows32", "cell-rows32-int8kv",
         "cell-walk288", "cell-chunk2048-tiles", "zaya-rows64", "zaya-rows64-window8", "zaya-rows64-int8kv"],
)
def test_ragged_paged_attention_compiles(one_chip, widths, nq, ck, rows, width, quant, chunk):
    """The kernel at the widths and shapes the programs launch it with: a VMEM
    overflow or a slice Mosaic refuses shows here, before a chip run."""
    from dynamo_tpu.engine.attention.megakernel import chunk_tile, ragged_paged_attention

    h, kvh, hd, bs = WIDTHS[widths]
    i32 = jnp.int32
    tile = chunk_tile(nq, h, kvh, hd, bs, kv_bytes=1 if quant else 2) if chunk else 1
    pages = _pages(one_chip, quant, kvh=kvh, hd=hd, bs=bs)
    compiled = ragged_paged_attention.lower(
        _sds((nq, h, hd), BF16, one_chip),
        _sds((ck, kvh, hd), BF16, one_chip), _sds((ck, kvh, hd), BF16, one_chip),
        pages, pages,
        _sds((rows, width), i32, one_chip), _sds((5, nq), i32, one_chip),
        num_kv_heads=kvh, block_size=bs, tile=tile, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert (tile > 1) == chunk
    if not chunk and nq == rows:
        # Length-1 rows: pages a step by the page's bytes, 2 P page operands of the launch (4 P over int8 pages).
        from dynamo_tpu.engine.attention.megakernel import pages_per_step

        p = pages_per_step(bs, kvh * hd, 1 if quant else 2, width)
        assert p == {"1b": 8, "cell": 2 if quant else 1, "zaya": 8 if quant else 4}[widths]


@pytest.mark.parametrize("T", [512, 2048])
def test_flash_chunk_attention_compiles(one_chip, T):
    from dynamo_tpu.engine.attention.prefill import flash_chunk_attention

    compiled = flash_chunk_attention.lower(
        _sds((T, H, HD), BF16, one_chip),
        _sds((T, KVH, HD), BF16, one_chip), _sds((T, KVH, HD), BF16, one_chip),
        _sds((), jnp.int32, one_chip),
        num_kv_heads=KVH, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# --- whole step programs of the 16-layer 1B on one chip -----------------------

B, W = 8, 64  # decode batch bucket, block-table width (1024 tokens)


def _decode_io(sh):
    i32 = jnp.int32
    return dict(
        tokens=_sds((B,), i32, sh), positions=_sds((B,), i32, sh),
        tables=_sds((B, W), i32, sh), active=_sds((B,), jnp.bool_, sh),
        temps=_sds((B,), jnp.float32, sh), top_ks=_sds((B,), i32, sh),
        top_ps=_sds((B,), jnp.float32, sh), key=_sds((2,), jnp.uint32, sh),
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is in, not a fallback
    return compiled


def test_decode_step_compiles(one_chip, on_tpu):
    p, k, v = _one_chip_args(one_chip)
    io = _decode_io(one_chip)
    assert llama.resolve_attention_impl(CFG, k) == "megakernel"
    _compile(
        lambda p, k, v, t, pos, bt, act: llama.decode(p, CFG, k, v, t, pos, bt, act),
        p, k, v, io["tokens"], io["positions"], io["tables"], io["active"],
    )


@pytest.mark.parametrize("attention_impl", ["auto", "gather"], ids=["megakernel-rows", "flash-kernel"])
def test_prefill_step_compiles(one_chip, on_tpu, attention_impl):
    """T 512 fresh prefill as the scheduler jits it (``use_flash=True``): under
    the megakernel the chunk is one ragged row of that kernel; with the gather
    the chunk runs the flash kernel."""
    cfg = CFG.replace(attention_impl=attention_impl)
    assert llama.resolve_prefill_impl(cfg) == "flash"
    p, k, v = _one_chip_args(one_chip)
    i32 = jnp.int32
    _compile(
        lambda p, k, v, t, vl, cl, bt: llama.prefill(
            p, cfg, k, v, t, vl, cl, bt, use_flash=True, has_prefix=False
        ),
        p, k, v, _sds((512,), i32, one_chip), _sds((), i32, one_chip),
        _sds((), i32, one_chip), _sds((32,), i32, one_chip),
    )


def test_mixed_step_compiles(one_chip, on_tpu):
    p, k, v = _one_chip_args(one_chip)
    io = _decode_io(one_chip)
    i32 = jnp.int32
    _compile(
        lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact: llama.mixed_step(
            p, CFG, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact,
            use_flash=True, has_prefix=False,
        ),
        p, k, v, _sds((512,), i32, one_chip), _sds((), i32, one_chip),
        _sds((), i32, one_chip), _sds((32,), i32, one_chip),
        io["tokens"], io["positions"], io["tables"], io["active"],
    )


def test_decode_multi_window32_compiles(one_chip, on_tpu):
    p, k, v = _one_chip_args(one_chip)
    io = _decode_io(one_chip)
    _compile(
        lambda p, k, v, t, pos, bt, act, te, tk, tp, key: llama.decode_multi(
            p, CFG, k, v, t, pos, bt, act, te, tk, tp, key, 32
        ),
        p, k, v, io["tokens"], io["positions"], io["tables"], io["active"],
        io["temps"], io["top_ks"], io["top_ps"], io["key"],
    )


# --- attention_kind "eva" at EvaByte's widths (32 KV heads of 128: 4096-lane pages), 4 layers ------


EVA = get_config("tiny-eva").replace(
    name="eva-wide", hidden_size=4096, num_layers=4, num_heads=32, num_kv_heads=32, head_dim=128,
    intermediate_size=11008, max_seq_len=10240, block_size=128, window_size=2048, chunk_size=16, num_pred_heads=8,
    attention_impl="paged",
)
EVA_BLOCKS = 256  # a pool of 1.07 GB: a copy of it, or of half of it, stands out among the temporaries


def _llama_step_jit(cfg, params, program, sh, blocks, S, Bd, Wd):
    """``mixed_step`` (a chunk of ``S`` with a cached prefix beside ``Bd`` decode
    rows) or ``decode_multi_w8`` of ``llama.py`` as the scheduler jits them:
    ``(jitted, its arguments as shapes on the described chip)``."""
    i32 = jnp.int32
    k = v = _sds((cfg.num_layers, blocks, cfg.block_size, cfg.kv_size), BF16, sh)
    if program == "mixed_step":
        fn = lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact: llama.mixed_step(  # noqa: E731
            p, cfg, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, use_flash=True, has_prefix=True)
        args = (_sds((S,), i32, sh), _sds((), i32, sh), _sds((), i32, sh), _sds((Wd,), i32, sh), _sds((Bd,), i32, sh),
                _sds((Bd,), i32, sh), _sds((Bd, Wd), i32, sh), _sds((Bd,), jnp.bool_, sh))
    else:
        fn = lambda p, k, v, t, pos, bt, act, te, tk, tp, key: llama.decode_multi(  # noqa: E731
            p, cfg, k, v, t, pos, bt, act, te, tk, tp, key, 8)
        args = (_sds((Bd,), i32, sh), _sds((Bd,), i32, sh), _sds((Bd, Wd), i32, sh), _sds((Bd,), jnp.bool_, sh),
                _sds((Bd,), jnp.float32, sh), _sds((Bd,), i32, sh), _sds((Bd,), jnp.float32, sh), _sds((2,), jnp.uint32, sh))
    return jax.jit(fn, donate_argnums=(1, 2)), (params, k, v, *args)


def _llama_step_program(*a):
    """``_llama_step_jit``'s program compiled for the described chip."""
    jitted, args = _llama_step_jit(*a)
    return jitted.lower(*args).compile()


def _param_shapes(cfg, sh, int8=False):
    """The params tree as shapes; ``int8``: as ``quant.quantize_params`` leaves it."""
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, sh),
                          jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0), dtype=BF16)))
    if int8:
        for key in QUANT_KEYS:
            w = params["layers"][key]
            params["layers"][key] = QuantW(_sds(w.shape, jnp.int8, sh), _sds((*w.shape[:-2], 1, w.shape[-1]), jnp.float32, sh))
    return params


@functools.cache  # a program that two tests read is compiled once
def _eva_compiled(sh, program):
    """The cell's step programs: bucket 16, tables of 20 blocks."""
    if program != "eva_roll":
        return _llama_step_program(EVA, _param_shapes(EVA, sh), program, sh, EVA_BLOCKS, 256, 16, 20)
    k = v = _sds((EVA.num_layers, EVA_BLOCKS, EVA.block_size, EVA.kv_size), BF16, sh)
    return jax.jit(lambda p, k, v, t, r0: llama.eva_roll(p, EVA, k, v, t, r0), donate_argnums=(1, 2)).lower(
        _param_shapes(EVA, sh), k, v, _sds((llama.eva_roll_blocks(EVA),), jnp.int32, sh), _sds((), jnp.int32, sh)).compile()


@pytest.mark.parametrize("program", ["mixed_step", "decode_multi_w8", "eva_roll"])
def test_eva_step_programs_compile_and_hold_no_copy_of_the_pool(one_chip, on_tpu, program):
    """``attention_impl="paged"``, as the benchmark's configuration sets it for
    4096-lane pages: the mixed step holds the megakernel's tile walk for the
    chunk (``llama.chunk_walks_tiles``: every live page of its prefix fetched
    once for all 256 queries, its own keys the same launch's fresh piece) and
    the paged kernel for the decode rows: no scores of the chunk against its
    table in HBM (until PR 52 XLA wrote them as ``bf16[32,256,2560]``, 42 MB a
    layer, and read them three times, in float32 inside its fusions) and no
    gathered copy of the table (``bf16[20,128,4096]``). No program
    may hold a temporary of the pool's size: XLA:TPU lowers a gather of whole
    4096-lane blocks by slicing the pool in halves (PERF.md section 6, PR 28),
    so one sequence's table is read by dynamic slices (``llama._GATHER_MAX_LANES``)."""
    compiled = _eva_compiled(one_chip, program)
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (program != "eva_roll")
    if program == "mixed_step":
        assert "ragged_paged_attention" in text and "paged_decode_partials" in text and "flash_chunk_attention" not in text
        heads, chunk, width = EVA.num_heads, 256, 20
        assert not re.search(rf"\[{heads},{chunk},(1,)?{width * EVA.block_size}\]", text)
        assert f"bf16[{width},{EVA.block_size},{EVA.kv_size}]" not in text
    pool = EVA.num_layers * EVA_BLOCKS * EVA.block_size * EVA.kv_size * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool // 2


# cell's configuration file -> (attention_impl as resolved on a TPU, query heads, the pool's lanes, a page's rows, the chunk,
# how a chunk meets its keys). The five cells that PR 52's rule bypasses read what they read before it.
CELL_CHUNKS = {
    "mistral-7b-w8": ("megakernel", 32, 1024, 128, 256, "tile256"),
    "mixtral-8x7b-d3": ("megakernel", 32, 1024, 128, 256, "tile256"),
    "evabyte-d16": ("paged", 32, 4096, 128, 256, "tile256"),  # "paged" until PR 52: gather + flash + an XLA prefix piece
    "granite-4.0-h-small-d10-e36": ("megakernel", 32, 1024, 128, 256, "tile256"),
    "zaya1-8b-d20": ("megakernel", 8, 256, 128, 256, "tile256"),
    "dots3-note-prev-d5-e32": ("gather", 128, 192, 1024, 512, "gather"),  # latent: resolved before the rule is read
}


@pytest.mark.parametrize("cell", list(CELL_CHUNKS))
def test_the_cells_chunks_take_the_path_their_shapes_and_their_impl_give(on_tpu, cell):
    """``llama.chunk_walks_tiles`` and ``megakernel.chunk_tile`` at the six
    cells' own configurations, as a TPU resolves them: a chunk walks tiles
    of 256 wherever a kernel serves the pool (``megakernel`` or ``paged``),
    and the decode rows keep the kernel the impl names."""
    from benchmark import families
    from dynamo_tpu.engine.models import get_module

    cfg = _cell_file(cell)
    mc = families.load(cfg["family"]).model_config(cfg, cell)
    chunk = cfg["scheduler"]["max_prefill_chunk"]
    pool = jax.ShapeDtypeStruct((mc.num_layers, 8, mc.block_size, mc.kv_size), BF16)
    impl, heads, lanes, page, want_chunk, path = CELL_CHUNKS[cell]
    assert (llama.resolve_attention_impl(mc, pool), mc.num_heads, mc.kv_size, mc.block_size, chunk) == (impl, heads, lanes, page, want_chunk)
    assert llama.chunk_walks_tiles(mc, pool) == (impl != "gather") == path.startswith("tile")
    assert (llama._use_megakernel(mc, pool), llama._use_paged_decode(mc, pool)) == (impl == "megakernel", impl == "paged")
    assert get_module(mc).chunk_attn_path(mc, pool, chunk, BF16) == path


# --- MoE at Mixtral-8x7B's widths, 3 layers: the expert stacks are read where they lie -----------

MOE = get_config("mixtral-8x7b").replace(name="mixtral-d3", num_layers=3, block_size=128, max_seq_len=2048)


@pytest.mark.parametrize("program", ["mixed_step", "decode_multi_w8"])
def test_moe_step_programs_hold_no_copy_of_an_expert_stack(one_chip, on_tpu, program):
    """One layer of one expert stack is 8 x 4096 x 14336 bf16 = 0.94 GB. A
    layer scan that slices the stacks copies it before ``ragged-dot`` reads
    it (XLA:TPU fuses no slice into that operation): 0.97-1.08 GB of
    temporaries in these programs before PR 29, 0.03-0.14 GB since
    (PERF.md section 6, PR 29)."""
    compiled = _llama_step_program(MOE, _param_shapes(MOE, one_chip), program, one_chip, 512, 256, 32, 16)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text
    one_layer = MOE.num_experts * MOE.hidden_size * MOE.intermediate_size * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer // 4


# --- tp=4 over the described 2x2: the kernels must partition -------------------


def _tp4_args(mesh):
    specs = param_specs(CFG.tie_word_embeddings)
    return _model_args(
        lambda shapes: jax.tree.map(
            lambda _, s: NamedSharding(mesh, s), shapes, specs,
        ),
        NamedSharding(mesh, kv_cache_spec(KVH, 4)),
    )


def _assert_partitioned(compiled, per_device_limit):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the tp=4 program"
    assert "all-reduce" in text, "no tp all-reduce: the program is not partitioned"
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < per_device_limit, mem.argument_size_in_bytes


def test_tp4_decode_step_partitions(tp4, on_tpu):
    """``--tp 4`` on a real host: ``llama.decode`` traced under the engine's
    mesh compiles with the megakernel inside a shard_map (8/2 heads per
    shard) — GSPMD alone refuses a Mosaic kernel."""
    model = bind_mesh(llama, tp4)
    p, k, v = _tp4_args(tp4)
    io = _decode_io(NamedSharding(tp4, P()))
    assert model.resolve_attention_impl(CFG, k) == "megakernel"
    compiled = jax.jit(
        lambda p, k, v, t, pos, bt, act: model.decode(p, CFG, k, v, t, pos, bt, act)
    ).lower(p, k, v, io["tokens"], io["positions"], io["tables"], io["active"]).compile()
    # 2.47 GB of bf16 weights + 0.27 GB cache on one chip → about a quarter each.
    _assert_partitioned(compiled, per_device_limit=1 << 30)


@pytest.mark.parametrize("attention_impl", ["auto", "gather"], ids=["megakernel-rows", "flash-kernel"])
def test_tp4_prefill_step_partitions(tp4, on_tpu, attention_impl):
    """Under the megakernel the chunk is walked by tiles inside the
    ``shard_map``: the tile is taken at a shard's 8/2 heads."""
    cfg = CFG.replace(attention_impl=attention_impl)
    model = bind_mesh(llama, tp4)
    assert model.resolve_prefill_impl(cfg) == "flash"
    p, k, v = _tp4_args(tp4)
    if attention_impl == "auto":
        from dynamo_tpu.engine.attention.megakernel import chunk_tile

        assert model.chunk_attn_path(cfg, k, 512, BF16) == f"tile{chunk_tile(512, H // 4, KVH // 4, HD, BS)}"
    rep = NamedSharding(tp4, P())
    i32 = jnp.int32
    compiled = jax.jit(
        lambda p, k, v, t, vl, cl, bt: model.prefill(
            p, cfg, k, v, t, vl, cl, bt, use_flash=True, has_prefix=False
        )
    ).lower(
        p, k, v, _sds((512,), i32, rep), _sds((), i32, rep), _sds((), i32, rep),
        _sds((32,), i32, rep),
    ).compile()
    _assert_partitioned(compiled, per_device_limit=1 << 30)


# --- layer_types at Granite-4.0-H-Small's widths: the slot kernel and the step programs ----------


@pytest.mark.parametrize("rows,tiles", [(32, 16), (64, 16), (64, 32)], ids=["rows32", "rows64", "rows64-blocks-of-32"])
def test_ssm_update_rows_compiles_in_place(one_chip, rows, tiles):
    """The in-place state update at the published widths (128 heads of 64 with
    a state of 128, stored [64, 128, 128]; the benchmark's 9 x 65 slots):
    Mosaic takes the tiles, the slot array is aliased to the result and
    nothing of its size is a temporary."""
    from dynamo_tpu.engine.models import hybrid

    mc = get_config("tiny-hybrid").replace(hidden_size=4096, mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128)
    Hm, Pm, Nm, slots = 128, 64, 128, 9 * 65
    assert mc.mamba_state_shape == (64, 128, 128) and hybrid._rows_kernel_fits(mc)
    f32 = lambda *s: _sds(s, jnp.float32, one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda ssm, idx, x, b, c, dt, a, d: hybrid.ssm_update_rows(mc, ssm, idx, x, b, c, dt, a, d, tiles=tiles),
        donate_argnums=(0,),
    ).lower(f32(slots, *mc.mamba_state_shape), _sds((rows,), jnp.int32, one_chip), f32(rows, Hm, Pm), f32(rows, Nm), f32(rows, Nm),
            f32(rows, Hm), f32(Hm), f32(Hm)).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.alias_size_in_bytes == slots * Hm * Pm * Nm * 4 and mem.temp_size_in_bytes < 64 << 20


@functools.cache  # as above
def _granite_jit(sh, program):
    """``(jitted, args, params, k, v)``: a step program of the benchmark's
    ``granite-4.0-h-small-d10-e36`` as configured (65 slots, 1,025 blocks, 64
    rows). ``check-``: as the output check calls it (``granite_hybrid.program_logits``:
    the same pool and slots, its bucket and table width, logits returned)."""
    from benchmark import families
    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import hybrid

    cfg = _cell_file("granite-4.0-h-small-d10-e36")
    fam = families.load("granite_hybrid")
    mc = fam.model_config(cfg, "granite")
    sc = cfg["scheduler"]
    place = lambda tree: jax.tree.map(lambda s: _sds(s.shape, s.dtype, sh), tree)  # noqa: E731
    params = place(jax.eval_shape(lambda: fam.make_params(mc, 0)))
    k, v = place(jax.eval_shape(
        lambda: (lambda c: (c.k, c.v))(KvCacheArrays.create(mc, sc["num_blocks"], dtype=BF16, num_slots=sc["max_running"] + 1))))
    B, W = sc["max_running"], 16
    check, program = program.startswith("check-"), program.removeprefix("check-")
    if check:
        B, W = cfg["parity"]["decode_bucket"], 8
    flash = check and hybrid.resolve_prefill_impl(mc) == "flash"
    i32 = lambda *s: _sds(s, jnp.int32, sh)  # noqa: E731
    f32 = lambda *s: _sds(s, jnp.float32, sh)  # noqa: E731
    act = _sds((B,), jnp.bool_, sh)
    if program == "decode_multi":
        jitted = jax.jit(
            lambda p, k, v, t, pos, bt, a, te, tk, tp, key: hybrid.decode_multi(p, mc, k, v, t, pos, bt, a, te, tk, tp, key, 8,
                                                                                return_logits=check),
            donate_argnums=(1, 2),
        )
        args = (params, k, v, i32(B), i32(B), i32(B, W), act, f32(B), i32(B), f32(B), _sds((2,), jnp.uint32, sh))
    elif program == "prefill":  # a chunk with no decode row: the slot array's layout stays pinned (hybrid._mamba_mixer)
        jitted = jax.jit(
            lambda p, k, v, t, vl, cl, bt: hybrid.prefill(p, mc, k, v, t, vl, cl, bt, all_logits=check, has_prefix=not check,
                                                          use_flash=flash),
            donate_argnums=(1, 2),
        )
        args = (params, k, v, i32(256), i32(), i32(), i32(W))
    else:
        jitted = jax.jit(
            lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, da: hybrid.mixed_step(p, mc, k, v, pt, pv, cl, ptab, dt, dpos, dtab, da,
                                                                                    use_flash=flash),
            donate_argnums=(1, 2),
        )
        args = (params, k, v, i32(256), i32(), i32(), i32(W), i32(B), i32(B), i32(B, W), act)
    return jitted, args, params, k, v


@functools.cache  # a program that two tests read is compiled once
def _granite_compiled(sh, program):
    """``(compiled, params, k, v)`` of ``_granite_jit``'s program."""
    jitted, args, params, k, v = _granite_jit(sh, program)
    return jitted.lower(*args).compile(), params, k, v


@pytest.mark.parametrize("program", ["decode_multi", "mixed_step", "prefill", "check-decode_multi", "check-mixed_step", "check-prefill"])
def test_granite_step_programs_compile_and_fit_beside_the_weights(one_chip, on_tpu, program):
    """The groups scan, the slot kernel and the megakernel compile into one
    program whose arguments (9.9 GB of weights, 3.0 GB of pool and slots,
    aliased to the results) and temporaries fit the chip."""
    compiled, _, k, v = _granite_compiled(one_chip, program)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "ssm_update_rows" in text  # the slot kernel and the attention kernel
    state = (k.slots.size * 4 + v.slots.size * 2)
    assert mem.alias_size_in_bytes >= state  # pool and slots are updated in place
    assert 9.9e9 < mem.argument_size_in_bytes - mem.alias_size_in_bytes < 10.0e9  # the weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)


@functools.cache  # as above
def _zaya_jit(sh, program):
    """``(jitted, args, params, k, v)``: a step program of the benchmark's
    ``zaya1-8b-d20`` as configured (65 slots, 1,025 blocks, 64 rows, tables of
    24). ``check-``: as the output check calls it (``zaya.program_logits``: its
    bucket and table width, every position's logits)."""
    from benchmark import families
    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import hybrid

    cfg = _cell_file("zaya1-8b-d20")
    fam = families.load("zaya")
    mc = fam.model_config(cfg, "zaya")
    sc = cfg["scheduler"]
    place = lambda tree: jax.tree.map(lambda s: _sds(s.shape, s.dtype, sh), tree)  # noqa: E731
    params = place(jax.eval_shape(lambda: fam.make_params(mc, 0)))
    k, v = place(jax.eval_shape(
        lambda: (lambda c: (c.k, c.v))(KvCacheArrays.create(mc, sc["num_blocks"], dtype=BF16, num_slots=sc["max_running"] + 1))))
    check, program = program.startswith("check-"), program.removeprefix("check-")
    B, W = (cfg["parity"]["decode_bucket"], 8) if check else (sc["max_running"], 24)
    i32 = lambda *s: _sds(s, jnp.int32, sh)  # noqa: E731
    f32 = lambda *s: _sds(s, jnp.float32, sh)  # noqa: E731
    act = _sds((B,), jnp.bool_, sh)
    if program == "decode_multi":
        jitted = jax.jit(
            lambda p, k, v, t, pos, bt, a, te, tk, tp, key: hybrid.decode_multi(p, mc, k, v, t, pos, bt, a, te, tk, tp, key, 8,
                                                                                return_logits=check),
            donate_argnums=(1, 2),
        )
        args = (params, k, v, i32(B), i32(B), i32(B, W), act, f32(B), i32(B), f32(B), _sds((2,), jnp.uint32, sh))
    elif program == "prefill":
        jitted = jax.jit(
            lambda p, k, v, t, vl, cl, bt: hybrid.prefill(p, mc, k, v, t, vl, cl, bt, all_logits=check),
            donate_argnums=(1, 2),
        )
        args = (params, k, v, i32(256), i32(), i32(), i32(W))
    else:
        jitted = jax.jit(
            lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, da: hybrid.mixed_step(p, mc, k, v, pt, pv, cl, ptab, dt, dpos, dtab, da),
            donate_argnums=(1, 2),
        )
        args = (params, k, v, i32(256), i32(), i32(), i32(W), i32(B), i32(B), i32(B, W), act)
    return jitted, args, params, k, v


@functools.cache  # a program that two tests read is compiled once
def _zaya_compiled(sh, program):
    """``(compiled, params, k, v)`` of ``_zaya_jit``'s program."""
    jitted, args, params, k, v = _zaya_jit(sh, program)
    return jitted.lower(*args).compile(), params, k, v


@pytest.mark.parametrize("program", ["decode_multi", "mixed_step", "check-prefill"])
def test_zaya_step_programs_compile_and_fit_beside_the_weights(one_chip, on_tpu, program):
    """The cca group's scan, the megakernel at 8/2 heads over 256-lane pages and
    the grouped products over 2048 x 2048 experts (tiles of 2048 x 1024) compile
    into one program whose arguments (9.4 GB of weights, 2.7 GB of pool and
    slots, aliased to the results) and temporaries fit the chip; no weight and
    no expert stack is copied or re-laid."""
    compiled, params, k, v = _zaya_compiled(one_chip, program)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("tpu_custom_call") >= 4 and "ragged_paged_attention" in text and "gmm" in text
    assert mem.alias_size_in_bytes >= 2 * k.pool.size * 2 + v.slots.size * 2  # pool and slots are updated in place
    assert 9.3e9 < mem.argument_size_in_bytes - mem.alias_size_in_bytes < 9.5e9  # the weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    shapes = {leaf.shape[1:] for leaf in jax.tree.leaves(params) if leaf.ndim >= 3 and math.prod(leaf.shape[1:]) >= 1 << 20}
    for line in text.splitlines():
        copied = re.search(r" copy\(%p__(layers|cca)__", line)
        for dims, layout in _ARRAY.findall(line):
            dims, layout = tuple(map(int, dims.split(","))), list(map(int, layout.split(",")))
            assert not (copied and math.prod(dims) >= 1 << 20), line.strip()[:160]  # (a layer's two temperatures may move)
            assert not (dims[1:] in shapes and len(dims) > 2 and layout != sorted(layout, reverse=True)), line.strip()[:160]


# --- no step program re-lays a weight ---------------------------------------------------------

M7_D4 = get_config("mistral-7b").replace(name="mistral-7b-d4", num_layers=4, block_size=128, max_seq_len=2048)
_ARRAY = re.compile(r"\b[a-z]+\d+\[([\d,]+)\]\{([\d,]+)")  # an array's dimensions and its layout, minor to major


@functools.cache  # a program that two tests read is compiled once
def _m7_compiled(sh, program):
    """An int8 tree at Mistral-7B's widths, 4 layers: bucket 32, tables of 16."""
    return _llama_step_program(M7_D4, _param_shapes(M7_D4, sh, int8=True), program, sh, 256, 256, 32, 16)


def _re_laid_weights(text, params):
    """The lines of a compiled program that copy a parameter of the layer
    stacks, or that hold an array of a layer weight's shape (the stack's
    ``[L, ...]``, a layer's ``[1, ...]`` or ``[...]``; weights of a Mi
    elements a layer or more, which no activation's shape meets) in another
    layout than the row-major one it is stored in."""
    shapes = set()
    for leaf in jax.tree.leaves(params):
        if leaf.ndim >= 3 and math.prod(leaf.shape[1:]) >= 1 << 20:
            shapes |= {leaf.shape, (1, *leaf.shape[1:]), leaf.shape[1:]}
    assert shapes and re.search(r"%p__(layers|attn)__[\w.]+ = ", text)  # the parameters carry their paths, as the first rule expects
    bad = []
    for line in text.splitlines():
        if re.search(r" copy\(%p__(layers|attn|mamba)__", line):
            bad.append(line.strip()[:160])
        for dims, layout in _ARRAY.findall(line):
            dims, layout = tuple(map(int, dims.split(","))), list(map(int, layout.split(",")))
            if dims in shapes and layout != sorted(layout, reverse=True):
                bad.append(line.strip()[:160])
                break
    return bad


@pytest.mark.parametrize("program", ["mixed_step", "decode_multi_w8"])
@pytest.mark.parametrize("tree", ["eva-bf16", "mistral-7b-int8", "granite"])
def test_no_step_program_re_lays_a_weight(one_chip, on_tpu, tree, program):
    """Every weight is read where it lies, sliced out of its stack inside the
    product that uses it. XLA:TPU wants q and k head-major and, left alone,
    gets them by transposing ``wq`` and ``wk``: both whole stacks once a
    window (``copy.69 = bf16[L,4096,4096]{1,2,0} copy(p.layers.wq)``), a layer's
    slice of each inside a mixed step's scan: ``llama.project_heads`` (PERF.md
    section 6, PR 40). The cells' bf16 tree at EvaByte's widths (bucket 16,
    tables of 20), an int8 tree at Mistral-7B's (bucket 32, tables of 16), and
    the granite programs with their one attention layer in ten."""
    if tree == "granite":
        compiled, params, _, _ = _granite_compiled(one_chip, program.removesuffix("_w8"))
    elif tree == "eva-bf16":
        compiled, params = _eva_compiled(one_chip, program), _param_shapes(EVA, one_chip)
    else:
        compiled, params = _m7_compiled(one_chip, program), _param_shapes(M7_D4, one_chip, int8=True)
    assert not _re_laid_weights(compiled.as_text(), params)


# --- a step program out of the program store compiles to what tracing compiles --------------------


@pytest.mark.parametrize("family", ["mistral-7b-int8", "eva", "granite", "zaya"])
def test_a_stored_step_program_compiles_with_its_kernels_and_its_pools_in_place(one_chip, on_tpu, family, tmp_path):
    """What ``program_store.StoredJit`` does on a miss and a hit, for the
    described chip: export the jitted step, write and read the module through
    the store's own file, and compile ``jit(call, donate)`` around it. Every
    kernel is in the result, the pool (and the slots) alias their results as
    in the direct compile, and a module is well under a megabyte."""
    from dynamo_tpu.engine.program_store import ProgramStore

    if family == "mistral-7b-int8":
        jitted, args = _llama_step_jit(M7_D4, _param_shapes(M7_D4, one_chip, int8=True), "decode_multi_w8", one_chip, 256, 256, 32, 16)
        direct = _m7_compiled(one_chip, "decode_multi_w8")
    elif family == "eva":
        jitted, args = _llama_step_jit(EVA, _param_shapes(EVA, one_chip), "mixed_step", one_chip, EVA_BLOCKS, 256, 16, 20)
        direct = _eva_compiled(one_chip, "mixed_step")
    else:
        jitted, args = (_granite_jit if family == "granite" else _zaya_jit)(one_chip, "decode_multi")[:2]
        direct = (_granite_compiled if family == "granite" else _zaya_compiled)(one_chip, "decode_multi")[0]
    store = ProgramStore(str(tmp_path), "a described v5e")
    path = store.path("decode_multi", family, "0" * 64)
    store.write(path, bytes(jax.export.export(jitted, platforms=["tpu"])(*args).serialize()))
    assert os.path.getsize(path) < 1 << 20
    exported = store.read(path)

    def call(*a):
        return exported.call(*a)

    compiled = jax.jit(call, donate_argnums=(1, 2)).lower(*args).compile()
    kernels = direct.as_text().count("tpu_custom_call")
    assert compiled.as_text().count("tpu_custom_call") == kernels > 0
    assert compiled.memory_analysis().alias_size_in_bytes == direct.memory_analysis().alias_size_in_bytes > 0
