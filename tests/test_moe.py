"""MoE model tests: routing actually selects experts, sparse dispatch parity
(ragged grouped-GEMM + capacity-factor) vs dense, FLOPs scaling with top-k K
rather than expert count E, paged decode parity, and expert-parallel sharding
on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.sharding import ParallelConfig, build_mesh, kv_cache_spec, shard_params

CFG = get_config("tiny-moe").replace(dtype="float32")


def test_moe_mlp_uses_topk_experts():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (8, CFG.hidden_size), dtype=jnp.float32)
    out = llama._mlp(x, lp, CFG)
    assert out.shape == x.shape

    # Routing must matter: zeroing the top experts' weights changes output.
    lp2 = dict(lp)
    lp2["w_down"] = jnp.zeros_like(lp["w_down"])
    out2 = llama._mlp(x, lp2, CFG)
    assert not np.allclose(np.asarray(out), np.asarray(out2))

    # Combine weights are normalized: uniform expert outputs pass through.
    lp3 = dict(lp)
    lp3["w_gate"] = jnp.broadcast_to(lp["w_gate"][0:1], lp["w_gate"].shape)
    lp3["w_up"] = jnp.broadcast_to(lp["w_up"][0:1], lp["w_up"].shape)
    lp3["w_down"] = jnp.broadcast_to(lp["w_down"][0:1], lp["w_down"].shape)
    ref_single = (jax.nn.silu(x @ lp["w_gate"][0]) * (x @ lp["w_up"][0])) @ lp["w_down"][0]
    out3 = llama._mlp(x, lp3, CFG)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(ref_single), rtol=1e-5, atol=1e-5)


def test_moe_prefill_decode_consistent():
    """Prefill then decode one token ≡ prefill of the extended sequence."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = KvCacheArrays.create(CFG, 16, dtype=jnp.float32)
    table = jnp.array([1, 2, 0, 0], dtype=jnp.int32)
    prompt = list(range(20, 36))

    logits, k, v = llama.prefill(
        params, CFG, cache.k, cache.v, jnp.array(prompt, dtype=jnp.int32), jnp.int32(16), jnp.int32(0), table
    )
    nxt = int(jnp.argmax(logits))

    toks = jnp.array([nxt, 0], dtype=jnp.int32)
    pos = jnp.array([16, 0], dtype=jnp.int32)
    tables = jnp.zeros((2, 4), dtype=jnp.int32).at[0].set(table)
    active = jnp.array([True, False])
    dec_logits, _, _ = llama.decode(params, CFG, k, v, toks, pos, tables, active)

    cache2 = KvCacheArrays.create(CFG, 16, dtype=jnp.float32)
    ext = prompt + [nxt]
    padded = jnp.array(ext + [0] * (32 - len(ext)), dtype=jnp.int32)
    full_logits, _, _ = llama.prefill(
        params, CFG, cache2.k, cache2.v, padded, jnp.int32(len(ext)), jnp.int32(0), table
    )
    np.testing.assert_allclose(np.asarray(dec_logits[0]), np.asarray(full_logits), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("ep,tp", [(2, 1), (4, 2)])
def test_moe_expert_parallel_matches_single_device(ep, tp):
    mesh = build_mesh(ParallelConfig(ep=ep, tp=tp))
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = KvCacheArrays.create(CFG, 16, dtype=jnp.float32)
    table = jnp.array([1, 2, 0, 0], dtype=jnp.int32)
    tokens = jnp.arange(10, 26, dtype=jnp.int32)

    ref_logits, _, _ = llama.prefill(
        params, CFG, cache.k, cache.v, tokens, jnp.int32(16), jnp.int32(0), table
    )

    sp = shard_params(params, mesh, CFG.tie_word_embeddings, CFG.num_experts)
    cache_sharding = NamedSharding(mesh, kv_cache_spec(CFG.num_kv_heads, tp))
    k_sh = jax.device_put(jnp.zeros_like(cache.k), cache_sharding)
    v_sh = jax.device_put(jnp.zeros_like(cache.v), cache_sharding)
    logits, _, _ = jax.jit(
        lambda p, k, v: llama.prefill(p, CFG, k, v, tokens, jnp.int32(16), jnp.int32(0), table)
    )(sp, k_sh, v_sh)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-4)


def _mk_moe_inputs(E, K, T=16, D=32, F=48, seed=0, dtype=jnp.float32):
    cfg = CFG.replace(num_experts=E, num_experts_per_tok=K, hidden_size=D, intermediate_size=F)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    lp = {
        "router": jax.random.normal(keys[0], (D, E), dtype=dtype) * 0.5,
        "w_gate": jax.random.normal(keys[1], (E, D, F), dtype=dtype) * D**-0.5,
        "w_up": jax.random.normal(keys[2], (E, D, F), dtype=dtype) * D**-0.5,
        "w_down": jax.random.normal(keys[3], (E, F, D), dtype=dtype) * F**-0.5,
    }
    x = jax.random.normal(keys[4], (T, D), dtype=dtype)
    return cfg, lp, x


@pytest.mark.parametrize("E,K", [(4, 2), (8, 3)])
def test_moe_ragged_matches_dense(E, K):
    cfg, lp, x = _mk_moe_inputs(E, K)
    ref = llama._moe_dense(x, lp, cfg)
    out = llama._moe_ragged(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_moe_ragged_matches_dense_bf16():
    cfg, lp, x = _mk_moe_inputs(8, 2, dtype=jnp.bfloat16)
    ref = llama._moe_dense(x, lp, cfg)
    out = llama._moe_ragged(x, lp, cfg)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32), rtol=0.1, atol=0.05
    )


CFG3 = CFG.replace(num_layers=3)  # three layers: a layer index can be wrong in two ways
T3 = 12


def _stacked(seed=0):
    """A 3-layer ``tiny-moe`` tree as the step programs take it (``[L, E, D, F]``
    stacks), what a layer scan makes of it, and rows to feed a layer."""
    layers = llama.init_params(CFG3, jax.random.PRNGKey(seed), dtype=jnp.float32)["layers"]
    scanned, experts = llama._split_expert_stacks(CFG3, layers)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T3, CFG3.hidden_size), dtype=jnp.float32)
    return layers, scanned, experts, x


def _layer(tree, l):
    return {k: v[l] for k, v in tree.items()}


VALID3 = jnp.arange(T3) % 3 != 1  # masked rows among live ones, the first row live


def test_split_expert_stacks_views_the_stored_tree():
    layers, scanned, experts, _ = _stacked()
    L, E = CFG3.num_layers, CFG3.num_experts
    assert set(experts) == {"w_gate", "w_up", "w_down"} and not set(experts) & set(scanned)
    assert set(scanned) | set(experts) == set(layers)
    for k, w in experts.items():
        assert w.shape == (L * E,) + layers[k].shape[2:]
        np.testing.assert_array_equal(np.asarray(w[1 * E + 2]), np.asarray(layers[k][1, 2]))
    # A dense FFN and a dispatch that keeps the per-layer slice come back as
    # they went in: the scan slices them as before.
    dense = llama.init_params(get_config("tiny"), jax.random.PRNGKey(0), dtype=jnp.float32)["layers"]
    assert llama._split_expert_stacks(get_config("tiny"), dense) == (dense, None)
    for mode in ("dense", "capacity"):
        assert llama._split_expert_stacks(CFG3.replace(moe_dispatch=mode), layers) == (layers, None)


@pytest.mark.parametrize("masked", [False, True], ids=["all-live", "masked-rows"])
@pytest.mark.parametrize("l", range(CFG3.num_layers))
def test_moe_stacked_layer_matches_dense(l, masked):
    """Layer ``l``'s grouped GEMMs over the whole ``[L*E, D, F]`` stacks equal
    ``_moe_dense`` on that layer's own weights."""
    layers, scanned, experts, x = _stacked()
    valid = VALID3 if masked else None
    out = llama._mlp(x, _layer(scanned, l), CFG3, valid=valid, experts=experts, layer=jnp.int32(l))
    ref = llama._moe_dense(x, _layer(layers, l), CFG3)
    if masked:
        np.testing.assert_array_equal(np.asarray(out)[~np.asarray(valid)], 0.0)
        ref = jnp.where(valid[:, None], ref, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # The other layers' experts are not what was read: their outputs differ.
    other = llama._moe_dense(x, _layer(layers, (l + 1) % CFG3.num_layers), CFG3)
    assert not np.allclose(np.asarray(out), np.asarray(jnp.where(valid[:, None], other, 0.0) if masked else other), atol=1e-3)


@pytest.mark.parametrize("l", [1, 2])
def test_moe_masked_rows_land_in_their_own_layers_expert_0(l):
    """Masked rows are folded into group ``l*E`` — this layer's expert 0 — and
    not into group 0, which in the stack is LAYER 0's expert 0. Layer 0's
    expert 0 is poisoned with weights at float32's maximum: a row multiplied
    by them overflows, and its weight of 0 then makes it NaN. (NaN weights
    would not tell on the CPU, whose ``ragged_dot`` multiplies every group's
    weights by zeroed rows: 0 x NaN poisons every output whatever the group
    sizes say, while 0 x max is 0.)"""
    _, scanned, experts, x = _stacked()
    poisoned = {k: w.at[0].set(jnp.finfo(jnp.float32).max) for k, w in experts.items()}
    out = llama._mlp(x, _layer(scanned, l), CFG3, valid=VALID3, experts=poisoned, layer=jnp.int32(l))
    assert np.isfinite(np.asarray(out)).all()
    clean = llama._mlp(x, _layer(scanned, l), CFG3, valid=VALID3, experts=experts, layer=jnp.int32(l))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    # The poison does tell where it is read: in layer 0 the masked rows (and
    # the live ones routed there) do reach group 0.
    hit = llama._mlp(x, _layer(scanned, 0), CFG3, valid=VALID3, experts=poisoned, layer=jnp.int32(0))
    assert not np.isfinite(np.asarray(hit)).all()


def test_moe_capacity_matches_dense_when_no_drops():
    # capacity_factor = E/K ⇒ C = T ⇒ no token can overflow.
    E, K = 8, 2
    cfg, lp, x = _mk_moe_inputs(E, K)
    cfg = cfg.replace(moe_capacity_factor=E / K)
    ref = llama._moe_dense(x, lp, cfg)
    out, dropped = llama._moe_capacity(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert int(dropped) == 0


def test_moe_capacity_drops_overflow_to_residual():
    """With capacity 1 slot/expert, overflowing assignments contribute zero
    (the MLP output is the residual-only fallback), and nothing crashes."""
    E, K = 4, 2
    cfg, lp, x = _mk_moe_inputs(E, K, T=16)
    cfg = cfg.replace(moe_capacity_factor=E / (16 * K))  # C = 1
    out, dropped = llama._moe_capacity(x, lp, cfg)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    # Drop counter reports the overflow: 16 tokens * K=2 wanted, 4 slots kept.
    assert int(dropped) == 16 * K - 4
    # Strictly fewer kept assignments than the no-drop run ⇒ smaller norm.
    full, d_full = llama._moe_capacity(x, lp, cfg.replace(moe_capacity_factor=E / K))
    assert int(d_full) == 0
    assert np.linalg.norm(np.asarray(out)) < np.linalg.norm(np.asarray(full))


def test_moe_sparse_flops_scale_with_k_not_e():
    """The VERDICT criterion: per-token expert FLOPs must scale with top-k K,
    not expert count E.

    The ragged path's work is T*K expert-GEMM rows by construction (xs has
    exactly T*K rows whatever E is); on the CPU *test* backend XLA lowers
    ragged_dot as a per-group decomposition whose cost_analysis reports
    E-proportional flops, so the strict E-independence assertion here uses
    shape math + a relative bound vs dense, and the lowering-independent
    einsum assertion lives in test_moe_capacity_flops_scale_with_k_not_e."""

    def flops(fn, *args):
        c = jax.jit(fn).lower(*args).compile().cost_analysis()
        return c["flops"] if isinstance(c, dict) else c[0]["flops"]

    T, D, F, K = 64, 32, 48, 2
    cfg_small, lp_small, x = _mk_moe_inputs(8, K, T=T, D=D, F=F)
    cfg_big, lp_big, _ = _mk_moe_inputs(32, K, T=T, D=D, F=F)

    dense_small = flops(lambda lp, x: llama._moe_dense(x, lp, cfg_small), lp_small, x)
    dense_big = flops(lambda lp, x: llama._moe_dense(x, lp, cfg_big), lp_big, x)

    assert dense_big / dense_small > 3.0, "dense baseline should scale with E"

    # Lowering-independent guarantee: the expert GEMMs consume a row buffer
    # of exactly T*K rows regardless of E — inspect the jaxpr for the
    # ragged_dot operands. (cost_analysis is NOT usable for this on the CPU
    # test backend: its reference decomposition pads every group to the full
    # row range, reporting E-proportional flops; the TPU Mosaic grouped-GEMM
    # kernel computes true ragged row counts.)
    # The same holds whatever the rhs: a lone layer's [E, D, F], or the whole
    # [L*E, D, F] stack of a 3-layer tree read at layer 1 (the step programs'
    # operands since PR 29) — L*E groups, still T*K rows.
    L = 3
    for cfg_i, lp_i in ((cfg_small, lp_small), (cfg_big, lp_big)):
        E = cfg_i.num_experts
        stack = {k: jnp.concatenate([lp_i[k]] * L) for k in ("w_gate", "w_up", "w_down")}
        router = {"router": lp_i["router"]}
        for groups, fn in (
            (E, lambda lp, x: llama._moe_ragged(x, lp, cfg_i)),
            (L * E, lambda st, x: llama._moe_ragged(x, router, cfg_i, experts=st, layer=jnp.int32(1))),
        ):
            jaxpr = jax.make_jaxpr(fn)(lp_i if groups == E else stack, x)
            ragged_eqns = [e for e in jaxpr.jaxpr.eqns if "ragged" in e.primitive.name]
            assert len(ragged_eqns) == 3, "expected 3 grouped GEMMs (gate/up/down)"
            for e in ragged_eqns:
                assert e.invars[0].aval.shape[0] == T * K, (
                    f"expert GEMM rows must be T*K={T * K}, got {e.invars[0].aval.shape[0]}"
                )
                assert e.invars[1].aval.shape[0] == groups == e.invars[2].aval.shape[0]


def test_moe_capacity_flops_scale_with_k_not_e():
    def flops(fn, *args):
        c = jax.jit(fn).lower(*args).compile().cost_analysis()
        return c["flops"] if isinstance(c, dict) else c[0]["flops"]

    T, D, F, K = 64, 32, 48, 2
    cfg_small, lp_small, x = _mk_moe_inputs(8, K, T=T, D=D, F=F)
    cfg_big, lp_big, _ = _mk_moe_inputs(32, K, T=T, D=D, F=F)
    cap_small = flops(lambda lp, x: llama._moe_capacity(x, lp, cfg_small), lp_small, x)
    cap_big = flops(lambda lp, x: llama._moe_capacity(x, lp, cfg_big), lp_big, x)
    dense_big = flops(lambda lp, x: llama._moe_dense(x, lp, cfg_big), lp_big, x)
    # Expert-GEMM FLOPs are fixed at cf*K*T*D*F; dispatch one-hots add E-
    # proportional but tiny terms. Allow 2x slack, require win over dense.
    assert cap_big / cap_small < 2.0
    assert cap_big < 0.6 * dense_big


@pytest.mark.parametrize("dispatch", ["ragged", "capacity"])
def test_moe_prefill_sparse_matches_dense_e2e(dispatch):
    """Full prefill forward with sparse dispatch ≡ dense dispatch."""
    cfg_d = CFG.replace(moe_dispatch="dense")
    cfg_s = CFG.replace(moe_dispatch=dispatch, moe_capacity_factor=CFG.num_experts / CFG.num_experts_per_tok)
    params = llama.init_params(cfg_d, jax.random.PRNGKey(0), dtype=jnp.float32)
    table = jnp.array([1, 2, 0, 0], dtype=jnp.int32)
    tokens = jnp.arange(10, 26, dtype=jnp.int32)

    cache = KvCacheArrays.create(cfg_d, 16, dtype=jnp.float32)
    ref, _, _ = llama.prefill(params, cfg_d, cache.k, cache.v, tokens, jnp.int32(16), jnp.int32(0), table)
    cache2 = KvCacheArrays.create(cfg_s, 16, dtype=jnp.float32)
    out, _, _ = llama.prefill(params, cfg_s, cache2.k, cache2.v, tokens, jnp.int32(16), jnp.int32(0), table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_moe_capacity_expert_parallel_on_mesh():
    """Capacity dispatch under a 4-way ep mesh ≡ dense on one device — the
    wide-EP serving configuration (VERDICT r2 #2)."""
    ep = 4
    mesh = build_mesh(ParallelConfig(ep=ep))
    cfg = CFG.replace(moe_dispatch="capacity",
                      moe_capacity_factor=CFG.num_experts / CFG.num_experts_per_tok)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    table = jnp.array([1, 2, 0, 0], dtype=jnp.int32)
    tokens = jnp.arange(10, 26, dtype=jnp.int32)

    cache = KvCacheArrays.create(cfg, 16, dtype=jnp.float32)
    ref, _, _ = llama.prefill(
        params, cfg.replace(moe_dispatch="dense"), cache.k, cache.v,
        tokens, jnp.int32(16), jnp.int32(0), table,
    )

    sp = shard_params(params, mesh, cfg.tie_word_embeddings, cfg.num_experts)
    cache_sharding = NamedSharding(mesh, kv_cache_spec(cfg.num_kv_heads, 1))
    k_sh = jax.device_put(jnp.zeros_like(cache.k), cache_sharding)
    v_sh = jax.device_put(jnp.zeros_like(cache.v), cache_sharding)
    logits, _, _ = jax.jit(
        lambda p, k, v: llama.prefill(p, cfg, k, v, tokens, jnp.int32(16), jnp.int32(0), table)
    )(sp, k_sh, v_sh)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_moe_capacity_inactive_lanes_cannot_steal_slots():
    """Decode batches carry padded/finished lanes; with capacity dispatch the
    dead lanes (all embedding token 0, identical routing) must not consume
    expert slots ahead of live tokens. The live lane sits at the HIGHEST
    batch index — without the valid mask, identical dead lanes at lower
    indices exhaust C and drop it to residual."""
    E, K, T = 4, 2, 16
    cfg, lp, _ = _mk_moe_inputs(E, K, T=T)
    cfg = cfg.replace(moe_capacity_factor=1.0)  # C = 8: dead lanes could fill it
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    live = jax.random.normal(keys[0], (1, cfg.hidden_size), dtype=jnp.float32)
    dead = jnp.broadcast_to(jax.random.normal(keys[1], (1, cfg.hidden_size)), (T - 1, cfg.hidden_size))
    x = jnp.concatenate([dead, live], axis=0)  # live token last
    valid = jnp.zeros((T,), dtype=bool).at[T - 1].set(True)

    out_masked, dropped = llama._moe_capacity(x, lp, cfg, valid=valid)
    assert int(dropped) == 0  # dead lanes are not live assignments
    # Reference: live token alone (no contention at all).
    ref, _ = llama._moe_capacity(live, lp, cfg.replace(moe_capacity_factor=E / K))
    np.testing.assert_allclose(np.asarray(out_masked[-1]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)
    # And the dead lanes contribute nothing.
    np.testing.assert_allclose(np.asarray(out_masked[:-1]), 0.0, atol=1e-6)


def test_moe_counters_drain_on_direct_read():
    """moe_dropped_total / moe_assignments_total are drained-on-read
    properties: jitted steps stage aux scalars in _pending_aux (no per-step
    host sync), so a direct reader — not just metrics() — must see them
    (regression: stale counters for anyone bypassing metrics())."""
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig

    cfg = CFG.replace(moe_dispatch="capacity")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    sched = Scheduler(cfg, params, SchedulerConfig(num_blocks=16), dtype=jnp.float32)
    assert sched._moe_stats

    sched._pending_aux.append((jnp.int32(3), jnp.int32(40)))
    sched._pending_aux.append((jnp.int32(2), jnp.int32(24)))
    assert sched.moe_dropped_total == 5
    assert sched.moe_assignments_total == 64
    assert not sched._pending_aux  # drained, not double-counted
    assert sched.moe_dropped_total == 5

    m = sched.metrics()
    assert m.moe_dropped_total == 5 and m.moe_assignments_total == 64
