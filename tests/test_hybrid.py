"""``ModelConfig.layer_types`` on the served path: Mamba-2 state-space layers
around an attention layer, sparse experts of which a share is held, a shared
expert, no rope, the Granite multipliers. At the ``tiny-hybrid`` preset (two
groups of Mamba around one attention layer, 8 experts top-3 with 4 held), on
seeded weights, against the plain reference
``benchmark/families/granite_hybrid_reference`` (which imports nothing of the
program): logits where a program returns them, and for what the scheduler
serves the reference's logit of each token it chose (a tie on rounding cannot
fail it, a wrong state does)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import parity  # noqa: E402
from benchmark.families import granite_hybrid, granite_hybrid_reference as reference  # noqa: E402
from dynamo_tpu.engine.config import get_config  # noqa: E402
from dynamo_tpu.engine.kv_cache import KvCacheArrays, OutOfBlocksError, SlotAllocator, SlotKv  # noqa: E402
from dynamo_tpu.engine.models import get_module, hybrid, llama  # noqa: E402
from dynamo_tpu.engine.sampling import SamplingParams  # noqa: E402
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions  # noqa: E402

CFG = get_config("tiny-hybrid")


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


# --- the configuration -------------------------------------------------------------


def test_config_states_the_stack_as_groups_and_what_each_array_holds():
    assert CFG.is_hybrid and CFG.layer_groups == (("mamba", 2), ("attention", 1), ("mamba", 2))
    assert (CFG.num_attention_layers, CFG.num_mamba_layers, CFG.experts_held) == (1, 4, 4)
    assert CFG.mamba_d_inner == 128 and CFG.mamba_conv_dim == 128 + 2 * 16
    assert get_module(CFG) is hybrid and get_module(get_config("tiny")) is llama
    cache = KvCacheArrays.create(CFG, 12, dtype=jnp.float32, num_slots=3)
    assert isinstance(cache.k, SlotKv) and cache.k.pool.shape == (1, 12, 8, 32)  # attention layers only: L_a, not L
    assert CFG.mamba_state_shape == (1, 16, 128)  # eight heads of 16 side by side on the lanes, d_state down the sublanes
    assert cache.k.slots.shape == (4, 3, 1, 16, 128) and cache.k.slots.dtype == jnp.float32
    assert cache.v.slots.shape == (4, 3, 3, 160) and cache.k.slot_of.shape == (12,)
    plain = get_config("tiny")
    assert not plain.is_hybrid and plain.num_attention_layers == plain.num_layers and plain.experts_held == 0
    assert KvCacheArrays.create(plain, 4).k.shape[0] == plain.num_layers


@pytest.mark.parametrize("bad,error", [
    (dict(layer_types=("mamba",) * 4), ValueError), (dict(layer_types=("mamba", "conv", "attention", "mamba", "mamba")), ValueError),
    (dict(mamba_n_heads=7), ValueError), (dict(mamba_d_state=0), ValueError), (dict(num_experts_held=9), ValueError),
    (dict(first_expert_held=5), ValueError), (dict(weight_dtype="int8"), (NotImplementedError, ValueError)),
    (dict(kv_cache_dtype="int8"), NotImplementedError), (dict(attention_impl="paged"), NotImplementedError),
    (dict(moe_dispatch="capacity"), NotImplementedError), (dict(residual_fp32=True), NotImplementedError),
], ids=lambda v: "-".join(f"{k}" for k in v) if isinstance(v, dict) else "")
def test_config_refuses_what_the_group_programs_cannot_be(bad, error):
    with pytest.raises(error):
        CFG.replace(**bad)


@pytest.mark.parametrize("field", [dict(num_experts_held=2), dict(shared_intermediate_size=8), dict(use_rope=False),
                                   dict(attention_scale=0.1), dict(residual_multiplier=0.5)], ids=lambda d: next(iter(d)))
def test_fields_read_by_the_group_programs_alone_need_layer_types(field):
    with pytest.raises(ValueError, match="layer_types"):
        get_config("tiny-moe").replace(**field)


def test_slot_allocator_hands_out_every_slot_but_the_scratch_one():
    a = SlotAllocator(4)
    got = [a.allocate() for _ in range(3)]
    assert sorted(got) == [1, 2, 3] and a.in_use == 3 and a.allocs_total == 3
    with pytest.raises(OutOfBlocksError):
        a.allocate()
    a.release(2)
    assert a.in_use == 2 and a.allocate() == 2
    for bad in (0, 4, 7):
        with pytest.raises(ValueError):
            a.release(bad)
    a.release(1)
    with pytest.raises(ValueError):
        a.release(1)


# --- the step programs against the reference, logits ------------------------------

SPEC = {"prompt_lens": [12, 40, 20, 9, 45], "chunk": 16, "window": 4, "windows": 2, "decode_bucket": 8,
        "num_blocks": 64, "max_running": 11, "limit_rel_err": 2e-4, "limit_group_rel_err": 5e-4}


@pytest.fixture
def rows_kernel(monkeypatch):
    """The step programs take the state kernel as they do on a TPU, interpreted
    here: ``ssm_update_rows`` for the decode rows and a bare chunk's idle launch."""
    assert hybrid._rows_kernel_fits(CFG) and not hybrid._use_rows_kernel(CFG)
    launches, kernel = [], hybrid.ssm_update_rows
    monkeypatch.setattr(hybrid, "_use_rows_kernel", hybrid._rows_kernel_fits)
    monkeypatch.setattr(hybrid, "ssm_update_rows", lambda *a, **kw: (launches.append(kw["interpret"]), kernel(*a, **kw))[1])
    return launches


@pytest.mark.parametrize("impl", ["gather", "megakernel", "gather+rows-kernel"],
                         ids=["gather", "megakernel-interpreted", "state-kernel-interpreted"])
def test_step_programs_agree_with_the_reference(params, impl, request):
    """prefill into a slot another sequence just left, a mixed step per chunk
    (state and convolution columns carried across two boundaries, last chunks
    partly padding) with earlier sequences riding as decode rows, and
    decode_multi windows, on one pool and its slots of the sizes a scheduler
    makes, the compared sequences on the highest slots and blocks in a bucket
    that live rows fill. Every control fails a limit, and so does the program
    that does not zero a reused slot."""
    launches = request.getfixturevalue("rows_kernel") if impl.endswith("+rows-kernel") else None
    c = CFG.replace(attention_impl=impl.split("+")[0])
    controls = granite_hybrid.CONTROLS if impl == "gather" else ()
    r = parity.check(granite_hybrid, params, c, 5, SPEC, controls=controls, fault=bool(controls))
    assert r["ok"], {k: r[k] for k in ("rel_err", "group_rel_err", "worst_group", "sampled_is_argmax")}
    assert {"prefill", "chunk_first", "chunk_carried", "mixed_decode", "window_s4"} <= set(r["groups"])
    for name in controls:
        assert r["controls"][name]["fails"], (name, r["controls"][name])
    if controls:
        assert r["fault_control"]["fails"] and r["fault_control"]["worst_group"] == "prefill"
    assert launches is None or (launches and all(launches))  # traced into the programs, interpreted


def _ssm_inputs(T, seed=0):
    H, P, N = CFG.mamba_n_heads, CFG.mamba_d_head, CFG.mamba_d_state
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (T, H, P))
    Bh = jnp.repeat(jax.random.normal(ks[1], (T, 1, N)), H, axis=1)
    Ch = jnp.repeat(jax.random.normal(ks[2], (T, 1, N)), H, axis=1)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (T, H)) - 2.0)
    A, D = -jnp.exp(jax.random.normal(ks[4], (H,))), jax.random.normal(ks[5], (H,))
    return x, Bh, Ch, dt, A, D, jax.random.normal(ks[6], (H, P, N))


def _stepwise(state, x, Bh, Ch, dt, A, D):
    ys = []
    for t in range(x.shape[0]):
        y, state = hybrid._ssm_update(state[None], x[t:t + 1], Bh[t:t + 1], Ch[t:t + 1], dt[t:t + 1], A, D)
        state = state[0]
        ys.append(y[0])
    return jnp.stack(ys), state


@pytest.mark.parametrize("length,block,pieces", [(32, 16, (32,)), (37, 16, (16, 16, 5)), (48, 8, (16, 32)), (5, 16, (5,)), (33, 16, (32, 1))],
                         ids=lambda v: str(v).replace(" ", ""))
def test_chunked_form_is_the_recurrence_across_chunk_boundaries(length, block, pieces):
    """``_ssd_chunk`` over a prompt cut into ``pieces`` (each padded to whole
    blocks, the padding a step of zero length) gives the outputs and the final
    state of the recurrence one position at a time."""
    x, Bh, Ch, dt, A, D, state0 = _ssm_inputs(length)
    want_y, want_state = _stepwise(state0, x, Bh, Ch, dt, A, D)
    state, ys, start = hybrid._to_slot(CFG, state0), [], 0  # the chunked form takes and leaves the state as stored
    for n in pieces:
        T = -(-n // block) * block
        pad = lambda a: jnp.concatenate([a[start:start + n], jnp.ones((T - n,) + a.shape[1:], a.dtype)])  # noqa: E731
        dts = jnp.where((jnp.arange(T) < n)[:, None], pad(dt), 0.0)
        y, state = hybrid._ssd_chunk(state, pad(x), pad(Bh), pad(Ch), dts, A, D, block)
        ys.append(y[:n])
        start += n
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys)), np.asarray(want_y), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hybrid._from_slot(CFG, state)), np.asarray(want_state), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rows,heads,d_head,d_state,tiles", [([3, 5, 7, 9], 8, 16, 16, 16), ([1, 1, 0, 0, 11], 8, 16, 16, 16),
                                                             ([2], 4, 32, 24, 16), ([4, 6], 32, 64, 8, 8)],
                         ids=["distinct", "repeated-and-scratch", "four-heads-a-lane-row", "two-blocks-of-lane-rows"])
def test_the_in_place_kernel_is_the_single_step_on_the_named_slots(rows, heads, d_head, d_state, tiles):
    """``ssm_update_rows`` (interpreted) on the state as stored: the rows'
    slots advance as ``_ssm_update`` advances them, every other slot keeps its
    bits; ``_from_slot`` / ``_to_slot`` are each other's inverse."""
    c = CFG.replace(hidden_size=heads * d_head // 2, mamba_n_heads=heads, mamba_d_head=d_head, mamba_d_state=d_state)
    B = len(rows)
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    x, Bm, Cm = jax.random.normal(ks[0], (B, heads, d_head)), jax.random.normal(ks[1], (B, d_state)), jax.random.normal(ks[2], (B, d_state))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, heads)) - 2.0)
    A, D = -jnp.exp(jax.random.normal(ks[4], (heads,))), jax.random.normal(ks[5], (heads,))
    state = jax.random.normal(ks[6], (12, heads, d_head, d_state))
    stored = hybrid._to_slot(c, state)
    assert stored.shape == (12, *c.mamba_state_shape) and c.mamba_state_shape[-1] == 128
    assert np.array_equal(np.asarray(hybrid._from_slot(c, stored)), np.asarray(state))
    idx = jnp.asarray(rows, jnp.int32)
    Bh, Ch = (jnp.repeat(a[:, None], heads, axis=1) for a in (Bm, Cm))
    want_y, want_state = hybrid._ssm_update(state[idx], x, Bh, Ch, dt, A, D)
    got, y = hybrid.ssm_update_rows(c, stored, idx, x, Bm, Cm, dt, A, D, interpret=True, tiles=tiles)
    got = hybrid._from_slot(c, got)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5, atol=1e-5)
    last = {r: i for i, r in enumerate(rows)}  # of rows that name one slot, the last written stands
    for r in range(12):
        if r in last:
            np.testing.assert_allclose(np.asarray(got[r]), np.asarray(want_state[last[r]]), rtol=1e-5, atol=1e-5)
        else:
            assert np.array_equal(np.asarray(got[r]), np.asarray(state[r]))
    assert hybrid._rows_kernel_fits(c) and not hybrid._rows_kernel_fits(c.replace(mamba_d_head=d_head * 3, hidden_size=heads * d_head * 3 // 2))


@pytest.mark.parametrize("program", ["decode", "decode_multi", "mixed_step", "prefill"])
def test_padded_rows_and_positions_leave_every_slot_but_the_scratch_one_untouched(params, program):
    """Inactive rows, rows whose table is zeros and the padded positions of a
    chunk read and write scratch slot 0 (and scratch block 0) only."""
    cache = KvCacheArrays.create(CFG, 12, dtype=jnp.float32, num_slots=4)
    k = cache.k._replace(slots=cache.k.slots + 1.5, slot_of=cache.k.slot_of.at[3].set(2))
    v = cache.v._replace(slots=cache.v.slots + 0.5)
    B = 4
    i32, z = (lambda *s: jnp.zeros(s, jnp.int32)), jnp.zeros((B,), jnp.float32)
    tables = i32(B, 4).at[1].set(jnp.asarray([3, 4, 0, 0]))  # a real table on an INACTIVE row
    off = jnp.zeros((B,), bool)
    if program == "decode":
        out = hybrid.decode(params, CFG, k, v, i32(B), i32(B), tables, off)
    elif program == "decode_multi":
        out = hybrid.decode_multi(params, CFG, k, v, i32(B), i32(B), tables, off, z, i32(B), z + 1, jax.random.PRNGKey(0), 3)
    elif program == "mixed_step":
        out = hybrid.mixed_step(params, CFG, k, v, i32(16), jnp.int32(5), jnp.int32(0), i32(16), i32(B), i32(B), tables, off)
    else:
        out = hybrid.prefill(params, CFG, k, v, i32(16), jnp.int32(5), jnp.int32(0), i32(16))
    k2, v2 = out[-3], out[-2]
    assert np.array_equal(np.asarray(k2.slots[:, 1:]), np.asarray(k.slots[:, 1:]))
    assert np.array_equal(np.asarray(v2.slots[:, 1:]), np.asarray(v.slots[:, 1:]))
    assert np.array_equal(np.asarray(k2.pool[:, 1:]), np.asarray(k.pool[:, 1:]))
    assert set(out[-1]) == {"held_assignments", "experts_visited"}


@pytest.mark.parametrize("program", ["decode", "decode_multi", "mixed_step", "prefill"])
def test_a_whole_step_program_with_the_state_kernel_is_the_program_without_it(params, program, request):
    """Live rows on the highest slots of a cache of eleven (and one inactive
    row): the program that advances them in place with ``ssm_update_rows``
    (interpreted; for a bare chunk the idle launch) returns the logits and
    leaves every slot and block as the gather, ``_ssm_update`` and scatter do."""
    rs = np.random.default_rng(3)
    cache = KvCacheArrays.create(CFG, 24, dtype=jnp.float32, num_slots=11)
    k = cache.k._replace(slots=jnp.asarray(rs.normal(size=cache.k.slots.shape), jnp.float32),
                         slot_of=cache.k.slot_of.at[jnp.asarray([5, 9, 13, 17])].set(jnp.asarray([10, 9, 7, 4])))
    v = cache.v._replace(slots=jnp.asarray(rs.normal(size=cache.v.slots.shape), jnp.float32))
    B = 4
    toks = jnp.asarray(rs.integers(1, CFG.vocab_size, size=B), jnp.int32)
    pos = jnp.asarray([3, 9, 0, 20], jnp.int32)
    tables = jnp.asarray([[5, 6, 7, 8], [9, 10, 11, 12], [13, 14, 15, 16], [17, 18, 19, 20]], jnp.int32)
    act = jnp.asarray([True, True, False, True])
    tables = jnp.where(act[:, None], tables, 0)
    chunk = jnp.asarray(rs.integers(1, CFG.vocab_size, size=16), jnp.int32)
    z, i32 = jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32)

    def run():
        if program == "decode":
            return hybrid.decode(params, CFG, k, v, toks, pos, tables, act)
        if program == "decode_multi":
            return hybrid.decode_multi(params, CFG, k, v, toks, pos, tables, act, z, i32, z + 1, jax.random.PRNGKey(0), 3,
                                       return_logits=True)
        ptab = jnp.zeros((16,), jnp.int32).at[:4].set(jnp.asarray([13, 14, 15, 16]))
        if program == "mixed_step":
            return hybrid.mixed_step(params, CFG, k, v, chunk, jnp.int32(11), jnp.int32(0), ptab, toks, pos, tables, act)
        return hybrid.prefill(params, CFG, k, v, chunk, jnp.int32(11), jnp.int32(0), ptab)

    want = run()
    launches = request.getfixturevalue("rows_kernel")
    got = run()
    assert launches and all(launches)
    for a, b in zip(jax.tree.leaves(got[:-1]), jax.tree.leaves(want[:-1])):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-5, atol=2e-5)
    assert all(int(got[-1][key]) == int(want[-1][key]) for key in want[-1])


def test_a_chunks_padding_leaves_the_state_where_its_last_valid_position_left_it(params):
    """The same 21 positions as one padded chunk of 32 and as chunks of 16 and
    5 (padded to 16) leave the slot's state and columns alike."""
    toks = jnp.asarray(np.random.default_rng(2).integers(1, CFG.vocab_size, size=32), jnp.int32)
    table = jnp.zeros((16,), jnp.int32).at[:4].set(jnp.asarray([1, 2, 3, 4]))

    def fresh():
        cache = KvCacheArrays.create(CFG, 8, dtype=jnp.float32, num_slots=3)
        return hybrid.open_slot(cache.k, cache.v, jnp.int32(1), jnp.int32(2))

    k, v = fresh()
    _, k1, v1, _ = hybrid.prefill(params, CFG.replace(mamba_chunk_size=32), k, v, toks, jnp.int32(21), jnp.int32(0), table)
    k, v = fresh()
    _, k, v, _ = hybrid.prefill(params, CFG, k, v, toks[:16], jnp.int32(16), jnp.int32(0), table)
    _, k2, v2, _ = hybrid.prefill(params, CFG, k, v, jnp.concatenate([toks[16:21], toks[:11]]), jnp.int32(5), jnp.int32(16), table)
    np.testing.assert_allclose(np.asarray(k1.slots[:, 2]), np.asarray(k2.slots[:, 2]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v1.slots[:, 2]), np.asarray(v2.slots[:, 2]), rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(k1.slots[:, 2]).max()) > 0 and not np.any(np.asarray(k1.slots[:, 1]))  # slot 1 was never named


# --- the share of the experts -------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 as two shares (the program's ``_moe_held``, each on
    its own half of the stacks), the shared expert counted once: their sum is
    the uncut reference's FFN of the layer (every chosen expert, held as 0-7)."""
    whole = CFG.replace(num_experts_held=0)
    p = hybrid.init_params(whole, jax.random.PRNGKey(4), dtype=jnp.float32)
    L = p["layers"]
    x = jax.random.normal(jax.random.PRNGKey(5), (11, CFG.hidden_size))
    for l in (0, 3):
        lp = {k: v[l] for k, v in L.items()}
        shares = []
        for first in (0, 4):
            c = CFG.replace(num_experts_held=4, first_expert_held=first)
            half = dict(lp, **{k: lp[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")})
            out, held, visited = llama._moe_held(x, half, c)
            shares.append(out)
            assert 0 < int(held) < 11 * 3 and 0 < int(visited) <= 4
        shared = (jax.nn.silu(x @ lp["shared_gate"]) * (x @ lp["shared_up"])) @ lp["shared_down"]
        with jax.default_matmul_precision("highest"):
            gates, idx = jax.nn.softmax(jax.lax.top_k(x @ lp["router"], 3)[0], axis=-1), jax.lax.top_k(x @ lp["router"], 3)[1]
            want = reference._experts(x, gates, idx, L, l, 0, 8, False, None) + reference._swiglu(
                x, lp["shared_gate"], lp["shared_up"], lp["shared_down"], act=None)
            one = reference._experts(x, gates, idx, L, l, 0, 4, False, None)
        np.testing.assert_allclose(np.asarray(shares[0] + shares[1] + shared), np.asarray(want), rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(shares[0]), np.asarray(one), rtol=2e-4, atol=2e-5)  # and a share is the reference's share
        assert float(jnp.abs(shares[1]).max()) > 1e-3  # the other share is not nothing


# --- through the scheduler --------------------------------------------------------


def serve(params, requests, *, num_blocks=64, max_running=3, arrive_at=None):
    """Run ``requests`` {id: (prompt, max_tokens)} through a Scheduler to the
    end; ``arrive_at[id]`` is the iteration before which a request arrives."""
    sc = SchedulerConfig(num_blocks=num_blocks, max_running=max_running, prefill_buckets=[16],
                         decode_buckets=[4], max_prefill_chunk=16, mixed_prefill_budget=16, num_scheduler_steps=4)
    s = Scheduler(CFG, params, sc, dtype=jnp.float32)
    out = {rid: [] for rid in requests}
    arrive_at = arrive_at or {}
    step = 0
    while step == 0 or s.has_work() or any(v >= step for v in arrive_at.values()):
        for rid, (prompt, n) in requests.items():
            if arrive_at.get(rid, 0) == step:
                s.add_request(rid, prompt, SamplingParams(temperature=0.0), StopConditions(max_tokens=n, ignore_eos=True))
        for seq, o in s.step():
            if o.token_id >= 0:
                out[seq.request_id].append(o.token_id)
        step += 1
        live = [q for q in s.running + s.waiting if q.block_ids]
        held = sorted(q.state_slot for q in live)
        assert 0 not in held and len(set(held)) == len(held) == s.slots.in_use  # one slot a live sequence, none twice
        assert step < 400
    return s, out


def assert_served_as_the_reference(params, requests, out):
    for rid, (prompt, n) in requests.items():
        assert len(out[rid]) == n
        full = np.asarray(list(prompt) + out[rid])
        lg = reference.forward(params, CFG, [full[:-1]], [list(range(len(prompt) - 1, len(full) - 1))])[0]
        gap = [float(lg[i].max() - lg[i][tok]) for i, tok in enumerate(out[rid])]
        assert max(gap) < 1e-3, (rid, int(np.argmax(gap)), max(gap))


CASES = {
    # name: (requests {id: (prompt length, answer length)}, arrivals, blocks, max_running, what must have happened)
    "one-prompt-over-three-chunks": ({"a": (37, 9)}, {}, 64, 3, dict(allocs=1)),
    "slots-reused-after-a-finish-start-from-zero": ({"a": (37, 10), "b": (5, 4), "c": (20, 12), "d": (33, 6), "e": (9, 14)},
                                                    {}, 64, 2, dict(allocs=5, slots=2)),
    "a-prompt-rides-beside-decode-rows": ({"a": (12, 20), "b": (45, 8), "c": (17, 12)}, {"b": 2, "c": 3}, 64, 3, dict(mixed=True)),
    "preempted-and-recomputed": ({"a": (20, 30), "b": (40, 30), "c": (25, 30)}, {}, 12, 3, dict(preempted=True)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_scheduler_serves_the_hybrid_exactly(params, case):
    """A slot from admission to finish, zeroed when taken, state carried from
    chunk to chunk and through mixed steps and multi-step windows, dropped at
    preemption and recomputed: what the scheduler serves is the reference's
    own greedy continuation, and every slot and block goes back."""
    shape, arrive_at, blocks, max_running, want = CASES[case]
    rng = np.random.default_rng(1)
    requests = {rid: (rng.integers(1, CFG.vocab_size, size=n).tolist(), m) for rid, (n, m) in shape.items()}
    s, out = serve(params, requests, num_blocks=blocks, max_running=max_running, arrive_at=arrive_at)
    assert_served_as_the_reference(params, requests, out)
    assert len(s.allocator._free) == blocks - 1 and s.slots.in_use == 0
    g = s.kv_gauges()
    assert g["ssm_slots_total"] == s.slots.num_slots - 1 == max_running and g["ssm_slots_in_use"] == 0
    spans = [(n, a) for n, _, _, _, a in s.flight.log.spans]
    assert sum(n == "sched.slots" for n, _ in spans) == g["ssm_slot_allocs_total"]
    steps = [a for n, a in spans if n == "sched.step" and a and "kind" in a]
    assert all(a["ssm_rows"] == a["rows"] + (a["kind"] == "mixed") and 0 < a["ssm_slots"] <= max_running for a in steps)
    counted = [a for a in steps if "experts_visited" in a]
    assert counted and all(0 < a["experts_visited"] <= a["held_assignments"] for a in counted)
    assert {a["kind"] for a in counted} >= {"decode_multi"}
    if "allocs" in want:
        assert g["ssm_slot_allocs_total"] == want["allocs"] and s.preempt_total == 0
    if want.get("mixed"):
        assert any(a["kind"] == "mixed" and a["decode"] >= 1 for a in steps)
    if want.get("preempted"):
        assert s.preempt_total >= 1 and g["ssm_preempt_recomputes_total"] == s.preempt_total
        assert g["ssm_slot_allocs_total"] == len(requests) + s.preempt_total
    assert "ssm_slots_in_use" in s.debug_state()["block_pool"]


def test_debug_state_names_each_sequences_slot_and_warmup_builds_the_slot_program(params):
    sc = SchedulerConfig(num_blocks=32, max_running=2, prefill_buckets=[16], decode_buckets=[4], max_prefill_chunk=16,
                         num_scheduler_steps=4)
    s = Scheduler(CFG, params, sc, dtype=jnp.float32)
    assert s.sc.enable_prefix_caching is False and not s._supports_chunk_admit and s.slots.num_slots == 3
    assert s.warmup(ctx_tokens=64) > 0 and ("open_slot",) in s.flight._exec_keys
    assert not np.any(np.asarray(s.cache.k.slots[:, 1:]))  # warm-up wrote the scratch slot alone
    s.add_request("a", [3, 4, 5], SamplingParams(temperature=0.0), StopConditions(max_tokens=6, ignore_eos=True))
    s.step()
    info = s.debug_state()["running"][0]
    assert info["state_slot"] == 1 and int(s.cache.k.slot_of[s.running[0].block_ids[0]]) == 1
    assert s._kv_cache_bytes == 2 * 1 * 32 * CFG.block_size * CFG.kv_size * 4  # the one attention layer's pool
    assert s._param_bytes == sum(int(x.size) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))


# --- refusals ----------------------------------------------------------------------


def _bare(params, **kw):
    return Scheduler(CFG, params, SchedulerConfig(num_blocks=16, max_running=2, **kw), dtype=jnp.float32)


REFUSALS = {
    "kvbm-tiers": lambda s, p: s.attach_kvbm(object()),
    "speculation": lambda s, p: s.attach_draft(get_config("tiny"), None),
    "speculation-with-a-hybrid-draft": lambda s, p: Scheduler(
        get_config("tiny"), llama.init_params(get_config("tiny"), jax.random.PRNGKey(0)),
        SchedulerConfig(num_blocks=16)).attach_draft(CFG, p),
    "export": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(), keep_blocks_on_finish=True),
    "injection": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(), prefilled={"blocks": []}),
    "take-export": lambda s, p: s.take_export("x"),
    "take-export-device": lambda s, p: s.take_export_device("x"),
    "multimodal": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(),
                                            mm_features=np.zeros((1, 64), np.float32)),
    "prefix-registration": lambda s, p: (setattr(s.sc, "enable_prefix_caching", True),
                                         s._register_full_blocks(type("S", (), {"block_hashes": [1]})())),
    "prefix-matching": lambda s, p: s._match_prefix_tiers(None),
    "wave-admission-program": lambda s, p: llama.chunk_decode(p, CFG, None, None, jnp.zeros((1, 4), jnp.int32), None, None, None),
    "embeddings-program": lambda s, p: llama.embed(p, CFG, jnp.zeros((4,), jnp.int32), 4),
    "a-mesh": lambda s, p: Scheduler(CFG, p, SchedulerConfig(num_blocks=16), mesh=object()),
    "a-mesh-for-the-parameters": lambda s, p: __import__("dynamo_tpu.engine.sharding", fromlist=["x"]).shard_params(
        p, object(), True, CFG.num_experts),
    "a-sharded-cache": lambda s, p: KvCacheArrays.create(CFG, 8, num_slots=3, sharding=object()),
    "int8-weights": lambda s, p: CFG.replace(weight_dtype="int8", num_experts=0, num_experts_held=0),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_equates_a_sequence_with_its_table_is_refused_for_layer_types(params, what):
    s = _bare(params)
    with pytest.raises(NotImplementedError, match="layer_types"):
        REFUSALS[what](s, params)


def test_the_slots_are_one_a_running_sequence_and_the_scratch_slot(params):
    s = _bare(params)  # max_running 2
    assert s.slots.num_slots == 3 == s.cache.k.slots.shape[1] and not hasattr(s.sc, "num_state_slots")
    with pytest.raises(ValueError, match="num_slots"):
        KvCacheArrays.create(CFG, 8, num_slots=1)
