"""The "cca" mixer kind and the ZAYA router on the served path: attention in a
compressed latent behind two causal convolutions and a value shift (a pool
row a token AND a slot of columns a sequence, in every layer), top-1 of the
experts or a skip choice behind a router MLP that carries its state from layer
to layer, the residual merge. At the ``tiny-zaya`` preset, on seeded float32
weights, against the plain reference ``benchmark/families/zaya_reference``
(which imports nothing of the program): logits where a program returns them,
and for what the scheduler serves the reference's logit of each token it chose
(a tie on rounding cannot fail it, a wrong column does)."""

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
from benchmark.families import zaya, zaya_reference as reference  # noqa: E402
from dynamo_tpu.engine.config import ModelConfig, get_config  # noqa: E402
from dynamo_tpu.engine.kv_cache import KvCacheArrays, SlotKv  # noqa: E402
from dynamo_tpu.engine.models import get_module, hybrid, llama  # noqa: E402
from dynamo_tpu.engine.sampling import SamplingParams  # noqa: E402
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions  # noqa: E402

CFG = get_config("tiny-zaya")
L, BS = CFG.num_layers, CFG.block_size
SPEC = dict(prompt_lens=[12, 40, 20, 9, 45], chunk=16, window=4, windows=2, decode_bucket=8, num_blocks=256, max_running=8,
            limit_rel_err=1e-4, limit_group_rel_err=1e-4)
GREEDY = (jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.float32))


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def fresh_cache(num_blocks=24, num_slots=5):
    cache = KvCacheArrays.create(CFG, num_blocks, dtype=jnp.float32, num_slots=num_slots)
    return cache.k, cache.v


def prefill_chunks(params, k, v, tokens, table, chunks, chunk=16):
    """``tokens`` through ``hybrid.prefill`` in pieces of the given lengths, each padded to ``chunk``; every position's logits."""
    out, start = [], 0
    for n in chunks:
        buf = np.zeros((chunk,), np.int32)
        buf[:n] = tokens[start:start + n]
        lg, k, v, _ = hybrid.prefill(params, CFG, k, v, jnp.asarray(buf), jnp.int32(n), jnp.int32(start), jnp.asarray(table),
                                     all_logits=True)
        out.append(np.asarray(lg)[:n])
        start += n
    return np.concatenate(out), k, v


# --- the configuration -------------------------------------------------------------


def test_config_states_a_pool_and_a_slot_for_every_layer():
    assert CFG.is_hybrid and CFG.layer_groups == (("cca", 4),) and get_module(CFG) is hybrid
    assert (CFG.num_attention_layers, CFG.num_cca_layers, CFG.num_mamba_layers) == (4, 4, 0)
    assert (CFG.q_size, CFG.kv_size, CFG.cca_channels, CFG.cca_slot_lanes, CFG.router_choices) == (64, 32, 96, 208, 5)
    cache = KvCacheArrays.create(CFG, 12, dtype=jnp.float32, num_slots=3)
    assert isinstance(cache.k, SlotKv) and cache.k.pool.shape == cache.v.pool.shape == (4, 12, 8, 32)  # every layer has rows
    assert cache.v.slots.shape == (4, 3, 208) and cache.k.slots.shape == (4, 3, 0) and cache.k.slot_of.shape == (12,)


@pytest.mark.parametrize("bad,error", [
    (dict(layer_types=("cca", "cca", "attention", "cca")), NotImplementedError),
    (dict(layer_types=("cca",) * 3), ValueError),
    (dict(cca_time0=3), ValueError),
    (dict(rope_fraction=0.3), ValueError),
    (dict(num_kv_heads=1), ValueError),
    (dict(num_experts_per_tok=2), ValueError),
    (dict(router_hidden_size=0), ValueError),
    (dict(use_rope=False), NotImplementedError),
    (dict(num_experts_held=2), NotImplementedError),
    (dict(weight_dtype="int8", num_experts=0, router_kind="linear", moe_skip_choice=False), NotImplementedError),
    (dict(kv_cache_dtype="int8"), NotImplementedError),
    (dict(attention_impl="paged"), NotImplementedError),
    (dict(router_kind="softmax"), ValueError),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else "")
def test_config_refuses_what_the_cca_programs_cannot_be(bad, error):
    with pytest.raises(error):
        CFG.replace(**bad)


@pytest.mark.parametrize("field", [dict(rope_fraction=0.5), dict(router_kind="zaya", router_hidden_size=8), dict(moe_skip_choice=True),
                                   dict(residual_merge=True)], ids=lambda f: next(iter(f)))
def test_fields_of_this_kind_need_cca_layers(field):
    with pytest.raises(ValueError, match="layer_types"):
        get_config("tiny-moe").replace(**field)
    with pytest.raises((NotImplementedError, ValueError)):
        get_config("tiny-hybrid").replace(**field)


# --- the step programs against the reference ------------------------------------------


@pytest.mark.parametrize("impl", ["gather", "megakernel"], ids=["gather", "megakernel-interpreted"])
def test_step_programs_agree_with_the_reference(params, impl):
    """prefill into a slot another sequence just left, position-by-position
    prefill of prompts of three chunks (columns and pool rows carried across
    two boundaries, a decode step between the chunks), a mixed step per chunk of
    the shorter ones with earlier sequences riding as decode rows, and
    decode_multi windows, on one pool and its slots in a bucket that live rows
    fill. Every control fails a limit, and so does the program that does not
    zero a reused slot."""
    controls = zaya.CONTROLS if impl == "gather" else ()
    r = parity.check(zaya, params, CFG.replace(attention_impl=impl), 5, SPEC, controls=controls, fault=bool(controls))
    assert r["ok"], {k: r[k] for k in ("rel_err", "group_rel_err", "worst_group", "sampled_is_argmax")}
    assert set(r["groups"]) == {"slot_head", "chunk_head", "body", "rows", "windows"}
    for name in controls:
        assert r["controls"][name]["fails"], (name, r["controls"][name])
    if controls:
        assert r["fault_control"]["fails"] and r["fault_control"]["worst_group"] == "slot_head"
        assert r["controls"]["no_conv_carry"]["groups"]["chunk_head"] > 0.1 > r["controls"]["no_conv_carry"]["groups"]["slot_head"]


def test_prefill_then_decode_through_pool_and_slot_is_the_references_full_forward(params):
    rng = np.random.default_rng(3)
    seq = rng.integers(1, CFG.vocab_size, size=40).astype(np.int32)
    table = np.array([5, 6, 7, 8, 9, 0], np.int32)
    k, v = hybrid.open_slot(*fresh_cache(), jnp.int32(5), jnp.int32(3))
    probs = []
    ref = reference.forward(params, CFG, [seq], [list(range(40))], probs=probs)[0]
    got, k, v = prefill_chunks(params, k, v, seq[:27], table, (16, 11))
    np.testing.assert_allclose(got, ref[:27], atol=2e-5)
    tables = np.zeros((4, 6), np.int32)
    tables[2] = table
    active = np.array([False, False, True, False])
    for t in range(27, 40):
        tok, pos = np.zeros((4,), np.int32), np.zeros((4,), np.int32)
        tok[2], pos[2] = seq[t], t
        lg, k, v, aux = hybrid.decode(params, CFG, k, v, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tables), jnp.asarray(active))
        np.testing.assert_allclose(np.asarray(lg)[2], ref[t], atol=2e-5)
        choice = probs[0][:, t].argmax(-1)  # (beta is small: where it turns the choice the counts below would differ)
        skipped = int((np.asarray(probs[0][:, t] + np.asarray(params["layers"]["router_beta"])).argmax(-1) == CFG.num_experts).sum())
        assert int(aux["skipped_rows"]) == skipped and int(aux["held_assignments"]) == L - skipped, (t, choice)


def test_router_probabilities_are_the_references(params):
    """``_zaya_route`` on a layer's input and a carried state: the chosen
    probability, the choice and the state handed on are the reference's."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(24, CFG.hidden_size)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(24, CFG.router_hidden_size)), jnp.float32)
    lay = params["layers"]
    for l in range(L):
        lp = {k: a[l] for k, a in lay.items()}
        weights, ids, state = hybrid._zaya_route(CFG, lp, llama.rms_norm(x, lp["mlp_norm"], CFG.rms_norm_eps), s)
        _, p, choice, r = reference._router(x, *(lp[k] for k in (
            "mlp_norm", "router_down", "router_down_b", "router_gamma", "router_norm", "router_w1", "router_b1", "router_w2",
            "router_b2", "router_w3", "router_beta")), s, eps=CFG.rms_norm_eps, act=None, low=False)
        assert np.array_equal(np.asarray(ids)[:, 0], np.asarray(choice))
        np.testing.assert_allclose(np.asarray(weights)[:, 0], np.asarray(p)[np.arange(24), np.asarray(choice)], atol=1e-6)
        np.testing.assert_allclose(np.asarray(state), np.asarray(r), atol=1e-5)
        assert weights.dtype == state.dtype == jnp.float32


@pytest.mark.parametrize("pieces", [(16, 16, 16), (16, 16, 9), (16, 1), (7,)], ids=lambda p: "+".join(map(str, p)))
def test_a_prompt_in_one_chunk_is_the_same_prompt_in_several(params, pieces):
    n = sum(pieces)
    seq = np.random.default_rng(n).integers(1, CFG.vocab_size, size=n).astype(np.int32)
    table = np.array([3, 4, 5, 6, 7, 8], np.int32)
    k, v = hybrid.open_slot(*fresh_cache(), jnp.int32(3), jnp.int32(2))
    whole, k1, v1 = prefill_chunks(params, k, v, seq, table, (n,), chunk=48)
    k, v = hybrid.open_slot(*fresh_cache(), jnp.int32(3), jnp.int32(2))
    parts, k3, v3 = prefill_chunks(params, k, v, seq, table, pieces)
    np.testing.assert_allclose(parts, whole, atol=2e-5)
    np.testing.assert_allclose(np.asarray(v3.slots), np.asarray(v1.slots), atol=1e-5)  # the slot holds the last valid row's columns
    rows = (slice(None), table[: -(-n // BS)])
    np.testing.assert_allclose(np.asarray(k3.pool)[rows].reshape(L, -1, 32)[:, :n], np.asarray(k1.pool)[rows].reshape(L, -1, 32)[:, :n], rtol=1e-5, atol=2e-4)  # keys carry their temperature times sqrt(head_dim)
    assert np.any(np.asarray(v1.slots[:, 2])) and not np.any(np.asarray(v1.slots[:, [0, 1, 3, 4]]))


def _two_live_rows(params):
    """Two sequences prefilled into slots 1 and 3, lanes 0 and 2 of a bucket of four; lanes 1 and 3 are padding."""
    rng = np.random.default_rng(8)
    k, v = fresh_cache()
    tables = np.zeros((4, 4), np.int32)
    tables[0], tables[2] = [2, 3, 4, 5], [9, 10, 11, 12]
    lens = {0: 11, 2: 19}
    for lane, slot in ((0, 1), (2, 3)):
        k, v = hybrid.open_slot(k, v, jnp.int32(tables[lane][0]), jnp.int32(slot))
        _, k, v = prefill_chunks(params, k, v, rng.integers(1, CFG.vocab_size, size=lens[lane]), tables[lane],
                                 (16, 3) if lens[lane] > 16 else (lens[lane],))
    tok = np.array([7, 0, 9, 0], np.int32)
    pos = np.array([11, 0, 19, 0], np.int32)
    return k, v, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tables), jnp.asarray([True, False, True, False])


def test_a_multi_step_window_is_single_steps(params):
    k, v, tok, pos, tables, active = _two_live_rows(params)
    out, lg, k_w, v_w, aux_w = hybrid.decode_multi(params, CFG, k, v, tok, pos, tables, active, *GREEDY, jax.random.PRNGKey(0), 4,
                                                   return_logits=True)
    counts = {name: 0 for name in hybrid.AUX_KEYS}
    for s in range(4):
        lg1, k, v, aux = hybrid.decode(params, CFG, k, v, tok, pos + s, tables, active)
        np.testing.assert_allclose(np.asarray(lg[s])[[0, 2]], np.asarray(lg1)[[0, 2]], atol=2e-5)
        tok = jnp.argmax(lg1, axis=-1).astype(jnp.int32)
        assert np.array_equal(np.asarray(out[s])[[0, 2]], np.asarray(tok)[[0, 2]])
        counts = {name: counts[name] + int(aux[name]) for name in counts}
    np.testing.assert_allclose(np.asarray(v_w.slots)[:, 1:], np.asarray(v.slots)[:, 1:], atol=1e-5)  # (slot 0: the padded rows' sink)
    np.testing.assert_allclose(np.asarray(k_w.pool)[:, 1:], np.asarray(k.pool)[:, 1:], rtol=1e-5, atol=2e-4)
    assert {name: int(n) for name, n in aux_w.items()} == counts and counts["held_assignments"] + counts["skipped_rows"] == 4 * 2 * L


@pytest.mark.parametrize("program", ["decode", "decode_multi", "mixed_step", "prefill"])
def test_live_rows_leave_slot_0_and_block_0_alone_and_padding_leaves_the_rest(params, program):
    """A live row reads and writes its own slot and blocks: the scratch slot
    and block stay as they were when every row is live. Padded rows and a
    chunk's padded positions sink there: no other slot, and no block of a
    table, changes under them."""
    k, v, tok, pos, tables, active = _two_live_rows(params)
    live = lambda a: a[jnp.asarray([0, 2])]  # noqa: E731 - the two live lanes alone: no padding
    chunk = np.zeros((16,), np.int32)
    chunk[:5] = [4, 5, 6, 7, 8]
    p_table = jnp.asarray([14, 15, 0, 0], jnp.int32)
    k, v = hybrid.open_slot(k, v, jnp.int32(14), jnp.int32(4))
    before_slots, before_pool, before_values = np.asarray(v.slots), np.asarray(k.pool), np.asarray(v.pool)
    run = {
        "decode": lambda rows: hybrid.decode(params, CFG, k, v, rows(tok), rows(pos), rows(tables), rows(active)),
        "decode_multi": lambda rows: hybrid.decode_multi(params, CFG, k, v, rows(tok), rows(pos), rows(tables), rows(active),
                                                         *(rows(g) for g in GREEDY), jax.random.PRNGKey(0), 4),
        "mixed_step": lambda rows: hybrid.mixed_step(params, CFG, k, v, jnp.asarray(chunk), jnp.int32(16 if rows is live else 5),
                                                     jnp.int32(0), p_table, rows(tok), rows(pos), rows(tables), rows(active)),
        "prefill": lambda rows: hybrid.prefill(params, CFG, k, v, jnp.asarray(chunk), jnp.int32(16 if rows is live else 5),
                                               jnp.int32(0), p_table),
    }[program]
    _, k1, v1, _ = run(live)
    for got, was in ((v1.slots, before_slots), (k1.pool, before_pool), (v1.pool, before_values)):
        np.testing.assert_array_equal(np.asarray(got)[:, 0], was[:, 0])
    _, k2, v2, _ = run(lambda a: a)  # with the padded lanes, and a chunk of 5 valid positions in 16
    untouched = [s for s in range(5) if s not in ((4,) if program == "prefill" else (1, 3, 4) if program == "mixed_step" else (1, 3))]
    np.testing.assert_array_equal(np.asarray(v2.slots)[:, untouched[1:]], before_slots[:, untouched[1:]])
    written = {"decode": [2, 3, 10, 11], "decode_multi": [2, 3, 10, 11, 12], "mixed_step": [2, 3, 10, 11, 14], "prefill": [14]}[program]
    others = [b for b in range(1, 24) if b not in written]
    np.testing.assert_array_equal(np.asarray(k2.pool)[:, others], before_pool[:, others])
    if program in ("mixed_step", "prefill"):  # the chunk's 11 padded positions: the slot holds position 4's columns, block 15 nothing
        assert not np.any(np.asarray(k2.pool[:, 15])) and np.any(np.asarray(v2.slots[:, 4]))
        _, _, v5 = prefill_chunks(params, *hybrid.open_slot(k, v, jnp.int32(14), jnp.int32(4)), chunk[:5], np.asarray(p_table), (5,), chunk=8)
        np.testing.assert_allclose(np.asarray(v2.slots[:, 4]), np.asarray(v5.slots[:, 4]), atol=1e-5)


def test_the_skip_choice_adds_nothing_and_is_counted(params):
    """With the balancing bias pushed to the skip choice every token passes by
    the experts in every layer: the step is the reference's (which adds
    nothing there), whatever the experts' weights, and every row is counted."""
    lay = params["layers"]
    skip_all = dict(params, layers=dict(lay, router_beta=lay["router_beta"].at[:, CFG.num_experts].set(10.0)))
    noisy = dict(skip_all, layers=dict(skip_all["layers"], w_down=lay["w_down"] * 7.0 + 1.0))
    seq = np.random.default_rng(6).integers(1, CFG.vocab_size, size=13).astype(np.int32)
    table = np.array([2, 3, 0, 0], np.int32)
    ref = reference.forward(skip_all, CFG, [seq], [list(range(13))])[0]
    outs = []
    for p in (skip_all, noisy, params):
        k, v = hybrid.open_slot(*fresh_cache(), jnp.int32(2), jnp.int32(1))
        buf = np.zeros((16,), np.int32)
        buf[:13] = seq
        lg, _, _, aux = hybrid.prefill(p, CFG, k, v, jnp.asarray(buf), jnp.int32(13), jnp.int32(0), jnp.asarray(table), all_logits=True)
        outs.append((np.asarray(lg)[:13], {name: int(n) for name, n in aux.items()}))
    np.testing.assert_allclose(outs[0][0], ref, atol=2e-5)
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    assert outs[0][1] == outs[1][1] == {"held_assignments": 0, "experts_visited": 0, "skipped_rows": 13 * L}  # padded positions are not counted
    assert outs[2][1]["skipped_rows"] < 13 * L and outs[2][1]["held_assignments"] + outs[2][1]["skipped_rows"] == 13 * L
    assert np.abs(outs[2][0] - ref).max() > 1e-3


def test_moe_held_takes_its_router_as_a_function():
    """Top-1 through ``_moe_held``: a choice past the experts takes the absent
    expert's path (no group, nothing added), a held one its weight times its expert."""
    c = ModelConfig(name="t", vocab_size=8, hidden_size=8, num_layers=1, num_heads=2, num_kv_heads=2, head_dim=4, intermediate_size=4,
                    num_experts=3, num_experts_per_tok=1, layer_types=("attention",))
    rng = np.random.default_rng(0)
    lp = {n: jnp.asarray(rng.normal(size=s), jnp.float32) for n, s in (("w_gate", (3, 8, 4)), ("w_up", (3, 8, 4)), ("w_down", (3, 4, 8)))}
    x = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    ids = jnp.asarray([[2], [3], [0], [3], [2]], jnp.int32)
    w = jnp.asarray([[0.5], [0.9], [0.25], [0.1], [1.0]], jnp.float32)
    out, held, visited = llama._moe_held(x, lp, c, jnp.asarray([True] * 5), route=lambda x, lp: (w, ids))
    assert int(held) == 3 and int(visited) == 2 and not np.any(np.asarray(out)[[1, 3]])
    for t, e in ((0, 2), (2, 0), (4, 2)):
        want = float(w[t, 0]) * ((jax.nn.silu(x[t] @ lp["w_gate"][e]) * (x[t] @ lp["w_up"][e])) @ lp["w_down"][e])
        np.testing.assert_allclose(np.asarray(out)[t], np.asarray(want), rtol=1e-5, atol=1e-6)


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
        assert max(gap) < 1e-4, (rid, int(np.argmax(gap)), max(gap))


CASES = {
    # name: (requests {id: (prompt length, answer length)}, arrivals, blocks, max_running, what must have happened)
    "one-prompt-over-three-chunks": ({"a": (37, 9)}, {}, 64, 3, dict(allocs=1)),
    "slots-reused-after-a-finish-start-from-zero": ({"a": (37, 10), "b": (5, 4), "c": (20, 12), "d": (33, 6), "e": (9, 14)},
                                                    {}, 64, 2, dict(allocs=5)),
    "a-prompt-rides-beside-decode-rows": ({"a": (12, 20), "b": (45, 8), "c": (17, 12)}, {"b": 2, "c": 3}, 64, 3, dict(mixed=True)),
    "preempted-and-recomputed": ({"a": (20, 30), "b": (40, 30), "c": (25, 30)}, {}, 12, 3, dict(preempted=True)),
}


def _requests(shape):
    rng = np.random.default_rng(1)
    return {rid: (rng.integers(1, CFG.vocab_size, size=n).tolist(), m) for rid, (n, m) in shape.items()}


@pytest.mark.parametrize("case", sorted(CASES))
def test_scheduler_serves_the_kind_exactly(params, case):
    """A slot and a table from admission to finish, the slot zeroed when taken,
    columns carried from chunk to chunk and through mixed steps and multi-step
    windows, dropped at preemption and recomputed: what the scheduler serves is
    the reference's own greedy continuation, and every slot and block goes back."""
    shape, arrive_at, blocks, max_running, want = CASES[case]
    requests = _requests(shape)
    s, out = serve(params, requests, num_blocks=blocks, max_running=max_running, arrive_at=arrive_at)
    assert_served_as_the_reference(params, requests, out)
    assert len(s.allocator._free) == blocks - 1 and s.slots.in_use == 0
    g = s.kv_gauges()
    assert g["cca_slots_total"] == s.slots.num_slots - 1 == max_running and g["cca_slots_in_use"] == 0 and "ssm_slots_total" not in g
    spans = [(n, a) for n, _, _, _, a in s.flight.log.spans]
    assert sum(n == "sched.slots" for n, _ in spans) == g["cca_slot_allocs_total"]
    steps = [a for n, a in spans if n == "sched.step" and a and "kind" in a]
    assert all(a["cca_rows"] == a["rows"] + (a["kind"] == "mixed") and 0 < a["cca_slots"] <= max_running for a in steps)
    counted = [a for a in steps if "experts_visited" in a]
    assert counted and all(a["experts_visited"] <= a["held_assignments"] and a["skipped_rows"] >= 0 for a in counted)
    windows = [a for a in counted if a["kind"] == "decode_multi"]
    # A row a layer a step: an expert or the skip (an iteration that also prefilled adds that dispatch's rows to its entry).
    assert windows and all((a["held_assignments"] + a["skipped_rows"]) % L == 0 for a in windows)
    assert all(a["held_assignments"] + a["skipped_rows"] >= a["key"][0] * L * a["rows"] for a in windows)
    assert any(a["held_assignments"] + a["skipped_rows"] == a["key"][0] * L * a["rows"] for a in windows)
    assert g["moe_skipped_rows_total"] == sum(a["skipped_rows"] for a in counted) > 0
    if "allocs" in want:
        assert g["cca_slot_allocs_total"] == want["allocs"] and s.preempt_total == 0
    if want.get("mixed"):
        assert any(a["kind"] == "mixed" and a["decode"] >= 1 for a in steps)
    if want.get("preempted"):
        assert s.preempt_total >= 1 and g["cca_preempt_recomputes_total"] == s.preempt_total
        assert g["cca_slot_allocs_total"] == len(requests) + s.preempt_total
        _, calm = serve(params, requests, num_blocks=64, max_running=max_running)  # the same requests, never preempted
        assert calm == out
    assert "cca_slots_in_use" in s.debug_state()["block_pool"]


def test_debug_state_names_each_sequences_slot_and_warmup_builds_the_slot_program(params):
    sc = SchedulerConfig(num_blocks=32, max_running=2, prefill_buckets=[16], decode_buckets=[4], max_prefill_chunk=16,
                         num_scheduler_steps=4)
    s = Scheduler(CFG, params, sc, dtype=jnp.float32)
    assert s.sc.enable_prefix_caching is False and not s._supports_chunk_admit and s.slots.num_slots == 3
    assert s.warmup(ctx_tokens=64) > 0 and ("open_slot",) in s.flight._exec_keys
    assert not np.any(np.asarray(s.cache.v.slots[:, 1:]))  # warm-up wrote the scratch slot alone
    s.add_request("a", [3, 4, 5], SamplingParams(temperature=0.0), StopConditions(max_tokens=6, ignore_eos=True))
    s.step()
    info = s.debug_state()["running"][0]
    assert info["state_slot"] == 1 and int(s.cache.k.slot_of[s.running[0].block_ids[0]]) == 1
    assert s._kv_cache_bytes == 2 * L * 32 * CFG.block_size * CFG.kv_size * 4  # every layer's pool


# --- refusals ----------------------------------------------------------------------


def _bare(params, **kw):
    return Scheduler(CFG, params, SchedulerConfig(num_blocks=16, max_running=2, **kw), dtype=jnp.float32)


REFUSALS = {
    "kvbm-tiers": lambda s, p: s.attach_kvbm(object()),
    "speculation": lambda s, p: s.attach_draft(get_config("tiny"), None),
    "export": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(), keep_blocks_on_finish=True),
    "injection": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(), prefilled={"blocks": []}),
    "take-export": lambda s, p: s.take_export("x"),
    "prefix-registration": lambda s, p: (setattr(s.sc, "enable_prefix_caching", True),
                                         s._register_full_blocks(type("S", (), {"block_hashes": [1]})())),
    "prefix-matching": lambda s, p: s._match_prefix_tiers(None),
    "wave-admission-program": lambda s, p: llama.chunk_decode(p, CFG, None, None, jnp.zeros((1, 4), jnp.int32), None, None, None),
    "a-mesh": lambda s, p: Scheduler(CFG, p, SchedulerConfig(num_blocks=16), mesh=object()),
    "a-sharded-cache": lambda s, p: KvCacheArrays.create(CFG, 8, num_slots=3, sharding=object()),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_built_for_the_kind_is_refused_by_name(params, what):
    s = _bare(params)
    with pytest.raises(NotImplementedError, match="layer_types"):
        REFUSALS[what](s, params)
