"""The latent kinds on the served path ("mla_full": latent attention whose
learned indexer picks the cached rows a query attends, one pool row and one
index key a token; "mla_window": latent attention of its own sizes over a ring
of ``sliding_window`` rows in the sequence's slot, no blocks), a gate a head, a
dense first layer and sigmoid routing over experts of which a share is held.
At the ``tiny-dots3`` preset (one dense-FFN full layer, one full, three
sliding; 16 experts top-2 of which 4 held; ``index_topk`` 8, window 5), on
seeded float32 weights, against the plain reference
``benchmark/families/dots3_reference`` (which imports nothing of the program):
contexts run past the top-k and past a ring's wrap."""

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
from benchmark.families import dots3, dots3_reference as reference  # noqa: E402
from dynamo_tpu.engine.config import get_config  # noqa: E402
from dynamo_tpu.engine.kv_cache import KvCacheArrays  # noqa: E402
from dynamo_tpu.engine.models import get_module, hybrid, latent, llama  # noqa: E402
from dynamo_tpu.engine.sampling import SamplingParams  # noqa: E402
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions  # noqa: E402

CFG = get_config("tiny-dots3")
BS, TOPK, WINDOW = CFG.block_size, CFG.index_topk, CFG.sliding_window
SPEC = dict(prompt_lens=[12, 40, 20, 30, 50], chunk=16, window=4, windows=2, decode_bucket=8, num_blocks=128, max_running=8,
            limit_rel_err=1e-4, limit_group_rel_err=1e-4)


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


# --- the configuration -------------------------------------------------------------


def test_config_states_pool_rows_for_the_full_layers_and_rings_for_the_window_layers():
    assert get_module(CFG) is hybrid and CFG.is_latent and CFG.is_hybrid
    assert (CFG.num_attention_layers, CFG.num_window_layers) == (2, 3)
    assert CFG.latent_groups == (("mla_full", True, 1), ("mla_full", False, 1), ("mla_window", False, 3))
    full, win = CFG.latent_sizes("mla_full"), CFG.latent_sizes("mla_window")
    assert (full.row, win.row) == (32 + 8, 48 + 8) and (full.heads, win.heads) == (4, 2)
    cache = KvCacheArrays.create(CFG, 16, dtype=jnp.float32, num_slots=4)
    assert cache.k.pool.shape == (2, 16, BS, 128) and full.row == 40 and cache.v.pool.shape == (2, 16, BS, CFG.index_head_dim)
    assert cache.v.slots.shape == (3, 4, WINDOW, win.row) and cache.k.slots.size == 0 and cache.k.slot_of.shape == (16,)
    assert llama.resolve_attention_impl(CFG, cache.k.pool) == "gather" and llama.resolve_prefill_impl(CFG) == "xla"


@pytest.mark.parametrize("bad,error", [
    (dict(layer_types=("mla_full", "attention", "mla_window", "mla_window", "mla_window")), "beside layers of another kind"),
    (dict(use_rope=False), "use_rope=False"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(weight_dtype="int8"), "int8"),
    (dict(attention_impl="paged"), "paged"),
    (dict(index_topk=0), "index_topk"),
    (dict(sliding_window=0), "sliding_window"),
    (dict(swa_kv_lora_rank=0), "ranks"),
    (dict(dense_intermediate_size=0), "dense_intermediate_size"),
    (dict(router_kind="softmax"), "router_kind"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_config_refuses_what_the_latent_programs_cannot_be(bad, error):
    with pytest.raises((ValueError, NotImplementedError), match=error):
        CFG.replace(**bad)


@pytest.mark.parametrize("field", [dict(router_kind="sigmoid"), dict(first_k_dense=1, dense_intermediate_size=8),
                                   dict(sliding_window=5), dict(index_topk=8), dict(attention_gate=True)], ids=lambda f: next(iter(f)))
@pytest.mark.parametrize("preset", ["tiny", "tiny-hybrid"])
def test_fields_of_these_kinds_need_latent_layers(preset, field):
    with pytest.raises((ValueError, NotImplementedError)):
        get_config(preset).replace(**field)


# --- against the reference -----------------------------------------------------------


def test_step_programs_agree_with_the_reference(params):
    """``prefill`` -> ``decode``, ``mixed_step`` and ``decode_multi`` on the pool
    and the rings, contexts of up to 70 rows (top-k 8, ring 5): every compared
    position's logits are the reference's, and every control reads far off."""
    r = parity.check(dots3, params, CFG, 3, SPEC, controls=reference.CONTROLS, fault=True)
    assert r["ok"] and r["rel_err"] < 1e-5 and r["group_rel_err"] < 1e-5 and r["sampled_is_argmax"]
    assert set(r["groups"]) == {"prefill", "body", "chosen", "rows", "windows"}
    assert all(c["fails"] and c["rel_err"] > 0.05 for c in r["controls"].values())
    assert r["fault_control"]["fails"] and r["fault_control"]["group_rel_err"] > 0.1


def _layer0(params, tokens):
    """Layer 0's normed input, queries and index parts of ``tokens``, as the program computes them."""
    lp = jax.tree.map(lambda a: a[0], params["mla_full"])
    pos = jnp.arange(len(tokens), dtype=jnp.int32)
    x = llama.rms_norm(params["embed"][jnp.asarray(tokens)], lp["attn_norm"], CFG.rms_norm_eps)
    q, row, c_q = latent.project(CFG, CFG.latent_sizes("mla_full"), lp, x, pos)
    qi, w = latent.index_query(CFG, lp, c_q, x, pos)
    return pos, latent.index_scores(qi, w, latent.index_key(CFG, lp, x, pos))


def test_the_indexers_set_is_the_references(params):
    """Every query's chosen rows (both forms of the choice) are the reference's set: ``min(t + 1, index_topk)`` of them."""
    tokens = np.random.default_rng(5).integers(1, CFG.vocab_size, size=60)
    sets = []
    reference.forward(params, CFG, [tokens], [[0]], chosen=sets)
    want = sets[0][0]  # layer 0: [T, T]
    assert np.array_equal(want.sum(axis=1), np.minimum(np.arange(60) + 1, TOPK))
    pos, scores = _layer0(params, tokens)
    valid = pos[None, :] <= pos[:, None]
    assert np.array_equal(np.asarray(latent.topk_mask(scores, valid, TOPK)), want)
    idx, chosen = latent.topk_rows(scores, valid, TOPK)
    got = np.zeros_like(want)
    got[np.arange(60)[:, None], np.asarray(idx)] = np.asarray(chosen)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k", [1, 3, 8])
def test_both_forms_of_the_choice_cut_ties_by_position(k):
    """Scores with many equal values, zeros of both signs among them: the threshold form and ``lax.top_k`` pick one set."""
    rng = np.random.default_rng(k)
    scores = jnp.asarray(rng.choice([-1.0, -0.0, 0.0, 0.5, 2.0], size=(16, 24)).astype(np.float32))
    scores = jnp.where(scores == 0.0, 0.0, scores)  # as index_scores leaves them
    valid = jnp.asarray(rng.random((16, 24)) < 0.7)
    mask = np.asarray(latent.topk_mask(scores, valid, k))
    idx, chosen = latent.topk_rows(scores, valid, k)
    got = np.zeros_like(mask)
    got[np.arange(16)[:, None], np.asarray(idx)] |= np.asarray(chosen)
    assert np.array_equal(mask, got) and np.array_equal(mask.sum(1), np.minimum(np.asarray(valid).sum(1), k))
    assert not np.any(mask & ~np.asarray(valid))


@pytest.mark.parametrize("pieces", [(16, 16, 16), (16, 9), (3, 16, 1), (7,)], ids=lambda p: "+".join(map(str, p)))
def test_the_ring_is_a_masked_full_cache(pieces):
    """Chunks and then length-1 rows through a ring of 5 rows: every query's
    latents are those of attention over ALL the rows so far under the window
    mask, whatever the chunking (a chunk wider than the ring, a wrap inside
    one, padding that must not be written)."""
    z = CFG.latent_sizes("mla_window")
    rng = np.random.default_rng(sum(pieces))
    n = sum(pieces) + 6
    q = jnp.asarray(rng.normal(size=(n, z.heads, z.row)).astype(np.float32))
    rows = jnp.asarray(rng.normal(size=(n, z.row)).astype(np.float32))
    at = jnp.arange(n)
    ahead = at[:, None] - at[None, :]
    want = latent.attend(q, rows, (ahead >= 0) & (ahead < WINDOW), z.kv_rank, (z.nope + z.rope) ** -0.5)
    rings = jnp.full((3, WINDOW, z.row), 7.0, jnp.float32)  # a slot another sequence left: nothing of it may be read
    got, start = [], 0
    for length in pieces:
        pad = lambda a: jnp.concatenate([a[start:start + length], jnp.ones((16 - length, *a.shape[1:]), a.dtype)])  # noqa: E731
        pos = start + jnp.arange(16, dtype=jnp.int32)
        lat, rings = latent.window_chunk(z, pad(q), pad(rows), pos, jnp.int32(1), jnp.int32(length), rings)
        got.append(lat[:length])
        start += length
    for t in range(start, n):
        lat, rings = latent.window_rows(z, q[t:t + 1].repeat(2, 0), rows[t:t + 1].repeat(2, 0), jnp.array([t, 0], jnp.int32),
                                        jnp.array([1, 0], jnp.int32), jnp.array([True, False]), rings)
        got.append(lat[:1])
    np.testing.assert_allclose(np.concatenate(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(rings[2]) == 7.0)  # another sequence's slot: untouched (slot 0 is the padded row's scratch)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four chips share the expert layer by its experts (4 of 16 each): the
    four shares' routed parts and the shared expert counted once are the uncut
    reference's layer."""
    E, held = CFG.num_experts, CFG.num_experts_held
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(24, CFG.hidden_size)).astype(np.float32))
    base = jax.tree.map(lambda a: a[0], params["layers"])
    stacks = {n: jnp.asarray(rng.normal(size=(E, *base[n].shape[1:])).astype(np.float32)) * 0.2 for n in ("w_gate", "w_up", "w_down")}
    whole = reference.expert_layer(u, {**base, **stacks}, CFG, first=0, held=E)
    route = lambda x, lp: hybrid._sigmoid_route(CFG, x, lp)  # noqa: E731
    total = reference._swiglu(u, base["shared_gate"], base["shared_up"], base["shared_down"], act=None)
    counts = []
    for first in range(0, E, held):
        share = CFG.replace(first_expert_held=first)
        lp = {**base, **{n: w[first:first + held] for n, w in stacks.items()}}
        out, n_held, _ = llama._moe_held(u, lp, share, route=route)
        ref = reference.expert_layer(u, lp, share, shared=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)
        total = total + out
        counts.append(int(n_held))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=1e-4, atol=1e-5)
    assert sum(counts) == 24 * CFG.num_experts_per_tok and min(counts) > 0  # every assignment on one share, once


def test_the_old_latent_family_attends_through_the_same_product():
    """``mla.py``'s rows go through ``latent.attend`` and ``latent.absorb``: one absorbed product in the tree."""
    from dynamo_tpu.engine.models import mla

    c = get_config("tiny-mla")
    p = mla.init_params(c, jax.random.PRNGKey(1), dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], p["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (6, c.hidden_size), jnp.float32)
    pos = jnp.arange(6, dtype=jnp.int32)
    q_eff, q_rope = mla._project_q(x, lp, c, pos)
    rows = mla._latent_kv(x, lp, c, pos)
    mask = pos[None, :] <= pos[:, None]
    got = mla._attend_latent(q_eff, q_rope, rows, mask, lp, c)
    lat = latent.attend(jnp.concatenate([q_eff, q_rope], -1), rows, mask, c.kv_lora_rank, (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5)
    want = jnp.einsum("thr,hrv->thv", lat, lp["w_uv"]).reshape(6, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


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
        held = sorted(q.state_slot for q in s.running + s.waiting if q.block_ids)
        assert 0 not in held and len(set(held)) == len(held) == s.slots.in_use  # one slot a live sequence, none twice
        assert step < 400
    return s, out


CASES = {
    # name: (requests {id: (prompt length, answer length)}, arrivals, blocks, max_running, what must have happened)
    "one-prompt-over-three-chunks": ({"a": (37, 9)}, {}, 64, 3, dict(allocs=1)),
    "slots-reused-after-a-finish": ({"a": (37, 10), "b": (5, 4), "c": (20, 12), "d": (33, 6), "e": (9, 14)}, {}, 64, 2, dict(allocs=5)),
    "a-prompt-rides-beside-decode-rows": ({"a": (12, 20), "b": (45, 8), "c": (17, 12)}, {"b": 2, "c": 3}, 64, 3, dict(mixed=True)),
    "preempted-and-recomputed": ({"a": (20, 30), "b": (40, 30), "c": (25, 30)}, {}, 12, 3, dict(preempted=True)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_scheduler_serves_the_kinds_exactly(params, case):
    """A ring slot and a table from admission to finish, rings carried from
    chunk to chunk and through mixed steps and multi-step windows, dropped at
    preemption and recomputed: what the scheduler serves is the reference's
    own greedy continuation, every slot and block goes back, and the step
    entries count exactly ``min(t + 1, index_topk)`` chosen rows a row and
    full layer."""
    shape, arrive_at, blocks, max_running, want = CASES[case]
    rng = np.random.default_rng(1)
    requests = {rid: (rng.integers(1, CFG.vocab_size, size=n).tolist(), m) for rid, (n, m) in shape.items()}
    s, out = serve(params, requests, num_blocks=blocks, max_running=max_running, arrive_at=arrive_at)
    for rid, (prompt, n) in requests.items():
        assert len(out[rid]) == n
        full = np.asarray(list(prompt) + out[rid])
        lg = reference.forward(params, CFG, [full[:-1]], [list(range(len(prompt) - 1, len(full) - 1))])[0]
        gap = [float(lg[i].max() - lg[i][tok]) for i, tok in enumerate(out[rid])]
        assert max(gap) < 1e-4, (rid, int(np.argmax(gap)), max(gap))
    assert len(s.allocator._free) == blocks - 1 and s.slots.in_use == 0
    g = s.kv_gauges()
    assert g["window_slots_total"] == max_running and g["window_slots_in_use"] == 0 and "ssm_slots_total" not in g
    spans = [(n, a) for n, _, _, _, a in s.flight.log.spans]
    assert sum(n == "sched.slots" for n, _ in spans) == g["window_slot_allocs_total"]
    steps = [a for n, a in spans if n == "sched.step" and a and "kind" in a]
    assert all(a["window_rows"] == a["rows"] + (a["kind"] == "mixed") and 0 < a["window_slots"] <= max_running for a in steps)
    counted = [a for a in steps if "indexed_rows" in a]
    assert counted and all(0 < a["indexed_rows"] <= a["index_ctx"] and a["experts_visited"] <= a["held_assignments"] for a in counted
                           if a["kind"] != "prefill")
    single = [a for a in counted if a["kind"] == "decode" and a.get("dispatches", 1) == 1]
    for a in single:  # ctx: the rows' lengths with their current tokens = the rows each indexer scores
        assert a["index_ctx"] == 2 * a["ctx"] and a["indexed_rows"] <= 2 * TOPK * a["rows"]
    assert any(a["indexed_rows"] < a["index_ctx"] for a in counted)  # the indexer chose
    if "allocs" in want:
        assert g["window_slot_allocs_total"] == want["allocs"] and s.preempt_total == 0
    if want.get("mixed"):
        assert any(a["kind"] == "mixed" and a["decode"] >= 1 for a in steps)
    if want.get("preempted"):
        assert s.preempt_total >= 1 and g["window_preempt_recomputes_total"] == s.preempt_total
        _, calm = serve(params, requests, num_blocks=64, max_running=max_running)  # the same requests, never preempted
        assert calm == out
    assert "window_slots_in_use" in s.debug_state()["block_pool"]


def test_warmup_builds_the_slot_program_and_the_pool_is_counted_alone(params):
    sc = SchedulerConfig(num_blocks=32, max_running=2, prefill_buckets=[16], decode_buckets=[4], max_prefill_chunk=16,
                         num_scheduler_steps=4)
    s = Scheduler(CFG, params, sc, dtype=jnp.float32)
    assert s.sc.enable_prefix_caching is False and not s._supports_chunk_admit and s.slots.num_slots == 3
    assert s._attn_impl == "gather"
    assert s.warmup(ctx_tokens=64) > 0 and ("open_slot",) in s.flight._exec_keys
    assert not np.any(np.asarray(s.cache.v.slots[:, 1:]))  # warm-up wrote the scratch slot alone
    s.add_request("a", [3, 4, 5], SamplingParams(temperature=0.0), StopConditions(max_tokens=6, ignore_eos=True))
    s.step()
    assert s.debug_state()["running"][0]["state_slot"] == 1 and int(s.cache.k.slot_of[s.running[0].block_ids[0]]) == 1
    full = CFG.latent_sizes("mla_full")
    assert s._kv_cache_bytes == 2 * 32 * BS * (128 + CFG.index_head_dim) * 4  # the full layers' rows and keys: no blocks for the rings


# --- refusals ----------------------------------------------------------------------


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
    "embeddings": lambda s, p: llama.embed(p, CFG, jnp.zeros((4,), jnp.int32), jnp.int32(4)),
    "image-and-audio-parts": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(),
                                                         mm_features=np.zeros((1, CFG.hidden_size), np.float32)),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_built_for_the_kinds_is_refused_by_name(params, what):
    s = Scheduler(CFG, params, SchedulerConfig(num_blocks=16, max_running=2), dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="layer_types"):
        REFUSALS[what](s, params)
