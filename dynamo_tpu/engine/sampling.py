"""Jit-compatible token sampling: greedy / temperature / top-k / top-p.

Sampling parameters arrive per-request (ref: protocols/common SamplingOptions,
SURVEY.md §2b protocols); the scheduler batches them into per-slot arrays so
one compiled sampler serves mixed-parameter batches.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp


@dataclass
class SamplingParams:
    """Host-side per-request sampling options."""

    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0
    # Per-request PRNG: same seed + same prompt ⇒ same sample sequence,
    # independent of batch composition (the key folds in the per-request
    # token position, not the global step counter).
    seed: Optional[int] = None
    # OpenAI penalties over generated tokens (vLLM semantics: counts cover
    # the OUTPUT so far, not the prompt). Applied to logits before
    # temperature/top-k/top-p — ref: protocols/common SamplingOptions +
    # protocols/openai/validate.rs.
    frequency_penalty: float = 0.0
    presence_penalty: float = 0.0
    # Return the chosen token's log-probability with each step.
    logprobs: bool = False
    # Number of top-alternative (token, logprob) pairs to return per step
    # (OpenAI ``top_logprobs``). Served from the same fused sampling
    # dispatch with a STATIC candidate cap (TOP_LOGPROBS_CAP) so every
    # request shares one executable; implies ``logprobs``.
    top_logprobs: int = 0
    # Per-request processors (dynamo_tpu.logits_processing) — host path.
    logits_processors: List = field(default_factory=list)

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    @property
    def has_penalties(self) -> bool:
        return self.frequency_penalty != 0.0 or self.presence_penalty != 0.0


def pack_param_rows(samplings: List["SamplingParams"], bucket: int):
    """Pack per-request sampling params into the sampler's per-slot numpy
    rows, padded to ``bucket``. Pad rows are greedy (temperature 0.0,
    top_p 1.0) so all-greedy batches hit the sampler's argmax fast path
    regardless of bucket padding. One packing rule for every batched
    sampler call site: single-step decode, multi-step windows, spec-decode
    rounds, wave admission, and mixed prefill+decode steps — a mixed step
    samples only at each sequence's last row, and these rows ARE those."""
    import numpy as np

    temps = np.zeros((bucket,), dtype=np.float32)
    top_ks = np.zeros((bucket,), dtype=np.int32)
    top_ps = np.ones((bucket,), dtype=np.float32)
    for i, s in enumerate(samplings):
        temps[i] = s.temperature
        top_ks[i] = s.top_k
        top_ps[i] = s.top_p
    return temps, top_ks, top_ps


# Top-k/top-p thresholds are resolved inside the best-SAMPLE_WINDOW logits
# (lax.top_k) instead of a full-vocab sort: two O(V log V) sorts per step
# cost ~7 ms on a 128k vocab (v5e, b8) — more than the whole 1B forward
# pass. The windowed result is checked for exactness per row: when any row
# requests top_k > SAMPLE_WINDOW, or its window holds less than ``top_p``
# probability mass, the batch falls back to the exact full-vocab sort for
# that step (runtime lax.cond — the fast path stays sort-free). Sampling
# semantics therefore always match the requested top-k/top-p exactly.
SAMPLE_WINDOW = 64


def _exact_thresholds(scaled, lse, top_k, top_p):
    """Full-vocab top-k/top-p truncation thresholds (one descending sort)."""
    V = scaled.shape[-1]
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V] descending
    k_idx = jnp.clip(jnp.where(top_k > 0, top_k, V) - 1, 0, V - 1)
    kth = jnp.take_along_axis(srt, k_idx[:, None], axis=1)[:, 0]
    k_thresh = jnp.where(top_k > 0, kth, -jnp.inf)

    probs = jnp.exp(srt - lse)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]
    min_kept = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
    p_thresh = jnp.where(top_p < 1.0, min_kept, -jnp.inf)
    return jnp.maximum(k_thresh, p_thresh)


def filtered_probs_rows(
    logits: jax.Array,  # [B, V] f32
    temps: jax.Array,  # [B] f32 (0 = greedy)
    top_ks: jax.Array,  # [B] i32 (0 = off)
    top_ps: jax.Array,  # [B] f32 (1 = off)
) -> jax.Array:
    """THE reference sampling distribution: temperature scale + exact
    top-k/top-p truncation (``_exact_thresholds``) + softmax, per row.
    Greedy rows (temperature 0) return a one-hot argmax distribution.

    spec_decode's verifier filters the draft's and the target's logits
    through this one implementation — tie-breaking (the ``>= thresh`` keep
    rule after one descending sort) is bit-identical on both sides, so
    draft-vs-verify distribution agreement holds exactly."""
    V = logits.shape[-1]
    safe_t = jnp.where(temps > 0, temps, 1.0)
    scaled = logits / safe_t[:, None]
    lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
    thresh = _exact_thresholds(scaled, lse, top_ks, top_ps)
    masked = jnp.where(scaled >= thresh[:, None], scaled, -jnp.inf)
    probs = jax.nn.softmax(masked, axis=-1)
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), V, dtype=probs.dtype)
    return jnp.where((temps > 0)[:, None], probs, greedy)


def sample_batch(
    logits: jax.Array,  # [B, V] f32
    temperature: jax.Array,  # [B] f32 (0 = greedy)
    top_k: jax.Array,  # [B] i32 (0 = off)
    top_p: jax.Array,  # [B] f32 (1 = off)
    key: jax.Array,
    row_keys: Optional[jax.Array] = None,  # [B, 2] per-row PRNG keys (seeded requests)
) -> jax.Array:
    """Sample one token per row honouring per-row parameters. Greedy rows
    (temperature 0) take argmax; all-greedy batches skip sampling entirely
    (runtime branch — the common temperature=0 serving case). With
    ``row_keys`` each row draws from its own key (per-request seeds)."""
    B, V = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sample_path(_):
        safe_temp = jnp.where(temperature > 0, temperature, 1.0)
        scaled = logits / safe_temp[:, None]
        cap = min(SAMPLE_WINDOW, V)
        top_vals = jax.lax.top_k(scaled, cap)[0]  # [B, cap] descending
        lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
        probs_top = jnp.exp(top_vals - lse)  # true probabilities of window
        cum = jnp.cumsum(probs_top, axis=-1)

        def windowed(_):
            # top-k threshold: the k-th largest (k ≤ window by construction).
            k_idx = jnp.clip(jnp.where(top_k > 0, top_k, cap) - 1, 0, cap - 1)
            kth = jnp.take_along_axis(top_vals, k_idx[:, None], axis=1)[:, 0]
            k_thresh = jnp.where(top_k > 0, kth, -jnp.inf)
            # top-p threshold: smallest prob among the nucleus.
            keep = (cum - probs_top) < top_p[:, None]  # keep while prior mass < p
            min_kept = jnp.min(jnp.where(keep, top_vals, jnp.inf), axis=-1)
            p_thresh = jnp.where(top_p < 1.0, min_kept, -jnp.inf)
            return jnp.maximum(k_thresh, p_thresh)

        # Window is exact for a row iff requested k fits and (top_p off or
        # the window holds ≥ top_p of the probability mass).
        sampling_row = temperature > 0
        k_fits = (top_k <= 0) | (top_k <= cap)
        p_fits = (top_p >= 1.0) | (cum[:, -1] >= top_p)
        window_exact = jnp.all(~sampling_row | (k_fits & p_fits)) | (cap == V)

        thresh = jax.lax.cond(
            window_exact, windowed, lambda _: _exact_thresholds(scaled, lse, top_k, top_p), None
        )
        masked = jnp.where(scaled >= thresh[:, None], scaled, -jnp.inf)
        if row_keys is not None:
            sampled = jax.vmap(
                lambda k, row: jax.random.categorical(k, row)
            )(row_keys, masked).astype(jnp.int32)
        else:
            sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy_tok)

    return jax.lax.cond(jnp.any(temperature > 0), sample_path, lambda _: greedy_tok, None)


def apply_token_masks(
    logits: jax.Array,  # [B, V] f32
    pool: jax.Array,  # [P, ceil(V/32)] uint32 — shared guided mask pool
    row_ids: jax.Array,  # [B] i32 — pool row per batch row (0 = allow-all)
) -> jax.Array:
    """Grammar-constrained decoding's jit-side hook: gather each row's
    allowed-token bitmask from the device mask pool by FSM-state row id and
    add ``-inf`` to disallowed logits. Row 0 of the pool is the reserved
    allow-everything row, so unguided rows in a mixed batch pass through the
    same executable unchanged (llm/guided/processor.py owns the pool)."""
    B, V = logits.shape
    rows = pool[row_ids]  # [B, W]
    idx = jnp.arange(V, dtype=jnp.int32)
    words = rows[:, idx >> 5]  # [B, V] uint32
    bit = jnp.right_shift(words, (idx & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(bit.astype(bool), logits, -jnp.inf)


def guided_sample_batch(
    logits: jax.Array,  # [B, V] f32
    pool: jax.Array,  # [P, W] uint32
    k_rows: jax.Array,  # [2, B] i32: row 0 = top_k, row 1 = mask-pool row ids
    temperature: jax.Array,  # [B] f32
    top_p: jax.Array,  # [B] f32
    key: jax.Array,
    row_keys: Optional[jax.Array] = None,
) -> jax.Array:
    """Mask-gather fused with the batched sampler: ONE dispatch per step for
    guided batches, identical semantics to ``sample_batch`` over the
    FSM-allowed token set. ``top_k`` and the pool row ids ride one packed
    i32 upload, so a guided step pays the same number of per-step
    host→device transfers as an unguided one (measured: each small upload
    costs ~0.1 ms of dispatch on CPU-class links — the whole guided margin)."""
    return sample_batch(
        apply_token_masks(logits, pool, k_rows[1]), temperature, k_rows[0], top_p, key, row_keys
    )


def sample_batch_logprobs(
    logits: jax.Array,  # [B, V] f32
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    key: jax.Array,
    row_keys: Optional[jax.Array] = None,
) -> tuple:
    """``sample_batch`` with the chosen-token log-probabilities folded into
    the SAME dispatch → (tokens [B] i32, logprobs [B] f32). When any row
    requests logprobs the scheduler used to issue a separate
    ``compute_logprobs`` device op (+ its own host sync) per step; fusing it
    here keeps logprobs batches at one dispatch and one readback, same as
    plain ones."""
    tok = sample_batch(logits, temperature, top_k, top_p, key, row_keys)
    return tok, compute_logprobs(logits, tok)


def guided_sample_batch_logprobs(
    logits: jax.Array,
    pool: jax.Array,
    k_rows: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    key: jax.Array,
    row_keys: Optional[jax.Array] = None,
) -> tuple:
    """``guided_sample_batch`` + fused logprobs (see sample_batch_logprobs).
    Logprobs are of the MASKED distribution — the model's renormalized
    probability over the FSM-allowed set, which is what the row actually
    sampled from."""
    masked = apply_token_masks(logits, pool, k_rows[1])
    tok = sample_batch(masked, temperature, k_rows[0], top_p, key, row_keys)
    return tok, compute_logprobs(masked, tok)


@jax.jit
def apply_penalties(
    logits: jax.Array,  # [B, V] f32
    hist: jax.Array,  # [B, H] i32 — generated-token history, padded
    hist_len: jax.Array,  # [B] i32 — valid history per row
    frequency_penalty: jax.Array,  # [B] f32
    presence_penalty: jax.Array,  # [B] f32
) -> jax.Array:
    """Batched OpenAI frequency/presence penalties in ONE dispatch:
    per-row output-token counts built by scatter-add from the padded
    history, then ``logits - freq·count - pres·(count > 0)``. Host cost is
    the [B, H] history upload (H = longest output, bucketed); the [B, V]
    count tensor exists only on device. vLLM semantics: counts cover
    generated tokens only, not the prompt."""
    B, V = logits.shape
    H = hist.shape[1]
    valid = jnp.arange(H, dtype=jnp.int32)[None, :] < hist_len[:, None]
    tok = jnp.where(valid, hist, 0)
    counts = jnp.zeros((B, V), jnp.float32).at[
        jnp.arange(B, dtype=jnp.int32)[:, None], tok
    ].add(valid.astype(jnp.float32))  # padded rows add 0 to token 0
    return logits - frequency_penalty[:, None] * counts - presence_penalty[:, None] * (
        counts > 0
    )


@jax.jit
def make_row_keys(
    base_key: jax.Array,
    seeds: jax.Array,  # [B] i32 (0 where unseeded)
    positions: jax.Array,  # [B] i32 per-request token position
    has_seed: jax.Array,  # [B] bool
) -> jax.Array:
    """Per-row sampling keys in ONE dispatch (a per-row Python loop of
    fold_in calls costs ~B tiny dispatches on the decode hot path): seeded
    rows fold their request position into PRNGKey(seed) — batch-composition
    independent — while unseeded rows fold their row index into the step's
    base key."""

    def mk(seed, pos, i, has):
        seeded = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        unseeded = jax.random.fold_in(base_key, i)
        return jnp.where(has, seeded, unseeded)

    idx = jnp.arange(seeds.shape[0], dtype=jnp.int32)
    return jax.vmap(mk)(seeds, positions, idx, has_seed)


def compute_logprobs(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Log-probability of chosen tokens. logits [B, V], tokens [B] → [B]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, None], axis=1)[:, 0]


# Static per-executable candidate count for top-logprobs rows. Requests ask
# for k ∈ [1, TOP_LOGPROBS_CAP] (the OpenAI bound is 20) but the dispatch
# always computes the cap: a traced k would compile one executable per
# distinct requested k. Rows trim to their own k on the host.
TOP_LOGPROBS_CAP = 20


def compute_topk_logprobs(logits: jax.Array, tokens: jax.Array) -> tuple:
    """Chosen-token logprob plus the TOP_LOGPROBS_CAP most likely tokens'
    ids and logprobs in one op group — logits [B, V], tokens [B] →
    (chosen [B] f32, top_ids [B, CAP] i32, top_lps [B, CAP] f32)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(logp, tokens[:, None], axis=1)[:, 0]
    cap = min(TOP_LOGPROBS_CAP, logits.shape[-1])
    top_lps, top_ids = jax.lax.top_k(logp, cap)
    return chosen, top_ids.astype(jnp.int32), top_lps


def sample_batch_top_logprobs(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    key: jax.Array,
    row_keys: Optional[jax.Array] = None,
) -> tuple:
    """``sample_batch_logprobs`` widened with the per-row top-k alternatives
    (OpenAI ``top_logprobs``) in the SAME dispatch → (tokens [B] i32,
    logprobs [B] f32, top_ids [B, CAP] i32, top_lps [B, CAP] f32). One
    executable regardless of each row's requested k (static cap)."""
    tok = sample_batch(logits, temperature, top_k, top_p, key, row_keys)
    chosen, top_ids, top_lps = compute_topk_logprobs(logits, tok)
    return tok, chosen, top_ids, top_lps


def guided_sample_batch_top_logprobs(
    logits: jax.Array,
    pool: jax.Array,
    k_rows: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    key: jax.Array,
    row_keys: Optional[jax.Array] = None,
) -> tuple:
    """``guided_sample_batch_logprobs`` + fused top-k alternatives. Like the
    lp variant, all logprobs (chosen and alternatives) are of the MASKED
    distribution — the renormalized probability over the FSM-allowed set."""
    masked = apply_token_masks(logits, pool, k_rows[1])
    tok = sample_batch(masked, temperature, k_rows[0], top_p, key, row_keys)
    chosen, top_ids, top_lps = compute_topk_logprobs(masked, tok)
    return tok, chosen, top_ids, top_lps
