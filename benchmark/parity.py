"""The output check: the programs the cells serve against the plain reference.

A seeded handful of sequences is taken through the program's own step
programs in the order a scheduler would, on one paged cache, with the kernels
"auto" resolves to on this backend (on a TPU: the ragged megakernel and the
flash kernel) and the served shapes (the configuration's chunk, decode bucket
and window):

1. ``llama.prefill`` of the first sequence (every position's logits);
2. one ``llama.mixed_step`` per chunk of each later prompt, the sequences
   already in the cache riding along as decode rows (teacher-forced from the
   seed). A prompt longer than the chunk takes several steps, all but the
   first with a cached prefix (``has_prefix=True``);
3. ``llama.decode_multi`` windows over all sequences: on-device greedy
   sampling, the window-local KV and its fused scatter, the second window
   reading what the first wrote.

The reference computes the same positions from one dense float32 forward pass
over each whole sequence. Its inputs are the seeded weights and the token ids;
the ids a window sampled are fed to it as they were fed back on the device (a
window cannot be teacher-forced), and each must be the argmax of the window's
own logits. Logits are compared, never tokens: with random weights the largest
logit changes on rounding.

Two numbers are held to limits, both from ``|got - ref|_2 / |ref|_2`` of each
compared position. ``rel_err`` is the spec's ``quantile`` (default: the median)
over all positions: the step down in precision moves every position, and this
number is steady from seed to seed. ``group_rel_err`` is the largest, over
groups of positions, of the group's median, where a group is one program path
(``prefill``, ``chunk_fresh``, ``chunk_prefix``, ``mixed_decode``) or one
sequence's window rows (``window_s<i>``): a fault in one path or in one
sequence's cache rows cannot hide behind the others. With sparse experts a
token that the bfloat16 program routes to another expert than the float32
reference raises the error of every later position of its sequence, so a small
group swings with the seed and ``group_rel_err`` has a limit of its own, set
against a control of its own: the program with the cached-prefix chunk handed
another sequence's block table (``fault=True``).
"""

from __future__ import annotations

import numpy as np

from benchmark import reference


def _pieces(length: int, chunk: int):
    return [(s, min(chunk, length - s)) for s in range(0, length, chunk)]


def sample_inputs(mc, seed: int, spec: dict):
    """Prompts and teacher-forced decode inputs from the seed. Sequence ``i``
    decodes one forced token in every mixed step after its own prompt is in
    the cache, and one more opens the first window."""
    rs = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    lens = [int(n) for n in spec["prompt_lens"]]
    chunk = int(spec["chunk"])
    if lens[0] > chunk:
        raise ValueError("the first sequence is prefilled in one piece: prompt_lens[0] <= chunk")
    steps_after = [sum(len(_pieces(n, chunk)) for n in lens[i + 1:]) for i in range(len(lens))]
    prompts = [rs.integers(1, mc.vocab_size, size=n).astype(np.int32) for n in lens]
    forced = [rs.integers(1, mc.vocab_size, size=m + 1).astype(np.int32) for m in steps_after]
    return lens, prompts, forced


def program_logits(params, mc, spec: dict, lens, prompts, forced, fault: bool = False):
    """Runs the programs. Returns ``(rows, sampled, sampled_is_argmax)``:
    ``rows`` is a list of ``(group, sequence, position, logits [V])`` and
    ``sampled[i]`` the ids the windows fed back for sequence ``i``. With
    ``fault`` every chunk with a cached prefix is handed the block table of
    the sequence before it: the control of ``group_rel_err``."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import resolve_moe_dispatch
    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import llama

    cfg = resolve_moe_dispatch(mc, 1)
    use_flash = llama.resolve_prefill_impl(cfg) == "flash"
    chunk, window, windows, batch = (int(spec[k]) for k in ("chunk", "window", "windows", "decode_bucket"))
    n, bs = len(lens), cfg.block_size
    if n > batch:
        raise ValueError("more sequences than decode lanes")
    longest = max(lens[i] + len(forced[i]) for i in range(n)) + windows * window
    W = 1 << (-(-(longest + 1) // bs) - 1).bit_length()  # table width: a power of two, as the scheduler's rungs
    cache = KvCacheArrays.create(cfg, 1 + n * W, dtype=jnp.bfloat16)  # block 0 is the scratch block
    tables = np.zeros((batch, W), np.int32)
    for row in range(n):
        tables[row] = 1 + row * W + np.arange(W)

    prefill = jax.jit(
        lambda p, k, v, t, vl, bt: llama.prefill(
            p, cfg, k, v, t, vl, jnp.int32(0), bt, all_logits=True, use_flash=use_flash, has_prefix=False),
        donate_argnums=(1, 2),
    )
    mixed = jax.jit(
        lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, hp: llama.mixed_step(
            p, cfg, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, use_flash=use_flash, has_prefix=hp),
        donate_argnums=(1, 2), static_argnums=(11,),
    )
    multi = jax.jit(
        lambda p, k, v, t, pos, bt, act, te, tk, tp, key: llama.decode_multi(
            p, cfg, k, v, t, pos, bt, act, te, tk, tp, key, window, return_logits=True),
        donate_argnums=(1, 2),
    )

    k, v = cache.k, cache.v
    rows = []
    toks = np.zeros((chunk,), np.int32)
    toks[: lens[0]] = prompts[0]
    lg, k, v = prefill(params, k, v, jnp.asarray(toks), jnp.int32(lens[0]), jnp.asarray(tables[0]))
    lg = np.asarray(lg)
    rows += [("prefill", 0, t, lg[t]) for t in range(lens[0])]

    fed = [0] * n  # forced tokens each sequence has consumed
    for j in range(1, n):
        for start, length in _pieces(lens[j], chunk):
            toks = np.zeros((chunk,), np.int32)
            toks[:length] = prompts[j][start:start + length]
            d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)
            for i in range(j):
                d_tok[i], d_pos[i], d_act[i] = forced[i][fed[i]], lens[i] + fed[i], True
            lg, k, v = mixed(params, k, v, jnp.asarray(toks), jnp.int32(length), jnp.int32(start),
                             jnp.asarray(tables[j - 1 if fault and start > 0 else j]), jnp.asarray(d_tok),
                             jnp.asarray(d_pos), jnp.asarray(tables),
                             jnp.asarray(d_act), start > 0)
            lg = np.asarray(lg)
            rows.append(("chunk_prefix" if start > 0 else "chunk_fresh", j, start + length - 1, lg[0]))
            for i in range(j):
                rows.append(("mixed_decode", i, int(d_pos[i]), lg[1 + i]))
                fed[i] += 1

    d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)
    for i in range(n):
        d_tok[i], d_pos[i], d_act[i] = forced[i][fed[i]], lens[i] + fed[i], True
    sampled = [[] for _ in range(n)]
    is_argmax = True
    greedy = (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.float32))
    for _ in range(windows):
        out, lg, k, v = multi(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(tables),
                              jnp.asarray(d_act), *greedy, jax.random.PRNGKey(0))
        out, lg = np.asarray(out), np.asarray(lg)
        for i in range(n):
            rows += [(f"window_s{i}", i, int(d_pos[i]) + s, lg[s, i]) for s in range(window)]
            sampled[i] += out[:, i].tolist()
        is_argmax = is_argmax and bool(np.array_equal(out[:, :n], np.argmax(lg[:, :n], axis=-1)))
        d_tok, d_pos = out[-1].astype(np.int32), d_pos + window * d_act.astype(np.int32)
    del k, v, cache
    return rows, sampled, is_argmax


def _errors(rows, ref) -> list:
    """``(group, sequence, position, relative error)`` of every compared position."""
    err = reference.rel_err(np.stack([got for (_, _, _, got) in rows]),
                            np.stack([ref[seq][pos] for (_, seq, pos, _) in rows]))
    return [(g, seq, pos, float(e)) for (g, seq, pos, _), e in zip(rows, err)]


def summarize(errors, spec: dict) -> dict:
    """The two numbers compared, from every position's relative error."""
    groups: dict = {}
    for (g, _, _, e) in errors:
        groups.setdefault(g, []).append(e)
    groups = {g: float(np.median(v)) for g, v in sorted(groups.items())}
    worst = max(groups, key=groups.get)
    return {"rel_err": float(np.quantile([e for (_, _, _, e) in errors], float(spec.get("quantile", 0.5)))),
            "group_rel_err": groups[worst], "worst_group": worst, "smallest_group": min(groups.values()),
            "groups": groups}


def check(params, mc, seed: int, spec: dict, *, controls=(), fault: bool = False, per_position: bool = False) -> dict:
    """Program against reference; for each name in ``controls`` also the
    reference in that lower precision against the reference, and with
    ``fault`` the program with the fault above against the reference.
    Returns the numbers compared, their limits, and the verdict."""
    lens, prompts, forced = sample_inputs(mc, seed, spec)

    def against_reference(fault: bool):
        rows, sampled, is_argmax = program_logits(params, mc, spec, lens, prompts, forced, fault=fault)
        seqs = [np.concatenate([prompts[i], forced[i], np.asarray(sampled[i][:-1], np.int32)])
                for i in range(len(lens))]
        wanted = [sorted({pos for (_, seq, pos, _) in rows if seq == i}) for i in range(len(lens))]

        def ref_logits(lower=None):
            out = reference.forward(params, mc, seqs, wanted, lower=lower)
            return [dict(zip(w, lg)) for w, lg in zip(wanted, out)]

        ref = ref_logits()
        return rows, ref, ref_logits, is_argmax

    rows, ref, ref_logits, is_argmax = against_reference(False)
    limit, group_limit = float(spec["limit_rel_err"]), float(spec["limit_group_rel_err"])
    errors = _errors(rows, ref)
    out = {"limit_rel_err": limit, "limit_group_rel_err": group_limit, **summarize(errors, spec),
           "positions": len(rows), "sampled_is_argmax": is_argmax}
    out["ok"] = bool(out["rel_err"] <= limit and out["group_rel_err"] <= group_limit and is_argmax)
    if per_position:
        out["per_position"] = [(g, seq, pos, round(e, 5)) for (g, seq, pos, e) in errors]
    if controls:
        out["controls"] = {}
        for lower in controls:
            low = ref_logits(lower)
            c = summarize(_errors([(grp, seq, pos, low[seq][pos]) for (grp, seq, pos, _) in rows], ref), spec)
            c["fails"] = bool(c["rel_err"] > limit or c["group_rel_err"] > group_limit)  # judged as the program is
            out["controls"][lower] = c
    if fault:
        rows_f, ref_f, _, _ = against_reference(True)
        c = summarize(_errors(rows_f, ref_f), spec)
        c["fails"] = bool(c["group_rel_err"] > group_limit)
        out["fault_control"] = c
    return out
