"""The output check: the programs the cells serve against the plain reference.

A seeded handful of sequences (``sample_inputs``) is taken through the
program's own step programs in the order a scheduler would, with the kernels
"auto" resolves to on this backend and the served shapes (the configuration's
chunk, decode bucket and window). Which programs those are, and what cache
they run on, is the family's (``benchmark/families/<f>.py::program_logits``);
it names each compared position's *group*: one program path (``prefill``,
``chunk_fresh``, ``chunk_prefix``, ``mixed_decode``) or one sequence's rows of
the decode windows (``window_s<i>``).

The family's reference computes the same positions from one dense float32
forward pass over each whole sequence. Its inputs are the seeded weights and
the token ids; the ids a window sampled are fed to it as they were fed back on
the device (a window cannot be teacher-forced), and each must be the argmax of
the window's own logits. Logits are compared, never tokens: with random
weights the largest logit changes on rounding.

Two numbers are held to limits, both from ``|got - ref|_2 / |ref|_2`` of each
compared position. ``rel_err`` is the spec's ``quantile`` (default: the median)
over all positions: the step down in precision moves every position, and this
number is steady from seed to seed. ``group_rel_err`` is the largest, over
groups of positions, of the group's median: a fault in one path or in one
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


def pieces(length: int, chunk: int):
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
    steps_after = [sum(len(pieces(n, chunk)) for n in lens[i + 1:]) for i in range(len(lens))]
    prompts = [rs.integers(1, mc.vocab_size, size=n).astype(np.int32) for n in lens]
    forced = [rs.integers(1, mc.vocab_size, size=m + 1).astype(np.int32) for m in steps_after]
    return lens, prompts, forced


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


def check(family, params, mc, seed: int, spec: dict, *, controls=(), fault: bool = False,
          per_position: bool = False) -> dict:
    """``family``'s program against its reference; for each name in
    ``controls`` also the reference in that lower precision against the
    reference, and with ``fault`` the program with the fault above against the
    reference. Returns the numbers compared, their limits, and the verdict."""
    lens, prompts, forced = sample_inputs(mc, seed, spec)

    def against_reference(fault: bool):
        rows, sampled, is_argmax = family.program_logits(params, mc, spec, lens, prompts, forced, fault=fault)
        seqs = [np.concatenate([prompts[i], forced[i], np.asarray(sampled[i][:-1], np.int32)])
                for i in range(len(lens))]
        wanted = [sorted({pos for (_, seq, pos, _) in rows if seq == i}) for i in range(len(lens))]

        def ref_logits(lower=None):
            out = family.reference_forward(params, mc, seqs, wanted, lower=lower)
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
