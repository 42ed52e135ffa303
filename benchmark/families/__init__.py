"""One module per model family: whatever depends on the architecture.

A configuration file names its family (``"family": "<f>"``), and the runner
imports ``benchmark.families.<f>`` by that name and nothing else about the
model. A family module exposes exactly the names of ``SEAM``:

``model_config(cfg, name) -> ModelConfig``
    the program's configuration from the configuration file's keys, as run;
    the family owns the mapping.
``make_params(mc, seed)``
    the parameter tree ``TpuEngine.build(params=...)`` takes, made on the
    device from the seed in the type it is served in.
``program_logits(params, mc, spec, lens, prompts, forced, fault=False)``
    the sequences of ``parity.sample_inputs`` through the step programs the
    scheduler serves, on whatever cache those need: ``(rows, sampled,
    sampled_is_argmax)`` as ``parity.check`` reads them.
``reference_forward(params, mc, seqs, positions, lower=None)`` and ``CONTROLS``
    the plain reference (it imports nothing of the program) and the names
    ``lower`` takes: the family's lower-precision controls.
``decode_step_cost(cfg, weight_dtype, rows, ctx_tokens)``
    FLOPs and bytes of one decode step, for ``roofline.min_seconds``.

A second family is a new file here with its reference in it or beside it
(``<f>_reference.py``); no file that exists changes.
"""

from __future__ import annotations

import importlib

SEAM = ("model_config", "make_params", "program_logits", "reference_forward", "CONTROLS", "decode_step_cost")


def load(name: str):
    """The module of the family a configuration file names."""
    return importlib.import_module(f"{__name__}.{name}")
