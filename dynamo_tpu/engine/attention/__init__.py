"""Attention implementations: the ragged paged-attention megakernel
(megakernel.py — one Pallas kernel for a step's ragged batch: decode rows
a query a grid row, a prefill chunk by tiles of its queries; TPU
auto-selection),
the Pallas flash prefill kernel (prefill.py — 40.8 TF/s causal at 1B
shapes on v5e), the opt-in per-piece paged decode kernel (decode.py),
ring attention for sequence/context parallelism (ring.py), and the XLA
width-bucketed gather fallback (models/llama.py). Selection + the full
dispatch-overhead record: ModelConfig.attention_impl."""
