"""Model architecture configuration + presets.

The reference carries per-model config in the ModelDeploymentCard
(lib/llm/src/model_card.rs:91 — tokenizer, context length, kv block size);
engine-side architecture lives in the engines themselves. Here both meet:
:class:`ModelConfig` is the engine-side architecture record the MDC points at.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    # Paged KV cache block size in tokens (ref default: 64 in MDC, vLLM
    # uses 16). Measured on v5e at 1B/b32/ctx1024 with the gather path and
    # equal gathered bytes: bs=16 7.9 ms/step, bs=64 8.3, bs=256 9.8 — XLA
    # gathers 16-token rows at full efficiency, so bigger pages only add
    # fragmentation. Revisit if the Pallas paged kernel (attention_impl=
    # "paged") becomes the default — it wants ≥128-token pages.
    block_size: int = 16
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # MoE (0 experts = dense).
    num_experts: int = 0
    num_experts_per_tok: int = 0
    # MoE dispatch strategy (ref exposes wide-EP only as engine config,
    # components/backends/trtllm/utils/trtllm_utils.py:37-39; here it is a
    # native engine concern):
    # - "dense":    every expert computes every token (exact, tiny models).
    # - "ragged":   grouped GEMM via lax.ragged_dot — exact (no token drops),
    #               per-token FLOPs scale with top-k K, not E. Single-shard /
    #               tp-sharded meshes. Layer l's GEMMs read the stored
    #               [L, E, D, F] stacks in place, viewed [L*E, D, F] with
    #               group sizes that are zero outside [l*E, (l+1)*E)
    #               (llama._split_expert_stacks); the other two dispatches
    #               take a per-layer slice from the layer scan, a copy of
    #               the layer's experts on XLA:TPU.
    # - "capacity": GShard-style capacity-factor dispatch/combine einsums —
    #               GSPMD partitions experts over the ``ep`` mesh axis; tokens
    #               beyond an expert's capacity fall back to their residual.
    # - "auto":     "ragged"; the engine resolves to "capacity" when ep > 1.
    moe_dispatch: str = "auto"
    # Per-expert slot budget for "capacity" dispatch, as a multiple of the
    # balanced load T*K/E. 2.0 absorbs typical routing imbalance.
    moe_capacity_factor: float = 2.0
    # Architecture family: "llama" (GQA) or "mla" (DeepSeek-style multi-head
    # latent attention — compressed KV latent cache).
    architecture: str = "llama"
    # MLA dims (ignored for llama): per-head nope/rope query dims, value dim,
    # and the shared latent rank. Cache row = kv_lora_rank + qk_rope_head_dim.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Attention implementation for the paged-prefix piece — in plain
    # decode steps, the decode rows AND chunk rows of MIXED prefill+decode
    # steps, and prefill chunks (llama.mixed_step / prefill /
    # decode_layer_scan):
    # - "gather": XLA width-bucketed gather, two-piece online-softmax
    #   merge, once-per-window hoist (decode_multi). The CPU/debug
    #   baseline, and the off-TPU resolution of "auto".
    # - "megakernel": the ragged paged-attention megakernel
    #   (attention/megakernel.py) — one kernel for the step's ragged batch:
    #   scalar-prefetched block tables, pl.when-skipped dead slots, and an
    #   int8-KV dequant-in-VMEM path. Length-1 decode rows walk the grid
    #   (query, page) under the block-diagonal GQA fold, one launch a layer
    #   (decode, decode_multi). A wide row — a prefill chunk — walks (tile of
    #   queries, page): a tile read off the shapes (megakernel.chunk_tile; 256
    #   at the benchmark's widths) shares each page fetch and each dot, per
    #   lane group of whole KV heads. prefill launches it once a layer,
    #   mixed_step twice (the chunk, then the decode rows: disjoint outputs,
    #   no merge). A sched.step entry of a chunk-carrying dispatch names the
    #   path as traced: chunk_attn = tile<TQ> | gather.
    # - "paged": the length-1 decode rows' prefix through the r5 per-piece
    #   Pallas paged flash-decode kernel (attention/decode.py), merged with
    #   the current token in-register — correct (interpret-mode parity
    #   tests) but NEVER auto-selected (a configuration may set it:
    #   evabyte-d16's 4096-lane pages, PERF.md section 6 PR 28 and PR 31).
    #   A prefill chunk beside them walks the megakernel's tiles, as under
    #   "megakernel" (llama.chunk_walks_tiles, PERF.md section 6 PR 52): the
    #   two differ in the decode rows' kernel alone. No int8 path — int8
    #   caches degrade to gather with a logged warning
    #   (llama.resolve_attention_impl). Honoured off the TPU (interpreted).
    # - "auto": "megakernel" on TPU, "gather" elsewhere (interpreted
    #   Pallas is test-only). The gather's read + packed-copy write +
    #   attend re-read is 3× the true KV bytes; the megakernel streams
    #   each page HBM→VMEM once per grid row (a decode query, or a tile
    #   of a chunk's queries). Its share of a step: PERF.md section 5.
    attention_impl: str = "auto"
    # Prefill chunk attention — for phase-separated prefills AND the
    # ragged chunk rows of mixed steps (attention/ragged.py): "auto" =
    # Pallas flash kernel on TPU (attention/prefill.py — 40.8 TFLOP/s
    # causal vs ~2 for the two-piece XLA path at 1B shapes on v5e), XLA
    # path elsewhere; "flash"/"xla" force one ("flash" off-TPU runs the
    # kernel interpreted — tests only).
    prefill_impl: str = "auto"
    # KV cache storage dtype: "auto" follows the compute dtype; "int8" stores
    # quantized KV (per-token-per-head symmetric scale) — halves KV memory,
    # i.e. double the block capacity per HBM byte (longer contexts, bigger
    # batches before preemption). Decode latency is NOT improved on current
    # XLA:TPU (the int8 gather widens bytes internally — measured).
    # Covers llama KV and MLA latent rows (per-token scale over the latent).
    # Ref role: the engines' --kv-cache-dtype fp8 levers.
    kv_cache_dtype: str = "auto"
    # Weight storage dtype: "int8" stores dense layer matmul weights as
    # int8 + per-output-channel scale, dequantized one layer at a time in
    # the scan (engine/quant.py) — ~2× model capacity per HBM byte.
    # Measured necessity: Llama-3-8B bf16 is 15.0 GiB of weights and OOMs
    # a 16 GiB v5e before the first decode step; int8 weights serve it.
    # Embed/lm_head stay in compute dtype (per-step re-dequant of a
    # vocab-size matrix would add ~1 GB/token of traffic at 8B).
    weight_dtype: str = "auto"
    # Attention kind of the llama-family layer: "causal" (every earlier key,
    # one cache row a token for ever) or "eva" (EVA chunked linear attention:
    # exact softmax over the query's own ``window_size`` window, joined in one
    # softmax with one learned, softmax-pooled (key, value) summary per
    # ``chunk_size`` chunk of every earlier window). A summary has the shape
    # of a cached row, so the paged pool holds ``window_size // chunk_size``
    # summary rows for each completed window in front of the current
    # window's exact rows: ``kv_cache.cache_rows`` maps a position to its
    # row, and the scheduler rolls a completed window (``llama.eva_roll``).
    attention_kind: str = "causal"
    window_size: int = 0
    chunk_size: int = 0
    # RMSNorm multiplies by ``1 + weight`` (weights stored around zero).
    norm_unit_offset: bool = False
    # The residual stream is carried and added in float32; matmul inputs
    # stay in the compute dtype.
    residual_fp32: bool = False
    # Prediction heads of ``lm_head`` ([hidden, vocab * num_pred_heads]):
    # head 0 (columns [0, vocab)) gives the next-token logits, the only ones
    # served; the others' weights are held, not multiplied.
    num_pred_heads: int = 1
    # Mixer of each layer, in order: "attention", "mamba" (a Mamba-2
    # state-space mixer) or "cca" (attention in a compressed latent behind two
    # causal convolutions and a value shift; every layer of the stack or
    # none). Empty: every layer is attention, one stack, the llama step
    # programs. Non-empty: ``models/hybrid.py`` drives the stack as ordered
    # groups of one kind (``layer_groups``), each group one scan; the paged
    # pool then holds the attention and cca layers only, and every running
    # sequence holds one *slot* beside it (``kv_cache.SlotKv``): of recurrent
    # state in each mamba layer, of the convolutions' and the shift's last
    # columns in each cca layer. Every layer has the same FFN.
    layer_types: Tuple[str, ...] = ()
    # Mamba-2 sizes under their published names (``mamba_<key>``): the state
    # of one head is [d_head, d_state]; d_inner = expand * hidden_size =
    # n_heads * d_head; the causal depthwise convolution of width d_conv runs
    # over d_inner + 2 * n_groups * d_state lanes; ``chunk_size`` is the block
    # of the chunked (SSD) form a prefill chunk goes through.
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    # An expert layer that holds a share of its experts: the router is
    # ``num_experts`` wide and picks ``num_experts_per_tok`` of them, the
    # stacks hold experts [first_expert_held, first_expert_held +
    # num_experts_held) and an assignment to any other adds nothing here (the
    # chip that holds it adds it). 0 held = all of them.
    num_experts_held: int = 0
    first_expert_held: int = 0
    # Width of a dense SwiGLU every token passes beside the routed experts,
    # added ungated (0 = none).
    shared_intermediate_size: int = 0
    # Rotary position embedding on queries and keys (off: "nope").
    use_rope: bool = True
    # Multiplier of q . k before the softmax (0 = head_dim ** -0.5).
    attention_scale: float = 0.0
    # The Granite multipliers: of the embedding rows, of every residual
    # branch, and the divisor of the logits.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # "cca" layers (Compressed Convolutional Attention, arXiv:2510.04476):
    # queries live in ``q_size`` lanes and keys and values in ``kv_size``
    # lanes, both narrower than ``hidden_size``; their projections pass a
    # causal depthwise convolution of ``cca_time0`` taps and one grouped by
    # head of ``cca_time1`` taps, and the second value head comes from the
    # token before (``hybrid._cca_mixer``).
    cca_time0: int = 2
    cca_time1: int = 2
    # Share of each head's lanes that rotate (the leading ones).
    rope_fraction: float = 1.0
    # The expert layer's router: "linear" (softmax over the top-k logits of
    # one matrix) or "zaya" (a down-projection to ``router_hidden_size`` that
    # adds the router state of the layer before, scaled, and hands its own
    # on; then a norm and a three-matrix GELU MLP in float32, a softmax over
    # all choices and the top-1 of it plus a stored balancing bias).
    router_kind: str = "linear"
    router_hidden_size: int = 0
    # One more choice than experts: a token that draws it passes by them.
    moe_skip_choice: bool = False
    # Residual merge with four learned vectors a sublayer:
    # x <- (gx * x + bx) + (gf * f + bf).
    residual_merge: bool = False
    # "mla_full" and "mla_window" layers (``models/latent.py``): multi-head
    # latent attention behind low-rank queries (``q_lora_rank``), with a
    # sigmoid gate a head (``attention_gate``) and, where ``mla_lora_rescale``,
    # both normed latents times sqrt(hidden_size / rank). A full layer reads
    # ``num_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    # ``v_head_dim`` and ``rope_theta`` and keeps one pool row a token (latent
    # and rotated key on the ``k`` side, the indexer's key on the ``v`` side);
    # its learned indexer (``index_n_heads`` heads of ``index_head_dim`` lanes)
    # picks the ``index_topk`` cached rows a query attends. A window layer
    # reads the ``swa_*`` sizes, attends the last ``sliding_window`` positions
    # (the token itself included) and keeps them as a ring in the sequence's
    # slot: no pool rows.
    q_lora_rank: int = 0
    mla_lora_rescale: bool = False
    attention_gate: bool = False
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    sliding_window: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # Router kind "sigmoid": scores are sigmoids (float32), the choice is the
    # top-k of score + a stored correction bias, the weights are the chosen
    # scores, normalised to sum 1 where ``norm_topk_prob``, times
    # ``routed_scaling_factor``.
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    # The first ``first_k_dense`` layers have a dense SwiGLU of
    # ``dense_intermediate_size`` in place of the experts (latent kinds only).
    first_k_dense: int = 0
    dense_intermediate_size: int = 0

    def __post_init__(self):
        if self.attention_impl not in ("auto", "gather", "paged", "megakernel"):
            raise ValueError(
                "attention_impl must be auto|gather|paged|megakernel, "
                f"got {self.attention_impl!r}"
            )
        # attention_impl='paged' + int8 KV no longer raises: the paged
        # kernel has no int8 path, so the engine degrades that combination
        # to the gather with a logged warning (llama.resolve_attention_impl)
        # — the megakernel is the int8-capable fused path.
        if self.prefill_impl not in ("auto", "flash", "xla"):
            raise ValueError(f"prefill_impl must be auto|flash|xla, got {self.prefill_impl!r}")
        if self.moe_dispatch not in ("auto", "dense", "ragged", "capacity"):
            raise ValueError(
                f"moe_dispatch must be auto|dense|ragged|capacity, got {self.moe_dispatch!r}"
            )
        if self.kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(f"kv_cache_dtype must be auto|int8, got {self.kv_cache_dtype!r}")
        if self.weight_dtype not in ("auto", "int8"):
            raise ValueError(f"weight_dtype must be auto|int8, got {self.weight_dtype!r}")
        if self.weight_dtype == "int8" and self.architecture != "llama":
            raise ValueError(
                "weight_dtype='int8' is llama-family only (MLA layer scans "
                "do not dequantize yet)"
            )
        if self.attention_kind not in ("causal", "eva"):
            raise ValueError(f"attention_kind must be causal|eva, got {self.attention_kind!r}")
        if self.attention_kind == "eva":
            if self.architecture != "llama":
                raise ValueError("attention_kind='eva' is served by the llama-family step programs only")
            if self.chunk_size <= 0 or self.window_size <= 0 or self.window_size % self.chunk_size:
                raise ValueError(
                    "attention_kind='eva' needs window_size and chunk_size > 0 with chunk_size dividing "
                    f"window_size, got {self.window_size} / {self.chunk_size}"
                )
            if self.kv_cache_dtype == "int8":
                raise ValueError("attention_kind='eva' has no int8 KV path (summaries are pooled from real rows)")
        if self.num_pred_heads < 1 or (self.num_pred_heads > 1 and self.tie_word_embeddings):
            raise ValueError("num_pred_heads >= 1, and > 1 only with an untied lm_head")
        if self.weight_dtype == "int8" and self.num_experts > 0:
            raise ValueError(
                "weight_dtype='int8' does not cover MoE expert stacks "
                "(ragged/capacity dispatch would re-dequantize per expert)"
            )
        if self.layer_types:
            self._check_hybrid()
        elif (
            self.num_experts_held
            or self.shared_intermediate_size
            or not self.use_rope
            or self.attention_scale
            or (self.embedding_multiplier, self.residual_multiplier, self.logits_scaling) != (1.0, 1.0, 1.0)
            or self.rope_fraction != 1.0
            or self.router_kind != "linear"
            or self.moe_skip_choice
            or self.residual_merge
            or self.first_k_dense
            or self.sliding_window
            or self.index_topk
            or self.attention_gate
        ):
            raise ValueError(
                "a share of the experts, a shared expert, use_rope=False, attention_scale, the multipliers, "
                "rope_fraction, a router kind, a skip choice, the residual merge, a dense first layer, a sliding "
                "window, an indexer and a gate on the heads "
                "are read by the layer-group step programs only: state layer_types"
            )

    def _check_hybrid(self) -> None:
        """What ``models/hybrid.py`` serves, and what it refuses by name."""
        kinds = {"attention", "mamba", "cca"} | set(LATENT_KINDS)
        if len(self.layer_types) != self.num_layers or set(self.layer_types) - kinds:
            raise ValueError(
                f"layer_types names one of {sorted(kinds)} for each of num_layers={self.num_layers} layers, "
                f"got {self.layer_types!r}"
            )
        cca = "cca" in self.layer_types
        latent = self.is_latent
        refused = {
            "architecture other than 'llama'": self.architecture != "llama",
            "attention_kind 'eva'": self.is_eva,
            "weight_dtype 'int8' (int8 weights)": self.weight_dtype == "int8",
            "kv_cache_dtype 'int8'": self.kv_cache_dtype == "int8",
            "attention_impl 'paged'": self.attention_impl == "paged",
            "a float32 residual, a norm unit offset or several prediction heads": (
                self.residual_fp32 or self.norm_unit_offset or self.num_pred_heads > 1
            ),
            "moe_dispatch other than auto|ragged": self.num_experts > 0 and self.moe_dispatch not in ("auto", "ragged"),
            "'cca' layers beside layers of another kind": cca and len(set(self.layer_types)) > 1,
            "'cca' layers with use_rope=False, an attention_scale or a share of the experts": cca and (
                not self.use_rope or self.attention_scale or self.num_experts_held
            ),
            "rope_fraction, router_kind 'zaya', a skip choice or the residual merge without 'cca' layers": not cca and (
                self.rope_fraction != 1.0 or self.router_kind == "zaya" or self.moe_skip_choice or self.residual_merge
            ),
            "'mla_full' / 'mla_window' layers beside layers of another kind": latent and bool(
                set(self.layer_types) - set(LATENT_KINDS)
            ),
            "'mla_full' / 'mla_window' layers with use_rope=False, an attention_scale or a multiplier off 1": latent and (
                not self.use_rope or self.attention_scale
                or (self.embedding_multiplier, self.residual_multiplier, self.logits_scaling) != (1.0, 1.0, 1.0)
            ),
            "router_kind 'sigmoid', a dense first layer, a sliding window, an indexer or a gate on the heads "
            "without 'mla_full' / 'mla_window' layers": not latent and bool(
                self.router_kind == "sigmoid" or self.first_k_dense or self.sliding_window or self.index_topk
                or self.attention_gate
            ),
        }
        for what, hit in refused.items():
            if hit:
                self.refuse_for_layer_types(what)
        if "mamba" in self.layer_types:
            if min(self.mamba_d_state, self.mamba_n_heads, self.mamba_d_head, self.mamba_n_groups) <= 0:
                raise ValueError("a 'mamba' layer needs mamba_d_state, mamba_n_heads, mamba_d_head, mamba_n_groups > 0")
            if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
                raise ValueError(
                    f"mamba_n_heads * mamba_d_head = {self.mamba_n_heads * self.mamba_d_head} is not "
                    f"mamba_expand * hidden_size = {self.mamba_expand * self.hidden_size}"
                )
            if self.mamba_n_heads % self.mamba_n_groups or self.mamba_d_conv < 2 or self.mamba_chunk_size < 1:
                raise ValueError("mamba_n_groups divides mamba_n_heads; mamba_d_conv >= 2; mamba_chunk_size >= 1")
        if cca:
            rot = self.head_dim * self.rope_fraction
            if (self.cca_time0, self.cca_time1) != (2, 2):
                raise ValueError("a 'cca' layer's convolutions have two taps (cca_time0 = cca_time1 = 2): one column a slot")
            if self.num_heads % self.num_kv_heads or self.num_kv_heads % 2 or rot != int(rot) or int(rot) % 2 or not 0 < rot <= self.head_dim:
                raise ValueError(
                    "a 'cca' layer needs num_kv_heads dividing num_heads, an even num_kv_heads (half the value heads "
                    "are the shifted ones) and an even number of rotating lanes"
                )
        if latent:
            self._check_latent()
        if self.router_kind not in ("linear", "zaya", "sigmoid"):
            raise ValueError(f"router_kind must be linear|zaya|sigmoid, got {self.router_kind!r}")
        if self.router_kind == "zaya" and (self.router_hidden_size <= 0 or self.num_experts_per_tok != 1 or not self.num_experts):
            raise ValueError("router_kind 'zaya' needs router_hidden_size > 0, experts, and num_experts_per_tok = 1")
        if self.moe_skip_choice and self.router_kind != "zaya":
            raise ValueError("the skip choice is the 'zaya' router's")
        held = self.num_experts_held
        if held and not (0 < held <= self.num_experts and 0 <= self.first_expert_held <= self.num_experts - held):
            raise ValueError(
                f"experts [{self.first_expert_held}, {self.first_expert_held + held}) are not among "
                f"the router's {self.num_experts}"
            )

    def _check_latent(self) -> None:
        """Sizes the latent kinds need (``models/latent.py``)."""
        for kind in set(self.layer_types):
            z = self.latent_sizes(kind)
            if min(z) <= 0 or z.rope % 2:
                raise ValueError(f"a {kind!r} layer needs its heads, ranks, head sizes and rope_theta > 0 (rope lanes even), got {z}")
        if "mla_full" in self.layer_types and (
            min(self.index_n_heads, self.index_head_dim, self.index_topk) <= 0 or self.index_head_dim % 4
        ):
            raise ValueError("an 'mla_full' layer needs index_n_heads, index_head_dim (a multiple of 4) and index_topk > 0")
        if "mla_window" in self.layer_types and self.sliding_window < 1:
            raise ValueError("an 'mla_window' layer needs sliding_window >= 1 (the token itself counts)")
        if not 0 <= self.first_k_dense <= self.num_layers or (self.first_k_dense and self.dense_intermediate_size <= 0):
            raise ValueError("first_k_dense layers need dense_intermediate_size > 0")
        if self.first_k_dense < self.num_layers and not self.num_experts:
            raise ValueError("the layers past first_k_dense are expert layers: state num_experts")

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_eva(self) -> bool:
        return self.attention_kind == "eva"

    @property
    def summaries_per_window(self) -> int:
        """Cache rows a completed window leaves behind (eva)."""
        return self.window_size // self.chunk_size

    def refuse_for_layer_types(self, what: str) -> None:
        """What equates a sequence with its block table alone is not built for
        a model that also holds a slot of recurrent state: refuse by name,
        never serve a sequence without its state."""
        if self.layer_types:
            raise NotImplementedError(f"{what} is not built for layer_types (model {self.name!r})")

    @property
    def is_hybrid(self) -> bool:
        """Layers of stated kinds, driven as groups (``models/hybrid.py``)."""
        return bool(self.layer_types)

    @property
    def layer_groups(self) -> Tuple[Tuple[str, int], ...]:
        """``layer_types`` as ordered runs of one kind: ((kind, count), ...)."""
        groups: list = []
        for kind in self.layer_types:
            if groups and groups[-1][0] == kind:
                groups[-1][1] += 1
            else:
                groups.append([kind, 1])
        return tuple((k, n) for k, n in groups)

    @property
    def num_attention_layers(self) -> int:
        """Layers the paged pool holds rows for."""
        if not self.layer_types:
            return self.num_layers
        return self.layer_types.count("attention") + self.num_cca_layers + self.layer_types.count("mla_full")

    @property
    def is_latent(self) -> bool:
        """A stack of "mla_full" / "mla_window" layers (``models/latent.py``)."""
        return bool(self.layer_types) and self.layer_types[0] in LATENT_KINDS

    @property
    def num_window_layers(self) -> int:
        """Layers that keep a ring of ``sliding_window`` rows in a sequence's slot."""
        return self.layer_types.count("mla_window")

    def latent_sizes(self, kind: str) -> "LatentSizes":
        """The sizes of one latent kind's attention."""
        if kind == "mla_full":
            return LatentSizes(self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
                               self.q_lora_rank, self.kv_lora_rank, float(self.rope_theta))
        return LatentSizes(self.swa_num_heads, self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                           self.swa_q_lora_rank, self.swa_kv_lora_rank, float(self.swa_rope_theta))

    @property
    def latent_groups(self) -> Tuple[Tuple[str, bool, int], ...]:
        """``layer_types`` as ordered runs of one kind AND one FFN:
        ((kind, dense FFN?, count), ...): one scan a run."""
        groups: list = []
        for l, kind in enumerate(self.layer_types):
            dense = l < self.first_k_dense
            if groups and groups[-1][:2] == [kind, dense]:
                groups[-1][2] += 1
            else:
                groups.append([kind, dense, 1])
        return tuple((k, d, n) for k, d, n in groups)

    @property
    def num_mamba_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def num_cca_layers(self) -> int:
        return self.layer_types.count("cca")

    @property
    def cca_channels(self) -> int:
        """Lanes the two convolutions run over: queries and keys, head by head."""
        return self.q_size + self.kv_size

    @property
    def cca_slot_lanes(self) -> int:
        """What a sequence carries in ONE cca layer: the last input of each
        convolution (``cca_channels`` lanes each) and the last token's
        projection for the shifted value head (``head_dim`` lanes for each
        second half of the key/value heads)."""
        return 2 * self.cca_channels + self.kv_size // 2

    @property
    def router_choices(self) -> int:
        """Width of the router's softmax: the experts, and the skip choice."""
        return self.num_experts + int(self.moe_skip_choice)

    @property
    def experts_held(self) -> int:
        """Experts the stacks hold (all of the router's unless a share is stated)."""
        return self.num_experts_held or self.num_experts

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_state_shape(self) -> Tuple[int, int, int]:
        """How one slot's recurrent state of one layer is STORED: ``[H/g, N,
        g*P]``, ``g`` heads side by side on the lanes (``g*P`` = 128 where
        ``d_head`` divides it and ``g`` divides the heads, else ``g`` = 1) and
        ``d_state`` on the sublanes. Head ``h``'s ``[P, N]`` state is
        ``stored[h // g, :, (h % g) * P : (h % g + 1) * P].T``: a row of ``x``
        or of ``y`` (``d_head`` lanes of ``g`` heads) then meets the state
        without a transpose, and ``B``/``C`` broadcast along lanes
        (``hybrid.ssm_update_rows``)."""
        H, P, N = self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state
        g = 128 // P if P and 128 % P == 0 and H % (128 // P) == 0 else 1
        return (H // g, N, g * P)

    @property
    def mamba_conv_dim(self) -> int:
        """Lanes the convolution runs over: x, B and C."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def replace(self, **kwargs) -> "ModelConfig":
        return dataclasses.replace(self, **kwargs)


class LatentSizes(NamedTuple):
    """One latent kind's attention: heads, a head's nope / rope / value lanes,
    the queries' and the keys' ranks, the rope base. A cached row is
    ``kv_rank + rope`` lanes."""

    heads: int
    nope: int
    rope: int
    value: int
    q_rank: int
    kv_rank: int
    theta: float

    @property
    def row(self) -> int:
        return self.kv_rank + self.rope

    @property
    def scale(self) -> float:
        """The multiplier of a score: one over the root of a head's query lanes."""
        return (self.nope + self.rope) ** -0.5


LATENT_KINDS = ("mla_full", "mla_window")


PRESETS = {
    # Tiny config for unit tests: fast on a single CPU core.
    "tiny": ModelConfig(
        name="tiny",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        max_seq_len=256,
        block_size=16,
        rope_theta=10000.0,
    ),
    # Tiny MoE config for EP tests.
    "tiny-moe": ModelConfig(
        name="tiny-moe",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=64,
        max_seq_len=256,
        block_size=16,
        rope_theta=10000.0,
        num_experts=4,
        num_experts_per_tok=2,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=1000000.0,
        max_seq_len=32768,
        num_experts=8,
        num_experts_per_tok=2,
    ),
    # Wide-EP MoE decode target (ref recipe: recipes/gpt-oss-120b) —
    # architecture approximated from public specs.
    "gpt-oss-120b": ModelConfig(
        name="gpt-oss-120b",
        vocab_size=201088,
        hidden_size=2880,
        num_layers=36,
        num_heads=64,
        num_kv_heads=8,
        head_dim=64,
        intermediate_size=2880,
        max_seq_len=131072,
        num_experts=128,
        num_experts_per_tok=4,
    ),
    # Tiny EVA config (chunked linear attention over the paged pool) for unit
    # tests: 8 summary rows a window of 32, MHA, float32 residual, two
    # prediction heads.
    "tiny-eva": ModelConfig(
        name="tiny-eva",
        vocab_size=320,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        intermediate_size=128,
        max_seq_len=256,
        block_size=8,
        rope_theta=100000.0,
        attention_kind="eva",
        window_size=32,
        chunk_size=4,
        norm_unit_offset=True,
        residual_fp32=True,
        num_pred_heads=2,
    ),
    # Tiny hybrid config for unit tests: the shape of a published pattern
    # (Mamba-2 layers around one attention layer) at hidden 64: two groups of
    # Mamba, 8 experts top-3 of which 4 are held, a shared expert, no rope,
    # a stated attention scale, every multiplier off 1.
    "tiny-hybrid": ModelConfig(
        name="tiny-hybrid",
        vocab_size=256,
        hidden_size=64,
        num_layers=5,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=32,
        max_seq_len=256,
        block_size=8,
        tie_word_embeddings=True,
        num_experts=8,
        num_experts_per_tok=3,
        num_experts_held=4,
        shared_intermediate_size=48,
        layer_types=("mamba", "mamba", "attention", "mamba", "mamba"),
        mamba_d_state=16,
        mamba_n_heads=8,
        mamba_d_head=16,
        mamba_chunk_size=16,
        use_rope=False,
        attention_scale=0.05,
        embedding_multiplier=6.0,
        residual_multiplier=0.5,
        logits_scaling=4.0,
    ),
    # Tiny ZAYA-shaped config for unit tests: every layer "cca" (attention in
    # a latent half the hidden size, two convolutions, a value shift) and four
    # experts top-1 with a skip choice behind a router MLP that carries its
    # state; half of each head's lanes rotate; the residual merge.
    "tiny-zaya": ModelConfig(
        name="tiny-zaya",
        vocab_size=512,
        hidden_size=64,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=32,
        max_seq_len=256,
        block_size=8,
        rope_theta=5000000.0,
        tie_word_embeddings=True,
        num_experts=4,
        num_experts_per_tok=1,
        layer_types=("cca",) * 4,
        rope_fraction=0.5,
        router_kind="zaya",
        router_hidden_size=16,
        moe_skip_choice=True,
        residual_merge=True,
    ),
    # Tiny config of the two latent kinds for unit tests: one full layer with a
    # dense FFN, one full, three window layers; the indexer picks 8 rows, a
    # ring holds 5; 16 experts top-2 by sigmoid scores of which 4 are held.
    "tiny-dots3": ModelConfig(
        name="tiny-dots3",
        vocab_size=256,
        hidden_size=64,
        num_layers=5,
        num_heads=4,
        num_kv_heads=1,
        head_dim=24,
        intermediate_size=32,
        max_seq_len=256,
        block_size=8,
        rope_theta=80000.0,
        num_experts=16,
        num_experts_per_tok=2,
        num_experts_held=4,
        shared_intermediate_size=32,
        layer_types=("mla_full", "mla_full", "mla_window", "mla_window", "mla_window"),
        q_lora_rank=32,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        mla_lora_rescale=True,
        attention_gate=True,
        index_n_heads=4,
        index_head_dim=16,
        index_topk=8,
        sliding_window=5,
        swa_num_heads=2,
        swa_q_lora_rank=32,
        swa_kv_lora_rank=48,
        swa_qk_nope_head_dim=24,
        swa_qk_rope_head_dim=8,
        swa_v_head_dim=16,
        swa_rope_theta=5000.0,
        router_kind="sigmoid",
        norm_topk_prob=True,
        first_k_dense=1,
        dense_intermediate_size=96,
    ),
    # Tiny MLA config (DeepSeek-style latent attention) for unit tests.
    "tiny-mla": ModelConfig(
        name="tiny-mla",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=1,
        head_dim=16,
        intermediate_size=128,
        max_seq_len=256,
        block_size=16,
        rope_theta=10000.0,
        architecture="mla",
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
    ),
    # DeepSeek-V2-Lite (public specs): MLA + 64-expert MoE.
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite",
        vocab_size=102400,
        hidden_size=2048,
        num_layers=27,
        num_heads=16,
        num_kv_heads=1,
        head_dim=128,
        intermediate_size=1408,
        max_seq_len=32768,
        rope_theta=10000.0,
        architecture="mla",
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=64,
        num_experts_per_tok=6,
    ),
    # DeepSeek-V3 / R1 (public specs): the wide-EP MLA decode target
    # (ref recipe: components/backends/sglang slurm_jobs DeepSeek-R1).
    "deepseek-v3": ModelConfig(
        name="deepseek-v3",
        vocab_size=129280,
        hidden_size=7168,
        num_layers=61,
        num_heads=128,
        num_kv_heads=1,
        head_dim=128,
        intermediate_size=2048,
        max_seq_len=131072,
        rope_theta=10000.0,
        architecture="mla",
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=256,
        num_experts_per_tok=8,
    ),
    # Llama-architecture aliases with their own dims.
    "qwen2.5-7b": ModelConfig(
        name="qwen2.5-7b",
        vocab_size=152064,
        hidden_size=3584,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        intermediate_size=18944,
        rope_theta=1000000.0,
        max_seq_len=32768,
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b",
        vocab_size=32768,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=1000000.0,
        max_seq_len=32768,
    ),
    "llama-3.2-1b": ModelConfig(
        name="llama-3.2-1b",
        vocab_size=128256,
        hidden_size=2048,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        intermediate_size=8192,
        max_seq_len=131072,
        tie_word_embeddings=True,
    ),
    "llama-3.2-3b": ModelConfig(
        name="llama-3.2-3b",
        vocab_size=128256,
        hidden_size=3072,
        num_layers=28,
        num_heads=24,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=8192,
        max_seq_len=131072,
        tie_word_embeddings=True,
    ),
    "llama-3-8b": ModelConfig(
        name="llama-3-8b",
        vocab_size=128256,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        max_seq_len=8192,
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b",
        vocab_size=128256,
        hidden_size=8192,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=28672,
        max_seq_len=8192,
    ),
}


def get_config(name: str) -> ModelConfig:
    if name in PRESETS:
        return PRESETS[name]
    raise KeyError(f"unknown model preset: {name} (have {sorted(PRESETS)})")


def resolve_moe_dispatch(config: ModelConfig, ep: int) -> ModelConfig:
    """Resolve "auto" MoE dispatch against the actual expert-parallel degree.

    Called by every entry point that knows the mesh (Scheduler, pipelined
    decode, profilers). Wide-EP meshes need "capacity" (its einsum expert
    axis partitions over ``ep``); single-shard/tp meshes use the exact
    "ragged" grouped GEMM. Direct model calls that never see a mesh keep the
    "auto"→"ragged" default in ``_mlp``."""
    if config.num_experts and config.moe_dispatch == "auto":
        return config.replace(moe_dispatch="capacity" if ep > 1 else "ragged")
    return config
