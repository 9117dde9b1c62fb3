"""Llama-style decoder-only transformer, TPU-first.

Net-new vs the reference (no model code in its tree — SURVEY.md §2); this is
the flagship consumer of the ingest pipeline for BASELINE configs 3 and 5.

Design choices, all for the TPU/XLA compilation model:

- **Pure pytree params, stacked layers.** Parameters are a plain dict with
  every per-layer tensor stacked on a leading [L, ...] axis, and the forward
  pass runs ``lax.scan`` over that axis: one traced layer body, compile time
  independent of depth, and a single PartitionSpec per tensor covers all
  layers.
- **bfloat16 compute, float32 params/accumulators.** Matmuls hit the MXU in
  bf16 (``cfg.dtype``); master weights, optimizer moments, softmax and the
  online-attention recurrence stay f32.
- **Sharding by spec, collectives by XLA.** ``param_specs`` gives each tensor
  a PartitionSpec over a {data, fsdp, tp, sp} mesh (2D "megatron" TP for
  attention/MLP, fsdp sharding on the other matmul dim, replicated norms).
  The train step is one ``jax.jit`` whose in/out shardings are those specs —
  XLA inserts all_gather/reduce_scatter/psum where the math demands them.
  No hand-written collectives outside ring attention's explicit ppermute.
- **Sequence parallelism is real.** With an ``sp`` axis of size > 1 the
  activations are sharded over sequence, and attention runs as ring
  attention (torchkafka_tpu.ops.attention) so no device ever materialises
  the full sequence. RoPE/norms/MLP are elementwise-in-sequence and need no
  communication.
- **Remat.** ``cfg.remat`` wraps the scanned layer body (and gpipe's
  ``layer_fn``) in ``jax.checkpoint``, trading recompute for HBM — the
  standard long-context lever. The backward pass recomputes a layer from its
  input, with one exception (``_remat_layer``): where the layer runs the
  flash kernel, its ``[B·H, S, D]`` output and ``[B·H, S, 1]`` log-sum-exp
  are KEPT (the two names ``ops/flash.py`` gives them), so the forward kernel
  runs once a layer and not again in the recompute. That costs
  ``2·B·S·H·D + 4·B·S·H`` bytes a layer in bf16, as much again as the layer
  input remat already keeps where ``H·D = d_model``. A layer that runs no
  flash kernel (dense fallback, ring/ulysses) names nothing of these.
  Under a mesh whose ``tp`` axis is larger than 1 the layer also names, and
  the policy keeps, the residual stream AFTER the attention block
  (``REMAT_SAVED_TP``: ``x + attn·wo``, the product already summed over
  ``tp``; whatever the attention kernel). It is what ``ln2`` and the FFN
  read, so a recompute from the layer input would run the ``wo`` product
  and its all-reduce a second time, one of five reductions a layer where a
  tensor-parallel layer needs four. That costs ``2·B·S·d_model`` bytes a
  layer a chip in bf16, the layer input's size again, written by the
  fusion that makes the sum. With no ``tp`` axis (one chip, data, fsdp, sp
  or pp alone) there is no reduction to save, the product alone is about a
  hundredth of a step, and the one-chip program is cut to the memory it
  has: nothing is named, and the program is the one of before. The rule
  reads the mesh (``Transformer._keeps_attn_residual``); no option chooses
  it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchkafka_tpu.models.quant import QTensor, embed_rows, load_weight
from torchkafka_tpu.ops.attention import (
    axis_is_manual,
    mha,
    ring_attention,
    ulysses_attention,
)
from torchkafka_tpu.ops.xent import dense_softmax_xent, fused_softmax_xent
from torchkafka_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class RopeKind:
    """How one KIND of layer rotates its queries and keys: ``theta`` alone
    is the plain rotary embedding; ``factor`` > 1 is YaRN (the pairs that
    turn fewer than ``beta_slow`` times in ``original_len`` positions are
    slowed ``factor``-fold, those that turn more than ``beta_fast`` times
    are left, a linear ramp between; cos and sin are both multiplied by
    ``attention_factor``). What a checkpoint's config states, a kind."""

    theta: float
    factor: float = 1.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def correction_range(self, dim: int) -> tuple[int, int]:
        """(low, high): the pair indices YaRN's ramp runs between."""
        def pair_of(turns: float) -> float:
            return dim * math.log(
                self.original_len / (turns * 2 * math.pi)
            ) / (2 * math.log(self.theta))

        low = max(math.floor(pair_of(self.beta_fast)), 0)
        high = min(math.ceil(pair_of(self.beta_slow)), dim - 1)
        return low, high

    def inv_freq(self, dim: int) -> np.ndarray:
        """float32 [dim // 2]: the angle a pair turns a position."""
        plain = self.theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        if self.factor == 1.0:
            return plain.astype(np.float32)
        low, high = self.correction_range(dim)
        ramp = np.clip(
            (np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0
        )
        return (plain * (1 - ramp) + plain / self.factor * ramp).astype(
            np.float32
        )


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8  # < n_heads → grouped-query attention
    d_ff: int = 1376
    max_seq_len: int = 512
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16  # compute dtype (MXU)
    param_dtype: Any = jnp.float32  # master weights
    remat: bool = False
    # 'dense' | 'flash' | 'ring' | 'ulysses' | 'auto': auto picks ring when
    # the mesh has sp>1 (no head-divisibility constraint), else the Pallas
    # flash kernel on TPU, else dense XLA. 'ulysses' selects all-to-all
    # sequence parallelism (heads must divide by the sp size).
    attn_impl: str = "auto"
    # Sequence-parallel attention over the Pallas flash kernels — governs
    # BOTH 'ring' (per ring step) and 'ulysses' (per head-shard): None =
    # on TPU when the shard tiles; True forces (tests/dryruns exercise the
    # kernels in interpret mode off-TPU); False forces the dense body.
    ring_use_flash: bool | None = None
    # Mixture-of-experts MLP: 0 = dense SwiGLU; >0 = that many experts with
    # top-k routing, expert weights sharded over the mesh's 'ep' axis.
    n_experts: int = 0
    expert_top_k: int = 2
    router_aux_coef: float = 0.01  # load-balance loss weight (0 disables)
    # 'dense': exact one-hot combine, every ep shard computes all tokens
    # for its local experts (no drops, E/ep-fold compute). 'capacity':
    # Switch-style dispatch — each expert takes at most
    # ceil(group·k/E · capacity_factor) tokens PER TOKEN GROUP, overflow
    # drops, per-shard compute scales down E/ep-fold (the pod-scale path).
    # TRAINING-ONLY knob: the KV-cache decode path (models/generate.py,
    # serve.py) always routes exactly — capacity drops are a training
    # throughput/regularization tradeoff, and decode-sized batches fit
    # under any capacity anyway (standard MoE serving semantics).
    moe_dispatch: str = "dense"
    capacity_factor: float = 1.25
    # Tokens dispatch within groups of exactly this size (the tail group is
    # padded with masked rows, so ANY token count — including primes —
    # keeps full groups). The one-hot dispatch einsum costs n_g·E·C·D per
    # group; ungrouped (n_g = all tokens) it grows QUADRATIC in tokens and
    # dwarfs the expert MLP itself (measured 20x at 16k tokens); 256 keeps
    # it a fraction of MLP cost.
    moe_group_size: int = 256
    # Pipeline parallelism: with a 'pp' mesh axis of size > 1 the layer
    # stack runs as a GPipe schedule (ops/pipeline.py) with this many
    # microbatches (None = pipeline depth). The router aux loss IS
    # collected under pp: per-microbatch routing statistics accumulate
    # through the schedule and psum across stages into exactly the
    # full-batch statistic (see ``router_aux``).
    pp_microbatches: int | None = None
    # Fused blocked cross-entropy (ops/xent.py): None = auto block size,
    # >0 = that sequence block, 0 = disable (always full-logits dense CE).
    # Auto-disabled under sp>1 meshes and quantized heads either way.
    ce_block_size: int | None = None
    # Unroll factor for the lax.scan over the stacked layers. None = auto:
    # fully unroll stacks of ≤ 8 layers (XLA schedules the unrolled trunk
    # ~15% faster on v5e at batch 64; measured in PERF.md), scan deeper
    # stacks (compile time independent of depth — the reason scan is the
    # default structure). 1 = never unroll.
    scan_unroll: int | None = None
    # ---- What a checkpoint's architecture states (none is a tuning knob,
    # and a config that leaves them at their defaults builds what it built
    # before they existed).
    # Latent attention (MLA, DeepSeek-V2/V3): ``kv_lora_rank`` > 0 caches
    # ONE tensor a layer, the normed ``kv_lora_rank``-wide latent beside
    # the roped ``qk_rope_dim``-wide key all heads share; a head's q/k
    # width is ``qk_nope_dim + qk_rope_dim``, its v width ``v_head_dim``
    # (models/mla.py). ``q_lora_rank`` > 0 compresses the queries too
    # (``wqa`` -> RMSNorm -> ``wqb``); the two flags, as the source has
    # them, multiply the normed compressed query by ``sqrt(d_model /
    # q_lora_rank)`` and the normed latent by ``sqrt(d_model /
    # kv_lora_rank)`` (the scaled latent is what is cached).
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # 2: the shortcut-connected double layer. A layer holds two latent
    # attention blocks and two dense SwiGLUs of ``d_ff`` in a row, and ONE
    # expert branch that leaves the first block's normed output and
    # rejoins after the second SwiGLU (``_double_layer``); the cache holds
    # a row a BLOCK, ``2 * n_layers`` of them, block ``i`` of layer ``l``
    # at ``2l + i``.
    attn_blocks: int = 1
    # RoPE over interleaved pairs (2i, 2i+1) instead of the split halves
    # (i, i + D/2): how the checkpoint's q/k columns are ordered.
    rope_interleave: bool = False
    # The first ``first_dense_layers`` layers keep a dense SwiGLU of
    # ``d_ff`` (``params["dense_layers"]``, run first); the expert layers
    # (``params["layers"]``) follow. The cache's layer index runs over both.
    first_dense_layers: int = 0
    # An expert's FFN width (0 = ``d_ff``) and the shared experts every
    # token passes through beside its routed ones (one SwiGLU of
    # ``n_shared_experts * expert_d_ff``).
    expert_d_ff: int = 0
    n_shared_experts: int = 0
    # 'softmax': without latent attention, the gates are the renormalised
    # top-k of a softmax (the ``moe_dispatch`` family above). 'sigmoid'
    # (DeepSeek-V3's ``noaux_tc``): scores are sigmoids. With latent
    # attention either score is served by the routed expert layer
    # (ops/moe.py), which drops no token at any load: a per-expert bias
    # moves the SELECTION alone, the selected scores are divided by their
    # sum if ``norm_topk`` and multiplied by ``routed_scaling``.
    router_score: str = "softmax"
    routed_scaling: float = 1.0
    norm_topk: bool = True
    # Zero-compute experts: the router has ``n_experts + zero_experts``
    # outputs, and a pair that chose an index >= ``n_experts`` adds its
    # weight times the layer's input itself (the identity, no weights).
    zero_experts: int = 0
    # ``(first, count)``: this device holds experts ``[first, first +
    # count)`` of each layer and no others (one chip's share of an
    # expert-parallel deployment). The router keeps all its outputs and its
    # top-k; a pair that chose an absent expert adds nothing HERE: the
    # layer's output is this chip's part of the sum. None: every expert.
    experts_held: tuple[int, int] | None = None
    # A grouped-query head's width where the checkpoint states one that is
    # not ``d_model // n_heads`` (``wq`` then maps d_model to ``n_heads *
    # head_dim`` and ``wo`` back). 0: ``d_model // n_heads``.
    stated_head_dim: int = 0
    # Two KINDS of layer in one model: ``window_pattern`` says which layers
    # of a period are sliding-window layers (a query at i sees the keys j
    # with ``i - sliding_window < j <= i``), the others are full; the
    # period is the pattern's length and divides ``n_layers``. ``(False,)``
    # is a model of full layers alone. Each kind rotates by its own
    # ``RopeKind`` (None: ``rope_theta``, plain). The serving pool is
    # allocated by kind: a window layer holds a ring of ``sliding_window``
    # rows a slot, a full layer the whole context (``kvcache/backend.py``,
    # layout "by_kind").
    sliding_window: int = 0
    window_pattern: tuple[bool, ...] = ()
    rope_window: RopeKind | None = None
    rope_full: RopeKind | None = None
    # Linear-attention (KDA, ops/kda.py) layers beside latent-attention
    # layers (models/linear_attn.py): ``linear_pattern`` says which layers
    # of a period are linear (True) and which latent (False); the period
    # divides the layers after ``first_dense_layers``, which are linear
    # too. A linear layer has ``n_heads`` heads of ``linear_head_dim``, a
    # causal depthwise convolution over ``linear_conv`` tokens on q, k and
    # v, and a decay a channel ``linear_lower_bound * sigmoid(..)``. What a
    # slot keeps of it is a recurrent state and a conv tail, not rows by
    # position (``kvcache/backend.py``, layout "state").
    linear_pattern: tuple[bool, ...] = ()
    linear_head_dim: int = 128
    linear_conv: int = 4
    linear_lower_bound: float = -5.0
    # A head-wise sigmoid gate on the attention's output before ``wo``
    # (one scalar a head, ``wg`` [D, H]); built beside ``linear_pattern``.
    attn_gate: bool = False
    # WHICH recurrence a linear layer of a ``linear_pattern`` runs, apart
    # from which attention the other layers run (latent where
    # ``kv_lora_rank`` > 0, else grouped-query with K and V rows): "kda",
    # the delta rule above, or "ssd", the Mamba-2 mixer (ops/ssd.py):
    # ``ssd_heads`` heads of ``ssd_head_dim`` with a state of
    # ``ssd_state_dim`` a head and ONE group of B and C, a scalar decay a
    # head, a conv with a bias over ``linear_conv`` tokens on x, B and C, a
    # gated RMSNorm over all the heads' channels, the admission's scan in
    # chunks of ``ssd_chunk``; or "conv", the gated short convolution
    # (ops/gconv.py): ``d_model`` channels, ``linear_conv`` taps, no bias,
    # no activation, gated on both sides, and NO state: a slot keeps the
    # conv tail alone.
    linear_kind: str = "kda"
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_state_dim: int = 0
    ssd_chunk: int = 256
    # What a checkpoint of the ``linear_pattern`` families states beside
    # its shapes (each at its default builds what was built before it):
    # the embedding's rows times ``embedding_multiplier``; both branches of
    # every layer times ``residual_multiplier`` before they join the
    # stream; the attention's scores times ``attention_multiplier`` in
    # place of ``1 / sqrt(head_dim)`` (0: the latter); the logits divided
    # by ``logits_scaling``; ``use_rope`` False: no rotation in any layer
    # (no positions at all: "nope"); ``tie_embeddings``: the head is the
    # embedding's transpose and the tree has no ``lm_head``; ``norm_eps``
    # under every RMSNorm's root.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    use_rope: bool = True
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # A grouped-query layer's q and k are RMS-normed over a HEAD's width
    # with a learned weight (``q_head_norm``, ``k_head_norm`` [Dh]) before
    # the rotation: the cache's K rows are normed and rotated.
    qk_norm: bool = False
    # Group-limited selection (DeepSeek-V3's): the router's outputs fall
    # into ``n_group`` groups of consecutive experts, a group scores the
    # sum of its two best biased scores, the ``topk_group`` best groups
    # stay and the top-k is taken among them. (1, 1): no groups.
    n_group: int = 1
    topk_group: int = 1
    # Learned sparse attention (DeepSeek-Sparse-Attention's indexer beside
    # grouped-query attention, ops/dsa.py): every full layer of a
    # ``window_pattern`` of full layers alone has an indexer of
    # ``index_heads`` heads of ``index_head_dim`` (``wiq``), ONE index key a
    # position (``wik``) and a weight a head (``wiw``), all from the
    # layer's normed input, queries and key roped with the layer's theta;
    # a query attends to the ``index_topk`` earlier positions of largest
    # ``sum_j w_j relu(qI_j . kI)`` alone. The serving pool keeps the index
    # key beside the K and V rows (``kvcache/backend.py``, layout
    # "indexed"). 0: none.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    @property
    def head_dim(self) -> int:
        return self.stated_head_dim or self.d_model // self.n_heads

    def layer_kind(self, j: int) -> tuple[int | None, "RopeKind | float"]:
        """``(window or None, rope)`` of the ``j``-th layer of a period:
        what ``_rope`` and the attention of that layer are handed."""
        if self.window_pattern[j]:
            return self.sliding_window, self.rope_window or self.rope_theta
        return None, self.rope_full or self.rope_theta

    def kind_rank(self, j: int) -> tuple[int, int]:
        """Of the ``j``-th layer of a period: ``(how many layers of its
        kind come before it in the period, how many a period has)``. Layer
        ``j`` of period ``i`` is row ``i * count + rank`` of its kind's
        pool."""
        same = [w == self.window_pattern[j] for w in self.window_pattern]
        return sum(same[:j]), sum(same)

    def kind_layers(self, window: bool) -> int:
        """Layers of one kind in the whole model."""
        if not self.window_pattern:
            return 0 if window else self.n_layers
        per = sum(w == window for w in self.window_pattern)
        return self.n_layers // len(self.window_pattern) * per

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_sparse(self) -> bool:
        """Learned sparse attention: an indexer selects what a query reads."""
        return self.index_topk > 0

    @property
    def routed_moe(self) -> bool:
        """Expert layers of the routed kind (ops/moe.py): sigmoid scores,
        any score beside latent attention, or experts of a stated width
        of their own (``expert_d_ff``) beside grouped-query attention."""
        return self.n_experts > 0 and (
            self.router_score == "sigmoid" or self.is_mla
            or self.expert_d_ff > 0
        )

    @property
    def latent_dim(self) -> int:
        """What MLA caches a position a block: latent beside roped key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def cache_layers(self) -> int:
        """Rows of the stacked cache: one an attention block (a hybrid
        model's latent layers alone)."""
        if self.linear_pattern:
            return self.hybrid_layers(False)
        return self.n_layers * self.attn_blocks

    def hybrid_layers(self, linear: bool) -> int:
        """Layers of one kind of a ``linear_pattern`` model: the leading
        dense layers are linear."""
        pattern = self.linear_pattern
        periods = (self.n_layers - self.first_dense_layers) // len(pattern)
        per = sum(k == linear for k in pattern)
        return periods * per + (self.first_dense_layers if linear else 0)

    @property
    def router_width(self) -> int:
        return self.n_experts + self.zero_experts

    @property
    def moe_partial(self) -> bool:
        """The routed layer's pairs do not all meet an expert's weights
        here: some choose the identity, or an expert on another chip."""
        return self.routed_moe and (
            self.experts_held is not None or self.zero_experts > 0
        )

    @property
    def held_experts(self) -> tuple[int, int]:
        """``(first, count)`` of the experts whose weights are here."""
        return self.experts_held or (0, self.n_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def moe_d_ff(self) -> int:
        return self.expert_d_ff or self.d_ff

    @property
    def ssd_inner(self) -> int:
        """The Mamba-2 mixer's channels: every head's."""
        return self.ssd_heads * self.ssd_head_dim

    @property
    def ssd_conv_dim(self) -> int:
        """What the mixer's convolution runs over, and its tail keeps: x
        beside the one group's B and C."""
        return self.ssd_inner + 2 * self.ssd_state_dim

    @property
    def attn_scale(self) -> float:
        """What multiplies a grouped-query layer's scores."""
        return self.attention_multiplier or 1.0 / math.sqrt(self.head_dim)

    def __post_init__(self) -> None:
        if self.d_model % self.n_heads:
            raise ValueError("d_model must divide by n_heads")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must divide by n_kv_heads")
        if self.n_experts and self.expert_top_k > self.router_width:
            raise ValueError(
                "expert_top_k cannot exceed n_experts (+ zero_experts)"
            )
        if self.moe_dispatch not in ("dense", "capacity"):
            raise ValueError(
                f"moe_dispatch must be 'dense' or 'capacity', got "
                f"{self.moe_dispatch!r}"
            )
        if self.capacity_factor <= 0:
            raise ValueError("capacity_factor must be positive")
        if self.moe_group_size < 1:
            raise ValueError("moe_group_size must be >= 1")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router_score must be 'softmax' or 'sigmoid', got "
                f"{self.router_score!r}"
            )
        if self.is_mla and not (
            self.qk_nope_dim > 0 and self.v_head_dim > 0
            and self.qk_rope_dim > 0 and self.qk_rope_dim % 2 == 0
        ):
            raise ValueError(
                "kv_lora_rank > 0 (latent attention) needs qk_nope_dim, "
                "v_head_dim and an even qk_rope_dim"
            )
        gqa_routed = (
            # The routed layer beside grouped-query attention, as built:
            # every expert held or a share (``experts_held``), no zero
            # experts; a shared expert in a ``linear_pattern`` model alone
            # (whose groups are stacked by ``_hybrid_shapes``); and either
            # the renormalised softmax top-k, no selection bias, every
            # layer an expert layer, or, beside the gated convolution
            # (``linear_kind`` "conv"), sigmoid scores with a selection
            # bias after leading dense layers.
            self.routed_moe and not self.is_mla and not self.zero_experts
            and (bool(self.linear_pattern) or not self.n_shared_experts)
            and (
                (self.router_score == "softmax" and self.norm_topk
                 and not self.first_dense_layers
                 and self.routed_scaling == 1.0)
                or (self.router_score == "sigmoid"
                    and bool(self.linear_pattern)
                    and self.linear_kind == "conv")
            )
        )
        if self.is_moe and self.routed_moe != self.is_mla and not gqa_routed:
            raise ValueError(
                "router_score='sigmoid' (the routed expert layer, a "
                "leading dense group) and latent attention (kv_lora_rank "
                "> 0) are built together only: the grouped-query decode "
                'loops scan params["layers"] alone, and the latent '
                "layers' experts are the routed ones (beside grouped-query "
                "attention the routed layer is built for experts of a "
                "stated width, expert_d_ff, under the renormalised softmax "
                "top-k alone: norm_topk=True, routed_scaling 1, no zero "
                "experts, no leading dense layer, every expert held or a "
                "share, experts_held; a shared expert, n_shared_experts, in "
                "a linear_pattern model alone; sigmoid scores with a "
                "selection bias and leading dense layers in a "
                "linear_pattern of linear_kind='conv' alone)"
            )
        if self.is_mla and self.is_moe and (
            self.router_score == "softmax" and self.norm_topk
        ):
            raise ValueError(
                "router_score='softmax' beside latent attention is built "
                "with norm_topk=False alone (the selected probabilities "
                "times routed_scaling, as the zero-compute-expert family "
                "routes): the renormalised softmax top-k is the "
                "moe_dispatch family's, which latent attention does not run"
            )
        if not self.is_mla and (
            self.q_lora_rank or self.mla_scale_q_lora
            or self.mla_scale_kv_lora or self.attn_blocks != 1
        ):
            raise ValueError(
                "q_lora_rank, mla_scale_q_lora, mla_scale_kv_lora and "
                "attn_blocks describe latent attention: set kv_lora_rank "
                "> 0 (the grouped-query layer has one attention block and "
                "no compressed query)"
            )
        if self.q_lora_rank < 0 or (
            self.mla_scale_q_lora and not self.q_lora_rank
        ):
            raise ValueError(
                "mla_scale_q_lora scales the COMPRESSED query: it needs "
                "q_lora_rank > 0"
            )
        if self.attn_blocks not in (1, 2):
            raise ValueError(
                f"attn_blocks must be 1 or 2, got {self.attn_blocks}: the "
                "layers built are the plain one and the shortcut-connected "
                "double layer"
            )
        if self.attn_blocks == 2 and not (
            self.routed_moe and not self.first_dense_layers
            and not self.n_shared_experts
        ):
            raise ValueError(
                "attn_blocks=2 is the shortcut-connected double layer as "
                "built: two latent blocks, two dense SwiGLUs of d_ff and "
                "ONE routed expert branch in EVERY layer (n_experts > 0, "
                "no first_dense_layers, no shared experts: its dense "
                "SwiGLUs take their place)"
            )
        if not self.routed_moe and (
            self.zero_experts or self.experts_held is not None
            or not self.norm_topk
        ):
            raise ValueError(
                "zero_experts, experts_held and norm_topk=False describe "
                "the routed expert layer (ops/moe.py): set n_experts > 0 "
                "beside latent attention; the softmax family of "
                "moe_dispatch computes every expert for every token and "
                "renormalises its top-k"
            )
        if self.zero_experts < 0:
            raise ValueError("zero_experts must be >= 0")
        if self.experts_held is not None:
            first, count = self.experts_held
            if not (0 <= first and count >= 1
                    and first + count <= self.n_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} must name a "
                    f"non-empty range (first, count) inside the "
                    f"{self.n_experts} routed experts"
                )
        if self.rope_interleave and not self.is_mla:
            raise ValueError(
                "rope_interleave is read by latent attention alone "
                "(kv_lora_rank > 0): the grouped-query paths rotate split "
                "halves"
            )
        if not self.routed_moe and (
            self.first_dense_layers or self.n_shared_experts
            or self.expert_d_ff or self.routed_scaling != 1.0
        ):
            raise ValueError(
                "first_dense_layers, n_shared_experts, expert_d_ff and "
                "routed_scaling describe the routed expert layer: "
                "set n_experts > 0 and router_score='sigmoid'"
            )
        if not 0 <= self.first_dense_layers < max(self.n_layers, 1):
            raise ValueError(
                "first_dense_layers must leave at least one expert layer"
            )
        if self.stated_head_dim < 0 or (self.stated_head_dim and self.is_mla):
            raise ValueError(
                "stated_head_dim is a grouped-query head's width (>= 0); "
                "latent attention states its own (qk_nope_dim, qk_rope_dim, "
                "v_head_dim)"
            )
        pattern = self.window_pattern
        if pattern and (
            self.n_layers % len(pattern)
            or any(pattern) != (self.sliding_window > 0)
        ):
            raise ValueError(
                f"window_pattern={pattern} must divide n_layers="
                f"{self.n_layers} into whole periods, and sliding_window="
                f"{self.sliding_window} must be positive exactly when the "
                "pattern has a window layer"
            )
        if not pattern and (
            self.sliding_window or self.rope_window or self.rope_full
        ):
            raise ValueError(
                "sliding_window, rope_window and rope_full describe the "
                "kinds of layer of a window_pattern: set one ((False,) is "
                "a model of full layers alone)"
            )
        hybrid = self.linear_pattern
        if hybrid and self.linear_kind == "kda" and not (
            self.is_mla and not self.q_lora_rank
            and self.linear_head_dim > 0 and self.linear_lower_bound < 0
        ):
            raise ValueError(
                "linear_kind='kda' (the delta rule with a decay a channel) "
                "is built beside latent attention (kv_lora_rank > 0, no "
                "q_lora_rank) with linear_head_dim > 0 and a negative "
                "linear_lower_bound"
            )
        if hybrid and self.linear_kind == "ssd" and not (
            not self.is_mla and not self.attn_gate
            and min(self.ssd_heads, self.ssd_head_dim, self.ssd_state_dim,
                    self.ssd_chunk) > 0
        ):
            raise ValueError(
                "linear_kind='ssd' (the Mamba-2 mixer) is built beside "
                "grouped-query attention over K and V rows (no "
                "kv_lora_rank, no attn_gate) with ssd_heads, ssd_head_dim, "
                "ssd_state_dim and ssd_chunk > 0"
            )
        if hybrid and self.linear_kind == "conv" and (
            self.is_mla or self.attn_gate
        ):
            raise ValueError(
                "linear_kind='conv' (the gated short convolution) is built "
                "beside grouped-query attention over K and V rows (no "
                "kv_lora_rank, no attn_gate)"
            )
        if hybrid and not (
            self.attn_blocks == 1 and not pattern
            and any(hybrid) and not all(hybrid)
            and (self.n_layers - self.first_dense_layers) % len(hybrid) == 0
            and self.linear_conv >= 2
            and self.linear_kind in ("kda", "ssd", "conv")
        ):
            raise ValueError(
                f"linear_pattern={hybrid} (linear_kind={self.linear_kind!r}) "
                "is a period of linear AND attention layers that divides "
                "the layers after first_dense_layers, linear_conv >= 2, no "
                "attn_blocks 2 and no window_pattern; the linear layers "
                "run linear_kind 'kda' (the delta rule), 'ssd' (the "
                "Mamba-2 mixer) or 'conv' (the gated short convolution), "
                "the others latent attention where "
                "kv_lora_rank > 0 and grouped-query attention over K and "
                "V rows otherwise"
            )
        if not (hybrid and self.linear_kind == "ssd") and (
            self.linear_kind not in (("kda", "conv") if hybrid else ("kda",))
            or self.ssd_heads or self.ssd_head_dim or self.ssd_state_dim
        ):
            raise ValueError(
                "linear_kind, ssd_heads, ssd_head_dim and ssd_state_dim "
                "describe the linear layers of a linear_pattern: set one "
                "with linear_kind='ssd' (the sizes) or 'conv' (none)"
            )
        if self.qk_norm and (not hybrid or self.is_mla):
            raise ValueError(
                "qk_norm (an RMSNorm a head on q and k before the rotation) "
                "is built into the grouped-query layers of a linear_pattern "
                "model alone: no other path draws, shards or quantises "
                "q_head_norm and k_head_norm"
            )
        if self.attn_gate and not hybrid:
            raise ValueError(
                "attn_gate (the head-wise output gate) is built for the "
                "layers of a linear_pattern alone"
            )
        stated = {
            "embedding_multiplier": self.embedding_multiplier != 1.0,
            "residual_multiplier": self.residual_multiplier != 1.0,
            "attention_multiplier": self.attention_multiplier != 0.0,
            "logits_scaling": self.logits_scaling != 1.0,
            "use_rope=False": not self.use_rope,
            "tie_embeddings": self.tie_embeddings,
            "norm_eps": self.norm_eps != 1e-6,
        }
        if any(stated.values()) and not hybrid:
            raise ValueError(
                f"{', '.join(n for n, on in stated.items() if on)}: the "
                "multipliers, attention without positions, the tied head "
                "and a stated norm_eps are built into the layers and the "
                "head of a linear_pattern model alone (they serve on one "
                "device through StreamingGenerator and run Transformer's "
                "forward); the dense, windowed and latent paths, training, "
                "generate(), pages, speculation and quantize_params have "
                "not been taught them"
            )
        if self.is_mla and (not self.use_rope or self.attention_multiplier):
            raise ValueError(
                "use_rope=False (no positions) and attention_multiplier "
                "are built for grouped-query layers: latent attention "
                "caches a roped key beside its latent and scales by its "
                "own head width"
            )
        if (self.attention_multiplier < 0 or self.logits_scaling <= 0
                or self.norm_eps <= 0):
            raise ValueError(
                "attention_multiplier must be >= 0 (0: 1 / sqrt(head_dim)), "
                "logits_scaling and norm_eps positive"
            )
        if (self.n_group, self.topk_group) != (1, 1) and not (
            self.routed_moe and not self.zero_experts
            and self.n_group >= 1 and self.n_experts % self.n_group == 0
            and 1 <= self.topk_group <= self.n_group
            and self.n_experts // self.n_group >= 2
            and self.expert_top_k
            <= self.topk_group * (self.n_experts // self.n_group)
        ):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group} "
                "describe the routed expert layer's group-limited "
                "selection: n_group divides n_experts into groups of at "
                "least two, topk_group of them hold expert_top_k experts, "
                "and there are no zero experts"
            )
        if pattern and (
            self.is_mla or self.first_dense_layers
            or self.attn_impl in ("ring", "ulysses")
        ):
            raise ValueError(
                "window_pattern is built for grouped-query layers in one "
                "stacked group on one device: not beside latent attention, "
                "first_dense_layers or a sequence-parallel attn_impl"
            )
        indexer = (self.index_heads, self.index_head_dim, self.index_topk)
        if any(indexer) and not (
            min(indexer) > 0 and self.index_head_dim % 2 == 0
            and pattern == (False,) and not hybrid
            and not self.attention_multiplier
        ):
            raise ValueError(
                f"index_heads={self.index_heads}, index_head_dim="
                f"{self.index_head_dim}, index_topk={self.index_topk} "
                "describe learned sparse attention (ops/dsa.py): all three "
                "positive, an even index_head_dim, beside the grouped-query "
                "full layers of window_pattern=(False,) (no window layer, "
                "no latent attention, no linear_pattern)"
            )


# --------------------------------------------------------------------- params


def param_specs(cfg: TransformerConfig) -> dict:
    """PartitionSpecs per tensor, over mesh axes {data, fsdp, tp, sp, ep}.

    Megatron 2D layout: the "output features" dim of up-projections (wq/wk/wv,
    w_gate/w_up) and the vocab dim shard over ``tp``; the opposing dim shards
    over ``fsdp`` (ZeRO-3-style weight sharding that XLA turns into
    all_gathers just-in-time). MoE expert weights add a leading expert dim
    sharded over ``ep``. Mesh axes absent from the actual Mesh are stripped
    by ``shardings_for_mesh``.
    """
    why = _arch_refusal(cfg, "param_specs (a sharded layout)")
    if why:
        raise ValueError(why)
    if cfg.is_moe:
        mlp = {
            "router": P(None, "fsdp", None),  # [L, D, E] — replicated over ep
            "w_gate": P(None, "ep", "fsdp", "tp"),  # [L, E, D, F]
            "w_up": P(None, "ep", "fsdp", "tp"),
            "w_down": P(None, "ep", "tp", "fsdp"),  # [L, E, F, D]
        }
    else:
        mlp = {
            "w_gate": P(None, "fsdp", "tp"),  # [L, D, F]
            "w_up": P(None, "fsdp", "tp"),
            "w_down": P(None, "tp", "fsdp"),  # [L, F, D]
        }
    # The stacked layer dim shards over 'pp' (each pipeline stage owns a
    # contiguous slice of layers); on meshes without pp it strips to None.
    def with_pp(spec: P) -> P:
        return P("pp", *tuple(spec)[1:])

    return {
        "embed": P("tp", "fsdp"),  # [V, D]
        "layers": {
            k: with_pp(v)
            for k, v in {
                "ln1": P(None, None),  # [L, D]
                "ln2": P(None, None),
                "wq": P(None, "fsdp", "tp", None),  # [L, D, H, Dh]
                "wk": P(None, "fsdp", "tp", None),  # [L, D, K, Dh]
                "wv": P(None, "fsdp", "tp", None),
                "wo": P(None, "tp", None, "fsdp"),  # [L, H, Dh, D]
                **mlp,
            }.items()
        },
        "ln_f": P(None),  # [D]
        "lm_head": P("fsdp", "tp"),  # [D, V]
    }


def _layer_groups(cfg: TransformerConfig) -> tuple[tuple[str, int, bool], ...]:
    """The stacked groups of a parameter tree in the order they run:
    ``(key, layers, expert_mlp)``. One group, ``"layers"``, unless the
    architecture leads with dense layers (``first_dense_layers``)."""
    lead = cfg.first_dense_layers
    groups = (("dense_layers", lead, False),) if lead else ()
    return groups + (("layers", cfg.n_layers - lead, cfg.is_moe),)


# What each attention block of the double layer has of its own, stacked on
# a [2] axis after [L]; the rest of a layer (router, experts) is the one
# expert branch's.
_BLOCK_TENSORS = (
    "ln1", "ln2", "wq", "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb",
    "wo", "w_gate", "w_up", "w_down",
)
_EXPERT_TENSORS = ("we_gate", "we_up", "we_down")


def _arch_shapes(cfg: TransformerConfig, expert_mlp: bool) -> dict:
    """One layer's tensors of a latent-attention config, or of the
    grouped-query layer of a ``linear_pattern`` model: name -> (shape,
    fan_in or None for a norm's scale or the selection bias). In the
    double layer (``attn_blocks`` 2) the attention's and the dense
    SwiGLU's tensors lead with the block axis, and the held experts have
    names of their own (``we_*``)."""
    dm, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    shapes = {"ln1": ((dm,), None), "ln2": ((dm,), None)}
    if not cfg.is_mla:
        k, dh = cfg.n_kv_heads, cfg.head_dim
        shapes.update(
            wq=((dm, h, dh), dm), wk=((dm, k, dh), dm), wv=((dm, k, dh), dm),
            wo=((h, dh, dm), h * dh),
        )
        if cfg.qk_norm:
            shapes.update(q_head_norm=((dh,), None), k_head_norm=((dh,), None))
    elif cfg.q_lora_rank:
        q = cfg.q_lora_rank
        shapes.update(
            wqa=((dm, q), dm), q_norm=((q,), None),
            wqb=((q, h, cfg.qk_head_dim), q),
        )
    else:
        shapes["wq"] = ((dm, h, cfg.qk_head_dim), dm)
    if cfg.is_mla:
        shapes.update({
            # One projection gives the latent and the shared roped key.
            "wkva": ((dm, cfg.latent_dim), dm),
            "kv_norm": ((r,), None),
            # Up-projection of the latent: a head's k_nope beside its v.
            "wkvb": ((r, h, cfg.qk_nope_dim + cfg.v_head_dim), r),
            "wo": ((h, cfg.v_head_dim, dm), h * cfg.v_head_dim),
        })
    double = cfg.attn_blocks == 2
    lead, f = (), cfg.d_ff
    if expert_mlp:
        e, w = cfg.held_experts[1], cfg.router_width
        shapes["router"] = ((dm, w), dm)
        # The latent families state a selection bias, and the sigmoid
        # router beside grouped-query attention.
        if cfg.is_mla or cfg.router_score == "sigmoid":
            shapes["router_bias"] = ((w,), None)
        fs = cfg.n_shared_experts * cfg.moe_d_ff
        if fs:  # the shared experts, one SwiGLU
            shapes.update(
                ws_gate=((dm, fs), dm), ws_up=((dm, fs), dm),
                ws_down=((fs, dm), fs),
            )
        if double:  # the dense SwiGLUs keep ``w_*``
            fe = cfg.moe_d_ff
            shapes.update(
                we_gate=((e, dm, fe), dm), we_up=((e, dm, fe), dm),
                we_down=((e, fe, dm), fe),
            )
        else:
            lead, f = (e,), cfg.moe_d_ff
    shapes.update(
        w_gate=(lead + (dm, f), dm), w_up=(lead + (dm, f), dm),
        w_down=(lead + (f, dm), f),
    )
    if double:
        shapes = {
            n: (((2,) + shape) if n in _BLOCK_TENSORS else shape, fan)
            for n, (shape, fan) in shapes.items()
        }
    return shapes


# A hybrid model's tensors by kind (``linear_pattern``): what a linear
# layer has of its own by its recurrence, what an attention layer has by
# its attention; the rest (the norms, the MLP's) every layer has.
_KDA_TENSORS = (
    "lqkv", "lconv", "lf", "l_alog", "l_dt", "lb", "lg", "lnorm", "lo",
)
_SSD_TENSORS = (
    "s_in", "s_in_dt", "s_conv", "s_conv_b", "s_dt", "s_alog", "s_d",
    "s_norm", "s_out",
)
# (the output projection [1, D, D]: one "head" of every channel, as ``lo``
# and ``s_out`` are [H, E, D], so the layers' tail is one einsum)
_CONV_TENSORS = ("g_in", "g_conv", "g_out")
_LINEAR_TENSORS = {
    "kda": _KDA_TENSORS, "ssd": _SSD_TENSORS, "conv": _CONV_TENSORS,
}
_LATENT_TENSORS = ("wq", "wkva", "kv_norm", "wkvb", "wo", "wg")
_GQA_TENSORS = ("wq", "wk", "wv", "wo")
_QK_NORM_TENSORS = ("q_head_norm", "k_head_norm")  # (``qk_norm``)


def hybrid_tensors(cfg: TransformerConfig) -> dict[bool, tuple[str, ...]]:
    """``{True: a linear layer's own tensors, False: an attention
    layer's}`` of a ``linear_pattern`` model."""
    return {
        True: _LINEAR_TENSORS[cfg.linear_kind],
        False: _LATENT_TENSORS if cfg.is_mla
        else _GQA_TENSORS + (_QK_NORM_TENSORS if cfg.qk_norm else ()),
    }


def _linear_shapes(cfg: TransformerConfig) -> dict:
    """One linear layer's own tensors (``models/linear_attn.py``): name
    -> (shape, fan_in or None)."""
    dm = cfg.d_model
    if cfg.linear_kind == "conv":
        return {
            # [b | c | x] side by side; no bias anywhere.
            "g_in": ((dm, 3 * dm), dm),
            "g_conv": ((cfg.linear_conv, dm), cfg.linear_conv),
            "g_out": ((1, dm, dm), dm),
        }
    if cfg.linear_kind == "ssd":
        h, inner, conv = cfg.ssd_heads, cfg.ssd_inner, cfg.ssd_conv_dim
        return {
            # [z | x B C] side by side; the step apart, read in float32.
            "s_in": ((dm, inner + conv), dm), "s_in_dt": ((dm, h), dm),
            "s_conv": ((cfg.linear_conv, conv), cfg.linear_conv),
            "s_conv_b": ((conv,), None), "s_dt": ((h,), None),
            "s_alog": ((h,), None), "s_d": ((h,), None),
            "s_norm": ((inner,), None),
            # (a head's rows apart, as ``lo`` and ``wo``: reshaped inside
            # the program the matrix is copied every tick)
            "s_out": ((h, cfg.ssd_head_dim, dm), inner),
        }
    h, e = cfg.n_heads, cfg.linear_head_dim
    return {
        "lqkv": ((dm, 3 * h * e), dm),
        "lconv": ((cfg.linear_conv, 3 * h * e), cfg.linear_conv),
        "lf": ((dm, h, e), dm), "l_alog": ((h,), None),
        "l_dt": ((h, e), None), "lb": ((dm, h), dm), "lg": ((dm, h), dm),
        "lnorm": ((e,), None), "lo": ((h, e, dm), h * e),
    }


def _hybrid_shapes(cfg: TransformerConfig, nl: int, expert_mlp: bool) -> dict:
    """A hybrid group of ``nl`` layers, stacked by kind: name -> (stacked
    shape, fan_in or None). What every layer has leads with ``nl``, a
    kind's own tensors with the group's layers of the kind (the leading
    dense layers are all linear)."""
    shapes = _arch_shapes(cfg, expert_mlp)
    attn = {
        n: v for n, v in shapes.items() if n in hybrid_tensors(cfg)[False]
    }
    if cfg.attn_gate:
        attn["wg"] = ((cfg.d_model, cfg.n_heads), cfg.d_model)
    common = {n: v for n, v in shapes.items() if n not in attn}
    lead = cfg.first_dense_layers and not expert_mlp
    pattern = (True,) if lead else cfg.linear_pattern
    n_lin = nl // len(pattern) * sum(pattern)
    return {
        n: ((count, *shape), fan)
        for count, part in (
            (nl, common), (n_lin, _linear_shapes(cfg)), (nl - n_lin, attn),
        ) if count
        for n, (shape, fan) in part.items()
    }


def hybrid_groups(cfg: TransformerConfig):
    """The stacked groups of a ``linear_pattern`` model in the order they
    run: ``(key, the period's pattern, the group's first row among the
    linear layers, among the latent layers)``. The leading dense layers
    are a group of periods of one linear layer."""
    lead = cfg.first_dense_layers
    groups = (("dense_layers", (True,), 0, 0),) if lead else ()
    return groups + (("layers", tuple(cfg.linear_pattern), lead, 0),)


def scan_hybrid(cfg, group, pattern, carry, step, lin0=0, lat0=0):
    """``scan_periods`` for a group stacked BY KIND: the tensors every
    layer has ``[L, ...]``, a linear layer's own over the group's linear
    layers, a latent layer's over its latent layers; each taken by one
    dynamic index at the layer's row in its stack. ``step(carry, layer,
    linear, row) -> (carry, y)`` with ``row`` the layer's row among ALL
    the model's layers of its kind (``lin0``, ``lat0``: the group's
    first). Returns (carry, a tuple over the period's layers of the ``y``
    stacked over the periods)."""
    p = len(pattern)
    per = {True: sum(pattern), False: p - sum(pattern)}
    experts, rest = _expert_stacks(cfg, group)
    own = hybrid_tensors(cfg)

    def period(carry, i):
        ys = []
        for j, linear in enumerate(pattern):
            at = i * p + j
            kind_at = i * per[linear] + sum(k == linear for k in pattern[:j])
            layer = {
                n: lax.dynamic_index_in_dim(
                    w, kind_at if n in own[linear] else at, keepdims=False
                )
                for n, w in rest.items() if n not in own[not linear]
            }
            if experts:
                layer["experts_at"] = (*experts, at * cfg.held_experts[1])
            carry, y = step(
                carry, layer, linear, (lin0 if linear else lat0) + kind_at
            )
            ys.append(y)
        return carry, tuple(ys)

    return lax.scan(period, carry, jnp.arange(group["ln1"].shape[0] // p))


def _arch_init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """``init_params`` for the configs ``_arch_shapes`` describes: scaled
    normals, norms at one, the selection bias at zero; every tensor of
    every group from a key of its own."""
    pd, dm, v = cfg.param_dtype, cfg.d_model, cfg.vocab_size

    def draw(key, shape, fan_in):
        if fan_in is None:  # a norm's scale, or the selection bias
            return jnp.ones(shape, pd)
        return (jax.random.normal(key, shape, pd) / math.sqrt(fan_in)).astype(pd)

    k_embed, k_head, k_groups = jax.random.split(rng, 3)
    out = {
        "embed": draw(k_embed, (v, dm), dm),
        "ln_f": jnp.ones((dm,), pd),
        "lm_head": draw(k_head, (dm, v), dm),
    }
    for g, (key, nl, expert_mlp) in enumerate(_layer_groups(cfg)):
        if cfg.linear_pattern:
            shapes = _hybrid_shapes(cfg, nl, expert_mlp)
        else:
            shapes = {
                n: ((nl, *shape), fan)
                for n, (shape, fan) in _arch_shapes(cfg, expert_mlp).items()
            }
        keys = jax.random.split(jax.random.fold_in(k_groups, g), len(shapes))
        out[key] = {
            name: draw(k, shape, fan_in)
            for k, (name, (shape, fan_in)) in zip(keys, shapes.items())
        }
        # A rate of one and no shift; the mixer's, and no conv bias.
        for name in ("l_alog", "l_dt", "s_alog", "s_dt", "s_conv_b"):
            if name in out[key]:
                out[key][name] = jnp.zeros_like(out[key][name])
        if "router_bias" in out[key]:
            out[key]["router_bias"] = jnp.zeros((nl, cfg.router_width), pd)
    if cfg.tie_embeddings:  # the head is the embedding's transpose
        del out["lm_head"]
    return out


def shardings_for_mesh(mesh: Mesh, specs: Any) -> Any:
    """Convert specs → NamedShardings, dropping axis names the mesh lacks."""

    def fix(spec: P) -> NamedSharding:
        parts = []
        for entry in spec:
            if entry is None:
                parts.append(None)
            elif isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a in mesh.shape)
                parts.append(kept if kept else None)
            else:
                parts.append(entry if entry in mesh.shape else None)
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map(
        fix, specs, is_leaf=lambda x: isinstance(x, P)
    )


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Scaled-normal init, stacked [L, ...] per layer tensor."""
    if cfg.is_mla or cfg.linear_pattern:
        return _arch_init_params(rng, cfg)
    keys = jax.random.split(rng, 10)
    dm, dff, nl = cfg.d_model, cfg.d_ff, cfg.n_layers
    h, k, dh, v = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size
    pd = cfg.param_dtype

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, pd) / math.sqrt(fan_in)).astype(pd)

    if cfg.is_moe:
        # (a share's layer holds ``experts_held`` of the router's outputs)
        ne, eff = cfg.held_experts[1], cfg.moe_d_ff
        mlp = {
            "router": norm(keys[8], (nl, dm, cfg.router_width), dm),
            "w_gate": norm(keys[5], (nl, ne, dm, eff), dm),
            "w_up": norm(keys[6], (nl, ne, dm, eff), dm),
            "w_down": norm(keys[7], (nl, ne, eff, dm), eff),
        }
    else:
        mlp = {
            "w_gate": norm(keys[5], (nl, dm, dff), dm),
            "w_up": norm(keys[6], (nl, dm, dff), dm),
            "w_down": norm(keys[7], (nl, dff, dm), dff),
        }
    return {
        "embed": norm(keys[0], (v, dm), dm),
        "layers": {
            "ln1": jnp.ones((nl, dm), pd),
            "ln2": jnp.ones((nl, dm), pd),
            "wq": norm(keys[1], (nl, dm, h, dh), dm),
            "wk": norm(keys[2], (nl, dm, k, dh), dm),
            "wv": norm(keys[3], (nl, dm, k, dh), dm),
            "wo": norm(keys[4], (nl, h, dh, dm), h * dh),
            **mlp,
            **_index_params(rng, cfg, norm),
        },
        "ln_f": jnp.ones((dm,), pd),
        "lm_head": norm(keys[9], (dm, v), dm),
    }


def _index_params(rng: jax.Array, cfg: TransformerConfig, norm) -> dict:
    """The indexer's tensors of a learned-sparse-attention config (none
    otherwise), from keys of their own: the other tensors are drawn as
    they were before it existed."""
    if not cfg.is_sparse:
        return {}
    nl, dm = cfg.n_layers, cfg.d_model
    hi, di = cfg.index_heads, cfg.index_head_dim
    kq, kk, kw = (jax.random.fold_in(rng, 100 + i) for i in range(3))
    return {
        "wiq": norm(kq, (nl, dm, hi, di), dm),
        "wik": norm(kk, (nl, dm, di), dm),
        "wiw": norm(kw, (nl, dm, hi), dm),
    }


# -------------------------------------------------------------------- forward


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms).astype(x.dtype) * scale.astype(x.dtype)


def qk_head_norm(q, k, layer, cfg: "TransformerConfig"):
    """``cfg.qk_norm``: q [B, S, H, Dh] and k [B, S, K, Dh] RMS-normed over
    a head's width with the layer's learned weights, before any rotation;
    else as they came."""
    if not cfg.qk_norm:
        return q, k
    return (
        _rms_norm(q, layer["q_head_norm"], cfg.norm_eps),
        _rms_norm(k, layer["k_head_norm"], cfg.norm_eps),
    )


def embed_tokens(params, cfg: "TransformerConfig", tokens: jax.Array):
    """The embedding's rows of ``tokens`` in the compute dtype, times the
    config's ``embedding_multiplier``."""
    x = embed_rows(params["embed"], tokens, cfg.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def join_residual(x, branch, cfg: "TransformerConfig"):
    """``x`` plus a layer's branch, times ``residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)
    return x + branch


def head_product(params, cfg: "TransformerConfig", x: jax.Array) -> jax.Array:
    """Final-normed x [..., D] → float32 logits [..., V]: against
    ``lm_head``, or the embedding's transpose where the head is tied,
    over the config's ``logits_scaling``."""
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "...d,vd->...v", x, load_weight(params["embed"], cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    else:
        logits = jnp.einsum(
            "...d,dv->...v", x, load_weight(params["lm_head"], cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.float32(cfg.logits_scaling)
    return logits


def router_aux(stats: jax.Array, n_tokens: int | jax.Array) -> jax.Array:
    """Switch load-balance loss from routing sufficient statistics.

    stats: [2, E] f32 — row 0 = Σ_tokens routed-one-hot (how many of the
    token·top-k assignments landed on each expert), row 1 = Σ_tokens router
    softmax prob per expert. aux = E · Σ_e (routed_e/N) · (probs_e/N),
    minimized at top_k when routing is uniform. Keeping token SUMS (not the
    pre-reduced scalar) is what lets pipeline parallelism collect the loss:
    per-microbatch sums add across microbatches/stages/sequence shards into
    exactly the full-batch statistic, where a product-of-means scalar would
    not (mean of products ≠ product of means)."""
    e = stats.shape[-1]
    return e * jnp.sum((stats[0] / n_tokens) * (stats[1] / n_tokens))


def _moe_mlp(
    h: jax.Array, layer: Mapping[str, jax.Array], cfg: "TransformerConfig"
) -> tuple[jax.Array, jax.Array]:
    """Top-k routed mixture of SwiGLU experts, expert dim sharded over the
    mesh's ``ep`` axis. Dense (one-hot combine) dispatch: each ep shard
    computes its local experts for all tokens and the gate-weighted combine
    reduces across ``ep`` (a psum XLA inserts). Exact w.r.t. the routing —
    no capacity-factor token dropping — at the cost of E/ep-fold local MLP
    compute; an all_to_all token-routing dispatch is the scale-up path.
    h: [B, S, D] → (output [B, S, D], router stats [2, E] for
    ``router_aux``)."""
    logits = jnp.einsum(
        "bsd,de->bse", h.astype(jnp.float32), layer["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [B,S,E]
    top_vals, top_idx = lax.top_k(probs, cfg.expert_top_k)  # [B,S,K]
    gates = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, cfg.n_experts, dtype=probs.dtype) * gates[..., None],
        axis=2,
    )  # [B,S,E] — gate weight per (token, expert), 0 if not routed
    gate_e = jax.nn.silu(
        jnp.einsum("bsd,edf->ebsf", h, load_weight(layer["w_gate"], cfg.dtype))
    )
    up_e = jnp.einsum("bsd,edf->ebsf", h, load_weight(layer["w_up"], cfg.dtype))
    out_e = jnp.einsum(
        "ebsf,efd->ebsd", gate_e * up_e, load_weight(layer["w_down"], cfg.dtype)
    )
    out = jnp.einsum("ebsd,bse->bsd", out_e, combine.astype(cfg.dtype))
    # Load-balance sufficient stats: token-summed routed counts and probs.
    routed = jnp.sum(
        jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32), axis=2
    )
    stats = jnp.stack([routed.sum(axis=(0, 1)), probs.sum(axis=(0, 1))])
    return out, stats


def moe_capacity(cfg: "TransformerConfig", n_tokens: int) -> int:
    """Per-expert token slots under capacity dispatch: the even share of
    (token, choice) assignments times ``capacity_factor``, padded to a
    multiple of 8 (TPU sublane) with a floor of 8."""
    even = n_tokens * cfg.expert_top_k / cfg.n_experts
    cap = int(math.ceil(even * cfg.capacity_factor))
    return max(8, -(-cap // 8) * 8)


def _moe_mlp_capacity(
    h: jax.Array, layer: Mapping[str, jax.Array], cfg: "TransformerConfig"
) -> tuple[jax.Array, jax.Array]:
    """Capacity-based token dispatch (the scale-up path): tokens are split
    into contiguous groups of ``moe_group_size``; within each group every
    expert accepts at most C = ceil(n_g·k/E · capacity_factor) tokens,
    routed via one-hot dispatch/combine einsums (the Mesh-TensorFlow /
    Switch MoE formulation — einsums, not gathers, so XLA shards the
    [G, E, C, D] expert batches over the mesh's ``ep`` axis and inserts
    the token-exchange collectives itself). Per-ep-shard MLP compute is
    k·cf·tokens/ep slots instead of the dense path's ALL tokens × local
    experts — the E/ep-fold saving the dense docstring calls out. Grouping
    bounds the dispatch einsum at n_g·E·C·D per group; ungrouped it grows
    quadratic in tokens and dominates (measured 20× the MLP at 16k
    tokens).

    Overflow beyond C (an uneven router within a group) is DROPPED,
    Switch-style: the token's k-th choice contributes nothing and its
    residual passes through; primary choices outrank secondary ones (the
    k axis is ordered ahead of the token axis in the position cumsum).
    Exactness: with ``capacity_factor`` high enough for zero drops this
    matches ``_moe_mlp`` to float tolerance (differential-tested)."""
    b, s, d = h.shape
    n = b * s
    e, k = cfg.n_experts, cfg.expert_top_k
    # Contiguous groups of exactly ``moe_group_size`` tokens, the tail group
    # padded with masked rows. Padding (vs the old largest-divisor search)
    # keeps groups full-size for ANY token count: a prime n used to
    # degenerate to 1-token groups, whose per-group capacity floor of 8
    # slots/expert blew the dispatch up 8·E-fold (ADVICE r3).
    n_g = min(cfg.moe_group_size, n)
    g = -(-n // n_g)
    n_pad = g * n_g
    cap = moe_capacity(cfg, n_g)
    x = h.reshape(n, d)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    # 1.0 for real tokens, 0.0 for padding: padded rows claim no capacity
    # slots, combine to zero output, and are excluded from the aux stats.
    valid = (jnp.arange(n_pad) < n).astype(jnp.float32)
    logits = jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), layer["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [N_pad, E]
    top_vals, top_idx = lax.top_k(probs, k)  # [N_pad, K]
    gates = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)

    def to_group_major(t: jax.Array) -> jax.Array:
        """[N_pad, K] → [G, K·n_g]: all primary choices outrank all
        secondary ones, tokens in sequence order within a tier."""
        return t.reshape(g, n_g, k).transpose(0, 2, 1).reshape(g, k * n_g)

    idx_g = to_group_major(top_idx)
    valid_g = to_group_major(jnp.broadcast_to(valid[:, None], (n_pad, k)))
    onehot = jax.nn.one_hot(idx_g, e, dtype=jnp.float32) * valid_g[..., None]
    pos = jnp.cumsum(onehot, axis=1) - onehot  # slot within (group, expert)
    keep = onehot * (pos < cap)  # overflow drops
    # dispatch/combine [G, K·n_g, E, C]: one-hot in the slot dim where kept.
    slot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    dispatch = keep[..., None] * slot
    gates_g = to_group_major(gates)
    combine = dispatch * gates_g[..., None, None]

    # Expose the k axis to the einsums instead of tiling activations
    # k-fold (a [K·N, D] copy that would survive into backward): the
    # contraction indexes tokens once and sums k inside the einsum.
    disp5 = dispatch.reshape(g, k, n_g, e, cap).astype(cfg.dtype)
    comb5 = combine.reshape(g, k, n_g, e, cap).astype(cfg.dtype)
    x_g = x.reshape(g, n_g, d)
    expert_in = jnp.einsum(
        "gknec,gnd->gecd", disp5, x_g
    )  # [G, E, C, D] — E ep-sharded; XLA inserts the token exchange
    gate_e = jax.nn.silu(
        jnp.einsum(
            "gecd,edf->gecf", expert_in, load_weight(layer["w_gate"], cfg.dtype)
        )
    )
    up_e = jnp.einsum(
        "gecd,edf->gecf", expert_in, load_weight(layer["w_up"], cfg.dtype)
    )
    out_e = jnp.einsum(
        "gecf,efd->gecd", gate_e * up_e, load_weight(layer["w_down"], cfg.dtype)
    )
    # Combine sums over (k, e, c) in one contraction → [G, n_g, D]; padded
    # rows combine to zero and are sliced off.
    out = jnp.einsum("gknec,gecd->gnd", comb5, out_e)
    out = out.reshape(n_pad, d)[:n].reshape(b, s, d)

    # Same Switch load-balance stats as the dense path (computed on the
    # PRE-capacity routing — the balance loss exists to prevent the very
    # imbalance that causes capacity drops). Padded rows excluded.
    routed = jnp.sum(
        jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=1
    ) * valid[:, None]  # [N_pad, E]
    stats = jnp.stack(
        [routed.sum(axis=0), (probs * valid[:, None]).sum(axis=0)]
    )
    return out, stats


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _rope(
    x: jax.Array, positions: jax.Array, theta: "float | RopeKind",
    interleave: bool = False,
) -> jax.Array:
    """Rotary embedding. x: [B, S, H, D]; positions: [S] global positions
    shared across the batch, or [B, S] per-row positions (the continuous-
    batching server's slots sit at different depths). Pair i rotates by
    ``position * theta**(-2i/D)``, or by a layer kind's own table
    (``theta`` a ``RopeKind``: YaRN's slowed pairs, cos and sin times its
    attention factor); its two members are columns (i, i + D/2), or (2i,
    2i + 1) with ``interleave`` (``TransformerConfig.rope_interleave``: how
    a checkpoint orders its columns)."""
    dim = x.shape[-1]
    gain = 1.0
    if isinstance(theta, RopeKind):
        freqs, gain = jnp.asarray(theta.inv_freq(dim)), theta.attention_factor
    else:
        freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [(B,) S, D/2]
    if angles.ndim == 2:
        angles = angles[None]  # broadcast over batch
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if gain != 1.0:
        cos, sin = cos * jnp.float32(gain), sin * jnp.float32(gain)
    if interleave:
        xf = x.astype(jnp.float32).reshape(*x.shape[:-1], dim // 2, 2)
        x1, x2 = xf[..., 0], xf[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def index_project(h, layer, cfg: "TransformerConfig", positions, rope):
    """The indexer's projections of normed h [B, S, D] (learned sparse
    attention): index queries qI [B, S, Hi, Di] and the ONE index key a
    position kI [B, S, Di], both roped over all their columns with the
    layer's ``rope``, in the compute dtype; the heads' weights w [B, S,
    Hi] in float32."""
    qi = jnp.einsum("bsd,dhe->bshe", h, load_weight(layer["wiq"], cfg.dtype))
    w = jnp.einsum(
        "bsd,dh->bsh", h, load_weight(layer["wiw"], cfg.dtype),
        preferred_element_type=jnp.float32,
    )
    return _rope(qi, positions, rope), index_key(h, layer, cfg, positions, rope), w


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def index_key(h, layer, cfg: "TransformerConfig", positions, rope):
    """``index_project``'s kI alone [B, S, Di] (what a cache keeps)."""
    ki = jnp.einsum("bsd,de->bse", h, load_weight(layer["wik"], cfg.dtype))
    return _rope(ki[:, :, None, :], positions, rope)[:, :, 0]


@tracing.scope(tracing.SCOPE_FFN)
def _dense_mlp(h: jax.Array, layer: Mapping[str, jax.Array], cfg) -> jax.Array:
    """SwiGLU on normed activations h [B, S, D]."""
    gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, load_weight(layer["w_gate"], cfg.dtype)))
    up = jnp.einsum("bsd,df->bsf", h, load_weight(layer["w_up"], cfg.dtype))
    return jnp.einsum("bsf,fd->bsd", gate * up, load_weight(layer["w_down"], cfg.dtype))


# The residual stream after the attention block (the output projection
# summed over ``tp`` and added): named, and so kept by ``_remat_layer``, only
# where a remat layer runs under a ``tp`` axis
# (``Transformer._keeps_attn_residual``).
REMAT_SAVED_TP = "tk_attn_residual"


def _remat_layer(fn: Callable) -> Callable:
    """``cfg.remat``'s ``jax.checkpoint`` of one layer (module docstring,
    "Remat."): everything is recomputed but what ``ops/flash.py`` names
    and, where the layer names it, ``REMAT_SAVED_TP``."""
    from torchkafka_tpu.ops.flash import REMAT_SAVED

    return jax.checkpoint(
        fn,
        policy=jax.checkpoint_policies.save_only_these_names(
            *REMAT_SAVED, REMAT_SAVED_TP
        ),
    )


def _double_scan(group: Mapping[str, jax.Array], first: int = 0):
    """What a ``lax.scan`` over the double layers of a stacked group
    runs on: ``(xs, layer_of)``. ``xs`` are the router and its bias
    (``[L, ...]``) beside the layer index from ``first``; ``layer_of(x)``
    makes ``_double_layer``'s ``(layer, stacks, l)`` of a step's slice.
    The blocks' tensors ``[L, 2, ...]`` and the held experts' ``[L, E,
    ...]`` do not ride the scan: they stay stacked, seen ``[2L, ...]`` and
    ``[L * E, ...]``, and a block or an expert takes its own by ONE
    dynamic index that fuses into the product reading it. As a scan's
    slice ``[2, ...]`` indexed again by block, the compiler materialises
    every layer's slice first: 14 of a 36 ms tick were copies of the
    dense weights (PERF.md, PR 31)."""
    stacks = {
        n: w.reshape(-1, *w.shape[2:]) for n, w in group.items()
        if n in _BLOCK_TENSORS + _EXPERT_TENSORS
    }
    rest = {n: w for n, w in group.items() if n not in stacks}
    count = rest["router"].shape[0]

    def layer_of(x):
        rest_l, l = x
        return rest_l, stacks, l - first

    return (rest, jnp.arange(first, first + count)), layer_of


def _double_layer(x, layer, cfg: "TransformerConfig", attend):
    """The shortcut-connected double layer (``attn_blocks`` 2), shared by
    the full forward, the admission's prefill and the decode tick:

        a0 = x  + MLA_0(N_in0(x));   m = N_post0(a0)
        s  = Experts(m)                          the shortcut branch
        b0 = a0 + F_0(m)
        a1 = b0 + MLA_1(N_in1(b0))
        y  = a1 + F_1(N_post1(a1)) + s           the branch rejoins

    ``layer``: one layer's tensors, its blocks' ``[2, ...]`` and its
    experts' ``[E, ...]``; or, inside a scan, ``_double_scan``'s ``(the
    router's slice, the stacks of every layer's blocks [2L, ...] and
    experts [L * E, ...], the layer's index in them)``. ``attend(i, h,
    block)`` is block ``i``'s attention on its normed input ``h`` → [B, S,
    H, v]: the caller's, because what is cached and how it is read differ
    between a whole sequence and a tick. Returns (y, the routing [B, S,
    top_k])."""
    from torchkafka_tpu.ops.moe import routed_moe_mlp

    layer, stacks, l = layer if isinstance(layer, tuple) else (layer, layer, 0)

    def block(i, x):
        blk = {
            n: lax.dynamic_index_in_dim(stacks[n], 2 * l + i, keepdims=False)
            for n in _BLOCK_TENSORS if n in stacks
        }
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            h = _rms_norm(x, blk["ln1"])
        attn = attend(i, h, blk)
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            x = x + jnp.einsum(
                "bshe,hed->bsd", attn, load_weight(blk["wo"], cfg.dtype)
            )
            return x, _rms_norm(x, blk["ln2"]), blk

    a0, m, blk0 = block(0, x)
    branch, routing = routed_moe_mlp(m, layer, cfg, experts=(
        *(stacks[n] for n in _EXPERT_TENSORS), l * cfg.held_experts[1]
    ))
    a1, m1, blk1 = block(1, a0 + _dense_mlp(m, blk0, cfg))
    return a1 + _dense_mlp(m1, blk1, cfg) + branch, routing


def _expert_stacks(cfg: "TransformerConfig", stacks):
    """A group's tensors as a period scan hands them on: (a routed expert
    layer's matrices as stacks of every layer's experts ``[L * E, ...]``,
    or () where the group has none; the group's other tensors)."""
    names = ("w_gate", "w_up", "w_down")
    if not (cfg.routed_moe and "router" in stacks):
        return (), dict(stacks)
    experts = tuple(
        stacks[n].reshape(-1, *stacks[n].shape[2:]) for n in names
    )
    return experts, {n: w for n, w in stacks.items() if n not in names}


def scan_periods(cfg: "TransformerConfig", stacks, carry, step, first=0):
    """``lax.scan`` over the PERIODS of ``cfg.window_pattern``, shared by
    the full forward, the admission's prefill and the decode tick. A
    period's layers run in a row inside one scan step, so each knows its
    kind statically (``cfg.layer_kind(j)``: a window or none, its rope);
    ``stacks`` (a group's tensors, ``[L, ...]``) do not ride the scan: layer
    ``j`` of period ``i`` takes its own by ONE dynamic index, which fuses
    into the product reading it (``_double_scan`` has the lesson).
    A routed expert layer's matrices are handed on as stacks of every
    layer's experts, ``layer["experts_at"]`` = ``(w_gate, w_up, w_down
    [L * E, ...], the layer's first row)``: ``ops/moe.py`` reaches an
    expert there, and its grouped matmul (Pallas calls, into which no
    slice fuses) takes the stack whole.
    ``step(carry, layer, j, i) -> (carry, y)``. Returns (carry, a tuple
    over ``j`` of the ``y`` stacked over the periods)."""
    p = len(cfg.window_pattern)
    experts, rest = _expert_stacks(cfg, stacks)

    def period(carry, i):
        ys = []
        for j in range(p):
            at = first + i * p + j
            layer = {
                n: lax.dynamic_index_in_dim(w, at, keepdims=False)
                for n, w in rest.items()
            }
            if experts:
                layer["experts_at"] = (*experts, at * cfg.held_experts[1])
            carry, y = step(carry, layer, j, i)
            ys.append(y)
        return carry, tuple(ys)

    count = next(iter(stacks.values())).shape[0]
    return lax.scan(period, carry, jnp.arange(count // p))


_STATEFUL = (
    "a recurrent state and a conv tail", "a float32 state and a conv tail",
)


def _arch_refusal(cfg: "TransformerConfig", what: str) -> str | None:
    """Why ``what`` does not take a latent-attention config, or one with
    kinds of layer or the routed layer beside grouped-query attention
    (None: it does, the config is neither)."""
    if cfg.linear_pattern:
        layers, keeps, memory = {
            "kda": ("linear-attention layers (linear_pattern)",) + _STATEFUL,
            "ssd": (
                "state-space layers (linear_pattern, linear_kind='ssd': the "
                "Mamba-2 mixer)",
            ) + _STATEFUL,
            "conv": (
                "gated short convolutions (linear_pattern, linear_kind="
                "'conv')", "a conv tail and no state,", "a conv tail",
            ),
        }[cfg.linear_kind]
        return (
            f"{what} is not built for a config with {layers}: what a slot "
            f"keeps of such a layer is {keeps} "
            "that no position indexes, so nothing that rebuilds, shares, "
            "pages, quantises, shards or differentiates a cache of rows by "
            "position can hold it (nor has any of them been taught the "
            "multipliers, the attention without positions or the tied head "
            "such a config may state). These configs serve on one device "
            f"through StreamingGenerator's slot memory by kind ({memory} a "
            "linear layer; a compute-dtype pool "
            "the attention layers, latent rows or K and V rows) and run "
            "Transformer's forward"
        )
    if cfg.is_sparse:
        return (
            f"{what} is not built for learned sparse attention (index_heads, "
            "index_head_dim, index_topk: an indexer scores every cached "
            "position and a query reads the rows it selected): the cache "
            "holds an index key a position beside the K and V rows, which "
            "nothing that pages, shares, quantises, shards, speculates on "
            "or rebuilds a cache of K and V rows knows of, and training the "
            "indexer needs its own alignment loss. These configs serve on "
            "one device through StreamingGenerator's indexed slot pool "
            "(compute-dtype rows, read by tk_dsa_index and tk_dsa_attend) "
            "and run Transformer's forward"
        )
    if cfg.window_pattern or (cfg.routed_moe and not cfg.is_mla):
        return (
            f"{what} is not built for a config with kinds of layer "
            "(window_pattern: sliding-window and full layers, a rope a "
            "kind) or the routed expert layer beside grouped-query "
            "attention (expert_d_ff): these configs serve on one device "
            "through StreamingGenerator's dense slot pools (compute-dtype "
            "cache, by layer kind where there is a window; bf16 or float32 "
            "weights) and run Transformer's forward. The backward flash "
            "kernels, the int8 pool and its Pallas read take no window, "
            "pages and the radix cache address one kind of pool, and no "
            "sharded layout has been taught the two pools"
        )
    if not cfg.is_mla:
        return None
    return (
        f"{what} is not built for latent attention (kv_lora_rank > 0) and "
        "its routed expert layer: these configs (with or without "
        "compressed queries, the double layer, zero-compute experts, a "
        "held share of the experts) serve on one device through "
        "StreamingGenerator's dense slot pool (compute-dtype cache, bf16 "
        "or float32 weights) and run Transformer's forward; nothing else "
        "has been taught their layouts, and no exchange across chips "
        "stands behind a held share"
    )


class Transformer:
    """Functional model bound to a config (and optionally a mesh for SP)."""

    def __init__(self, cfg: TransformerConfig, mesh: Mesh | None = None):
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None and mesh.size > 1:
            why = _arch_refusal(cfg, "a mesh of more than one device")
            if why:
                raise ValueError(why)
        sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
        if cfg.attn_impl in ("ring", "ulysses") and sp_size <= 1:
            # An *explicitly* requested sequence-parallel impl that cannot
            # engage is a misconfigured mesh, not a preference — degrading
            # silently would run without the parallelism the caller asked
            # for (ADVICE r2). 'auto' remains the adaptive spelling.
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} requires a mesh with an 'sp' "
                f"axis of size > 1 (got sp={sp_size}); use attn_impl='auto' "
                "to fall back to flash/dense when sp is absent"
            )
        use_ring = cfg.attn_impl == "ring" or (
            cfg.attn_impl == "auto" and sp_size > 1
        )
        self._use_ring = use_ring and mesh is not None
        self._use_ulysses = cfg.attn_impl == "ulysses"
        self._use_flash = not (self._use_ring or self._use_ulysses) and (
            cfg.attn_impl == "flash"
            or (cfg.attn_impl == "auto" and jax.default_backend() == "tpu")
        )
        # Flash on a multi-device auto-sharded mesh must go through
        # shard_map (a Pallas call is opaque to GSPMD — it cannot split
        # the kernel the way it splits einsums): batch over (data, fsdp),
        # heads over tp, zero collectives. Head counts must divide tp for
        # even shards; otherwise the dense path serves (GSPMD partitions
        # plain einsums fine). Batch divisibility is checked per call.
        self._flash_shard_mesh = None
        if (
            self._use_flash
            and mesh is not None
            # NOT under pipeline parallelism: pp>1 runs the layers inside
            # gpipe's manual-over-pp shard_map region, where a nested
            # shard_map over the full mesh trips a context-mesh mismatch
            # — there the kernel stays plain, as before this gate.
            and mesh.shape.get("pp", 1) == 1
            and any(
                mesh.shape.get(a, 1) > 1 for a in ("data", "fsdp", "tp")
            )
        ):
            tp_sz = mesh.shape.get("tp", 1)
            if cfg.n_heads % tp_sz or cfg.n_kv_heads % tp_sz:
                self._use_flash = False
            else:
                self._flash_shard_mesh = mesh
        # A remat layer under a ``tp`` axis keeps the residual stream after
        # its attention block, reduction done (module docstring, "Remat.");
        # with no ``tp`` axis there is no reduction to save: nothing is named.
        self._keeps_attn_residual = (
            cfg.remat and mesh is not None and mesh.shape.get("tp", 1) > 1
        )

    def init(self, rng: jax.Array) -> dict:
        return init_params(rng, self.cfg)

    @tracing.scope(tracing.SCOPE_ATTN_FLASH)
    def _attention(self, q, k, v, window=None, scale=None):
        if window is not None or scale is not None:
            # A sliding-window layer, or scores under a stated multiplier
            # (never under a mesh or a sequence-parallel impl: the config
            # and ``__init__`` refused). Forward only: the kernel's
            # backward takes no window and has its own scale.
            from torchkafka_tpu.ops.flash import _repeat_kv, flash_forward

            if self._use_flash:
                out = flash_forward(
                    q, k, v, scale=scale or 1.0 / math.sqrt(q.shape[-1]),
                    window=window,
                )
                if out is not None:
                    return out  # else S does not tile: the dense form
            k, v = _repeat_kv(q, k, v)
            return mha(q, k, v, causal=True, window=window, scale=scale)
        if self._use_ulysses:
            return ulysses_attention(
                q, k, v, mesh=self.mesh, axis_name="sp", causal=True,
                use_flash=self.cfg.ring_use_flash,
            )
        if self._use_ring:
            return ring_attention(
                q, k, v, mesh=self.mesh, axis_name="sp", causal=True,
                use_flash=self.cfg.ring_use_flash,
            )
        if self._use_flash:
            from torchkafka_tpu.ops.flash import (
                flash_attention,
                flash_attention_sharded,
            )

            if self._flash_shard_mesh is not None:
                m = self._flash_shard_mesh
                n_b = m.shape.get("data", 1) * m.shape.get("fsdp", 1)
                if q.shape[0] % n_b == 0:
                    return flash_attention_sharded(q, k, v, m, causal=True)
                # Batch does not split evenly (e.g. a small serving slot
                # pool on a wide mesh): dense body, repeating GQA kv here
                # because the flash path skipped _layer's repeat.
                from torchkafka_tpu.ops.flash import _repeat_kv

                k, v = _repeat_kv(q, k, v)
                return mha(q, k, v, causal=True)
            return flash_attention(q, k, v, True)
        return mha(q, k, v, causal=True)

    def _moe_mlp(
        self, h: jax.Array, layer: Mapping[str, jax.Array]
    ) -> tuple[jax.Array, jax.Array]:
        if self.cfg.moe_dispatch == "capacity":
            return _moe_mlp_capacity(h, layer, self.cfg)
        return _moe_mlp(h, layer, self.cfg)

    @staticmethod
    def _seq_positions(local_len: int) -> jax.Array:
        """Global RoPE positions. Inside a manual region over 'sp' (a
        pipeline stage) the layer sees only its sequence shard, so offset by
        the shard index; in the auto-sharded path jit sees the global view."""
        if axis_is_manual("sp"):
            return lax.axis_index("sp") * local_len + jnp.arange(local_len)
        return jnp.arange(local_len)

    def _layer(
        self, x: jax.Array, layer: Mapping[str, jax.Array], kind=None
    ) -> tuple[jax.Array, jax.Array]:
        """One decoder layer. Returns (activation, router stats [2, E] for
        MoE configs / [2, 1] zeros otherwise — see ``router_aux``).
        ``kind``: ``cfg.layer_kind(j)`` where the config has kinds of
        layer, ``(window or None, rope)``."""
        x, stats, _cached = self._layer_capture(x, layer, kind)
        return x, stats

    def _layer_capture(
        self, x: jax.Array, layer: Mapping[str, jax.Array], kind=None
    ):
        """``_layer`` with what serving keeps of it: (activation, router
        stats, capture). For a latent-attention config the capture is
        ``(latent [B, S, rank + rope], routing [B, S, top_k] | None)``,
        the layer's cache rows (``[2, B, S, rank + rope]``, a row a block,
        for the double layer) and its expert choices; otherwise ``(None,
        routing | None)`` (``generate.prefill`` computes k and v beside
        the layer)."""
        cfg = self.cfg
        positions = self._seq_positions(x.shape[1])
        if cfg.attn_blocks == 2:
            from torchkafka_tpu.models import mla

            latents = [None, None]

            def attend(i, h, blk):
                q_nope, q_rope, latents[i] = mla.project(h, blk, cfg, positions)
                return mla.attend_full(
                    q_nope, q_rope, latents[i], blk, cfg,
                    use_flash=self._use_flash,
                )

            x, routing = _double_layer(x, layer, cfg, attend)
            stats = jnp.zeros((2, 1), jnp.float32)
            return x, stats, (jnp.stack(latents), routing)
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            h = _rms_norm(x, layer["ln1"])
        latent = None
        if cfg.is_mla:
            from torchkafka_tpu.models import mla

            q_nope, q_rope, latent = mla.project(h, layer, cfg, positions)
            attn = mla.attend_full(
                q_nope, q_rope, latent, layer, cfg, use_flash=self._use_flash
            )
        else:
            attn = self._gqa(h, layer, positions, kind)
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            x = x + jnp.einsum(
                "bshe,hed->bsd", attn, load_weight(layer["wo"], cfg.dtype)
            )
            if self._keeps_attn_residual:
                x = checkpoint_name(x, REMAT_SAVED_TP)
            h = _rms_norm(x, layer["ln2"])
        stats, routing = jnp.zeros((2, 1), jnp.float32), None
        if "router" not in layer:  # a dense layer (all of a dense config's)
            x = x + _dense_mlp(h, layer, cfg)
        elif cfg.routed_moe:
            from torchkafka_tpu.ops.moe import routed_moe_mlp

            mlp_out, routing = routed_moe_mlp(h, layer, cfg)
            x = x + mlp_out
        else:
            mlp_out, stats = self._moe_mlp(h, layer)
            x = x + mlp_out
        return x, stats, (latent, routing)

    def _gqa_qkv(self, h, layer, positions, rope):
        """The grouped-query projections of normed h [B, S, D]: q [B, S,
        H, Dh], k and v [B, S, K, Dh], q and k rotated unless the config
        has no positions."""
        cfg = self.cfg
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            q = jnp.einsum("bsd,dhe->bshe", h, load_weight(layer["wq"], cfg.dtype))
            k = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wk"], cfg.dtype))
            v = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wv"], cfg.dtype))
            q, k = qk_head_norm(q, k, layer, cfg)
            if cfg.use_rope:
                q = _rope(q, positions, rope)
                k = _rope(k, positions, rope)
        return q, k, v

    def _gqa(self, h, layer, positions, kind=None):
        window, rope = kind or (None, self.cfg.rope_theta)
        q, k, v = self._gqa_qkv(h, layer, positions, rope)
        if self.cfg.is_sparse:
            # Learned sparse attention: causal and selected (ops/dsa.py).
            from torchkafka_tpu.ops.dsa import sparse_prefill_attention

            qi, ki, w = index_project(h, layer, self.cfg, positions, rope)
            with tracing.scope(tracing.SCOPE_ATTN_FLASH):
                return sparse_prefill_attention(
                    q, k, v, qi, ki, w, topk=self.cfg.index_topk,
                    scale=self.cfg.attn_scale, use_kernel=self._use_flash,
                )
        return self._gqa_attend(q, k, v, window)

    def _gqa_attend(self, q, k, v, window=None):
        cfg = self.cfg
        if cfg.attention_multiplier:
            return self._attention(q, k, v, window, cfg.attn_scale)
        if window is not None:
            return self._attention(q, k, v, window)
        if cfg.n_kv_heads != cfg.n_heads and not (
            self._use_flash or self._use_ulysses
        ):
            # GQA: dense/ring paths need explicit head repeat; the flash
            # kernels (and ulysses, which calls them per head-shard) serve
            # K < H through their kv index map instead of materialising
            # H/K× the kv bytes in HBM.
            rep = cfg.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return self._attention(q, k, v)

    def trunk(
        self, params: dict, tokens: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """tokens [B, S] int32 → (final-norm hidden states [B, S, D] in
        compute dtype, mean per-layer router aux loss). Everything except
        the lm_head projection — split out so ``loss`` can feed the fused
        blocked CE without ever materialising [B, S, V] logits."""
        cfg = self.cfg
        with tracing.scope(tracing.SCOPE_EMBED):
            x = embed_tokens(params, cfg, tokens)
        n_tokens = tokens.shape[0] * tokens.shape[1]

        if self.mesh is not None and self.mesh.shape.get("pp", 1) > 1:
            # (never a latent or sigmoid-routed config: __init__ refused)
            # GPipe over the stacked layers; embed/head/norm stay outside the
            # pipeline (replicated across pp). Router stats accumulate
            # through the schedule (valid-tick masked) and psum across
            # pp (and sp when manual) into full-batch sums — the aux here
            # equals the pp=1 value up to summation order.
            # With sp>1 the stage also binds 'sp' manually so ring attention
            # runs its collectives directly inside the stage body.
            from jax.sharding import PartitionSpec as _P

            from torchkafka_tpu.ops.pipeline import gpipe

            sp_size = self.mesh.shape.get("sp", 1)
            if sp_size > 1 and not (self._use_ring or self._use_ulysses):
                raise ValueError(
                    "a pp mesh with sp>1 requires sequence-parallel "
                    "attention (attn_impl='ring', 'ulysses', or 'auto')"
                )
            layer_fn = lambda a, layer: self._layer(a, layer)  # noqa: E731
            if cfg.remat:
                layer_fn = _remat_layer(layer_fn)
            x, stats = gpipe(
                layer_fn, params["layers"], x,
                mesh=self.mesh, axis="pp", microbatches=cfg.pp_microbatches,
                extra_manual={"sp"} if sp_size > 1 else set(),
                act_spec=_P(None, "sp", None) if sp_size > 1 else None,
                collect_stats=True,
            )
        else:
            def body(x, layer):
                x, stats = self._layer(x, layer)
                return x, stats

            if cfg.remat:
                body = _remat_layer(body)
            unroll = cfg.scan_unroll
            if unroll is None:
                # Auto-unroll only when no mesh axis shards the WEIGHTS.
                # The ~15% unroll win (PERF.md) was measured single-chip;
                # under tp/fsdp the unrolled backward's per-layer grad
                # intermediates make SPMD fall back to replicate-then-
                # repartition ("[SPMD] Involuntary full rematerialization"
                # — reproduced on a data2×fsdp2×tp2 mesh, gone at
                # unroll=1), which costs far more than the unroll saves.
                weight_sharded = self.mesh is not None and any(
                    self.mesh.shape.get(ax, 1) > 1
                    for ax in ("tp", "fsdp", "ep")
                )
                unroll = (
                    cfg.n_layers if cfg.n_layers <= 8 and not weight_sharded
                    else 1
                )
            # One stacked group, or the leading dense layers and then the
            # expert layers (``first_dense_layers``).
            stats = []
            if cfg.linear_pattern:
                # Linear and latent layers (forward only, no remat:
                # ``make_train_step`` refuses them).
                from torchkafka_tpu.models.linear_attn import hybrid_forward

                x, _kept, _routing = hybrid_forward(params, self, x)
                with tracing.scope(tracing.SCOPE_HEAD):
                    return (
                        _rms_norm(x, params["ln_f"], cfg.norm_eps),
                        jnp.float32(0.0),
                    )
            for key, nl, _expert_mlp in _layer_groups(cfg):
                if cfg.window_pattern:
                    # Kinds of layer: a scan over periods (forward only,
                    # so no remat: ``make_train_step`` refuses them).
                    x, st = scan_periods(
                        cfg, params[key], x,
                        lambda x, layer, j, _i: self._layer(
                            x, layer, cfg.layer_kind(j)
                        ),
                    )
                    stats.append(jnp.stack(st, axis=1).reshape(
                        -1, *st[0].shape[1:]
                    ))
                    continue
                xs, step = params[key], body
                if cfg.attn_blocks == 2:
                    xs, layer_of = _double_scan(params[key])
                    step = lambda x, s, f=layer_of: body(x, f(s))  # noqa: E731
                x, st = lax.scan(step, x, xs, unroll=min(unroll, nl))
                stats.append(st)
            stats = stats[0] if len(stats) == 1 else jnp.concatenate(stats)
        # stats: [L, 2, E] token-summed routing statistics; per-layer aux,
        # averaged over layers (identical math in both branches).
        aux = jnp.mean(jax.vmap(lambda s: router_aux(s, n_tokens))(stats))
        with tracing.scope(tracing.SCOPE_HEAD):
            return _rms_norm(x, params["ln_f"]), aux

    def __call__(
        self, params: dict, tokens: jax.Array, *, return_aux: bool = False
    ):
        """tokens [B, S] int32 → logits [B, S, V] float32 (and, with
        ``return_aux``, the mean per-layer router load-balance loss)."""
        x, aux = self.trunk(params, tokens)
        with tracing.scope(tracing.SCOPE_HEAD):
            logits = head_product(params, self.cfg, x)
        if return_aux:
            return logits, aux
        return logits

    def _use_fused_ce(self, params: dict) -> bool:
        """Fused blocked CE engages unless disabled, sequence-sharded (the
        block scan would serialise over sp), or the head is quantized."""
        if self.cfg.ce_block_size == 0:
            return False
        if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
            return False
        return not isinstance(params["lm_head"], QTensor)

    def loss(
        self, params: dict, tokens: jax.Array, mask: jax.Array | None = None
    ) -> jax.Array:
        """Next-token cross-entropy. mask [B, S] 1=real row/token, 0=padding
        (the ingest batcher's valid_mask — padded rows must not train).

        The forward runs at full length S (so the sequence stays divisible
        by the sp axis) and the shift happens on the loss side: position i
        predicts token i+1, the final position is masked out. The default
        path is the fused blocked CE (ops/xent.py) — full [B, S, V] logits
        are never materialised; sp>1 / quantized heads take the dense path.
        """
        cfg = self.cfg
        x, aux = self.trunk(params, tokens)
        aux = aux if (cfg.is_moe and cfg.router_aux_coef > 0) else 0.0
        # Shift once for both CE paths: position i predicts token i+1; the
        # final position (and padded rows) carry mask 0. Keeping full length
        # S also keeps the batch divisible over an sp axis.
        with tracing.scope(tracing.SCOPE_LOSS):
            targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
            m = jnp.ones(tokens.shape, jnp.float32) if mask is None else mask
            m = jnp.pad(m[:, 1:].astype(jnp.float32), ((0, 0), (0, 1)))
            if self._use_fused_ce(params):
                ce = fused_softmax_xent(
                    x, params["lm_head"], targets, m,
                    cfg.ce_block_size, cfg.dtype,
                )
            else:
                # Dense fallback shares the oracle implementation
                # (ops/xent.py): one CE definition, two materialisation
                # strategies.
                ce = dense_softmax_xent(
                    x, load_weight(params["lm_head"], cfg.dtype), targets, m,
                    cfg.dtype,
                )
            return ce + cfg.router_aux_coef * aux


# ----------------------------------------------------------------- train step


def batch_spec(mesh: Mesh) -> P:
    """Tokens [B, S]: batch over data(+fsdp), sequence over sp."""
    daxes = tuple(a for a in ("data", "fsdp") if a in mesh.shape)
    return P(daxes if daxes else None, "sp" if "sp" in mesh.shape else None)


def opt_shardings_like(opt_state, params, p_shardings, repl):
    """Sharding tree for an optax state: a leaf that MIRRORS a param
    (its tree path ends with the param's full path and the shapes match
    — adam's mu/nu, sgd's trace, any chain wrapping them) takes that
    param's sharding; everything else (step counts, scalars) replicates.

    Exists for jax 0.4.x, where a with_sharding_constraint on params
    inside a jitted init commits the PARAMS' output layout but
    ``optimizer.init``'s mirrors still come back replicated — which then
    breaks the train step's donation aliasing (input sharding !=
    out_shardings, an XLA INTERNAL error). On newer jax the constraint
    propagates and committing to the same layout is a no-op."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    p_leaves, _ = tree_flatten_with_path(params)
    s_leaves, _ = tree_flatten_with_path(p_shardings)
    by_path = {
        tuple(str(k) for k in path): (leaf.shape, sh)
        for (path, leaf), (_, sh) in zip(p_leaves, s_leaves)
    }

    def pick(path, leaf):
        key = tuple(str(k) for k in path)
        for i in range(len(key)):
            hit = by_path.get(key[i:])
            if hit is not None and hit[0] == getattr(leaf, "shape", None):
                return hit[1]
        return repl

    o_leaves, treedef = tree_flatten_with_path(opt_state)
    return tree_unflatten(treedef, [pick(p, l) for p, l in o_leaves])


def make_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    optimizer: Any,
) -> tuple[Callable[[jax.Array], tuple], Callable[..., tuple]]:
    """Build (init_fn, step_fn) jitted over the mesh.

    init_fn(rng) → (params, opt_state) laid out per ``param_specs``.
    step_fn(params, opt_state, tokens, mask) → (params, opt_state, loss);
    donates params/opt_state, so the caller rebinds them every step.
    """
    why = _arch_refusal(cfg, "make_train_step")
    if why:
        # No test holds their loss and gradients to a reference yet, and
        # the routed expert layer carries no load-balance term.
        raise ValueError(why)
    model = Transformer(cfg, mesh)
    p_shardings = shardings_for_mesh(mesh, param_specs(cfg))
    tok_sharding = NamedSharding(mesh, batch_spec(mesh))
    mask_sharding = tok_sharding
    repl = NamedSharding(mesh, P())

    @jax.jit
    def _init(rng):
        params = init_params(rng, cfg)
        params = jax.lax.with_sharding_constraint(params, p_shardings)
        opt_state = optimizer.init(params)
        return params, opt_state

    # Pin the optimizer state's layout EXPLICITLY on both sides of the
    # donated step (see opt_shardings_like): jax 0.4.x neither propagates
    # the param constraint into optimizer.init's output nor infers the
    # step's opt output layout consistently with its input — either
    # mismatch is an XLA INTERNAL donation-aliasing error. eval_shape
    # gives the opt tree without materialising it.
    p_shapes, o_shapes = jax.eval_shape(_init, jax.random.key(0))
    o_shardings = opt_shardings_like(o_shapes, p_shapes, p_shardings, repl)

    def init_fn(rng: jax.Array):
        params, opt_state = _init(rng)
        opt_state = jax.device_put(opt_state, o_shardings)
        return params, opt_state

    def _step(params, opt_state, tokens, mask):
        # Constrain inside the jit (rather than via in_shardings) so callers
        # may pass batches committed to any layout — e.g. the ingest path's
        # data-axis-only sharding — and XLA inserts the reshard to add sp.
        tokens = jax.lax.with_sharding_constraint(tokens, tok_sharding)
        mask = jax.lax.with_sharding_constraint(mask, mask_sharding)
        loss, grads = jax.value_and_grad(model.loss)(params, tokens, mask)
        # Pin grads to the param layout at the AD boundary. Without this,
        # SPMD is free to pick a layout for the backward's grad-accumulation
        # intermediates from the (batch-sharded) contraction operands, then
        # discovers at the optimizer that the param layout differs and falls
        # back to replicate-then-repartition ("[SPMD] Involuntary full
        # rematerialization" on fsdp×tp meshes) — wasted HBM and ICI every
        # step. Constraining here lets the wanted layout propagate back
        # into the transpose instead.
        grads = jax.lax.with_sharding_constraint(grads, p_shardings)
        with tracing.scope(tracing.SCOPE_OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        params = jax.lax.with_sharding_constraint(params, p_shardings)
        return params, opt_state, loss

    step_fn = jax.jit(
        _step,
        donate_argnums=(0, 1),
        out_shardings=(p_shardings, o_shardings, repl),
    )
    return init_fn, step_fn


def count_params(params: dict) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(params)))
