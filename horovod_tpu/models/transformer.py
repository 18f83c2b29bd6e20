"""GPT-style transformer family, TPU-first.

The long-context flagship of the model zoo (the reference's zoo is conv
nets via tf_cnn_benchmarks; transformers are where TPU-native design —
MXU-shaped matmuls, bf16 compute, flash/ring attention — pays off most).

TPU-first choices:
* bf16 compute / fp32 params and layer norms (MXU-native mixed precision).
* Attention impl is pluggable per config:
    - ``"flash"``     — the Pallas kernel (ops/flash_attention.py);
    - ``"reference"`` — plain softmax attention (parallel/ring_attention.py
      ``local_attention``), for tests and tiny shapes;
    - ``"ring"``      — ring attention over a sequence-parallel mesh axis
      (call the model inside shard_map with tokens sharded along seq);
    - ``"zigzag"``    — the load-balanced causal ring (zigzag layout;
      requires an explicit ``positions`` vector from
      ``zigzag_positions``);
    - ``"ulysses"``   — all-to-all head-parallel attention over that axis.
* Positions: ``pos_offset`` (scalar, contiguous shards) or an explicit
  per-token ``positions`` vector (required for zigzag); both the learned
  table (gather) and RoPE rotate/index by position VALUE, so the
  embeddings are layout-agnostic.
* GQA/MQA via ``num_kv_heads``: native in the flash kernel; ring/zigzag
  carry narrow k/v through the ppermute and broadcast after.
* Head dim and MLP width default to multiples of 128 (MXU lane width) at
  the named sizes.
* No data-dependent Python control flow — the whole forward is one traced
  graph.
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass, fields, replace
from typing import Any, Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import scopes
from ..ops.rope import yarn_mscale
from ..parallel.moe import ACTIVATIONS, SCORE_RULES
from ..parallel.moe import DEFAULT_GROUP_SIZE as MOE_DEFAULT_GROUP_SIZE


# What ``layer_types`` may name: one mixer per layer.
# ``sliding_attention`` and ``full_attention`` are attention layers told
# apart by their mask (and, where ``rope_layer_types`` says so, by their
# positions); ``cross_attention`` makes queries only and reads the keys
# and values of the layer ``shared_kv_layer`` names.  ``mamba`` is the
# Mamba-2 mixer, ``selective_scan`` the Mamba-1 mixer, ``gmu`` a
# gated memory unit: a gate on the scan output of the layer
# ``memory_layer`` names, ``conv`` a gated short convolution: a
# causal depthwise filter of ``conv_taps`` taps between two gates, and
# ``kda`` a gated delta rule with a decay per channel of the key
# (ops/kda.py), ``gdn`` the gated delta rule whose decay is one number a
# head, with fewer key heads than value heads (Gated DeltaNet; the same
# kernels).  ``feed_forward`` is no mixer: a model that names it is
# made of layers of ONE half each (``TransformerConfig.one_half``), one
# norm and a mixer in a layer of a mixer type, one norm and the
# feed-forward (dense or routed: ``ffn_type``) in a ``feed_forward`` one.
ATTENTION_LAYER_TYPES = ("attention", "mla", "sliding_attention",
                         "full_attention", "cross_attention")
FEED_FORWARD = "feed_forward"
LAYER_TYPES = ATTENTION_LAYER_TYPES + ("mamba", "selective_scan", "gmu",
                                       "conv", "kda", "gdn", FEED_FORWARD)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    # GQA/MQA: fewer K/V heads than query heads (None = MHA).  The flash
    # kernel routes q heads to kv groups natively (no broadcast); other
    # attention impls repeat k/v to full heads before attending.
    num_kv_heads: Optional[int] = None
    emb_dim: int = 768
    mlp_ratio: int = 4
    max_len: int = 1024
    dtype: Any = jnp.bfloat16
    attention_impl: str = "flash"  # flash | reference | ring | ulysses | zigzag
    sp_axis: Optional[str] = None  # mesh axis for ring/ulysses/zigzag
    # Mistral-style sliding window (each position sees its last W keys,
    # self included).  Flash-kernel-only: the banded tiles are skipped in
    # fwd AND bwd, so attention compute scales with S*W instead of S^2.
    attention_window: Optional[int] = None
    # "learned" = wpe table (GPT-2 style); "rope" = rotary, driven by the
    # explicit per-token position vector, so it composes with ANY sequence
    # layout (contiguous or zigzag shards).
    pos_embedding: str = "learned"
    rope_theta: float = 10000.0
    # Measured on TPU v5e (docs/performance.md round-5 sweep): q512 x k256
    # tiles lift gpt-small from MFU 0.193 (128 x 128) to 0.325 — the
    # dominant single-chip lever.  _pick_block shrinks them to divide
    # short sequences, so the large default is shape-safe.
    flash_block_q: int = 512
    flash_block_k: int = 256
    # Rematerialize each block in the backward pass: trades HBM for
    # recomputed FLOPs, buying larger per-chip batches — the MFU lever
    # when activations bound the batch.  A block keeps what remat_policy
    # says and, always, what its Pallas kernels' forward made (flash
    # attention's o and lse, the scan's y and chunk states:
    # block_remat_policy): a kernel's cost is quadratic in the sequence
    # (the scan's, a chunk's square a chunk) and its output one
    # activation of the stream, so it is never run a second time.  While
    # the chip the step is traced on has room (remat_room: its limit and
    # what is resident, from the device; the working set, from the
    # shapes) a block keeps its matmuls' outputs too, in trace order,
    # and the recompute is left the norms, gates and elementwise chains;
    # a backend that reports no memory (the CPU) keeps none.
    remat: bool = False
    # Mixture-of-experts MLP (parallel/moe.py): >0 replaces every block's
    # dense MLP with moe_experts experts (GShard one-hot dispatch, static
    # capacity).  The auxiliary load-balancing loss is sowed into the
    # "losses" collection: apply with mutable=["losses"] and add
    # sum(losses) * your coefficient to the training loss.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    # routing group (keeps dispatch O(n*group)); default tracks the one
    # source of truth in parallel/moe.py
    moe_group_size: int = MOE_DEFAULT_GROUP_SIZE
    # Activation storage dtype (e.g. jnp.float8_e4m3fn) for the big saved
    # activations backward re-reads: the residual-branch deltas (attention
    # and MLP outputs), the pre-proj attention context, and the gelu
    # intermediate (the 4x-wide one) materialize at 1 B/elt; matmuls widen
    # in-register to the compute dtype.  Lossy — changes the numerics
    # contract (tests/test_fp8.py pins how far it may drift) — so opt-in,
    # mirroring models/resnet.py act_store_dtype.
    act_store_dtype: Optional[Any] = None
    # ---- what a block is made of.  Every default is GPT-2's, so the
    # named GPT sizes build the tree and the mathematics they always did.
    # One mixer per layer: "attention" or "mamba" (a Mamba-2 state-space
    # mixer, ops/ssd.py).  None = attention in every layer.
    layer_types: Optional[tuple] = None
    norm: str = "layernorm"            # layernorm | rmsnorm
    norm_eps: float = 1e-6
    use_bias: bool = True              # biases of the dense projections
    # gelu | silu_gated: W_out(silu(g) * u) | relu2: W_out(relu(W_in x)^2),
    # no gate matrix
    mlp: str = "gelu"
    # softmax(attention_scale * q k^T); None = head_dim ** -0.5
    attention_scale: Optional[float] = None
    embedding_multiplier: float = 1.0  # x = multiplier * wte[tokens]
    residual_multiplier: float = 1.0   # x = x + multiplier * branch(x)
    logits_scaling: float = 1.0        # logits = head(x) / scaling
    tie_embeddings: bool = False       # the head is wte, transposed
    # Which of a rematerialized block's values are kept for the backward
    # pass whatever the chip's memory, beside its kernels' outputs (a
    # name in jax.checkpoint_policies; the default keeps matmul outputs
    # with no batch dims, the standard TPU transformer policy;
    # "nothing_saveable" leaves them to the room the chip has:
    # block_remat_policy).
    remat_policy: str = "dots_with_no_batch_dims_saveable"
    # The Mamba-2 mixer: ssm_heads heads of ssm_head_dim (their product is
    # the inner width), a state of ssm_state per head channel, B and C
    # shared by the heads of a group, a causal depthwise conv of ssm_conv
    # taps, the scan in chunks of ssm_chunk tokens.
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # Latent attention (layer type "mla"): queries through a rank
    # q_lora_rank (0: one matrix, no rank and no norm), keys and values
    # through one latent of kv_lora_rank,
    # each with a norm of its own (the configuration's); a head's query and key are
    # qk_nope_head_dim latent-made channels beside qk_rope_head_dim
    # rotary ones, the rotary key one vector shared by all heads; values
    # are v_head_dim wide.  The rotary channels turn where the layer
    # type rotates (``rotates("mla")``); where it does not they are plain
    # channels, the shared key's among them.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Routed experts that drop nothing (parallel/moe.py, the dropless
    # core; not the GShard path of moe_experts above): >0 puts, in every
    # layer after the first dense_layers_first, routed_experts gated
    # experts of width routed_width behind a sigmoid router that picks
    # routed_top_k a token and scales their normalised weights by
    # routed_scaling, beside shared_experts experts every token takes.
    # This chip holds routed_held of them (None = all) from
    # routed_first_held on; the router scores all.  The selection bias
    # (collection "moe_state") and the last step's rows per held expert
    # and load of every routed expert, with the count of the steps in
    # which the layer passed its row bound (collection "moe_stats"), are
    # state, not parameters; the training step moves the bias against the
    # load (parallel/moe.py:rebalanced).
    routed_experts: int = 0
    routed_held: Optional[int] = None
    routed_first_held: int = 0
    routed_top_k: int = 0
    routed_width: int = 0
    routed_scaling: float = 1.0
    shared_experts: int = 0
    dense_layers_first: int = 0
    # What the router reads: "ffn_input", the normed stream the experts
    # read (after the attention half), or "layer_input", the residual
    # stream as the layer receives it, before ln1: the decision is then
    # made ahead of the attention half and applied to the normed stream
    # after it (parallel/moe.py: routing_decision, apply_routing).
    routed_router_input: str = "ffn_input"
    # How the router's outputs become a choice and weights
    # (parallel/moe.py:SCORE_RULES): "sigmoid" scores with the selection
    # bias and routed_scaling, or "softmax_chosen", the routed_top_k
    # largest raw logits and a softmax over those alone; that rule has no
    # bias, so the layer keeps no "moe_state" and nothing but a loss
    # holds the load even.
    routed_scores: str = "sigmoid"
    # The gate's activation in a routed expert: act(x W_gate) * (x W_up)
    # (parallel/moe.py:ACTIVATIONS).  routed_gated=False: an expert has
    # no gate matrix and no product, W_down(act(x W_up)), and
    # ``experts_fc1`` is [held, emb, width]; the shared expert and the
    # dense layers (``mlp``) are then ungated too.
    routed_activation: str = "silu"
    routed_gated: bool = True
    # The shared expert's width (None = shared_experts * routed_width).
    shared_width: Optional[int] = None
    # The shared expert behind a learned gate of its own: sigmoid(x w_s)
    # * shared(x), w_s [emb, 1] (``shared_gate``), a number a token.
    shared_expert_gate: bool = False
    # > 0: every expert layer sows its load-balance loss (E * sum_e f_e
    # P_e over the layer's own tokens, 1.0 at an even load; scope
    # "moe_balance") into the "losses" collection as "moe_balance" and
    # keeps it in "moe_stats"; the training step adds
    # routed_balance_coef * their sum to its loss.
    routed_balance_coef: float = 0.0
    # Multi-token-prediction modules after the last block (0 or 1): the
    # model then also returns logits for the token after next wherever
    # it is handed next_tokens.
    mtp_modules: int = 0
    # ---- the attention layer's own settings; every default is GPT-2's.
    # One head's channels (None = emb_dim // num_heads): q is num_heads *
    # head_size wide, k and v kv_heads * head_size, whatever emb_dim is.
    head_size: Optional[int] = None
    # Layer types "sliding_attention" and "full_attention" are attention
    # layers that differ in their mask alone: the first sees its last
    # attention_window keys, the second every earlier key whatever
    # attention_window says.  (Types "attention" and "mla", and a model
    # with layer_types=None, take attention_window as it stands.)
    # Which layer types rotate q and k under pos_embedding="rope"
    # (None = every attention layer); the others see no positions.
    rope_layer_types: Optional[tuple] = None
    # The configuration's norm over each head's channels of q and of k
    # (one scale of head_dim each, shared by the heads), before RoPE.
    qk_norm: bool = False
    # att * sigmoid(gate(h)) before proj: a gate as wide as q, from the
    # normed stream the queries are made of.  True: its values come from
    # a projection of its own (``gate``); "query": from the query
    # projection itself, made twice as wide (``qkv`` is then [gate ; q ;
    # k ; v]: the same parameters as a q_proj whose every head is
    # [query ; gate]).
    attention_gate: Union[bool, str] = False
    # The share of a head's channels RoPE turns: the first head_dim *
    # partial_rotary_factor of them, the others see no positions.
    partial_rotary_factor: float = 1.0
    # RMS norms scale by 1 + w with w from zeros, not by w from ones:
    # the blocks' norms, the final norm and the head norms of q and k
    # (``norm`` must be "rmsnorm").  The same function at the start;
    # weight decay then pulls the scale towards 1 and not towards 0.
    norm_unit_offset: bool = False
    # A norm on each branch's OUTPUT before the residual add, beside the
    # two on its input: four norms a block.
    post_norms: bool = False
    # The feed-forward's biases where they differ from the attention
    # projections' (None = use_bias).
    mlp_bias: Optional[bool] = None
    # Differential attention (arXiv:2410.05258) in every attention layer:
    # query and key heads pair up (even, odd), a pair's two softmax maps
    # read the pair's values (twice a head wide) and the second is
    # subtracted lambda times; an RMSNorm over the pair's 2 * head_dim
    # channels, then (1 - lambda_init).  lambda = exp(lq1 . lk1) -
    # exp(lq2 . lk2) + lambda_init from four learned vectors a layer,
    # lambda_init = 0.8 - 0.6 exp(-0.3 i) with i the layer's index in
    # the whole model: first_layer_index + its place here, so that a
    # slice of a model keeps its layers' own.
    differential_attention: bool = False
    first_layer_index: int = 0
    # Values that cross layers beside the residual stream.  Every
    # "cross_attention" layer reads the keys and values that layer
    # shared_kv_layer (an attention layer before them) made for itself;
    # every "gmu" layer reads the scan output, before its gate, of layer
    # memory_layer (a "selective_scan" layer before them).  The block
    # that makes a value returns it beside x and the readers take it as
    # an argument (GPT.__call__), through jax.checkpoint where the
    # blocks are rematerialised; its gradient is the sum over readers.
    shared_kv_layer: Optional[int] = None
    memory_layer: Optional[int] = None
    # The Mamba-1 mixer (layer type "selective_scan", ops/
    # selective_scan.py): ssm_width inner channels (the gated memory
    # units' width too), each with a state of ssm_state, a causal
    # depthwise conv of ssm_conv taps, dt through a rank of ssm_dt_rank.
    ssm_width: int = 0
    ssm_dt_rank: int = 0
    # The dense feed-forward's width where it is no whole multiple of
    # emb_dim (None = mlp_ratio * emb_dim).
    mlp_width: Optional[int] = None
    # The gated short convolution (layer type "conv"): the taps of its
    # causal depthwise filter, the current token's included.
    conv_taps: int = 3
    # Kimi Delta Attention (layer type "kda", ops/kda.py): kda_heads
    # heads whose keys and values are kda_head_dim wide (the two low-rank
    # gates' rank too), a causal depthwise filter of kda_conv taps on q,
    # k and v, the rule in chunks of kda_chunk tokens (a power of two
    # that divides the sequence: the rule refuses any other length), a
    # state kept for the backward every kda_states_every chunks.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64
    kda_states_every: int = 4
    # Gated DeltaNet (layer type "gdn", arXiv:2412.06464; ops/kda.py:
    # gated_delta_rule): gdn_value_heads heads of values gdn_value_head_dim
    # wide over gdn_key_heads heads of queries and keys gdn_key_head_dim
    # wide (a key head serves value_heads // key_heads value heads in
    # order), ONE causal depthwise filter of gdn_conv taps over q, k and
    # v together, a log-decay and a beta a value head.  One rule and one
    # kernel pair with "kda": its chunks are kda_chunk tokens and a state
    # is kept every kda_states_every of them.
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_head_dim: int = 128
    gdn_value_head_dim: int = 128
    gdn_conv: int = 4
    # The attention mask.  None: causal (with the layer type's window).
    # B, a power of two: the block-diffusion training mask
    # (arXiv:2503.09573; models/block_diffusion.py makes the step's
    # inputs).  The sequence a call sees is then a noised copy of L
    # tokens followed by the clean one, both in blocks of B: a noised row
    # sees its own block's noised rows and the clean rows of strictly
    # earlier blocks, a clean row the clean rows of its own and earlier
    # blocks.  L is half the sequence; positions repeat (0..L-1 twice),
    # and the head runs over the noised half alone.
    block_diffusion: Optional[int] = None
    # Look the tokens up in the float32 table and cast after, so that a
    # row's gradient adds up in float32: for a step that looks ONE row up
    # thousands of times (a mask token's), whose sum in the compute dtype
    # loses a quarter of its norm.  The same values forward.
    embed_grad_float32: bool = False
    # What RoPE's frequencies are scaled by: the source's ``rope_scaling``
    # record (ops/rope.py:scaled_frequencies; type "yarn"), kept as its
    # sorted items.  YaRN's factor on the softmax is ``attention_scale``.
    rope_scaling: Optional[tuple] = None
    # Manifold-constrained hyper-connections (models/hyper_connections.py,
    # arXiv:2512.24880): the residual stream is hc_mult copies, stored
    # [batch, seq, hc_mult * emb_dim]; each half of a block reads them
    # through learned weights and writes back through a doubly stochastic
    # hc_mult x hc_mult map that hc_sinkhorn_iters rounds of Sinkhorn-Knopp
    # (hc_eps in both denominators) make of the exponential of logits
    # clamped to hc_res_clamp.  1: one stream and a plain residual add.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)

    def __post_init__(self):
        if self.rope_scaling is not None:
            object.__setattr__(self, "rope_scaling", tuple(sorted(
                dict(self.rope_scaling).items())))
            if self.pos_embedding != "rope":
                raise ValueError(
                    f"rope_scaling scales RoPE's frequencies: "
                    f"pos_embedding must be 'rope', got "
                    f"{self.pos_embedding!r}")
        object.__setattr__(self, "hc_res_clamp", tuple(self.hc_res_clamp))
        if (self.hc_mult < 1 or self.hc_sinkhorn_iters < 1
                or self.hc_eps < 0 or len(self.hc_res_clamp) != 2
                or not self.hc_res_clamp[0] < self.hc_res_clamp[1]):
            raise ValueError(
                f"hyper-connections need hc_mult={self.hc_mult} >= 1 "
                f"streams, hc_sinkhorn_iters={self.hc_sinkhorn_iters} >= 1, "
                f"hc_eps={self.hc_eps} >= 0 and hc_res_clamp="
                f"{self.hc_res_clamp!r} a (low, high) pair")
        if self.hc_mult > 1 and self.routed_router_input == "layer_input":
            raise ValueError(
                f"hc_mult={self.hc_mult}: a router on the layer's input "
                f"would read hc_mult streams; routed_router_input must be "
                f"'ffn_input'")
        if self.num_kv_heads is not None:
            if self.num_kv_heads <= 0 or self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"num_heads={self.num_heads} must be a positive "
                    f"multiple of num_kv_heads={self.num_kv_heads}"
                )
        if self.pos_embedding not in ("learned", "rope", "none"):
            raise ValueError(
                f"pos_embedding must be 'learned', 'rope' or 'none', got "
                f"{self.pos_embedding!r}"
            )
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"norm must be 'layernorm' or 'rmsnorm', got {self.norm!r}")
        if self.mlp not in ("gelu", "silu_gated", "relu2"):
            raise ValueError(
                f"mlp must be 'gelu', 'silu_gated' or 'relu2', got "
                f"{self.mlp!r}")
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            unknown = set(self.layer_types) - set(LAYER_TYPES)
            if unknown or len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types must name one of {LAYER_TYPES} "
                    f"for each of num_layers={self.num_layers} layers, got "
                    f"{self.layer_types!r}")
            if "sliding_attention" in self.layer_types and (
                    self.attention_window is None):
                raise ValueError(
                    "a 'sliding_attention' layer sees its last "
                    "attention_window keys: attention_window must be set")
            if "mamba" in self.layer_types and (
                    self.ssm_heads <= 0
                    or self.ssm_heads % self.ssm_groups):
                raise ValueError(
                    f"a 'mamba' layer needs ssm_heads={self.ssm_heads} to be "
                    f"a positive multiple of ssm_groups={self.ssm_groups}")
            if "selective_scan" in self.layer_types and min(
                    self.ssm_width, self.ssm_dt_rank, self.ssm_state) <= 0:
                raise ValueError(
                    f"a 'selective_scan' layer needs positive ssm_width="
                    f"{self.ssm_width}, ssm_dt_rank={self.ssm_dt_rank} and "
                    f"ssm_state={self.ssm_state}")
            self._check_handed_on(
                "cross_attention", "shared_kv_layer",
                ("attention", "sliding_attention", "full_attention"))
            self._check_handed_on("gmu", "memory_layer",
                                  ("selective_scan",))
            chunks_ok = (self.kda_states_every > 0 and self.kda_chunk > 0
                         and not self.kda_chunk & (self.kda_chunk - 1))
            if "kda" in self.layer_types and (
                    min(self.kda_heads, self.kda_head_dim,
                        self.kda_conv) <= 0 or not chunks_ok):
                raise ValueError(
                    f"a 'kda' layer needs positive kda_heads="
                    f"{self.kda_heads}, kda_head_dim={self.kda_head_dim}, "
                    f"kda_conv={self.kda_conv} and kda_states_every="
                    f"{self.kda_states_every}, and kda_chunk="
                    f"{self.kda_chunk} a power of two")
            if "gdn" in self.layer_types and (
                    min(self.gdn_key_heads, self.gdn_value_heads,
                        self.gdn_key_head_dim, self.gdn_value_head_dim,
                        self.gdn_conv) <= 0
                    or self.gdn_value_heads % self.gdn_key_heads
                    or not chunks_ok):
                raise ValueError(
                    f"a 'gdn' layer needs gdn_value_heads="
                    f"{self.gdn_value_heads} a positive multiple of "
                    f"gdn_key_heads={self.gdn_key_heads}, positive "
                    f"gdn_key_head_dim={self.gdn_key_head_dim}, "
                    f"gdn_value_head_dim={self.gdn_value_head_dim}, "
                    f"gdn_conv={self.gdn_conv} and kda_states_every="
                    f"{self.kda_states_every}, and kda_chunk="
                    f"{self.kda_chunk} a power of two")
            if "mla" in self.layer_types:
                sizes = ("kv_lora_rank", "qk_nope_head_dim",
                         "qk_rope_head_dim", "v_head_dim")
                if min(getattr(self, k) for k in sizes) <= 0 or (
                        self.q_lora_rank < 0):
                    raise ValueError(
                        f"an 'mla' layer needs positive {', '.join(sizes)} "
                        f"(q_lora_rank may be 0: one matrix)")
                if self.pos_embedding == "learned":
                    raise ValueError(
                        "an 'mla' layer rotates its rotary channels or "
                        "sees no positions: pos_embedding must be 'rope' "
                        "or 'none'")
        # (a layer hands on from its mixer, so the makers and readers of
        # a value stay mixer layers: _check_handed_on above; and
        # dense_layers_first counts layers of any type)
        if self.one_half and (self.mtp_modules or self.hc_mult > 1):
            raise ValueError(
                f"a model of one-half layers (layer_types names "
                f"{FEED_FORWARD!r}) has no prediction module and one "
                f"residual stream: a block of the last layer's kind "
                f"would be a feed-forward alone (mtp_modules="
                f"{self.mtp_modules}), and a half's hyper-connection "
                f"alone is not implemented (hc_mult={self.hc_mult})")
        if self.shared_width is not None and self.shared_width <= 0:
            raise ValueError(
                f"shared_width={self.shared_width} must be positive (None "
                f"= shared_experts * routed_width)")
        if self.routed_experts > 0:
            if self.moe_experts > 0:
                raise ValueError(
                    "routed_experts (dropless) and moe_experts (GShard "
                    "capacity) are two expert layers: set one")
            # the shared expert and the dense layers take the experts' form
            form, says = (("silu_gated", "silu-gated") if self.routed_gated
                          else ("relu2", "ungated"))
            if self.mlp != form:
                raise ValueError(
                    f"the routed experts are {says}: mlp must be {form!r}")
            held = self.held_experts
            if not (0 < self.routed_top_k <= self.routed_experts
                    and self.routed_width > 0 and held > 0
                    and 0 <= self.routed_first_held
                    and self.routed_first_held + held
                    <= self.routed_experts):
                raise ValueError(
                    f"routed_experts={self.routed_experts} needs "
                    f"0 < routed_top_k <= routed_experts, a routed_width "
                    f"and held experts {self.routed_first_held}.."
                    f"{self.routed_first_held + held - 1} among them")
        if self.norm_unit_offset and self.norm != "rmsnorm":
            raise ValueError(
                f"norm_unit_offset is the RMS norms' scale 1 + w: norm "
                f"must be 'rmsnorm', got {self.norm!r}")
        rotary = self.head_dim * self.partial_rotary_factor
        if self.partial_rotary_factor != 1.0 and (
                not 0 < self.partial_rotary_factor < 1
                or rotary != int(rotary) or int(rotary) % 2):
            raise ValueError(
                f"partial_rotary_factor={self.partial_rotary_factor} of a "
                f"head of {self.head_dim} must be an even number of "
                f"channels, at most the head")
        for setting, allowed in (
                ("attention_gate", (False, True, "query")),
                ("routed_router_input", ("ffn_input", "layer_input")),
                ("routed_scores", SCORE_RULES),
                ("routed_activation", tuple(ACTIVATIONS))):
            if getattr(self, setting) not in allowed:
                raise ValueError(
                    f"{setting} must be one of {allowed}, got "
                    f"{getattr(self, setting)!r}")
        if self.routed_balance_coef < 0:
            raise ValueError(
                f"routed_balance_coef={self.routed_balance_coef} is a "
                f"loss's weight: it must not be negative")
        if self.layer_types is None and (
                self.shared_kv_layer is not None
                or self.memory_layer is not None):
            raise ValueError(
                "shared_kv_layer and memory_layer name a layer of "
                "layer_types: layer_types must be set")
        if self.query_gate and (
                self.differential_attention
                or "cross_attention" in (self.layer_types or ())):
            raise ValueError(
                "attention_gate='query' widens the projection that "
                "makes q, k and v: it is implemented for the plain "
                "attention layers, not for differential or "
                "'cross_attention' ones")
        if self.differential_attention:
            if self.num_heads % 2 or self.kv_heads % 2:
                raise ValueError(
                    f"differential attention pairs its heads: num_heads="
                    f"{self.num_heads} and kv heads={self.kv_heads} must "
                    f"be even")
            if "mla" in (self.layer_types or ()):
                raise ValueError(
                    "differential attention is implemented for the "
                    "attention layers that split one qkv, not for 'mla'")
        if self.block_diffusion is not None:
            block = self.block_diffusion
            if block < 1 or block & (block - 1):
                raise ValueError(
                    f"block_diffusion={block} is a block length: a power "
                    f"of two")
            masked_otherwise = set(self.layer_types or ()) - {
                "attention", "full_attention"}
            if (masked_otherwise or self.attention_window is not None
                    or self.differential_attention or self.mtp_modules
                    or self.attention_impl not in ("flash", "reference")):
                raise ValueError(
                    f"block_diffusion={block} is the mask of every layer: "
                    f"it goes with plain attention layers on the flash or "
                    f"reference schedule, without a window, differential "
                    f"attention or a prediction module (layer_types="
                    f"{self.layer_types!r}, attention_window="
                    f"{self.attention_window!r}, attention_impl="
                    f"{self.attention_impl!r})")
        if self.mtp_modules not in (0, 1):
            raise ValueError(
                f"mtp_modules={self.mtp_modules}: one prediction module is "
                f"implemented, or none")
        if self.head_size is not None and self.head_size <= 0:
            raise ValueError(
                f"head_size={self.head_size} must be positive (None = "
                f"emb_dim // num_heads)")
        if self.mlp_width is not None and self.mlp_width <= 0:
            raise ValueError(
                f"mlp_width={self.mlp_width} must be positive (None = "
                f"mlp_ratio * emb_dim)")
        if self.conv_taps <= 0:
            raise ValueError(
                f"conv_taps={self.conv_taps}: a 'conv' layer's filter "
                f"reads at least the current token")
        if self.rope_layer_types is not None:
            object.__setattr__(self, "rope_layer_types",
                               tuple(self.rope_layer_types))
            unknown = set(self.rope_layer_types) - set(ATTENTION_LAYER_TYPES)
            if unknown or self.pos_embedding != "rope":
                raise ValueError(
                    f"rope_layer_types names the layer types among "
                    f"{ATTENTION_LAYER_TYPES} that rotate under "
                    f"pos_embedding='rope', got {self.rope_layer_types!r} "
                    f"with pos_embedding={self.pos_embedding!r}")

    def _check_handed_on(self, reader: str, setting: str, makers: tuple):
        """Layers of type ``reader`` need ``setting`` to name an earlier
        layer of one of the types ``makers``; without readers it stays
        unset."""
        at = getattr(self, setting)
        readers = [i for i, kind in enumerate(self.layer_types)
                   if kind == reader]
        if not readers and at is None:
            return
        if not readers or at is None or not (
                0 <= at < min(readers) and self.layer_types[at] in makers):
            raise ValueError(
                f"a {reader!r} layer reads what layer {setting} made: "
                f"{setting}={at!r} must name a layer of type "
                f"{' or '.join(makers)} before the first of them, and be "
                f"None where there is none (layer_types="
                f"{self.layer_types!r})")

    def hands_on(self, i: int) -> Optional[str]:
        """What layer ``i`` returns beside the stream for later layers:
        ``"kv"``, ``"memory"`` or nothing."""
        return ("kv" if i == self.shared_kv_layer
                else "memory" if i == self.memory_layer else None)

    @property
    def ffn_width(self) -> int:
        """The dense feed-forward's width."""
        return (self.mlp_width if self.mlp_width is not None
                else self.mlp_ratio * self.emb_dim)

    @property
    def ffn_bias(self) -> bool:
        return self.use_bias if self.mlp_bias is None else self.mlp_bias

    @property
    def head_dim(self) -> int:
        return (self.head_size if self.head_size is not None
                else self.emb_dim // self.num_heads)

    @property
    def kv_heads(self) -> int:
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)

    def layer_type(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else "attention"

    def window_of(self, layer_type: Optional[str]) -> Optional[int]:
        """The keys a layer of this type sees beside the causal mask:
        its last ``attention_window``, or ``None`` for all of them."""
        return (None if layer_type in ("full_attention", "cross_attention")
                else self.attention_window)

    def rotates(self, layer_type: str) -> bool:
        """Does a layer of this type turn q and k by position?"""
        return self.pos_embedding == "rope" and (
            self.rope_layer_types is None
            or layer_type in self.rope_layer_types)

    def ffn_type(self, i: int) -> str:
        """``"routed"`` where layer ``i``'s feed-forward is the dropless
        expert layer, else ``"dense"``; ``"none"`` for a mixer layer of a
        model of one-half layers."""
        if self.one_half and self.layer_types[i] != FEED_FORWARD:
            return "none"
        return ("routed" if self.routed_experts > 0
                and i >= self.dense_layers_first else "dense")

    @property
    def one_half(self) -> bool:
        """Is every layer one half (a mixer or a feed-forward, with one
        norm) and not a mixer and a feed-forward?"""
        return FEED_FORWARD in (self.layer_types or ())

    @property
    def shared_ffn_width(self) -> int:
        return (self.shared_width if self.shared_width is not None
                else self.shared_experts * self.routed_width)

    @property
    def held_experts(self) -> int:
        return (self.routed_held if self.routed_held is not None
                else self.routed_experts)

    @property
    def rope_dim(self) -> int:
        """The channels RoPE rotates: a head's ``partial_rotary_factor``
        (all of it by default), or an MLA head's rotary part."""
        return self.qk_rope_head_dim or int(
            self.head_dim * self.partial_rotary_factor)

    @property
    def query_gate(self) -> bool:
        """Does the attention layers' output gate come out of the query
        projection?"""
        return self.attention_gate == "query"

    @property
    def gdn_key_inner(self) -> int:
        return self.gdn_key_heads * self.gdn_key_head_dim

    @property
    def gdn_value_inner(self) -> int:
        return self.gdn_value_heads * self.gdn_value_head_dim

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim


# What the raw-weights paths honour of a configuration: the sizes, the
# positions, the attention schedule and its tiles, the activation store.
# ``remat`` and ``remat_policy`` are the flax model's and change no
# mathematics (the pipeline takes its own ``remat``); ``layer_types`` may
# say "attention" in every layer; the GShard expert layer (``moe_*``)
# each path refuses in its own words, from the weights it is handed.
RAW_BLOCK_SETTINGS = frozenset({
    "vocab_size", "num_layers", "num_heads", "num_kv_heads", "emb_dim",
    "mlp_ratio", "max_len", "dtype", "attention_impl", "sp_axis",
    "attention_window", "pos_embedding", "rope_theta", "flash_block_q",
    "flash_block_k", "act_store_dtype", "remat", "remat_policy",
    "layer_types", "moe_experts", "moe_top_k", "moe_capacity_factor",
    "moe_group_size",
})


def require_gpt2_block(cfg: TransformerConfig, who: str) -> None:
    """Refuse, before anything is traced, a configuration that ``who``
    cannot run: the decode and serving paths, the tensor-parallel and
    the pipeline schedules build on :func:`block_math` with GPT-2's five
    callables from raw weights (LayerNorm, biased dense projections, a
    gelu MLP, attention in every layer) and would run that wiring under
    another model's name.  Every setting outside ``RAW_BLOCK_SETTINGS``
    has to stand at its default: a field a later architecture adds is
    refused here until these paths implement it."""
    gpt2 = TransformerConfig()
    if cfg.layer_types and set(cfg.layer_types) != {"attention"}:
        raise ValueError(
            f"{who} runs attention layers only: layer_types="
            f"{cfg.layer_types!r} holds a layer it has no state for")
    refused = [f.name for f in fields(cfg)
               if f.name not in RAW_BLOCK_SETTINGS
               and getattr(cfg, f.name) != getattr(gpt2, f.name)]
    if refused:
        says = lambda c: ", ".join(
            f"{setting}={getattr(c, setting)!r}" for setting in refused)
        raise ValueError(
            f"{who} implements GPT-2's block only ({says(gpt2)}); this "
            f"configuration says {says(cfg)}")
    if cfg.pos_embedding == "none":
        raise ValueError(
            f"{who} implements learned and rotary positions; this "
            f"configuration says pos_embedding='none'")


def _attend(cfg: TransformerConfig, q, k, v, positions,
            layer_type: Optional[str] = None):
    """Dispatch to the configured attention schedule under the
    configuration's mask: causal, or with ``cfg.block_diffusion`` the
    block-diffusion one over a noised copy and the clean one (the rows
    decide it, not ``positions``, which repeat there).
    ``positions``: int [s_local] global positions of the local rows —
    used by schedules that mask in global coordinates.  ``layer_type``
    decides the window (``cfg.window_of``); a call that has one traces
    under the scope ``attn_window``, so a device trace tells the banded
    kernels from the full ones, the call of a layer that reads another
    layer's keys and values under ``attn_cross``, and a call under the
    block-diffusion mask under ``attn_block_diffusion``.  ``q``, ``k``
    and ``v`` are ``[b, s, heads, hd]``, or for the flash schedule alone
    head-major ``[b heads, s, hd]`` as ``ops/attn_prep.py`` writes
    them; the result is ``[b, s, heads, value dim]`` either way."""
    window = cfg.window_of(layer_type)
    with (jax.named_scope(scopes.ATTN_BLOCK_DIFFUSION)
          if cfg.block_diffusion is not None
          else jax.named_scope(scopes.ATTN_WINDOW) if window is not None
          else jax.named_scope(scopes.ATTN_CROSS)
          if layer_type == "cross_attention" else contextlib.nullcontext()):
        return _attend_schedule(cfg, q, k, v, positions, layer_type, window)


def differential_lambda_init(layer_index: int) -> float:
    """``0.8 - 0.6 exp(-0.3 i)``, ``i`` the layer's index in the whole
    model (arXiv:2410.05258, section 2.1)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


def _attend_differential(cfg: TransformerConfig, q, k, v, positions,
                         layer_type, *, lambdas, subln, lambda_init):
    """Differential attention over ``q`` [b, s, heads, hd] and ``k``,
    ``v`` [b, s, kv heads, hd].  Sub-heads pair up (even, odd): pair
    ``p``'s two maps ``softmax(q1 k1^T)`` and ``softmax(q2 k2^T)`` each
    read the pair's values ``[v_even ; v_odd]``, 2 hd wide.  ONE call at
    the algorithm's own shape: the queries ``[q1 ; q2]`` (heads rows of
    hd) on the keys ``[k1 ; k2]`` (kv heads rows of hd), and under both
    halves of the keys the pairs' values, 2 hd wide (a reshape of ``v``,
    repeated once: 42 MB a layer at Phi-4-mini-flash's 8192 tokens), so
    each score map is formed once and its ``P V`` carries the pair's 2 hd
    channels side by side.  Query row ``i`` reads key/value row
    ``i // group`` as in any grouped call.  The flash kernels take values
    wider than keys (``ops/flash_attention.py``), the reference and
    Ulysses schedules are einsums that never asked, and the ring and
    zigzag schedules size their accumulator by the values: every
    schedule gets this one call.
    ``lambdas`` are the four learned vectors, ``subln`` the norm over a
    pair's 2 hd channels.  Returns [b, s, heads // 2, 2 hd]."""
    b, s, nh, hd = q.shape
    halves = lambda t: jnp.concatenate([t[:, :, 0::2], t[:, :, 1::2]], axis=2)
    pairs = v.reshape(b, s, v.shape[2] // 2, 2 * hd)
    out = _attend(cfg, halves(q), halves(k),
                  jnp.concatenate([pairs, pairs], axis=2),
                  positions, layer_type)
    with jax.named_scope(scopes.ATTN_DIFF):
        first, second = out[:, :, :nh // 2], out[:, :, nh // 2:]
        lq1, lk1, lq2, lk2 = (t.astype(jnp.float32) for t in lambdas)
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + lambda_init)
        att = first.astype(jnp.float32) - lam * second.astype(jnp.float32)
        return (subln(att) * (1.0 - lambda_init)).astype(q.dtype)


def _attend_schedule(cfg: TransformerConfig, q, k, v, positions, layer_type,
                     window):
    if cfg.attention_impl == "flash":
        from ..obs.registry import get_registry  # noqa: PLC0415
        from ..ops.flash_attention import (  # noqa: PLC0415
            flash_attention, flash_attention_folded, flash_plan, unfolded,
        )

        # counted while the step is traced, like remat.kept_values: what
        # the kernels' own plan says of this call
        call = dict(causal=cfg.block_diffusion is None,
                    block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                    window=window, block_diffusion=cfg.block_diffusion)
        attended = functools.partial(flash_attention, q, k, v)
        if q.ndim == 3:
            # head-major already ([b heads, s, hd]: ops/attn_prep.py
            # wrote them); the plan and the gauges read the shapes they
            # stand for
            attended = functools.partial(
                flash_attention_folded, q, k, v, heads=cfg.num_heads,
                kv_heads=cfg.kv_heads)
            q, k, v = (unfolded(q, cfg.num_heads), unfolded(k, cfg.kv_heads),
                       unfolded(v, cfg.kv_heads))
        plan = flash_plan(q, k, v, **call)
        label = layer_type or "attention"
        gauge = lambda name: get_registry().gauge(name, layer_type=label)
        gauge("flash.tiles_live").set(plan.tiles_live)
        gauge("flash.tiles_grid").set(plan.tiles_grid)
        gauge("flash.tiles_mask").set(plan.tiles_mask)
        gauge("flash.bwd_kernels").set(plan.bwd_kernels)
        if cfg.block_diffusion is not None:
            from .block_diffusion import visible_pairs  # noqa: PLC0415

            # the mask's own counts: the block, the rows of both copies,
            # the (query, key) pairs it shows and those inside the live
            # tiles the kernels compute, over batch and heads
            rows = q.shape[0] * q.shape[2]
            get_registry().gauge("bd.block").set(cfg.block_diffusion)
            get_registry().gauge("bd.rows").set(q.shape[1])
            gauge("bd.visible_pairs").set(rows * visible_pairs(
                q.shape[1] // 2, cfg.block_diffusion))
            gauge("bd.live_tile_pairs").set(
                plan.tiles_live * plan.block_q * plan.block_k)
        return attended(scale=cfg.attention_scale, **call)
    if window is not None and cfg.attention_impl != "reference":
        raise ValueError(
            "attention_window is flash-only on a chip (the reference "
            "schedule takes it for tests); "
            f"attention_impl={cfg.attention_impl!r} does not support it"
        )
    if cfg.kv_heads != cfg.num_heads and cfg.attention_impl in (
        "reference", "ulysses"
    ):
        # these schedules attend at full heads; ring/zigzag carry narrow
        # k/v through the ppermute and broadcast after (so GQA's
        # interconnect saving survives sequence parallelism)
        rep = cfg.num_heads // cfg.kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if cfg.attention_scale is not None and cfg.attention_impl != "reference":
        raise ValueError(
            "attention_scale is for the flash and reference schedules; "
            f"attention_impl={cfg.attention_impl!r} does not take it"
        )
    if cfg.attention_impl == "ring":
        from ..parallel.ring_attention import ring_attention  # noqa: PLC0415

        if cfg.sp_axis is None:
            raise ValueError("attention_impl='ring' requires sp_axis")
        return ring_attention(q, k, v, cfg.sp_axis, causal=True)
    if cfg.attention_impl == "zigzag":
        from ..parallel.ring_attention import (  # noqa: PLC0415
            ring_attention_zigzag,
        )

        if cfg.sp_axis is None:
            raise ValueError("attention_impl='zigzag' requires sp_axis")
        return ring_attention_zigzag(q, k, v, cfg.sp_axis)
    if cfg.attention_impl == "ulysses":
        from ..parallel.ring_attention import ulysses_attention  # noqa: PLC0415

        if cfg.sp_axis is None:
            raise ValueError("attention_impl='ulysses' requires sp_axis")
        return ulysses_attention(q, k, v, cfg.sp_axis, causal=True)
    if cfg.attention_impl != "reference":
        raise ValueError(
            f"unknown attention_impl {cfg.attention_impl!r}; expected "
            f"'flash', 'reference', 'ring', 'zigzag', or 'ulysses'"
        )
    from ..parallel.ring_attention import local_attention  # noqa: PLC0415

    # local_attention masks from scalar offsets: valid because every
    # non-zigzag layout is contiguous per shard (zigzag never routes here)
    return local_attention(
        q, k, v, causal=cfg.block_diffusion is None,
        scale=cfg.attention_scale, window=window,
        block_diffusion=cfg.block_diffusion,
        q_offset=positions[0], kv_offset=positions[0]
    )


def act_store(y, cfg: TransformerConfig):
    """The opt-in lossy activation-storage round-trip: materialize ``y``
    at ``cfg.act_store_dtype`` (1 B/elt for e4m3) and widen back to the
    compute dtype — a no-op when the knob is off.  Shared by block_math
    and every MLP closure so the fp8 story has one definition."""
    if cfg.act_store_dtype is None:
        return y
    return jnp.asarray(jnp.asarray(y, cfg.act_store_dtype), cfg.dtype)


def causal_depthwise_conv(x, kernel, bias=None):
    """``x`` [b, s, channels] through a depthwise conv of ``kernel``
    [taps, channels] and ``bias`` (``None``: the filter has none),
    float32: output ``t`` reads inputs ``t-(taps-1) .. t``, zeros before
    the sequence."""
    s, taps = x.shape[1], kernel.shape[0]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, k:k + s] * kernel[k] for k in range(taps))
    return out if bias is None else out + bias


def ssm_prep_chain(fused, conv_kernel, conv_bias, *, inner, heads, groups):
    """The float32 chain in front of the Mamba-2 scan as XLA compiles
    it: what ``ops/ssm_chain.py:ssm_prep`` computes (same arguments,
    same results), for the shapes the kernels do not take
    (``ssm_chain.plan``), and what the tests hold them against."""
    b, s, _ = fused.shape
    bc = (conv_kernel.shape[1] - inner) // 2
    xbc = fused[..., inner:2 * inner + 2 * bc]
    xbc = jax.nn.silu(causal_depthwise_conv(
        xbc, conv_kernel, conv_bias)).astype(fused.dtype)
    x = xbc[..., :inner].reshape(b, s, heads, inner // heads)
    B = xbc[..., inner:inner + bc].reshape(b, s, groups, bc // groups)
    C = xbc[..., inner + bc:].reshape(b, s, groups, bc // groups)
    return x, B, C


def ssm_norm_chain(y, fused, norm_scale, *, groups, eps):
    """The gate and the grouped RMS norm behind the Mamba-2 scan as XLA
    compiles them: what ``ops/ssm_chain.py:ssm_norm`` computes (same
    arguments, same results), for the shapes the kernels do not take,
    and what the tests hold them against."""
    b, s, _ = fused.shape
    inner = norm_scale.shape[0]
    gated = y.reshape(b, s, inner).astype(jnp.float32) \
        * jax.nn.silu(fused[..., :inner].astype(jnp.float32))
    if groups > 1:  # one group: the array as it stands
        gated = gated.reshape(b, s, groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    normed = normed.reshape(b, s, inner) * norm_scale
    # the rounding out_proj's Dense does to a float32 input
    return normed.astype(fused.dtype)


def mamba_mixer(cfg: TransformerConfig, h, *, in_proj, conv_kernel,
                conv_bias, dt_bias, a_log, d_skip, norm_scale, out_proj):
    """The Mamba-2 mixer on the normed stream ``h`` [b, s, emb]: one
    projection to the gate ``z``, the conv's input ``xBC`` and ``dt``;
    a causal depthwise conv and silu over ``xBC`` under the scope
    ``ssm_prep``; the state-space scan (``ops/ssd.py``); the gate, THEN
    the RMS norm over each of the ``ssm_groups`` groups' ``ssm_inner /
    ssm_groups`` channels, every group with its own mean square and all
    under the one learned scale (one group: over all inner channels),
    under the scope ``ssm_norm``; the output projection.  Both chains
    are float32 and rounded once to the compute dtype: the kernel pairs
    of ``ops/ssm_chain.py`` where its ``plan`` takes the shape, else
    :func:`ssm_prep_chain` and :func:`ssm_norm_chain`.  ``in_proj`` and
    ``out_proj`` are callables like ``block_math``'s, the rest raw
    arrays.  ``dt``, ``A`` and everything the scan carries are float32.
    Returns the residual delta."""
    from ..ops import ssm_chain  # noqa: PLC0415
    from ..ops.ssd import ssd_scan  # noqa: PLC0415

    s = h.shape[1]
    inner, groups = cfg.ssm_inner, cfg.ssm_groups
    fused = in_proj(h)
    tiles = ssm_chain.plan(s, inner, groups, cfg.ssm_state,
                           conv_kernel.shape[0])
    prep, norm = (ssm_prep_chain, ssm_norm_chain) if tiles is None else (
        functools.partial(ssm_chain.ssm_prep, tiles=tiles),
        functools.partial(ssm_chain.ssm_norm, tiles=tiles))
    with jax.named_scope(scopes.SSM_PREP):
        x, B, C = prep(fused, conv_kernel, conv_bias, inner=inner,
                       heads=cfg.ssm_heads, groups=groups)
    # a head's one number a token, a few MiB a layer: XLA's
    dt = jax.nn.softplus(
        fused[..., 2 * inner + 2 * groups * cfg.ssm_state:].astype(
            jnp.float32) + dt_bias)
    y = ssd_scan(x, dt, -jnp.exp(a_log.astype(jnp.float32)), B, C, d_skip,
                 cfg.ssm_chunk)
    with jax.named_scope(scopes.SSM_NORM):
        normed = norm(y, fused, norm_scale, groups=groups, eps=cfg.norm_eps)
    return out_proj(normed)


def selective_scan_mixer(cfg: TransformerConfig, h, *, in_proj, conv_kernel,
                         conv_bias, x_proj, dt_proj, a_log, d_skip,
                         out_proj):
    """The Mamba-1 mixer on the normed stream ``h`` [b, s, emb]: one
    projection to the conv's input ``u`` and the gate ``z``; a causal
    depthwise conv and silu over ``u``; ``x_proj(u)`` gives a rank-
    ``ssm_dt_rank`` vector, ``B`` and ``C``; ``dt = softplus(dt_proj(.))``
    a channel; the selective scan (``ops/selective_scan.py``); the gate;
    the output projection (no norm inside the mixer).  The projections
    are callables like ``block_math``'s (``dt_proj`` returns float32 with
    its bias), the rest raw arrays.  ``dt``, ``A`` and everything the
    scan carries are float32.  Returns the residual delta and the scan's
    output ``y`` (with its ``D u`` term, BEFORE the gate): the memory a
    gated memory unit reads."""
    from ..obs.registry import get_registry  # noqa: PLC0415
    from ..ops.selective_scan import kept_mib, selective_scan  # noqa: PLC0415

    b, s, _ = h.shape
    inner, n, rank = cfg.ssm_width, cfg.ssm_state, cfg.ssm_dt_rank
    fused = in_proj(h)
    u, z = fused[..., :inner], fused[..., inner:]
    u = jax.nn.silu(causal_depthwise_conv(
        u, conv_kernel, conv_bias)).astype(fused.dtype)
    low = x_proj(u)
    dt = jax.nn.softplus(dt_proj(low[..., :rank]).astype(jnp.float32))
    # counted while the step is traced: what one layer's scan keeps
    get_registry().gauge("sscan.kept_mib").set(kept_mib(b, s, inner, n))
    y = selective_scan(u, dt, -jnp.exp(a_log.astype(jnp.float32)),
                       low[..., rank:rank + n], low[..., rank + n:], d_skip)
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return out_proj(gated.astype(fused.dtype)), y


def short_conv_filter_bytes(batch: int, seq: int, channels: int,
                            itemsize: int) -> int:
    """What one gated short convolution's elementwise chain has to move
    in a training step, from shapes: forward it reads ``B``, ``C`` and
    ``u`` and writes ``z``; backward it reads those three and ``dz`` and
    writes their three gradients.  Eleven arrays of ``[batch, seq,
    channels]`` in the compute dtype; the filter's taps are a few KiB and
    a recompute is not counted."""
    return 11 * batch * seq * channels * itemsize


def short_conv_mixer(cfg: TransformerConfig, h, *, in_proj, conv_kernel,
                     out_proj):
    """The gated short convolution on the normed stream ``h`` [b, s,
    emb]: one projection to ``[B ; C ; u]``, each emb wide; ``z = C *
    conv(B * u)``, the conv a causal depthwise filter of
    ``conv_kernel`` [taps, emb] without a bias; the output projection.
    ``in_proj`` and ``out_proj`` are callables like ``block_math``'s
    (matmuls in the compute dtype), the gates and the filter float32 as
    :func:`causal_depthwise_conv` computes them, under the scope
    ``short_conv_filter``.  Returns the residual delta."""
    d = h.shape[-1]
    fused = in_proj(h)
    with jax.named_scope(scopes.SHORT_CONV_FILTER):
        gate_b, gate_c, u = (fused[..., i * d:(i + 1) * d].astype(jnp.float32)
                             for i in range(3))
        z = gate_c * causal_depthwise_conv(gate_b * u, conv_kernel)
        z = z.astype(fused.dtype)
    return out_proj(z)


def kda_prep_chain(fused, conv_kernel, decay, dt_bias, a_log):
    """The float32 chain in front of the delta rule as XLA compiles it:
    what ``ops/kda_prep.py``'s kernels compute (same arguments, same
    results), for the shapes they do not take (``kda_prep.plan``), and
    what the tests hold them against."""
    b, s, inner = decay.shape
    heads = a_log.shape[0]
    by_head = lambda t: t.reshape(b, s, heads, inner // heads)
    mixed = jax.nn.silu(causal_depthwise_conv(fused, conv_kernel))
    q, k, v = (by_head(mixed[..., i * inner:(i + 1) * inner])
               for i in range(3))
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * (inner // heads) ** -0.5, unit(k)
    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * by_head(
        jax.nn.softplus(decay.astype(jnp.float32) + dt_bias))
    return (*(t.astype(fused.dtype) for t in (q, k, v)), g)


def kda_mixer(cfg: TransformerConfig, h, *, qkv, conv_kernel, f_a, f_b,
              dt_bias, a_log, b_proj, g_a, g_b, norm_scale, o_proj):
    """Kimi Delta Attention (arXiv:2510.26692) on the normed stream ``h``
    [b, s, emb]: one projection to ``[q ; k ; v]``, each ``kda_heads x
    kda_head_dim`` wide; a causal depthwise filter of ``conv_kernel``
    [taps, 3 inner] and silu on all three; ``q`` and ``k`` of unit
    length over a head's channels (``+ 1e-6`` under the root), ``q``
    then scaled by ``kda_head_dim ** -0.5``; the log-decay a channel
    ``g = -exp(a_log) * softplus(f_b(f_a(h)) + dt_bias)`` (``a_log`` one
    number a head); ``beta = sigmoid(b_proj(h))`` a head; the gated
    delta rule (``ops/kda.py``) in chunks of ``kda_chunk`` tokens, which
    refuses a sequence the chunk does not divide; an RMS norm over each head's channels with the one scale
    ``norm_scale`` times ``sigmoid(g_b(g_a(h)))``; the output
    projection.  The projections are callables like ``block_math``'s
    (matmuls in the compute dtype), the rest raw arrays; the chain from
    the filters to the rule's inputs is float32 under the scope
    ``kda_prep`` (``q``, ``k`` and ``v`` enter the rule in the compute
    dtype, ``g`` and ``beta`` in float32).  Returns the residual
    delta."""
    from ..ops import kda_prep  # noqa: PLC0415
    from ..ops.kda import kda  # noqa: PLC0415

    b, s, _ = h.shape
    heads, hd, inner = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner
    by_head = lambda t: t.reshape(b, s, heads, hd)
    fused = qkv(h)
    decay, beta, gate = f_b(f_a(h)), b_proj(h), g_b(g_a(h))
    with jax.named_scope(scopes.KDA_PREP):
        tiles = kda_prep.plan(s, heads, hd, conv_kernel.shape[0])
        prep = kda_prep_chain if tiles is None else functools.partial(
            kda_prep.kda_prep, tiles=tiles)
        q, k, v, g = prep(fused, conv_kernel, decay, dt_bias, a_log)
        # a head's one number a token, a few hundred KB: XLA's
        beta = jax.nn.sigmoid(beta.astype(jnp.float32))
    o = kda(q, k, v, g, beta, chunk=cfg.kda_chunk,
            states_every=cfg.kda_states_every).astype(jnp.float32)
    normed = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
    gated = normed * norm_scale * jax.nn.sigmoid(
        by_head(gate.astype(jnp.float32)))
    return o_proj(gated.reshape(b, s, inner).astype(fused.dtype))


def gdn_prep_chain(fused, decay, beta, conv_kernel, dt_bias, a_log, *,
                   key_heads, value_heads, d_k, d_v):
    """The float32 chain in front of the scalar-decay delta rule:
    ``fused`` [b, s, >= 2 key_heads d_k + value_heads d_v] holds ``[q ;
    k ; v]`` first, through ONE causal depthwise filter ``conv_kernel``
    [taps, that width] and silu; ``q`` and ``k`` of unit length over a
    head's channels (``+ 1e-6`` under the root), ``q`` then scaled by
    ``d_k ** -0.5``, all three rounded to ``fused``'s dtype; ``g =
    -exp(a_log) * softplus(decay + dt_bias)`` and ``sigmoid(beta)`` a
    value head, float32."""
    b, s, _ = fused.shape
    key_inner, value_inner = key_heads * d_k, value_heads * d_v
    mixed = jax.nn.silu(causal_depthwise_conv(
        fused[..., :2 * key_inner + value_inner], conv_kernel))
    q = mixed[..., :key_inner].reshape(b, s, key_heads, d_k)
    k = mixed[..., key_inner:2 * key_inner].reshape(b, s, key_heads, d_k)
    v = mixed[..., 2 * key_inner:].reshape(b, s, value_heads, d_v)
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * d_k ** -0.5, unit(k)
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        decay.astype(jnp.float32) + dt_bias)
    return (*(t.astype(fused.dtype) for t in (q, k, v)), g,
            jax.nn.sigmoid(beta.astype(jnp.float32)))


def gdn_mixer(cfg: TransformerConfig, h, *, in_proj, ba_proj, conv_kernel,
              dt_bias, a_log, norm_scale, out_proj):
    """Gated DeltaNet (arXiv:2412.06464, as the ``qwen3_next`` family
    builds it) on the normed stream ``h`` [b, s, emb]: ``in_proj`` gives
    ``[q ; k ; v ; z]``, ``q`` and ``k`` ``gdn_key_heads x
    gdn_key_head_dim`` wide, ``v`` and the gate ``z`` ``gdn_value_heads
    x gdn_value_head_dim``; ``ba_proj`` gives ``[b ; a]``, a number a
    value head each.  Under the scope ``gdn_prep``
    (:func:`gdn_prep_chain`): one filter of ``conv_kernel`` [taps, q + k
    + v] and silu, the two L2 norms, ``g = -exp(a_log) * softplus(a +
    dt_bias)`` and ``beta = sigmoid(b)``.  The rule
    (``ops/kda.py:gated_delta_rule``, scope ``gdn_scan``) in chunks of
    ``kda_chunk`` tokens, which refuses a sequence the chunk does not
    divide; an RMS norm over each value head's channels with the one
    scale ``norm_scale`` (plain, whatever ``norm_unit_offset`` says)
    times ``silu(z)``; the output projection.  The projections are
    callables like ``block_math``'s, the rest raw arrays; ``q``, ``k``
    and ``v`` enter the rule in the compute dtype, ``g`` and ``beta`` in
    float32.  Returns the residual delta."""
    from ..ops.kda import gated_delta_rule  # noqa: PLC0415

    b, s, _ = h.shape
    heads, d_v = cfg.gdn_value_heads, cfg.gdn_value_head_dim
    fused, ba = in_proj(h), ba_proj(h)
    with jax.named_scope(scopes.GDN_PREP):
        q, k, v, g, beta = gdn_prep_chain(
            fused, ba[..., heads:], ba[..., :heads], conv_kernel, dt_bias,
            a_log, key_heads=cfg.gdn_key_heads, value_heads=heads,
            d_k=cfg.gdn_key_head_dim, d_v=d_v)
    o = gated_delta_rule(q, k, v, g, beta, chunk=cfg.kda_chunk,
                         states_every=cfg.kda_states_every).astype(
                             jnp.float32)
    z = fused[..., 2 * cfg.gdn_key_inner + cfg.gdn_value_inner:]
    normed = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
    gated = normed * norm_scale * jax.nn.silu(
        z.astype(jnp.float32).reshape(b, s, heads, d_v))
    return out_proj(gated.reshape(b, s, heads * d_v).astype(fused.dtype))


def gmu_mixer(h, memory, *, in_proj, out_proj):
    """A gated memory unit (arXiv:2507.06607) on the normed stream ``h``:
    ``out_proj(silu(in_proj(h)) * memory)``, ``memory`` [b, s, width]
    the scan output another layer handed on.  Returns the residual
    delta."""
    gate = in_proj(h)
    gated = jax.nn.silu(gate.astype(jnp.float32)) * memory.astype(jnp.float32)
    return out_proj(gated.astype(gate.dtype))


def mla_mixer(cfg: TransformerConfig, h, positions, rope_tabs, *, q_b,
              kv_a, kv_a_norm, kv_b, proj, q_a=None, q_a_norm=None):
    """Latent attention on the normed stream ``h`` [b, s, emb]: queries
    ``q_b(norm(q_a(h)))``, or ``q_b(h)`` where the configuration has no
    query rank (``q_a`` is ``None``), heads of ``[nope ; rope]``;
    ``kv_a(h)`` gives the latent and ONE rotary key for all heads;
    ``kv_b(norm(latent))`` gives each head's ``[k_nope ; v]``.  RoPE
    (``ops/rope.py``, split halves) turns the rotary parts only, and
    nothing where the caller hands in ``rope_tabs=None`` (a layer that
    sees no positions: the rotary channels are plain ones); a head's key
    is its own ``k_nope`` beside the shared rotary key.  Scores over the
    whole ``nope + rope`` channels, causal, through the configured
    schedule; the values may be narrower than the keys.  The layers are
    callables like ``block_math``'s.  Returns the residual delta."""
    b, s, _ = h.shape
    nh, latent = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    with jax.named_scope(scopes.MLA_PROJ):
        q = q_b(h if q_a is None else q_a_norm(q_a(h)))
        q = q.reshape(b, s, nh, nope + rope)
        kv = kv_a(h)
        k_v = kv_b(kv_a_norm(kv[..., :latent])).reshape(b, s, nh, nope + vd)
        k_rope = kv[..., None, latent:]
        if rope_tabs is not None:
            from ..ops.rope import apply_rope_tables  # noqa: PLC0415

            q_rope = apply_rope_tables(q[..., nope:], *rope_tabs)
            k_rope = apply_rope_tables(k_rope, *rope_tabs)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [k_v[..., :nope], jnp.broadcast_to(k_rope, (b, s, nh, rope))],
            axis=-1)
        v = k_v[..., nope:]
    att = _attend(cfg, q, k, v, positions)
    return proj(act_store(att.reshape(b, s, nh * vd), cfg))


def attn_prep_chain(fused, q_norm, k_norm, rope_tabs, *, heads, kv_heads,
                    head_dim, shared_kv=None):
    """What stands between the fused q/k/v matmul and the attention call
    as XLA compiles it: ``fused`` [b, s, (heads + 2 kv_heads) head_dim]
    split into ``q`` [b, s, heads, head_dim] and ``k``, ``v`` [b, s,
    kv_heads, head_dim] (``shared_kv=(k, v)``: ``fused`` holds the
    queries alone), ``q_norm`` and ``k_norm`` over each head's channels
    where there are any, rounded to ``fused``'s dtype, then the rotation
    by ``rope_tabs`` where the layer sees positions.  What
    ``ops/attn_prep.py``'s kernels compute (head-major there), for the
    calls they do not take (``attn_prep.plan``), and what the tests hold
    them against."""
    b, s, _ = fused.shape
    q_dim, kv_dim = heads * head_dim, kv_heads * head_dim
    q = fused[..., :q_dim].reshape(b, s, heads, head_dim)
    if shared_kv is not None:
        k, v = shared_kv
    else:
        k = fused[..., q_dim:q_dim + kv_dim].reshape(b, s, kv_heads, head_dim)
        v = fused[..., q_dim + kv_dim:].reshape(b, s, kv_heads, head_dim)
    if q_norm is not None:
        q = q_norm(q).astype(fused.dtype)
    if k_norm is not None:
        k = k_norm(k).astype(fused.dtype)
    if rope_tabs is not None:
        from ..ops.rope import apply_rope_tables  # noqa: PLC0415

        q = apply_rope_tables(q, *rope_tabs)
        k = apply_rope_tables(k, *rope_tabs)
    return q, k, v


def _attn_prep_plan(cfg: TransformerConfig, seq: int, heads: int,
                    kv_heads: int, *, norm, rotates: bool, plain: bool):
    """What ``ops/attn_prep.py:plan`` says of an attention layer of
    ``cfg`` at ``seq`` rows: the kernels' tiles, or ``None`` for the
    chain.  One place for :func:`attention_mixer`, which decides by it,
    and for the gauges ``GPT.__call__`` sets.  A layer whose gate comes
    out of the query projection, whose head norms scale by ``1 + w`` or
    whose heads are turned in part gets the chain: the kernels take none
    of the three."""
    from ..ops import attn_prep  # noqa: PLC0415

    if (cfg.query_gate or (norm and cfg.norm_unit_offset)
            or (rotates and cfg.rope_dim != cfg.head_dim)):
        # the kernels read [q ; k ; v], scale by w and turn whole heads
        return None
    return attn_prep.plan(seq, heads, kv_heads, cfg.head_dim, norm=norm,
                          rotates=rotates,
                          flash=cfg.attention_impl == "flash", plain=plain)


def _scale_of(norm, width: int):
    """The learned scale [width] of a flax norm over ``width`` channels
    that may not have run yet: a call on one row of zeros makes the
    parameter where the module is being initialised (the compiler drops
    the row)."""
    norm(jnp.zeros((width,), jnp.float32))
    return norm.variables["params"]["scale"]


def attention_mixer(cfg: TransformerConfig, h, positions, rope_tabs, *,
                    qkv, proj, num_heads: Optional[int] = None,
                    num_kv_heads: Optional[int] = None, attend=None,
                    layer_type: Optional[str] = None, q_norm=None,
                    k_norm=None, gate=None, differential=None,
                    shared_kv=None, hand_on: Optional[str] = None,
                    query_gate: bool = False):
    """Attention on the normed stream ``h`` [b, s, emb]: ``qkv →
    split-heads → rope → attend → proj``.  ``qkv`` and ``proj`` are
    callables like ``block_math``'s (flax modules, raw-weight closures,
    or psum-rejoined tensor-parallel closures).  Returns the residual
    delta, or ``(delta, (k, v))`` with ``hand_on="kv"``: the keys and
    values as this layer attends them, for the layers that read them.

    What stands between ``qkv`` and the attention call (the split into
    heads, the head norms, the rotation) traces under the scope
    ``attn_prep``: the kernel pair of ``ops/attn_prep.py``, which hands
    the flash kernels ``q``, ``k`` and ``v`` head-major, where its
    ``plan`` takes the call (the flash schedule, the layer's own keys
    and values, RMS norms or none, heads of whole 128-lane tiles), else
    :func:`attn_prep_chain`.

    ``num_heads`` / ``num_kv_heads`` override the config's head counts
    for callers operating on a per-rank head shard (TP).  ``attend``
    overrides the attention schedule itself: a callable ``(q, k, v) ->
    att`` over the rope-applied ``[b, s, heads, head_dim]`` tensors — the
    KV-cache decode path (models/decode.py) supplies one that appends to
    its cache and attends the single query against the prefix, so
    decoding reuses THIS wiring instead of a third copy.

    The optional steps, each a callable where the configuration asks for
    it and ``None`` where not: ``q_norm`` and ``k_norm`` over each
    head's channels after the head split and before RoPE; ``gate``, from
    the same normed stream as the queries and as wide, whose sigmoid
    multiplies the attended values before ``proj`` (scope
    ``attn_gate``); ``query_gate`` instead of ``gate``: ``qkv``'s output
    is ``[gate ; q ; k ; v]`` and its leading ``q``-wide part is the
    gate's values (the same scope around sigmoid and product).
    ``layer_type`` gives the attention call its window
    (``cfg.window_of``); the caller hands in ``rope_tabs=None`` for a
    layer that sees no positions.  ``shared_kv=(k, v)``, what another
    layer handed on, makes ``qkv`` a projection to the queries alone
    (scope ``attn_cross`` around the attention call).  ``differential``
    holds ``lambdas``, ``subln`` and ``lambda_init`` where the attention
    is differential (:func:`_attend_differential`, scope ``attn_diff``).
    """
    b, s, _ = h.shape
    nh = num_heads if num_heads is not None else cfg.num_heads
    nkv = num_kv_heads if num_kv_heads is not None else cfg.kv_heads
    hd = cfg.head_dim
    q_dim = nh * hd
    fused = qkv(h)
    if query_gate:
        gate_values, fused = fused[..., :q_dim], fused[..., q_dim:]
    norms = q_norm is not None or k_norm is not None
    rms = isinstance(q_norm, nn.RMSNorm) and isinstance(k_norm, nn.RMSNorm)
    tiles = _attn_prep_plan(
        cfg, s, nh, nkv,
        norm="rmsnorm" if rms else "other" if norms else None,
        rotates=rope_tabs is not None,
        plain=(attend is None and shared_kv is None and hand_on is None
               and differential is None))
    with jax.named_scope(scopes.ATTN_PREP):
        if tiles is None:
            q, k, v = attn_prep_chain(
                fused, q_norm, k_norm, rope_tabs, heads=nh, kv_heads=nkv,
                head_dim=hd, shared_kv=shared_kv)
        else:
            from ..ops import attn_prep  # noqa: PLC0415

            # head-major, as the flash kernels read them
            q, k, v = attn_prep.attn_prep(
                fused,
                (_scale_of(q_norm, hd), _scale_of(k_norm, hd)) if rms
                else None,
                rope_tabs, heads=nh, kv_heads=nkv,
                eps=q_norm.epsilon if rms else 0.0, tiles=tiles)
    if differential is not None:
        att_4d = _attend_differential(cfg, q, k, v, positions, layer_type,
                                      **differential)
    elif attend is None:
        attend_cfg = cfg
        if nh != cfg.num_heads or nkv != cfg.kv_heads:
            # per-rank head shard: _attend sees the LOCAL head geometry
            attend_cfg = replace(cfg, num_heads=nh, num_kv_heads=nkv,
                                 emb_dim=q_dim)
        att_4d = _attend(attend_cfg, q, k, v, positions, layer_type)
    else:
        att_4d = attend(q, k, v)
    att = att_4d.reshape(b, s, q_dim)
    if gate is not None or query_gate:
        with jax.named_scope(scopes.ATTN_GATE):
            if not query_gate:
                gate_values = gate(h)
            att = (att * jax.nn.sigmoid(
                gate_values.astype(jnp.float32))).astype(att.dtype)
    delta = proj(act_store(att, cfg))
    return (delta, (k, v)) if hand_on == "kv" else delta


# The scope a layer type's mixer traces under; any type not named here
# is an attention layer and traces under ``attn``.
MIXER_SCOPES = {"mamba": scopes.SSM, "selective_scan": scopes.SSM,
                "gmu": scopes.GMU, "conv": scopes.SHORT_CONV,
                "kda": scopes.KDA, "gdn": scopes.GDN}
# The layer types that do not run :func:`attention_mixer`.
NOT_ATTENTION_MIXER = frozenset({*MIXER_SCOPES, "mla", FEED_FORWARD})


def block_math(cfg: TransformerConfig, x, *, ln1, mixer=None, ln2=None,
               mlp=None, layer_type: Optional[str] = None,
               post_attn_norm=None, post_mlp_norm=None,
               hand_on: Optional[str] = None, route=None,
               connections=None):
    """THE pre-norm block wiring — the single source of truth.

    One stream (``connections=None``): ``norm → mixer → (+res) → norm →
    feed-forward → (+res)``, each branch's output added to the stream
    times ``cfg.residual_multiplier``.  ``cfg.hc_mult`` streams
    (``x`` is ``[b, s, hc_mult * emb]``; ``connections`` a pair of
    callables, the mixer half's and the feed-forward half's, each ``x →
    (pre, post, res)``: ``models/hyper_connections.py:coefficients`` with
    the caller's parameters closed over): each half is ``coefficients →
    read-out → norm → branch → write-back``, the streams' weighted sum
    in, the streams mixed by ``res`` plus ``post`` times the branch's
    output back.  The branch, its norm and its scope are the same in
    both; the hyper-connection's three stages trace under ``hc_coeff``,
    ``hc_read`` and ``hc_write``, outside ``attn`` and ``mlp``.

    A layer of one half (``cfg.one_half``) hands in the half it has:
    ``mixer`` without ``ln2`` and ``mlp``, or ``mlp`` without ``mixer``
    and ``ln2``; ``ln1`` is then the one norm, and nothing of the absent
    half is called, normed or traced.  Everything below holds for the
    half that is there.

    ``mixer`` is a callable of the normed stream (``[b, s, emb]``
    whatever ``hc_mult`` is) that returns the residual DELTA:
    :func:`attention_mixer`, :func:`mla_mixer`, :func:`mamba_mixer`,
    :func:`selective_scan_mixer`, :func:`gmu_mixer`,
    :func:`short_conv_mixer`, :func:`kda_mixer` or :func:`gdn_mixer` with
    the caller's parameterized layer applications closed over; it traces
    under the scope of its ``layer_type`` (``MIXER_SCOPES``: ``ssm``,
    ``gmu``, ``short_conv``, ``kda``, ``gdn`` or ``attn``).  Shared by
    the flax :class:`Block`, the raw-weights pipeline-parallel and decode block (:func:`raw_block_forward`), and
    the Megatron tensor-parallel block (``parallel/tensor_parallel.py``),
    so a change to the block (a norm variant, the residual's scale, a
    post-norm, the residual path itself) is made exactly once, and a new
    architecture is a new mixer function, not an edit here.

    ``ln1``, ``ln2``, ``mlp`` and the optional ``post_attn_norm`` and
    ``post_mlp_norm`` (on a branch's output before it joins the stream)
    are callables too; ``mlp`` returns its DELTA.

    Values that cross layers: with ``hand_on`` (``"kv"`` or
    ``"memory"``) the mixer returns ``(delta, value)`` and the function
    returns ``(x, value)`` instead of ``x``.  A layer that reads one
    has it closed over in its mixer.

    ``route``: where the configuration's router reads the layer's input,
    a callable applied to the block's input ``x`` before ``ln1`` (it
    traces under the scope ``moe_route``, here at the block's top); what
    it decides is handed to ``mlp`` as a second argument, which applies
    it to ``ln2`` of the stream after the mixer.  In a rematerialised
    block the decision crosses the mixer half inside one
    ``jax.checkpoint``, and its recompute is the sort again.
    """
    from . import hyper_connections as hc  # noqa: PLC0415

    def joining(delta, post, dtype):
        """A branch's output as it joins the stream."""
        delta = act_store(delta, cfg)
        if post is not None:
            delta = post(delta).astype(dtype)
        if cfg.residual_multiplier != 1.0:
            delta = cfg.residual_multiplier * delta
        return delta

    def half(x, scope, norm, branch, post, connection, hands=False):
        """One half on the stream ``x``: the next stream and what the
        branch handed on beside its delta."""
        handed = None
        if connection is not None:
            pre, post_weights, res = connection(x)
            read = hc.read_out(x, pre)
        with jax.named_scope(scope):
            delta = branch(norm(x if connection is None else read))
            if hands:
                delta, handed = delta
            delta = joining(delta, post, x.dtype)
            if connection is None:
                x = x + delta
        if connection is not None:
            x = hc.write_back(x, delta, post_weights, res)
        return x, handed

    # The two halves trace under scopes (``jax.named_scope``) of their own, so
    # a device trace tells the mixer from the MLP whatever XLA names the
    # fusions.  A scope is metadata: it names no parameter, so the flax
    # tree stays ``block<i>/{ln1,qkv,proj,ln2,fc1,fc2}``.
    for_mixer, for_mlp = connections or (None, None)
    decided = () if route is None else (route(x),)
    handed = None
    if mixer is not None:
        x, handed = half(x, MIXER_SCOPES.get(layer_type, scopes.ATTN), ln1,
                         mixer, post_attn_norm, for_mixer,
                         hands=hand_on is not None)
    if mlp is not None:
        x, _ = half(x, scopes.MLP, ln1 if mixer is None else ln2,
                    lambda h: mlp(h, *decided), post_mlp_norm, for_mlp)
    return x if hand_on is None else (x, handed)


def raw_layer_norm(x, scale, bias, eps: float = 1e-6):
    """LayerNorm from raw weights, fp32 math (matches flax's
    ``nn.LayerNorm(dtype=jnp.float32)`` as the models use it)."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y * scale + bias


def raw_dense(sub, dtype):
    """The dense-layer application from a raw ``{kernel, bias}`` subtree
    in the given compute dtype — the one definition of "apply a Dense
    from raw weights" shared by the pipeline and tensor-parallel block
    closures."""
    return lambda h: h.astype(dtype) @ sub["kernel"].astype(dtype) \
        + sub["bias"].astype(dtype)


def raw_block_forward(cfg: TransformerConfig, p, x, positions, rope_tabs,
                      attend=None):
    """One dense transformer block from a raw ``Block`` weight subtree
    ``p`` (keys ``ln1/qkv/proj/ln2/fc1/fc2``) — :func:`block_math` with
    plain-matmul closures.  Used by the pipeline-parallel stage body
    (``parallel/pipeline.py``) and, with an ``attend`` override, the
    KV-cache decode path (models/decode.py); numerically equivalent to
    the flax :class:`Block` (pinned by tests/test_pipeline.py)."""
    require_gpt2_block(cfg, "raw_block_forward")
    dt = cfg.dtype

    def mlp(h):
        m = act_store(jax.nn.gelu(raw_dense(p["fc1"], dt)(h)), cfg)
        return raw_dense(p["fc2"], dt)(m)

    return block_math(
        cfg, x,
        ln1=lambda h: raw_layer_norm(h, p["ln1"]["scale"], p["ln1"]["bias"]),
        mixer=lambda h: attention_mixer(
            cfg, h, positions, rope_tabs, qkv=raw_dense(p["qkv"], dt),
            proj=raw_dense(p["proj"], dt), attend=attend),
        ln2=lambda h: raw_layer_norm(h, p["ln2"]["scale"], p["ln2"]["bias"]),
        mlp=mlp,
    )


class UnitOffsetRMSNorm(nn.Module):
    """``x * rsqrt(mean x^2 + epsilon) * (1 + scale)`` over the last
    axis, ``scale`` from zeros, float32 math: the RMS norm of the
    families that store the scale's distance from 1."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            + self.epsilon) * (1.0 + scale)


def _norm(cfg: TransformerConfig, name: str):
    """The configuration's norm as a flax module, float32 math."""
    if cfg.norm_unit_offset:
        return UnitOffsetRMSNorm(epsilon=cfg.norm_eps, name=name)
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1] (the Mamba-2
    paper's initialisation)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse


def _conv_init(key, shape, dtype=jnp.float32):
    """Uniform in +-1/sqrt(taps): what the Mamba-2 reference code's
    depthwise ``Conv1d`` starts from (PyTorch's default).  With normal
    0.02 the conv's output, and with it B, C and the state's share of
    ``y``, would start two orders of magnitude under the skip ``D x``."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Block(nn.Module):
    """Pre-norm block: norm → mixer → +res, norm → MLP → +res.

    The wiring lives in :func:`block_math`; this module only declares
    the flax parameters (the attention mixer's, a state-space mixer's, a
    gated memory unit's, a gated short convolution's, Kimi Delta
    Attention's, Gated DeltaNet's or latent attention's, by
    ``layer_type``; a dense feed-forward's or the routed
    experts', by ``ffn``; with ``cfg.hc_mult`` streams the two
    hyper-connections') and hands
    their applications in as callables: one ``mixer`` closure over the
    layer type's mixer function, the norms and ``mlp``.  ``hand_on`` says what the block
    returns beside ``x`` for later layers (``cfg.hands_on``);
    ``layer_index`` is the layer's index in the whole model.  A
    ``cross_attention`` block is called with ``shared_kv``, a ``gmu``
    block with ``memory``.  In a model of one-half layers a block holds
    ``ln1`` and one half's parameters: the mixer's (``ffn="none"``) or,
    for ``layer_type="feed_forward"``, the feed-forward's.
    """

    cfg: TransformerConfig
    layer_type: str = "attention"
    ffn: str = "dense"
    hand_on: Optional[str] = None
    layer_index: int = 0

    @nn.compact
    def __call__(self, x, positions, rope_tabs=None, shared_kv=None,
                 memory=None):
        cfg = self.cfg
        kv_dim = cfg.kv_heads * cfg.head_dim
        width = cfg.ffn_width

        def dense(features, name, use_bias=cfg.use_bias):
            return nn.Dense(features, dtype=cfg.dtype, use_bias=use_bias,
                            name=name)

        def unbiased(features, name):
            return dense(features, name, use_bias=False)

        def feed_forward(h, wide, fc1, fc2):
            """The configuration's dense feed-forward, ``wide`` wide."""
            layer = lambda features, name: dense(features, name,
                                                 cfg.ffn_bias)
            if cfg.mlp == "silu_gated":
                gate_up = layer(2 * wide, fc1)(h)
                m = jax.nn.silu(gate_up[..., :wide]) * gate_up[..., wide:]
            elif cfg.mlp == "relu2":
                m = jnp.square(jax.nn.relu(layer(wide, fc1)(h)))
            else:
                m = nn.gelu(layer(wide, fc1)(h))
            return layer(cfg.emb_dim, fc2)(act_store(m, cfg))

        def decide(x2):
            """The router's decision for the flat tokens ``x2 [n, d]``
            (parallel/moe.py:routing_decision): the stream the experts
            read, or the layer's input where the router stands before
            attention."""
            from ..parallel.moe import routing_decision  # noqa: PLC0415

            bias = None
            if cfg.routed_scores == "sigmoid":
                bias = self.variable(
                    "moe_state", "bias", lambda: jax.random.uniform(
                        self.make_rng("params"), (cfg.routed_experts,),
                        jnp.float32, -0.05, 0.05)).value
            router = self.param("router", nn.initializers.normal(0.02),
                                (x2.shape[-1], cfg.routed_experts),
                                jnp.float32)
            return routing_decision(
                x2, router, bias, top_k=cfg.routed_top_k,
                scaling=cfg.routed_scaling,
                first_held=cfg.routed_first_held, held=cfg.held_experts,
                score_rule=cfg.routed_scores,
                balance=cfg.routed_balance_coef > 0)

        def routed(h, routing=None):
            """Routed experts that drop nothing, the shared expert
            beside them (parallel/moe.py has the core), by the decision
            made from the layer's input or, without one, from ``h``."""
            from ..obs.registry import get_registry  # noqa: PLC0415
            from ..ops import moe_combine  # noqa: PLC0415
            from ..parallel.moe import (  # noqa: PLC0415
                apply_routing, ffn_tile_fill, row_bound,
            )

            b, s, d = h.shape
            held, ff = cfg.held_experts, cfg.routed_width
            bound = row_bound(b * s, cfg.routed_top_k, held,
                              cfg.routed_experts)
            # set while the step is traced, like flash.tiles_*: the share
            # of what the six grouped matmuls multiply that is needed, the
            # rows every [row_bound, .] buffer carries, and the slots the
            # layer runs on in a step that passes the bound
            for name, value in (
                    ("gmm_tile_fill", ffn_tile_fill(d, ff, cfg.dtype,
                                                    cfg.routed_gated)),
                    ("row_bound", bound),
                    ("slots", b * s * cfg.routed_top_k)):
                get_registry().gauge(
                    f"moe.{name}", layer="/".join(self.path)).set(value)
            # the expert layers of the program whose way back to the
            # tokens takes the kernel (all or none: they share a shape)
            layers = [cfg.ffn_type(i) for i in range(cfg.num_layers)]
            get_registry().gauge("moe.combine_kernel_layers").set(
                (layers + layers[-1:] * cfg.mtp_modules).count("routed")
                if moe_combine.engaged(b * s, d, held, bound, cfg.dtype)
                else 0)
            stacked = nn.initializers.lecun_normal(batch_axis=(0,))
            x2 = h.reshape(b * s, d)
            if routing is None:
                routing = decide(x2)
            y, routing = apply_routing(
                routing, x2,
                self.param("experts_fc1", stacked,
                           (held, d, 2 * ff if cfg.routed_gated else ff),
                           jnp.float32),
                self.param("experts_fc2", stacked, (held, ff, d),
                           jnp.float32),
                dtype=cfg.dtype, activation=cfg.routed_activation,
                # initialising makes variables from shapes: the grouped
                # matmul's stand-in keeps the kernel out of that program
                interpret=True if self.is_initializing() else None)
            if self.is_mutable_collection("moe_stats"):
                self.variable("moe_stats", "rows", lambda: None).value = \
                    routing.group_sizes[:held]
                self.variable("moe_stats", "dropped", lambda: None).value \
                    = routing.dropped
                self.variable("moe_stats", "load", lambda: None).value = \
                    routing.load
                # counted up from the state's making: the steps in which
                # this layer ran on the whole slot buffer
                overflows = self.variable(
                    "moe_stats", "overflow_steps",
                    lambda: jnp.zeros((), jnp.int32))
                if not self.is_initializing():
                    overflows.value = overflows.value + jnp.asarray(
                        routing.overflowed, jnp.int32)
                if routing.balance is not None:
                    self.variable("moe_stats", "balance_loss",
                                  lambda: None).value = routing.balance
            if routing.balance is not None:
                # as the GShard path sows its auxiliary loss: the step
                # adds routed_balance_coef times the layers' sum
                self.sow("losses", "moe_balance", routing.balance)
            y = y.reshape(b, s, d)
            if cfg.shared_experts > 0:
                with jax.named_scope(scopes.MOE_SHARED):
                    shared = feed_forward(h, cfg.shared_ffn_width,
                                          "shared_fc1", "shared_fc2")
                    if cfg.shared_expert_gate:
                        shared = (shared * jax.nn.sigmoid(
                            unbiased(1, "shared_gate")(h).astype(
                                jnp.float32))).astype(shared.dtype)
                    y = y + shared
            return y

        def mlp(h, routing=None):
            if self.ffn == "routed":
                return routed(h, routing)
            if cfg.moe_experts > 0:
                from ..parallel.moe import (  # noqa: PLC0415
                    moe_flax_params, moe_mlp,
                )

                moe_p = moe_flax_params(
                    self, cfg.emb_dim, width, cfg.moe_experts,
                )
                y, aux = moe_mlp(
                    h, moe_p, top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    group_size=cfg.moe_group_size, dtype=cfg.dtype,
                    act_store_dtype=cfg.act_store_dtype,
                )
                self.sow("losses", "moe_aux", aux)
                # y inherits ln2's fp32; keep the residual stream in the
                # compute dtype like the dense-MLP path does
                return y.astype(cfg.dtype)
            return feed_forward(h, width, "fc1", "fc2")

        if self.layer_type == FEED_FORWARD:
            mixer = None
        elif self.layer_type == "mamba":
            inner, heads = cfg.ssm_inner, cfg.ssm_heads
            conv_dim = inner + 2 * cfg.ssm_groups * cfg.ssm_state
            zeros, ones = nn.initializers.zeros, nn.initializers.ones

            def mixer(h):
                return mamba_mixer(
                    cfg, h,
                    in_proj=nn.Dense(inner + conv_dim + heads,
                                     dtype=cfg.dtype, use_bias=False,
                                     name="in_proj"),
                    conv_kernel=self.param(
                        "conv_kernel", _conv_init,
                        (cfg.ssm_conv, conv_dim), jnp.float32),
                    conv_bias=self.param("conv_bias", zeros, (conv_dim,),
                                         jnp.float32),
                    dt_bias=self.param("dt_bias", _dt_bias_init, (heads,),
                                       jnp.float32),
                    a_log=self.param(
                        "A_log", lambda *_: jnp.log(
                            jnp.arange(1, heads + 1, dtype=jnp.float32))),
                    d_skip=self.param("D", ones, (heads,), jnp.float32),
                    norm_scale=self.param("ssm_norm", ones, (inner,),
                                          jnp.float32),
                    out_proj=nn.Dense(cfg.emb_dim, dtype=cfg.dtype,
                                      use_bias=False, name="out_proj"),
                )

        elif self.layer_type == "selective_scan":
            inner, n = cfg.ssm_width, cfg.ssm_state

            def mixer(h):
                delta, y = selective_scan_mixer(
                    cfg, h,
                    in_proj=unbiased(2 * inner, "in_proj"),
                    conv_kernel=self.param(
                        "conv_kernel", _conv_init, (cfg.ssm_conv, inner),
                        jnp.float32),
                    conv_bias=self.param(
                        "conv_bias", nn.initializers.zeros, (inner,),
                        jnp.float32),
                    x_proj=unbiased(cfg.ssm_dt_rank + 2 * n, "x_proj"),
                    # float32 out: dt is float32 from its making on
                    dt_proj=nn.Dense(inner, dtype=jnp.float32,
                                     bias_init=_dt_bias_init,
                                     name="dt_proj"),
                    a_log=self.param(
                        "A_log", lambda *_: jnp.broadcast_to(jnp.log(
                            jnp.arange(1, n + 1, dtype=jnp.float32)),
                            (inner, n))),
                    d_skip=self.param("D", nn.initializers.ones, (inner,),
                                      jnp.float32),
                    out_proj=unbiased(cfg.emb_dim, "out_proj"))
                return (delta, y) if self.hand_on == "memory" else delta

        elif self.layer_type == "gmu":
            mixer = lambda h: gmu_mixer(
                h, memory, in_proj=unbiased(cfg.ssm_width, "in_proj"),
                out_proj=unbiased(cfg.emb_dim, "out_proj"))
        elif self.layer_type == "conv":
            mixer = lambda h: short_conv_mixer(
                cfg, h, in_proj=unbiased(3 * cfg.emb_dim, "in_proj"),
                conv_kernel=self.param(
                    "conv_kernel", _conv_init,
                    (cfg.conv_taps, cfg.emb_dim), jnp.float32),
                out_proj=unbiased(cfg.emb_dim, "out_proj"))
        elif self.layer_type == "kda":
            heads, hd, inner = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner
            mixer = lambda h: kda_mixer(
                cfg, h, qkv=unbiased(3 * inner, "qkv"),
                conv_kernel=self.param(
                    "conv_kernel", _conv_init, (cfg.kda_conv, 3 * inner),
                    jnp.float32),
                f_a=unbiased(hd, "f_a"), f_b=unbiased(inner, "f_b"),
                dt_bias=self.param("dt_bias", _dt_bias_init, (inner,),
                                   jnp.float32),
                a_log=self.param(
                    "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                        key, shape, jnp.float32, 1.0, 16.0)), (heads,)),
                b_proj=unbiased(heads, "b_proj"),
                g_a=unbiased(hd, "g_a"), g_b=unbiased(inner, "g_b"),
                norm_scale=self.param("o_norm", nn.initializers.ones,
                                      (hd,), jnp.float32),
                o_proj=unbiased(cfg.emb_dim, "o_proj"))
        elif self.layer_type == "gdn":
            heads = cfg.gdn_value_heads
            streams = 2 * cfg.gdn_key_inner + cfg.gdn_value_inner
            mixer = lambda h: gdn_mixer(
                cfg, h,
                in_proj=unbiased(streams + cfg.gdn_value_inner, "in_proj"),
                ba_proj=unbiased(2 * heads, "ba_proj"),
                conv_kernel=self.param(
                    "conv_kernel", _conv_init, (cfg.gdn_conv, streams),
                    jnp.float32),
                dt_bias=self.param("dt_bias", nn.initializers.ones,
                                   (heads,), jnp.float32),
                # A = U(0, 16) as the family draws it, kept off 0
                a_log=self.param(
                    "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                        key, shape, jnp.float32, 1e-3, 16.0)), (heads,)),
                norm_scale=self.param("o_norm", nn.initializers.ones,
                                      (cfg.gdn_value_head_dim,),
                                      jnp.float32),
                out_proj=unbiased(cfg.emb_dim, "out_proj"))
        elif self.layer_type == "mla":
            heads = cfg.num_heads
            query = {}
            if cfg.q_lora_rank > 0:
                query = dict(q_a=dense(cfg.q_lora_rank, "q_a"),
                             q_a_norm=_norm(cfg, "q_a_norm"))
            tabs = rope_tabs if cfg.rotates("mla") else None

            def mixer(h):
                return mla_mixer(
                    cfg, h, positions, tabs, **query,
                    q_b=dense(heads * (cfg.qk_nope_head_dim
                                       + cfg.qk_rope_head_dim), "q_b"),
                    kv_a=dense(cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                               "kv_a"),
                    kv_a_norm=_norm(cfg, "kv_a_norm"),
                    kv_b=dense(heads * (cfg.qk_nope_head_dim
                                        + cfg.v_head_dim), "kv_b"),
                    proj=dense(cfg.emb_dim, "proj"))

        else:
            q_dim = cfg.num_heads * cfg.head_dim
            attn = {}
            if self.layer_type == "cross_attention":
                attn["qkv"] = dense(q_dim, "q")
                attn["shared_kv"] = shared_kv
            else:
                attn["qkv"] = dense(
                    (2 if cfg.query_gate else 1) * q_dim + 2 * kv_dim, "qkv")
            attn["proj"] = dense(cfg.emb_dim, "proj")
            if cfg.differential_attention:
                vector = lambda name: self.param(
                    name, nn.initializers.normal(0.1), (cfg.head_dim,),
                    jnp.float32)
                attn["differential"] = dict(
                    lambdas=[vector(name) for name in (
                        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")],
                    subln=nn.RMSNorm(epsilon=cfg.norm_eps,
                                     dtype=jnp.float32, name="subln"),
                    lambda_init=differential_lambda_init(self.layer_index))
            if cfg.qk_norm:
                attn["q_norm"] = _norm(cfg, "q_norm")
                attn["k_norm"] = _norm(cfg, "k_norm")
            if cfg.query_gate:
                attn["query_gate"] = True
            elif cfg.attention_gate:
                attn["gate"] = dense(q_dim, "gate")
            tabs = rope_tabs if cfg.rotates(self.layer_type) else None
            mixer = lambda h: attention_mixer(
                cfg, h, positions, tabs, layer_type=self.layer_type,
                hand_on=self.hand_on, **attn)
        def connection(name):
            """One half's hyper-connection: the parameters of
            ``hyper_connections.coefficients`` under ``<name>_{scale, phi,
            b, alpha}`` and, where the caller asks for the collection
            ``hc_stats``, how far its map was from doubly stochastic.
            Initialised so that every term of the mathematics shows in
            the first step (the paper's small gains and near-identity
            map would let a program without Sinkhorn pass a check):
            gains 1, ``phi`` lecun-normal, the biases normal(0, 1), the
            map's with 2 on its diagonal."""
            from .hyper_connections import (  # noqa: PLC0415
                coefficients, stochastic_err,
            )

            n = cfg.hc_mult
            wide, outs = n * cfg.emb_dim, n * n + 2 * n
            ones = nn.initializers.ones

            def b_init(key, shape, dtype=jnp.float32):
                return jax.random.normal(key, shape, dtype).at[2 * n:].add(
                    2.0 * jnp.eye(n, dtype=dtype).reshape(-1))

            def apply(x):
                pre, post, res = coefficients(
                    x, n,
                    scale=self.param(f"{name}_scale", ones, (wide,),
                                     jnp.float32),
                    phi=self.param(f"{name}_phi",
                                   nn.initializers.lecun_normal(),
                                   (wide, outs), jnp.float32),
                    b=self.param(f"{name}_b", b_init, (outs,), jnp.float32),
                    alpha=self.param(f"{name}_alpha", ones, (3,),
                                     jnp.float32),
                    norm_eps=cfg.norm_eps, iters=cfg.hc_sinkhorn_iters,
                    eps=cfg.hc_eps, clamp=cfg.hc_res_clamp)
                if self.is_mutable_collection("hc_stats"):
                    self.variable("hc_stats", name, lambda: None).value = \
                        stochastic_err(res)
                return pre, post, res

            return apply

        block = {}
        if cfg.hc_mult > 1:
            block["connections"] = (connection("hc_attn"),
                                    connection("hc_mlp"))
        if cfg.post_norms:
            block["post_attn_norm"] = _norm(cfg, "post_attn_norm")
            block["post_mlp_norm"] = _norm(cfg, "post_mlp_norm")
        if self.ffn == "routed" and cfg.routed_router_input == "layer_input":
            block["route"] = lambda x: decide(x.reshape(-1, x.shape[-1]))
        if self.ffn != "none":
            block["mlp"] = mlp
            if mixer is not None:
                block["ln2"] = _norm(cfg, "ln2")
        return block_math(
            cfg, x, ln1=_norm(cfg, "ln1"), mixer=mixer,
            layer_type=self.layer_type, hand_on=self.hand_on, **block,
        )


class MTP(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3's report, section
    2.2): ``[norm(emb(t_{i+1})) ; norm(h_i)]`` through ``eh_proj`` back to
    the stream's width, one more block of the last layer's kind, a final
    norm of its own.  The caller passes the result through the model's
    own head."""

    cfg: TransformerConfig
    block_cls: Any
    layer_type: str
    ffn: str

    @nn.compact
    def __call__(self, h, emb_next, positions, rope_tabs):
        cfg = self.cfg
        both = jnp.concatenate(
            [_norm(cfg, "enorm")(emb_next), _norm(cfg, "hnorm")(h)], axis=-1)
        x = nn.Dense(cfg.emb_dim, dtype=cfg.dtype, use_bias=False,
                     name="eh_proj")(both)
        x = self.block_cls(cfg, self.layer_type, self.ffn, name="block")(
            x, positions, rope_tabs)
        return _norm(cfg, "norm")(x)


class GPT(nn.Module):
    """Decoder-only causal LM.

    ``tokens``: int32 ``[batch, seq]`` (local shard under sequence
    parallelism).  Positions, either/or:

    * ``pos_offset``: global position of ``tokens[:, 0]`` for CONTIGUOUS
      shards — pass ``axis_index(sp_axis) * local_seq`` inside shard_map;
    * ``positions``: explicit int ``[seq]`` global positions — REQUIRED
      (and only supported) non-contiguous layout is the zigzag schedule:
      ``attention_impl="zigzag"`` with positions from
      ``zigzag_positions(axis_index, P, s_local)``.  The position
      *embeddings* (learned gather, RoPE rotation) are layout-agnostic,
      but the flash/reference/ring attention impls mask assuming
      contiguous per-shard rows.

    Under ``cfg.block_diffusion`` ``tokens`` holds a noised copy of
    ``seq // 2`` tokens and then the clean one
    (``models/block_diffusion.py:paired``), the default positions repeat
    (``0 .. seq // 2 - 1`` twice) and the logits are those of the noised
    half alone, ``[batch, seq // 2, vocab]``.

    With ``cfg.hc_mult`` > 1 the residual stream between the blocks is
    that many copies, ``[batch, seq, hc_mult * emb]``: each starts as the
    embedding, the blocks' hyper-connections mix them
    (:func:`block_math`), and their sum is what the final norm reads.

    Returns logits ``[batch, seq, vocab]`` in fp32.  With a
    multi-token-prediction module (``cfg.mtp_modules``) and
    ``next_tokens`` (``tokens`` shifted left by one: position ``i``
    holds token ``i + 1``) it returns the pair ``(logits, mtp_logits)``,
    the second for the token after next, through the same head.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, pos_offset=0, positions=None,
                 next_tokens=None):
        cfg = self.cfg
        wte = nn.Embed(cfg.vocab_size, cfg.emb_dim, dtype=cfg.dtype,
                       name="wte")
        with jax.named_scope(scopes.EMBED):
            if cfg.embed_grad_float32:
                # the module casts the table and then gathers, so a row's
                # gradient would add up in the compute dtype
                tok = jnp.take(wte.embedding, tokens, axis=0).astype(
                    cfg.dtype)
            else:
                tok = wte(tokens)
            if cfg.embedding_multiplier != 1.0:
                tok = tok * cfg.embedding_multiplier
        s = tokens.shape[1]
        if s > cfg.max_len:
            raise ValueError(
                f"sequence length {s} exceeds max_len={cfg.max_len}"
            )
        if positions is None:
            if cfg.attention_impl == "zigzag":
                # contiguous default positions can NEVER match the zigzag
                # layout: silently wrong on every rank — fail at trace time
                raise ValueError(
                    "attention_impl='zigzag' requires explicit positions "
                    "(zigzag_positions(axis_index, P, s_local))"
                )
            positions = pos_offset + jnp.arange(s)
            if cfg.block_diffusion is not None:
                # a noised token and its clean twin stand at one position
                positions = jnp.concatenate([positions[:s // 2]] * 2)
        x = tok
        if cfg.pos_embedding == "learned":
            pos_table = self.param(
                "wpe",
                nn.initializers.normal(0.02),
                (cfg.max_len, cfg.emb_dim),
                jnp.float32,
            )
            # Gather (not dynamic_slice): position layouts need not be
            # contiguous (zigzag shards).  mode="fill" + NaN makes an
            # out-of-range position (e.g. global S > max_len under SP,
            # which the local s<=max_len check can't see) poison the loss
            # LOUDLY instead of silently reusing the clamped last row.
            with jax.named_scope(scopes.EMBED):
                pos = jnp.take(pos_table, positions, axis=0,
                               mode="fill", fill_value=jnp.nan)
                x = x + pos.astype(cfg.dtype)[None]
        rope_tabs = None
        if cfg.pos_embedding == "rope":
            from ..ops.rope import rope_tables  # noqa: PLC0415

            # once for ALL blocks: under remat a per-block recompute would
            # re-run the transcendentals in the backward pass too
            rope_tabs = rope_tables(
                positions, cfg.rope_dim, cfg.rope_theta,
                cfg.rope_scaling and dict(cfg.rope_scaling))
        if cfg.hc_mult > 1:
            from ..obs.registry import get_registry  # noqa: PLC0415

            if cfg.mtp_modules:
                raise ValueError(
                    f"hc_mult={cfg.hc_mult} with mtp_modules="
                    f"{cfg.mtp_modules}: how a prediction module joins a "
                    f"stream of several copies is not implemented; build "
                    f"the model with mtp_modules=0")
            # every stream starts as the embedding; counted while the
            # step is traced
            with jax.named_scope(scopes.EMBED):
                x = jnp.tile(x, (1, 1, cfg.hc_mult))
            for gauge, value in (("streams", cfg.hc_mult),
                                 ("sinkhorn_iters", cfg.hc_sinkhorn_iters),
                                 ("sublayers", 2 * cfg.num_layers)):
                get_registry().gauge(f"hc.{gauge}").set(value)
        block_cls = Block
        if cfg.remat:
            # one policy a block, so that the room the chip has left
            # is spent knowing how many blocks are still to come
            # (block_remat_policy)
            policy_of_block = block_remat_policy(cfg, remat_room(
                cfg, (tokens.shape[0], s), self.variables.get("params")))

            def block_cls(*args, **kwargs):
                return nn.remat(Block, policy=policy_of_block())(
                    *args, **kwargs)
        # what a layer made for later layers, beside the stream
        handed = {"kv": None, "memory": None}
        # counted while the step is traced: the attention layers that
        # have a norm over each head or a rotation between the fused
        # matmul and the attention call, and those of them whose chain
        # takes the kernels of ops/attn_prep.py
        preps = [_attn_prep_plan(
            cfg, s, cfg.num_heads, cfg.kv_heads,
            norm=cfg.norm if cfg.qk_norm else None,
            rotates=cfg.rotates(cfg.layer_type(i)),
            plain=(cfg.layer_type(i) != "cross_attention"
                   and cfg.hands_on(i) is None
                   and not cfg.differential_attention))
            for i in range(cfg.num_layers)
            if cfg.layer_type(i) not in NOT_ATTENTION_MIXER
            and (cfg.qk_norm or cfg.rotates(cfg.layer_type(i)))]
        if preps:
            from ..obs.registry import get_registry  # noqa: PLC0415

            get_registry().gauge("attn_prep.layers").set(len(preps))
            get_registry().gauge("attn_prep.kernel_layers").set(
                sum(tiles is not None for tiles in preps))
        if cfg.layer_types:
            from ..obs.registry import get_registry  # noqa: PLC0415

            # counted while the step is traced: the layers that read
            # each handed-on value (its gradient sums over them)
            for gauge, reader in (("shared.kv_readers", "cross_attention"),
                                  ("shared.memory_readers", "gmu")):
                get_registry().gauge(gauge).set(
                    cfg.layer_types.count(reader))
            convs = cfg.layer_types.count("conv")
            if convs:
                # the gated short convolutions of the program and what
                # their elementwise chains move a step
                get_registry().gauge("short_conv.layers").set(convs)
                get_registry().gauge("short_conv.filter_bytes").set(
                    convs * short_conv_filter_bytes(
                        tokens.shape[0], s, cfg.emb_dim,
                        jnp.dtype(cfg.dtype).itemsize))
            if "mamba" in cfg.layer_types:
                from ..ops import ssm_chain  # noqa: PLC0415
                from ..ops.ssd import kept_mib as ssd_kept_mib  # noqa: PLC0415

                # the Mamba-2 layers of the program and those whose two
                # float32 chains take the kernels (all or none: they
                # share a shape)
                mambas = cfg.layer_types.count("mamba")
                get_registry().gauge("ssm_chain.layers").set(mambas)
                get_registry().gauge("ssm_chain.kernel_layers").set(
                    0 if ssm_chain.plan(s, cfg.ssm_inner, cfg.ssm_groups,
                                        cfg.ssm_state, cfg.ssm_conv) is None
                    else mambas)
                # the scan's groups and chunk, and what one layer's scan
                # keeps for its backward
                get_registry().gauge("ssd.groups").set(cfg.ssm_groups)
                get_registry().gauge("ssd.chunk").set(cfg.ssm_chunk)
                get_registry().gauge("ssd.kept_mib").set(ssd_kept_mib(
                    tokens.shape[0], s, cfg.ssm_heads, cfg.ssm_head_dim,
                    cfg.ssm_state, cfg.ssm_chunk,
                    jnp.dtype(cfg.dtype).itemsize))
            kdas = cfg.layer_types.count("kda")
            if kdas:
                from ..ops import kda, kda_prep  # noqa: PLC0415
                from ..ops.kda import kept_mib  # noqa: PLC0415

                # the delta-rule layers of the program, those whose
                # float32 chain and those whose rule take the kernels
                # (all or none: they share a shape), the chunk their
                # rule runs at and what one layer keeps for its backward
                get_registry().gauge("kda.layers").set(kdas)
                get_registry().gauge("kda.prep_kernel_layers").set(
                    0 if kda_prep.plan(s, cfg.kda_heads, cfg.kda_head_dim,
                                       cfg.kda_conv) is None else kdas)
                get_registry().gauge("kda.kernel_layers").set(
                    0 if kda.plan(s, cfg.kda_heads, cfg.kda_head_dim,
                                  cfg.kda_head_dim, cfg.kda_chunk,
                                  cfg.kda_states_every,
                                  jnp.dtype(cfg.dtype).itemsize) is None
                    else kdas)
                get_registry().gauge("kda.chunk").set(cfg.kda_chunk)
                get_registry().gauge("kda.kept_mib").set(kept_mib(
                    tokens.shape[0], s, cfg.kda_heads, cfg.kda_head_dim,
                    cfg.kda_head_dim, cfg.kda_chunk, cfg.kda_states_every,
                    jnp.dtype(cfg.dtype).itemsize))
            gdns = cfg.layer_types.count("gdn")
            if gdns:
                from ..ops import kda  # noqa: PLC0415

                # the scalar-decay delta-rule layers of the program, those
                # whose rule takes the kernels (all or none: they share a
                # shape; the rule enters them at the value heads' count),
                # the chunk it runs at and what one layer keeps for its
                # backward
                rule = (cfg.gdn_value_heads, cfg.gdn_key_head_dim,
                        cfg.gdn_value_head_dim, cfg.kda_chunk,
                        cfg.kda_states_every, jnp.dtype(cfg.dtype).itemsize)
                get_registry().gauge("gdn.layers").set(gdns)
                get_registry().gauge("gdn.kernel_layers").set(
                    0 if kda.plan(s, *rule) is None else gdns)
                get_registry().gauge("gdn.chunk").set(cfg.kda_chunk)
                get_registry().gauge("gdn.kept_mib").set(
                    kda.kept_mib(tokens.shape[0], s, *rule))
        for i in range(cfg.num_layers):
            kind, hand_on = cfg.layer_type(i), cfg.hands_on(i)
            block = block_cls(cfg, kind, cfg.ffn_type(i), hand_on,
                              cfg.first_layer_index + i, name=f"block{i}")
            if kind == "cross_attention":
                x = block(x, positions, rope_tabs, handed["kv"])
            elif kind == "gmu":
                x = block(x, positions, rope_tabs, None, handed["memory"])
            else:
                x = block(x, positions, rope_tabs)
            if hand_on is not None:
                x, handed[hand_on] = x
        if not cfg.tie_embeddings:
            # one module, so that the prediction module's pass shares it
            untied = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                              use_bias=False, name="head")

        def head(x):
            if cfg.tie_embeddings:
                # one matrix, two uses: it receives both gradients
                logits = jnp.einsum(
                    "bsd,vd->bsv", x.astype(cfg.dtype),
                    wte.embedding.astype(cfg.dtype),
                    preferred_element_type=jnp.float32)
            else:
                logits = untied(x).astype(jnp.float32)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
            return logits

        # Final norm, LM head and the fp32 cast under one scope, like
        # the raw-weights epilogue (tensor_parallel._gpt_head).
        if cfg.block_diffusion is not None:
            # the clean copy's last-layer outputs feed no loss (its keys
            # and values fed the noised rows in every layer)
            x = x[:, :s // 2]
        with jax.named_scope(scopes.HEAD):
            if cfg.hc_mult > 1:
                # the streams' plain sum is what the final norm reads
                x = x.reshape(*x.shape[:2], cfg.hc_mult, cfg.emb_dim).astype(
                    jnp.float32).sum(axis=2).astype(cfg.dtype)
            logits = head(_norm(cfg, "lnf")(x))
        if cfg.mtp_modules == 0 or (next_tokens is None
                                    and not self.is_initializing()):
            return logits
        if next_tokens is None:
            next_tokens = tokens  # initialising: only the shapes matter
        # The prediction module reads the last block's output BEFORE the
        # final norm and the embedding of the next token, and predicts
        # the token after next through the same embedding and head,
        # which so receive both gradients.
        with jax.named_scope(scopes.MTP):
            with jax.named_scope(scopes.EMBED):
                nxt = wte(next_tokens)
                if cfg.embedding_multiplier != 1.0:
                    nxt = nxt * cfg.embedding_multiplier
            last = cfg.num_layers - 1
            x = MTP(cfg, block_cls, cfg.layer_type(last),
                    cfg.ffn_type(last), name="mtp")(
                        x, nxt, positions, rope_tabs)
            with jax.named_scope(scopes.HEAD):
                mtp_logits = head(x)
        return logits, mtp_logits


# The projected peak a rematerialised model may fill its chip to, as a
# share of the limit the device reports: 14.5 of a v5e's 15.75 GiB, what
# the fullest training cell of the benchmark runs at (PERF.md section 3).
REMAT_CEILING = 14.5 / 15.75
# The working set beside what is kept, in the terms of ``remat_room``;
# the three counts are fitted to the peaks of the benchmark's nine
# rematerialised cells (PERF.md section 6 has the table).
REMAT_HEAD_COPIES = 3.0    # of the float32 logits: they, their gradient,
#                            a compute-dtype copy of each
REMAT_BLOCK_COPIES = 2.5   # of the widest block's matmul inputs and
#                            outputs: the recompute's and the cotangents'
REMAT_SLOT_COPIES = 6.0    # of an expert layer's tokens x top_k rows of
#                            the stream: sorted, gathered, weighted, back


# The name the gauges ``remat.kept_values`` / ``remat.kept_mib`` count
# kept matmul outputs under, beside the kernels' ``scopes.KERNEL_OUTPUTS``.
MATMUL_OUT = "matmul"


def device_memory():
    """``(bytes_limit, bytes_in_use)`` of this process's first device as
    its allocator reports them, or None where the backend reports no
    limit (the CPU).  The one reading ``remat_room`` rests on."""
    stats = jax.local_devices()[0].memory_stats() or {}
    if not stats.get("bytes_limit"):
        return None
    return stats["bytes_limit"], stats.get("bytes_in_use", 0)


def remat_room(cfg: TransformerConfig, tokens_shape, params):
    """Bytes that the rematerialised blocks of a model applied to
    ``params`` on ``tokens_shape = (batch, seq)`` may keep beside their
    inputs before the step's projected peak passes ``REMAT_CEILING`` of
    the device's limit; None where the device reports none.  Read when
    the model is traced: the train state is on the chip by then.

        room = ceiling - resident - stream - transient
        ceiling   = REMAT_CEILING x bytes_limit
        resident  = bytes_in_use (parameters, optimizer state, batch)
        stream    = blocks x tokens x stream width: each block's input
        transient = max(head, block) + slots
          head  = REMAT_HEAD_COPIES x tokens x vocabulary x 4
          block = REMAT_BLOCK_COPIES x tokens x the largest sum, over a
                  block's Dense kernels [K, N], of K + N
          slots = REMAT_SLOT_COPIES x tokens x top_k x width, where a
                  layer routes

    The gradients are as large as the parameters, but they grow while
    the kept values are given back, block by block: they count only
    where ``resident + gradients + transient`` alone passes the ceiling
    (no room then).  What the kernels' outputs take is not in the
    formula: ``block_remat_policy`` charges them to the room as they
    come, before any matmul's."""
    memory = device_memory()
    if memory is None or not params:
        return None
    limit, resident = memory
    itemsize = jnp.dtype(cfg.dtype).itemsize
    tokens = tokens_shape[0] * tokens_shape[1]
    blocks = cfg.num_layers + cfg.mtp_modules
    stream = blocks * tokens * cfg.emb_dim * cfg.hc_mult * itemsize
    head_tokens = tokens // 2 if cfg.block_diffusion is not None else tokens
    head = REMAT_HEAD_COPIES * head_tokens * cfg.vocab_size * 4
    widest = max((
        sum(sum(leaf.shape)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if leaf.ndim == 2 and path[-1].key == "kernel")
        for name, tree in params.items()
        if name.startswith("block") or name == "mtp"), default=0)
    block = REMAT_BLOCK_COPIES * tokens * widest * itemsize
    slots = (REMAT_SLOT_COPIES * tokens * cfg.routed_top_k * cfg.emb_dim
             * itemsize)
    transient = max(head, block) + slots
    gradients = sum(leaf.size * leaf.dtype.itemsize
                    for leaf in jax.tree.leaves(params))
    ceiling = REMAT_CEILING * limit
    if resident + gradients + transient > ceiling:
        return 0
    return max(0, int(ceiling - resident - stream - transient))


def block_remat_policy(cfg: TransformerConfig, room=None):
    """What a model's rematerialised blocks keep, as a function that
    makes one block's policy (call it once a block).

    Whatever ``cfg.remat_policy`` keeps; always what a Pallas kernel's
    forward made (``scopes.KERNEL_OUTPUTS``, named in the kernels'
    ``custom_vjp`` forward rules), so the recompute never runs a kernel
    a second time; and, while ``room`` bytes (``remat_room``) last, the
    outputs of the block's matmuls (a ``dot_general`` with no batch
    dimensions: every Dense projection; not the expert layers' grouped
    matmuls, not a kernel's inner products), as the operation wrote
    them, so the recompute does not run those a second time either.
    ``room=None`` (no device limit: the CPU) keeps no matmul output.

    The room is spent in trace order.  A kernel's outputs are charged to
    it as they come (they are kept whatever is left); a matmul's output
    is kept if it fits in what is left after holding back, for every
    block still to come, what the fullest block so far gave its
    kernels' outputs, so that the first blocks do not spend what the
    last ones' kernels need.  A policy is shown one operation at a time
    and cannot weigh a deep contraction against a later one: trace
    order is the order there is.

    The decision is taken when the step is differentiated, so it is
    counted there: gauges ``remat.kept_values{name}`` and
    ``remat.kept_mib{name}`` of the metrics registry hold, by name
    (a kernel output's, or ``matmul``), how many values the blocks of
    the last such trace kept and their size, from the shapes the policy
    was shown.  With a room, ``remat.room_mib{trace}``,
    ``remat.eligible_mib{trace}`` and ``remat.matmul_kept_mib{trace}``
    hold what the formula found, the matmul outputs that could be kept
    and those that were, of the process's ``trace``-th such trace (1 is
    the training step's: the first thing a runner differentiates).  One
    policy maker a model trace: its tally starts at nothing."""
    from ..obs.registry import get_registry  # noqa: PLC0415

    policies = jax.checkpoint_policies
    configured = getattr(policies, cfg.remat_policy)
    is_kernel_output = policies.save_only_these_names(*scopes.KERNEL_OUTPUTS)
    blocks = cfg.num_layers + cfg.mtp_modules
    kept = {}
    plan = {"left": room, "started": 0, "eligible": 0, "kernels": 0,
            "trace": None}

    def count(name, nbytes):
        values, total = kept.get(name, (0, 0))
        kept[name] = values, total = values + 1, total + nbytes
        registry = get_registry()
        registry.gauge("remat.kept_values", name=name).set(values)
        registry.gauge("remat.kept_mib", name=name).set(total / 2 ** 20)

    def publish():
        registry = get_registry()
        if plan["trace"] is None:
            traces = registry.counter("remat.traces")
            traces.inc()
            plan["trace"] = str(traces.value)
            registry.gauge("remat.room_mib", trace=plan["trace"]).set(
                room / 2 ** 20)
        for gauge, nbytes in (
                ("eligible_mib", plan["eligible"]),
                ("matmul_kept_mib", kept.get(MATMUL_OUT, (0, 0))[1])):
            registry.gauge(f"remat.{gauge}", trace=plan["trace"]).set(
                nbytes / 2 ** 20)

    def block_policy():
        started, kernels = False, 0  # this block's outputs of kernels

        def policy(prim, *avals, **params):
            nonlocal started, kernels
            if not started:
                started = True
                plan["started"] += 1
            if is_kernel_output(prim, *avals, **params):
                nbytes = sum(a.size * a.dtype.itemsize for a in avals)
                count(params["name"], nbytes)
                if room is not None:
                    plan["left"] -= nbytes
                    kernels += nbytes
                    plan["kernels"] = max(plan["kernels"], kernels)
                return True
            keep = configured(prim, *avals, **params)
            if (room is None or prim is not jax.lax.dot_general_p
                    or any(params["dimension_numbers"][1])):
                return keep
            out, _ = prim.abstract_eval(*avals, **params)
            nbytes = out.size * out.dtype.itemsize
            plan["eligible"] += nbytes
            held_back = (blocks - plan["started"]) * plan["kernels"]
            if keep or nbytes <= plan["left"] - held_back:
                keep = True
                plan["left"] -= nbytes
                count(MATMUL_OUT, nbytes)
            publish()
            return keep

        return policy

    if not room:
        # nothing to apportion: one policy for every block, as before
        # the rule, so that what the blocks share lowers once
        shared = block_policy()
        return lambda: shared
    return block_policy


# Named sizes (GPT-2 family geometry; head_dim 64, MXU-friendly widths).
GPT_CONFIGS = {
    "nano": TransformerConfig(num_layers=3, num_heads=4, emb_dim=128,
                              max_len=256, vocab_size=1024),
    "small": TransformerConfig(num_layers=12, num_heads=12, emb_dim=768),
    "medium": TransformerConfig(num_layers=24, num_heads=16, emb_dim=1024),
    "large": TransformerConfig(num_layers=36, num_heads=20, emb_dim=1280),
    # https://huggingface.co/ibm-granite/granite-4.0-h-micro config.json
    # (model_type granitemoehybrid, no routed experts): nine Mamba-2
    # layers to one grouped-query attention layer, no positional
    # embedding, a silu-gated feed-forward of 8192 in every layer.
    # Training path only (require_gpt2_block says who refuses it).
    "granite-4.0-h-micro": TransformerConfig(
        vocab_size=100352, num_layers=40, emb_dim=2048, max_len=131072,
        layer_types=tuple("attention" if i % 10 == 5 else "mamba"
                          for i in range(40)),
        num_heads=32, num_kv_heads=8, attention_scale=0.015625,
        pos_embedding="none", mlp_ratio=4, mlp="silu_gated",
        norm="rmsnorm", norm_eps=1e-5, use_bias=False,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, tie_embeddings=True,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv=4, ssm_chunk=256,
        # a Mamba block's matmul outputs are 0.47 GB at 8192 tokens, ten
        # blocks' more than a chip holds beside the train state: keep
        # each block's input, as every policy does what its kernels
        # made (the scan's y and states 128 MiB a block, the attention
        # layer's o and lse 33 MiB), and of the matmul outputs as many
        # blocks' as the chip has room for (block_remat_policy: six of
        # ten at 8192 tokens on a v5e)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/zai-org/GLM-4.7-Flash config.json
    # (model_type glm4_moe_lite): latent attention in every layer, layer
    # 0 a dense gated feed-forward of 10240, the other 46 layers 64
    # routed experts of 1536 (sigmoid scores, 4 a token, weights
    # normalised and scaled 1.8, nothing dropped) beside one shared
    # expert, one multi-token-prediction module, an untied head.
    # Training path only (require_gpt2_block says who refuses it).
    "glm-4.7-flash": TransformerConfig(
        vocab_size=154880, num_layers=47, emb_dim=2048, max_len=202752,
        layer_types=("mla",) * 47, num_heads=20, num_kv_heads=20,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256,
        pos_embedding="rope", rope_theta=1e6,
        mlp_ratio=5, mlp="silu_gated", norm="rmsnorm", norm_eps=1e-5,
        use_bias=False, tie_embeddings=False,
        routed_experts=64, routed_top_k=4, routed_width=1536,
        routed_scaling=1.8, shared_experts=1, dense_layers_first=1,
        mtp_modules=1,
        # 8192 x 5120 queries, keys and values a block: keep each
        # block's input and, as every policy does, what its kernels made
        # (o 80 MiB and lse 0.6 MiB a block: a second flash forward
        # costs 8.5 ms at 8192 tokens)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/arcee-ai/Trinity-Mini config.json
    # (model_type afmoe): 32 query heads over 4 key/value heads of 128
    # (q 4096 wide on a stream of 2048), three sliding_attention layers
    # (window 2048, rotary) to one full_attention layer (no positions),
    # a norm over each head of q and k, a sigmoid output gate, four
    # norms a block, the embedding times sqrt(2048); two dense layers
    # of 6144, then 128 routed experts of 1024 (sigmoid scores, 8 a
    # token, weights normalised and scaled 2.826, nothing dropped)
    # beside one shared expert; an untied head.
    # Training path only (require_gpt2_block says who refuses it).
    "trinity-mini": TransformerConfig(
        vocab_size=200192, num_layers=32, emb_dim=2048, max_len=131072,
        layer_types=tuple(
            "full_attention" if i % 4 == 3 else "sliding_attention"
            for i in range(32)),
        num_heads=32, num_kv_heads=4, head_size=128,
        attention_window=2048, pos_embedding="rope", rope_theta=10000.0,
        rope_layer_types=("sliding_attention",), qk_norm=True,
        attention_gate=True, post_norms=True,
        embedding_multiplier=2048 ** 0.5,
        mlp_ratio=3, mlp="silu_gated", norm="rmsnorm", norm_eps=1e-5,
        use_bias=False, tie_embeddings=False,
        routed_experts=128, routed_top_k=8, routed_width=1024,
        routed_scaling=2.826, shared_experts=1, dense_layers_first=2,
        # 8192 x 5120 queries, keys and values and a 8192 x 4096 gate a
        # block: keep each block's input and, as every policy does, what
        # its kernels made (o 64 MiB and lse 1 MiB a block)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning
    # config.json (model_type phi4flash; arXiv:2507.06607): a self-decoder
    # of Mamba-1 layers (even) and sliding-window attention layers (odd,
    # window 512) up to layer 15, layer 16 the Mamba-1 layer whose scan
    # output is the memory, layer 17 the one full-attention layer whose
    # keys and values are the cache, then a cross-decoder of gated memory
    # units (even) and cross-attention layers (odd) that read those two.
    # Differential attention (40 sub-heads over 20 of 64), LayerNorm,
    # biases on the attention projections only, no positions, a tied
    # head.  Training path only (require_gpt2_block says who refuses it).
    "phi-4-mini-flash-reasoning": TransformerConfig(
        vocab_size=200064, num_layers=32, emb_dim=2560, max_len=262144,
        layer_types=tuple(
            ("selective_scan" if i <= 16 else "gmu") if i % 2 == 0
            else "sliding_attention" if i <= 15
            else "full_attention" if i == 17 else "cross_attention"
            for i in range(32)),
        shared_kv_layer=17, memory_layer=16,
        num_heads=40, num_kv_heads=20, differential_attention=True,
        attention_window=512, pos_embedding="none",
        mlp_ratio=4, mlp="silu_gated", norm="layernorm", norm_eps=1e-5,
        use_bias=True, mlp_bias=False, tie_embeddings=True,
        ssm_width=5120, ssm_state=16, ssm_conv=4, ssm_dt_rank=160,
        # 8192 x 10240 of in_proj and 8192 x 20480 of the feed-forward a
        # block: keep each block's input and, as every policy does, what
        # its kernels made (the scan's y 80 MiB and states 20 MiB, a
        # differential layer's o 160 MiB and lse 2.5 MiB)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
    # config.json (model_name smallthinker_21b_instruct): 28 query heads
    # over 4 key/value heads of 128, layer 0 of every four a
    # full_attention layer without positions, the other three
    # sliding_attention layers (window 4096, rotary, theta 1.5e6); in
    # every layer 64 routed experts of 768, 6 a token, no shared expert,
    # no dense layer; the router reads the layer's input ahead of
    # attention, its weights are a softmax over the six chosen logits
    # (no selection bias), the experts' gate is a ReLU; a load-balance
    # loss keeps the load even (its coefficient is the family's
    # convention, not a published key); an untied head.
    # Training path only (require_gpt2_block says who refuses it).
    "smallthinker-21ba3b-instruct": TransformerConfig(
        vocab_size=151936, num_layers=52, emb_dim=2560, max_len=16384,
        layer_types=tuple(
            "full_attention" if i % 4 == 0 else "sliding_attention"
            for i in range(52)),
        num_heads=28, num_kv_heads=4, head_size=128,
        attention_window=4096, pos_embedding="rope", rope_theta=1.5e6,
        rope_layer_types=("sliding_attention",),
        mlp="silu_gated", norm="rmsnorm", norm_eps=1e-6,
        use_bias=False, tie_embeddings=False,
        routed_experts=64, routed_top_k=6, routed_width=768,
        routed_router_input="layer_input", routed_scores="softmax_chosen",
        routed_activation="relu", routed_balance_coef=0.001,
        # 16384 x 4608 queries, keys and values a block: keep each
        # block's input and, as every policy does, what its kernels made
        # (o 112 MiB and lse 1.75 MiB a block)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/LiquidAI/LFM2-24B-A2B config.json
    # (model_type lfm2_moe): gated short convolutions (three taps, no
    # bias) three to one with grouped-query attention layers (32 query
    # heads over 8 key/value heads of 64, a norm over each head of q and
    # k, rotary theta 1e6) at published indices 2, 6, ..., 38; layers 0
    # and 1 a silu-gated feed-forward of 11776, the other 38 hold 64
    # routed experts of 1536 (sigmoid scores, 4 a token, a selection
    # bias, weights normalised, scaling 1, nothing dropped), no shared
    # expert; a tied head.
    # Training path only (require_gpt2_block says who refuses it).
    "lfm2-24b-a2b": TransformerConfig(
        vocab_size=65536, num_layers=40, emb_dim=2048, max_len=128000,
        layer_types=tuple("full_attention" if i % 4 == 2 else "conv"
                          for i in range(40)),
        num_heads=32, num_kv_heads=8, qk_norm=True,
        pos_embedding="rope", rope_theta=1e6,
        rope_layer_types=("full_attention",), conv_taps=3,
        mlp_width=11776, mlp="silu_gated", norm="rmsnorm", norm_eps=1e-5,
        use_bias=False, tie_embeddings=True,
        routed_experts=64, routed_top_k=4, routed_width=1536,
        routed_scaling=1.0, shared_experts=0, dense_layers_first=2,
        # 32768 x 6144 of in_proj and 32768 x 23552 of the dense
        # feed-forward a block: keep each block's input and, as every
        # policy does, what its kernels made (the attention layer's o
        # 128 MiB and lse 4 MiB)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct
    # config.json (model_type kimi_linear; arXiv:2510.26692): Kimi Delta
    # Attention layers (32 heads of 128, a four-tap filter on q, k and v,
    # a decay per channel of the key) three to one with latent attention
    # layers at published layers 4, 8, ..., 24 and 27 (no query rank, a
    # latent of 512, keys of 128 + 64 over values of 128, NO positions
    # anywhere: the recurrence carries the order); layer 0 a silu-gated
    # feed-forward of 9216, the other 26 hold 256 routed experts of 1024
    # (sigmoid scores, 8 a token, a selection bias, weights normalised
    # and scaled 2.446, nothing dropped) beside one shared expert; an
    # untied head.
    # Training path only (require_gpt2_block says who refuses it).
    "kimi-linear-48b-a3b-instruct": TransformerConfig(
        vocab_size=163840, num_layers=27, emb_dim=2304, max_len=1048576,
        layer_types=tuple("mla" if i % 4 == 3 or i == 26 else "kda"
                          for i in range(27)),
        num_heads=32, num_kv_heads=32, q_lora_rank=0, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kda_heads=32, kda_head_dim=128, kda_conv=4, pos_embedding="none",
        mlp_ratio=4, mlp="silu_gated", norm="rmsnorm", norm_eps=1e-5,
        use_bias=False, tie_embeddings=False,
        routed_experts=256, routed_top_k=8, routed_width=1024,
        routed_scaling=2.446, shared_experts=1, dense_layers_first=1,
        # 16384 x 12288 of q, k and v and three 16384 x 4096 gates a
        # block: keep each block's input and, as every policy does, what
        # its kernels made (the rule's o 128 MiB and states 128 MiB, the
        # latent layer's o 128 MiB and lse 2 MiB)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/JetLM/SDAR-30B-A3B-Chat config.json
    # (model_type sdar_moe; arXiv:2510.06303, trained as a block-diffusion
    # model, arXiv:2503.09573): 32 query heads over 4 key/value heads of
    # 128, a norm over each head of q and k, rotary over all 128 channels
    # (theta 1e6); in every layer 128 routed experts of 768, 8 a token,
    # weights the softmax over the eight chosen logits (norm_topk_prob),
    # a silu gate, no shared expert, no dense layer; an untied head.  The
    # step runs a noised copy beside the clean one under the
    # block-diffusion mask in blocks of 4 (the Chat release's block
    # length; config.json has no key for it) and a load-balance loss
    # whose coefficient config.json does not publish either: 0.1, the
    # one the benchmark's cell was measured at (its configuration file
    # says why not Qwen3-MoE's 0.001).
    # Training path only (require_gpt2_block says who refuses it).
    "sdar-30b-a3b-chat": TransformerConfig(
        vocab_size=151936, num_layers=48, emb_dim=2048, max_len=32768,
        num_heads=32, num_kv_heads=4, head_size=128, qk_norm=True,
        pos_embedding="rope", rope_theta=1e6, block_diffusion=4,
        # the mask token's row is looked up once a masked position
        embed_grad_float32=True,
        mlp_ratio=3, mlp="silu_gated", norm="rmsnorm", norm_eps=1e-6,
        use_bias=False, tie_embeddings=False,
        routed_experts=128, routed_top_k=8, routed_width=768,
        routed_scores="softmax_chosen", routed_balance_coef=0.1,
        # 16384 x 5120 queries, keys and values a block: keep each
        # block's input and, as every policy does, what its kernels made
        # (o 128 MiB and lse 2 MiB a block)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B config.json
    # (model_type xing4_0): DeepSeek-V3's keys (latent attention with a
    # query rank of 768 and a latent of 512, keys of 128 + 64 over values
    # of 128, the 64 rotary channels turned at YaRN's blended frequencies
    # and the softmax scaled by its mscale squared; layers 0 and 1 a
    # silu-gated feed-forward of 9216, the other 38 hold 64 routed experts
    # of 1024, sigmoid scores, 4 a token, a selection bias, weights
    # normalised and scaled 2, beside one shared expert; one prediction
    # module; an untied head) plus manifold-constrained hyper-connections
    # (arXiv:2512.24880): a residual stream of four copies, each half of a
    # block reading and writing it through maps made per token, the 4 x 4
    # one by twenty rounds of Sinkhorn-Knopp.  How the prediction module
    # joins four streams is published nowhere: the model refuses to be
    # traced until ``mtp_modules=0`` overrides it.
    # Training path only (require_gpt2_block says who refuses it).
    "xing4.0-29b-a4b": TransformerConfig(
        vocab_size=131072, num_layers=40, emb_dim=3584, max_len=262144,
        layer_types=("mla",) * 40, num_heads=32, num_kv_heads=32,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        pos_embedding="rope", rope_theta=10000.0,
        rope_scaling=dict(
            type="yarn", factor=64, original_max_position_embeddings=4096,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
        # 192 ** -0.5 times YaRN's (0.1 mscale_all_dim ln 64 + 1) ** 2
        attention_scale=192 ** -0.5 * yarn_mscale(64, 1) ** 2,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_res_clamp=(-30.0, 30.0),
        mlp_width=9216, mlp="silu_gated", norm="rmsnorm", norm_eps=1e-6,
        use_bias=False, tie_embeddings=False,
        routed_experts=64, routed_top_k=4, routed_width=1024,
        routed_scaling=2.0, shared_experts=1, dense_layers_first=2,
        mtp_modules=1,
        # 8192 x 12288 queries, keys and values a block and a stream of
        # 8192 x 14336: keep each block's input and, as every policy
        # does, what its kernels made (o 64 MiB and lse 1 MiB a block)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
    # config.json (model_type nemotron_h; arXiv:2512.20848): 52 layers of
    # ONE half each, ``hybrid_override_pattern`` naming them: ``M`` a
    # Mamba-2 mixer (64 heads of 64, state 128, B and C in 8 groups, the
    # gated norm a group, chunk 128), ``*`` grouped-query attention (32
    # query heads over 2 key/value heads of 128, nothing rotated), ``E``
    # 128 routed experts of 1856 WITHOUT a gate matrix, W_down
    # relu(W_up x)^2 (sigmoid scores, 6 a token, a selection bias,
    # weights normalised and scaled 2.5, nothing dropped), beside a
    # shared expert of the same form, 3712 wide; one RMSNorm a layer, an
    # untied head.
    # Training path only (require_gpt2_block says who refuses it).
    "nvidia-nemotron-3-nano-30b-a3b-bf16": TransformerConfig(
        vocab_size=131072, num_layers=52, emb_dim=2688, max_len=262144,
        layer_types=tuple(
            {"M": "mamba", "*": "full_attention", "E": FEED_FORWARD}[kind]
            for kind in ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                         "EMEMEMEME")),
        num_heads=32, num_kv_heads=2, head_size=128, pos_embedding="none",
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
        ssm_conv=4, ssm_chunk=128,
        mlp_width=1856, mlp="relu2", norm="rmsnorm", norm_eps=1e-5,
        use_bias=False, tie_embeddings=False,
        routed_experts=128, routed_top_k=6, routed_width=1856,
        routed_scaling=2.5, routed_activation="relu2", routed_gated=False,
        shared_experts=1, shared_width=3712,
        # 16384 x 10304 of in_proj a Mamba layer: keep each layer's
        # input and, as every policy does, what its kernels made (the
        # scan's y 128 MiB and states 256 MiB, the attention layer's o
        # 128 MiB and lse 2 MiB)
        remat_policy="nothing_saveable",
    ),
    # https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json
    # (model_type qwen3_next; the rule: arXiv:2412.06464): Gated DeltaNet
    # layers (16 key heads under 32 value heads of 128, one four-tap
    # filter over q, k and v, a decay that is one number a head, the
    # head norm gated by silu(z)) three to one with gated attention
    # layers where (i + 1) % 4 == 0 (16 query heads over 2 key/value
    # heads of 256, the query projection twice as wide: a head's query
    # and its sigmoid output gate; 1 + w norms over each head of q and
    # k; the first 64 of a head's 256 channels rotated, theta 1e7);
    # every norm of the stream scales by 1 + w; in every layer 512
    # routed experts of 512, 10 a token, weights the full softmax
    # renormalised over the ten (norm_topk_prob), beside a shared expert
    # of 512 behind a sigmoid gate of its own; a load-balance loss at
    # the family's router_aux_loss_coef 0.001 (not in config.json); an
    # untied head.  The release's prediction module has no key in
    # config.json and is not built.
    # Training path only (require_gpt2_block says who refuses it).
    "qwen3-next-80b-a3b-instruct": TransformerConfig(
        vocab_size=151936, num_layers=48, emb_dim=2048, max_len=262144,
        layer_types=tuple("full_attention" if (i + 1) % 4 == 0 else "gdn"
                          for i in range(48)),
        num_heads=16, num_kv_heads=2, head_size=256, qk_norm=True,
        attention_gate="query",
        pos_embedding="rope", rope_theta=1e7, partial_rotary_factor=0.25,
        gdn_key_heads=16, gdn_value_heads=32, gdn_key_head_dim=128,
        gdn_value_head_dim=128, gdn_conv=4,
        mlp_width=5120, mlp="silu_gated", norm="rmsnorm", norm_eps=1e-6,
        norm_unit_offset=True, use_bias=False, tie_embeddings=False,
        routed_experts=512, routed_top_k=10, routed_width=512,
        routed_scores="softmax_chosen", routed_balance_coef=0.001,
        shared_experts=1, shared_expert_gate=True,
        # 16384 x 12288 of in_proj a DeltaNet block and 16384 x 9216 of
        # the attention block's projection: keep each block's input and,
        # as every policy does, what its kernels made (the rule's o 128
        # MiB and states 128 MiB, the attention layer's o 128 MiB and
        # lse 1 MiB)
        remat_policy="nothing_saveable",
    ),
}


def gpt(size: str = "small", **overrides) -> GPT:
    """``gpt("small", attention_impl="ring", sp_axis="sp")`` etc."""
    cfg = GPT_CONFIGS[size]
    if overrides:
        cfg = replace(cfg, **overrides)
    return GPT(cfg)
