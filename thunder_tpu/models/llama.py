"""LitGPT-style Llama model family, functional and TPU-first.

Capability analog of the reference's LitGPT config zoo + GPT module
(``thunder/tests/litgpt_model.py:7-118``) re-designed for TPU:

- params are a pytree (nested dicts / list of per-block dicts) of
  ``jax.Array`` — no nn.Module graph, so the forward is a pure function
  that works identically under ``thunder_tpu.jit`` tracing, plain
  ``jax.jit``, and ``pjit`` over a ``jax.sharding.Mesh``;
- rope caches are precomputed host-side and passed as inputs (static
  shapes, no data-dependent control flow inside the traced program);
- GQA (n_query_groups < n_head) is expressed with reshape/expand so XLA
  keeps the attention matmuls MXU-shaped;
- default parameter dtype is bfloat16 (MXU-native), with float32 math in
  the normalization/softmax/loss where precision matters.

Supported architecture knobs mirror the reference zoo: rotary_percentage,
parallel_residual (GPT-NeoX style) vs sequential (Llama style), optional
biases, GQA, shared/untied lm_head, MLP class (GptNeoxMLP/LLaMAMLP).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp

import thunder_tpu.torch as ltorch
from thunder_tpu.observability.events import scope

__all__ = [
    "Config",
    "configs",
    "name_to_config",
    "init_params",
    "build_rope_cache",
    "gpt_forward",
    "gpt_loss",
    "param_count",
]


# the layer kinds of a decoder-hybrid-decoder (SambaY): built in models.generate, for tt.serve
HYBRID_DECODER_KINDS = ("ssm", "sliding_attention", "gmu", "cross_attention")
# the server's alone too: a Mamba-2 (SSD) mixer, and a layer that is its feed-forward alone
SINGLE_SUBLAYER_KINDS = ("mamba2", "mlp")


@dataclass
class Config:
    """Architecture description (reference: litgpt Config; tests/litgpt_model.py:7)."""

    name: str = "tiny-llama-debug"
    block_size: int = 4096
    vocab_size: int = 32000
    padded_vocab_size: int | None = None
    n_layer: int = 16
    n_head: int = 32
    n_embd: int = 4096
    head_size: int | None = None
    n_query_groups: int | None = None  # None → MHA; 1 → MQA; else GQA
    rotary_percentage: float = 1.0
    parallel_residual: bool = False
    bias: bool = False
    norm_eps: float = 1e-5
    intermediate_size: int | None = None
    mlp_class: str = "LLaMAMLP"  # or "GptNeoxMLP" / "GemmaMLP" / "LLaMAMoE"
    norm_class: str = "RMSNorm"  # or "LayerNorm"
    # Gemma style: hidden states scaled by sqrt(n_embd) after the embedding
    scale_embedding: bool = False
    rope_base: int = 10000
    rope_condense_ratio: float = 1.0
    shared_attention_norm: bool = False
    lm_head_bias: bool = False
    tie_embeddings: bool = False
    # GPT-2/nanoGPT style: learned absolute position embeddings (wpe); used
    # with rotary_percentage=0.0 (reference nanogpt_model.py)
    learned_pos_embedding: bool = False
    # MoE (reference: litgpt LLaMAMoE via tests/litgpt_model.py:98-110)
    n_expert: int = 0
    n_expert_per_token: int = 2
    # Mistral-style sliding-window attention: query i attends keys in
    # (i-window, i].  None = full causal.  The fused SDPA prim and the flash
    # kernels band their block iteration, so long-T attention cost scales
    # O(T·window) instead of O(T²)
    sliding_window: int | None = None
    # Llama-3.1-style rope frequency rescaling (hf rope_scaling rope_type=
    # "llama3"): low-frequency components stretch by ``factor``, high-freq
    # stay, mid-band interpolates — long-context finetunes of Llama-3 need
    # this or logits diverge at every position.  None = plain rope.
    # Stored as a sorted (key, value) tuple so configs stay hashable for the
    # compiled-program caches (dicts are normalized in __post_init__)
    rope_scaling_llama3: tuple | dict | None = None
    # Fuse the lm-head matmul into a chunked-vocab cross-entropy (no (N, V)
    # logits in HBM; Liger-class fused_linear_cross_entropy).  Off by default
    # pending an on-TPU A/B against the XLA-fused plain path
    fused_head_ce: bool = False
    # GPT-2 uses the tanh gelu approximation ("gelu_new"); torch/our default
    # is the exact erf form
    gelu_approximate: str = "none"
    # A kind per layer (hf ``layer_types``): "full_attention" (softmax
    # attention, the default for every layer), "linear_attention" (a gated
    # delta-rule mixer, below), "conv" (a gated short convolution, below; in
    # the server alone), or, in the server alone too, the four kinds of a
    # decoder-hybrid-decoder: "ssm" (a selective scan, below),
    # "sliding_attention" (softmax attention over the last ``layer_window``
    # keys: the window is this kind's, where ``sliding_window`` is every
    # layer's), "gmu" (a gated memory unit: the last ssm layer's scan output at
    # the same position, gated) and "cross_attention" (queries alone; keys and
    # values are the last full_attention layer's); and, in the server alone,
    # "mamba2" (a Mamba-2 mixer, below) and "mlp" (the layer is its feed-forward
    # alone, no mixer).  "sliding_attention" also stands beside
    # "full_attention" in an ordinary decoder (no ssm, gmu or cross layer, no
    # differential attention; ``rope_kinds`` below).  A model with an "mlp" layer is made of single
    # sublayers: every layer is ``x + f(norm_1(x))`` with ``f`` the kind's mixer
    # or, for "mlp", the model's ``mlp_class``.  None = all full attention
    layer_types: tuple | None = None
    layer_window: int | None = None
    # "sliding_attention" beside "full_attention" in an ordinary decoder (GQA,
    # q/k norm, any feed-forward; in the server alone): the window kind keeps a
    # ring a request, the other its whole length in blocks.  ``rope_kinds``: the
    # layer kinds whose q and k are rotated (None = every attention layer): a
    # model that rotates in its window layers and not at all in its global
    # ones names ("sliding_attention",)
    rope_kinds: tuple | None = None
    # Differential attention in every attention layer of the model (the cross
    # ones too): query heads in adjacent pairs ``(2j, 2j + 1)``, KV heads in
    # adjacent pairs ``(2g, 2g + 1)``, ``g = j // (n_head / n_query_groups)``;
    # ``o_j = (1 - l0) RMSNorm_{2 hs}((A_1 - lambda A_2) [v_2g | v_2g+1])``,
    # ``A_1 = softmax(q_2j k_2g^T)``, ``A_2 = softmax(q_2j+1 k_2g+1^T)``,
    # ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 layer)``
    diff_attention: bool = False
    # The selective scan of "ssm" layers (Mamba-1): ``[u | z] = x W_in`` (to 2
    # ``ssm_inner``); ``u <- SiLU(conv(u) + b)``, causal and depthwise over
    # ``ssm_conv_kernel`` taps; ``[r | B | C] = u W_x`` (to ``ssm_dt_rank`` + 2
    # ``ssm_state``); ``dt = softplus(r W_dt + b_dt)``; ``S_t = exp(dt_t A) S_t-1 +
    # (dt_t u_t) B_t^T`` with ``A = -exp(A_log)`` (``ssm_inner`` x ``ssm_state``),
    # in float32; ``m_t = S_t C_t + D u_t``; ``(m SiLU(z)) W_out``.  A sequence
    # keeps ``S`` and the conv's last ``ssm_conv_kernel - 1`` inputs.  A "gmu"
    # layer is ``(m SiLU(x W_1)) W_2`` on the last ssm layer's ``m``
    ssm_inner: int = 0
    ssm_state: int = 16
    ssm_dt_rank: int = 0
    ssm_conv_kernel: int = 4
    # The Mamba-2 (SSD) mixer of "mamba2" layers: ``mamba_heads`` heads of
    # ``mamba_head_dim`` channels (``d`` = their product), ``mamba_groups`` groups
    # of heads that share ``B`` and ``C`` (``mamba_state`` numbers each).  ``[z | xBC
    # | dt] = u W_in`` (to ``2 d + 2 G N + H``); ``xBC <- SiLU(conv(xBC) + b)``,
    # causal and depthwise over ``mamba_conv_kernel`` taps; ``dt <- softplus(dt +
    # dt_bias)`` and ``A = -exp(A_log)``, one scalar a head; ``S_t[h] = exp(dt_t[h]
    # A[h]) S_t-1[h] + dt_t[h] x_t[h] B_t[g]^T`` (``mamba_head_dim`` x
    # ``mamba_state``, float32); ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``; ``y <-
    # RMSNorm_group(y SiLU(z)) w`` (the gate before the norm, the norm over a
    # group's ``d / G`` channels); ``y W_out``.  A sequence keeps ``S`` and the
    # conv's last ``mamba_conv_kernel - 1`` inputs
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_groups: int = 1
    mamba_state: int = 128
    mamba_conv_kernel: int = 4
    # The gated short convolution of "conv" layers (hf Lfm2ShortConv): ``[B | C |
    # u] = x W_in``, a causal depthwise conv of ``conv_kernel`` taps (hf
    # ``conv_L_cache``) without bias or activation over ``B * u``, gated by ``C``,
    # then ``W_out``.  A sequence keeps the conv's last ``conv_kernel - 1`` inputs
    conv_kernel: int = 3
    # Gated softmax attention: RMSNorm of q and k a head, and an output gate
    # (``wq`` projects to q and gate; o <- o * sigmoid(gate) before ``wo``)
    qk_norm: bool = False
    attn_output_gate: bool = False
    # RMSNorm weights stored zero-centred: the scale is ``1 + w``
    norm_zero_centered: bool = False
    # The gated delta-rule mixer of "linear_attention" layers: key/value head
    # counts and sizes, and the causal depthwise conv's width
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    # hf ``linear_allow_neg_eigval``: beta = 2 sigmoid(b), in (0, 2), so the
    # transition exp(g) (I - beta k k^T) has the eigenvalue exp(g) (1 - beta) in
    # (-1, 1) along k
    linear_allow_neg_eigval: bool = False
    # OLMo 2/3 blocks: the norms sit on the sublayers' *outputs*
    # (``x + norm_1(mixer(x))``, then ``+ norm_2(mlp(.))``), not their inputs
    post_sublayer_norm: bool = False
    # A norm on both sides of every sublayer (in the server alone): ``x +
    # norm_1_post(mixer(norm_1(x)))``, then ``+ norm_2_post(mlp(norm_2(.)))``
    sandwich_norm: bool = False
    # q/k RMSNorm over the whole projected width (``q_norm (nh * hs)``, ``k_norm
    # (ng * hs)``) before the split into heads (OLMo 2/3); ``qk_norm`` norms a head
    qk_norm_whole: bool = False
    # mlp_class "SparseMoE": softmax router over all ``n_expert``, top
    # ``n_expert_per_token`` renormalised, SwiGLU experts of width
    # ``intermediate_size``.  The layer holds experts ``[expert_first,
    # expert_first + expert_held)`` (None = all of them) and computes their
    # part of the result; a shared expert of width ``shared_expert_size``
    # sits behind a sigmoid gate (0 = none)
    expert_first: int = 0
    expert_held: int | None = None
    shared_expert_size: int = 0
    # The router of a SparseMoE layer.  "softmax" as above; "sigmoid_group"
    # (DeepSeek-V3): float32 sigmoid scores over all experts in ``n_group``
    # groups, a group's score the sum of its two best, the best ``topk_group``
    # groups kept, the top ``n_expert_per_token`` of what is left renormalised
    # and scaled by ``routed_scaling_factor``; "sigmoid_bias" (LFM2): the same
    # scores, the top ``n_expert_per_token`` chosen on ``score + expert_bias`` (a
    # float32 vector a layer), the weights the chosen *scores* renormalised and
    # scaled.  ``shared_expert_gate`` False: the shared expert is added ungated
    moe_router: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    shared_expert_gate: bool = True
    # ``moe_latent_size`` > 0: the routed experts work in a latent of that width
    # (the router still reads the token): one shared down-projection before
    # them (``latent_down``), their weighted sum through one shared
    # up-projection (``latent_up``); the shared expert stays at the model's
    # width.  ``moe_activation`` "relu2": experts and shared expert are ungated,
    # ``W2 relu(W1 x)^2`` (two matrices, no ``fc_2``); "reglu": the gated ReLU,
    # ``W2 (relu(W1 x) * W3 x)``, SwiGLU's three matrices (in the server alone);
    # "swiglu" as above
    moe_latent_size: int = 0
    moe_activation: str = "swiglu"
    # The router of every SparseMoE layer reads the *block's* input (the residual
    # stream before ``norm_1`` and the mixer) where the experts read
    # ``norm_2(x + mixer)``: the choice is known before attention runs
    # (SmallThinker; in the server alone, the pre-norm sequential block alone)
    moe_route_block_input: bool = False
    # The first ``first_k_dense`` layers of a SparseMoE model keep a dense
    # SwiGLU of width ``dense_intermediate_size`` in place of the experts
    first_k_dense: int = 0
    dense_intermediate_size: int | None = None
    # Multi-head latent attention (DeepSeek-V2/V3), on where ``kv_lora_rank``
    # > 0: q through a rank-``q_lora_rank`` bottleneck with an RMSNorm
    # (required), heads of ``qk_nope_head_dim`` unrotated and
    # ``qk_rope_head_dim`` rotated query dims; keys and values expanded a head
    # from one normed latent of ``kv_lora_rank`` a token, beside one rotated key
    # of ``qk_rope_head_dim`` shared by all heads; values of ``v_head_dim``.
    # The cache keeps the latent and the rotated key alone
    # (``latent_width`` numbers a token a layer)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope rescaling (hf rope_scaling type "yarn"): ``factor``,
    # ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    # ``mscale``, ``mscale_all_dim``; stored like ``rope_scaling_llama3``
    rope_scaling_yarn: tuple | dict | None = None
    # Manifold-constrained hyper-connections (arXiv:2512.24880, on arXiv:2409.19606;
    # in the server alone), on where ``hc_mult`` > 1: the residual stream is
    # ``hc_mult`` copies wide, ``X (n, C)`` a token.  A sublayer ``F`` reads ``u =
    # H_pre X`` and writes ``X <- H_res X + H_post^T F(norm(u))``; the three maps are
    # the token's own, from one RMSNorm (eps ``hc_eps``) over the flattened ``n C``:
    # ``H_pre = sigmoid(.)`` and ``H_post = 2 sigmoid(.)`` of ``n`` numbers each,
    # ``H_res (n, n)`` the exponential of ``n^2`` numbers clipped to
    # ``hc_res_clamp``, its columns then its rows normalised ``hc_sinkhorn_iters``
    # times (``models.generate.hc_maps``).  The embedding is copied to the ``n``
    # streams and they are summed before the last norm
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # A looped model (arXiv:2510.25741; in the server alone), on where ``n_pass`` >
    # 1: the stack of ``n_layer`` blocks runs ``n_pass`` times over one set of
    # weights.  The last norm closes *every* pass and its output opens the next;
    # layer ``l`` of pass ``t`` keeps K and V of its own (``kv_slab``: a cache a
    # pass); an exit gate ``sigmoid(w_g . h + b_g)`` reads each pass's closed state
    # and the head reads, a token, the first pass whose cumulative exit
    # probability reaches ``exit_threshold`` (the last pass where none does)
    n_pass: int = 1
    exit_threshold: float = 1.0

    def __post_init__(self):
        if isinstance(self.rope_scaling_llama3, dict):
            self.rope_scaling_llama3 = tuple(sorted(self.rope_scaling_llama3.items()))
        if isinstance(self.rope_scaling_yarn, dict):
            self.rope_scaling_yarn = tuple(sorted(self.rope_scaling_yarn.items()))
        if self.kv_lora_rank:
            assert self.qk_nope_head_dim > 0 and self.qk_rope_head_dim > 0 and self.v_head_dim > 0, (
                "latent attention needs qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
            assert self.q_lora_rank > 0, "latent attention needs q_lora_rank (a single query projection is not built)"
            assert self.qk_rope_head_dim % 2 == 0 and self.sliding_window is None and not self.bias
            assert self.layer_types is None and not (self.qk_norm or self.qk_norm_whole or self.attn_output_gate)
            if self.head_size is None:
                self.head_size = self.qk_nope_head_dim + self.qk_rope_head_dim
            assert self.head_size == self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.padded_vocab_size is None:
            # pad to a multiple of 64 for TPU-friendly gather/matmul tiling
            self.padded_vocab_size = ((self.vocab_size + 63) // 64) * 64
        if self.head_size is None:
            assert self.n_embd % self.n_head == 0
            self.head_size = self.n_embd // self.n_head
        if self.n_query_groups is None:
            self.n_query_groups = self.n_head
        assert self.n_head % self.n_query_groups == 0
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.n_embd
        if self.mlp_class == "LLaMAMoE":
            assert self.n_expert > 0, "LLaMAMoE requires n_expert > 0"
            assert 0 < self.n_expert_per_token <= self.n_expert
            assert not self.bias, "bias is not supported for the MoE MLP"
        if self.mlp_class == "SparseMoE":
            assert 0 < self.n_expert_per_token <= self.n_expert, "SparseMoE requires n_expert > 0"
            if self.expert_held is None:
                self.expert_held = self.n_expert - self.expert_first
            assert 0 <= self.expert_first and 0 < self.expert_held <= self.n_expert - self.expert_first
            assert not self.bias, "bias is not supported for the MoE MLP"
            assert self.moe_router in ("softmax", "sigmoid_group", "sigmoid_bias"), self.moe_router
            if self.moe_router == "sigmoid_group":
                assert self.n_expert % self.n_group == 0 and 0 < self.topk_group <= self.n_group
                assert self.n_expert // self.n_group >= 2, "a group's score is the sum of its two best"
                assert self.n_expert_per_token <= self.topk_group * (self.n_expert // self.n_group)
            if self.first_k_dense and self.dense_intermediate_size is None:
                self.dense_intermediate_size = self.intermediate_size
            assert self.moe_activation in ("swiglu", "relu2", "reglu"), self.moe_activation
            assert self.moe_latent_size >= 0
            assert not self.moe_route_block_input or not (
                self.parallel_residual or self.post_sublayer_norm or self.sandwich_norm or self.hc_mult > 1
                or self.first_k_dense or set(self.layer_types or ()) & set(SINGLE_SUBLAYER_KINDS)), (
                "moe_route_block_input: every layer a pre-norm sequential block with an expert layer")
        else:
            assert not self.first_k_dense, "first_k_dense: the leading dense layers of a SparseMoE model"
            assert not self.moe_latent_size and self.moe_activation == "swiglu" and not self.moe_route_block_input, (
                "moe_latent_size, moe_activation and moe_route_block_input are a SparseMoE layer's")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            assert len(self.layer_types) == self.n_layer, "layer_types needs one kind a layer"
            assert set(self.layer_types) <= {"full_attention", "linear_attention", "conv", *HYBRID_DECODER_KINDS,
                                             *SINGLE_SUBLAYER_KINDS}, self.layer_types
            assert sum(k in self.layer_types for k in ("linear_attention", "conv", "ssm", "mamba2")) <= 1, (
                "linear_attention, conv, ssm and mamba2 layers: a request's state slot holds one kind's arenas")
            if self.hybrid_decoder:
                self._check_hybrid_decoder()
            elif "sliding_attention" in self.layer_types:
                assert set(self.layer_types) <= {"full_attention", "sliding_attention"}, (
                    "sliding_attention outside a decoder-hybrid-decoder stands beside full_attention alone "
                    "(a ring beside another kind's state slot is untested)")
                assert self.layer_window and self.layer_window > 0, "sliding_attention layers need layer_window"
                assert self.sliding_window is None and not self.latent and not self.qk_norm_whole, (
                    "layer_window is the window of sliding_attention layers; sliding_window is model-wide")
            if "mamba2" in self.layer_types:
                H, G = self.mamba_heads, self.mamba_groups
                assert H > 0 and self.mamba_head_dim > 0 and G > 0 and H % G == 0 and self.mamba_state > 0, (
                    "mamba2 layers need mamba_heads (a multiple of mamba_groups), mamba_head_dim and mamba_state")
                assert self.mamba_conv_kernel >= 2, "mamba2 layers need mamba_conv_kernel >= 2 taps"
                assert not self.bias and not self.parallel_residual and not self.post_sublayer_norm, (
                    "mamba2: sequential, bias-free blocks only")
            if "mlp" in self.layer_types:
                assert set(self.layer_types) <= {"full_attention", "mamba2", "mlp"}, (
                    "single-sublayer blocks: a full_attention mixer, a mamba2 mixer or the feed-forward alone")
                assert not (self.bias or self.parallel_residual or self.post_sublayer_norm or self.latent
                            or self.first_k_dense), "single-sublayer blocks: x + f(norm_1(x)), bias-free"
            if "conv" in self.layer_types:
                assert self.conv_kernel >= 2, "conv layers need conv_kernel >= 2 taps"
                assert not self.bias and not self.parallel_residual, "conv: sequential, bias-free blocks only"
            if "linear_attention" in self.layer_types:
                nk, nv = self.linear_num_key_heads, self.linear_num_value_heads
                assert nk > 0 and nv % nk == 0 and self.linear_key_head_dim > 0 and self.linear_value_head_dim > 0, (
                    "linear_attention layers need linear_num_key_heads/_value_heads and their head dims")
                assert not self.bias and not self.parallel_residual, "linear_attention: sequential, bias-free blocks only"
        if self.rope_kinds is not None:
            self.rope_kinds = tuple(self.rope_kinds)
            assert set(self.rope_kinds) <= {"full_attention", "sliding_attention"} and not self.latent, self.rope_kinds
        self.hc_res_clamp = tuple(float(v) for v in self.hc_res_clamp)
        if self.hc_mult > 1:
            assert not (self.parallel_residual or self.post_sublayer_norm or self.sandwich_norm
                        or self.shared_attention_norm or self.single_sublayer or self.hybrid_decoder), (
                "hc_mult > 1: the plain pre-norm block alone, x + f(norm(x)) a sublayer (a hyper-connection "
                "takes the place of each residual sum; no other block layout has one)")
            assert self.hc_sinkhorn_iters >= 1 and len(self.hc_res_clamp) == 2
        if self.sandwich_norm:
            assert not (self.parallel_residual or self.post_sublayer_norm or self.shared_attention_norm or self.bias
                        or self.single_sublayer), "sandwich_norm: sequential bias-free blocks, a norm before and after each sublayer"
        assert self.n_pass >= 1, self.n_pass
        if self.n_pass > 1:
            assert self.layer_types is None and not (self.latent or self.hc_mult > 1 or self.sliding_window
                                                     or self.learned_pos_embedding or self.tie_embeddings), (
                "n_pass > 1: a stack of full_attention blocks over K and V (no layer kinds, latent cache, "
                "hyper-connections, model-wide window, learned positions or tied head has a looped form)")
        assert not (self.qk_norm and self.qk_norm_whole), "qk_norm norms a head, qk_norm_whole the projection: one of them"
        if self.post_sublayer_norm:
            assert not self.parallel_residual and not self.shared_attention_norm, (
                "post_sublayer_norm: sequential blocks with a norm after each sublayer")
        if self.bias:
            assert self.norm_class == "LayerNorm", "bias implies LayerNorm (GPT-2/NeoX style)"
        assert not (self.lm_head_bias and self.fused_head_ce), (
            "fused_head_ce computes logits inside the fused prim and has no "
            "bias input — it would silently drop lm_head_b; disable one of "
            "lm_head_bias / fused_head_ce"
        )

    @property
    def rope_n_elem(self) -> int:
        if self.latent:
            return self.qk_rope_head_dim
        return int(self.rotary_percentage * self.head_size)

    @property
    def latent(self) -> bool:
        """Multi-head latent attention: the cache is one latent a token a layer."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Numbers a token a layer of a latent cache: the normed latent and the one rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        """What attention multiplies its scores by: ``head_size^-1/2``, times
        YaRN's ``mscale^2`` (``0.1 mscale_all_dim ln(factor) + 1``) where set."""
        scale = self.head_size ** -0.5
        yarn = dict(self.rope_scaling_yarn or ())
        if yarn.get("mscale_all_dim"):
            scale *= _yarn_mscale(float(yarn["factor"]), float(yarn["mscale_all_dim"])) ** 2
        return scale

    def _check_hybrid_decoder(self) -> None:
        """What the four kinds of a decoder-hybrid-decoder need of each other."""
        kinds = self.layer_types
        assert self.sliding_window is None, "layer_window is the window of sliding_attention layers; sliding_window is model-wide"
        assert not (self.parallel_residual or self.post_sublayer_norm or self.latent or self.qk_norm
                    or self.qk_norm_whole), "ssm / sliding_attention / gmu / cross_attention: plain sequential blocks"
        if "sliding_attention" in kinds:
            assert self.layer_window and self.layer_window > 0, "sliding_attention layers need layer_window"
        if "ssm" in kinds or "gmu" in kinds:
            assert self.ssm_inner > 0 and self.ssm_state > 0 and self.ssm_dt_rank > 0 and self.ssm_conv_kernel >= 2, (
                "ssm layers need ssm_inner, ssm_state, ssm_dt_rank and ssm_conv_kernel >= 2")
        if "gmu" in kinds:
            assert "ssm" in kinds[:kinds.index("gmu")], "a gmu layer gates the scan output of an ssm layer before it"
        if "cross_attention" in kinds:
            assert "full_attention" in kinds[:kinds.index("cross_attention")], (
                "a cross_attention layer reads the K/V of a full_attention layer before it")
            first = min(kinds.index(k) for k in ("gmu", "cross_attention") if k in kinds)
            assert not set(kinds[first:]) - {"gmu", "cross_attention"}, (
                "the cross half (gmu and cross_attention layers) is the model's last layers")
        if self.diff_attention:
            assert self.n_head % 2 == 0 and self.n_query_groups % 2 == 0 and 2 * self.head_size == 128, (
                "differential attention: head pairs that fill a 128-lane row (heads of 64)")

    def mlp_dense(self, i: int) -> bool:
        """Whether layer ``i`` of a SparseMoE model is one of its leading dense layers."""
        return self.mlp_class == "SparseMoE" and i < self.first_k_dense

    def layer_kind(self, i: int) -> str:
        return "full_attention" if self.layer_types is None else self.layer_types[i]

    @property
    def kv_layers(self) -> tuple:
        """The model layers that keep K and V, in order: the one map from a
        model layer to its layer of the server's K/V cache and arenas
        (``kv_layers.index(i)``).  Every layer of a model without ``layer_types``."""
        return tuple(i for i in range(self.n_layer) if self.layer_kind(i) in ("full_attention", "sliding_attention"))

    def kv_slab(self, t, l):
        """The one map from (pass ``t``, K/V layer ``l``: ``kv_layers.index`` of the model
        layer) to its layer of the server's K/V cache and arenas: a cache a pass,
        the passes one after another.  ``t`` may be a traced value; a one-pass
        model's slab is its layer."""
        return t * len(self.kv_layers) + l

    @property
    def kv_slabs(self) -> int:
        """Layers of the dense K/V cache: one a layer that keeps K and V, a pass."""
        return self.n_pass * len(self.kv_layers)

    @property
    def paged_kv_slabs(self) -> int:
        """Layers of the paged K/V arenas: ``kv_slabs`` less the sliding_attention
        layers, whose K and V live in the ring arenas (no looped model has one)."""
        return self.kv_slabs - len(self.ring_layers)

    @property
    def ring_layers(self) -> tuple:
        """The sliding_attention layers, in order: their K/V live in a ring of
        ``layer_window`` tokens (and a block of slack) a request, whatever its
        length (``ring_layers.index(i)`` is the layer of the ring arenas)."""
        return tuple(i for i in range(self.n_layer) if self.layer_kind(i) == "sliding_attention")

    @property
    def paged_kv_layers(self) -> tuple:
        """The layers whose K/V fill a request's block table, a block every
        ``block_size`` tokens of its whole length: ``kv_layers`` less ``ring_layers``."""
        return tuple(i for i in self.kv_layers if self.layer_kind(i) == "full_attention")

    @property
    def ssm_layers(self) -> tuple:
        """The selective-scan layers, in order (``ssm_layers.index(i)`` is the layer of the ssm and conv arenas)."""
        return tuple(i for i in range(self.n_layer) if self.layer_kind(i) == "ssm")

    @property
    def mamba2_layers(self) -> tuple:
        """The Mamba-2 layers, in order (``mamba2_layers.index(i)`` is the layer of the state and conv arenas)."""
        return tuple(i for i in range(self.n_layer) if self.layer_kind(i) == "mamba2")

    @property
    def mamba_inner(self) -> int:
        """Channels of a Mamba-2 layer's ``x``, ``z`` and ``y``: heads times their size."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_width(self) -> int:
        """Channels of a Mamba-2 layer's conv: ``[x | B | C]``."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def single_sublayer(self) -> bool:
        """Every layer is one sublayer, ``x + f(norm_1(x))``: the model has layers
        that are their feed-forward alone ("mlp"), so its mixer layers have none."""
        return "mlp" in (self.layer_types or ())

    @property
    def hybrid_decoder(self) -> bool:
        """A decoder-hybrid-decoder: an ssm, gmu or cross_attention layer, or
        differential attention (a sliding_attention layer alone is an ordinary
        decoder's window kind).  The server keeps such a model's caches a layer
        kind and runs it through the paged decode program and whole-prompt
        prefills alone, as it does any model with ``ring_layers``."""
        return bool(set(self.layer_types or ()) & {"ssm", "gmu", "cross_attention"}) or self.diff_attention

    def rotates(self, i: int) -> bool:
        """Whether layer ``i`` rotates its q and k (``rope_kinds``; every attention layer where None)."""
        return self.rope_n_elem > 0 and (self.rope_kinds is None or self.layer_kind(i) in self.rope_kinds)

    @property
    def keeps_slot(self) -> bool:
        """A request leases a slot of the server's state pool: for a state or a
        tail (``state_layers``) or for its window layers' rings (``ring_layers``)."""
        return bool(self.state_layers or self.ring_layers)

    @property
    def cross_from(self) -> int | None:
        """The layer whose K/V the cross_attention layers read (the last
        full_attention layer before them), or None.  From this layer on a
        prompt needs one row alone: the layer's own K/V on every position, and
        its query, the layers after it and the head on the row that is sampled."""
        kinds = self.layer_types or ()
        if "cross_attention" not in kinds:
            return None
        return max(i for i in range(kinds.index("cross_attention")) if kinds[i] == "full_attention")

    @property
    def gmu_source(self) -> int | None:
        """The ssm layer whose scan output the gmu layers gate: the last before them."""
        kinds = self.layer_types or ()
        if "gmu" not in kinds:
            return None
        return max(i for i in range(kinds.index("gmu")) if kinds[i] == "ssm")

    @property
    def linear_layers(self) -> tuple:
        """The model layers that keep a recurrent state and a conv tail, in
        order (``linear_layers.index(i)`` is the layer of the state arena)."""
        return tuple(i for i in range(self.n_layer) if self.layer_kind(i) == "linear_attention")

    @property
    def conv_layers(self) -> tuple:
        """The model layers that keep a short convolution's tail and nothing
        else, in order (``conv_layers.index(i)`` is the layer of the conv arena)."""
        return tuple(i for i in range(self.n_layer) if self.layer_kind(i) == "conv")

    @property
    def state_layers(self) -> tuple:
        """The layers that keep something a request beside K and V (a slot of
        the server's state pool): the linear_attention, the conv, the ssm or the mamba2 layers."""
        return self.linear_layers or self.conv_layers or self.ssm_layers or self.mamba2_layers

    @property
    def linear_qkv_width(self) -> int:
        """Channels of a linear_attention layer's conv: q and k a key head, v a value head."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def training_only(self) -> str | None:
        """Why ``models.generate`` and ``tt.serve`` cannot run this config, or
        None: the server's norms have no zero-centred weights."""
        if self.norm_zero_centered:
            return "it sets norm_zero_centered (the serving forward's norms have no such form)"
        return None

    @classmethod
    def from_name(cls, name: str, **overrides) -> "Config":
        cfg = name_to_config[name]
        if not overrides:
            return cfg
        # rebuild so derived fields recompute when their source fields are
        # overridden — but only those whose stored value matches what
        # derivation produced (an explicitly-configured value, e.g. 70B's
        # n_query_groups=8, is never silently discarded)
        base = {f: getattr(cfg, f) for f in cfg.__dataclass_fields__}
        was_derived = {
            "padded_vocab_size": cfg.padded_vocab_size == ((cfg.vocab_size + 63) // 64) * 64,
            "head_size": cfg.head_size == cfg.n_embd // cfg.n_head,
            "n_query_groups": cfg.n_query_groups == cfg.n_head,
        }
        derived_sources = {
            "padded_vocab_size": ("vocab_size",),
            "head_size": ("n_embd", "n_head"),
            "n_query_groups": ("n_head",),
        }
        for derived, sources in derived_sources.items():
            if derived not in overrides and was_derived[derived] and any(s in overrides for s in sources):
                base[derived] = None
        base.update(overrides)
        return cls(**base)


# Public architecture hyperparameters (same zoo coverage as the reference's
# tests/litgpt_model.py: llama1/2, long-context variant, plus debug sizes).
configs: list[Config] = [
    Config(name="tiny-llama-debug", block_size=128, vocab_size=256, n_layer=2, n_head=4,
           n_embd=64, n_query_groups=2, intermediate_size=176),
    Config(name="llama1-like", block_size=2048, vocab_size=32000, n_layer=32, n_head=32,
           n_embd=4096, intermediate_size=11008),
    Config(name="long-context-like", block_size=32768, vocab_size=32000, n_layer=32,
           n_head=32, n_embd=4096, intermediate_size=11008, rope_condense_ratio=4.0),
    Config(name="llama2-like", block_size=4096, vocab_size=32000, n_layer=32, n_head=32,
           n_embd=4096, intermediate_size=11008),
    Config(name="Llama-2-7b-hf", block_size=4096, vocab_size=32000, n_layer=32, n_head=32,
           n_embd=4096, intermediate_size=11008),
    Config(name="Llama-2-13b-hf", block_size=4096, vocab_size=32000, n_layer=40, n_head=40,
           n_embd=5120, intermediate_size=13824),
    Config(name="Llama-2-70b-hf", block_size=4096, vocab_size=32000, n_layer=80, n_head=64,
           n_embd=8192, n_query_groups=8, intermediate_size=28672),
    Config(name="Llama-3-8B", block_size=8192, vocab_size=128000, padded_vocab_size=128256,
           n_layer=32, n_head=32, n_embd=4096, n_query_groups=8, rope_base=500000,
           intermediate_size=14336),
    Config(name="CodeLlama-2-like", block_size=16384, vocab_size=32016, n_layer=32,
           n_head=32, n_embd=4096, intermediate_size=11008, rope_base=1000000),
    # bias=True + tanh gelu: the REAL nanoGPT/GPT-2 architecture (reference
    # nanogpt_model.py defaults bias=True) — checkpoint-compatible with
    # models/hf_weights.from_gpt2_state_dict
    Config(name="nanogpt-debug", block_size=128, vocab_size=256, n_layer=2, n_head=4,
           n_embd=64, rotary_percentage=0.0, learned_pos_embedding=True,
           parallel_residual=False, norm_class="LayerNorm", mlp_class="GptNeoxMLP",
           tie_embeddings=True, bias=True, gelu_approximate="tanh"),
    Config(name="gpt2-124m", block_size=1024, vocab_size=50257, n_layer=12, n_head=12,
           n_embd=768, rotary_percentage=0.0, learned_pos_embedding=True,
           norm_class="LayerNorm", mlp_class="GptNeoxMLP", tie_embeddings=True,
           bias=True, gelu_approximate="tanh"),
    # Gemma family: gelu-gated MLP, tied embeddings, sqrt(d) embedding scale
    Config(name="tiny-gemma-debug", block_size=128, vocab_size=256, n_layer=2, n_head=4,
           n_embd=64, intermediate_size=176, mlp_class="GemmaMLP", gelu_approximate="tanh",
           tie_embeddings=True, scale_embedding=True),
    Config(name="Gemma-7b-like", block_size=8192, vocab_size=256000, n_layer=28, n_head=16,
           n_embd=3072, head_size=256, intermediate_size=24576, mlp_class="GemmaMLP",
           gelu_approximate="tanh", tie_embeddings=True, scale_embedding=True),
    # Falcon family: MQA, parallel residual with one shared attention norm
    Config(name="tiny-falcon-debug", block_size=128, vocab_size=256, n_layer=2, n_head=4,
           n_embd=64, n_query_groups=1, intermediate_size=256, parallel_residual=True,
           shared_attention_norm=True, norm_class="LayerNorm", mlp_class="GptNeoxMLP"),
    Config(name="Falcon-7b-like", block_size=2048, vocab_size=65024, n_layer=32, n_head=71,
           n_embd=4544, n_query_groups=1, intermediate_size=18176, parallel_residual=True,
           shared_attention_norm=True, norm_class="LayerNorm", mlp_class="GptNeoxMLP"),
    # Pythia / GPT-NeoX family: parallel residual, biased LayerNorm+linears,
    # partial rotary
    Config(name="tiny-pythia-debug", block_size=128, vocab_size=256, n_layer=2, n_head=4,
           n_embd=64, intermediate_size=256, parallel_residual=True, norm_class="LayerNorm",
           mlp_class="GptNeoxMLP", bias=True, rotary_percentage=0.25),
    Config(name="Pythia-6.9b-like", block_size=2048, vocab_size=50254, n_layer=32, n_head=32,
           n_embd=4096, intermediate_size=16384, parallel_residual=True, norm_class="LayerNorm",
           mlp_class="GptNeoxMLP", bias=True, rotary_percentage=0.25),
    Config(name="tiny-mistral-debug", block_size=128, vocab_size=256, n_layer=2, n_head=4,
           n_embd=64, n_query_groups=2, intermediate_size=176, sliding_window=32),
    Config(name="Mistral-7B-like", block_size=32768, vocab_size=32000, n_layer=32,
           n_head=32, n_embd=4096, n_query_groups=8, intermediate_size=14336,
           sliding_window=4096),
    Config(name="tiny-moe-debug", block_size=128, vocab_size=256, n_layer=2, n_head=4,
           n_embd=64, n_query_groups=2, intermediate_size=96, mlp_class="LLaMAMoE",
           n_expert=4, n_expert_per_token=2),
    Config(name="mixtral-like", block_size=512, vocab_size=500, n_layer=2, n_head=64,
           n_embd=256, n_query_groups=8, intermediate_size=224, rope_base=1000000,
           mlp_class="LLaMAMoE", n_expert=8, n_expert_per_token=2),
    Config(name="Mixtral-8x7B-like", block_size=32768, vocab_size=32000, n_layer=32,
           n_head=32, n_embd=4096, n_query_groups=8, intermediate_size=14336,
           rope_base=1000000, mlp_class="LLaMAMoE", n_expert=8, n_expert_per_token=2),
    # a looped model (hf ByteDance/Ouro-2.6B config.json): 48 blocks with a norm on both sides of
    # each sublayer, run total_ut_steps = 4 times over one set of weights, a K/V cache a pass, an
    # exit gate on each pass's closed state (early_exit_threshold 1: the last pass unless a gate saturates)
    Config(name="Ouro-2.6B", block_size=65536, vocab_size=49152, padded_vocab_size=49152, n_layer=48, n_head=16,
           n_embd=2048, head_size=128, n_query_groups=16, intermediate_size=5632, norm_eps=1e-6,
           rope_base=1000000, sandwich_norm=True, n_pass=4, exit_threshold=1.0),
]
name_to_config: dict[str, Config] = {c.name: c for c in configs}


#
# Parameter initialization (host-side, pure JAX — runs outside tracing)
#


def _inv_softplus(y):
    """``x`` with ``softplus(x) = y``, for ``y > 0``."""
    return y + jnp.log(-jnp.expm1(-y))


def init_params(config: Config, key: jax.Array | None = None, dtype=jnp.bfloat16) -> dict:
    """Builds the params pytree.  Layout (per block):
    attn: qkv packed as separate wq/wk/wv + wo; mlp: fc_1 (gate), fc_2 (up),
    proj (down) for LLaMAMLP, fc/proj for GptNeoxMLP."""
    if key is None:
        key = jax.random.PRNGKey(0)
    hs, nh, ng = config.head_size, config.n_head, config.n_query_groups
    std = 0.02

    def dense(key, fan_in, fan_out):
        return (jax.random.normal(key, (fan_out, fan_in), dtype=jnp.float32) * std).astype(dtype)

    n_keys = 3 + config.n_layer * (5 + 3 * max(1, config.n_expert) + (8 if config.mlp_class == "SparseMoE" else 0)
                                   + (2 if config.latent else 0) + (2 if config.hc_mult > 1 else 0))
    keys = iter(jax.random.split(key, n_keys))

    def zeros(n):
        return jnp.zeros((n,), dtype=dtype)

    params: dict[str, Any] = {
        "wte": (jax.random.normal(next(keys), (config.padded_vocab_size, config.n_embd),
                                  dtype=jnp.float32) * std).astype(dtype),
        "blocks": [],
        "ln_f": jnp.ones((config.n_embd,), dtype=dtype),
    }
    if config.bias:
        params["ln_f_b"] = zeros(config.n_embd)
    if config.lm_head_bias:
        params["lm_head_b"] = zeros(config.padded_vocab_size)
    if not config.tie_embeddings:
        params["lm_head"] = dense(next(keys), config.n_embd, config.padded_vocab_size)
    if config.learned_pos_embedding:
        params["wpe"] = (jax.random.normal(next(keys), (config.block_size, config.n_embd),
                                           dtype=jnp.float32) * std).astype(dtype)

    if config.n_pass > 1:       # the exit gate on a pass's closed state: Linear(n_embd, 1) with a bias
        params["exit_gate"] = {"w": dense(jax.random.fold_in(key, config.n_pass), config.n_embd, 1)[0],
                               "b": jnp.zeros((), dtype=dtype)}
    # a zero-centred norm weight starts at 0 (scale 1 + w = 1)
    norm_init = jnp.zeros if config.norm_zero_centered else jnp.ones
    if config.norm_zero_centered:
        params["ln_f"] = norm_init((config.n_embd,), dtype=dtype)

    for i in range(config.n_layer):
        block = {"norm_1": norm_init((config.n_embd,), dtype=dtype)}
        if config.layer_kind(i) == "linear_attention":
            nk, nv = config.linear_num_key_heads, config.linear_num_value_heads
            dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
            block["gdn"] = {
                "in_proj_qkvz": dense(next(keys), config.n_embd, 2 * nk * dk + 2 * nv * dv),
                "in_proj_ba": dense(next(keys), config.n_embd, 2 * nv),
                "conv_w": dense(next(keys), config.linear_conv_kernel, 2 * nk * dk + nv * dv),
                "A_log": jnp.log(jax.random.uniform(next(keys), (nv,), jnp.float32, 1e-3, 16.0)).astype(dtype),
                "dt_bias": jnp.ones((nv,), dtype=dtype),
                "norm": jnp.ones((dv,), dtype=dtype),
                "out_proj": dense(next(keys), nv * dv, config.n_embd),
            }
        elif config.layer_kind(i) == "conv":
            C = config.n_embd
            block["conv"] = {
                "in_proj": dense(next(keys), C, 3 * C),           # [B | C | u]
                "conv_w": dense(next(keys), config.conv_kernel, C),
                "out_proj": dense(next(keys), C, C),
            }
        elif config.layer_kind(i) == "ssm":
            C, d, N, R = config.n_embd, config.ssm_inner, config.ssm_state, config.ssm_dt_rank
            block["ssm"] = {
                "in_proj": dense(next(keys), C, 2 * d),             # [u | z]
                "conv_w": dense(next(keys), config.ssm_conv_kernel, d),
                "conv_b": zeros(d),
                "x_proj": dense(next(keys), d, R + 2 * N),           # [r | B | C]
                "dt_proj": dense(next(keys), R, d),
                # as the layer that trains these models starts them: dt log-uniform
                # in [1e-3, 0.1] through the softplus, A = -(1 .. N), D = 1
                "dt_bias": _inv_softplus(jnp.exp(jnp.linspace(math.log(1e-3), math.log(0.1), d))).astype(jnp.float32),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (d, N)),
                "D": jnp.ones((d,), jnp.float32),
                "out_proj": dense(next(keys), d, C),
            }
        elif config.layer_kind(i) == "gmu":
            block["gmu"] = {"in_proj": dense(next(keys), config.n_embd, config.ssm_inner),
                            "out_proj": dense(next(keys), config.ssm_inner, config.n_embd)}
        elif config.layer_kind(i) == "mamba2":
            C, d, H = config.n_embd, config.mamba_inner, config.mamba_heads
            block["mamba2"] = {
                "in_proj": dense(next(keys), C, d + config.mamba_conv_width + H),       # [z | x B C | dt]
                "conv_w": dense(next(keys), config.mamba_conv_kernel, config.mamba_conv_width),
                "conv_b": zeros(config.mamba_conv_width),
                # as the layer that trains these models starts them: dt log-uniform in
                # [1e-3, 0.1] through the softplus, A = -(1 .. 16) a head, D = 1
                "dt_bias": _inv_softplus(jnp.exp(jnp.linspace(math.log(1e-3), math.log(0.1), H))).astype(jnp.float32),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
                "D": jnp.ones((H,), jnp.float32),
                "norm": jnp.ones((d,), dtype=dtype),
                "out_proj": dense(next(keys), d, C),
            }
        elif config.layer_kind(i) == "mlp":
            pass                                                # the layer is its feed-forward alone
        elif config.latent:
            dc, dr, rq = config.kv_lora_rank, config.qk_rope_head_dim, config.q_lora_rank
            block["attn"] = {
                "wkv_a": dense(next(keys), config.n_embd, dc + dr),
                "kv_norm": jnp.ones((dc,), dtype=dtype),
                "wkv_b": dense(next(keys), dc, nh * (config.qk_nope_head_dim + config.v_head_dim)),
                "wo": dense(next(keys), nh * config.v_head_dim, config.n_embd),
                "wq_a": dense(next(keys), config.n_embd, rq),
                "q_norm": jnp.ones((rq,), dtype=dtype),
                "wq_b": dense(next(keys), rq, nh * hs),
            }
        else:
            block["attn"] = {"wq": dense(next(keys), config.n_embd, nh * hs * (2 if config.attn_output_gate else 1))}
            if config.layer_kind(i) != "cross_attention":       # a cross layer projects its queries alone
                block["attn"].update(wk=dense(next(keys), config.n_embd, ng * hs),
                                     wv=dense(next(keys), config.n_embd, ng * hs))
            block["attn"]["wo"] = dense(next(keys), nh * hs, config.n_embd)
            if config.diff_attention:
                lam = jax.random.normal(next(keys), (4, hs), jnp.float32) * 0.1
                block["attn"].update(lambda_q1=lam[0], lambda_k1=lam[1], lambda_q2=lam[2], lambda_k2=lam[3],
                                     subln=jnp.ones((2 * hs,), dtype=dtype))
            if config.qk_norm:
                block["attn"].update(q_norm=norm_init((hs,), dtype=dtype), k_norm=norm_init((hs,), dtype=dtype))
            if config.qk_norm_whole:
                block["attn"].update(q_norm=norm_init((nh * hs,), dtype=dtype), k_norm=norm_init((ng * hs,), dtype=dtype))
        if config.bias:     # never beside a linear_attention or conv layer (Config refuses)
            block["norm_1_b"] = zeros(config.n_embd)
            if "attn" in block:
                block["attn"].update(bq=zeros(nh * hs), bo=zeros(config.n_embd))
                if "wk" in block["attn"]:
                    block["attn"].update(bk=zeros(ng * hs), bv=zeros(ng * hs))
        if config.single_sublayer and config.layer_kind(i) != "mlp":
            params["blocks"].append(block)                      # a mixer alone: no second norm, no feed-forward
            continue
        if config.hc_mult > 1:          # a hyper-connection a sublayer: the mixer's and the feed-forward's
            n, nC = config.hc_mult, config.hc_mult * config.n_embd
            for name in ("hc_1", "hc_2"):
                block[name] = {
                    # rows [pre (n) | post (n) | res (n * n, to stream i from stream j at i * n + j)] over vec(X)
                    "phi": dense(next(keys), nC, n * (n + 2)),
                    "norm": jnp.ones((nC,), dtype=dtype),
                    # as the paper starts them: the token's own part small, the stream passed on nearly as it is
                    "alpha": jnp.full((3,), 0.01, jnp.float32),
                    "bias": jnp.concatenate([jnp.zeros((2 * n,)), 4.0 * jnp.eye(n).reshape(-1)]).astype(jnp.float32),
                }
        if config.sandwich_norm:        # the norms on what the two sublayers give
            block.update(norm_1_post=norm_init((config.n_embd,), dtype=dtype),
                         norm_2_post=norm_init((config.n_embd,), dtype=dtype))
        if not config.shared_attention_norm and not config.single_sublayer:
            block["norm_2"] = norm_init((config.n_embd,), dtype=dtype)
            if config.bias:
                block["norm_2_b"] = zeros(config.n_embd)
        if config.mlp_class == "LLaMAMoE":
            # experts stacked on a leading E dim: one array per weight kind, so
            # expert parallelism is a dim-0 sharding and the per-expert slices
            # stay MXU-shaped matmuls
            E = config.n_expert

            def stacked(fan_in, fan_out):
                ws = [dense(next(keys), fan_in, fan_out) for _ in range(E)]
                return jnp.stack(ws, axis=0)

            block["mlp"] = {
                "gate": dense(next(keys), config.n_embd, E),
                "fc_1": stacked(config.n_embd, config.intermediate_size),
                "fc_2": stacked(config.n_embd, config.intermediate_size),
                "proj": stacked(config.intermediate_size, config.n_embd),
            }
        elif config.mlp_dense(i):
            Id = config.dense_intermediate_size
            block["mlp"] = {"fc_1": dense(next(keys), config.n_embd, Id), "fc_2": dense(next(keys), config.n_embd, Id),
                            "proj": dense(next(keys), Id, config.n_embd)}
        elif config.mlp_class == "SparseMoE":
            # held experts stacked and flattened to two dims, "x @ W" layout:
            # fc_1/fc_2 (held * C, I), proj (held * I, C); the grouped
            # products view them as (held, C, I) and (held, I, C)
            # (a latent share: C is the latent's width, and two shared projections stand around the experts)
            Eh, I, C = config.expert_held, config.intermediate_size, config.n_embd
            Cx = config.moe_latent_size or C
            gated = config.moe_activation != "relu2"            # "relu2": two matrices an expert, no fc_2
            block["mlp"] = {
                "gate": dense(next(keys), C, config.n_expert),
                "fc_1": dense(next(keys), I, Eh * Cx),
                **({"fc_2": dense(next(keys), I, Eh * Cx)} if gated else {}),
                "proj": dense(next(keys), Cx, Eh * I),
            }
            if config.moe_latent_size:
                block["mlp"].update(latent_down=dense(next(keys), C, Cx), latent_up=dense(next(keys), Cx, C))
            if config.moe_router == "sigmoid_bias":     # hf starts it at zero; it takes no gradient
                block["mlp"]["expert_bias"] = jnp.zeros((config.n_expert,), jnp.float32)
            if config.shared_expert_size:
                Is = config.shared_expert_size
                block["mlp"]["shared"] = {
                    "fc_1": dense(next(keys), C, Is), **({"fc_2": dense(next(keys), C, Is)} if gated else {}),
                    "proj": dense(next(keys), Is, C),
                }
                if config.shared_expert_gate:
                    block["mlp"]["shared"]["gate"] = dense(next(keys), C, 1)
        elif config.mlp_class in ("LLaMAMLP", "GemmaMLP"):
            block["mlp"] = {
                "fc_1": dense(next(keys), config.n_embd, config.intermediate_size),
                "fc_2": dense(next(keys), config.n_embd, config.intermediate_size),
                "proj": dense(next(keys), config.intermediate_size, config.n_embd),
            }
            if config.bias:
                block["mlp"].update(
                    fc_1_b=zeros(config.intermediate_size),
                    fc_2_b=zeros(config.intermediate_size),
                    proj_b=zeros(config.n_embd),
                )
        else:  # GptNeoxMLP
            block["mlp"] = {
                "fc": dense(next(keys), config.n_embd, config.intermediate_size),
                "proj": dense(next(keys), config.intermediate_size, config.n_embd),
            }
            if config.bias:
                block["mlp"].update(
                    fc_b=zeros(config.intermediate_size), proj_b=zeros(config.n_embd)
                )
        params["blocks"].append(block)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def _llama3_rescale_freqs(theta: jax.Array, params: dict) -> jax.Array:
    """Llama-3.1 rope rescaling (matches HF ROPE_INIT_FUNCTIONS["llama3"]):
    wavelengths longer than ``original_max_position_embeddings /
    low_freq_factor`` divide by ``factor``; shorter than ``.../
    high_freq_factor`` stay; the band between interpolates smoothly."""
    import math as _math

    factor = float(params["factor"])
    low = float(params.get("low_freq_factor", 1.0))
    high = float(params.get("high_freq_factor", 4.0))
    orig = float(params.get("original_max_position_embeddings", 8192))
    wavelen = 2 * _math.pi / theta
    smooth = (orig / wavelen - low) / (high - low)
    scaled = jnp.where(
        wavelen > orig / low,   # low-frequency: full stretch
        theta / factor,
        jnp.where(
            wavelen < orig / high,  # high-frequency: untouched
            theta,
            (1 - smooth) * theta / factor + smooth * theta,
        ),
    )
    return scaled


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_rescale_freqs(theta: jax.Array, n_elem: int, base: float, params: dict) -> jax.Array:
    """YaRN (hf ``_compute_yarn_parameters``): the dims that turn more than
    ``beta_fast`` times over the original context keep their frequency, those
    that turn fewer than ``beta_slow`` times divide it by ``factor``, a linear
    ramp over the dims between.  ``dim(b) = n ln(orig / (2 pi b)) / (2 ln base)``."""
    factor = float(params["factor"])
    orig = float(params["original_max_position_embeddings"])

    def dim(turns):
        return n_elem * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim(float(params.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim(float(params.get("beta_slow", 1)))), n_elem - 1)
    ramp = jnp.clip((jnp.arange(n_elem // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return theta * (1.0 - ramp) + theta / factor * ramp


def build_rope_cache(config: Config, seq_len: int, dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    """Precomputed (cos, sin) of shape (seq_len, rope_n_elem), host-side."""
    n_elem = config.rope_n_elem
    theta = 1.0 / (config.rope_base ** (jnp.arange(0, n_elem, 2, dtype=jnp.float32) / n_elem))
    if config.rope_scaling_llama3 is not None:
        theta = _llama3_rescale_freqs(theta, dict(config.rope_scaling_llama3))
    mag = 1.0
    if config.rope_scaling_yarn is not None:
        yarn = dict(config.rope_scaling_yarn)
        theta = _yarn_rescale_freqs(theta, n_elem, float(config.rope_base), yarn)
        # cos and sin carry mscale / mscale_all_dim (1 where both are set alike)
        factor = float(yarn["factor"])
        mag = (_yarn_mscale(factor, float(yarn.get("mscale") or 1.0))
               / _yarn_mscale(factor, float(yarn.get("mscale_all_dim") or 0.0)))
    seq = jnp.arange(seq_len, dtype=jnp.float32) / config.rope_condense_ratio
    idx_theta = jnp.outer(seq, theta)  # (T, n_elem/2)
    idx_theta = jnp.concatenate([idx_theta, idx_theta], axis=-1)  # (T, n_elem)
    cos, sin = jnp.cos(idx_theta), jnp.sin(idx_theta)
    if mag != 1.0:          # no eager product (a program of its own) where there is nothing to scale
        cos, sin = cos * mag, sin * mag
    return cos.astype(dtype), sin.astype(dtype)


#
# Forward (traced: written against the thunder_tpu.torch surface)
#


def apply_rope(x, cos, sin):
    """NeoX-style rotary embedding.  x: (B, nh, T, rope_n_elem); cos/sin (T, rope_n_elem).

    The f32 rope cache promotes low-precision activations during the rotation
    (precision where it matters), then the result is cast back to x.dtype so
    the attention matmuls stay MXU-native bf16.
    """
    half = x.shape[-1] // 2
    x1 = x[..., :half]
    x2 = x[..., half:]
    rotated = ltorch.cat([-x2, x1], dim=-1)
    roped = x * cos + rotated * sin
    return roped.to(x.dtype)


def _rms_weight(weight, config: Config):
    """The scale a stored RMSNorm weight stands for: ``1 + w`` in float32
    where the config stores them zero-centred."""
    return ltorch.to(weight, ltorch.float32) + 1.0 if config.norm_zero_centered else weight


def _norm(x, weight, config: Config, bias=None):
    if config.norm_class == "RMSNorm":
        return ltorch.rms_norm(x, (config.n_embd,), _rms_weight(weight, config), eps=config.norm_eps)
    return ltorch.layer_norm(x, (config.n_embd,), weight, bias, eps=config.norm_eps)


def attention(ap, x, cos, sin, config: Config):
    B, T, C = x.shape
    hs, nh, ng = config.head_size, config.n_head, config.n_query_groups
    # optional single-adapter LoRA hook: ap["lora"] = {target: (a, b)} with
    # a (r, in_features), b (out_features, r) — the low-rank delta B(A(x))
    # rides next to the target matmul (fold the alpha/r scaling into b).
    # Per-request multi-tenant serving lives in thunder_tpu.serving.lora;
    # this hook is the traced-path analog for fine-tune forwards.
    lora = ap.get("lora") or {}

    def proj(name, x_in, w, bias):
        o = ltorch.linear(x_in, w, bias)
        if name in lora:
            a, b = lora[name]
            o = o + ltorch.linear(ltorch.linear(x_in, a), b)
        return o

    with scope("qkv"):
        q = proj("wq", x, ap["wq"], ap.get("bq"))  # (B, T, nh*hs)
        k = proj("wk", x, ap["wk"], ap.get("bk"))  # (B, T, ng*hs)
        v = proj("wv", x, ap["wv"], ap.get("bv"))

        if config.qk_norm_whole:
            q = ltorch.rms_norm(q, (nh * hs,), _rms_weight(ap["q_norm"], config), eps=config.norm_eps)
            k = ltorch.rms_norm(k, (ng * hs,), _rms_weight(ap["k_norm"], config), eps=config.norm_eps)
        gate = None
        if config.attn_output_gate:
            # wq projects to (q, gate) a head
            qg = q.reshape(B, T, nh, 2 * hs)
            q, gate = qg[..., :hs], qg[..., hs:].reshape(B, T, nh * hs)
        q = q.reshape(B, T, nh, hs)
        k = k.reshape(B, T, ng, hs)
        if config.qk_norm:
            q = ltorch.rms_norm(q, (hs,), _rms_weight(ap["q_norm"], config), eps=config.norm_eps)
            k = ltorch.rms_norm(k, (hs,), _rms_weight(ap["k_norm"], config), eps=config.norm_eps)
        q = q.permute(0, 2, 1, 3)  # (B, nh, T, hs)
        k = k.permute(0, 2, 1, 3)  # (B, ng, T, hs)
        v = v.reshape(B, T, ng, hs).permute(0, 2, 1, 3)

    n_elem = config.rope_n_elem
    if n_elem > 0:
        with scope("rope"):
            q_roped = apply_rope(q[..., :n_elem], cos, sin)
            k_roped = apply_rope(k[..., :n_elem], cos, sin)
            if n_elem < hs:
                q = ltorch.cat([q_roped, q[..., n_elem:]], dim=-1)
                k = ltorch.cat([k_roped, k[..., n_elem:]], dim=-1)
            else:
                q, k = q_roped, k_roped

    # GQA (ng != nh) is passed natively: the fused SDPA prim gathers KV
    # groups by index inside the flash kernels, so K/V are never expanded
    # to nh heads in HBM (nh/ng× KV-bandwidth saving at Llama-70B/Mixtral)
    with scope("attn"):
        y = ltorch.scaled_dot_product_attention(
            q, k, v, is_causal=True, sliding_window=config.sliding_window
        )  # (B, nh, T, hs)
    with scope("out"):
        y = y.permute(0, 2, 1, 3).reshape(B, T, nh * hs)
        if gate is not None:
            y = y * ltorch.sigmoid(gate)
        return proj("wo", y, ap["wo"], ap.get("bo"))


def _l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum(x^2) + eps) over the head, in float32, back in x's dtype."""
    xf = ltorch.to(x, ltorch.float32)
    return ltorch.to(xf * ltorch.rsqrt(ltorch.sum(xf * xf, -1, True) + eps), x.dtype)


def gated_delta_net(gp, x, config: Config):
    """The "linear_attention" mixer (Gated DeltaNet, hf Qwen3NextGatedDeltaNet):
    q, k, v pass a causal depthwise conv and SiLU; each value head keeps a
    ``(dk, dv)`` float32 state that decays by ``exp(g_t)`` and takes the
    rank-1 delta-rule update ``k_t ((v_t - S^T k_t) beta_t)^T``; the read-out
    ``S^T q_t`` is RMS-normed a head, gated by ``silu(z)`` and projected.
    The recurrence is ``ltorch.gated_delta_rule``: the chunked algorithm."""
    B, T, _ = x.shape
    nk, nv = config.linear_num_key_heads, config.linear_num_value_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    with scope("gdn/in_proj"):
        qkvz = ltorch.linear(x, gp["in_proj_qkvz"])
        ba = ltorch.linear(x, gp["in_proj_ba"])
        n_qkv = 2 * nk * dk + nv * dv
        qkv, z = qkvz[..., :n_qkv], qkvz[..., n_qkv:]
    # causal depthwise conv over time (torch conv1d, groups = channels, K - 1
    # zeros on the left, no bias): tap j of a channel weighs the token K - 1 - j back
    with scope("gdn/conv"):
        qkv = ltorch.causal_conv1d(qkv, gp["conv_w"], activation="silu")
    with scope("gdn/gates"):
        q = qkv[..., : nk * dk].reshape(B, T, nk, dk)
        k = qkv[..., nk * dk: 2 * nk * dk].reshape(B, T, nk, dk)
        v = qkv[..., 2 * nk * dk:].reshape(B, T, nv, dv)
        q = _l2norm(q) * (dk ** -0.5)
        k = _l2norm(k)
        beta = ltorch.sigmoid(ltorch.to(ba[..., :nv], ltorch.float32))
        if config.linear_allow_neg_eigval:
            beta = beta * 2.0
        a = ltorch.to(ba[..., nv:], ltorch.float32)
        g = -ltorch.exp(ltorch.to(gp["A_log"], ltorch.float32)) * ltorch.softplus(
            a + ltorch.to(gp["dt_bias"], ltorch.float32))
    with scope("gdn/scan"):
        o = ltorch.gated_delta_rule(
            q.permute(0, 2, 1, 3), k.permute(0, 2, 1, 3), v.permute(0, 2, 1, 3),
            g.permute(0, 2, 1), beta.permute(0, 2, 1))  # (B, nv, T, dv)
    with scope("gdn/out"):
        o = ltorch.rms_norm(o.permute(0, 2, 1, 3), (dv,), gp["norm"], eps=config.norm_eps)
        o = o * ltorch.silu(z.reshape(B, T, nv, dv))
        return ltorch.linear(o.reshape(B, T, nv * dv), gp["out_proj"])


def sparse_moe_mlp(mp, x, config: Config):
    """An expert layer told which experts it holds (``expert_first``,
    ``expert_held`` of ``n_expert``): the router is a float32 softmax over
    *all* experts, the top ``n_expert_per_token`` renormalised; the held
    experts' part of the result comes from ``ltorch.moe_expert_share``
    (one fused prim: sorted rows, grouped products, no capacity and no
    dropped token), what
    the other experts would add is left out; the shared expert, behind its
    sigmoid gate, is added once.  Under an ``ep`` axis the shares' results
    would be summed across chips; on one chip there is no exchange."""
    B, T, C = x.shape
    I, Eh = config.intermediate_size, config.expert_held
    x2 = x.reshape(B * T, C)
    # router logits leave the product in float32 (no rounding to bfloat16 before
    # the top-k: a rounded logit flips choices that a float32 router keeps)
    with scope("router"):
        probs = ltorch.softmax(ltorch.linear(ltorch.to(x2, ltorch.float32), ltorch.to(mp["gate"], ltorch.float32)), -1)
        top_w, top_idx = ltorch.topk(probs, config.n_expert_per_token, -1)
        top_w = top_w / ltorch.sum(top_w, -1, True)
    with scope("experts"):
        y = ltorch.moe_expert_share(
            x2, top_idx, top_w, mp["fc_1"].reshape(Eh, C, I), mp["fc_2"].reshape(Eh, C, I),
            mp["proj"].reshape(Eh, I, C), config.expert_first, config.n_expert)
    if config.shared_expert_size:
        with scope("shared"):
            sp = mp["shared"]
            shared = ltorch.linear(ltorch.silu(ltorch.linear(x2, sp["fc_1"])) * ltorch.linear(x2, sp["fc_2"]),
                                   sp["proj"])
            y = y + ltorch.sigmoid(ltorch.linear(x2, sp["gate"])) * shared
    return y.reshape(B, T, C)


def moe_mlp(mp, x, config: Config):
    """Mixture-of-experts MLP (litgpt LLaMAMoE semantics, reference
    tests/litgpt_model.py:98-110): top-k on the raw router logits, softmax
    over the selected k in float32, weighted sum of expert outputs.

    TPU-first dense formulation: every expert runs on every token and the
    router weight masks the result — static shapes, no scatter, E small.
    XLA turns the per-expert slices of the stacked (E, ·, ·) weights into
    plain MXU matmuls; for expert-parallel execution over an ``ep`` mesh
    axis see ``thunder_tpu.distributed.moe``."""
    E, k = config.n_expert, config.n_expert_per_token
    with scope("router"):
        router = ltorch.linear(x, mp["gate"])  # (B, T, E)
        top_logits, top_idx = ltorch.topk(router, k, -1)  # (B, T, k)
        probs = ltorch.softmax(ltorch.to(top_logits, ltorch.float32), -1)
    y = None
    with scope("experts"):
        for e in range(E):
            # summed routing weight for expert e over the k slots: (B, T)
            w_e = ltorch.sum(probs * ltorch.to(ltorch.eq(top_idx, e), ltorch.float32), -1)
            xe = ltorch.linear(
                ltorch.silu(ltorch.linear(x, mp["fc_1"][e])) * ltorch.linear(x, mp["fc_2"][e]),
                mp["proj"][e],
            )
            contrib = xe * ltorch.to(ltorch.unsqueeze(w_e, -1), x.dtype)
            y = contrib if y is None else y + contrib
    return y


def mlp(mp, x, config: Config):
    if config.mlp_class == "LLaMAMoE":
        return moe_mlp(mp, x, config)
    if config.mlp_class == "SparseMoE":
        return sparse_moe_mlp(mp, x, config)
    with scope("up"):
        if config.mlp_class == "LLaMAMLP":
            h = (ltorch.silu(ltorch.linear(x, mp["fc_1"], mp.get("fc_1_b")))
                 * ltorch.linear(x, mp["fc_2"], mp.get("fc_2_b")))
        elif config.mlp_class == "GemmaMLP":
            # gated MLP with a gelu gate (litgpt GemmaMLP: LLaMAMLP with gelu)
            h = (ltorch.gelu(ltorch.linear(x, mp["fc_1"], mp.get("fc_1_b")),
                             approximate=config.gelu_approximate)
                 * ltorch.linear(x, mp["fc_2"], mp.get("fc_2_b")))
        else:
            h = ltorch.gelu(ltorch.linear(x, mp["fc"], mp.get("fc_b")), approximate=config.gelu_approximate)
    with scope("down"):
        return ltorch.linear(h, mp["proj"], mp.get("proj_b"))


def serving_only(config: Config) -> str | None:
    """Why ``block_forward`` (``tt.jit`` / ``make_train_step``) cannot run this
    config, or None: latent attention, the gated short convolution, a
    decoder-hybrid-decoder's kinds (selective scan, per-kind window, gated
    memory unit, cross attention, differential attention), the Mamba-2 mixer,
    single-sublayer blocks, the window kind of an ordinary decoder with its
    rotation a layer kind and its norms on both sides of a sublayer, the sigmoid
    routers, a router that reads the block's input, leading dense layers, the
    latent ungated expert share, the gated-ReLU experts, a stream under
    hyper-connections and a stack run ``n_pass`` times are built in
    ``models.generate`` for the server alone."""
    if config.n_pass > 1:
        return ("n_pass > 1 (a looped model: the stack run n_pass times over one set of weights, the last norm "
                "closing every pass, and the exit gate are built in models.generate, for tt.serve; the trainer "
                "has no backward pass through the loop and no source for the published objective)")
    if config.conv_layers:
        return ("layer_types with 'conv' (the gated short convolution is built in models.generate, for tt.serve, "
                "and has no traced form)")
    if config.hybrid_decoder:
        return ("layer_types with 'ssm', 'sliding_attention', 'gmu' or 'cross_attention', or differential attention "
                "(a decoder-hybrid-decoder's kinds are built in models.generate, for tt.serve, and have no traced form)")
    if config.ring_layers or config.rope_kinds is not None or config.sandwich_norm:
        return ("layer_types with 'sliding_attention' in an ordinary decoder, rope_kinds or sandwich_norm (a window a "
                "layer kind, a rotation a layer kind and a norm on both sides of a sublayer are built in "
                "models.generate, for tt.serve, and have no traced form)")
    if config.latent:
        return "kv_lora_rank > 0 (latent attention is built in models.generate, for tt.serve, and has no traced form)"
    if config.hc_mult > 1:
        return ("hc_mult > 1 (a residual stream hc_mult wide under hyper-connections is built in models.generate, "
                "for tt.serve, and has no traced form: block_forward carries one stream)")
    if set(config.layer_types or ()) & set(SINGLE_SUBLAYER_KINDS):
        return ("layer_types with 'mamba2' or 'mlp' (the Mamba-2 mixer and single-sublayer blocks are built in "
                "models.generate, for tt.serve, and have no traced form: the chunked scan has no backward)")
    if config.mlp_class == "SparseMoE" and (config.moe_router != "softmax" or config.first_k_dense
                                            or (config.shared_expert_size and not config.shared_expert_gate)
                                            or config.moe_latent_size or config.moe_activation != "swiglu"
                                            or config.moe_route_block_input):
        return ("a SparseMoE layer with moe_router='sigmoid_group' or 'sigmoid_bias', first_k_dense, an ungated "
                "shared expert, moe_latent_size, moe_activation='relu2' or 'reglu', or moe_route_block_input (built "
                "in models.generate, for tt.serve; the traced expert layer routes by softmax on the tensor its "
                "SwiGLU experts read, at the model's width)")
    return None


def block_forward(bp, x, cos, sin, config: Config, kind: str = "full_attention"):
    why = serving_only(config)
    if why:
        raise NotImplementedError(f"config {config.name!r} cannot be trained through tt.jit: it sets {why}")
    # each sublayer's norm and residual add count with the sublayer
    def mixer(x_in):
        if kind == "linear_attention":
            return gated_delta_net(bp["gdn"], x_in, config)
        return attention(bp["attn"], x_in, cos, sin, config)

    if config.post_sublayer_norm:
        with scope("mixer"):
            h = mixer(x)
            with scope("norm"):
                x = x + _norm(h, bp["norm_1"], config)
        with scope("mlp"):
            h = mlp(bp["mlp"], x, config)
            with scope("norm"):
                return x + _norm(h, bp["norm_2"], config)
    with scope("mixer"):
        with scope("norm"):
            n1 = _norm(x, bp["norm_1"], config, bp.get("norm_1_b"))
        h = mixer(n1)
        if not config.parallel_residual:
            with scope("residual"):
                x = x + h
    with scope("mlp"):
        if config.parallel_residual:
            if config.shared_attention_norm:
                n2 = n1
            else:
                with scope("norm"):
                    n2 = _norm(x, bp["norm_2"], config, bp.get("norm_2_b"))
            m = mlp(bp["mlp"], n2, config)
            with scope("residual"):
                return x + h + m
        with scope("norm"):
            n2 = _norm(x, bp["norm_2"], config, bp.get("norm_2_b"))
        m = mlp(bp["mlp"], n2, config)
        with scope("residual"):
            return x + m


def gpt_hidden(params, idx, cos, sin, config: Config):
    """Token ids (B, T) int32 → final hidden states (B, T, C) (pre-head)."""
    with scope("embed"):
        x = ltorch.embedding(idx, params["wte"])
        if config.scale_embedding:
            x = x * (config.n_embd ** 0.5)
        if config.learned_pos_embedding:
            T = idx.shape[1]
            x = x + params["wpe"][:T]
    for i, bp in enumerate(params["blocks"]):
        with scope(f"blk{i}"):
            x = block_forward(bp, x, cos, sin, config, config.layer_kind(i))
    with scope("head/norm"):
        return _norm(x, params["ln_f"], config, params.get("ln_f_b"))


def gpt_forward(params, idx, cos, sin, config: Config):
    """Token ids (B, T) int32 → logits (B, T, padded_vocab_size)."""
    x = gpt_hidden(params, idx, cos, sin, config)
    head = params["wte"] if config.tie_embeddings else params["lm_head"]
    with scope("head/logits"):
        return ltorch.linear(x, head, params.get("lm_head_b"))


def gpt_loss(params, idx, targets, cos, sin, config: Config):
    """Next-token cross-entropy over the padded vocab, float32 accumulation.

    Targets of ``-100`` are ignored with exact mean normalization (torch's
    ignore_index default), so bucket-padded batches (``batch_bucketer``)
    produce bit-identical losses to the unpadded shapes."""
    if config.fused_head_ce:
        x = gpt_hidden(params, idx, cos, sin, config)
        head = params["wte"] if config.tie_embeddings else params["lm_head"]
        C = x.shape[-1]
        # the logits' product is inside the fused prim: it counts as loss
        with scope("head/loss"):
            return ltorch.fused_linear_cross_entropy(
                x.reshape(-1, C), head, targets.reshape(-1)
            )
    logits = gpt_forward(params, idx, cos, sin, config)
    V = logits.shape[-1]
    with scope("head/loss"):
        return ltorch.cross_entropy(logits.reshape(-1, V).to(ltorch.float32), targets.reshape(-1))


def _bucket_up(n: int, minimum: int) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def batch_bucketer(config: Config, *, min_b: int = 1, min_t: int = 16):
    """Pads ``(idx, targets, cos, sin)`` batches up to power-of-two (B, T)
    buckets so one compiled program serves every shape inside a bucket — the
    TPU-native realization of the reference's symbolic-values caching
    (``core/options.py:95`` CACHE_OPTIONS.SYMBOLIC_VALUES): XLA needs static
    shapes, so instead of symbolic shapes the *program count* is made
    logarithmic in the shape range.

    Exactness: padded positions sit at the sequence tail (causal attention —
    valid tokens never attend them), padded targets are ``-100`` (ignored
    with exact mean normalization in ``gpt_loss``), and rope caches are
    rebuilt for the bucketed T.  Pass to ``make_train_step(bucketer=...)``.
    """
    rope_cache: dict[tuple[int, str], tuple[jax.Array, jax.Array]] = {}

    def bucket(batch):
        idx, targets, cos, sin = batch
        B, T = idx.shape
        B2, T2 = _bucket_up(B, min_b), _bucket_up(T, min_t)
        if (B2, T2) == (B, T):
            return batch
        idx2 = jnp.pad(idx, ((0, B2 - B), (0, T2 - T)))
        tgt2 = jnp.pad(targets, ((0, B2 - B), (0, T2 - T)), constant_values=-100)
        key = (T2, str(cos.dtype))
        if key not in rope_cache:
            rope_cache[key] = build_rope_cache(config, T2, dtype=cos.dtype)
        cos2, sin2 = rope_cache[key]
        return idx2, tgt2, cos2, sin2

    return bucket
