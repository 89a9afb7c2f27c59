"""A causal decoder whose mixer and FFN are chosen layer by layer. Seven
families (``student.arch``): ``kimi_linear`` (Kimi Linear, Moonshot AI;
``config.json`` of Kimi-Linear-48B-A3B-Instruct and the model's report:
KDA and MLA mixers, a dense SwiGLU, routed + shared experts),
``smallthinker`` (SmallThinker, PowerInfer; ``config.json`` of
SmallThinker-21BA3B-Instruct: grouped-query mixers with a window and
rotary or with neither, routed ReGLU experts whose router reads the
layer's input) and ``qwen3_next`` (Qwen3-Next, Qwen; ``config.json`` of
Qwen3-Next-80B-A3B-Instruct: Gated DeltaNet and gated grouped-query
mixers 3 : 1, zero-centred norms, routed SwiGLU experts beside a shared
one behind a sigmoid gate) and ``keye_vl2`` (Keye-VL-2.0's language model,
Kwai; ``config.json`` of Keye-VL-2.0-30B-A3B: a Qwen3-MoE block whose
grouped-query attention reads, for each query, the ``topk`` keys a
learned indexer selects; text tokens only) and ``lfm2_moe`` (LFM2-MoE,
LiquidAI; ``config.json`` of LFM2-24B-A2B: gated short convolutions 3 : 1
with grouped-query attention on heads of 64, leading dense SwiGLU layers,
then routed experts behind a biased sigmoid router, a tied head) and
``deepseek_v3`` (the DeepSeek-V3 block as Kakao's Kanana-2 runs it;
``config.json`` of kanana-2-30b-a3b-instruct-2601: latent attention with
a ROTATED shared key on every layer, a leading dense SwiGLU, then routed
experts behind a biased sigmoid router beside two shared ones) and
``nemotron_h`` (Nemotron-H as NVIDIA's Nemotron 3 Nano runs it;
``config.json`` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16: blocks of ONE
sublayer each, as ``hybrid_override_pattern`` spells them — M a Mamba-2
mixer, * grouped-query attention without positions, E un-gated
squared-ReLU experts behind a biased sigmoid router beside a shared one).

A layer is a mixer and a feed-forward part, each pre-normed (``norm1``,
``norm2``) with its own residual add; a block may be ONE sublayer
(``layers`` entries ``(mixer, None)`` / ``(None, ffn)``: ``nemotron_h``),
then it has one ``norm`` and one residual add. RMSNorm everywhere
(``qwen3_next``: zero-centred,
n(x) = x / rms(x) * (1 + w), but for the delta rule's output norm):

- **KDA** (Kimi Delta Attention), per head h with d_k = d_v = head_dim,
  x_t the normed input:
  q_t = L2norm(SiLU(conv(W_q x)_t)) * d_k^-0.5, k_t = L2norm(SiLU(conv(W_k x)_t)),
  v_t = SiLU(conv(W_v x)_t) (causal depthwise convolutions of width
  ``short_conv_kernel_size``); a_t = exp(-exp(A_log_h) softplus(W_f2 W_f1 x_t
  + dt_bias)), one decay per key channel; b_t = sigmoid(w_b x_t);
  S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T; o_t = S_t^T q_t;
  y_t = W_o [RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x_t)]. The delta rule
  itself is ``ops/kda.py`` (chunked, float32).
- **MLA**: q_t = W_q x_t, heads of qk_nope + qk_rope; [c_t ; kpe_t] =
  W_kva x_t; [k_nope ; v] = W_kvb RMSNorm(c_t); k = [k_nope ; kpe_t for
  every head]; causal softmax(q k^T / sqrt(d_qk)) v through
  ``ops/attention.py``; W_o. WITHOUT rotary (``kimi_linear``:
  ``mla_use_nope``) the qk_rope channels are one more slice of q and of
  the shared key, never turned. WITH rotary (``deepseek_v3``:
  ``rope_interleave``) the last qk_rope channels of every q head and the
  ONE key head kpe are turned over token positions before kpe is repeated
  over the heads, NEIGHBOURING channels a pair: (2i, 2i + 1) of token t by
  t * rope_theta^(-2i / qk_rope), float32 (``ops/rope.py
  rope_apply_interleaved``; the turned slice is held evens' results
  before odds', in q and k alike, so the scores are the published ones).
  No scaling of positions or of the softmax.
- **GQA** (``swa`` | ``full_attn``): q = W_q x as ``num_attention_heads``
  heads of ``head_dim``, k = W_k x and v = W_v x as ``num_key_value_heads``;
  query head i reads key/value head i // (heads / kv heads). ``swa``: q
  and k rotated over token positions (rotate-half, ``rope_theta``) and
  token t sees keys t - ``sliding_window`` < j <= t. ``full_attn``: every
  key up to its own and no rotation at all. Same ``ops/attention.py``
  tiles as MLA. W_o.
- **GDN** (Gated DeltaNet; ``gdn``), ``linear_num_key_heads`` key heads
  under ``linear_num_value_heads`` value heads: [q ; k ; v ; z] = W_qkvz x,
  [b ; a] = W_ba x; [q ; k ; v] <- SiLU(conv([q ; k ; v])), ONE causal
  depthwise convolution of width ``linear_conv_kernel_dim`` over the
  joined channels; per value head j, served by key head j // (value
  heads / key heads): q_t, k_t L2-normalised (eps 1e-6), q_t * d_k^-0.5;
  b_t = sigmoid(b); g_t = -exp(A_log_j) softplus(a_t + dt_bias_j), ONE
  log decay a head and token; S_t = (I - b_t k_t k_t^T) e^{g_t} S_{t-1} +
  b_t k_t v_t^T; o_t = S_t^T q_t; y_t = W_o [RMSNorm_head(o_t) * SiLU(z_t)]
  (this one norm's scale is w, from ones). The delta rule is KDA's own
  ``ops/kda.py``, which reads ONE decay a head off the gate's rank and
  takes q and k at the key heads. W_qkvz's
  and W_ba's columns are held in the published grouping, a key head at a
  time: [q d_k | k d_k | v r d_v | z r d_v] and [b r | a r], r = value
  heads / key heads.
- **Gated attention** (``gated_attn``): GQA with [q_i ; gate_i] = W_q x a
  head, q_i <- n_q(q_i) and k <- n_k(k) (the layer's kind of norm, one
  scale vector over ``head_dim`` for all heads), the first ``rotary_dim``
  channels of every q and k head rotated over token positions, the rest
  untouched; no window; y = W_o [o * sigmoid(gate)].
- **DSA** (``dsa``; DeepSeek-V3.2-Exp's sparse attention on a
  grouped-query layer): q, k, v as GQA with n_q, n_k on every q and k head
  and the whole head rotated. The INDEXER reads the layer's normed input
  detached, h' = stop_gradient(h): q^I = W^I_q h' as ``index_num_heads``
  heads of ``index_head_dim``, ONE key head k^I = LayerNorm(W^I_k h'),
  both rotated whole, head weights a = W^I_w h' * (heads * width)^-1/2;
  I[t, s] = sum_j a[t, j] ReLU(q^I[t, j] . k^I[s]) over s <= t; S_t = the
  ``index_topk`` largest (all of them while t < topk; ties to the lower
  s), one selection a query for all heads; o[t, i] = softmax over S_t of
  q_i . k / sqrt(d) times v; W_o. With p = stop_gradient(mean over heads
  of those softmaxes) the layer also gives the INDEX LOSS L^I = mean_t
  KL(p[t, .] || softmax_{S_t} I[t, .]), whose gradient reaches the
  indexer's leaves alone, as the next-token loss reaches every leaf but
  them; no gradient crosses the selection (``ops/sparse_index.py``).
- **Gated short convolution** (``conv``): [B ; C ; u] = W_in x, three
  blocks of ``hidden_size`` in that order; z = B * u; c_t = sum_j k_j *
  z_{t - (W-1) + j}, depthwise and causal, W = ``conv_L_cache``, no bias;
  y = W_out (C * c). No activation. The chain between the two matmuls is
  ``ops/mixer_chains.py gated_short_conv`` on a TPU, the plain XLA chain
  elsewhere. ``lfm2_moe``'s ``full_attn`` layers are GQA with n_q, n_k on
  every q and k head and the whole head rotated, no window.
- **SSD** (``ssm``; Mamba-2's state-space duality), ``mamba_num_heads``
  heads of ``mamba_head_dim`` (inner width their product) on a state of
  ``ssm_state_size`` in ``n_groups`` groups: [z ; xBC ; dt] = W_in x,
  widths inner | inner + 2 G N | heads in that order, no bias;
  xBC' = SiLU(c + conv(xBC)), ONE causal depthwise convolution of width
  ``conv_kernel`` WITH a bias c over the joined channels; [u ; B ; C] =
  xBC', head i reads group i // (heads / groups)'s B and C;
  dt = softplus(dt + dt_bias), a = -exp(A_log), ONE rate a head;
  S_t = e^{dt_t a} S_{t-1} + dt_t u_t B_t^T, y_t = S_t C_t + D u_t
  (``ops/ssd.py``: a kernel pair on a TPU at heads of 64 on a state of
  128, the plain chunked scan elsewhere; the state, the decays and dt
  float32); g = y * SiLU(z), normalised over each GROUP's inner / G
  channels (the gate BEFORE the norm, one scale over the inner width);
  W_out g. The two chains between the matmuls (``ssm_chain``) are the
  kernel pairs ``ops/mixer_chains.py ssm_conv_silu`` and ``ssm_gate_norm``
  on a TPU (``ssm_chain_path``), the plain XLA chains elsewhere.
  ``nemotron_h``'s ``full_attn`` blocks are GQA with no rotation, no
  window and no q/k norm.
- **FFN**: ``kimi_linear``: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them the routed experts this
  shard holds (``ops/ffn.py RoutedExpertsFFN``, sigmoid router) plus
  ``num_shared_experts`` shared ones of the same width side by side,
  every token through them. ``smallthinker``: every layer routed, ReGLU
  experts, softmax over the chosen logits, no shared expert; the router's
  logits are W_r x of the LAYER'S INPUT x, before the first norm and the
  mixer (``router_reads_layer_input``), the experts read n2(x').
  ``qwen3_next``: every layer routed, SwiGLU experts, softmax over the
  chosen logits of a router that reads n2(x') like its experts, plus ONE
  shared expert times sigmoid(w_s . n2(x')) (``shared_expert_gate``); the
  row buffer of the routed layer holds ``expert_rows_factor`` times its
  experts' even share (the recipe's ``lm.expert_rows_factor``;
  ``deepseek_v3``'s recipe sets it too).
  ``keye_vl2``: every layer routed, SwiGLU experts, softmax over the
  chosen logits of a router that reads n2(x'), no shared expert.
  ``lfm2_moe``: SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` layers, then routed SwiGLU experts under Kimi's
  sigmoid rule with w = s_sel / (sum(s_sel) + 1e-6), no shared expert.
  ``deepseek_v3``: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers, then routed SwiGLU experts under the
  same rule (``topk_method`` ``noaux_tc`` with ONE group: the
  ``num_experts_per_tok`` largest of s + bias) with w =
  ``routed_scaling_factor`` * s_sel / (sum(s_sel) + 1e-20), plus
  ``n_shared_experts`` shared ones as ONE SwiGLU of that many widths.
  ``nemotron_h``: every E block routed, UN-GATED experts of two matrices,
  W2 relu(W1 x)^2 (``gate`` "relu2": ``w1`` [held, D, H], ``w2``
  [held, H, D]; H may end in half a lane tile), under ``deepseek_v3``'s
  rule (ONE group, sum + 1e-20, ``routed_scaling_factor``), plus ONE
  shared un-gated MLP of ``moe_shared_expert_intermediate_size``.

The vocabulary may be a slice (``vocab_size`` rows of the published
table): ids, logits and the loss are over the slice. Embedding and head
are untied, but in ``lfm2_moe`` (``tie_word_embeddings``), whose logits
are n(x) E^T of the embedding table E: ONE leaf, its gradient the sum of
both uses. The loss is the mean next-token cross-entropy, float32, the
head applied a block of tokens at a time so that the ``[tokens, vocab]``
logits never exist whole.

The step's phases (``utils.STEP_PHASES``): ``lm_embed``, ``kda_mixer``
(inner ``kda_core``), ``mla_mixer`` (inner ``mla_rope``: the two turns,
nothing else, and ``mla_core``: the repeat of the shared key and the
attention call), ``swa_mixer`` and ``full_attn_mixer``
(inner ``gqa_core``), ``gdn_mixer`` (inner ``gdn_core``),
``gated_attn_mixer`` (inner ``gqa_core``), ``dsa_mixer`` (inner
``dsa_index``: the indexer's projections and score planes, ``dsa_select``:
the thresholds, ``dsa_core``, ``dsa_index_loss``), ``sconv_mixer`` (inner ``sconv_chain``: the kernel
pair or the plain chain, nothing else), ``ssm_mixer`` (inner ``ssd_core``:
the scan alone, and ``ssm_chain``: the convolution, softplus, the skip,
the gate and the grouped norm, as two kernel pairs or the plain chains),
``dense_ffn``, ``moe_ffn`` (inner
``moe_route``, ``moe_experts`` from the routed layer — inside it
``moe_rows``, the dispatch and combine of ``ops/routed_rows.py`` —
``moe_shared``: the shared expert, with its gate where it has one),
``lm_head_loss``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from dinov3_tpu.ops.attention import dispatch_attention
from dinov3_tpu.ops.causal_attention import (
    latent_attention,
    latent_attention_path,
)
from dinov3_tpu.ops.common import l2_normalize, part, trunc_normal_init
from dinov3_tpu.ops.ffn import (
    ROWS_CAPACITY_FACTOR,
    Mlp,
    RoutedExpertsFFN,
    SwiGLUFFN,
)
from dinov3_tpu.ops.kda import kda_chunked
from dinov3_tpu.ops.mixer_chains import (
    IN_ORDER,
    conv_silu_norm,
    gated_rms_norm,
    gated_short_conv,
    log_decay,
    mixer_chain_path,
    ssm_chain_path,
    ssm_conv_silu,
    ssm_gate_norm,
)
from dinov3_tpu.ops.norms import LayerNorm, RMSNorm
from dinov3_tpu.ops.rope import (
    rope_apply_full,
    rope_apply_interleaved,
    rope_apply_leading,
    rope_apply_pairs,
    token_rope_pair_sincos,
    token_rope_sincos,
)
from dinov3_tpu.ops.ssd import ssd_chunked
from dinov3_tpu.ops.sparse_index import (
    INT_MIN,
    index_loss,
    pack_selection,
    select_thresholds,
    selection_plane,
)
from dinov3_tpu.utils import step_phase


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The ``lm`` section of a recipe as the layers read it, with the
    layer table made from the published lists (``kimi_linear``: layer
    numbers, 1-based; ``smallthinker``: one 0/1 flag a layer), each family
    under its own ``config.json``'s key names. A size a family has no use
    for stays 0."""

    hidden_size: int
    vocab_size: int
    layers: tuple              # ((mixer, ffn), ...); one of a pair may be
                               # None: a block of ONE sublayer
    rms_norm_eps: float
    num_attention_heads: int
    num_experts: int
    num_experts_per_token: int
    moe_intermediate_size: int
    expert_shards: int
    expert_shard: int
    # kimi_linear
    intermediate_size: int = 0
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    short_conv_kernel_size: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    num_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    # smallthinker
    num_key_value_heads: int = 0
    head_dim: int = 0
    sliding_window: int = 0
    rope_theta: float = 0.0
    router: str = "sigmoid"            # ops/ffn.py RoutedExpertsFFN's rules
    gate: str = "silu"
    router_reads_layer_input: bool = False
    # qwen3_next
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    rotary_dim: int = 0                # of head_dim; 0: all of it
    zero_centered_norms: bool = False
    shared_expert_gate: bool = False
    expert_rows_factor: float = ROWS_CAPACITY_FACTOR
    # keye_vl2 (sa_config)
    index_num_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_chunk: int = 0               # queries a strip of the index planes
    # lfm2_moe
    full_attn_rotary: bool = False     # a "full_attn" layer rotates q and k
    attn_qk_norm: bool = False         # ... and norms every q and k head
    router_norm_eps: float = 0.0       # the sigmoid rule's normaliser
    tie_word_embeddings: bool = False  # the head is the embedding table
    # deepseek_v3
    mla_rotary: bool = False           # an "mla" layer turns q_pe and kpe
    # nemotron_h
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 0            # groups of heads that share B and C
    ssm_state_size: int = 0
    time_step_limits: tuple = (1e-3, 1e-1, 1e-4)   # min, max, floor of dt
    shared_expert_width: int = 0       # of the ONE un-gated shared MLP
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32

    @classmethod
    def from_cfg(cls, cfg, param_dtype=None) -> "DecoderConfig":
        from dinov3_tpu.ops.common import Policy

        policy = Policy.from_cfg(cfg.compute_precision)
        family = {"kimi_linear": _kimi_linear_fields,
                  "smallthinker": _smallthinker_fields,
                  "qwen3_next": _qwen3_next_fields,
                  "keye_vl2": _keye_vl2_fields,
                  "lfm2_moe": _lfm2_moe_fields,
                  "deepseek_v3": _deepseek_v3_fields,
                  "nemotron_h": _nemotron_h_fields}[str(cfg.student.arch)]
        return cls(dtype=policy.compute_dtype,
                   param_dtype=param_dtype or policy.param_dtype,
                   reduce_dtype=policy.reduce_dtype, **family(cfg.lm))


def _kimi_linear_fields(lm) -> dict:
    if lm.get("q_lora_rank") is not None:
        raise ValueError("lm.q_lora_rank: only null (a full q projection)")
    if str(lm.moe_router_activation_func) != "sigmoid" \
            or not bool(lm.moe_renormalize):
        raise ValueError("the routed layer is a renormalised sigmoid router")
    depth = int(lm.num_hidden_layers)
    kda, full = set(lm.kda_layers), set(lm.full_attn_layers)
    if kda & full or (kda | full) != set(range(1, depth + 1)):
        raise ValueError(
            f"lm.kda_layers {sorted(kda)} and lm.full_attn_layers "
            f"{sorted(full)} must split layers 1..{depth}")
    layers = tuple(
        ("kda" if i in kda else "mla",
         "dense" if i <= int(lm.first_k_dense_replace) else "moe")
        for i in range(1, depth + 1))
    names = {f.name for f in dataclasses.fields(DecoderConfig)} - {
        "layers", "dtype", "param_dtype", "reduce_dtype"}
    return dict(layers=layers, **{k: lm[k] for k in names if k in lm})


def _smallthinker_fields(lm) -> dict:
    if not bool(lm.moe_primary_router_apply_softmax) \
            or not bool(lm.norm_topk_prob):
        raise ValueError("the routed layer is a softmax router renormalised "
                         "over the chosen experts")
    depth = int(lm.num_hidden_layers)
    windowed, rotated = list(lm.sliding_window_layout), list(lm.rope_layout)
    if len(windowed) < depth or windowed[:depth] != rotated[:depth]:
        raise ValueError(
            "lm.sliding_window_layout and lm.rope_layout must give the "
            f"same flag for each of the {depth} layers (a window layer "
            "rotates, a global layer does not)")
    return dict(
        layers=tuple(("swa" if windowed[i] else "full_attn", "moe")
                     for i in range(depth)),
        hidden_size=lm.hidden_size, vocab_size=lm.vocab_size,
        rms_norm_eps=lm.rms_norm_eps,
        num_attention_heads=lm.num_attention_heads,
        num_key_value_heads=lm.num_key_value_heads, head_dim=lm.head_dim,
        sliding_window=lm.sliding_window_size, rope_theta=float(lm.rope_theta),
        num_experts=lm.moe_num_primary_experts,
        num_experts_per_token=lm.moe_num_active_primary_experts,
        moe_intermediate_size=lm.moe_ffn_hidden_size,
        expert_shards=lm.expert_shards, expert_shard=lm.expert_shard,
        router="softmax", gate="relu", router_reads_layer_input=True)


def _qwen3_next_fields(lm) -> dict:
    if not bool(lm.norm_topk_prob):
        raise ValueError("the routed layer is a softmax router renormalised "
                         "over the chosen experts")
    if int(lm.decoder_sparse_step) != 1 or list(lm.mlp_only_layers):
        raise ValueError("every layer's FFN is routed (lm.decoder_sparse_step "
                         "1, no lm.mlp_only_layers)")
    width, shared = (int(lm.moe_intermediate_size),
                     int(lm.shared_expert_intermediate_size))
    if shared % width:
        raise ValueError(f"lm.shared_expert_intermediate_size {shared} is not "
                         f"a multiple of lm.moe_intermediate_size {width}")
    rotary = float(lm.partial_rotary_factor) * int(lm.head_dim)
    if rotary != int(rotary) or int(rotary) % 2 or not 0 < rotary <= lm.head_dim:
        raise ValueError(f"lm.partial_rotary_factor {lm.partial_rotary_factor} "
                         f"of lm.head_dim {lm.head_dim} is not an even "
                         "number of channels")
    every = int(lm.full_attention_interval)
    return dict(
        layers=tuple(("gated_attn" if (i + 1) % every == 0 else "gdn", "moe")
                     for i in range(int(lm.num_hidden_layers))),
        hidden_size=lm.hidden_size, vocab_size=lm.vocab_size,
        rms_norm_eps=lm.rms_norm_eps,
        num_attention_heads=lm.num_attention_heads,
        num_key_value_heads=lm.num_key_value_heads, head_dim=lm.head_dim,
        rope_theta=float(lm.rope_theta), rotary_dim=int(rotary),
        zero_centered_norms=True,
        linear_num_key_heads=lm.linear_num_key_heads,
        linear_num_value_heads=lm.linear_num_value_heads,
        linear_key_head_dim=lm.linear_key_head_dim,
        linear_value_head_dim=lm.linear_value_head_dim,
        linear_conv_kernel_dim=lm.linear_conv_kernel_dim,
        num_experts=lm.num_experts,
        num_experts_per_token=lm.num_experts_per_tok,
        moe_intermediate_size=width, num_shared_experts=shared // width,
        shared_expert_gate=True,
        expert_shards=lm.expert_shards, expert_shard=lm.expert_shard,
        expert_rows_factor=float(lm.expert_rows_factor),
        router="softmax", gate="silu")


def _keye_vl2_fields(lm) -> dict:
    if not bool(lm.norm_topk_prob):
        raise ValueError("the routed layer is a softmax router renormalised "
                         "over the chosen experts")
    if int(lm.decoder_sparse_step) != 1 or list(lm.mlp_only_layers):
        raise ValueError("every layer's FFN is routed (lm.decoder_sparse_step "
                         "1, no lm.mlp_only_layers)")
    sa = lm.sa_config
    if int(sa.indexer_num_kv_heads) != 1:
        raise ValueError("the indexer has ONE key head "
                         "(lm.sa_config.indexer_num_kv_heads 1)")
    if int(sa.q_chunk_size) != int(sa.kv_chunk_size):
        raise ValueError("lm.sa_config.q_chunk_size and kv_chunk_size are one "
                         "tile's two sides")
    return dict(
        layers=(("dsa", "moe"),) * int(lm.num_hidden_layers),
        hidden_size=lm.hidden_size, vocab_size=lm.vocab_size,
        rms_norm_eps=lm.rms_norm_eps,
        num_attention_heads=lm.num_attention_heads,
        num_key_value_heads=lm.num_key_value_heads, head_dim=lm.head_dim,
        rope_theta=float(lm.rope_theta),
        index_num_heads=sa.indexer_num_heads,
        index_head_dim=sa.indexer_head_dim, index_topk=sa.topk,
        index_chunk=sa.q_chunk_size,
        num_experts=lm.num_experts,
        num_experts_per_token=lm.num_experts_per_tok,
        moe_intermediate_size=lm.moe_intermediate_size,
        expert_shards=lm.expert_shards, expert_shard=lm.expert_shard,
        router="softmax", gate="silu")


LFM2_ROUTER_EPS = 1e-6  # the public implementation's, added to the sum


def _lfm2_moe_fields(lm) -> dict:
    if not bool(lm.norm_topk_prob) or not bool(lm.use_expert_bias):
        raise ValueError("the routed layer is a renormalised sigmoid router "
                         "with a selection bias (lm.norm_topk_prob, "
                         "lm.use_expert_bias)")
    if bool(lm.conv_bias):
        raise ValueError("lm.conv_bias: only false")
    depth, kinds = int(lm.num_hidden_layers), list(lm.layer_types)
    if len(kinds) != depth or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(
            f"lm.layer_types must name each of the {depth} layers conv or "
            f"full_attention: {kinds}")
    heads = int(lm.num_attention_heads)
    if int(lm.hidden_size) % heads:
        raise ValueError(f"lm.hidden_size {lm.hidden_size} on {heads} heads")
    return dict(
        layers=tuple(("conv" if kind == "conv" else "full_attn",
                      "dense" if i < int(lm.num_dense_layers) else "moe")
                     for i, kind in enumerate(kinds)),
        hidden_size=lm.hidden_size, vocab_size=lm.vocab_size,
        rms_norm_eps=lm.norm_eps, intermediate_size=lm.intermediate_size,
        short_conv_kernel_size=lm.conv_L_cache,
        num_attention_heads=heads,
        num_key_value_heads=lm.num_key_value_heads,
        head_dim=int(lm.hidden_size) // heads,
        rope_theta=float(lm.rope_parameters.rope_theta),
        full_attn_rotary=True, attn_qk_norm=True,
        num_experts=lm.num_experts,
        num_experts_per_token=lm.num_experts_per_tok,
        moe_intermediate_size=lm.moe_intermediate_size,
        routed_scaling_factor=float(lm.routed_scaling_factor),
        router_norm_eps=LFM2_ROUTER_EPS,
        expert_shards=lm.expert_shards, expert_shard=lm.expert_shard,
        tie_word_embeddings=True, router="sigmoid", gate="silu")


DEEPSEEK_V3_ROUTER_EPS = 1e-20  # the public implementation's, added to the sum


def _deepseek_v3_fields(lm) -> dict:
    if lm.get("q_lora_rank") is not None:
        raise ValueError("lm.q_lora_rank: only null (a full q projection)")
    if int(lm.n_group) != 1 or int(lm.topk_group) != 1:
        raise ValueError("lm.n_group / lm.topk_group: only 1 / 1 (ONE group "
                         "of experts: the group-limited step selects all)")
    if str(lm.scoring_func) != "sigmoid" or str(lm.topk_method) != "noaux_tc" \
            or not bool(lm.norm_topk_prob):
        raise ValueError("the routed layer is a renormalised sigmoid router "
                         "with a selection bias (lm.scoring_func sigmoid, "
                         "lm.topk_method noaux_tc, lm.norm_topk_prob)")
    if not bool(lm.rope_interleave) or lm.get("rope_scaling") is not None:
        raise ValueError("lm.rope_interleave: only true; lm.rope_scaling: "
                         "only null (positions as they are, no softmax "
                         "scale of their own)")
    if int(lm.moe_layer_freq) != 1:
        raise ValueError("every layer past the leading dense ones is routed "
                         "(lm.moe_layer_freq 1)")
    return dict(
        layers=tuple(("mla", "dense" if i < int(lm.first_k_dense_replace)
                      else "moe") for i in range(int(lm.num_hidden_layers))),
        hidden_size=lm.hidden_size, vocab_size=lm.vocab_size,
        rms_norm_eps=lm.rms_norm_eps, intermediate_size=lm.intermediate_size,
        num_attention_heads=lm.num_attention_heads,
        kv_lora_rank=lm.kv_lora_rank, qk_nope_head_dim=lm.qk_nope_head_dim,
        qk_rope_head_dim=lm.qk_rope_head_dim, v_head_dim=lm.v_head_dim,
        rope_theta=float(lm.rope_theta), mla_rotary=True,
        num_experts=lm.n_routed_experts,
        num_experts_per_token=lm.num_experts_per_tok,
        moe_intermediate_size=lm.moe_intermediate_size,
        num_shared_experts=lm.n_shared_experts,
        routed_scaling_factor=float(lm.routed_scaling_factor),
        router_norm_eps=DEEPSEEK_V3_ROUTER_EPS,
        expert_shards=lm.expert_shards, expert_shard=lm.expert_shard,
        expert_rows_factor=float(lm.expert_rows_factor),
        router="sigmoid", gate="silu")


NEMOTRON_H_BLOCKS = {"M": ("ssm", None), "*": ("full_attn", None),
                     "E": (None, "moe")}


def _nemotron_h_fields(lm) -> dict:
    depth, pattern = int(lm.num_hidden_layers), str(lm.hybrid_override_pattern)
    unknown = sorted(set(pattern) - set(NEMOTRON_H_BLOCKS))
    if unknown or len(pattern) != depth:
        raise ValueError(
            f"lm.hybrid_override_pattern {pattern!r} must name each of the "
            f"{depth} blocks M (Mamba-2), E (routed experts) or * "
            f"(attention): {unknown or len(pattern)}")
    if int(lm.n_group) != 1 or int(lm.topk_group) != 1:
        raise ValueError("lm.n_group / lm.topk_group: only 1 / 1 (ONE group "
                         "of experts: the group-limited step selects all)")
    if not bool(lm.norm_topk_prob) or str(lm.mlp_hidden_act) != "relu2" \
            or int(lm.n_shared_experts) != 1:
        raise ValueError("the routed block is a renormalised sigmoid router "
                         "over un-gated squared-ReLU experts beside ONE "
                         "shared one (lm.norm_topk_prob, lm.mlp_hidden_act "
                         "relu2, lm.n_shared_experts 1)")
    if str(lm.mamba_hidden_act) != "silu" or not bool(lm.use_conv_bias):
        raise ValueError("lm.mamba_hidden_act: only silu; lm.use_conv_bias: "
                         "only true")
    biased = [k for k in ("mamba_proj_bias", "use_bias", "attention_bias",
                          "mlp_bias") if bool(lm[k])]
    if biased:
        raise ValueError(f"lm.{biased[0]}: only false (no projection has a "
                         "bias)")
    return dict(
        layers=tuple(NEMOTRON_H_BLOCKS[kind] for kind in pattern),
        hidden_size=lm.hidden_size, vocab_size=lm.vocab_size,
        rms_norm_eps=lm.layer_norm_epsilon,
        num_attention_heads=lm.num_attention_heads,
        num_key_value_heads=lm.num_key_value_heads, head_dim=lm.head_dim,
        mamba_num_heads=lm.mamba_num_heads, mamba_head_dim=lm.mamba_head_dim,
        mamba_n_groups=lm.n_groups, ssm_state_size=lm.ssm_state_size,
        short_conv_kernel_size=lm.conv_kernel,
        time_step_limits=(float(lm.time_step_min), float(lm.time_step_max),
                          float(lm.time_step_floor)),
        num_experts=lm.n_routed_experts,
        num_experts_per_token=lm.num_experts_per_tok,
        moe_intermediate_size=lm.moe_intermediate_size,
        num_shared_experts=lm.n_shared_experts,
        shared_expert_width=lm.moe_shared_expert_intermediate_size,
        routed_scaling_factor=float(lm.routed_scaling_factor),
        router_norm_eps=DEEPSEEK_V3_ROUTER_EPS,
        expert_shards=lm.expert_shards, expert_shard=lm.expert_shard,
        expert_rows_factor=float(lm.expert_rows_factor),
        router="sigmoid", gate="relu2")


class _LaneTiledDense(nn.Module):
    """``_dense``'s product (the same ``kernel`` leaf, no bias) with zero
    columns appended up to whole lane tiles. XLA lays a ``[B, T,
    features]`` plane whose width is no whole tile out TOKEN-minor, and a
    kernel that cuts its blocks from the plane then pays a transposing
    copy of it a pass; the appended columns cost the product their share
    (64 of 10,368 in ``Mamba2Mixer``) and nothing else reads them."""

    features: int
    kernel_init: Callable = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init,
                            (x.shape[-1], self.features), self.param_dtype)
        kernel = jnp.pad(kernel.astype(self.dtype),
                         ((0, 0), (0, -self.features % 128)))
        return jnp.dot(x.astype(self.dtype), kernel)


def _dense(features: int, axes, name: str, dtype, param_dtype,
           lane_tiled: bool = False) -> nn.Module:
    kind = _LaneTiledDense if lane_tiled else functools.partial(
        nn.Dense, use_bias=False)
    return kind(features, dtype=dtype, param_dtype=param_dtype, name=name,
                kernel_init=part(trunc_normal_init(), axes))


def _swiglu(width: int, name: str, dtype, param_dtype) -> SwiGLUFFN:
    """``SwiGLUFFN`` sizes its hidden layer as 2/3 of what it is given."""
    if width % 2:
        raise ValueError(f"SwiGLU width {width} must be even")
    return SwiGLUFFN(hidden_dim=width * 3 // 2, use_bias=False, align_to=1,
                     dtype=dtype, param_dtype=param_dtype, name=name)


def _relu2_mlp(width: int, name: str, dtype, param_dtype) -> Mlp:
    """W2 relu(W1 x)^2: an un-gated feed-forward of two matrices."""
    return Mlp(hidden_dim=width, act=lambda h: jnp.square(nn.relu(h)),
               use_bias=False, dtype=dtype, param_dtype=param_dtype, name=name)


def causal_depthwise_conv(x, kernel):
    """y_t = sum_j kernel[j] * x_{t - (W-1) + j}: [B, T, C] by [W, C]."""
    w = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    t = x.shape[1]
    return sum(xp[:, j:j + t] * kernel[j] for j in range(w))


def a_log_init(key, shape, dtype=jnp.float32, lo=1.0):
    """A_log = log A, A uniform on [lo, 16) (the released code's init)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, 16.0)
                   ).astype(dtype)


def dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=1e-1, floor=0.0):
    """softplus(dt_bias) = dt, dt log-uniform on [lo, hi] and no less than
    ``floor`` (the released code's init, after Mamba's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(lo), jnp.log(hi)))
    if floor:
        dt = jnp.maximum(dt, floor)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class KDAMixer(nn.Module):
    num_heads: int
    head_dim: int
    conv_size: int = 4
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    chains_interpret: bool | None = None   # tests: ops/mixer_chains.py's

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, d = self.num_heads, self.head_dim
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        xc = x.astype(self.dtype)
        # The elementwise chains between the matmuls run in float32 and
        # hand on bfloat16 activations (g: float32), and a layer's backward
        # keeps their ends alone, not the dozen [tokens, heads * head_dim]
        # float32 planes in between. On the kernel path
        # (``mixer_chain_path``: a TPU, bfloat16, heads of 128, whole time
        # blocks) each is a kernel pair of ops/mixer_chains.py: the float32
        # lives in VMEM, the backward makes it again there from the chain's
        # inputs, and the [B, T, H, d] planes are written and read as the
        # delta rule's kernels lay them. On the plain path each is the XLA
        # chain below, rematerialised by itself (``jax.checkpoint``).
        fused = mixer_chain_path(
            t, (d,), (h,), self.dtype, interpret=self.chains_interpret
        )[0] == "kernel"
        chain = dict(interpret=self.chains_interpret)

        @functools.partial(jax.checkpoint, static_argnums=(2,))
        def plain_conv_act(y, kernel, normalise):
            y = nn.silu(causal_depthwise_conv(
                y.astype(jnp.float32), kernel.astype(jnp.float32)))
            y = y.reshape(b, t, h, d)
            if normalise:
                y = l2_normalize(y)
            return y.astype(self.dtype)

        def short_conv(name, normalise):
            y = _dense(h * d, ("embed", "heads"), f"{name}_proj", **kw)(xc)
            kernel = self.param(
                f"{name}_conv", part(trunc_normal_init(), (None, "heads")),
                (self.conv_size, h * d), self.param_dtype)
            if fused:
                return conv_silu_norm(y, (kernel,), (IN_ORDER,), (normalise,),
                                      d, **chain)[0]
            return plain_conv_act(y, kernel, normalise)

        q, k, v = (short_conv("q", True), short_conv("k", True),
                   short_conv("v", False))
        # the decay, one per key channel, in log space and float32
        a_log = self.param("A_log", part(a_log_init, ("heads",)),
                           (h,), self.param_dtype)
        dt_bias = self.param("dt_bias", part(dt_bias_init, ("heads",)),
                             (h * d,), self.param_dtype)
        f = _dense(d, ("embed", None), "f_a", **kw)(xc)
        f = _dense(h * d, (None, "heads"), "f_b", **kw)(f)

        @jax.checkpoint
        def plain_log_decay(f, a_log, dt_bias):
            return -jnp.exp(a_log.astype(jnp.float32))[:, None] \
                * jax.nn.softplus(
                    (f.astype(jnp.float32) + dt_bias.astype(jnp.float32))
                    .reshape(b, t, h, d))

        g = (log_decay(f, a_log, dt_bias, **chain) if fused
             else plain_log_decay(f, a_log, dt_bias))
        beta = jax.nn.sigmoid(
            _dense(h, ("embed", None), "b_proj", **kw)(xc).astype(jnp.float32))
        with jax.named_scope("kda_core"):
            # q's d^-0.5 goes in with the float32 products, after the
            # bf16 hand-over
            o = kda_chunked(q, k, v, g, beta, q_scale=d ** -0.5)
        gate = _dense(d, ("embed", None), "g_a", **kw)(xc)
        gate = _dense(h * d, (None, "heads"), "g_b", **kw)(gate)
        scale = self.param("o_norm_scale", part(nn.initializers.ones, (None,)),
                           (d,), self.param_dtype)

        @jax.checkpoint
        def gated_norm(o, gate, scale):
            ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            o = o * jax.lax.rsqrt(ms + self.eps) * scale.astype(jnp.float32)
            o = o * jax.nn.sigmoid(
                gate.astype(jnp.float32).reshape(b, t, h, d))
            return o.reshape(b, t, h * d).astype(self.dtype)

        o = (gated_rms_norm(o, gate, scale, IN_ORDER, "sigmoid", self.eps,
                            **chain)
             if fused else gated_norm(o, gate, scale))
        return _dense(x.shape[-1], ("heads", "embed"), "o_proj", **kw)(o)


GDN_A_FLOOR = 1e-6  # A uniform on [floor, 16): no A_log is -inf


class GDNMixer(nn.Module):
    """Gated DeltaNet: the delta rule with ONE decay a value head and
    token, ``value_heads`` heads on ``key_heads`` key heads."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    chains_interpret: bool | None = None   # tests: ops/mixer_chains.py's

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        if hv % hk:
            raise ValueError(f"{hv} value heads on {hk} key heads")
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        xc = x.astype(self.dtype)
        r, nk = hv // hk, hk * dk
        # the published grouping of the columns, a key head at a time:
        # [q dk | k dk | v r dv | z r dv] and [b r | a r]
        qkvz = _dense(2 * nk + 2 * hv * dv, ("embed", "heads"), "in_proj_qkvz",
                      **kw)(xc)
        # on the kernel path (KDAMixer's words; one head width) the chains
        # read q, k, v and z out of that grouping where they lie, a lane
        # group a head: nothing is concatenated and the plane stays
        # [B, T, columns]
        fused = mixer_chain_path(
            t, (dk, dv), (hk, hv), self.dtype, interpret=self.chains_interpret
        )[0] == "kernel"
        chain = dict(interpret=self.chains_interpret)
        # (first, n, per): n heads side by side from lane group ``first``
        # of every key head's 2 + 2 r
        layout = lambda first, n: (first, n, 2 + 2 * r)  # noqa: E731
        if not fused:
            qkvz = qkvz.reshape(b, t, hk, 2 * dk + 2 * r * dv)
        ba = _dense(2 * hv, ("embed", None), "in_proj_ba", **kw)(xc)
        ba = ba.reshape(b, t, hk, 2 * r).astype(jnp.float32)
        kernel = self.param(
            "conv", part(trunc_normal_init(), (None, "heads")),
            (self.conv_size, 2 * nk + hv * dv), self.param_dtype)

        # (the float32 chains between the matmuls: on the plain path
        # rematerialised by themselves, as KDAMixer's are)
        @jax.checkpoint
        def conv_act(qkvz, kernel):
            # ONE convolution over the joined channels: every q head, then
            # every k head, then every v head
            joined = jnp.concatenate([
                qkvz[..., :dk].reshape(b, t, nk),
                qkvz[..., dk:2 * dk].reshape(b, t, nk),
                qkvz[..., 2 * dk:2 * dk + r * dv].reshape(b, t, hv * dv)], -1)
            y = nn.silu(causal_depthwise_conv(
                joined.astype(jnp.float32), kernel.astype(jnp.float32)))
            # eps INSIDE the root, the released code's 1e-6
            unit = lambda u: l2_normalize(  # noqa: E731
                u.reshape(b, t, hk, dk), eps=1e-3).astype(self.dtype)
            return (unit(y[..., :nk]), unit(y[..., nk:2 * nk]),
                    y[..., 2 * nk:].reshape(b, t, hv, dv).astype(self.dtype))

        if fused:
            # the same ONE convolution: the taps are in the joined order,
            # q's then k's then v's; eps as above
            q, k, v = conv_silu_norm(
                qkvz, (kernel[:, :nk], kernel[:, nk:2 * nk], kernel[:, 2 * nk:]),
                (layout(0, 1), layout(1, 1), layout(2, r)),
                (True, True, False), dk, eps=1e-3, **chain)
        else:
            q, k, v = conv_act(qkvz, kernel)
        a_log = self.param(
            "A_log", part(functools.partial(a_log_init, lo=GDN_A_FLOOR),
                          ("heads",)), (hv,), self.param_dtype)
        dt_bias = self.param("dt_bias", part(nn.initializers.ones, ("heads",)),
                             (hv,), self.param_dtype)
        f32 = lambda u: u.astype(jnp.float32)  # noqa: E731
        beta = jax.nn.sigmoid(ba[..., :r].reshape(b, t, hv))
        g = -jnp.exp(f32(a_log)) * jax.nn.softplus(
            ba[..., r:].reshape(b, t, hv) + f32(dt_bias))
        with jax.named_scope("gdn_core"):
            # q and k at the key heads and ONE decay a value head: the
            # gate's rank tells ops/kda.py which delta rule this is
            o = kda_chunked(q, k, v, g, beta, q_scale=dk ** -0.5)
        scale = self.param("o_norm_scale", part(nn.initializers.ones, (None,)),
                           (dv,), self.param_dtype)

        @jax.checkpoint
        def gated_norm(o, qkvz, scale):
            ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            o = o * jax.lax.rsqrt(ms + self.eps) * f32(scale)
            o = o * nn.silu(f32(qkvz[..., 2 * dk + r * dv:]).reshape(b, t, hv, dv))
            return o.reshape(b, t, hv * dv).astype(self.dtype)

        o = (gated_rms_norm(o, qkvz, scale, layout(2 + r, r), "silu",
                            self.eps, **chain)
             if fused else gated_norm(o, qkvz, scale))
        return _dense(x.shape[-1], ("heads", "embed"), "o_proj", **kw)(o)


def conv_taps_init(key, shape, dtype=jnp.float32):
    """Uniform on +-1/sqrt(W): a depthwise ``Conv1d``'s default."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound
                              ).astype(dtype)


class ShortConvMixer(nn.Module):
    """y = W_out (C * conv(B * u)), [B ; C ; u] = W_in x (the module's
    docstring, **Gated short convolution**). The chain between the two
    matmuls keeps its bfloat16 input plane for the backward and nothing
    else, on either path (``KDAMixer``'s words)."""

    conv_size: int = 3
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    chains_interpret: bool | None = None   # tests: ops/mixer_chains.py's

    @nn.compact
    def __call__(self, x):
        t, c = x.shape[1:]
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        plane = _dense(3 * c, ("embed", "heads"), "in_proj", **kw)(
            x.astype(self.dtype))
        taps = self.param("conv", part(conv_taps_init, (None, "heads")),
                          (self.conv_size, c), self.param_dtype)
        fused = mixer_chain_path(
            t, (c,), (), self.dtype, interpret=self.chains_interpret
        )[0] == "kernel"

        @jax.checkpoint
        def plain_chain(plane, taps):
            gate, mid, u = (plane[..., i * c:(i + 1) * c].astype(jnp.float32)
                            for i in range(3))
            return (mid * causal_depthwise_conv(
                gate * u, taps.astype(jnp.float32))).astype(self.dtype)

        with jax.named_scope("sconv_chain"):
            y = (gated_short_conv(plane, taps, interpret=self.chains_interpret)
                 if fused else plain_chain(plane, taps))
        return _dense(c, ("heads", "embed"), "out_proj", **kw)(y)


class Mamba2Mixer(nn.Module):
    """y = W_out gnorm((ssd(u, B, C, dt) + D u) * SiLU(z)), [z ; xBC ; dt] =
    W_in x, [u ; B ; C] = SiLU(conv(xBC) + c) (the module's docstring,
    **SSD**). The recurrence is ``ops/ssd.py``'s; everything else between
    the two matmuls is two float32 chains with bfloat16 ends: on a TPU at
    bfloat16, whole time blocks and lane-tiled widths the kernel pairs
    ``ops/mixer_chains.py ssm_conv_silu`` and ``ssm_gate_norm``, which cut
    their blocks from in_proj's plane and the convolution's where they
    lie (``ssm_chain_path`` says which and why; softplus(dt + dt_bias)
    over ``heads`` lanes stays XLA's); elsewhere the plain XLA chains
    below, each rematerialised by itself. Either way a block's backward
    keeps the chains' ends, not the float32 planes in between
    (``KDAMixer``'s words)."""

    num_heads: int
    head_dim: int
    groups: int
    state: int
    conv_size: int = 4
    eps: float = 1e-5
    time_step_limits: tuple = (1e-3, 1e-1, 1e-4)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    core_interpret: bool | None = None   # tests: ops/ssd.py ssd_path's
    chains_interpret: bool | None = None   # tests: ops/mixer_chains.py's

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, p, g, n = self.num_heads, self.head_dim, self.groups, self.state
        inner, joined = h * p, h * p + 2 * g * n
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
        chain = dict(interpret=self.chains_interpret)
        fused = ssm_chain_path(t, inner, joined, g, self.dtype,
                               **chain)[0] == "kernel"
        # [z | xBC | dt], the published order of in_proj's columns (and
        # under the kernels zeros up to a whole lane tile after them)
        plane = _dense(inner + joined + h, ("embed", "heads"), "in_proj",
                       lane_tiled=fused, **kw)(x.astype(self.dtype))
        taps = self.param("conv", part(conv_taps_init, (None, "heads")),
                          (self.conv_size, joined), self.param_dtype)
        bias = self.param("conv_bias", part(nn.initializers.zeros, ("heads",)),
                          (joined,), self.param_dtype)
        a_log = self.param("A_log", part(a_log_init, ("heads",)), (h,),
                           self.param_dtype)
        lo, hi, floor = self.time_step_limits
        dt_bias = self.param(
            "dt_bias", part(functools.partial(dt_bias_init, lo=lo, hi=hi,
                                              floor=floor), ("heads",)),
            (h,), self.param_dtype)
        skip = self.param("D", part(nn.initializers.ones, ("heads",)), (h,),
                          self.param_dtype)
        scale = self.param("norm_scale", part(nn.initializers.ones, ("heads",)),
                           (inner,), self.param_dtype)

        @jax.checkpoint
        def conv_act(plane, taps, bias, dt_bias):
            xbc = nn.silu(causal_depthwise_conv(
                f32(plane[..., inner:inner + joined]), f32(taps)) + f32(bias))
            dt = jax.nn.softplus(f32(plane[..., inner + joined:])
                                 + f32(dt_bias))
            return xbc.astype(self.dtype), dt

        @jax.checkpoint
        def gated_group_norm(y, xbc, plane, skip, scale):
            # the gate BEFORE the norm, and the norm over a GROUP's channels
            y = f32(y).reshape(b, t, h, p) + f32(skip)[:, None] * f32(
                xbc[..., :inner]).reshape(b, t, h, p)
            y = y.reshape(b, t, inner) * nn.silu(f32(plane[..., :inner]))
            y = y.reshape(b, t, g, inner // g)
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), axis=-1, keepdims=True) + self.eps)
            return (y.reshape(b, t, inner) * f32(scale)).astype(self.dtype)

        @jax.checkpoint
        def step_size(plane, dt_bias):   # conv_act's, of a lane-tiled plane
            return jax.nn.softplus(
                f32(plane[..., inner + joined:inner + joined + h])
                + f32(dt_bias))

        with jax.named_scope("ssm_chain"):
            if fused:
                xbc = ssm_conv_silu(plane, taps, bias, first=inner, **chain)
                dt = step_size(plane, dt_bias)
            else:
                xbc, dt = conv_act(plane, taps, bias, dt_bias)
        with jax.named_scope("ssd_core"):
            y = ssd_chunked(xbc, dt, -jnp.exp(f32(a_log)), h, p, g, n,
                            interpret=self.core_interpret)
        with jax.named_scope("ssm_chain"):
            y = (ssm_gate_norm(y, xbc, plane, jnp.repeat(f32(skip), p), scale,
                               g, self.eps, **chain)
                 if fused else gated_group_norm(y, xbc, plane, skip, scale))
        return _dense(x.shape[-1], ("heads", "embed"), "out_proj", **kw)(y)


class MLAMixer(nn.Module):
    """Latent attention (the module's docstring, **MLA**): ``rope_theta``
    None carries the qk_rope channels unturned, a number turns them,
    neighbouring channels a pair."""

    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    eps: float = 1e-5
    rope_theta: float | None = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32
    core_interpret: bool | None = None   # tests: latent_attention_path's

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h = self.num_heads
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        xc = x.astype(self.dtype)
        # the kernel pair reads q, kvb and the ONE shared key where the
        # projections leave them; the plain tiles want a key a head
        latent = latent_attention_path(
            t, h, (nope, rope, dv), self.core_interpret, dtype=self.dtype,
            reduce_dtype=self.reduce_dtype)[0] == "kernel"
        q = _dense(h * (nope + rope), ("embed", "heads"), "q_proj", **kw)(xc)
        if not latent:
            q = q.reshape(b, t, h, nope + rope)
        kva = _dense(self.kv_lora_rank + rope, ("embed", None), "kv_a", **kw)(xc)
        c, kpe = kva[..., :self.kv_lora_rank], kva[..., self.kv_lora_rank:]
        c = RMSNorm(epsilon=self.eps, param_dtype=self.param_dtype,
                    name="kv_a_norm")(c)
        kvb = _dense(h * (nope + dv), (None, "heads"), "kv_b", **kw)(c)
        if latent:
            if self.rope_theta is not None:
                # q's rope channels are turned in the kernels, neighbours
                # staying neighbours: the shared key likewise, here
                with jax.named_scope("mla_rope"):
                    kpe = rope_apply_pairs(
                        kpe, *token_rope_pair_sincos(t, rope, self.rope_theta))
            with jax.named_scope("mla_core"):
                o = latent_attention(q, kvb, kpe, self.rope_theta,
                                     interpret=bool(self.core_interpret))
            return _dense(x.shape[-1], ("heads", "embed"), "o_proj", **kw)(o)
        kvb = kvb.reshape(b, t, h, nope + dv)
        if self.rope_theta is not None:
            # ONE [B, T, 1, rope] turn of the shared key, not one a head;
            # float32 tables: the turn itself is float32, its ends bf16
            with jax.named_scope("mla_rope"):
                table = token_rope_pair_sincos(t, rope, self.rope_theta)
                q = rope_apply_interleaved(q, *table)
                kpe = rope_apply_interleaved(kpe[:, :, None, :], *table)[:, :, 0]
        with jax.named_scope("mla_core"):
            # kpe, turned or not (mla_use_nope), is one more slice of the
            # key, the same for every head
            k = jnp.concatenate([
                kvb[..., :nope],
                jnp.broadcast_to(kpe[:, :, None, :], (b, t, h, rope))], axis=-1)
            o = dispatch_attention(q, k, kvb[..., nope:], causal=True,
                                   reduce_dtype=self.reduce_dtype)
        return _dense(x.shape[-1], ("heads", "embed"), "o_proj", **kw)(
            o.reshape(b, t, h * dv))


class GQAMixer(nn.Module):
    """Grouped-query attention: ``window`` None is every key up to the
    query's own, ``rope_theta`` None no rotation, ``rotary_dim`` None the
    whole head rotated (else its leading channels). ``output_gate``: the
    q projection makes [q ; gate] a head and the core's output goes
    through sigmoid(gate). ``qk_norm``: given a name, makes the norm that
    every q head (``q_norm``) and every k head (``k_norm``) goes through
    before the rotation, one ``[head_dim]`` scale each."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None = None
    rope_theta: float | None = None
    rotary_dim: int | None = None
    output_gate: bool = False
    qk_norm: Callable[[str], nn.Module] | None = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        xc = x.astype(self.dtype)
        q = _dense(h * d * (2 if self.output_gate else 1), ("embed", "heads"),
                   "q_proj", **kw)(xc)
        k = _dense(hk * d, ("embed", "heads"), "k_proj", **kw)(xc)
        v = _dense(hk * d, ("embed", "heads"), "v_proj", **kw)(xc)
        if self.output_gate:
            q = q.reshape(b, t, h, 2 * d)
            q, gate = q[..., :d], q[..., d:]
        q, k, v = (q.reshape(b, t, h, d), k.reshape(b, t, hk, d),
                   v.reshape(b, t, hk, d))
        if self.qk_norm is not None:
            q, k = self.qk_norm("q_norm")(q), self.qk_norm("k_norm")(k)
        if self.rope_theta is not None:
            # float32 tables: the turn itself is float32, its ends bf16
            q, k = rope_apply_leading(q, k, *token_rope_sincos(
                t, self.rotary_dim or d, self.rope_theta))
        with jax.named_scope("gqa_core"):
            o = dispatch_attention(q, k, v, causal=True, window=self.window,
                                   reduce_dtype=self.reduce_dtype)
        if self.output_gate:
            o = (o.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
        return _dense(x.shape[-1], ("heads", "embed"), "o_proj", **kw)(
            o.reshape(b, t, h * d))


class DSAMixer(nn.Module):
    """Grouped-query attention over the ``topk`` keys a learned indexer
    selects for each query (the module's docstring, **DSA**): ``(y,
    {"index_loss", "select_excess"[, "selection"]})``. ``qk_norm`` as
    ``GQAMixer``'s. While a sequence is no longer than ``topk`` every
    query keeps every key up to its own (the selection is the causal
    triangle) and the core's output is the dense causal one's, bit for
    bit; the index loss is there all the same.

    The selection reaches the core as an operand, [B, T, T] int8, made
    from two int32 a query (the threshold and the last tie kept). The
    core runs first and hands on, beside its output, its rows'
    log-sum-exp over the selected keys where it ran the kernels (None
    where it ran the plain tiles): the index loss makes its target from
    it and runs no attention pass of its own. A
    rematerialised layer makes both again in its backward: kept across
    the layer's remat (``save_only_these_names``) they gave a wrong
    gradient on the chip, cause not found (PERF.md section 6, PR 39).
    ``keep_selection`` adds the plane as packed bits, for a caller that
    has to follow it."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    index_heads: int
    index_head_dim: int
    topk: int
    chunk: int
    qk_norm: Callable[[str], nn.Module]
    eps: float = 1e-6
    keep_selection: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        hi, di = self.index_heads, self.index_head_dim
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        xc = x.astype(self.dtype)
        q = _dense(h * d, ("embed", "heads"), "q_proj", **kw)(xc)
        k = _dense(hk * d, ("embed", "heads"), "k_proj", **kw)(xc)
        v = _dense(hk * d, ("embed", "heads"), "v_proj", **kw)(xc)
        q, k, v = (q.reshape(b, t, h, d), k.reshape(b, t, hk, d),
                   v.reshape(b, t, hk, d))
        q, k = self.qk_norm("q_norm")(q), self.qk_norm("k_norm")(k)
        q, k = rope_apply_full(q, k, *token_rope_sincos(t, d, self.rope_theta))
        with jax.named_scope("dsa_index"):
            hb = jax.lax.stop_gradient(xc)
            qi = _dense(hi * di, ("embed", "heads"), "index_q_proj", **kw)(hb)
            ki = _dense(di, ("embed", None), "index_k_proj", **kw)(hb)
            ki = LayerNorm(epsilon=self.eps, param_dtype=self.param_dtype,
                           fused=False, name="index_k_norm")(ki)
            a = _dense(hi, ("embed", None), "index_w_proj", **kw)(hb)
            a = a.astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
            qi, ki = rope_apply_full(
                qi.reshape(b, t, hi, di), ki[:, :, None, :],
                *token_rope_sincos(t, di, self.rope_theta))
            ki = ki[:, :, 0]
        if t > self.topk:
            thr, last = select_thresholds(qi, ki, a, topk=self.topk,
                                          chunk=self.chunk)
        else:  # every key up to the query's own
            thr = jnp.full((b, t), INT_MIN, jnp.int32)
            last = jnp.full((b, t), t, jnp.int32)
        plane, excess = selection_plane(qi, ki, a, thr, last, topk=self.topk,
                                        chunk=self.chunk)
        with jax.named_scope("dsa_core"):
            o, lse = dispatch_attention(
                q, k, v, causal=True, reduce_dtype=self.reduce_dtype,
                selection=plane)
        loss = index_loss(qi, ki, a, plane, *jax.lax.stop_gradient((q, k, lse)),
                          self.chunk)
        aux = {"index_loss": loss, "select_excess": excess}
        if self.keep_selection:
            aux["selection"] = pack_selection(plane)
        return _dense(x.shape[-1], ("heads", "embed"), "o_proj", **kw)(
            o.reshape(b, t, h * d)), aux


class DecoderLayer(nn.Module):
    """A block: a mixer and a feed-forward part, each pre-normed (``norm1``,
    ``norm2``) with its own residual add — or ONE of the two (the other
    None: ``nemotron_h``), then one ``norm`` and one residual add."""

    mixer: str | None          # "kda" | "mla" | "swa" | "full_attn" | "gdn"
                               # | "gated_attn" | "dsa" | "conv" | "ssm"
    ffn: str | None            # "dense" | "moe"
    cfg: Any                   # the frozen ``DecoderConfig``
    keep_selection: bool = False   # "dsa": the selection among the aux

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        kw = dict(dtype=c.dtype, param_dtype=c.param_dtype)
        norm = lambda name: RMSNorm(  # noqa: E731
            epsilon=c.rms_norm_eps, param_dtype=c.param_dtype,
            zero_centered=c.zero_centered_norms, name=name)
        # a phase holds its pre-norm and its residual add: what is left
        # outside every phase is what the compiler makes between layers
        x_in = x
        norm1 = "norm1" if self.ffn is not None else "norm"
        norm2 = "norm2" if self.mixer is not None else "norm"
        if self.mixer is None:
            pass
        elif self.mixer == "ssm":
            with step_phase("ssm_mixer"):
                y = Mamba2Mixer(c.mamba_num_heads, c.mamba_head_dim,
                                c.mamba_n_groups, c.ssm_state_size,
                                c.short_conv_kernel_size, c.rms_norm_eps,
                                c.time_step_limits, name="ssm", **kw)(
                                    norm(norm1)(x))
                x = x + y.astype(x.dtype)
        elif self.mixer == "kda":
            with step_phase("kda_mixer"):
                y = KDAMixer(c.kda_num_heads, c.kda_head_dim,
                             c.short_conv_kernel_size, c.rms_norm_eps,
                             name="kda", **kw)(norm("norm1")(x))
                x = x + y.astype(x.dtype)
        elif self.mixer == "mla":
            with step_phase("mla_mixer"):
                y = MLAMixer(c.num_attention_heads, c.kv_lora_rank,
                             c.qk_nope_head_dim, c.qk_rope_head_dim,
                             c.v_head_dim, c.rms_norm_eps,
                             c.rope_theta if c.mla_rotary else None,
                             reduce_dtype=c.reduce_dtype, name="mla", **kw)(
                                 norm("norm1")(x))
                x = x + y.astype(x.dtype)
        elif self.mixer == "gdn":
            with step_phase("gdn_mixer"):
                y = GDNMixer(c.linear_num_key_heads, c.linear_num_value_heads,
                             c.linear_key_head_dim, c.linear_value_head_dim,
                             c.linear_conv_kernel_dim, c.rms_norm_eps,
                             name="gdn", **kw)(norm("norm1")(x))
                x = x + y.astype(x.dtype)
        elif self.mixer == "dsa":
            with step_phase("dsa_mixer"):
                y, index_aux = DSAMixer(
                    c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                    c.rope_theta, c.index_num_heads, c.index_head_dim,
                    c.index_topk, c.index_chunk, norm, c.rms_norm_eps,
                    self.keep_selection, reduce_dtype=c.reduce_dtype,
                    name="attn", **kw)(norm("norm1")(x))
                x = x + y.astype(x.dtype)
        elif self.mixer == "conv":
            with step_phase("sconv_mixer"):
                y = ShortConvMixer(c.short_conv_kernel_size, name="conv",
                                   **kw)(norm("norm1")(x))
                x = x + y.astype(x.dtype)
        else:
            # "swa": a window and rotary; "full_attn": neither (``lfm2_moe``:
            # a rotary and normed q and k heads); "gated_attn": no window, a
            # partial rotary, an output gate and the layer's kind of norm on
            # q and k
            gated = self.mixer == "gated_attn"
            plain = self.mixer == "full_attn" and not c.full_attn_rotary
            with step_phase(f"{self.mixer}_mixer"):
                y = GQAMixer(
                    c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                    c.sliding_window if self.mixer == "swa" else None,
                    None if plain else c.rope_theta,
                    c.rotary_dim or None, gated,
                    norm if gated or c.attn_qk_norm else None,
                    reduce_dtype=c.reduce_dtype, name="attn", **kw)(
                        norm(norm1)(x))
                x = x + y.astype(x.dtype)
        aux = None
        if self.ffn is None:
            pass
        elif self.ffn == "dense":
            with step_phase("dense_ffn"):
                y = _swiglu(c.intermediate_size, "mlp", **kw)(norm("norm2")(x))
                x = x + y.astype(x.dtype)
        else:
            with step_phase("moe_ffn"):
                y = norm(norm2)(x)
                routed, aux = RoutedExpertsFFN(
                    c.moe_intermediate_size, c.num_experts,
                    c.num_experts_per_token, c.expert_shards, c.expert_shard,
                    c.routed_scaling_factor, c.expert_rows_factor,
                    router=c.router, gate=c.gate,
                    norm_eps=c.router_norm_eps, name="experts", **kw)(
                        y, x_in if c.router_reads_layer_input else None)
                if c.num_shared_experts:
                    with jax.named_scope("moe_shared"):
                        shared = (
                            _relu2_mlp(c.shared_expert_width, "shared", **kw)
                            if c.gate == "relu2" else _swiglu(
                                c.moe_intermediate_size * c.num_shared_experts,
                                "shared", **kw))(y)
                        if c.shared_expert_gate:
                            shared = shared * jax.nn.sigmoid(_dense(
                                1, ("embed", None), "shared_gate", **kw)(
                                    y.astype(c.dtype)).astype(jnp.float32)
                            ).astype(shared.dtype)
                    routed = routed + shared
                x = x + routed.astype(x.dtype)
        if self.mixer == "dsa":
            aux = {**(aux or {}), **index_aux}
        return x, aux


class LMDecoder(nn.Module):
    """``__call__(tokens)`` -> logits [B, T, V] float32 (small sizes);
    ``__call__(tokens, with_loss=True)`` -> (loss, aux): the mean
    next-token cross-entropy over positions 0..T-2 of every sequence, and
    the routed layers' ``choice`` [L_moe, B*T, K], ``rows``, ``capacity``,
    ``overflow`` and ``load_max_over_mean`` stacked [L_moe]; of ``dsa``
    layers also ``index_loss`` and ``select_excess`` [L] and, with
    ``with_selection``, ``selection`` [L, B, T, T / 8] uint8 (each query's
    kept keys as packed bits). The index losses are NOT in ``loss``."""

    cfg: Any

    @property
    def embed_dim(self) -> int:
        return self.cfg.hidden_size

    @nn.compact
    def __call__(self, tokens, with_loss: bool = False,
                 with_selection: bool = False):
        c = self.cfg
        b, t = tokens.shape
        table = self.param(
            "token_embed", part(trunc_normal_init(), ("vocab", "embed")),
            (c.vocab_size, c.hidden_size), c.param_dtype)
        with step_phase("lm_embed"):
            x = jnp.take(table.astype(c.dtype), tokens, axis=0)
        # a layer is rematerialised: the backward pass keeps the [B, T, D]
        # residual stream between layers and makes a layer's inside again
        layer_cls = nn.remat(DecoderLayer)
        auxes = []
        for i, (mixer, ffn) in enumerate(c.layers):
            x, aux = layer_cls(mixer, ffn, c, with_selection,
                               name=f"layers_{i}")(x)
            if aux is not None:
                auxes.append(aux)
        aux = ({k: jnp.stack([a[k] for a in auxes]) for k in auxes[0]}
               if auxes else {})
        # tied: the head IS the embedding table, one leaf read twice
        head = table.T if c.tie_word_embeddings else self.param(
            "lm_head", part(trunc_normal_init(), ("embed", "vocab")),
            (c.hidden_size, c.vocab_size), c.param_dtype)
        with step_phase("lm_head_loss"):
            x = RMSNorm(epsilon=c.rms_norm_eps, param_dtype=c.param_dtype,
                        zero_centered=c.zero_centered_norms, name="norm")(x)
            if not with_loss:
                return jnp.einsum("btd,dv->btv", x.astype(c.dtype),
                                  head.astype(c.dtype),
                                  preferred_element_type=jnp.float32)
            loss = next_token_loss(x.astype(c.dtype), head.astype(c.dtype),
                                   tokens)
        return loss, aux


LOSS_BLOCK = 2048  # tokens whose [block, V] logits exist at a time


def next_token_loss(x, head, tokens, block: int = LOSS_BLOCK):
    """Mean over b, t < T-1 of logsumexp(x_bt W) - (x_bt W)[token_b,t+1],
    float32, the [block, V] logits of one block of tokens at a time
    (rematerialised: the backward pass makes them again)."""
    b, t, d = x.shape
    targets = jnp.roll(tokens, -1, axis=1).reshape(-1)
    counted = (jnp.arange(t) < t - 1)[None].repeat(b, 0).reshape(-1)
    n = b * t
    block = min(block, n)
    pad = (-n) % block
    xs = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))
    counted = jnp.pad(counted, (0, pad))

    @jax.checkpoint
    def one(args):
        xb, tb, cb = args
        logits = jnp.dot(xb, head, preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(cb, nll, 0.0))

    shape = lambda a: a.reshape((-1, block) + a.shape[1:])  # noqa: E731
    sums = jax.lax.map(one, (shape(xs), shape(targets), shape(counted)))
    return jnp.sum(sums) / (b * (t - 1))
