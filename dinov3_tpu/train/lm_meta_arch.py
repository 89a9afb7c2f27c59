"""The meta-arch of a next-token step: one student, no teacher.

It stands where ``SSLMetaArch`` stands in ``build_train_setup`` and
``make_train_step``: the same ``init_params`` / ``init_state`` /
``forward`` contract, so the step's skeleton (rng fold, value_and_grad,
the fused clip + AdamW pass, the telemetry ring), the schedules, the
param groups and the checkpoints are the SSL step's own. What it lacks
it lacks outright: ``params`` has no ``teacher`` entry, the update has
no EMA leg (``ema_teacher`` is False), and the scalars ``teacher_temp``
and ``momentum`` reach ``forward`` and are not read.

It carries no non-param state (``TrainState.center_state`` is empty).
``routing`` gives what each token's router picks over all the experts,
for whoever asks (the benchmark's reference has to follow the same
routing); the step itself keeps none of it. ``selection`` gives beside
it the keys each query of a sparse-attention layer keeps.

A decoder whose layers select their keys (``dsa``) trains its indexers by
a loss of their own: the step minimises the next-token loss + the sum over
layers of the index loss (``models/decoder.py``: each reaches its own
leaves alone), ``lm_loss`` stays the next-token loss and ``lm_index_loss``
is that sum.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from dinov3_tpu.configs import ConfigNode
from dinov3_tpu.models import build_backbone
from dinov3_tpu.ops.causal_attention import (
    causal_attention_path,
    index_loss_path,
    latent_attention_path,
)
from dinov3_tpu.ops.ffn import routed_rows_capacity
from dinov3_tpu.ops.grouped_matmul import grouped_matmul_path
from dinov3_tpu.ops.kda import kda_path
from dinov3_tpu.ops.mixer_chains import mixer_chain_path, ssm_chain_path
from dinov3_tpu.ops.routed_rows import combine_form
from dinov3_tpu.ops.ssd import ssd_path

logger = logging.getLogger("dinov3")


class LMMetaArch:
    # what build_train_setup / make_train_step ask of a meta-arch
    rng_plan = False
    crop_packing = False
    distillation = False
    ema_teacher = False
    supports_accum = False
    teacher_source = "in_step"
    zero3_gather = False
    zero3_buckets = False

    def __init__(self, cfg: ConfigNode):
        self.cfg = cfg
        # masters are float32 whatever the policy stores (ssl_meta_arch.py)
        self.student_backbone = build_backbone(cfg, param_dtype=jnp.float32)
        self.embed_dim = self.student_backbone.embed_dim
        dc = self.student_backbone.cfg
        # the paths are static a shape: which calls take a kernel IS how
        # often it engages (the trace then names the kernels)
        rows = (int(cfg.train.batch_size_per_device), int(cfg.lm.seq_len))
        heads = dc.num_attention_heads
        gqa = ((heads, dc.head_dim),) + ((dc.num_key_value_heads, dc.head_dim),) * 2
        cores = {  # the scope, the (heads, width) of q, k, v and the window
            "swa": ("gqa_core", gqa, dc.sliding_window),
            "full_attn": ("gqa_core", gqa, None),
            "gated_attn": ("gqa_core", gqa, None),
            # under a per-query selection: an operand, not a shape
            "dsa": ("dsa_core", gqa, None)}
        gdn_heads = (dc.linear_num_key_heads, dc.linear_num_value_heads)
        delta = {  # the core's scope, the delta rule's (key, value) widths,
            # the head counts of the planes the mixer's chains lay out and,
            # where the gate is ONE decay a value head, those of its call
            "kda": ("kda_core", dc.kda_head_dim, dc.kda_head_dim,
                    (dc.kda_num_heads,), None),
            "gdn": ("gdn_core", dc.linear_key_head_dim, dc.linear_value_head_dim,
                    gdn_heads, gdn_heads)}
        held = dc.num_experts // dc.expert_shards
        tokens = rows[0] * rows[1]
        cap = routed_rows_capacity(
            tokens, dc.num_experts_per_token, dc.num_experts, held,
            dc.expert_rows_factor)
        for i, (mixer, ffn) in enumerate(dc.layers, 1):
            if ffn == "moe":
                path, why = grouped_matmul_path(
                    cap, dc.hidden_size, dc.moe_intermediate_size, dc.dtype,
                    gate=dc.gate)
                logger.info(
                    "layer %d moe_experts, both passes: %s (%s); rows moved "
                    "by gathers through index lists, the combine %s at %.1f "
                    "(token, choice) pairs a buffer row", i, path, why,
                    combine_form(tokens, cap, dc.hidden_size),
                    tokens * dc.num_experts_per_token / cap)
            if mixer is None:   # a block of the feed-forward part alone
                continue
            if mixer == "ssm":
                path, why = ssd_path(
                    dc.mamba_num_heads, dc.mamba_head_dim, dc.mamba_n_groups,
                    dc.ssm_state_size, rows[1], dc.dtype)
                logger.info("layer %d ssd_core, both passes: %s (%s)", i, path,
                            why)
                inner = dc.mamba_num_heads * dc.mamba_head_dim
                path, why = ssm_chain_path(
                    rows[1], inner,
                    inner + 2 * dc.mamba_n_groups * dc.ssm_state_size,
                    dc.mamba_n_groups, dc.dtype)
                logger.info("layer %d ssm_mixer's chains, both passes: %s (%s)",
                            i, path, why)
            elif mixer in delta:
                scope, dk, dv, heads, gate_heads = delta[mixer]
                path, why = kda_path(dk, dv, gate_heads=gate_heads)
                logger.info("layer %d %s, both passes: %s (%s)", i, scope, path, why)
                path, why = mixer_chain_path(rows[1], (dk, dv), heads, dc.dtype)
                logger.info("layer %d %s_mixer's chains, both passes: %s (%s)",
                            i, mixer, path, why)
            elif mixer == "conv":
                path, why = mixer_chain_path(rows[1], (dc.hidden_size,), (),
                                             dc.dtype)
                logger.info("layer %d sconv_chain (conv), both passes: %s (%s)",
                            i, path, why)
            elif mixer == "mla":
                path, why = latent_attention_path(
                    rows[1], dc.num_attention_heads,
                    (dc.qk_nope_head_dim, dc.qk_rope_head_dim, dc.v_head_dim),
                    dtype=dc.dtype, reduce_dtype=dc.reduce_dtype)
                logger.info("layer %d mla_core (mla), both passes: %s (%s)", i,
                            path, why)
            else:
                scope, shapes, window = cores[mixer]
                path, why = causal_attention_path(
                    tuple(rows + s for s in shapes), window, dtype=dc.dtype,
                    reduce_dtype=dc.reduce_dtype)
                logger.info("layer %d %s (%s), both passes: %s (%s)", i, scope,
                            mixer, path, why)
                if mixer == "dsa":
                    path, why = index_loss_path(
                        tuple(rows + s for s in shapes), dc.index_head_dim,
                        dtype=dc.dtype, reduce_dtype=dc.reduce_dtype)
                    logger.info("layer %d dsa_index_loss, both passes: %s (%s)",
                                i, path, why)

    def init_params(self, rng: jax.Array, batch: dict, unbox: bool = True) -> dict:
        import flax.linen as nn

        variables = self.student_backbone.init(rng, batch["tokens"])
        params = {"student": {"backbone": variables["params"]}}
        return nn.meta.unbox(params) if unbox else params

    def init_state(self) -> dict:
        return {}

    def routing(self, student_params, batch) -> jax.Array:
        """[routed layers, tokens, top_k] int32: the experts, out of all
        of them, that each token's router chooses under these weights."""
        _, aux = self.student_backbone.apply(
            {"params": student_params["backbone"]}, batch["tokens"],
            with_loss=True)
        return aux["choice"]

    def selection(self, student_params, batch) -> tuple:
        """(``routing``'s choices, [sparse-attention layers, B, T, T / 8]
        uint8: the keys each query keeps under these weights, one bit a
        key, ``numpy.unpackbits``'s order)."""
        _, aux = self.student_backbone.apply(
            {"params": student_params["backbone"]}, batch["tokens"],
            with_loss=True, with_selection=True)
        return aux["choice"], aux["selection"]

    def _zero3_gather_params(self, tree):
        return tree

    def forward(self, student_params, frozen_params, batch, *, state, **_):
        """(loss, (metrics, new_state)); what else the step hands every
        meta-arch (iteration, teacher temperature, rng streams) is not
        read. A routed layer whose compact row
        buffer overflowed left tokens out: the loss is then NaN, which
        the loop's non-finite watchdog stops on, and ``moe_rows_overflow``
        says by how many pairs. A sparse-attention layer whose selection
        miscounted (``dsa_select_excess``: queries whose kept keys are not
        min(t + 1, topk)) does the same."""
        loss, aux = self.student_backbone.apply(
            {"params": student_params["backbone"]}, batch["tokens"],
            with_loss=True)
        metrics = {"lm_loss": loss}
        if aux:
            overflow = jnp.sum(aux["overflow"])
            loss = jnp.where(overflow > 0, jnp.nan, loss)
            metrics.update(
                moe_rows_fill=jnp.max(aux["rows"] / aux["capacity"]),
                moe_rows_overflow=overflow,
                moe_load_max_over_mean=jnp.max(aux["load_max_over_mean"]))
        if "index_loss" in aux:
            index_loss = jnp.sum(aux["index_loss"])
            excess = jnp.sum(aux["select_excess"])
            loss = jnp.where(excess > 0, jnp.nan, loss + index_loss)
            metrics.update(lm_index_loss=index_loss, dsa_select_excess=excess)
        metrics["total_loss"] = loss
        return loss, (metrics, state)
