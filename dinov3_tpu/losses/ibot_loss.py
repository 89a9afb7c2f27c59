"""iBOT masked-patch loss (functional, static-shape row buffers).

(reference: dinov3_jax/loss/ibot_patch_loss.py. Differences by design:
- operates on a static-shape [M, K] buffer of masked-token rows with an
  explicit validity/weight vector — TPU-static shapes, no data-dependent
  slicing (SURVEY.md §7.3). The functions here are agnostic of what M is:
  the train step hands them its batch-wide compact buffer (``M_c`` rows,
  the masked tokens the batch has plus under 128 rows of padding —
  train/ssl_meta_arch.py ``masked_rows``), the tests also the per-image
  worst-case ``2B * M_img`` rows; padding rows carry weight 0 either way;
- the per-image mask weighting the reference commented out (:66, a latent
  bug per SURVEY.md §2.9.6) is applied;
- the sinkhorn variant's effective count is ``sum(weights > 0)``, the
  global masked-patch count, matching the psum of ``n_masked_patches``.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dinov3_tpu.losses.sinkhorn import sinkhorn_knopp


def sinkhorn_knopp_teacher_masked(
    teacher_logits: jnp.ndarray,
    teacher_temp: float | jnp.ndarray,
    valid: jnp.ndarray,
    n_iterations: int = 3,
) -> jnp.ndarray:
    """[M, K] padded masked-token logits; valid: [M] 0/1."""
    return sinkhorn_knopp(
        teacher_logits, teacher_temp, n_iterations, row_weights=valid
    )


def ibot_patch_loss_from_parts(
    dot: jnp.ndarray,
    qsum: jnp.ndarray,
    lse: jnp.ndarray,
    masks_weight: jnp.ndarray,
    n_images: int,
) -> jnp.ndarray:
    """Per-row CE parts -> scalar iBOT loss.

    dot: [M] <q_m, x_m>; qsum: [M] sum_k q_m; lse: [M] logsumexp(x_m);
    masks_weight: [M] with 1/(masked tokens in that image) for valid
    entries, 0 for padding; n_images: global number of mask rows.
    loss = -sum_m w_m * <q_m, log p_m> / n_images == mean over images of
    the mean CE over that image's masked tokens (PyTorch DINOv3
    semantics). Shared by the materialized and streaming (losses/
    streaming.py) paths so the weighting cannot drift between them.
    """
    per_token = dot - qsum * lse
    return -jnp.sum(per_token * masks_weight) / max(n_images, 1)


def ibot_patch_loss_masked(
    student_logits: jnp.ndarray,
    teacher_probs: jnp.ndarray,
    masks_weight: jnp.ndarray,
    n_images: int,
    student_temp: float = 0.1,
) -> jnp.ndarray:
    """CE on masked tokens (materialized-targets oracle).

    student_logits/teacher_probs: [M, K] padded buffers.
    """
    # CE without materializing log-probs: <q, logp> = <q, x> - sum(q)*lse(x)
    # — the [M, K] fp32 log_softmax buffer (65k-262k prototypes) never
    # exists; x is read in its storage dtype with fp32 accumulation.
    x = student_logits / student_temp
    lse = jax.scipy.special.logsumexp(x.astype(jnp.float32), axis=-1)  # [M]
    # Under target_dtype=bf16 BOTH operands are bf16, so the q * x
    # product is computed in bf16 (no elementwise promotion happens) —
    # the precision safeguard is solely the fp32 ACCUMULATION of the
    # reduction (dtype=jnp.float32 below). No fp32 copy of x is ever
    # materialized either way.
    dot = jnp.sum(teacher_probs * x, axis=-1, dtype=jnp.float32)       # [M]
    qsum = jnp.sum(teacher_probs, axis=-1, dtype=jnp.float32)
    return ibot_patch_loss_from_parts(dot, qsum, lse, masks_weight,
                                      n_images)


def ibot_patch_loss_dense(
    student_logits: jnp.ndarray,
    teacher_probs: jnp.ndarray,
    masks: jnp.ndarray,
    student_temp: float = 0.1,
) -> jnp.ndarray:
    """Dense variant on full [B, T, K] token grids with [B, T] bool masks
    (reference __call__:38-44)."""
    log_p = jax.nn.log_softmax(student_logits / student_temp, axis=-1)
    per_token = jnp.sum(teacher_probs * log_p, axis=-1)  # [B, T]
    m = masks.astype(per_token.dtype)
    per_image = jnp.sum(per_token * m, axis=-1) / jnp.clip(
        jnp.sum(m, axis=-1), 1.0, None
    )
    return -jnp.mean(per_image)
