from dinov3_tpu.train.fused_update import (
    BucketPlan,
    build_bucketed_update,
    build_fused_update,
    bucketed_adam_zeros,
    make_bucket_plan,
    make_bucketed_update,
    make_bucketed_update_schedule,
    make_fused_update,
)
from dinov3_tpu.train.optimizer import (
    build_optimizer,
    clip_by_per_submodel_norm,
    per_submodel_norms,
    scheduled_adamw,
)
from dinov3_tpu.train.param_groups import build_multiplier_trees
from dinov3_tpu.train.schedules import (
    Schedules,
    build_schedules,
    cosine_schedule,
    linear_warmup_cosine_decay,
)
from dinov3_tpu.train.setup import (
    TrainSetup,
    build_train_setup,
    elastic_resume,
    put_batch,
)
from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
from dinov3_tpu.train.train_step import TrainState, make_train_step

__all__ = [
    "build_fused_update", "make_fused_update",
    "BucketPlan", "make_bucket_plan", "bucketed_adam_zeros",
    "build_bucketed_update", "make_bucketed_update",
    "make_bucketed_update_schedule",
    "build_optimizer", "clip_by_per_submodel_norm", "per_submodel_norms",
    "scheduled_adamw",
    "build_multiplier_trees", "Schedules", "build_schedules",
    "cosine_schedule", "linear_warmup_cosine_decay",
    "TrainSetup", "build_train_setup", "elastic_resume", "put_batch",
    "SSLMetaArch", "TrainState", "make_train_step",
]
