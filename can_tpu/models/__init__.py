from .cannet import (
    FRONTEND_CFG,
    BACKEND_CFG,
    CONTEXT_SCALES,
    LocalOps,
    cannet_apply,
    cannet_init,
    has_batch_norm,
    init_batch_stats,
    load_vgg16_frontend,
    param_count,
    stage1_layout,
    stage1_traced,
)

__all__ = [
    "FRONTEND_CFG",
    "BACKEND_CFG",
    "CONTEXT_SCALES",
    "LocalOps",
    "cannet_apply",
    "cannet_init",
    "has_batch_norm",
    "init_batch_stats",
    "load_vgg16_frontend",
    "param_count",
    "stage1_layout",
    "stage1_traced",
]
