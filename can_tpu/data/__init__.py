from .density import gaussian_density_map, generate_density_maps
from .dataset import CrowdDataset, IMAGENET_MEAN, IMAGENET_STD, normalize_host
from .batching import (
    Batch,
    ShardedBatcher,
    StagingBatch,
    pad_batch,
    snap_to_bucket,
)
from .synthetic import make_synthetic_dataset
from .prefetch import PrefetchPutError, prefetch_to_device
from .prepared import ItemCache, PreparedStore, StaleStoreError, write_store

__all__ = [
    "gaussian_density_map",
    "generate_density_maps",
    "CrowdDataset",
    "IMAGENET_MEAN",
    "IMAGENET_STD",
    "normalize_host",
    "ShardedBatcher",
    "Batch",
    "StagingBatch",
    "pad_batch",
    "snap_to_bucket",
    "make_synthetic_dataset",
    "prefetch_to_device",
    "PrefetchPutError",
    "ItemCache",
    "PreparedStore",
    "StaleStoreError",
    "write_store",
]
