"""Data efficiency pipeline.

Rebuild of reference ``deepspeed/runtime/data_pipeline/``: curriculum
learning scheduler, difficulty-based data sampling, Megatron-format indexed
datasets, and random-LTD token dropping; beside them the block-diffusion
objective's noising (``block_diffusion.py``).
"""

from .curriculum_scheduler import CurriculumScheduler
from .data_sampler import DeepSpeedDataSampler
from .indexed_dataset import MMapIndexedDataset, MMapIndexedDatasetBuilder
from .data_routing import RandomLayerTokenDrop, RandomLTDScheduler
from .block_diffusion import BlockDiffusionNoiser, DiffusionBatch, noise_batch

__all__ = [
    "CurriculumScheduler", "DeepSpeedDataSampler",
    "MMapIndexedDataset", "MMapIndexedDatasetBuilder",
    "RandomLayerTokenDrop", "RandomLTDScheduler",
    "BlockDiffusionNoiser", "DiffusionBatch", "noise_batch",
]
