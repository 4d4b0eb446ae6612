"""core — the paper's contribution: MeshNet volumetric segmentation and the
memory-constrained inference pipeline (patching / cropping / streaming /
spatial sharding / connected components)."""

from repro.core.meshnet import MeshNetConfig, PAPER_MODELS
from repro.core.pipeline import PipelineConfig, PipelineResult

__all__ = [
    "MeshNetConfig",
    "PAPER_MODELS",
    "PipelineConfig",
    "PipelineResult",
]
