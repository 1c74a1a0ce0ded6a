from .base import (
    BaseSampler,
    EdgeSamplerInput,
    HeteroSamplerOutput,
    NegativeSampling,
    NodeSamplerInput,
    SamplerOutput,
    SamplingConfig,
)
from .neighbor_sampler import (
    HopBounds,
    NeighborSampler,
    calibrate_node_capacity,
    hop_bounds,
    measure_occupancy,
)

__all__ = [
    "calibrate_node_capacity",
    "hop_bounds",
    "measure_occupancy",
    "HopBounds",
    "BaseSampler",
    "EdgeSamplerInput",
    "HeteroSamplerOutput",
    "NegativeSampling",
    "NodeSamplerInput",
    "SamplerOutput",
    "SamplingConfig",
    "NeighborSampler",
]
