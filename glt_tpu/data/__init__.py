from .dataset import Dataset
from .feature import Feature, calibrate_cold_width, cold_rows_of
from .feature_cache import (
    FeatureCacheState,
    cache_gather,
    cache_init,
    cache_insert,
    cache_lookup,
    cache_stats,
)
from .graph import Graph
from .reorder import in_degree_order, sort_by_in_degree
from .shared import SharedArray, attach_dataset, share_dataset
from .topology import CSRTopo

__all__ = ["Dataset", "Feature", "Graph", "CSRTopo", "SharedArray",
           "attach_dataset", "share_dataset", "sort_by_in_degree",
           "in_degree_order", "calibrate_cold_width", "cold_rows_of",
           "FeatureCacheState", "cache_init", "cache_lookup",
           "cache_insert", "cache_gather", "cache_stats"]
