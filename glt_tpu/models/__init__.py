from .conv import GATConv, SAGEConv, scatter_mean, scatter_sum, segment_softmax
from .gat import GAT
from .hgt import HGT, HGTConv
from .rgat import RGAT, RGNN, HeteroConv
from .sage import GraphSAGE
from .train import (
    TrainState,
    create_train_state,
    make_eval_step,
    link_seed_blocks,
    make_cached_gather_xy,
    make_gather_xy,
    init_hetero_state,
    make_scanned_hetero_train_step,
    make_scanned_link_train_step,
    make_scanned_node_train_step,
    node_seed_blocks,
    run_scanned_epoch,
    make_scanned_subgraph_train_step,
    make_train_step,
    seed_cross_entropy,
)

__all__ = [
    "GAT",
    "GATConv",
    "GraphSAGE",
    "HGT",
    "HGTConv",
    "HeteroConv",
    "RGAT",
    "RGNN",
    "SAGEConv",
    "TrainState",
    "create_train_state",
    "link_seed_blocks",
    "make_cached_gather_xy",
    "make_eval_step",
    "make_gather_xy",
    "init_hetero_state",
    "make_scanned_hetero_train_step",
    "make_scanned_link_train_step",
    "make_scanned_node_train_step",
    "node_seed_blocks",
    "run_scanned_epoch",
    "make_scanned_subgraph_train_step",
    "make_train_step",
    "scatter_mean",
    "scatter_sum",
    "seed_cross_entropy",
    "segment_softmax",
]
