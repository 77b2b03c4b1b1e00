from repro.utils.tree import (
    tree_size_bytes,
    tree_count_params,
    tree_zeros_like,
    tree_cast,
    tree_global_norm,
    tree_add,
    tree_scale,
)
from repro.utils.timing import span
from repro.utils.logging import get_logger

__all__ = [
    "tree_size_bytes",
    "tree_count_params",
    "tree_zeros_like",
    "tree_cast",
    "tree_global_norm",
    "tree_add",
    "tree_scale",
    "span",
    "get_logger",
]
