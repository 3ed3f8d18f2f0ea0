from .compile_cache import enable_compile_cache
from .metrics import METRIC_KEY_PREFIXES, METRIC_KEYS, read_metrics
from .sanitizers import HostSyncSanitizer, RecompileSentinel
from .tree import (
    tree_map,
    tree_stack,
    tree_unstack,
    tree_index,
    tree_zeros_like,
    tree_concat,
    softmax,
)

__all__ = [
    "enable_compile_cache",
    "read_metrics",
    "METRIC_KEYS",
    "METRIC_KEY_PREFIXES",
    "HostSyncSanitizer",
    "RecompileSentinel",
    "tree_map",
    "tree_stack",
    "tree_unstack",
    "tree_index",
    "tree_zeros_like",
    "tree_concat",
    "softmax",
]
