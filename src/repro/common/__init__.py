"""Shared low-level utilities: dtypes, padding, timing, logging."""
from repro.common.util import (
    bench_engine_path,
    checkout_root,
    enable_compile_cache,
    ceil_div,
    pad_to_multiple,
    pad_axis_to,
    next_multiple,
    tree_size_bytes,
    tree_num_params,
    Timer,
    get_logger,
)

__all__ = [
    "bench_engine_path",
    "checkout_root",
    "enable_compile_cache",
    "ceil_div",
    "pad_to_multiple",
    "pad_axis_to",
    "next_multiple",
    "tree_size_bytes",
    "tree_num_params",
    "Timer",
    "get_logger",
]
