from .profiling import (
    device_memory_stats,
    format_memory_stats,
    trace,
)
from .rng import manual_seed, next_rng_key, rng_scope

__all__ = [
    "manual_seed",
    "next_rng_key",
    "rng_scope",
    "trace",
    "device_memory_stats",
    "format_memory_stats",
]
