from redpanda_tpu.compression.registry import (
    compress,
    uncompress,
    uncompress_many,
    register_backend,
    active_backend,
    is_available,
)

__all__ = [
    "compress",
    "uncompress",
    "uncompress_many",
    "register_backend",
    "active_backend",
    "is_available",
]
