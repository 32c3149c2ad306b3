"""Data substrate: paper stream generators + LM token pipeline."""

from .streams import (
    cauchy_stream,
    dynamic_cauchy_stream,
    flow_size_chunks,
    tcp_like_group_streams,
    twitter_like_interval_streams,
    zipf_keys,
)

__all__ = [
    "cauchy_stream",
    "dynamic_cauchy_stream",
    "flow_size_chunks",
    "tcp_like_group_streams",
    "twitter_like_interval_streams",
    "zipf_keys",
]
