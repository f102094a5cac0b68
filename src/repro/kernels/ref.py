"""Pure-jnp oracles for the kernel layer.

``segment_combine``: the fused receiver-side scatter+gather hot loop —
per-destination aggregation of on-demand messages (paper §4.1/§4.2). The
Pallas kernels in this package must match these bit-for-bit (up to
floating-point reduction-order tolerance for "add").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["segment_combine"]


def segment_combine(vals: jnp.ndarray, seg_ids: jnp.ndarray,
                    num_segments: int, combiner: str) -> jnp.ndarray:
    """Aggregate ``vals`` by ``seg_ids`` with a monoid. ``seg_ids`` may
    contain values >= num_segments for padding lanes (discarded)."""
    n = num_segments + 1
    clipped = jnp.minimum(seg_ids, num_segments)
    if combiner == "add":
        out = jax.ops.segment_sum(vals, clipped, num_segments=n)
    elif combiner == "min":
        out = jax.ops.segment_min(vals, clipped, num_segments=n)
    elif combiner == "max":
        out = jax.ops.segment_max(vals, clipped, num_segments=n)
    else:
        raise ValueError(f"unknown combiner: {combiner}")
    return out[:num_segments]
