"""Pure-jnp oracles for the kernel layer.

``segment_combine``: the fused receiver-side scatter+gather hot loop —
per-destination aggregation of on-demand messages (paper §4.1/§4.2). The
Pallas kernels in this package, and the in-degree-class reduces, must
match it bit-for-bit (up to floating-point reduction-order tolerance for
"add").
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

from .degree_class import ClassSegments, class_combine

__all__ = ["segment_combine"]


def segment_combine(vals: jnp.ndarray,
                    seg_ids: Union[jnp.ndarray, ClassSegments],
                    num_segments: int, combiner: str) -> jnp.ndarray:
    """Aggregate ``vals`` by segment with a monoid. ``seg_ids`` is one id
    per lane, combined by XLA's scatter (the oracle; ids >= num_segments
    are padding lanes, discarded), or the :class:`ClassSegments` of lanes
    laid out by in-degree class (padding lanes holding the identity),
    combined by one dense reduce per class."""
    if isinstance(seg_ids, ClassSegments):
        return class_combine(vals, seg_ids, combiner)
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
