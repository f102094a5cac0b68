"""Jitted dispatch wrappers for the kernel layer.

``segment_combine``: runs the Pallas edge-traversal kernel when a static
:class:`EdgeLayout` is supplied (interpreted when lowered for CPU,
compiled by Mosaic when lowered for TPU — see ``edge_gather``), falling
back to the pure-jnp oracle otherwise.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .edge_gather import segment_combine_windows, _identity_for
from .layout import EdgeLayout, build_layout

__all__ = ["segment_combine", "segment_combine_layout", "build_layout",
           "EdgeLayout", "identity_for"]

identity_for = _identity_for


def segment_combine_layout(vals_padded: jnp.ndarray, layout: EdgeLayout,
                           combiner: str):
    """Kernel path. ``vals_padded`` is (..., layout.num_lanes) with
    identity in padding lanes (use ``layout.place`` or mask with
    ``layout.lane_valid``). Returns (..., num_segments)."""
    return segment_combine_windows(
        jnp.asarray(layout.window_id), jnp.asarray(layout.rel), vals_padded,
        combiner=combiner, tile_e=layout.tile_e, tile_r=layout.tile_r,
        n_windows=layout.n_windows,
        window_written=jnp.asarray(layout.window_written),
        num_segments=layout.num_segments)


def segment_combine(vals: jnp.ndarray, seg_ids: jnp.ndarray,
                    num_segments: int, combiner: str,
                    layout: EdgeLayout | None = None):
    """Aggregate per-destination messages. With a layout → Pallas kernel;
    without → jnp oracle (used for the GraVF baseline path and as the
    reference in tests)."""
    if layout is None:
        return ref.segment_combine(vals, seg_ids, num_segments, combiner)
    ident = identity_for(combiner, vals.dtype)
    lane_valid = jnp.asarray(layout.lane_valid)
    vals_padded = jnp.where(lane_valid, vals, ident)
    return segment_combine_layout(vals_padded, layout, combiner)
