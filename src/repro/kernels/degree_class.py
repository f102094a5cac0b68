"""Static in-degree-class layout for the segmented combine.

Built once per partitioned graph (host-side numpy), like ``layout.py``'s
tile layout. The CSC lanes arrive sorted by destination segment, so each
segment's in-edges are already contiguous and a combine over them needs
no scatter. Segments are grouped by in-degree class: a segment with
in-degree ``d >= 1`` joins the class of the smallest width of
:func:`class_widths` that is ``>= d``. Each class is one contiguous
block of lanes holding a dense 2-D array with one column or row per
segment, and the combine is one reduce per block. Padding lanes (a segment with fewer
in-edges than its class width) are invalid and reduce as the
combiner's identity.

A block's orientation follows its width. On the chip XLA lays a
query-batched lane array out with the query axis minor and the lanes
tiled :data:`SUBLANES` deep, so a block reshapes in place only when its
axis next to the queries is a multiple of that depth; otherwise the
reshape copies the block. A width that is a multiple of it is laid out
segment-major, ``(rows, width)``: each segment's in-edges side by side.
Any other width (1 to 7, 9 to 15, 18, 20, ...) is laid out edge-major,
``(width, rows)``: row ``k`` holds the ``k``-th in-edge of every segment
of the class, and ``rows`` is rounded up to a multiple of the depth
with empty segments.

The classes follow the graph's in-degree histogram: a road network
gets a few classes and next to no padding, a Kronecker graph some fifty
and a few percent more lanes than edges.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .edge_gather import _identity_for

__all__ = ["ClassLayout", "ClassSegments", "SUBLANES", "build_classes",
           "class_combine", "class_widths", "edge_major"]

_REDUCE = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}

# depth of the chip's lane tiling for 32-bit and boolean arrays (the
# second-minor axis of its (8, 128) tiles)
SUBLANES = 8


def edge_major(width: int) -> bool:
    """Whether a class of ``width`` lays its block out ``(width, rows)``."""
    return width % SUBLANES != 0


def class_widths(max_degree: int) -> np.ndarray:
    """The class widths ``1, 2, ..., 16, 18, 20, ..., 32, 36, 40, ...``:
    eight steps an octave above 8, so padding stays under an eighth of
    a segment's lanes, up to the first width ``>= max_degree``."""
    out = [1]
    while out[-1] < max_degree:
        w = out[-1]
        out.append(w + max(1, (1 << (w.bit_length() - 1)) // 8))
    return np.asarray(out, np.int64)


@dataclasses.dataclass(frozen=True)
class ClassLayout:
    num_segments: int
    # (width, rows), widths ascending; an edge-major class's rows are
    # rounded up to a multiple of SUBLANES with empty segments
    classes: Tuple[Tuple[int, int], ...]
    edge: np.ndarray           # (E,) int64: input lanes kept (the valid)
    lane: np.ndarray           # (E,) int64: the lane each of them takes
    lane_valid: np.ndarray     # (L,) bool
    inv: np.ndarray            # (num_segments,) int32: segment -> out row

    @property
    def num_lanes(self) -> int:
        return int(self.lane_valid.shape[0])

    def place(self, arr: np.ndarray, fill) -> np.ndarray:
        """Scatter a per-edge array into the class lanes."""
        out = np.full((self.num_lanes,) + arr.shape[1:], fill, arr.dtype)
        out[self.lane] = arr[self.edge]
        return out

    @property
    def pad_share(self) -> float:
        """(placed lanes - valid edges) / valid edges."""
        e = int(self.lane_valid.sum())
        return (self.num_lanes - e) / max(e, 1)


class ClassSegments(NamedTuple):
    """The segments of lanes laid out by :func:`build_classes`, as a
    combine reads them: the layout's ``inv`` (an array, traced) and its
    static ``classes``."""
    inv: jnp.ndarray
    classes: Tuple[Tuple[int, int], ...]


def build_classes(seg_ids: np.ndarray, valid: np.ndarray,
                  num_segments: int) -> ClassLayout:
    """``seg_ids``: (E,) sorted ascending, in ``[0, num_segments]``;
    lanes with ``valid`` False are dropped (the partition's padding)."""
    seg_ids = np.asarray(seg_ids, np.int64)
    valid = np.asarray(valid, bool)
    assert seg_ids.ndim == 1 and seg_ids.shape == valid.shape
    if seg_ids.size:
        assert (np.diff(seg_ids) >= 0).all(), "seg_ids must be sorted"
    keep = np.flatnonzero(valid)
    seg = seg_ids[keep]
    assert seg.size == 0 or seg.max() < num_segments
    deg = np.bincount(seg, minlength=num_segments)
    widths = class_widths(int(deg.max()) if seg.size else 1)
    cls = np.searchsorted(widths, deg)           # deg 0 -> class 0, unused
    present = np.flatnonzero(deg)
    # segments in class order, ascending within a class
    order = present[np.argsort(cls[present], kind="stable")]
    nseg = np.bincount(cls[present], minlength=len(widths))
    em = np.array([edge_major(int(wd)) for wd in widths])
    rows = np.where(em, -(-nseg // SUBLANES) * SUBLANES, nseg)
    classes = tuple((int(widths[c]), int(rows[c]))
                    for c in range(len(widths)) if nseg[c])
    row_start = np.concatenate([[0], np.cumsum(rows)])[:-1]
    lane_start = np.concatenate([[0], np.cumsum(rows * widths)])[:-1]

    # rank of each segment within its class, and its output row
    rank = np.zeros(num_segments, np.int64)
    rank[order] = (np.arange(order.size)
                   - np.concatenate([[0], np.cumsum(nseg)])[cls[order]])
    inv = np.full(num_segments, int(rows.sum()), np.int32)
    inv[order] = row_start[cls[order]] + rank[order]

    # in-edge k of a segment lies at its first lane plus k lane strides:
    # rows apart edge-major, adjacent segment-major
    first = lane_start[cls] + np.where(em[cls], rank, rank * widths[cls])
    stride = np.where(em[cls], rows[cls], 1)
    k = np.arange(seg.size) - (np.cumsum(deg) - deg)[seg]
    lane = first[seg] + k * stride[seg]
    lane_valid = np.zeros(int((rows * widths).sum()), bool)
    lane_valid[lane] = True
    return ClassLayout(num_segments=num_segments, classes=classes,
                       edge=keep, lane=lane, lane_valid=lane_valid, inv=inv)


def class_combine(vals: jnp.ndarray, segs: ClassSegments,
                  combiner: str) -> jnp.ndarray:
    """Segmented combine of ``vals`` (..., L) over the class layout, whose
    padding lanes hold the identity: one reduce per class block, then
    ``segs.inv`` puts the rows in segment order. Returns
    (..., num_segments)."""
    ident = _identity_for(combiner, vals.dtype)
    lead = vals.shape[:-1]
    reduce = _REDUCE[combiner]
    parts, off = [], 0
    for width, rows in segs.classes:
        blk = jax.lax.slice_in_dim(vals, off, off + width * rows, axis=-1)
        if edge_major(width):
            parts.append(reduce(blk.reshape(lead + (width, rows)), axis=-2))
        else:
            parts.append(reduce(blk.reshape(lead + (rows, width)), axis=-1))
        off += width * rows
    parts.append(jnp.full(lead + (1,), ident, vals.dtype))
    return jnp.take(jnp.concatenate(parts, axis=-1), segs.inv, axis=-1)
