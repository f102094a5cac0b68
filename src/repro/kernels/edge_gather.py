"""Pallas TPU kernel: fused receiver-side scatter+gather edge traversal.

This is the paper's compute hot-spot (its whole §5 model is in traversed
edges/second), re-architected for the TPU memory hierarchy instead of
ported from the FPGA pipeline:

  * Edges arrive pre-sorted by destination segment (CSC order — a static
    property of the partitioned graph, prepared once at load time like the
    paper's per-PE edge lists).
  * The edge stream is cut into fixed ``TILE_E``-edge tiles. Rows are
    grouped into windows of ``TILE_R`` consecutive segments, and tiles are
    padded so NO tile straddles a window boundary (static layout, see
    ``layout.py``).
  * Each grid step stages one (rel, vals) tile through VMEM (BlockSpec),
    expands it against a broadcasted iota into a ``TILE_R x TILE_E``
    equality mask — the VPU's 8x128 lanes play the role of the paper's
    parallel PEs — and folds it into the window's partial with the
    semiring combiner. Messages are produced and consumed entirely in
    VMEM, never materialized to HBM: the exact TPU analogue of GraVF-M's
    "generate messages on demand, immediately consumed by gather".
  * Consecutive tiles of the same window hit the same output block, which
    therefore stays resident in VMEM (sequential TPU grid); a
    scalar-prefetched ``window_id`` array drives the output index_map —
    this is the floating-barrier-flavoured part: the output block "floats"
    forward only when the window changes, with no global flush.

Blocks are 2-D so they agree with XLA's TPU tiling at any tile size that
is a multiple of 128: the static ``rel`` stream is a ``(1, L)`` row, and
the message values are ``(rows, L)`` — one row per query. A query batch
(``jax.vmap`` over queries, which every served path applies) therefore
becomes the kernel's row axis through a custom batching rule, instead of
the ``Squeezed`` 1-D blocks that ``pallas_call``'s own rule would produce
and Mosaic refuses. The mask is built once per tile and reused by every
row.

Where the kernel runs is decided by the platform it is lowered for
(``jax.lax.platform_dependent``), never by the process's default backend:
on CPU it runs in the Pallas interpreter (the correctness vehicle), on TPU
it is compiled by Mosaic, and lowering for any other platform is an error.

Semirings: add (PageRank), min (BFS/WCC/SSSP), max.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["segment_combine_pallas", "segment_combine_windows"]


def _identity_for(combiner: str, dtype):
    """Combiner identity as a PYTHON scalar (weakly typed — safe to bake
    into kernel bodies and jnp.where without forcing a dtype)."""
    dt = jnp.dtype(dtype)
    if combiner == "add":
        return 0.0 if jnp.issubdtype(dt, jnp.floating) else 0
    if combiner == "min":
        return (float("inf") if jnp.issubdtype(dt, jnp.floating)
                else int(jnp.iinfo(dt).max))
    if combiner == "max":
        return (float("-inf") if jnp.issubdtype(dt, jnp.floating)
                else int(jnp.iinfo(dt).min))
    raise ValueError(combiner)


_REDUCE = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}
_FOLD = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _make_kernel(combiner: str, tile_e: int, tile_r: int, rows: int, dtype):
    ident = _identity_for(combiner, dtype)
    reduce, fold = _REDUCE[combiner], _FOLD[combiner]

    def kern(wid_ref, rel_ref, vals_ref, out_ref):
        t = pl.program_id(0)
        wid = wid_ref[t]
        prev = wid_ref[jnp.maximum(t - 1, 0)]
        is_first = (t == 0) | (wid != prev)

        rel = rel_ref[...]          # (1, tile_e) int32 row-within-window
        # (tile_r, tile_e) equality mask vs broadcasted iota: each VPU row
        # lane selects the messages destined for its vertex. Built once
        # per tile, shared by every query row.
        iota = jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_e), 0)
        mask = iota == rel

        def row(b, c):
            vals = vals_ref[pl.ds(b, 1), :]                 # (1, tile_e)
            part = reduce(jnp.where(mask, vals, ident), axis=1)[None, :]

            @pl.when(is_first)
            def _init():
                out_ref[pl.ds(b, 1), :] = part

            @pl.when(jnp.logical_not(is_first))
            def _accum():
                out_ref[pl.ds(b, 1), :] = fold(out_ref[pl.ds(b, 1), :], part)

            return c

        jax.lax.fori_loop(0, rows, row, 0)

    return kern


def _pallas_rows(window_id, rel, vals, *, combiner: str, tile_e: int,
                 tile_r: int, n_windows: int, interpret: bool):
    """The ``pallas_call`` over a ``(rows, L)`` value array."""
    rows, lanes = vals.shape
    n_tiles = window_id.shape[0]
    assert lanes == n_tiles * tile_e and rel.shape == (lanes,)
    # Both platform branches are traced, so tile sizes are not checked
    # here: Mosaic refuses tiles that are not multiples of 128 when it
    # lowers the TPU branch, and the interpreter takes any size.
    kern = _make_kernel(combiner, tile_e, tile_r, rows, vals.dtype)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((1, tile_e), lambda t, wid: (0, t)),
                pl.BlockSpec((rows, tile_e), lambda t, wid: (0, t)),
            ],
            out_specs=pl.BlockSpec((rows, tile_r),
                                   lambda t, wid: (0, wid[t])),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n_windows * tile_r),
                                       vals.dtype),
        interpret=interpret,
    )(window_id, rel.reshape(1, lanes), vals)


@functools.lru_cache(maxsize=None)
def _batched_kernel(combiner: str, tile_e: int, tile_r: int, n_windows: int,
                    interpret: Optional[bool]):
    """``(window_id, rel, vals[..., L]) -> (..., n_windows*tile_r)`` with a
    batching rule that folds vmapped query axes into the kernel's rows."""
    static = dict(combiner=combiner, tile_e=tile_e, tile_r=tile_r,
                  n_windows=n_windows)
    branches = {}
    if interpret is None or interpret:
        branches["cpu"] = functools.partial(_pallas_rows, interpret=True,
                                            **static)
    if interpret is None or not interpret:
        branches["tpu"] = functools.partial(_pallas_rows, interpret=False,
                                            **static)

    @custom_vmap
    def kernel(window_id, rel, vals):
        lead = vals.shape[:-1]
        rows = vals.reshape((-1, vals.shape[-1]))
        out = jax.lax.platform_dependent(window_id, rel, rows, **branches)
        return out.reshape(lead + (n_windows * tile_r,))

    @kernel.def_vmap
    def _kernel_vmap(axis_size, in_batched, window_id, rel, vals):
        wid_b, rel_b, vals_b = in_batched
        if wid_b or rel_b:
            raise NotImplementedError(
                "segment_combine_pallas batches message values only; the "
                "static tile layout (window_id, rel) must be unbatched")
        assert vals_b
        # vals now carries the batch as a leading axis: the (possibly
        # nested) batch becomes kernel rows
        return kernel(window_id, rel, vals), True

    return kernel


def segment_combine_pallas(window_id, rel, vals, *, combiner: str,
                           tile_e: int, tile_r: int, n_windows: int,
                           interpret: Optional[bool] = None):
    """Run the edge-traversal kernel.

    Args:
      window_id: (n_tiles,) int32 — output window per tile (non-decreasing).
      rel:       (n_tiles*tile_e,) int32 — row-within-window per edge lane;
                 padding lanes hold ``tile_r`` (matches no row).
      vals:      (..., n_tiles*tile_e) message values (padding lanes hold
                 the combiner identity); leading axes are query rows.
      n_windows: number of output windows; result is
                 (..., n_windows*tile_r).
      interpret: None (the default) picks by the platform the program is
                 lowered for: the Pallas interpreter on CPU, Mosaic on
                 TPU, an error anywhere else. True allows only the
                 interpreter and False only Mosaic (lowering for the
                 other platform then fails), e.g. to compile the kernel
                 for a described TPU from a CPU process.
    """
    return _batched_kernel(combiner, tile_e, tile_r, n_windows,
                           interpret)(window_id, rel, vals)


def segment_combine_windows(window_id, rel, vals, *, combiner: str,
                            tile_e: int, tile_r: int, n_windows: int,
                            window_written, num_segments: int,
                            interpret: Optional[bool] = None):
    """Windowed segment-combine with the full post-processing both engine
    paths need: run :func:`segment_combine_pallas`, force never-written
    windows (gaps in the segment range) back to the combiner identity via
    ``window_written`` (an ``(n_windows,)`` bool mask from the layout),
    and slice the ``(..., n_windows*tile_r)`` window grid down to the
    first ``num_segments`` true segments."""
    out = segment_combine_pallas(window_id, rel, vals, combiner=combiner,
                                 tile_e=tile_e, tile_r=tile_r,
                                 n_windows=n_windows, interpret=interpret)
    ident = _identity_for(combiner, vals.dtype)
    lead = out.shape[:-1]
    out = out.reshape(lead + (n_windows, tile_r))
    out = jnp.where(window_written[:, None], out, ident)
    return out.reshape(lead + (n_windows * tile_r,))[..., :num_segments]
