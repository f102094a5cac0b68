"""Pallas edge-traversal kernel: shape/dtype sweeps + hypothesis
properties against the pure-jnp oracle (ref.py)."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, don't abort collection
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.layout import build_layout


def _random_sorted_segments(rng, n_edges, n_segments):
    seg = np.sort(rng.integers(0, n_segments, size=n_edges)).astype(np.int64)
    return seg


def _run_both(seg, vals, num_segments, combiner, tile_e, tile_r):
    layout = build_layout(seg, num_segments, tile_e=tile_e, tile_r=tile_r)
    vals_padded = layout.place(np.asarray(vals), 0)
    ident = kops.identity_for(combiner, vals_padded.dtype)
    vp = jnp.where(jnp.asarray(layout.lane_valid), jnp.asarray(vals_padded),
                   ident)
    out_k = kops.segment_combine_layout(vp, layout, combiner)
    out_r = kref.segment_combine(jnp.asarray(vals),
                                 jnp.asarray(seg.astype(np.int32)),
                                 num_segments, combiner)
    return np.asarray(out_k), np.asarray(out_r)


@pytest.mark.parametrize("combiner,dtype", [
    ("min", np.float32), ("min", np.int32),
    ("max", np.float32), ("max", np.int32),
    ("add", np.float32), ("add", np.int32),
])
@pytest.mark.parametrize("n_edges,n_segments,tile_e,tile_r", [
    (0, 16, 32, 16),         # empty graph
    (1, 1, 32, 16),          # single edge
    (500, 64, 64, 32),       # dense-ish
    (500, 2000, 64, 32),     # sparse (most segments empty)
    (777, 130, 128, 64),     # non-multiple sizes
    (2048, 64, 256, 256),    # hub rows spanning many tiles
])
def test_kernel_vs_ref_sweep(combiner, dtype, n_edges, n_segments,
                             tile_e, tile_r):
    rng = np.random.default_rng(n_edges * 7 + n_segments)
    seg = _random_sorted_segments(rng, n_edges, n_segments)
    if np.issubdtype(dtype, np.floating):
        vals = rng.standard_normal(n_edges).astype(dtype)
    else:
        vals = rng.integers(-1000, 1000, size=n_edges).astype(dtype)
    out_k, out_r = _run_both(seg, vals, n_segments, combiner, tile_e,
                             tile_r)
    if combiner == "add" and np.issubdtype(dtype, np.floating):
        np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(out_k, out_r)


@pytest.mark.parametrize("nested", [False, True])
@pytest.mark.parametrize("combiner", ["min", "max", "add"])
def test_kernel_vmapped_rows_match_per_row(combiner, nested):
    """vmap over query rows (how every served path calls the kernel) folds
    the batch into the kernel's row axis; each row equals its own call."""
    import jax
    rng = np.random.default_rng(5)
    n_edges, n_seg = 900, 300
    seg = _random_sorted_segments(rng, n_edges, n_seg)
    layout = build_layout(seg, n_seg, tile_e=128, tile_r=64)
    rows = rng.integers(-100, 100, size=(6, n_edges)).astype(np.int32)
    ident = kops.identity_for(combiner, rows.dtype)
    padded = np.stack([np.where(layout.lane_valid, layout.place(r, 0), ident)
                       for r in rows]).astype(np.int32)

    def one(v):
        return kops.segment_combine_layout(v, layout, combiner)

    if nested:
        out = jax.vmap(jax.vmap(one))(jnp.asarray(padded.reshape(2, 3, -1)))
        out = np.asarray(out).reshape(6, n_seg)
    else:
        out = np.asarray(jax.vmap(one)(jnp.asarray(padded)))
    for r, got in zip(rows, out):
        want = kref.segment_combine(jnp.asarray(r),
                                    jnp.asarray(seg.astype(np.int32)),
                                    n_seg, combiner)
        np.testing.assert_array_equal(got, np.asarray(want))


@settings(max_examples=30, deadline=None)
@given(
    n_edges=st.integers(0, 300),
    n_segments=st.integers(1, 200),
    combiner=st.sampled_from(["min", "max", "add"]),
    seed=st.integers(0, 2 ** 16),
)
def test_kernel_vs_ref_hypothesis(n_edges, n_segments, combiner, seed):
    rng = np.random.default_rng(seed)
    seg = _random_sorted_segments(rng, n_edges, n_segments)
    vals = rng.integers(-50, 50, size=n_edges).astype(np.int32)
    out_k, out_r = _run_both(seg, vals, n_segments, combiner, 32, 16)
    np.testing.assert_array_equal(out_k, out_r)


@settings(max_examples=25, deadline=None)
@given(n_edges=st.integers(0, 400), n_segments=st.integers(1, 300),
       tile_e=st.sampled_from([16, 64, 256]),
       tile_r=st.sampled_from([8, 32, 128]), seed=st.integers(0, 99))
def test_layout_invariants(n_edges, n_segments, tile_e, tile_r, seed):
    """Structural invariants of the static tile layout:
    - every edge gets exactly one lane (injective placement),
    - window ids are non-decreasing (output blocks revisit contiguously),
    - a lane's window matches its edge's segment's window,
    - padding lanes carry rel == tile_r (match no row)."""
    rng = np.random.default_rng(seed)
    seg = _random_sorted_segments(rng, n_edges, n_segments)
    lo = build_layout(seg, n_segments, tile_e=tile_e, tile_r=tile_r)
    lanes = lo.lane_of_edge
    assert len(np.unique(lanes)) == n_edges
    assert (np.diff(lo.window_id) >= 0).all()
    lane_window = np.repeat(lo.window_id, tile_e)
    assert (lane_window[lanes] == seg // tile_r).all()
    pad = np.ones(lo.num_lanes, bool)
    pad[lanes] = False
    assert (lo.rel[pad] == tile_r).all()
    assert (lo.rel[lanes] == seg - (seg // tile_r) * tile_r).all()
