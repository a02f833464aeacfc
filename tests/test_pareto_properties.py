"""Property-based suite pinning the Pareto reduction's three layers to
each other (ISSUE 9 satellite): the scalar dominance reference, the
vectorized jit kernel, and the streaming accumulator.

Properties:
  * dominance is irreflexive and transitive, and exact ties dominate in
    neither direction;
  * the brute-force O(n^2) reference front matches the vectorized
    kernel bitwise on random (energy, latency, area) sets — ties,
    duplicates, and degenerate single-point grids included;
  * the front (as an index set) is invariant under row permutation and
    under arbitrary chunk-boundary placement through
    `ParetoAccumulator` — the identity the campaign's cross-chunk
    merging rests on.
"""
from __future__ import annotations

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.pareto import (ParetoAccumulator, dominates, pareto_mask,
                               pareto_mask_np, pareto_mask_ref)

# Integer-valued objectives drawn from a small range: collisions (exact
# ties, duplicate rows) are common, which is exactly the regime where a
# sloppy dominance predicate (<= instead of <) goes wrong.
coords = st.integers(min_value=0, max_value=6)
point3 = st.tuples(coords, coords, coords)
pointset = st.lists(point3, min_size=1, max_size=24)


def _arr(points) -> np.ndarray:
    return np.asarray(points, np.float32)


@given(point3)
@settings(max_examples=50)
def test_dominance_irreflexive(p):
    assert not dominates(p, p)


@given(point3, point3)
@settings(max_examples=100)
def test_dominance_antisymmetric(a, b):
    # a and b can never dominate each other simultaneously; exact ties
    # dominate in neither direction
    assert not (dominates(a, b) and dominates(b, a))
    if tuple(a) == tuple(b):
        assert not dominates(a, b) and not dominates(b, a)


@given(point3, point3, point3)
@settings(max_examples=150)
def test_dominance_transitive(a, b, c):
    if dominates(a, b) and dominates(b, c):
        assert dominates(a, c)


@given(pointset)
@settings(max_examples=80)
def test_vectorized_matches_bruteforce_bitwise(points):
    pts = _arr(points)
    ref = pareto_mask_ref(pts)
    vec = pareto_mask_np(pts)
    assert vec.dtype == np.bool_
    assert (ref == vec).all(), (pts, ref, vec)


def test_single_point_grid_is_its_own_front():
    assert pareto_mask_np(_arr([(3, 1, 4)])).tolist() == [True]
    assert pareto_mask_ref(_arr([(3, 1, 4)])).tolist() == [True]


def test_duplicate_rows_all_stay_on_front():
    pts = _arr([(1, 2, 3), (1, 2, 3), (9, 9, 9)])
    assert pareto_mask_np(pts).tolist() == [True, True, False]


def test_empty_set():
    assert pareto_mask_np(np.zeros((0, 3), np.float32)).shape == (0,)


def test_jit_kernel_accepts_traced_input():
    # pareto_mask itself is jit-compatible (the campaign promise);
    # compare an explicitly jitted call against the host path
    import jax
    pts = _arr([(1, 5, 2), (2, 2, 2), (3, 1, 9), (1, 5, 2)])
    jitted = np.asarray(jax.jit(pareto_mask)(pts))
    assert (jitted == pareto_mask_np(pts)).all()


@given(pointset, st.integers(min_value=0, max_value=2 ** 30))
@settings(max_examples=60)
def test_front_invariant_under_permutation(points, seed):
    pts = _arr(points)
    n = pts.shape[0]
    perm = np.random.RandomState(seed % (2 ** 31)).permutation(n)
    base = set(np.flatnonzero(pareto_mask_np(pts)).tolist())
    got_perm = pareto_mask_np(pts[perm])
    got = set(int(perm[i]) for i in np.flatnonzero(got_perm))
    assert got == base, (pts, perm)


@given(pointset, st.lists(st.integers(min_value=1, max_value=8),
                          min_size=1, max_size=6))
@settings(max_examples=60)
def test_front_invariant_under_chunk_placement(points, cuts):
    """Streaming the same rows through ParetoAccumulator under any
    chunk-boundary placement yields exactly the whole-batch front,
    points and indices both (bitwise: float32 in, float32 out)."""
    pts = _arr(points)
    n = pts.shape[0]
    whole = np.flatnonzero(pareto_mask_np(pts))

    acc = ParetoAccumulator(pts.shape[1])
    start = 0
    for c in cuts:
        stop = min(n, start + c)
        acc.update(pts[start:stop], np.arange(start, stop))
        start = stop
    acc.update(pts[start:], np.arange(start, n))   # remainder chunk

    front_pts, front_idx = acc.front()
    assert front_idx.tolist() == whole.tolist(), (pts, cuts)
    assert (front_pts == pts[whole]).all()
    assert acc.rows_seen == n
    assert len(acc) == len(whole)


def test_accumulator_rejects_nonfinite_and_bad_shapes():
    acc = ParetoAccumulator(3)
    with pytest.raises(ValueError, match="non-finite"):
        acc.update(_arr([(1, 2, np.inf)]), [0])
    with pytest.raises(ValueError, match=r"\(n, 3\)"):
        acc.update(np.zeros((2, 2), np.float32), [0, 1])
    with pytest.raises(ValueError, match="indices shape"):
        acc.update(np.zeros((2, 3), np.float32), [0])
    with pytest.raises(ValueError, match="n_objectives"):
        ParetoAccumulator(0)


def test_mask_np_rejects_non_matrix():
    with pytest.raises(ValueError, match=r"\(n, d\)"):
        pareto_mask_np(np.zeros(5, np.float32))
