"""Pareto utilities: frontier invariants (hypothesis) + hypervolume."""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.pareto import (
    crowding_distance,
    default_reference_point,
    hypervolume,
    non_dominated_sort,
    pareto_frontier_indices,
)

points = st.lists(
    st.tuples(st.floats(min_value=-10, max_value=10, allow_nan=False),
              st.floats(min_value=-10, max_value=10, allow_nan=False)),
    min_size=1, max_size=40)


def dominates(a, b):
    return all(x >= y for x, y in zip(a, b)) and any(x > y for x, y in zip(a, b))


@given(points)
@settings(max_examples=150, deadline=None)
def test_frontier_is_nondominated_and_complete(pts):
    y = np.asarray(pts)
    idx = pareto_frontier_indices(y)
    assert idx, "frontier never empty for nonempty input"
    front = {i for i in idx}
    for i in idx:
        for j in range(len(pts)):
            assert not dominates(pts[j], pts[i]), (i, j)
    # completeness: every excluded point is dominated by someone
    for i in range(len(pts)):
        if i not in front:
            assert any(dominates(pts[j], pts[i]) for j in range(len(pts)))


@given(points)
@settings(max_examples=80, deadline=None)
def test_non_dominated_sort_partitions(pts):
    y = np.asarray(pts)
    fronts = non_dominated_sort(y)
    flat = np.concatenate(fronts)
    assert sorted(flat.tolist()) == list(range(len(pts)))
    # rank-0 front matches pareto_frontier_indices
    assert set(fronts[0].tolist()) == set(pareto_frontier_indices(y))


def test_hypervolume_2d_exact():
    y = np.array([[1.0, 3.0], [2.0, 2.0], [3.0, 1.0]])
    ref = np.array([0.0, 0.0])
    # union of rectangles: 3 + 2 + 2 = ... compute: sorted desc by x: (3,1):3*1=3;
    # (2,2): 2*(2-1)=2; (1,3): 1*(3-2)=1 -> 6
    assert abs(hypervolume(y, ref) - 6.0) < 1e-6


def test_hypervolume_monotone_in_points():
    ref = np.array([0.0, 0.0, 0.0])
    y1 = np.array([[1.0, 1.0, 1.0]])
    y2 = np.array([[1.0, 1.0, 1.0], [2.0, 0.5, 0.5]])
    assert hypervolume(y2, ref, seed=1) >= hypervolume(y1, ref, seed=1) - 0.05


def test_crowding_distance_boundaries_infinite():
    y = np.array([[0.0, 3.0], [1.0, 2.0], [2.0, 1.0], [3.0, 0.0]])
    d = crowding_distance(y)
    assert np.isinf(d[0]) and np.isinf(d[3])
    assert np.isfinite(d[1]) and np.isfinite(d[2])


def test_nan_rows_never_on_frontier():
    """Regression: a NaN objective vector compares False against everything,
    so it used to be un-dominatable and survive EVERY domination test —
    ListOptimalTrials served NaN trials as 'optimal'. Non-finite rows must
    be incomparable: excluded from the frontier and unable to dominate."""
    y = np.array([
        [1.0, 1.0],
        [np.nan, 5.0],
        [5.0, np.nan],
        [np.inf, np.inf],
        [2.0, 0.5],
        [-np.inf, 3.0],
    ])
    idx = pareto_frontier_indices(y)
    assert idx == [0, 4]
    # an all-non-finite input yields an EMPTY frontier, not a crash
    assert pareto_frontier_indices(np.full((3, 2), np.nan)) == []
    # and non-finite rows cannot knock finite rows off the frontier either
    y2 = np.array([[1.0, 1.0], [np.inf, 2.0]])
    assert pareto_frontier_indices(y2) == [0]


def test_crowding_distance_duplicates_and_constant_metric():
    """Edge cases the NSGA-II truncation leans on: exact duplicates share
    ranks without NaN/inf poisoning, and a constant metric (zero span)
    contributes nothing instead of dividing by zero."""
    dup = np.array([[1.0, 2.0], [1.0, 2.0], [1.0, 2.0], [3.0, 0.0]])
    d = crowding_distance(dup)
    assert np.all(np.isfinite(d) | np.isinf(d))  # no NaN anywhere
    assert not np.any(np.isnan(d))
    const = np.array([[0.0, 5.0], [1.0, 5.0], [2.0, 5.0], [3.0, 5.0]])
    d2 = crowding_distance(const)
    assert not np.any(np.isnan(d2))
    # boundaries on the varying metric are still infinite
    assert np.isinf(d2[0]) and np.isinf(d2[3])
    # all-identical front: every point is a boundary (all infinite)
    same = np.array([[1.0, 1.0]] * 5)
    assert np.all(np.isinf(crowding_distance(same)) |
                  (crowding_distance(same) == 0.0))


@given(points, st.integers(min_value=1, max_value=20))
@settings(max_examples=60, deadline=None)
def test_nsga2_truncation_keeps_best_fronts(pts, keep):
    """NSGA-II environmental selection property: truncating to ``keep``
    survivors by (front rank, crowding distance) never drops a point whose
    whole front fits — lower-ranked fronts are consumed in order."""
    y = np.asarray(pts)
    keep = min(keep, len(pts))
    fronts = non_dominated_sort(y)
    survivors = []
    for front in fronts:
        if len(survivors) + len(front) <= keep:
            survivors.extend(front.tolist())
        else:
            room = keep - len(survivors)
            if room > 0:
                d = crowding_distance(y[front])
                order = np.argsort(-d)
                survivors.extend(front[order[:room]].tolist())
            break
    assert len(survivors) == keep
    ranks = {i: r for r, front in enumerate(fronts) for i in front}
    kept_ranks = sorted(ranks[i] for i in survivors)
    dropped = set(range(len(pts))) - set(survivors)
    # no dropped point outranks (strictly better front than) a kept point
    for i in dropped:
        assert ranks[i] >= kept_ranks[-1]


def test_hypervolume_mc_matches_exact_at_k3():
    """MC estimator (k>=3 path) cross-checked against a hand-computable
    3-D frontier: boxes [0,p]^3 for non-dominated p's, inclusion-exclusion
    union volume."""
    ref = np.array([0.0, 0.0, 0.0])
    # two boxes: [0,2]x[0,1]x[0,1] and [0,1]x[0,2]x[0,1]; union =
    # 2 + 2 - overlap([0,1]^2x[0,1]) = 4 - 1 = 3
    y = np.array([[2.0, 1.0, 1.0], [1.0, 2.0, 1.0]])
    exact = 3.0
    mc = hypervolume(y, ref, seed=7)
    assert abs(mc - exact) / exact < 0.05  # 16384-sample MC tolerance


def test_default_reference_point_dominated_by_all():
    y = np.array([[1.0, -3.0], [2.0, -5.0], [0.5, -1.0]])
    ref = default_reference_point(y)
    assert np.all(ref < y.min(axis=0))
    # every observed point dominates a positive-volume box
    assert hypervolume(y, ref) > 0.0
