"""The benchmark's reference, comparison, control and generators, on
the CPU at small sizes."""
import json
import os

import numpy as np
import pytest

from bench import compare, control, reference
from bench.generators import road3d

with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "configs", "road3d.json")) as _f:
    LIMITS = json.load(_f)["limits"]


@pytest.fixture(scope="module")
def case():
    pts = road3d.generate(3000, 7).astype(np.float32)
    rng = np.random.default_rng(1)
    q = rng.uniform(pts.min(0), pts.max(0), size=(96, 3)).astype(np.float32)
    diag = float(np.linalg.norm(pts.max(0).astype(np.float64) - pts.min(0)))
    r = float(np.float32(0.05 * diag))
    return pts, q, 8, r


def test_reference_agrees_with_repro_brute(case):
    from repro.core import brute

    pts, q, k, r = case
    ids, d = reference.constrained_knn(pts, q, k, r)
    for i in range(len(q)):
        bi, bd = brute.constrained_knn(pts.astype(np.float64),
                                       q[i].astype(np.float64), k, r)
        n = len(bi)
        assert (ids[i, n:] == -1).all() and np.isinf(d[i, n:]).all()
        np.testing.assert_allclose(d[i, :n], bd, rtol=1e-12)
        assert set(ids[i, :n].tolist()) == set(np.asarray(bi).tolist())


def test_reference_answers_compare_as_correct(case):
    pts, q, k, r = case
    ids, d = reference.constrained_knn(pts, q, k, r)
    dg, ig = compare.gaps(ids, d.astype(np.float32), q, pts, k, r)
    ok, checks = compare.judge(dg, ig, 0, LIMITS)
    assert ok, checks
    assert checks["dist_gap"]["value"] < 1e-6


def _first_full_row(ids):
    return int(np.flatnonzero((ids >= 0).all(axis=1))[0])


def test_swapped_id_is_incorrect(case):
    pts, q, k, r = case
    ids, d = reference.constrained_knn(pts, q, k, r)
    i = _first_full_row(ids)
    ids[i, 0] = (ids[i, 0] + len(pts) // 2) % len(pts)
    ok, checks = compare.judge(*compare.gaps(ids, d, q, pts, k, r), 0, LIMITS)
    assert not ok and checks["id_gap"]["value"] > LIMITS["id_gap"]


def test_perturbed_distance_is_incorrect(case):
    pts, q, k, r = case
    ids, d = reference.constrained_knn(pts, q, k, r)
    i = _first_full_row(ids)
    d[i, k - 1] *= 1.01
    ok, checks = compare.judge(*compare.gaps(ids, d, q, pts, k, r), 0, LIMITS)
    assert not ok and checks["dist_gap"]["value"] > LIMITS["dist_gap"]


def test_repeated_id_and_unanswered_are_incorrect(case):
    pts, q, k, r = case
    ids, d = reference.constrained_knn(pts, q, k, r)
    gaps = compare.gaps(ids, d, q, pts, k, r)
    assert not compare.judge(*gaps, 1, LIMITS)[0]
    i = _first_full_row(ids)
    ids[i, 1] = ids[i, 0]
    d[i, 1] = d[i, 0]
    dg, ig = compare.gaps(ids, d, q, pts, k, r)
    assert ig[i] == 1.0


def test_bf16_control_is_incorrect(case):
    pts, q, k, r = case
    ids, d = control.answers(pts, q, k, r)
    ok, checks = compare.judge(*compare.gaps(ids, d, q, pts, k, r), 0, LIMITS)
    assert not ok, checks


@pytest.fixture(scope="module")
def roads():
    return road3d.generate(20000, 3000000011)


def test_road3d_keeps_the_source_extent_and_altitude_marginal(roads):
    lo = np.array([e[0] for e in road3d.EXTENT])
    hi = np.array([e[1] for e in road3d.EXTENT])
    assert roads.shape == (20000, 3)
    assert (roads >= lo).all() and (roads <= hi).all()
    # the points reach across the extent in every column
    assert ((roads.max(0) - roads.min(0)) > 0.9 * (hi - lo)).all()
    alt = roads[:, 2]
    assert abs(alt.mean() - road3d.ALT_MEAN) < 0.5
    assert abs(alt.std() - road3d.ALT_SD) < 0.5
    assert np.median(alt) < road3d.ALT_MEAN   # skewed to the right
    # altitude's span dominates the diagonal, as in the source's units
    span = roads.max(0) - roads.min(0)
    assert span[2] > 0.99 * np.linalg.norm(span)


def test_road3d_points_lie_on_dense_roads_over_a_smooth_terrain(roads):
    from scipy.spatial import cKDTree

    lat_mid = np.deg2rad(np.mean(road3d.EXTENT[1]))
    ground = roads[:, :2] * road3d.M_PER_DEG_LAT * np.array([np.cos(lat_mid), 1.0])
    d, j = cKDTree(ground).query(ground, k=2)
    # every point's nearest neighbour on the ground is a road step away
    assert np.median(d[:, 1]) <= road3d.STEP_M * 1.01
    # and lies at nearly the same altitude: the terrain is smooth
    dz = np.abs(roads[:, 2] - roads[j[:, 1], 2])
    assert np.median(dz) < 0.5 and np.percentile(dz, 99) < 5.0


def test_road3d_is_made_from_the_seed():
    np.testing.assert_array_equal(road3d.generate(3000, 5), road3d.generate(3000, 5))
    assert not np.array_equal(road3d.generate(3000, 5), road3d.generate(3000, 6))
