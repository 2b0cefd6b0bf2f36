"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp oracle in ref.py."""
import numpy as np
import pytest

import jax.numpy as jnp

try:  # hypothesis is optional: fall back to fixed deterministic cases
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import brute
from repro.kernels import ops, ref

SHAPES_PW = [
    (1, 1, 1),
    (8, 8, 2),
    (128, 128, 2),
    (129, 127, 3),
    (64, 256, 4),
    (200, 50, 64),
    (33, 65, 128),
    (17, 300, 200),
]


@pytest.mark.parametrize("m,n,d", SHAPES_PW)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_sq_l2(m, n, d, dtype):
    rng = np.random.default_rng(m * 1000 + n + d)
    q = jnp.asarray(rng.standard_normal((m, d)), dtype)
    p = jnp.asarray(rng.standard_normal((n, d)), dtype)
    got = ops.pairwise_sq_l2(q, p)
    want = ref.pairwise_sq_l2(q, p)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert got.dtype == jnp.float32
    assert (np.asarray(got) >= 0).all()


@pytest.mark.parametrize("m,n,d", [(64, 64, 8), (100, 30, 17)])
@pytest.mark.parametrize("bm,bn,bk", [(8, 128, 128), (128, 256, 256)])
def test_pairwise_block_sweep(m, n, d, bm, bn, bk):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    p = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    got = ops.pairwise_sq_l2(q, p, bm=bm, bn=bn, bk=bk)
    want = ref.pairwise_sq_l2(q, p)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


SHAPES_CM = [(1, 1), (10, 2), (8, 128), (100, 3), (517, 130), (1024, 64)]


@pytest.mark.parametrize("n,d", SHAPES_CM)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cov_matvec(n, d, dtype):
    rng = np.random.default_rng(n + d)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    mean = jnp.mean(x.astype(jnp.float32), axis=0).astype(dtype)
    w = jnp.asarray(rng.standard_normal(d), dtype)
    got = ops.cov_matvec(x, mean, w)
    want = ref.cov_matvec(x, mean, w)
    tol = 1e-3 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(
        got, want, rtol=tol, atol=tol * max(1.0, float(jnp.abs(want).max()))
    )


# -- fused streaming top-k ---------------------------------------------------
def _check_topk_l2(seed, m, n, d, k, finite_r, dead_frac, quantize):
    """Fused kernel == brute.constrained_knn over the live set, and
    BIT-IDENTICAL ordering to the stable-argsort / `query/merge`
    convention (ties to the lower slot) — including dead-slot masks,
    finite radii, N < k, and non-block-multiple shapes."""
    rng = np.random.default_rng(seed)
    pts = rng.standard_normal((n, d)).astype(np.float32)
    if quantize:  # force distance ties so ordering is actually exercised
        pts = np.round(pts)
    q = rng.standard_normal((m, d)).astype(np.float32)
    if quantize:
        q = np.round(q)
    gids = np.arange(n, dtype=np.int32)
    if dead_frac:
        dead = rng.random(n) < dead_frac
        gids[dead] = -1
    if finite_r:
        # keep r away from any actual distance: the kernel gates in f32
        # while the numpy oracle compares in f64, and a point sitting
        # exactly on the radius boundary would make the comparison
        # depend on epsilon instead of on the kernel's contract
        r = float(rng.uniform(0.5, 3.0))
        all_d = np.sqrt(((q[:, None] - pts[None]) ** 2).sum(-1))
        while np.any(np.abs(all_d - r) < 1e-4):
            r += 3e-4
    else:
        r = np.inf
    got_d, got_g = ops.topk_l2(q, pts, jnp.asarray(gids), r, k)
    got_d, got_g = np.asarray(got_d), np.asarray(got_g)
    assert got_d.shape == (m, k) and got_g.shape == (m, k)
    # rows ascending-sorted (the merge-convention invariant); +inf
    # padding pairs are equal-rank (inf - inf is NaN, not a violation)
    d1, d2 = got_d[:, :-1], got_d[:, 1:]
    assert np.all((d1 <= d2) | (np.isinf(d1) & np.isinf(d2)))
    ref_d, ref_g = ref.topk_l2(q, pts, jnp.asarray(gids), r, k)
    if quantize:
        # integer coordinates: both distance formulations are exact, so
        # the ordering oracle (unfused stable argsort, ties to the
        # lower slot) must match BIT-IDENTICALLY even across ties
        assert np.array_equal(got_g, np.asarray(ref_g)), (seed, m, n, d, k)
        assert np.array_equal(got_d, np.asarray(ref_d))
    else:
        np.testing.assert_allclose(got_d, ref_d, rtol=1e-5, atol=1e-5)
    # value oracle: brute force over the live subset only
    live = gids >= 0
    live_pts, live_ids = pts[live], np.nonzero(live)[0]
    for i in range(m):
        if live_pts.shape[0]:
            bi, bd = brute.constrained_knn(live_pts, q[i], k, r)
            want_g = live_ids[bi]
        else:
            want_g, bd = np.zeros(0, np.int64), np.zeros(0)
        row = got_g[i][got_g[i] >= 0]
        assert set(row.tolist()) == set(want_g.tolist()), (seed, i)
        np.testing.assert_allclose(
            got_d[i][: len(bd)], bd, rtol=1e-4, atol=1e-5
        )
        assert np.isinf(got_d[i][len(bd):]).all()
        assert (got_g[i][len(bd):] == -1).all()


_TOPK_CASES = [
    # seed, m, n, d, k, finite_r, dead_frac, quantize
    (0, 5, 40, 8, 8, False, 0.0, False),
    (1, 17, 300, 20, 8, True, 0.3, False),
    (2, 3, 3, 2, 8, False, 0.0, False),     # N < k
    (3, 8, 64, 3, 1, True, 0.2, False),     # k = 1
    (4, 33, 257, 5, 64, False, 0.1, False),  # k = 64, non-multiples
    (5, 9, 130, 2, 8, True, 0.0, True),      # ties via quantization
    (6, 4, 50, 3, 8, False, 1.0, False),     # all-dead arena
    (7, 2, 1, 1, 3, False, 0.0, False),      # single point, D=1
]

if HAVE_HYPOTHESIS:
    test_topk_l2_property = settings(max_examples=25, deadline=None)(
        given(
            seed=st.integers(0, 10_000),
            m=st.integers(1, 20),
            n=st.integers(1, 150),
            d=st.integers(1, 24),
            k=st.sampled_from([1, 8, 64]),
            finite_r=st.booleans(),
            dead_frac=st.sampled_from([0.0, 0.3, 1.0]),
            quantize=st.booleans(),
        )(_check_topk_l2)
    )
else:

    @pytest.mark.parametrize(
        "seed,m,n,d,k,finite_r,dead_frac,quantize", _TOPK_CASES
    )
    def test_topk_l2_fallback(seed, m, n, d, k, finite_r, dead_frac, quantize):
        _check_topk_l2(seed, m, n, d, k, finite_r, dead_frac, quantize)


def test_topk_l2_merge_convention_ties():
    """Duplicate points (exact ties): the fused kernel must report the
    lower arena slot first — the order `query/merge.merge_sorted` and a
    stable argsort agree on."""
    pts = np.zeros((10, 2), np.float32)
    q = np.zeros((3, 2), np.float32)
    gids = np.arange(100, 110, dtype=np.int32)
    d, g = ops.topk_l2(q, pts, jnp.asarray(gids), np.inf, 4)
    assert np.array_equal(
        np.asarray(g), np.tile(np.arange(100, 104, dtype=np.int32), (3, 1))
    )
    assert np.allclose(np.asarray(d), 0.0)


def test_topk_l2_empty_inputs():
    """N = 0 (and Q = 0) must return the all-padding answer, not crash
    — the brute referent can legitimately scan an empty live set."""
    q = np.zeros((3, 2), np.float32)
    d, g = ops.topk_l2(q, np.zeros((0, 2), np.float32),
                       jnp.zeros((0,), jnp.int32), np.inf, 4)
    assert d.shape == (3, 4) and g.shape == (3, 4)
    assert np.isinf(np.asarray(d)).all() and (np.asarray(g) == -1).all()
    d, g = ops.topk_l2(np.zeros((0, 2), np.float32),
                       np.zeros((5, 2), np.float32),
                       jnp.arange(5, dtype=jnp.int32), np.inf, 4)
    assert d.shape == (0, 4) and g.shape == (0, 4)


def test_topk_l2_per_query_radius():
    rng = np.random.default_rng(8)
    pts = rng.standard_normal((60, 3)).astype(np.float32)
    q = rng.standard_normal((4, 3)).astype(np.float32)
    gids = jnp.arange(60, dtype=jnp.int32)
    radii = np.asarray([0.1, 0.5, 1.5, np.inf], np.float32)
    got_d, got_g = ops.topk_l2(q, pts, gids, jnp.asarray(radii), 5)
    ref_d, ref_g = ref.topk_l2(q, pts, gids, jnp.asarray(radii), 5)
    assert np.array_equal(np.asarray(got_g), np.asarray(ref_g))
    np.testing.assert_allclose(got_d, ref_d, rtol=1e-5, atol=1e-6)


def test_brute_topk_matches_brute_oracle():
    """core/search_jax.brute_topk — the fused brute referent."""
    from repro.core import search_jax as sj

    rng = np.random.default_rng(12)
    pts = rng.standard_normal((200, 4)).astype(np.float32)
    q = rng.standard_normal((7, 4)).astype(np.float32)
    res = sj.brute_topk(pts, q, 6, 1.8)
    for i in range(7):
        bi, bd = brute.constrained_knn(pts, q[i], 6, 1.8)
        row = np.asarray(res.indices)[i]
        assert np.array_equal(row[: len(bi)], bi)
        assert (row[len(bi):] == -1).all()
        np.testing.assert_allclose(
            np.asarray(res.distances)[i][: len(bd)], bd, rtol=1e-4, atol=1e-5
        )


def test_lower_bounds_matches_search_quantity():
    """ops.lower_bounds == the D_N pruning quantity of §4.2."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((10, 3)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((20, 3)), jnp.float32)
    r = jnp.asarray(rng.uniform(0.1, 1.0, 20), jnp.float32)
    got = ops.lower_bounds(q, c, r)
    want = np.maximum(
        np.sqrt(((np.asarray(q)[:, None] - np.asarray(c)[None]) ** 2).sum(-1))
        - np.asarray(r)[None],
        0.0,
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_power_iteration_matches_eigh():
    from repro.core.pca import first_component_exact

    rng = np.random.default_rng(6)
    x = jnp.asarray(
        rng.standard_normal((400, 6)) @ np.diag([5, 2, 1, 0.5, 0.2, 0.1]),
        jnp.float32,
    )
    w = ops.power_iteration(x, iters=40)
    we = first_component_exact(np.asarray(x))
    assert abs(float(np.dot(np.asarray(w), we))) > 0.999


# -- the selection network's compare-exchange (lane rolls + iota masks) -----

_CMPX_KP = [1, 2, 4, 8, 16, 32, 64, 128]
_CMPX_BN = [128, 256, 512]


def _cmpx_stages(d, g, s, stages):
    """Every (stride, size, desc) stage of `topk_l2._cmpx`, each applied
    to the same input, in one interpret-mode kernel."""
    import jax
    from jax.experimental import pallas as pl

    from repro.kernels import topk_l2 as tk

    def kernel(d_ref, g_ref, s_ref, od_ref, og_ref, os_ref):
        for i, (stride, size, desc) in enumerate(stages):
            dd, gg, ss = tk._cmpx(
                d_ref[...], g_ref[...], s_ref[...], stride, size, desc
            )
            od_ref[i] = dd
            og_ref[i] = gg
            os_ref[i] = ss

    shape = (len(stages),) + d.shape
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct(shape, jnp.int32),
        ],
        interpret=True,
    )(d, g, s)


def _cmpx_ref(d, g, s, stride, size, desc):
    """One compare-exchange stage written pair by pair: lanes i and
    i | stride; the group is ascending iff (i & size) == 0 (always, for
    size 0), flipped by `desc`; a pair swaps only when its keys are
    strictly out of order, so equal keys keep their lanes."""
    d, g, s = d.copy(), g.copy(), s.copy()
    for i in range(d.shape[1]):
        if i & stride:
            continue
        j = i | stride
        asc = (size == 0 or (i & size) == 0) != desc
        j_first = (d[:, j] < d[:, i]) | ((d[:, j] == d[:, i]) & (s[:, j] < s[:, i]))
        i_first = (d[:, i] < d[:, j]) | ((d[:, i] == d[:, j]) & (s[:, i] < s[:, j]))
        swap = j_first if asc else i_first
        for a in (d, g, s):
            a[swap, i], a[swap, j] = a[swap, j], a[swap, i].copy()
    return d, g, s


@pytest.mark.parametrize("bn", _CMPX_BN)
@pytest.mark.parametrize("kp", _CMPX_KP)
def test_cmpx_strides_and_directions(kp, bn):
    """Each stride from 1 to kp, in both group directions (alternating
    sort groups and one-direction merge stages), on tie-heavy keys:
    lane for lane equal to the pairwise reference. The width covers the
    carried merge (2·kp lanes) as well as the block (bn lanes)."""
    rng = np.random.default_rng(kp * 1000 + bn)
    w = max(bn, 2 * kp)
    d = rng.integers(0, 3, (8, w)).astype(np.float32)
    d[rng.random((8, w)) < 0.2] = np.inf
    s = rng.integers(0, 4, (8, w)).astype(np.int32)
    g = np.tile(np.arange(w, dtype=np.int32), (8, 1))
    stages = []
    stride = 1
    while stride <= kp:
        for size, desc in ((2 * stride, False), (2 * stride, True),
                           (0, False), (0, True)):
            stages.append((stride, size, desc))
        stride *= 2
    got = [np.asarray(x) for x in _cmpx_stages(d, g, s, stages)]
    for i, stage in enumerate(stages):
        want = _cmpx_ref(d, g, s, *stage)
        for a, b in zip((x[i] for x in got), want):
            assert np.array_equal(a, b), stage


@pytest.mark.parametrize("bn", _CMPX_BN)
@pytest.mark.parametrize("kp", _CMPX_KP)
def test_selection_network_matches_stable_argsort(kp, bn):
    """The whole network (chunk sort, tournament, carried merge over
    several N blocks and two row tiles) on tie-heavy integer points
    with dead slots: ids and distances bit-identical to a stable
    argsort of the masked squared-distance row."""
    rng = np.random.default_rng(kp * 7 + bn)
    m, n = 16, 2 * bn + 37
    q = rng.integers(0, 3, (m, 2)).astype(np.float32)
    pts = rng.integers(0, 3, (n, 2)).astype(np.float32)
    gids = np.arange(n, dtype=np.int32)
    gids[rng.random(n) < 0.25] = -1
    got_d, got_g = ops.topk_l2(
        q, pts, jnp.asarray(gids), np.inf, kp, bm=16, bn=bn, bk=128
    )
    sq = ((q[:, None] - pts[None]) ** 2).sum(-1)
    sq[:, gids < 0] = np.inf
    order = np.argsort(sq, axis=1, kind="stable")[:, :kp]
    want_sq = np.take_along_axis(sq, order, 1)
    want_g = np.where(np.isinf(want_sq), -1, gids[order])
    assert np.array_equal(np.asarray(got_g), want_g)
    assert np.array_equal(np.asarray(got_d), np.sqrt(want_sq))
