"""Stand-in for the UCI 3D Road Network (North Jutland): (lon, lat, alt).

The UCI file holds the points of North Jutland's road network as
longitude and latitude in degrees and altitude in metres. The stand-in
keeps those units and the file's extent (``EXTENT``), and its shape:

- roads are polylines of densely spaced points (``STEP_M`` apart, a
  gently turning heading), started uniformly over the extent and
  reflected at its edges;
- altitude is a smooth terrain over (lon, lat), so points close on the
  ground have close altitudes, mapped by rank onto a right-skewed
  marginal (shifted gamma, ``ALT_MEAN`` and ``ALT_SD``, truncated to
  the extent): most roads lie in the low tens of metres, a few hills
  reach the top.

In raw units the altitude's span (146 m) dwarfs the degrees (3 and
1.2), so it dominates the bounding box's diagonal, as in the source.
The numbers are the source's as the configuration's ``assumed`` list
states them; the file itself is not in the repository.
"""
from __future__ import annotations

import numpy as np
from scipy import special

# lon (deg), lat (deg), alt (m): the span of the UCI file's columns
EXTENT = ((8.1461, 11.1993), (56.5825, 57.7505), (-8.6188, 137.4498))
ALT_MEAN, ALT_SD = 22.2, 18.6       # metres
STEP_M = 30.0                       # spacing of points along a road
POINTS_PER_ROAD = 500
TURN_SD = 0.05                      # heading change per step, radians
M_PER_DEG_LAT = 111_320.0
N_HILLS = 400                       # terrain: a sum of Gaussian hills
HILL_SD_DEG = (0.02, 0.25)
GRID = (512, 256)                   # terrain nodes in lon, lat


def _reflect(x: np.ndarray, lo: float, hi: float) -> np.ndarray:
    w = hi - lo
    t = np.mod(x - lo, 2 * w)
    return lo + np.where(t <= w, t, 2 * w - t)


def _roads(n: int, rng) -> np.ndarray:
    """(n, 2) lon/lat points along polylines of ``POINTS_PER_ROAD``."""
    (lon0, lon1), (lat0, lat1), _ = EXTENT
    n_roads = -(-n // POINTS_PER_ROAD)
    start = rng.uniform((lon0, lat0), (lon1, lat1), size=(n_roads, 2))
    heading = rng.uniform(0, 2 * np.pi, size=(n_roads, 1))
    turns = heading + np.cumsum(
        rng.standard_normal((n_roads, POINTS_PER_ROAD)) * TURN_SD, axis=1)
    lat_mid = np.deg2rad((lat0 + lat1) / 2)
    step_lat = STEP_M / M_PER_DEG_LAT
    step_lon = STEP_M / (M_PER_DEG_LAT * np.cos(lat_mid))
    lon = start[:, :1] + np.cumsum(np.cos(turns), axis=1) * step_lon
    lat = start[:, 1:] + np.cumsum(np.sin(turns), axis=1) * step_lat
    xy = np.stack([_reflect(lon, lon0, lon1), _reflect(lat, lat0, lat1)], -1)
    return xy.reshape(-1, 2)[:n]


def _terrain(xy: np.ndarray, rng) -> np.ndarray:
    """A smooth field over (lon, lat): a sum of Gaussian hills on a
    ``GRID`` of nodes, interpolated bilinearly at the points."""
    (lon0, lon1), (lat0, lat1), _ = EXTENT
    centres = rng.uniform((lon0, lat0), (lon1, lat1), size=(N_HILLS, 2))
    sd = rng.uniform(*HILL_SD_DEG, size=N_HILLS)
    height = rng.exponential(1.0, size=N_HILLS)
    gx = np.linspace(lon0, lon1, GRID[0])
    gy = np.linspace(lat0, lat1, GRID[1])
    ex = np.exp(-(gx[None] - centres[:, :1]) ** 2 / (2 * sd[:, None] ** 2))
    ey = np.exp(-(gy[None] - centres[:, 1:]) ** 2 / (2 * sd[:, None] ** 2))
    grid = np.einsum("h,hx,hy->xy", height, ex, ey)
    fx = (xy[:, 0] - lon0) / (lon1 - lon0) * (GRID[0] - 1)
    fy = (xy[:, 1] - lat0) / (lat1 - lat0) * (GRID[1] - 1)
    ix = np.minimum(fx.astype(np.int64), GRID[0] - 2)
    iy = np.minimum(fy.astype(np.int64), GRID[1] - 2)
    wx, wy = fx - ix, fy - iy
    return ((1 - wx) * (1 - wy) * grid[ix, iy] + wx * (1 - wy) * grid[ix + 1, iy]
            + (1 - wx) * wy * grid[ix, iy + 1] + wx * wy * grid[ix + 1, iy + 1])


def _altitude(field: np.ndarray) -> np.ndarray:
    """Map the terrain by rank onto the shifted gamma marginal with
    ``ALT_MEAN`` and ``ALT_SD``, truncated to the altitude extent."""
    lo, hi = EXTENT[2]
    scale = ALT_SD ** 2 / (ALT_MEAN - lo)
    shape = (ALT_MEAN - lo) / scale
    u_top = special.gammainc(shape, (hi - lo) / scale)
    rank = np.empty(len(field))
    rank[np.argsort(field, kind="stable")] = np.arange(len(field))
    u = (rank + 0.5) / len(field) * u_top
    return lo + special.gammaincinv(shape, u) * scale


def generate(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    xy = _roads(n, rng)
    alt = _altitude(_terrain(xy, rng))
    return rng.permutation(np.column_stack([xy, alt]), axis=0)
