"""Each graph generator, drawn small, against the scipy reference."""
import json
import os

import numpy as np
import pytest

import repro
from bench import reference
from bench.batches import kronecker as kron_batches
from bench.generators import delaunay, kronecker
from bench.tests.conftest import ROOT

SEED = 2**40 + 3   # wider than 32 bits, as a run's seeds may be
KRON = {"scale": 8, "edge_factor": 16, "initiator": [0.57, 0.19, 0.19]}


def test_reference_on_a_hand_graph():
    src = np.array([0, 2, 3, 4]), np.array([1, 3, 1, 4])
    np.testing.assert_array_equal(
        reference.component_labels(*src, 6), [0, 0, 0, 0, 4, 5])
    assert reference.mismatches(np.array([0, 1]), np.array([0, 0])) == 1
    assert reference.mismatches(np.array([0]), np.array([0, 0])) == 2


def _solve_matches_reference(src, dst, n):
    want = reference.component_labels(np.asarray(src), np.asarray(dst), n)
    got = repro.solve(repro.Graph(src=src, dst=dst, n_vertices=n))
    np.testing.assert_array_equal(np.asarray(got.labels), want)
    return want


def test_kronecker_draw():
    src, dst, n = kronecker.draw(KRON, SEED)
    assert n == 256 and src.shape == dst.shape == (16 * 256,)
    s, d = np.asarray(src), np.asarray(dst)
    assert 0 <= min(s.min(), d.min()) and max(s.max(), d.max()) < n
    # the same seed draws the same edges; another seed, others
    s2, _, _ = kronecker.draw(KRON, SEED)
    np.testing.assert_array_equal(s, np.asarray(s2))
    assert not np.array_equal(s, np.asarray(kronecker.draw(KRON, SEED + 1)[0]))
    # skewed degrees, not led by the low ids: the ids are permuted
    deg = np.bincount(np.concatenate([s, d]), minlength=n)
    assert deg.max() > 8 * np.median(deg) and deg.argmax() != 0
    _solve_matches_reference(src, dst, n)


def test_kronecker_batches_are_fresh_edges_of_the_same_graph():
    batches = kron_batches.Batches(KRON, SEED, 128, 3)
    (b0, b1, b2), c1 = batches.chunk(0), batches.chunk(1)
    assert len(c1) == 3 and b0[0].shape == b2[1].shape == (128,)
    assert not np.array_equal(np.asarray(b0[0]), np.asarray(b1[0]))
    assert not np.array_equal(np.asarray(b0[0]), np.asarray(c1[0][0]))
    # a chunk drawn again, here or by another Batches of the same seed,
    # is the same edges
    again = kron_batches.Batches(KRON, SEED, 128, 3).chunk(0)
    np.testing.assert_array_equal(np.asarray(again[2][1]), np.asarray(b2[1]))
    assert max(int(b0[0].max()), int(c1[2][1].max())) < 256
    # the batches are not the graph's own edges, but share its ids: the
    # busiest vertex of the graph is busy in the batches too
    src, dst, _ = kronecker.draw(KRON, SEED)
    assert not np.array_equal(np.asarray(src[:128]), np.asarray(b0[0]))
    hub = np.bincount(np.concatenate([np.asarray(src), np.asarray(dst)]),
                      minlength=256).argmax()
    ends = np.concatenate([np.asarray(a) for b in (b0, b1, b2) for a in b])
    assert np.bincount(ends, minlength=256)[hub] > 3 * len(ends) / 256


@pytest.mark.parametrize("scale", [6, 9])
def test_delaunay_draw(scale):
    cfg = {"scale": scale, "points_seed": 20}
    src, dst, n = delaunay.draw(cfg, SEED)
    assert n == 1 << scale
    s, d = np.asarray(src), np.asarray(dst)
    # a planar triangulation of points in general position: 3n - 3 - h
    # edges for h >= 3 points on the hull, each once, lower id first
    assert 2 * n < len(s) <= 3 * n - 6
    assert np.all(s < d)
    keys = s.astype(np.int64) * n + d
    assert len(np.unique(keys)) == len(keys)
    # another seed: the same edges in another order
    s2, d2, _ = delaunay.draw(cfg, SEED + 1)
    keys2 = np.asarray(s2).astype(np.int64) * n + np.asarray(d2)
    assert not np.array_equal(keys, keys2)
    np.testing.assert_array_equal(np.sort(keys), np.sort(keys2))
    # the same seed, the same order
    np.testing.assert_array_equal(s, np.asarray(delaunay.draw(cfg, SEED)[0]))
    labels = _solve_matches_reference(src, dst, n)
    assert np.all(labels == 0)          # one component


def test_delaunay_edges_of_a_hand_square():
    # a unit square with one point inside: four triangles, eight sides
    pts = np.array([[0, 0], [1, 0], [1, 1], [0, 1], [0.4, 0.6]])
    src, dst = delaunay.triangulation_edges(pts)
    assert list(zip(src.tolist(), dst.tolist())) == [
        (0, 1), (0, 3), (0, 4), (1, 2), (1, 4), (2, 3), (2, 4), (3, 4)]


def test_delaunay_n20_draws_the_published_vertex_count():
    with open(os.path.join(ROOT, "bench", "configs", "delaunay-n20.json")) as f:
        cfg = json.load(f)
    assert 1 << cfg["scale"] == cfg["vertices"] == 1_048_576
    assert cfg["reduced"] == {} and isinstance(cfg["points_seed"], int)
