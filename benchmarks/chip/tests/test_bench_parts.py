"""The yardstick's parts: work counts, peaks, latency arithmetic, inputs."""
import numpy as np
import pytest

from chip import cost, gen, stats


def test_scoring_pass_counts_the_graph_not_the_plan():
    work = cost.scoring_pass(live_directed_edges=1_548_288, n_cap=262_144,
                             k=9)
    assert work == {"bytes": 12 * 1_548_288 + 4 * 262_144 * 9,
                    "flops": 2 * 1_548_288 + 4 * 262_144 * 9}


def test_least_time_names_its_bound():
    peak = cost.peaks("TPU v5 lite")
    least, bound = cost.least_time({"bytes": 819_000_000, "flops": 1}, peak)
    assert bound == "memory" and least == pytest.approx(1e-3)
    least, bound = cost.least_time({"bytes": 1, "flops": 197_000_000_000},
                                   peak)
    assert bound == "compute" and least == pytest.approx(1e-3)


def test_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")


def test_latency_is_due_to_commit_in_fifo_order():
    due = np.array([0.0, 0.1, 0.2, 0.3, 0.4])
    # superstep 1 commits the first two events, returning at 0.5 s;
    # superstep 2 commits none; superstep 3 the last three, at 1.2 s
    lat = stats.commit_latencies(due, [2, 0, 3], [0.5, 0.8, 1.2])
    np.testing.assert_allclose(lat, [0.5, 0.4, 1.0, 0.9, 0.8])


def test_latency_needs_every_event_committed():
    with pytest.raises(ValueError):
        stats.commit_latencies(np.zeros(3), [1, 1], [0.1, 0.2])


@pytest.mark.parametrize("q,want", [(50, 3.0), (95, 5.0), (100, 5.0),
                                    (1, 1.0)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(np.array([5.0, 1.0, 4.0, 2.0, 3.0]), q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile(np.array([]), 95)


def test_inputs_repeat_per_seed_and_differ_across_seeds():
    kw = dict(scale=8, a=0.57, b=0.19, c=0.19)
    a = gen.stream_events(2 ** 31 + 9, 500, **kw)
    b = gen.stream_events(2 ** 31 + 9, 500, **kw)
    c = gen.stream_events(2 ** 31 + 10, 500, **kw)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    ends = a[:, 1:]
    assert (a[:, 1] != a[:, 2]).all() and ends.min() >= 0 and ends.max() < 256
    u1, v1 = gen.kronecker_edges(5, gen.BASE_EDGES, m=1000, **kw)
    u2, v2 = gen.kronecker_edges(5, gen.BASE_EDGES, m=1000, **kw)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_kronecker_skew_follows_the_initiator():
    """Before the permutation, bit 0 of the source is 1 with probability
    C + D = 0.24; a relabelling keeps the degree skew, so the busiest tenth
    of the vertices holds far more than a tenth of the edge ends."""
    u, v = gen.kronecker_edges(1, gen.BASE_EDGES, scale=12, m=1 << 16,
                               a=0.57, b=0.19, c=0.19)
    deg = np.bincount(np.concatenate([np.asarray(u), np.asarray(v)]),
                      minlength=1 << 12)
    top = np.sort(deg)[::-1][: (1 << 12) // 10].sum() / deg.sum()
    assert top > 0.4


def test_base_graph_is_simple_and_sorted():
    u = np.array([3, 1, 1, 2, 5, 0], np.int32)
    v = np.array([1, 3, 1, 0, 4, 2], np.int32)
    src, dst, em, nm, edges = gen.dedupe_graph(u, v, n=6, e_cap=8)
    assert int(edges) == 3
    np.testing.assert_array_equal(np.asarray(src)[:3], [0, 1, 4])
    np.testing.assert_array_equal(np.asarray(dst)[:3], [2, 3, 5])
    np.testing.assert_array_equal(np.asarray(em), [1, 1, 1, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(nm), [1, 1, 1, 1, 1, 1])


def test_due_offsets():
    np.testing.assert_allclose(gen.due_offsets("uniform", 4.0, 3, 0),
                               [0.0, 0.25, 0.5])
    p = gen.due_offsets("poisson", 100.0, 2000, 3)
    assert p[0] == 0.0 and (np.diff(p) >= 0).all()
    assert 15 < p[-1] < 25
    np.testing.assert_array_equal(p, gen.due_offsets("poisson", 100.0,
                                                     2000, 3))
    with pytest.raises(ValueError):
        gen.due_offsets("bursty", 1.0, 1, 0)
