import numpy as np
import pytest

from bench import data, traffic


def test_poisson_rate():
    t = traffic.poisson_arrivals(40_000, 125.0, np.random.default_rng(1))
    assert np.all(np.diff(t) > 0)
    assert 40_000 / t[-1] == pytest.approx(125.0, rel=0.03)
    gaps = np.diff(t)
    # exponential gaps: the coefficient of variation is 1
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.03)


def test_window_count_is_fixed_and_times_uniform():
    for seed in (3, 2**31 + 11, 2**40 + 5):
        plan = traffic.make_plan({"kind": "open_loop", "rate_per_s": 80.0,
                                  "pool": 4096}, 20.0, data.host_rng(seed, 1))
        assert len(plan.due_s) == 1600
        assert np.all(np.diff(plan.due_s) >= 0)
        assert 0.0 <= plan.due_s[0] and plan.due_s[-1] < 20.0
        assert len(set(plan.order.tolist())) == 1600
    t = traffic.arrivals_in_window(20_000, 10.0, np.random.default_rng(2))
    # conditioned on its count, a Poisson process is uniform on the window
    counts, _ = np.histogram(t, bins=10, range=(0.0, 10.0))
    assert np.all(np.abs(counts - 2000) < 4 * np.sqrt(2000))


def test_seeding():
    mix = {"kind": "open_loop", "rate_per_s": 50.0, "pool": 2048}
    a = traffic.make_plan(mix, 10.0, data.host_rng(7, 1))
    b = traffic.make_plan(mix, 10.0, data.host_rng(7, 1))
    c = traffic.make_plan(mix, 10.0, data.host_rng(8, 1))
    d = traffic.make_plan(mix, 10.0, data.host_rng(7 + 2**32, 1))
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.order, b.order)
    assert not np.array_equal(a.due_s, c.due_s)
    assert not np.array_equal(a.due_s, d.due_s)


def test_corpus_seeding_uses_all_bits():
    spec = {"generator": "dirichlet", "d": 8, "alpha": 0.5}
    x1, q1 = data.make_corpus(spec, 64, 8, 5)
    x2, _ = data.make_corpus(spec, 64, 8, 5)
    x3, _ = data.make_corpus(spec, 64, 8, 5 + 2**32)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert not np.array_equal(np.asarray(x1), np.asarray(x3))
    np.testing.assert_allclose(np.asarray(x1).sum(axis=1), 1.0, rtol=1e-5)
    assert q1.shape == (8, 8)


def test_closed_batches_wrap_the_pool():
    plan = traffic.make_plan({"kind": "closed_batches", "batch": 4,
                              "pool": 10}, 5.0, np.random.default_rng(0))
    kind = traffic.kind("closed_batches")
    rows = np.concatenate([kind.batch_rows(plan, b) for b in range(5)])
    assert sorted(rows[:10].tolist()) == list(range(10))
    np.testing.assert_array_equal(rows[10:20], rows[:10])


def test_stall_watch_names_where_a_long_tick_waits():
    import time

    watch_cls = traffic.kind("open_loop").StallWatch
    with watch_cls(after_s=0.01, every_s=0.005) as watch:
        time.sleep(0.1)  # not inside a tick: nothing counted
        watch.since = time.perf_counter()
        time.sleep(0.2)
        watch.since = None
    (site, count), = watch.sites.most_common()
    assert "test_stall_watch_names_where_a_long_tick_waits" in site
    assert 10 <= count <= 40
