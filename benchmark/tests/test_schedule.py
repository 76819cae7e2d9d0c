"""The arrival schedule: Poisson, absolute, one sample path from the mix's
schedule_seed, entered at a point of its cycle that the seed draws."""

import numpy as np

import loadgen


def test_schedule_is_a_poisson_sample_path_and_every_seed_replays_it():
    rate, seconds, n = 100.0, 10.0, 1000
    a = loadgen.schedule(rate, seconds, 23)
    assert np.array_equal(a, loadgen.schedule(rate, seconds, 23))
    assert not np.array_equal(a, loadgen.schedule(rate, seconds, 24))
    assert len(a) == n and 0.0 < a[0] and np.all(np.diff(a) > 0) and a[-1] < seconds
    cycle = lambda d: np.diff(np.concatenate([[0.0], d, [seconds]]))  # noqa: E731 — all n + 1 gaps
    gaps = cycle(a)
    # exponential gaps: mean 1/rate, coefficient of variation 1, and the
    # seconds of the window differ as a Poisson count does (sd = sqrt(rate))
    assert abs(gaps.mean() - seconds / (n + 1)) < 1e-12 and 0.9 < gaps.std() / gaps.mean() < 1.1
    per_second = np.histogram(a, bins=10, range=(0, seconds))[0]
    assert 5.0 < per_second.std() < 20.0 and per_second.sum() == n
    ks = np.abs(np.sort(1.0 - np.exp(-gaps * (n + 1) / seconds)) - (np.arange(n + 1) + 0.5) / (n + 1)).max()
    assert ks < 1.36 / np.sqrt(n + 1)                   # Kolmogorov-Smirnov against Exp(1), 5% level
    # a turn enters the same cycle of gaps elsewhere
    b = loadgen.schedule(rate, seconds, 23, turn=0.37)
    k = int(0.37 * (n + 1))
    np.testing.assert_allclose(cycle(b), np.roll(gaps, -k), rtol=0, atol=1e-9)
    assert len(b) == n and b[-1] < seconds and not np.allclose(a, b)


def test_latency_runs_from_the_due_instant_and_lateness_is_reported():
    from drivers.serve_open import window_stats

    recs = [{"status": 200, "due": 10.0 + i * 0.1, "sent": 10.0 + i * 0.1 + 0.004,
             "end": 10.0 + i * 0.1 + 0.050} for i in range(100)]
    recs[7]["status"] = 429
    st = window_stats({"rate": 10.0, "records": recs})
    assert st["attempted"] == 100 and st["failed"] == 1 and st["statuses"]["429"] == 1
    assert abs(st["p50_ms"] - 50.0) < 1e-6 and abs(st["late_p95_ms"] - 4.0) < 1e-6


def test_caption_readings():
    from drivers.serve_open import candidates

    assert candidates([5, 6, 7], T=4, eos=1) == [[5, 6, 7, 1], [0, 5, 6, 7], [5, 0, 6, 7], [5, 6, 0, 7], [5, 6, 7, 0]]
    assert candidates([5, 6], T=4, eos=1) == [[5, 6, 1]]
    assert candidates([5, 6, 7, 8], T=4, eos=1) == [[5, 6, 7, 8]]
    assert candidates([], T=4, eos=1) == [[1]]
