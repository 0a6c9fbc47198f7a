"""Percentile, rate and spread arithmetic, the open loop's accounting of
rejected and unanswered probes, its retry of rejected ones, and the
stalls it logs."""
import math
import time

import numpy as np
import pytest

import _benchroot  # noqa: F401  (puts the benchmark on the path)

from bench.harness import probe, stats


def test_percentile_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 50) == 50
    assert stats.percentile(values, 99) == 99
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failed_requests_are_infinitely_late():
    answered = [1.0] * 98
    assert stats.percentile(answered + [math.inf] * 2, 99) == math.inf
    assert stats.percentile(answered + [math.inf] * 2, 98) == 1.0
    assert stats.percentile(answered + [math.inf] * 2, 50) == 1.0


def test_rate_and_spread():
    assert stats.rate(262_144, 2.0) == 131_072
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    # quartiles 1.75 and 5.25 (statistics' exclusive method), median 3.5
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


class _Result:
    candidates = np.zeros(0, np.int64)
    block_sizes = np.zeros(0, np.int64)


class _FakeService:
    """Rejects every third offer at admission, and every offer once it has
    accepted ``capacity`` probes; never answers every fifth one it
    accepted."""

    def __init__(self, capacity=math.inf):
        from repro.serving.service import BackpressureError
        self._reject = BackpressureError
        self.capacity = capacity
        self.probe_responses = []
        self.queue = []
        self.calls = 0
        self.accepted = 0

    @property
    def busy(self):
        return bool(self.queue)

    def submit_probe(self, tenant, keys, valid, include_probe=False):
        self.calls += 1
        if self.calls % 3 == 0 or self.accepted >= self.capacity:
            raise self._reject("full")
        self.accepted += 1
        self.queue.append(self.accepted)
        return self.accepted

    def step(self):
        from repro.serving.service import ProbeResponse
        keep = []
        for uid in self.queue:
            if uid % 5 == 0:
                keep.append(uid)   # never answered
            else:
                self.probe_responses.append(
                    ProbeResponse(uid, "t", "ok", [_Result()], 0.0))
        self.queue = keep


def _drive(svc, n):
    return probe.drive(svc, np.zeros((1, 2, 2), np.uint32),
                       np.ones((1, 2), bool), np.linspace(0.0, 0.05, n),
                       np.zeros(n, np.int64), include_probe=False,
                       drain_s=0.05)


def test_rejected_and_unanswered_probes_count_as_failed():
    """Probes that no step admits before the drain ends, and probes
    admitted but never answered, are failed."""
    n, admitted = 30, 20
    got = _drive(_FakeService(capacity=admitted), n)
    lat = got["latency"]
    # offered again in due order, so probe k got uid k + 1
    unanswered = np.arange(4, admitted, 5)
    assert np.isinf(lat[admitted:]).all()
    assert np.isinf(lat).sum() == n - admitted + len(unanswered)
    assert np.isinf(lat[unanswered]).all()
    assert got["lost"] == len(unanswered)
    assert len(got["answers"]) == admitted - len(unanswered)
    assert got["rejections"] > n - admitted
    # latency runs from the due time, so it is never below the wait
    ok = np.isfinite(lat)
    assert np.all(lat[ok] >= 0) and np.all(lat[ok] >= got["wait"][ok])
    assert stats.percentile(list(lat), 99) == math.inf


def test_a_rejected_probe_is_offered_again():
    """Every third offer is rejected; each is admitted on the next offer,
    after a step, and its latency and lateness run from its due time."""
    n = 30
    svc = _FakeService()
    got = _drive(svc, n)
    unanswered = np.arange(4, n, 5)
    assert got["rejections"] > 0 and svc.calls == n + got["rejections"]
    assert np.isinf(got["latency"]).sum() == len(unanswered)
    assert got["lost"] == len(unanswered)
    assert not np.isnan(got["late"]).any()
    ok = np.isfinite(got["latency"])
    assert np.all(got["latency"][ok] >= got["late"][ok])


class _StallingService(_FakeService):
    """Answers every probe; its third step stands still for 0.4 s."""

    def __init__(self):
        super().__init__()
        self.steps = 0

    def submit_probe(self, *args, **kwargs):
        self.accepted += 1
        self.queue.append(self.accepted)
        return self.accepted

    def step(self):
        from repro.serving.service import ProbeResponse
        self.steps += 1
        if self.steps == 3:
            time.sleep(0.4)
        for uid in self.queue:
            self.probe_responses.append(
                ProbeResponse(uid, "t", "ok", [_Result()], 0.0))
        self.queue = []


def test_a_long_step_is_logged_as_a_stall():
    n = 20
    got = probe.drive(_StallingService(), np.zeros((1, 2, 2), np.uint32),
                      np.ones((1, 2), bool), np.linspace(0.0, 0.1, n),
                      np.zeros(n, np.int64), include_probe=False,
                      drain_s=1.0)
    assert len(got["stalls"]) == 1
    stall = got["stalls"][0]
    assert 0.35 < stall["seconds"] < 0.7 and stall["cpu_s"] < 0.1
    assert got["longest_step"] == pytest.approx(stall["seconds"])
    assert np.isfinite(got["latency"]).all() and got["lost"] == 0
