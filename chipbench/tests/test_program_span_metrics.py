"""The per-layer metrics that read the program's own spans, fed a program
tracer filled by hand.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import bench  # noqa: E402
from repro.core import tracing  # noqa: E402

METRICS = ("job_ms.pack", "job_ms.plan", "window_ms.counters",
           "queue_wait_ms")


def _reader(name):
    return bench.load_module(BENCH / "metrics" / f"{name}.py",
                             "m_" + name.replace(".", "_"))


@pytest.fixture
def program():
    tracing.PROGRAM.flush()
    yield tracing.PROGRAM
    tracing.PROGRAM.flush()


def _span(program, name, t0, t1, n=-1):
    program.add_region(name, t0, t1, n=n)


def _fleet_jobs(program):
    """Two counter jobs of 3 windows each: pack 0.2 and 0.4 s, plan 0.05
    and 0.15 s, windows of 10 ms, totals 3 and 5 ms."""
    t = 100.0
    for pack, plan, totals in ((0.2, 0.05, 0.003), (0.4, 0.15, 0.005)):
        t0 = t
        _span(program, "fleet.pack", t, t + pack)
        t += pack
        _span(program, "fleet.plan", t, t + plan)
        t += plan
        for _ in range(3):
            _span(program, "fleet.window", t, t + 0.01, n=1024)
            t += 0.01
        _span(program, "fleet.totals", t, t + totals)
        t += totals
        _span(program, "fleet.attribute", t0, t)


def test_fleet_readers(program):
    _fleet_jobs(program)
    assert _reader("job_ms.pack").read({}) == pytest.approx(300.0)
    assert _reader("job_ms.plan").read({}) == pytest.approx(100.0)
    # (6 windows x 10 ms + 3 ms + 5 ms) over 6 windows
    assert _reader("window_ms.counters").read({}) == pytest.approx(68 / 6)
    assert _reader("queue_wait_ms").read({}) is None


def test_queue_wait_is_the_median_wait(program):
    for rid, wait in enumerate((0.0, 2.0, 9.0, 3.0, 30.0)):
        program.add_region("serve.queued", 50.0 - wait, 50.0, step=rid)
    _span(program, "serve.run", 10.0, 90.0, n=5)
    assert _reader("queue_wait_ms").read({}) == pytest.approx(3000.0)
    for name in ("job_ms.pack", "job_ms.plan", "window_ms.counters"):
        assert _reader(name).read({}) is None


@pytest.mark.parametrize("name", METRICS)
def test_reader_without_program_spans_reads_nothing(program, monkeypatch,
                                                    name):
    """An empty tracer, or a program that has none (an older commit),
    leaves the metric out of the line."""
    assert _reader(name).read({}) is None
    _fleet_jobs(program)
    program.add_region("serve.queued", 1.0, 2.0)
    monkeypatch.delattr(tracing, "PROGRAM")
    assert _reader(name).read({}) is None
