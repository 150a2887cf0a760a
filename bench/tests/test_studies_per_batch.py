"""The reader of ``studies_per_batch``, on synthetic worker batch spans;
times below in ms, the window is [1 s, 11 s]."""

import sys
import types

import pytest

from bench.lib import cells

tracing = pytest.importorskip("repro.tracing")

MS = 1_000_000


def _batches(monkeypatch, *spans_ms):
    """Worker batch spans (start, end, counts) on a stubbed snapshot."""
    recs = [tracing.SpanRecord("vizier.worker.batch", (f"op{i}",), i + 1,
                               None, 0, int(a * MS), int(b * MS), 0, counts)
            for i, (a, b, counts) in enumerate(spans_ms)]
    monkeypatch.setattr(
        tracing, "snapshot",
        lambda t0, t1: [r for r in recs if r.end_ns >= t0 and r.start_ns <= t1])
    return types.SimpleNamespace(t0=1.0, seconds=10.0)


def test_studies_per_batch_is_the_mean_count_of_batches_ending_inside(
        monkeypatch):
    ctx = _batches(monkeypatch,
                   (1020, 1220, {"ops": 3, "studies": 2}),
                   (2000, 2100, {"ops": 1, "studies": 1}),
                   (5000, 5400, {"ops": 5, "studies": 3}),
                   (10900, 11200, {"ops": 2, "studies": 2}))  # ends after
    assert cells.metric_reader("studies_per_batch.steady").read(ctx) == \
        pytest.approx(2.0)


def test_studies_per_batch_reads_nothing_without_the_count(monkeypatch):
    ctx = _batches(monkeypatch, (1020, 1220, {"ops": 3}),
                   (2000, 2100, {"ops": 1}))
    assert cells.metric_reader("studies_per_batch.steady").read(ctx) is None


def test_studies_per_batch_reads_nothing_from_a_program_without_spans(
        monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    ctx = types.SimpleNamespace(t0=1.0, seconds=10.0)
    assert cells.metric_reader("studies_per_batch").read(ctx) is None
