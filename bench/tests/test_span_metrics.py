"""The readers of the program-span metrics, on a synthetic span tree.

Two suggest ops, ``opA`` and ``opB``, served by one worker batch inside the
window [1 s, 11 s]; times below in ms. A batch that starts after the window
and the spans of its op must not count."""

import sys
import types

import pytest

from bench.lib import cells

tracing = pytest.importorskip("repro.tracing")

MS = 1_000_000


def _tree():
    recs = []

    def mk(name, a, b, trace=None, parent=None, cpu=None, **counts):
        r = tracing.SpanRecord(name, trace, len(recs) + 1,
                               None if parent is None else parent.span_id,
                               0, int(a * MS), int(b * MS),
                               None if cpu is None else int(cpu * MS), counts)
        recs.append(r)
        return r

    for op, rid, (c0, c1), (d0, d1), (p0, p1), (w0, w1) in (
            ("opA", "r1", (1000, 1012), (1002, 1010), (1003, 1009),
             (1003, 1004)),
            ("opB", "r3", (1005, 1011), (1006, 1010), (1007, 1009),
             (1007, 1008.5))):
        mk("vizier.rpc.call", c0, c1, method="SuggestTrials", rid=rid)
        d = mk("vizier.rpc.dispatch", d0, d1, op, method="SuggestTrials",
               rid=rid)
        p = mk("vizier.suggest.prepare", p0, p1, op, d)
        mk("vizier.lock.wait", w0, w1, op, p)
        mk("vizier.datastore.query", p0, p0 + 0.5, op, p)
        mk("vizier.queue.pending", p1, 1020, op)
    for rid, op, (c0, c1), (d0, d1) in (("r2", "opA", (1013, 1225),
                                         (1014, 1224)),
                                        ("r4", "opB", (1012, 1230),
                                         (1013, 1226))):
        mk("vizier.rpc.call", c0, c1, method="WaitOperation", rid=rid)
        d = mk("vizier.rpc.dispatch", d0, d1, op, method="WaitOperation",
               rid=rid)
        mk("vizier.op.wait", d0 + 1, d1 - 1, op, d)
    mk("vizier.lease.wait", 900, 1020, ops=2)
    mk("vizier.lease.wait", 1230, 1500, ops=1)
    mk("vizier.lease.wait", 11500, 11600)          # after the window
    ops = ("opA", "opB")
    b = mk("vizier.worker.batch", 1020, 1220, ops, cpu=150, ops=2)
    q = mk("vizier.datastore.query", 1021, 1031, ops, b)
    mk("vizier.datastore.lock.wait", 1021, 1023, ops, q)
    mk("vizier.datastore.decode", 1031, 1061, ops, b, trials=5000)
    mk("vizier.datastore.query", 1062, 1064, ops, b)
    mk("vizier.datastore.decode", 1064, 1066, ops, b, trials=100)
    mk("vizier.policy.suggest", 1070, 1200, ops, b)
    f = mk("vizier.finalize", 1200, 1218, ops, b)
    mk("vizier.lock.wait", 1200, 1203, ops, f)
    late = mk("vizier.worker.batch", 12000, 12100, ("opC",), cpu=10, ops=1)
    mk("vizier.datastore.decode", 12001, 12050, ("opC",), late, trials=9)
    return recs


@pytest.fixture
def ctx(monkeypatch):
    recs = _tree()
    monkeypatch.setattr(
        tracing, "snapshot",
        lambda t0, t1: [r for r in recs if r.end_ns >= t0 and r.start_ns <= t1])
    return types.SimpleNamespace(t0=1.0, seconds=10.0)


@pytest.mark.parametrize("metric, want", [
    ("datastore_query_ms", (8 + 2) / 2),
    ("datastore_decode_ms", (30 + 2) / 2),
    ("trials_decoded", 5100 / 2),
    ("study_lock_wait_ms", (1 + 1.5 + 3) / 2),
    ("finalize_ms", 15 / 2),
    ("rpc_transport_ms", ((4 + 2) + (2 + 5)) / 2),
    ("ops_per_lease", 1.5),
    ("worker_offcpu_ms", 50 / 2),
])
def test_reader_on_a_synthetic_tree(ctx, metric, want):
    assert cells.metric_reader(f"{metric}.steady").read(ctx) == \
        pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "datastore_query_ms", "datastore_decode_ms", "trials_decoded",
    "study_lock_wait_ms", "finalize_ms", "rpc_transport_ms",
    "ops_per_lease", "worker_offcpu_ms"])
def test_reader_reads_nothing_from_a_program_without_spans(monkeypatch,
                                                           metric):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    ctx = types.SimpleNamespace(t0=1.0, seconds=10.0)
    assert cells.metric_reader(metric).read(ctx) is None


def test_reader_reads_nothing_from_a_window_without_batches(monkeypatch):
    monkeypatch.setattr(tracing, "snapshot", lambda t0, t1: [])
    ctx = types.SimpleNamespace(t0=1.0, seconds=10.0)
    assert cells.metric_reader("datastore_query_ms").read(ctx) is None
