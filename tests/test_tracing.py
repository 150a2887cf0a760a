"""Program spans (repro.tracing): the module on its own, then the span tree
of one suggest op served through DefaultVizierServer."""

import threading
import time
import uuid

import pytest

from repro import tracing
from repro.core import Measurement, ScaleType, StudyConfig, Trial
from repro.service import DefaultVizierServer, VizierClient


def _mine(trace_id):
    return [r for r in tracing.snapshot() if r.serves(trace_id)]


def test_nested_spans_keep_parent_trace_id_counts_and_self_time():
    tid = uuid.uuid4().hex
    with tracing.span("test.outer", trace_id=tid, rows=3) as outer:
        with tracing.span("test.inner"):
            time.sleep(0.01)
        outer.add(bytes=10)
    by_name = {r.name: r for r in _mine(tid)}
    outer, inner = by_name["test.outer"], by_name["test.inner"]
    assert inner.parent_id == outer.span_id and outer.parent_id is None
    assert inner.trace_id == tid
    assert outer.counts == {"rows": 3, "bytes": 10}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    self_ns = outer.wall_ns - inner.wall_ns
    assert 0 <= self_ns < outer.wall_ns
    assert inner.wall_ns >= 10_000_000
    assert inner.thread_id == outer.thread_id == threading.get_ident()


def test_sleeping_span_reads_little_thread_cpu():
    tid = uuid.uuid4().hex
    with tracing.span("test.sleep.wait", trace_id=tid):
        time.sleep(0.05)
    (r,) = _mine(tid)
    assert r.waiting
    assert r.wall_ns >= 50_000_000
    assert 0 <= r.cpu_ns < r.wall_ns / 2


def test_end_closes_a_span_early_and_exit_does_not_close_it_twice():
    tid = uuid.uuid4().hex
    lock = threading.Lock()
    with tracing.span("test.op", trace_id=tid):
        with tracing.span("test.lock.wait") as wait, lock:
            wait.end()
            time.sleep(0.01)
    recs = _mine(tid)
    assert [r.name for r in recs] == ["test.op", "test.lock.wait"]
    op, wait = recs
    assert wait.parent_id == op.span_id
    assert wait.wall_ns < op.wall_ns - 5_000_000


def test_ring_keeps_only_the_newest_spans():
    ring = tracing.SpanRing(4)
    for i in range(10):
        ring.add(("test.r", None, i + 1, None, 0, i, i, 0, {"i": i}))
    got = ring.snapshot()
    assert [r.counts["i"] for r in got] == [6, 7, 8, 9]


def test_snapshot_returns_the_spans_that_overlap_the_window():
    ring = tracing.SpanRing(16)
    for sid, (a, b) in enumerate([(0, 10), (5, 25), (20, 30), (31, 40),
                                  (50, 60)], start=1):
        ring.add(("test.w", None, sid, None, 0, a, b, 0, {}))
    assert [r.span_id for r in ring.snapshot(20, 35)] == [2, 3, 4]
    assert [r.span_id for r in ring.snapshot(61, 70)] == []
    assert len(ring.snapshot()) == 5


def test_trace_id_kept_across_threads_and_resolved_through_parents():
    op = f"owners/t/studies/s/operations/{uuid.uuid4().hex}"
    start = time.perf_counter_ns()

    def worker():
        # the queue interval starts on the handler's thread and ends here
        tracing.record("test.pending", start, trace_id=op)
        with tracing.span("test.batch", trace_id=(op, "other"), ops=2):
            with tracing.span("test.child"):
                pass

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    with tracing.span("test.dispatch") as dispatch:
        with tracing.span("test.query"):
            pass                 # closed before the op has a name
        tracing.bind(op)
    assert dispatch.trace_id == op
    recs = {r.name: r for r in _mine(op)}
    assert set(recs) == {"test.pending", "test.batch", "test.child",
                         "test.dispatch", "test.query"}
    assert recs["test.pending"].cpu_ns is None
    assert recs["test.pending"].start_ns == start
    assert recs["test.child"].trace_id == (op, "other")
    assert recs["test.query"].trace_id == op       # via its parent
    assert recs["test.batch"].thread_id != recs["test.dispatch"].thread_id


# ---------------------------------------------------------------------------
# One served suggest op, end to end
# ---------------------------------------------------------------------------


def _gp_config() -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("x", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    root.add_float_param("y", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    return cfg


@pytest.fixture
def served_op(tmp_path):
    """Serves one GP suggest op of 2 trials through a worker-pool server on
    a SQLite store; returns the op's name and the spans of the test."""
    server = DefaultVizierServer(database_path=str(tmp_path / "db.sqlite3"),
                                 n_pythia_workers=1, n_shards=2)
    try:
        client = VizierClient.load_or_create_study(
            "traced", _gp_config(), client_id="w0", target=server.address)
        for i in range(8):
            x, y = (i + 1) / 9, ((i * 5) % 8) / 8
            t = Trial(parameters={"x": x, "y": y})
            t.complete(Measurement(metrics={"obj": -(x - 0.3) ** 2 - y}))
            client.add_trial(t)
        t0 = time.perf_counter_ns()
        trials = client.get_suggestions(count=2)
        t1 = time.perf_counter_ns()
        client.close()
    finally:
        server.stop()
    assert len(trials) == 2
    spans = tracing.snapshot(t0, t1)
    (call,) = [r for r in spans if r.name == "vizier.rpc.call"
               and r.counts["method"] == "SuggestTrials"
               and t0 <= r.start_ns]
    (dispatch,) = [r for r in spans if r.name == "vizier.rpc.dispatch"
                   and r.counts["rid"] == call.counts["rid"]]
    return dispatch.trace_id, [r for r in spans if r.serves(dispatch.trace_id)]


def _one(spans, name, **where):
    got = [r for r in spans if r.name == name
           and all(getattr(r, k) == v for k, v in where.items())]
    assert len(got) == 1, (name, where, got)
    return got[0]


def _inside(child, parent):
    return parent.start_ns <= child.start_ns <= child.end_ns <= parent.end_ns


def test_one_suggest_op_yields_its_span_tree(served_op):
    op, spans = served_op
    assert op.startswith("owners/default/studies/traced/operations/")
    by_id = {r.span_id: r for r in spans}
    for r in spans:              # every child lies inside its parent
        if r.parent_id in by_id:
            assert _inside(r, by_id[r.parent_id]), (r, by_id[r.parent_id])

    def under(ancestor):
        out = []
        for r in spans:
            p = r
            while p.parent_id in by_id:
                p = by_id[p.parent_id]
                if p is ancestor:
                    out.append(r)
                    break
        return out

    dispatches = [r for r in spans if r.name == "vizier.rpc.dispatch"]
    (suggest,) = [r for r in dispatches
                  if r.counts["method"] == "SuggestTrials"]
    prepare = _one(spans, "vizier.suggest.prepare")
    assert prepare.parent_id == suggest.span_id
    assert {"vizier.lock.wait", "vizier.datastore.query",
            "vizier.datastore.decode"} <= {r.name for r in under(prepare)}

    pending = _one(spans, "vizier.queue.pending")
    batch = _one(spans, "vizier.worker.batch")
    assert batch.trace_id == (op,) and batch.counts["ops"] == 1
    assert batch.counts["studies"] == 1
    assert prepare.start_ns <= pending.start_ns <= suggest.end_ns
    assert pending.end_ns <= batch.start_ns

    in_batch = under(batch)
    names = [r.name for r in in_batch]
    assert "vizier.datastore.query" in names
    decoded = [r.counts.get("trials") for r in in_batch
               if r.name == "vizier.datastore.decode"]
    assert 8 in decoded          # the completed trials, prefetched once
    policy = _one(in_batch, "vizier.policy.suggest")
    assert {r.name for r in under(policy)} >= {
        "vizier.policy.featurize", "vizier.policy.fit",
        "vizier.policy.acquire"}
    fit = _one(under(policy), "vizier.policy.fit")
    assert fit.counts["steps"] >= 1
    finalize = _one(in_batch, "vizier.finalize")
    assert finalize.start_ns >= policy.end_ns
    assert finalize.trace_id == (op,)
    assert "vizier.lock.wait" in {r.name for r in under(finalize)}

    waits = [r for r in dispatches if r.counts["method"] == "WaitOperation"]
    assert waits and waits[-1].end_ns >= finalize.end_ns
    # one long-poll park per WaitOperation frame that found the op pending
    parks = [r for r in spans if r.name == "vizier.op.wait"]
    assert parks
    assert all(any(p.parent_id == w.span_id for w in waits) for p in parks)


# ---------------------------------------------------------------------------
# A worker batch counts the studies its lease brought
# ---------------------------------------------------------------------------


def test_worker_batch_counts_the_distinct_studies_it_serves():
    """One queue shard holds ops of two studies (three ops): the lease
    brings all three and the batch records studies=2; a later lease of one
    study's op records 1."""
    from repro.service import operations as ops_lib
    from repro.service.work_queue import PythiaWorkerPool, ShardedWorkQueue

    tag = uuid.uuid4().hex
    mixed = [ops_lib.new_suggest_operation(f"owners/t/studies/{tag}{s}",
                                           "c", 1) for s in "aba"]
    single = [ops_lib.new_suggest_operation(f"owners/t/studies/{tag}c",
                                            "c", 1)]
    q = ShardedWorkQueue(1)
    for op in mixed:             # queued before any worker leases
        q.enqueue(op)

    def drain():
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and q.pending_count():
            time.sleep(0.01)
        assert q.pending_count() == 0

    pool = PythiaWorkerPool(q, lambda ops, guard: None, lambda op: False,
                            n_workers=1).start()
    try:
        drain()
        q.enqueue(single[0])
        drain()
    finally:
        pool.shutdown()
    for ops, studies in ((mixed, 2), (single, 1)):
        names = tuple(op["name"] for op in ops)
        batch = _one(_mine(names), "vizier.worker.batch")
        assert batch.trace_id == names
        assert batch.counts == {"ops": len(ops), "studies": studies}
