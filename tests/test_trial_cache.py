"""The SQLite backends' cache of decoded terminal trials.

``list_trials_multi`` for terminal states serves decoded trials from a
per-file cache and decodes only the rows written since the last read. These
tests hold it to a cold read of the same file after every kind of write,
count what it decodes on the ``vizier.datastore.decode`` span, and check
that the shared objects are not changed by the policy that reads them.
"""

import random
import sqlite3
import sys
import threading
import time

import pytest

from repro import tracing
from repro.core import Measurement, Metadata, ScaleType, StudyConfig, Trial, TrialState
from repro.core.metadata import MetadataDelta
from repro.core.study import Study
from repro.service import datastore as datastore_lib
from repro.service.datastore import (
    NotFoundError,
    ShardedSqliteDatastore,
    SQLiteDatastore,
)
from repro.service.vizier_service import InProcessPythia

STATE_SETS = (
    [TrialState.COMPLETED],
    [TrialState.INFEASIBLE],
    [TrialState.COMPLETED, TrialState.INFEASIBLE],
    [TrialState.ACTIVE],
    None,
)


def _open(kind, path):
    if kind == "sqlite":
        return SQLiteDatastore(str(path / "v.db"))
    return ShardedSqliteDatastore(str(path / "shards"), n_shards=3)


def _config(algorithm="RANDOM_SEARCH") -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("x", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    root.add_float_param("y", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = algorithm
    return cfg


def _study(name, algorithm="RANDOM_SEARCH") -> Study:
    return Study(name=name, display_name=name,
                 study_config=_config(algorithm))


def _trial(rng) -> Trial:
    return Trial(parameters={"x": rng.random(), "y": rng.random()})


def _complete(ds, name, tid, rng):
    t = ds.get_trial(name, tid)
    if rng.random() < 0.2:
        t.complete(infeasibility_reason="diverged")
    else:
        t.complete(Measurement(metrics={"obj": rng.random()}))
    ds.update_trial(name, t)


def _protos(by_study):
    return {name: [t.to_proto() for t in trials]
            for name, trials in by_study.items()}


def _same_shard_names(kind, n):
    """``n`` study names that one SQLite file holds, whatever ``kind``."""
    from repro.service.operations import shard_of

    first = "owners/o/studies/a"
    names = [f"owners/o/studies/b{i}" for i in range(200)]
    return [first] + [b for b in names
                      if kind == "sqlite" or shard_of(b, 3) == shard_of(first, 3)
                      ][:n - 1]


def _completed_study(ds, name, n, rng):
    ds.create_study(_study(name))
    for _ in range(n):
        _complete(ds, name, ds.create_trial(name, _trial(rng)).id, rng)


def _decode_counts(fn):
    """Runs ``fn``; returns its result and the summed ``trials`` and
    ``cached`` counts of the decode spans it recorded on this thread."""
    me = threading.get_ident()
    t0 = time.perf_counter_ns()
    out = fn()
    spans = [r for r in tracing.snapshot(t0)
             if r.name == "vizier.datastore.decode" and r.thread_id == me
             and r.start_ns >= t0]
    return (out, sum(int(r.counts.get("trials", 0)) for r in spans),
            sum(int(r.counts.get("cached", 0)) for r in spans))


# ---------------------------------------------------------------------------
# equivalence with a cold read
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", ["sqlite", "sharded"])
def test_cached_read_equals_cold_read(kind, seed, tmp_path):
    """Seeded random interleavings of every kind of trial write; after each
    step the cached ``list_trials_multi`` equals a read by a datastore
    freshly opened on the same path, by ``to_proto`` and in order."""
    rng = random.Random(seed)
    ds = _open(kind, tmp_path)
    other = _open(kind, tmp_path)  # a second connection to the same files
    names = [f"owners/o/studies/s{i}" for i in range(3)]
    for name in names:
        ds.create_study(_study(name))
    ops = ["create", "create", "complete", "complete", "metadata", "delta",
           "delete_trial", "delete_study", "rollback", "other"]

    def ids(name, states=None):
        return [t.id for t in ds.list_trials(name, states=states)]

    for step in range(60):
        name = rng.choice(names)
        op = rng.choice(ops)
        active = ids(name, [TrialState.ACTIVE])
        done = ids(name, [TrialState.COMPLETED, TrialState.INFEASIBLE])
        if op == "create":
            t = _trial(rng)
            if rng.random() < 0.3:  # stored terminal from the start
                t.complete(Measurement(metrics={"obj": rng.random()}))
            t = ds.create_trial(name, t)
            if not t.state.is_terminal and rng.random() < 0.5:
                _complete(ds, name, t.id, rng)
        elif op == "complete" and active:
            _complete(ds, name, rng.choice(active), rng)
        elif op == "metadata" and done:
            md = Metadata()
            md.ns("test")["step"] = str(step)
            ds.update_trial_metadata(name, rng.choice(done), md)
        elif op == "delta" and done:
            delta = MetadataDelta()
            for tid in rng.sample(done, min(3, len(done))) + [10_000]:
                delta.assign("test", "delta", str(step), trial_id=tid)
            delta.assign("test", "study", str(step))
            assert ds.apply_metadata_delta(name, delta) == [10_000]
        elif op == "delete_trial" and (active or done):
            ds.delete_trial(name, rng.choice(active + done))
        elif op == "delete_study":
            ds.delete_study(name)
            ds.create_study(_study(name))
        elif op == "rollback":
            with pytest.raises(RuntimeError):
                with ds.study_transaction(name):
                    t = ds.create_trial(name, _trial(rng))
                    _complete(ds, name, t.id, rng)
                    if active:
                        _complete(ds, name, active[0], rng)
                    # a read inside the transaction sees its own writes
                    ds.list_trials_multi(names, states=[TrialState.COMPLETED])
                    raise RuntimeError("abort")
        elif op == "other":
            t = other.create_trial(name, _trial(rng))
            _complete(other, name, t.id, rng)
            if done:
                md = Metadata()
                md.ns("other")["step"] = str(step)
                other.update_trial_metadata(name, rng.choice(done), md)

        cold = _open(kind, tmp_path)
        try:
            for states in STATE_SETS:
                order = rng.sample(names, len(names)) + [rng.choice(names)]
                assert _protos(ds.list_trials_multi(order, states=states)) == \
                    _protos(cold.list_trials_multi(order, states=states)), \
                    (step, op, states)
        finally:
            cold.close()
    other.close()
    ds.close()


@pytest.mark.parametrize("kind", ["sqlite", "sharded"])
def test_cached_read_keeps_the_missing_study_contract(kind, tmp_path):
    ds = _open(kind, tmp_path)
    ds.create_study(_study("owners/o/studies/here"))
    with pytest.raises(NotFoundError) as ei:
        ds.list_trials_multi(["owners/o/studies/here", "owners/o/studies/ghost"],
                             states=[TrialState.COMPLETED])
    assert "ghost" in str(ei.value)
    assert ds.list_trials_multi([], states=[TrialState.COMPLETED]) == {}
    ds.close()


@pytest.mark.parametrize("kind", ["sqlite", "sharded"])
def test_writes_during_a_cold_decode(kind, tmp_path, monkeypatch):
    """A study's first read decodes outside the connection lock. A write
    that lands meanwhile is read by the next read, and a study deleted and
    re-created meanwhile does not get the stale decode installed."""
    from repro.service.operations import shard_of

    rng = random.Random(0)
    ds = _open(kind, tmp_path)
    a = "owners/o/studies/a"
    b = next(f"owners/o/studies/b{i}" for i in range(100)  # a's shard file
             if shard_of(f"owners/o/studies/b{i}", 3) == shard_of(a, 3))
    for name in (a, b):
        ds.create_study(_study(name))
        for _ in range(5):
            _complete(ds, name, ds.create_trial(name, _trial(rng)).id, rng)
    pending = ds.create_trial(a, _trial(rng))

    def during_decode():
        _complete(ds, a, pending.id, rng)
        ds.delete_study(b)
        ds.create_study(_study(b))
        _complete(ds, b, ds.create_trial(b, _trial(rng)).id, rng)

    class Hooked(Trial):
        hook = during_decode

        @classmethod
        def from_proto(cls, proto):
            hook, Hooked.hook = Hooked.hook, None
            if hook is not None:
                hook()
            return Trial.from_proto(proto)

    states = [TrialState.COMPLETED, TrialState.INFEASIBLE]
    monkeypatch.setattr(datastore_lib, "Trial", Hooked)
    first = ds.list_trials_multi([a, b], states=states)
    monkeypatch.undo()
    assert [len(first[a]), len(first[b])] == [5, 5]  # the read's own snapshot
    cold = _open(kind, tmp_path)
    assert _protos(ds.list_trials_multi([a, b], states=states)) == \
        _protos(cold.list_trials_multi([a, b], states=states))
    cold.close()
    ds.close()


class _FailingConn:
    """A connection whose ``nth`` query containing ``needle`` raises, as a
    busy database does; everything else goes to the real connection."""

    def __init__(self, conn, needle, nth):
        self._conn, self._needle, self._left = conn, needle, nth

    def execute(self, sql, *args):
        if self._needle in sql:
            self._left -= 1
            if self._left == 0:
                raise sqlite3.OperationalError("database is locked")
        return self._conn.execute(sql, *args)

    def __getattr__(self, name):
        return getattr(self._conn, name)


@pytest.mark.parametrize("fails", ["fetch", "decode"])
@pytest.mark.parametrize("kind", ["sqlite", "sharded"])
def test_a_failed_warm_read_loses_no_dirty_row(kind, fails, tmp_path,
                                               monkeypatch):
    """One read of two cached studies, each with a dirty row, raises in the
    second study's dirty fetch or decode. The error reaches the caller, and
    the next read still equals a cold read: the first study's merge and the
    second study's dirty marks both survive."""
    rng = random.Random(0)
    ds = _open(kind, tmp_path)
    names = _same_shard_names(kind, 2)
    for name in names:
        _completed_study(ds, name, 4, rng)
    pending = {name: ds.create_trial(name, _trial(rng)).id for name in names}
    states = [TrialState.COMPLETED, TrialState.INFEASIBLE]
    ds.list_trials_multi(names, states=states)  # both studies cached
    for name in names:
        _complete(ds, name, pending[name], rng)
    shard = ds if kind == "sqlite" else ds._shard(names[0])
    if fails == "fetch":
        monkeypatch.setattr(shard, "_conn",
                            _FailingConn(shard._conn, "trial_id IN", 2))
        error = sqlite3.OperationalError
    else:
        class Failing(Trial):
            left = 2

            @classmethod
            def from_proto(cls, proto):
                Failing.left -= 1
                if Failing.left == 0:
                    raise ValueError("undecodable row")
                return Trial.from_proto(proto)

        monkeypatch.setattr(datastore_lib, "Trial", Failing)
        error = ValueError
    with pytest.raises(error):
        ds.list_trials_multi(names, states=states)
    monkeypatch.undo()
    cold = _open(kind, tmp_path)
    for _ in range(2):
        out = ds.list_trials_multi(names, states=states)
        assert [len(out[name]) for name in names] == [5, 5]
        assert _protos(out) == _protos(cold.list_trials_multi(names,
                                                              states=states))
    cold.close()
    ds.close()


def test_concurrent_writers_and_cached_readers(tmp_path):
    """Writers complete and annotate trials while readers read through the
    cache, with a short switch interval; every read is in id order and
    terminal, and afterwards the cache equals a cold read."""
    ds = ShardedSqliteDatastore(str(tmp_path / "shards"), n_shards=2)
    names = [f"owners/o/studies/c{i}" for i in range(3)]
    for name in names:
        ds.create_study(_study(name))
        for _ in range(30):
            ds.create_trial(name, _trial(random.Random(0)))
    errs, bad = [], []
    stop = threading.Event()

    def writer(wid):
        rng = random.Random(wid)
        try:
            for i in range(40):
                name = rng.choice(names)
                t = ds.create_trial(name, _trial(rng))
                _complete(ds, name, t.id, rng)
                md = Metadata()
                md.ns("w")["i"] = str(i)
                ds.update_trial_metadata(name, rng.randint(1, 30), md)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    def reader():
        try:
            while not stop.is_set():
                out = ds.list_trials_multi(
                    names, states=[TrialState.COMPLETED, TrialState.INFEASIBLE])
                for trials in out.values():
                    ids = [t.id for t in trials]
                    if ids != sorted(set(ids)) or not all(
                            t.state.is_terminal for t in trials):
                        bad.append(ids)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        writers = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
        readers = [threading.Thread(target=reader) for _ in range(4)]
        for t in writers + readers:
            t.start()
        for t in writers:
            t.join(timeout=120)
        stop.set()
        for t in readers:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in writers + readers)
    assert not errs, errs
    assert not bad
    cold = ShardedSqliteDatastore(str(tmp_path / "shards"))
    for states in STATE_SETS:
        assert _protos(ds.list_trials_multi(names, states=states)) == \
            _protos(cold.list_trials_multi(names, states=states))
    cold.close()
    ds.close()


# ---------------------------------------------------------------------------
# what a read decodes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["sqlite", "sharded"])
def test_decode_counts_follow_the_dirty_rows(kind, tmp_path):
    rng = random.Random(0)
    ds = _open(kind, tmp_path)
    name = "owners/o/studies/counted"
    ds.create_study(_study(name))
    for _ in range(20):
        t = ds.create_trial(name, _trial(rng))
        t.complete(Measurement(metrics={"obj": rng.random()}))
        ds.update_trial(name, t)
    pending = ds.create_trial(name, _trial(rng))

    def read():
        return ds.list_trials_multi([name], states=[TrialState.COMPLETED])

    first, trials, cached = _decode_counts(read)
    assert (len(first[name]), trials, cached) == (20, 20, 0)
    # unchanged study: nothing decoded, every trial served from the cache
    again, trials, cached = _decode_counts(read)
    assert (trials, cached) == (0, 20)
    assert all(a is b for a, b in zip(again[name], first[name]))
    # one completion: exactly that row is decoded
    pending.complete(Measurement(metrics={"obj": 0.5}))
    ds.update_trial(name, pending)
    after, trials, cached = _decode_counts(read)
    assert (len(after[name]), trials, cached) == (21, 1, 20)
    # ACTIVE reads still decode every matching row
    ds.create_trial(name, _trial(rng))
    _, trials, cached = _decode_counts(
        lambda: ds.list_trials_multi([name], states=[TrialState.ACTIVE]))
    assert (trials, cached) == (1, 0)
    # a read inside a transaction sees its uncommitted rows, decoded fresh
    with ds.study_transaction(name):
        inside, trials, cached = _decode_counts(read)
    assert (len(inside[name]), trials, cached) == (21, 21, 0)
    assert _decode_counts(read)[1:] == (0, 21)
    ds.close()


def test_study_over_the_cap_takes_the_full_read(tmp_path, monkeypatch):
    monkeypatch.setattr(datastore_lib, "TERMINAL_CACHE_TRIALS", 12)
    rng = random.Random(0)
    ds = SQLiteDatastore(str(tmp_path / "v.db"))
    big, small = "owners/o/studies/big", "owners/o/studies/small"
    for name, n in ((big, 15), (small, 8)):
        ds.create_study(_study(name))
        for _ in range(n):
            t = ds.create_trial(name, _trial(rng))
            t.complete(Measurement(metrics={"obj": rng.random()}))
            ds.update_trial(name, t)

    def read(name):
        return lambda: ds.list_trials_multi([name], states=[TrialState.COMPLETED])

    for _ in range(2):  # over the cap: every read decodes every trial
        out, trials, cached = _decode_counts(read(big))
        assert (len(out[big]), trials, cached) == (15, 15, 0)
    _decode_counts(read(small))
    assert _decode_counts(read(small))[1:] == (0, 8)
    # a second study that does not fit beside the first evicts it
    monkeypatch.setattr(datastore_lib, "TERMINAL_CACHE_TRIALS", 10)
    other = "owners/o/studies/other"
    ds.create_study(_study(other))
    for _ in range(4):
        t = ds.create_trial(other, _trial(rng))
        t.complete(Measurement(metrics={"obj": rng.random()}))
        ds.update_trial(other, t)
    assert _decode_counts(read(other))[1:] == (4, 0)
    assert _decode_counts(read(small))[1:] == (8, 0)
    ds.close()


def test_sharded_store_splits_the_cap(tmp_path, monkeypatch):
    """Each shard of a ShardedSqliteDatastore holds its share of
    ``TERMINAL_CACHE_TRIALS``, so the store as a whole holds no more."""
    monkeypatch.setattr(datastore_lib, "TERMINAL_CACHE_TRIALS", 12)
    rng = random.Random(0)
    ds = ShardedSqliteDatastore(str(tmp_path / "shards"), n_shards=2)
    big, small = "owners/o/studies/big", "owners/o/studies/small"
    _completed_study(ds, big, 8, rng)   # over a shard's share of 6
    _completed_study(ds, small, 5, rng)

    def read(name):
        return lambda: ds.list_trials_multi(
            [name], states=[TrialState.COMPLETED, TrialState.INFEASIBLE])

    for _ in range(2):
        assert _decode_counts(read(big))[1:] == (8, 0)
    _decode_counts(read(small))
    assert _decode_counts(read(small))[1:] == (0, 5)
    assert sum(s._term_size for s in ds._shards) <= 12
    ds.close()


# ---------------------------------------------------------------------------
# shared objects
# ---------------------------------------------------------------------------


def test_policy_and_get_trial_leave_cached_trials_unchanged(tmp_path):
    """One GP-bandit ``suggest_batch`` reads the cached terminal trials and
    changes none of them; a Trial from ``get_trial`` is the caller's own."""
    rng = random.Random(0)
    ds = SQLiteDatastore(str(tmp_path / "v.db"))
    study = _study("owners/o/studies/gp", algorithm="GP_UCB")
    ds.create_study(study)
    for i in range(10):
        t = ds.create_trial(study.name, _trial(rng))
        if i < 8:
            t.complete(Measurement(metrics={"obj": -(t.parameters["x"].as_float
                                                     - 0.4) ** 2}))
            ds.update_trial(study.name, t)

    def read():
        return ds.list_trials_multi([study.name],
                                    states=[TrialState.COMPLETED])[study.name]

    cached = read()
    before = [t.to_proto() for t in cached]
    (result,) = InProcessPythia(ds).suggest_batch([(study, 2, "c")])
    suggestions, _ = result
    assert len(suggestions) == 2
    assert [t.to_proto() for t in cached] == before
    assert all(a is b for a, b in zip(read(), cached))

    mine = ds.get_trial(study.name, cached[0].id)
    mine.metadata.ns("caller")["k"] = "v"
    mine.parameters["x"] = 0.123
    mine.final_measurement = Measurement(metrics={"obj": 9.0})
    assert [t.to_proto() for t in read()] == before
    ds.close()
