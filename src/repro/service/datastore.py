"""Persistent datastore (paper §3.1 "Persistent Datastore", §3.2 fault tolerance).

Two implementations behind one interface:

* InMemoryDatastore — dict-based, thread-safe; for tests/benchmarks.
* SQLiteDatastore — durable SQL store (WAL journal). Studies/trials/operations
  are stored as msgpack'd wire protos, so the schema is stable across code
  versions; secondary columns support the filtered queries PolicySupporter
  needs without deserializing everything (paper §6.2).

Server-side fault tolerance rests on this layer: `Operation`s are persisted
with enough information to restart suggestion computations after a crash.

Batched reads: ``list_trials_multi`` fetches the trials of N studies in one
call (one SQL query / one lock acquisition) so the batched suggestion path
(BatchSuggestTrials) can assemble feature matrices for a whole coalesced
request without N round-trips into the store. Secondary indexes cover the
(study_name, state) and (study_name, client_id) filters plus the pending-
operation scan used by crash recovery.

Incremental terminal reads: both backends keep the decoded trials of a
study that are terminal (COMPLETED, INFEASIBLE), so a suggest op on a
study of thousands of trials decodes only what changed since the last
read. ``InMemoryDatastore`` checks each cached trial against its stored
proto. ``SQLiteDatastore`` serves ``list_trials_multi`` for a state set
made only of terminal states from its cache: it marks a row dirty in
every write that touches it (under the connection lock), fetches and
decodes only the dirty rows on the next such read, and drops everything
when ``PRAGMA data_version`` shows a commit by another connection. Its
``vizier.datastore.decode`` span counts ``trials`` decoded and ``cached``
trials served without a decode. The cache holds at most
``TERMINAL_CACHE_TRIALS`` trials per backend, least recently read study
first; ``ShardedSqliteDatastore`` splits that among its shards, and a study
larger than its shard's share is read in full every time. Reads that hand
trials to a caller that may mutate them (``get_trial``, ``list_trials``,
ACTIVE reads) decode fresh, as does any read inside a transaction;
``list_trials_multi_raw`` returns protos.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sqlite3
import threading
from typing import Dict, List, Optional, Tuple

import msgpack

from repro import tracing
from repro.core.metadata import Metadata
from repro.service._lockwitness import make_rlock
from repro.core.study import Study, StudyState, Trial, TrialState


class KeyAlreadyExistsError(Exception):
    pass


class NotFoundError(Exception):
    pass


class DatastoreBusyError(Exception):
    """The storage backend is transiently contended (SQLite busy/locked).

    Carries ``code`` = UNAVAILABLE so the RPC dispatch surfaces a retryable
    status instead of INTERNAL — a handler must never leak a raw
    ``sqlite3.OperationalError: database is locked`` (error-discipline
    invariant); clients treat it like any other brownout and retry within
    their budget.
    """

    code = 14  # StatusCode.UNAVAILABLE (duck-typed; storage stays below rpc)


def _decode_trials(blobs_by_study: Dict[str, list]) -> Dict[str, List[Trial]]:
    """Stored trial blobs -> Trials, as one ``vizier.datastore.decode`` span."""
    with tracing.span("vizier.datastore.decode",
                      trials=sum(map(len, blobs_by_study.values()))):
        return {name: [Trial.from_proto(msgpack.unpackb(b, raw=False))
                       for b in blobs]
                for name, blobs in blobs_by_study.items()}


def _decode_raw(blobs_by_study: Dict[str, list]) -> Dict[str, list]:
    """Stored trial blobs -> wire protos (no Trial objects)."""
    with tracing.span("vizier.datastore.decode",
                      trials=sum(map(len, blobs_by_study.values()))):
        return {name: [msgpack.unpackb(b, raw=False) for b in blobs]
                for name, blobs in blobs_by_study.items()}


class Datastore:
    """Interface. All methods are thread-safe."""

    # studies
    def create_study(self, study: Study) -> str:
        raise NotImplementedError

    def get_study(self, study_name: str) -> Study:
        raise NotImplementedError

    def list_studies(self, owner_prefix: str = "") -> List[Study]:
        raise NotImplementedError

    def update_study(self, study: Study) -> None:
        raise NotImplementedError

    def delete_study(self, study_name: str) -> None:
        raise NotImplementedError

    # trials
    def create_trial(self, study_name: str, trial: Trial) -> Trial:
        """Assigns the next sequential id if trial.id == 0; stores; returns it."""
        raise NotImplementedError

    def get_trial(self, study_name: str, trial_id: int) -> Trial:
        raise NotImplementedError

    def list_trials(
        self,
        study_name: str,
        *,
        states: Optional[List[TrialState]] = None,
        client_id: Optional[str] = None,
        min_trial_id: Optional[int] = None,
    ) -> List[Trial]:
        raise NotImplementedError

    def update_trial(self, study_name: str, trial: Trial) -> None:
        raise NotImplementedError

    def delete_trial(self, study_name: str, trial_id: int) -> None:
        raise NotImplementedError

    def max_trial_id(self, study_name: str) -> int:
        raise NotImplementedError

    def list_trials_multi(
        self,
        study_names: List[str],
        *,
        states: Optional[List[TrialState]] = None,
    ) -> Dict[str, List[Trial]]:
        """Trials of several studies in one call (batched suggestion path).

        Returns {study_name: [trials sorted by id]}; every requested study is
        a key (possibly mapping to []). Raises NotFoundError naming the first
        missing study. Default implementation loops; backends override with a
        single query / single lock acquisition. Terminal trials may be the
        backend's cached objects, shared between calls: callers read them
        and never mutate them (a write goes get_trial -> update_trial).
        """
        return {name: self.list_trials(name, states=states) for name in study_names}

    def list_trials_multi_raw(
        self,
        study_names: List[str],
        *,
        states: Optional[List[TrialState]] = None,
    ) -> Dict[str, List[dict]]:
        """Like list_trials_multi but returns wire protos, not Trial objects.

        The GetTrialsMulti RPC is proto-in/proto-out: materializing a Trial
        per row on the server just to call to_proto() again doubles the
        serialization cost of the coalesced prefetch. Backends serve the
        stored proto dicts directly (trials are written by whole-proto
        replacement, so returned dicts are never mutated in place). Default
        implementation falls back through Trial objects.
        """
        return {
            name: [t.to_proto() for t in trials]
            for name, trials in self.list_trials_multi(
                study_names, states=states).items()
        }

    def study_transaction(self, study_name: str):
        """Context manager making every write inside it atomic and durable
        as one unit (the exactly-once-finalize write set: metadata delta +
        new trials + the done operation). A crash inside the block must
        leave either all of it or none of it; ``recover_pending_operations``
        relies on that to re-run interrupted ops cleanly. Default: no extra
        atomicity (single-write backends).
        """
        return contextlib.nullcontext()

    def close(self) -> None:
        pass

    # operations (long-running computations; paper §3.2)
    def put_operation(self, op: dict) -> None:
        raise NotImplementedError

    def get_operation(self, op_name: str) -> dict:
        raise NotImplementedError

    def list_operations(
        self, study_name: str, *, client_id: Optional[str] = None, only_pending: bool = False
    ) -> List[dict]:
        raise NotImplementedError

    # study-level metadata (Pythia state saving; paper §6.3)
    def update_study_metadata(self, study_name: str, metadata: Metadata) -> None:
        study = self.get_study(study_name)
        study.study_config.metadata.attach(metadata)
        self.update_study(study)

    def update_trial_metadata(self, study_name: str, trial_id: int, metadata: Metadata) -> None:
        trial = self.get_trial(study_name, trial_id)
        trial.metadata.attach(metadata)
        self.update_trial(study_name, trial)

    def apply_metadata_delta(self, study_name: str, delta) -> List[int]:
        """Applies a policy MetadataDelta (study + per-trial) in one go.

        This is how persisted algorithm state (e.g. the GP-bandit's
        ``repro.gp_bandit`` checkpoint) reaches the store. Per-trial updates
        naming a trial that no longer exists are skipped — a policy may
        reference ids deleted mid-operation — and the skipped ids are
        returned so RPC callers can surface them. Backends override to hold
        their lock across the whole read-modify-write so concurrent deltas
        cannot interleave and lose writes.
        """
        if delta.on_study._store:
            self.update_study_metadata(study_name, delta.on_study)
        skipped: List[int] = []
        for trial_id, md in delta.on_trials.items():
            try:
                self.update_trial_metadata(study_name, trial_id, md)
            except NotFoundError:
                skipped.append(trial_id)
        return skipped


# ---------------------------------------------------------------------------


# proto ``state`` values whose trials never change again once stored —
# safe to cache their materialized Trial objects across list_trials calls
_TERMINAL_STATE_VALUES = frozenset(
    s.value for s in TrialState if s.is_terminal)


class InMemoryDatastore(Datastore):
    def __init__(self):
        self._lock = make_rlock("InMemoryDatastore._lock")
        self._studies: Dict[str, dict] = {}
        self._trials: Dict[str, Dict[int, dict]] = {}
        self._ops: Dict[str, dict] = {}
        # Terminal-trial materialization cache: {study: {tid: (proto, Trial)}}.
        # list_trials deserializes every stored proto on every call, which
        # dominates suggestion latency once studies reach thousands of
        # completed trials (the Pythia supporter re-reads the full study per
        # operation). Terminal trials are immutable by whole-proto
        # replacement: update_trial swaps the stored dict, so an IDENTITY
        # check against the cached proto detects any write (including
        # metadata attach, which goes get_trial -> update_trial) and
        # invalidates the entry. Non-terminal trials are never cached — the
        # stalled-trial reassignment path mutates ACTIVE trials it listed.
        self._term_cache: Dict[str, Dict[int, tuple]] = {}

    def _materialize(self, study_name: str, tid: int, p: dict) -> Trial:
        """Trial for a stored proto, cached when the trial is terminal."""
        if p.get("state") not in _TERMINAL_STATE_VALUES:
            return Trial.from_proto(p)
        cache = self._term_cache.setdefault(study_name, {})
        hit = cache.get(tid)
        if hit is not None and hit[0] is p:
            return hit[1]
        trial = Trial.from_proto(p)
        cache[tid] = (p, trial)
        return trial

    # studies ----------------------------------------------------------------
    def create_study(self, study: Study) -> str:
        with self._lock:
            if study.name in self._studies:
                raise KeyAlreadyExistsError(study.name)
            self._studies[study.name] = study.to_proto()
            self._trials[study.name] = {}
            return study.name

    def get_study(self, study_name: str) -> Study:
        with self._lock:
            if study_name not in self._studies:
                raise NotFoundError(study_name)
            return Study.from_proto(self._studies[study_name])

    def list_studies(self, owner_prefix: str = "") -> List[Study]:
        with self._lock:
            return [
                Study.from_proto(p)
                for name, p in sorted(self._studies.items())
                if name.startswith(owner_prefix)
            ]

    def update_study(self, study: Study) -> None:
        with self._lock:
            if study.name not in self._studies:
                raise NotFoundError(study.name)
            self._studies[study.name] = study.to_proto()

    def delete_study(self, study_name: str) -> None:
        with self._lock:
            if study_name not in self._studies:
                raise NotFoundError(study_name)
            del self._studies[study_name]
            self._trials.pop(study_name, None)
            self._term_cache.pop(study_name, None)
            self._ops = {k: v for k, v in self._ops.items() if v.get("study_name") != study_name}

    # trials -------------------------------------------------------------------
    def create_trial(self, study_name: str, trial: Trial) -> Trial:
        with self._lock:
            if study_name not in self._studies:
                raise NotFoundError(study_name)
            bucket = self._trials[study_name]
            if trial.id == 0:
                trial.id = (max(bucket) + 1) if bucket else 1
            elif trial.id in bucket:
                raise KeyAlreadyExistsError(f"{study_name}/trials/{trial.id}")
            trial.study_name = study_name
            bucket[trial.id] = trial.to_proto()
            return trial

    def get_trial(self, study_name: str, trial_id: int) -> Trial:
        with self._lock:
            bucket = self._trials.get(study_name)
            if bucket is None or trial_id not in bucket:
                raise NotFoundError(f"{study_name}/trials/{trial_id}")
            return Trial.from_proto(bucket[trial_id])

    def list_trials(self, study_name, *, states=None, client_id=None, min_trial_id=None):
        with self._lock:
            if study_name not in self._trials:
                raise NotFoundError(study_name)
            out = []
            state_values = {s.value for s in states} if states else None
            for tid in sorted(self._trials[study_name]):
                p = self._trials[study_name][tid]
                if state_values and p.get("state") not in state_values:
                    continue
                if client_id is not None and p.get("client_id") != client_id:
                    continue
                if min_trial_id is not None and tid < min_trial_id:
                    continue
                out.append(self._materialize(study_name, tid, p))
            return out

    def update_trial(self, study_name: str, trial: Trial) -> None:
        with self._lock:
            bucket = self._trials.get(study_name)
            if bucket is None or trial.id not in bucket:
                raise NotFoundError(f"{study_name}/trials/{trial.id}")
            trial.study_name = study_name
            bucket[trial.id] = trial.to_proto()

    def delete_trial(self, study_name: str, trial_id: int) -> None:
        with self._lock:
            bucket = self._trials.get(study_name)
            if bucket is None or trial_id not in bucket:
                raise NotFoundError(f"{study_name}/trials/{trial_id}")
            del bucket[trial_id]
            self._term_cache.get(study_name, {}).pop(trial_id, None)

    def max_trial_id(self, study_name: str) -> int:
        with self._lock:
            bucket = self._trials.get(study_name)
            if bucket is None:
                raise NotFoundError(study_name)
            return max(bucket) if bucket else 0

    def list_trials_multi(self, study_names, *, states=None):
        # one lock acquisition for the whole batch: a consistent snapshot
        # across studies, which the coalesced Pythia dispatch relies on
        with self._lock:
            out: Dict[str, List[Trial]] = {}
            state_values = {s.value for s in states} if states else None
            for name in study_names:
                bucket = self._trials.get(name)
                if bucket is None:
                    raise NotFoundError(name)
                out[name] = [
                    self._materialize(name, tid, bucket[tid])
                    for tid in sorted(bucket)
                    if state_values is None or bucket[tid].get("state") in state_values
                ]
            return out

    def list_trials_multi_raw(self, study_names, *, states=None):
        with self._lock:
            out: Dict[str, List[dict]] = {}
            state_values = {s.value for s in states} if states else None
            for name in study_names:
                bucket = self._trials.get(name)
                if bucket is None:
                    raise NotFoundError(name)
                out[name] = [
                    bucket[tid]
                    for tid in sorted(bucket)
                    if state_values is None or bucket[tid].get("state") in state_values
                ]
            return out

    # metadata ----------------------------------------------------------------
    def update_study_metadata(self, study_name: str, metadata: Metadata) -> None:
        with self._lock:  # atomic read-modify-write (RLock: reentrant)
            super().update_study_metadata(study_name, metadata)

    def update_trial_metadata(self, study_name, trial_id, metadata) -> None:
        with self._lock:
            super().update_trial_metadata(study_name, trial_id, metadata)

    def apply_metadata_delta(self, study_name: str, delta) -> List[int]:
        with self._lock:
            return super().apply_metadata_delta(study_name, delta)

    # ops -------------------------------------------------------------------------
    def put_operation(self, op: dict) -> None:
        with self._lock:
            self._ops[op["name"]] = dict(op)

    def get_operation(self, op_name: str) -> dict:
        with self._lock:
            if op_name not in self._ops:
                raise NotFoundError(op_name)
            return dict(self._ops[op_name])

    def list_operations(self, study_name, *, client_id=None, only_pending=False):
        with self._lock:
            out = []
            for op in self._ops.values():
                if op.get("study_name") != study_name:
                    continue
                if client_id is not None and op.get("client_id") != client_id:
                    continue
                if only_pending and op.get("done"):
                    continue
                out.append(dict(op))
            return sorted(out, key=lambda o: o.get("create_time", 0))

    def study_transaction(self, study_name: str):
        # one backend lock ⇒ holding it makes the write set atomic w.r.t.
        # every reader; durability is moot for an in-memory store
        return self._lock


# ---------------------------------------------------------------------------


_SYNCHRONOUS_MODES = {"OFF", "NORMAL", "FULL", "EXTRA"}

# Decoded terminal trials one backend keeps over all its studies (a
# ShardedSqliteDatastore gives each shard an equal share). A decoded d = 20
# trial of one metric takes 4.3 KB (tracemalloc, CPython 3.10), so about
# 280 MB. A study with more terminal trials than its share is read in full
# on every read.
TERMINAL_CACHE_TRIALS = 1 << 16

# A study with more dirty rows than this is read in full, which keeps the
# dirty fetch's ``IN (...)`` under SQLite's limit on query parameters.
_MAX_DIRTY_FETCH = 512


class _TerminalTrials:
    """One study's decoded terminal trials by id, and the ids of the rows
    written since they were read. ``trials`` is None until the study's
    first full read is installed."""

    __slots__ = ("trials", "dirty")

    def __init__(self):
        self.trials: Optional[Dict[int, Trial]] = None
        self.dirty: set = set()


def _open_conn(path: str, busy_timeout_ms: int,
               synchronous: str) -> sqlite3.Connection:
    """Open a connection in manual-transaction mode.

    ``isolation_level=None`` disables sqlite3's implicit BEGIN so our
    explicit BEGIN IMMEDIATE / COMMIT below are the *only* transactions —
    the stdlib's autobegin interacts badly with reentrant write scopes
    (a nested ``with conn`` commits the outer transaction early).
    """
    if synchronous.upper() not in _SYNCHRONOUS_MODES:
        raise ValueError(f"bad synchronous mode {synchronous!r}")
    conn = sqlite3.connect(path, check_same_thread=False, isolation_level=None)
    conn.execute("PRAGMA journal_mode=WAL")
    # without a busy timeout a cross-process writer collision surfaces
    # instantly as "database is locked"; with it SQLite spins internally
    conn.execute(f"PRAGMA busy_timeout={int(busy_timeout_ms)}")
    conn.execute(f"PRAGMA synchronous={synchronous.upper()}")
    return conn


def _init_schema(conn: sqlite3.Connection) -> None:
    conn.execute(
        "CREATE TABLE IF NOT EXISTS studies ("
        " name TEXT PRIMARY KEY, proto BLOB NOT NULL)"
    )
    conn.execute(
        "CREATE TABLE IF NOT EXISTS trials ("
        " study_name TEXT NOT NULL, trial_id INTEGER NOT NULL,"
        " state TEXT NOT NULL, client_id TEXT, proto BLOB NOT NULL,"
        " PRIMARY KEY (study_name, trial_id))"
    )
    conn.execute(
        "CREATE TABLE IF NOT EXISTS operations ("
        " name TEXT PRIMARY KEY, study_name TEXT NOT NULL,"
        " client_id TEXT, done INTEGER NOT NULL, create_time REAL,"
        " proto BLOB NOT NULL)"
    )
    conn.execute(
        "CREATE INDEX IF NOT EXISTS trials_by_state"
        " ON trials (study_name, state)"
    )
    conn.execute(
        "CREATE INDEX IF NOT EXISTS trials_by_client"
        " ON trials (study_name, client_id)"
    )
    conn.execute(
        "CREATE INDEX IF NOT EXISTS ops_pending"
        " ON operations (study_name, done)"
    )


class SQLiteDatastore(Datastore):
    """Durable datastore; survives process crashes (server-side fault tolerance).

    All writes run inside explicit BEGIN IMMEDIATE transactions via
    ``_txn()`` (reentrant: nested scopes join the outer transaction, commit
    happens once at depth 0), so multi-row write sets — apply_metadata_delta,
    the finalize region under ``study_transaction`` — hit disk atomically:
    after a hard kill, recovery sees either the whole write set or none of
    it. Busy/locked contention surfaces as DatastoreBusyError (UNAVAILABLE),
    never a raw sqlite3.OperationalError.

    Terminal-trial cache (module doc): ``_term`` maps a study, least
    recently read first, to its ``_TerminalTrials``. Invariant, under
    ``_lock``: for every id not in ``dirty``, ``trials`` holds the row's
    committed content if the row is terminal and nothing if it is not.
    Every write of a ``trials`` row adds its id to ``dirty``, and an id
    leaves ``dirty`` only once its row is merged. Reads inside a
    transaction bypass the cache, so it holds committed rows only and a
    rollback needs nothing (its marks make the rows be read again). A
    commit by another connection (``PRAGMA data_version``) drops the whole
    cache. ``_cache_share`` is the number of backends that split
    ``TERMINAL_CACHE_TRIALS`` (``ShardedSqliteDatastore`` sets it).
    """

    def __init__(self, path: str = ":memory:", *,
                 busy_timeout_ms: int = 10_000, synchronous: str = "NORMAL"):
        self._path = path
        self._lock = make_rlock("SQLiteDatastore._lock")
        self._txn_depth = 0
        self._term: "collections.OrderedDict[str, _TerminalTrials]" = (
            collections.OrderedDict())
        self._term_size = 0  # trials held by the installed entries
        self._data_version: Optional[int] = None
        self._cache_share = 1
        if path != ":memory:":
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._conn = _open_conn(path, busy_timeout_ms, synchronous)
        with self._txn():
            _init_schema(self._conn)

    @contextlib.contextmanager
    def _txn(self):
        """Reentrant write scope: BEGIN IMMEDIATE at depth 0, COMMIT when
        the outermost scope exits cleanly, ROLLBACK if it raises."""
        with self._lock:
            if self._txn_depth == 0:
                try:
                    self._conn.execute("BEGIN IMMEDIATE")
                except sqlite3.OperationalError as e:
                    raise DatastoreBusyError(str(e)) from e
            self._txn_depth += 1
            try:
                yield self._conn
            except BaseException:
                self._txn_depth -= 1
                if self._txn_depth == 0:
                    try:
                        self._conn.execute("ROLLBACK")
                    except sqlite3.Error:
                        pass  # connection torn down mid-failure
                raise
            self._txn_depth -= 1
            if self._txn_depth == 0:
                try:
                    self._conn.execute("COMMIT")
                except sqlite3.OperationalError as e:
                    try:
                        self._conn.execute("ROLLBACK")
                    except sqlite3.Error:
                        pass
                    raise DatastoreBusyError(str(e)) from e

    def study_transaction(self, study_name: str):
        return self._txn()

    @contextlib.contextmanager
    def _reading(self):
        """The connection lock for one read: a ``vizier.datastore.query``
        span over the execute and fetch, whose ``vizier.datastore.lock.wait``
        child is the wait for the lock."""
        with tracing.span("vizier.datastore.query"), \
                tracing.span("vizier.datastore.lock.wait") as wait, \
                self._lock:
            wait.end()
            yield

    # terminal-trial cache (all under self._lock) -------------------------------
    def _mark(self, study_name: str, trial_id: int) -> None:
        """Every write of a ``trials`` row calls this in its transaction."""
        entry = self._term.get(study_name)
        if entry is not None:
            entry.dirty.add(trial_id)

    def _forget(self, study_name: str) -> None:
        entry = self._term.pop(study_name, None)
        if entry is not None and entry.trials is not None:
            self._term_size -= len(entry.trials)

    def _clear_cache(self) -> None:
        self._term.clear()
        self._term_size = 0

    def _cache_cap(self) -> int:
        return TERMINAL_CACHE_TRIALS // self._cache_share

    def _evict(self) -> None:
        """Drops least recently read studies until the cache fits; the
        study just read is last and alone fits, so it stays."""
        while self._term_size > self._cache_cap():
            _, entry = self._term.popitem(last=False)
            if entry.trials is not None:
                self._term_size -= len(entry.trials)

    # studies --------------------------------------------------------------------
    def create_study(self, study: Study) -> str:
        blob = msgpack.packb(study.to_proto(), use_bin_type=True)
        with self._txn():
            try:
                self._conn.execute(
                    "INSERT INTO studies (name, proto) VALUES (?, ?)", (study.name, blob)
                )
            except sqlite3.IntegrityError as e:
                raise KeyAlreadyExistsError(study.name) from e
        return study.name

    def get_study(self, study_name: str) -> Study:
        with self._reading():
            row = self._conn.execute(
                "SELECT proto FROM studies WHERE name = ?", (study_name,)
            ).fetchone()
        if row is None:
            raise NotFoundError(study_name)
        with tracing.span("vizier.datastore.decode"):
            return Study.from_proto(msgpack.unpackb(row[0], raw=False))

    def list_studies(self, owner_prefix: str = "") -> List[Study]:
        with self._reading():
            rows = self._conn.execute(
                "SELECT proto FROM studies WHERE name LIKE ? ORDER BY name",
                (owner_prefix + "%",),
            ).fetchall()
        return [Study.from_proto(msgpack.unpackb(r[0], raw=False)) for r in rows]

    def update_study(self, study: Study) -> None:
        blob = msgpack.packb(study.to_proto(), use_bin_type=True)
        with self._txn():
            cur = self._conn.execute(
                "UPDATE studies SET proto = ? WHERE name = ?", (blob, study.name)
            )
            if cur.rowcount == 0:
                raise NotFoundError(study.name)

    def delete_study(self, study_name: str) -> None:
        with self._txn():
            cur = self._conn.execute("DELETE FROM studies WHERE name = ?", (study_name,))
            if cur.rowcount == 0:
                raise NotFoundError(study_name)
            self._conn.execute("DELETE FROM trials WHERE study_name = ?", (study_name,))
            self._conn.execute("DELETE FROM operations WHERE study_name = ?", (study_name,))
            self._forget(study_name)

    # trials -------------------------------------------------------------------------
    def create_trial(self, study_name: str, trial: Trial) -> Trial:
        with self._txn():
            exists = self._conn.execute(
                "SELECT 1 FROM studies WHERE name = ?", (study_name,)
            ).fetchone()
            if exists is None:
                raise NotFoundError(study_name)
            if trial.id == 0:
                row = self._conn.execute(
                    "SELECT COALESCE(MAX(trial_id), 0) FROM trials WHERE study_name = ?",
                    (study_name,),
                ).fetchone()
                trial.id = int(row[0]) + 1
            trial.study_name = study_name
            blob = msgpack.packb(trial.to_proto(), use_bin_type=True)
            try:
                self._conn.execute(
                    "INSERT INTO trials (study_name, trial_id, state, client_id, proto)"
                    " VALUES (?, ?, ?, ?, ?)",
                    (study_name, trial.id, trial.state.value, trial.client_id, blob),
                )
            except sqlite3.IntegrityError as e:
                raise KeyAlreadyExistsError(f"{study_name}/trials/{trial.id}") from e
            self._mark(study_name, trial.id)
        return trial

    def get_trial(self, study_name: str, trial_id: int) -> Trial:
        with self._reading():
            row = self._conn.execute(
                "SELECT proto FROM trials WHERE study_name = ? AND trial_id = ?",
                (study_name, trial_id),
            ).fetchone()
        if row is None:
            raise NotFoundError(f"{study_name}/trials/{trial_id}")
        return _decode_trials({study_name: [row[0]]})[study_name][0]

    def list_trials(self, study_name, *, states=None, client_id=None, min_trial_id=None):
        query = "SELECT proto FROM trials WHERE study_name = ?"
        args: list = [study_name]
        if states:
            marks = ",".join("?" * len(states))
            query += f" AND state IN ({marks})"
            args += [s.value for s in states]
        if client_id is not None:
            query += " AND client_id = ?"
            args.append(client_id)
        if min_trial_id is not None:
            query += " AND trial_id >= ?"
            args.append(min_trial_id)
        query += " ORDER BY trial_id"
        with self._reading():
            exists = self._conn.execute(
                "SELECT 1 FROM studies WHERE name = ?", (study_name,)
            ).fetchone()
            if exists is None:
                raise NotFoundError(study_name)
            rows = self._conn.execute(query, args).fetchall()
        return _decode_trials({study_name: [r[0] for r in rows]})[study_name]

    def update_trial(self, study_name: str, trial: Trial) -> None:
        trial.study_name = study_name
        blob = msgpack.packb(trial.to_proto(), use_bin_type=True)
        with self._txn():
            cur = self._conn.execute(
                "UPDATE trials SET proto = ?, state = ?, client_id = ?"
                " WHERE study_name = ? AND trial_id = ?",
                (blob, trial.state.value, trial.client_id, study_name, trial.id),
            )
            if cur.rowcount == 0:
                raise NotFoundError(f"{study_name}/trials/{trial.id}")
            self._mark(study_name, trial.id)

    def delete_trial(self, study_name: str, trial_id: int) -> None:
        with self._txn():
            cur = self._conn.execute(
                "DELETE FROM trials WHERE study_name = ? AND trial_id = ?",
                (study_name, trial_id),
            )
            if cur.rowcount == 0:
                raise NotFoundError(f"{study_name}/trials/{trial_id}")
            self._mark(study_name, trial_id)

    def max_trial_id(self, study_name: str) -> int:
        with self._reading():
            exists = self._conn.execute(
                "SELECT 1 FROM studies WHERE name = ?", (study_name,)
            ).fetchone()
            if exists is None:
                raise NotFoundError(study_name)
            row = self._conn.execute(
                "SELECT COALESCE(MAX(trial_id), 0) FROM trials WHERE study_name = ?",
                (study_name,),
            ).fetchone()
        return int(row[0])

    def _missing(self, study_names: List[str]) -> List[str]:
        """The requested studies that do not exist (under the lock)."""
        marks = ",".join("?" * len(study_names))
        known = {r[0] for r in self._conn.execute(
            f"SELECT name FROM studies WHERE name IN ({marks})", study_names)}
        return [name for name in study_names if name not in known]

    def _fetch_trial_blobs_or_missing(
            self, study_names, states) -> "Tuple[Dict[str, list], List[str]]":
        """Single-query fetch returning (blobs by study, missing studies).

        Missing studies are *returned*, not raised, so the sharded backend
        can merge per-shard results and still report the first missing study
        in the caller's request order.
        """
        study_names = list(study_names)
        if not study_names:
            return {}, []
        marks = ",".join("?" * len(study_names))
        query = f"SELECT study_name, proto FROM trials WHERE study_name IN ({marks})"
        args: list = list(study_names)
        if states:
            smarks = ",".join("?" * len(states))
            query += f" AND state IN ({smarks})"
            args += [s.value for s in states]
        query += " ORDER BY study_name, trial_id"
        with self._reading():
            missing = self._missing(study_names)
            rows = (self._conn.execute(query, args).fetchall()
                    if not missing else [])
        out: Dict[str, list] = {name: [] for name in study_names}
        for study_name, blob in rows:
            out[study_name].append(blob)
        return out, missing

    def _trials_multi_or_missing(
            self, study_names, states) -> "Tuple[Dict[str, List[Trial]], List[str]]":
        """``list_trials_multi`` returning (trials by study, missing studies).

        A state set made only of terminal states is served from the cache
        (class doc): under the lock, one query per cached study fetches its
        dirty rows, which are decoded and merged there; a study not cached
        gets a placeholder entry, so that writes during its decode mark it,
        and all its terminal rows, decoded outside the lock and installed
        only if the placeholder is still there. Every other state set, and
        every read inside a transaction (which holds the lock, so only this
        thread can be in it), reads and decodes every matching row.
        """
        study_names = list(study_names)
        if (not states or not all(s.is_terminal for s in states)
                or self._txn_depth):
            blobs, missing = self._fetch_trial_blobs_or_missing(
                study_names, states)
            return ({} if missing else _decode_trials(blobs)), missing
        if not study_names:
            return {}, []
        wanted = frozenset(states)
        terminal = tuple(_TERMINAL_STATE_VALUES)
        out: Dict[str, List[Trial]] = {}
        cold: Dict[str, tuple] = {}
        with self._reading():
            missing = self._missing(study_names)
            if missing:
                return {}, missing
            version = self._conn.execute("PRAGMA data_version").fetchone()[0]
            if version != self._data_version:  # another connection committed
                self._clear_cache()
                self._data_version = version
            warm: Dict[str, tuple] = {}
            for name in dict.fromkeys(study_names):  # each study once
                entry = self._term.get(name)
                if (entry is None or entry.trials is None
                        or len(entry.dirty) > _MAX_DIRTY_FETCH):
                    self._forget(name)
                    entry = self._term[name] = _TerminalTrials()
                    cold[name] = (entry, self._conn.execute(
                        "SELECT trial_id, proto FROM trials WHERE study_name = ?"
                        f" AND state IN ({','.join('?' * len(terminal))})"
                        " ORDER BY trial_id", (name, *terminal)).fetchall())
                    continue
                self._term.move_to_end(name)
                ids = sorted(entry.dirty)
                rows = self._conn.execute(
                    "SELECT trial_id, state, proto FROM trials WHERE study_name = ?"
                    f" AND trial_id IN ({','.join('?' * len(ids))})",
                    (name, *ids)).fetchall() if ids else []
                warm[name] = (entry, ids, rows)
            if warm:
                with tracing.span("vizier.datastore.decode") as decode:
                    decoded = cached = 0
                    for name, (entry, ids, rows) in warm.items():
                        out[name], n_decoded, n_cached = self._merge_dirty(
                            name, entry, ids, rows, wanted)
                        decoded += n_decoded
                        cached += n_cached
                    decode.add(trials=decoded, cached=cached)
                self._evict()
        if cold:
            with tracing.span("vizier.datastore.decode",
                              trials=sum(len(r) for _, r in cold.values())):
                fresh = {name: {tid: Trial.from_proto(
                                    msgpack.unpackb(blob, raw=False))
                                for tid, blob in rows}
                         for name, (_, rows) in cold.items()}
            with self._lock:
                for name, (entry, _) in cold.items():
                    trials = fresh[name]
                    out[name] = [t for t in trials.values() if t.state in wanted]
                    if self._term.get(name) is not entry:
                        continue  # dropped or replaced while decoding
                    if len(trials) > self._cache_cap():
                        self._forget(name)
                        continue
                    entry.trials = trials
                    self._term_size += len(trials)
                    self._term.move_to_end(name)
                self._evict()
        return {name: out[name] for name in study_names}, []

    def _merge_dirty(self, name, entry, ids, rows, wanted):
        """Applies one cached study's dirty rows (under the lock). Returns
        its trials in ``wanted`` by id, the rows decoded, and the trials
        served without a decode. Decodes before it changes anything, so a
        raise leaves the entry and its dirty ids as they were."""
        fresh = {tid: Trial.from_proto(msgpack.unpackb(blob, raw=False))
                 for tid, state, blob in rows
                 if state in _TERMINAL_STATE_VALUES}
        trials = entry.trials
        before = len(trials)
        for tid in ids:
            trials.pop(tid, None)
        trials.update(fresh)
        entry.dirty.difference_update(ids)
        self._term_size += len(trials) - before
        out = [trials[t] for t in sorted(trials) if trials[t].state in wanted]
        if len(trials) > self._cache_cap():
            self._forget(name)
        return out, len(fresh), len(out) - sum(
            t.state in wanted for t in fresh.values())

    def list_trials_multi(self, study_names, *, states=None):
        out, missing = self._trials_multi_or_missing(study_names, states)
        if missing:
            raise NotFoundError(missing[0])
        return out

    def list_trials_multi_raw(self, study_names, *, states=None):
        blobs, missing = self._fetch_trial_blobs_or_missing(study_names, states)
        if missing:
            raise NotFoundError(missing[0])
        return _decode_raw(blobs)

    # metadata ----------------------------------------------------------------
    def update_study_metadata(self, study_name: str, metadata: Metadata) -> None:
        with self._txn():  # atomic RMW, one durable commit
            super().update_study_metadata(study_name, metadata)

    def update_trial_metadata(self, study_name, trial_id, metadata) -> None:
        with self._txn():
            super().update_trial_metadata(study_name, trial_id, metadata)

    def apply_metadata_delta(self, study_name: str, delta) -> List[int]:
        # the whole delta (study checkpoint + N trial rows) commits as one
        # transaction: a crash mid-delta must not leave half a GP state
        with self._txn():
            return super().apply_metadata_delta(study_name, delta)

    # ops ---------------------------------------------------------------------------
    def put_operation(self, op: dict) -> None:
        blob = msgpack.packb(op, use_bin_type=True)
        with self._txn():
            self._conn.execute(
                "INSERT INTO operations (name, study_name, client_id, done, create_time, proto)"
                " VALUES (?, ?, ?, ?, ?, ?)"
                " ON CONFLICT(name) DO UPDATE SET done = excluded.done, proto = excluded.proto",
                (
                    op["name"],
                    op.get("study_name", ""),
                    op.get("client_id"),
                    1 if op.get("done") else 0,
                    op.get("create_time", 0.0),
                    blob,
                ),
            )

    def get_operation(self, op_name: str) -> dict:
        with self._reading():
            row = self._conn.execute(
                "SELECT proto FROM operations WHERE name = ?", (op_name,)
            ).fetchone()
        if row is None:
            raise NotFoundError(op_name)
        return msgpack.unpackb(row[0], raw=False)

    def list_operations(self, study_name, *, client_id=None, only_pending=False):
        query = "SELECT proto FROM operations WHERE study_name = ?"
        args: list = [study_name]
        if client_id is not None:
            query += " AND client_id = ?"
            args.append(client_id)
        if only_pending:
            query += " AND done = 0"
        query += " ORDER BY create_time"
        with self._reading():
            rows = self._conn.execute(query, args).fetchall()
        return [msgpack.unpackb(r[0], raw=False) for r in rows]

    def close(self) -> None:
        with self._lock:
            self._clear_cache()
            self._conn.close()


# ---------------------------------------------------------------------------


class ShardedSqliteDatastore(Datastore):
    """Per-shard SQLite files keyed by ``operations.shard_of(study_name)``.

    The single-file backend serializes every write on one connection lock —
    under N Pythia workers the storage tier is a single point of contention
    (ROADMAP open item 1). Here each shard owns its own file, connection,
    and lock, so writes to different studies commit (and fsync) in parallel;
    a study's trials, operations, and metadata always live in the *same*
    shard file, so the ``study_transaction`` write set stays atomic within
    one SQLite transaction.

    Layout: ``<path>/layout.json`` ({"n_shards": N}, written once, adopted
    on reopen — the shard count is a property of the data on disk, not the
    process config) plus ``<path>/shard-00.sqlite3`` … ``shard-NN.sqlite3``,
    each with the full schema. The shard index of study S is
    ``shard_of(S, n_shards)`` (stable crc32, same function the work queue
    uses), and an operation name ``<study>/operations/<uuid>`` routes to its
    study's shard.
    """

    def __init__(self, path: str, *, n_shards: int = 8,
                 busy_timeout_ms: int = 10_000, synchronous: str = "NORMAL"):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self._path = os.path.abspath(path)
        os.makedirs(self._path, exist_ok=True)
        layout_path = os.path.join(self._path, "layout.json")
        if os.path.exists(layout_path):
            with open(layout_path, "r", encoding="utf-8") as f:
                persisted = int(json.load(f)["n_shards"])
            n_shards = persisted  # disk wins: rekeying would orphan studies
        else:
            tmp = layout_path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"n_shards": n_shards}, f)
            os.replace(tmp, layout_path)
        self.n_shards = n_shards
        self._shards = [
            SQLiteDatastore(
                os.path.join(self._path, f"shard-{i:02d}.sqlite3"),
                busy_timeout_ms=busy_timeout_ms, synchronous=synchronous)
            for i in range(n_shards)
        ]
        for shard in self._shards:  # one cache budget for the whole store
            shard._cache_share = n_shards

    def _shard(self, study_name: str) -> SQLiteDatastore:
        from repro.service.operations import shard_of
        return self._shards[shard_of(study_name, self.n_shards)]

    def _shard_of_op(self, op_name: str) -> Optional[SQLiteDatastore]:
        study_name, sep, _ = op_name.partition("/operations/")
        return self._shard(study_name) if sep else None

    # studies --------------------------------------------------------------------
    def create_study(self, study: Study) -> str:
        return self._shard(study.name).create_study(study)

    def get_study(self, study_name: str) -> Study:
        return self._shard(study_name).get_study(study_name)

    def list_studies(self, owner_prefix: str = "") -> List[Study]:
        # shards visited one at a time (never two shard locks at once)
        out: List[Study] = []
        for shard in self._shards:
            out.extend(shard.list_studies(owner_prefix))
        out.sort(key=lambda s: s.name)
        return out

    def update_study(self, study: Study) -> None:
        self._shard(study.name).update_study(study)

    def delete_study(self, study_name: str) -> None:
        self._shard(study_name).delete_study(study_name)

    # trials -------------------------------------------------------------------------
    def create_trial(self, study_name: str, trial: Trial) -> Trial:
        return self._shard(study_name).create_trial(study_name, trial)

    def get_trial(self, study_name: str, trial_id: int) -> Trial:
        return self._shard(study_name).get_trial(study_name, trial_id)

    def list_trials(self, study_name, *, states=None, client_id=None, min_trial_id=None):
        return self._shard(study_name).list_trials(
            study_name, states=states, client_id=client_id,
            min_trial_id=min_trial_id)

    def update_trial(self, study_name: str, trial: Trial) -> None:
        self._shard(study_name).update_trial(study_name, trial)

    def delete_trial(self, study_name: str, trial_id: int) -> None:
        self._shard(study_name).delete_trial(study_name, trial_id)

    def max_trial_id(self, study_name: str) -> int:
        return self._shard(study_name).max_trial_id(study_name)

    def _per_shard(self, study_names, fetch) -> Dict[str, list]:
        """Group the request by shard, call ``fetch(shard, names)`` ->
        (values by study, missing studies) once per shard, and keep the
        single-backend contract: NotFoundError names the first missing
        study in the *request* order even when it lives on a later shard."""
        study_names = list(study_names)
        by_shard: Dict[int, List[str]] = {}
        from repro.service.operations import shard_of
        for name in study_names:
            by_shard.setdefault(shard_of(name, self.n_shards), []).append(name)
        merged: Dict[str, list] = {}
        missing: List[str] = []
        for idx, names in by_shard.items():
            out, miss = fetch(self._shards[idx], names)
            merged.update(out)
            missing.extend(miss)
        if missing:
            missing_set = set(missing)
            first = next(n for n in study_names if n in missing_set)
            raise NotFoundError(first)
        return {name: merged[name] for name in study_names}

    def list_trials_multi(self, study_names, *, states=None):
        return self._per_shard(
            study_names,
            lambda shard, names: shard._trials_multi_or_missing(names, states))

    def list_trials_multi_raw(self, study_names, *, states=None):
        return _decode_raw(self._per_shard(
            study_names,
            lambda shard, names: shard._fetch_trial_blobs_or_missing(
                names, states)))

    # metadata ----------------------------------------------------------------
    def update_study_metadata(self, study_name: str, metadata: Metadata) -> None:
        self._shard(study_name).update_study_metadata(study_name, metadata)

    def update_trial_metadata(self, study_name, trial_id, metadata) -> None:
        self._shard(study_name).update_trial_metadata(
            study_name, trial_id, metadata)

    def apply_metadata_delta(self, study_name: str, delta) -> List[int]:
        return self._shard(study_name).apply_metadata_delta(study_name, delta)

    def study_transaction(self, study_name: str):
        return self._shard(study_name).study_transaction(study_name)

    # ops ---------------------------------------------------------------------------
    def put_operation(self, op: dict) -> None:
        study_name = op.get("study_name") or op["name"].partition(
            "/operations/")[0]
        self._shard(study_name).put_operation(op)

    def get_operation(self, op_name: str) -> dict:
        shard = self._shard_of_op(op_name)
        if shard is not None:
            return shard.get_operation(op_name)
        for shard in self._shards:  # malformed name: fall back to a scan
            try:
                return shard.get_operation(op_name)
            except NotFoundError:
                continue
        raise NotFoundError(op_name)

    def list_operations(self, study_name, *, client_id=None, only_pending=False):
        return self._shard(study_name).list_operations(
            study_name, client_id=client_id, only_pending=only_pending)

    def close(self) -> None:
        for shard in self._shards:
            shard.close()
