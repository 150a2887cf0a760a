"""Study-sharded suggestion work queue + Pythia worker pool (scale-out tier).

The Figure-2 topology stops being one API server driving one Pythia dispatch
thread: suggest operations are enqueued on ``hash(study_name) % n_shards``
shards, and a pool of Pythia workers each lease one shard's whole backlog as a
coalesced batch, run it through the existing coalesced-dispatch path, and ack
on completion. The invariants:

* **Shard keying** — a study maps to exactly one shard (stable CRC32 of the
  study name, see ``operations.shard_of``), and a shard is leased by at most
  one worker at a time, so one study's policy state is never computed by two
  workers concurrently.
* **Lease / ack / requeue** — ``lease`` hands a worker every op currently
  queued on one free shard and stamps the lease with the shard's generation
  counter. ``ack`` retires the lease only if the generation still matches. A
  worker that dies mid-lease (killed, or its lease outlives
  ``lease_timeout``) has its in-flight ops requeued at the *front* of their
  shard; the generation bump makes the dead worker's late ack — and, via
  ``lease_valid`` guards in the finalize path, its late op completions — a
  no-op, so a re-run never races a zombie.
* **Idempotent re-run** — requeued ops that the dead worker *did* finish are
  filtered out by the runner's done-check before (and again under the study
  lock during) finalization, so a kill between "op completed" and "ack" never
  produces duplicate trials.

``PythiaWorkerPool`` runs the workers as daemon threads inside the API-server
process; ``stop_worker``/``restart_worker`` give the fault-injection harness
worker-granular kills (extending the PR-2 ``stop_pythia``/``restart_pythia``
process-granular harness).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from repro import tracing
from repro.service import chaos
from repro.service import operations as ops_lib
from repro.service._lockwitness import make_condition

log = logging.getLogger(__name__)


class Lease:
    """One worker's claim on one shard's batch of suggest ops."""

    __slots__ = ("shard_id", "generation", "worker_id", "ops", "deadline")

    def __init__(self, shard_id: int, generation: int, worker_id: int,
                 ops: List[dict], deadline: float):
        self.shard_id = shard_id
        self.generation = generation
        self.worker_id = worker_id
        self.ops = ops
        self.deadline = deadline

    def __repr__(self) -> str:  # debugging/fault-test output
        return (f"Lease(shard={self.shard_id}, gen={self.generation}, "
                f"worker={self.worker_id}, ops={len(self.ops)})")


class _Shard:
    __slots__ = ("queued", "lease", "generation")

    def __init__(self):
        self.queued: deque = deque()
        self.lease: Optional[Lease] = None
        self.generation = 0


class ShardedWorkQueue:
    """In-process sharded op queue with exclusive shard leases.

    All state transitions happen under one condition variable; ``lease``
    blocks until some shard has queued work and no active lease. Expired
    leases are reclaimed lazily on the next ``lease``/``enqueue`` scan — no
    background reaper thread.
    """

    def __init__(self, n_shards: int = 8, *, lease_timeout: float = 30.0):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.n_shards = n_shards
        self.lease_timeout = lease_timeout
        self._shards = [_Shard() for _ in range(n_shards)]
        self._cv = make_condition("ShardedWorkQueue._cv")
        self._closed = False
        # op name -> when it (re)entered the queue, for its
        # ``vizier.queue.pending`` interval (ends at the lease grant)
        self._queued_since: Dict[str, int] = {}

    # -- producers -----------------------------------------------------------
    def shard_of(self, study_name: str) -> int:
        return ops_lib.shard_of(study_name, self.n_shards)

    def enqueue(self, op: dict) -> int:
        """Queue a suggest op on its study's shard; returns the shard id."""
        sid = self.shard_of(op["study_name"])
        with self._cv:
            self._shards[sid].queued.append(op)
            self._queued_since[op["name"]] = time.perf_counter_ns()
            self._cv.notify_all()
        return sid

    # -- workers -------------------------------------------------------------
    def _reclaim_expired_locked(self, now: float) -> List[Tuple[str, int]]:
        """Requeue expired leases; returns (lease repr, op count) for each so
        the caller can log after releasing the CV (logging does I/O)."""
        reclaimed: List[Tuple[str, int]] = []
        for shard in self._shards:
            lease = shard.lease
            if lease is not None and now > lease.deadline:
                reclaimed.append((repr(lease), len(lease.ops)))
                self._requeue_locked(lease)
        return reclaimed

    def _requeue_locked(self, lease: Lease) -> None:
        shard = self._shards[lease.shard_id]
        if shard.lease is not lease:
            return  # already reclaimed / acked
        # front of the queue, original order: re-runs keep arrival fairness
        now = time.perf_counter_ns()
        for op in reversed(lease.ops):
            shard.queued.appendleft(ops_lib.note_requeued(op))
            self._queued_since[op["name"]] = now
        shard.lease = None
        shard.generation += 1  # invalidates the dead holder's lease
        self._cv.notify_all()

    def lease(self, worker_id: int, timeout: Optional[float] = None
              ) -> Optional[Lease]:
        """Claim one free shard's whole backlog; None on timeout/close.

        The wait is one ``vizier.lease.wait`` span, which counts the ops
        a granted lease brings."""
        with tracing.span("vizier.lease.wait") as wait:
            granted = self._lease(worker_id, timeout)
            if granted is not None:
                wait.add(ops=len(granted.ops))
        return granted

    def _lease(self, worker_id: int, timeout: Optional[float]
               ) -> Optional[Lease]:
        deadline = None if timeout is None else time.monotonic() + timeout
        granted: Optional[Lease] = None
        queued_since: List[Tuple[str, int]] = []
        while granted is None:
            # the wait loop re-acquires the CV each iteration so reclaim
            # warnings flush outside the critical section
            reclaimed: List[Tuple[str, int]] = []
            try:
                with self._cv:
                    if self._closed:
                        return None
                    now = time.monotonic()
                    reclaimed = self._reclaim_expired_locked(now)
                    for sid, shard in enumerate(self._shards):
                        if shard.queued and shard.lease is None:
                            ops = list(shard.queued)
                            shard.queued.clear()
                            shard.generation += 1
                            granted = Lease(sid, shard.generation, worker_id,
                                            ops, now + self.lease_timeout)
                            shard.lease = granted
                            now_ns = time.perf_counter_ns()
                            queued_since = [
                                (op["name"],
                                 self._queued_since.pop(op["name"], now_ns))
                                for op in ops]
                            break
                    if granted is None:
                        if deadline is not None:
                            remaining = deadline - time.monotonic()
                            if remaining <= 0:
                                return None
                            self._cv.wait(remaining)
                        else:
                            self._cv.wait()
            finally:
                # the with-block has exited (CV released) before this runs
                for desc, n_ops in reclaimed:
                    log.warning("lease %s expired; requeueing %d ops",
                                desc, n_ops)
        for name, since in queued_since:
            tracing.record("vizier.queue.pending", since, trace_id=name)
        # strictly outside the CV: an injected stall or early expiry on this
        # grant must never block the other shards' lease traffic
        chaos.inject("queue.lease", lease=granted)
        return granted

    def lease_valid(self, lease: Lease) -> bool:
        """True while the lease still owns its shard (generation match)."""
        with self._cv:
            shard = self._shards[lease.shard_id]
            return shard.lease is lease and shard.generation == lease.generation

    def ack(self, lease: Lease) -> bool:
        """Retire a completed lease. False (no-op) if it was reclaimed."""
        with self._cv:
            shard = self._shards[lease.shard_id]
            if shard.lease is not lease or shard.generation != lease.generation:
                return False  # stale: ops were requeued to another worker
            shard.lease = None
            self._cv.notify_all()
            return True

    def release(self, lease: Lease) -> bool:
        """Hand a lease back *without* acking (the batch runner failed).

        The ops requeue at the front exactly like a crash reclaim — a worker
        whose runner raised must not ack work it may not have finished, or a
        still-pending op would be retired on a live server and stay pending
        forever (a lost acked op). False if the lease was already reclaimed.
        """
        with self._cv:
            shard = self._shards[lease.shard_id]
            if shard.lease is not lease or shard.generation != lease.generation:
                return False
            self._requeue_locked(lease)
            return True

    def reclaim_worker(self, worker_id: int) -> int:
        """Requeue every in-flight op of a dead worker's active leases."""
        requeued = 0
        with self._cv:
            for shard in self._shards:
                lease = shard.lease
                if lease is not None and lease.worker_id == worker_id:
                    requeued += len(lease.ops)
                    self._requeue_locked(lease)
        return requeued

    # -- introspection -------------------------------------------------------
    def pending_count(self) -> int:
        with self._cv:
            return sum(len(s.queued) for s in self._shards) + sum(
                len(s.lease.ops) for s in self._shards if s.lease is not None)

    def active_leases(self) -> List[Lease]:
        with self._cv:
            return [s.lease for s in self._shards if s.lease is not None]

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


# RunBatch(ops, op_guard) runs a coalesced batch; op_guard(op) -> False means
# "your lease is gone, do not finalize this op" (see VizierService).
RunBatch = Callable[[List[dict], Callable[[dict], bool]], None]
AlreadyDone = Callable[[dict], bool]


class PythiaWorkerPool:
    """N worker threads pulling coalesced batches off a ShardedWorkQueue.

    ``stop_worker`` simulates a worker crash: the thread is flagged dead,
    joined briefly (it may be stuck mid-dispatch — a real crash would be),
    and its leases are reclaimed so surviving workers re-run the in-flight
    ops. The zombie thread's eventual finalize attempts are rejected by the
    lease-validity guard.
    """

    _POLL = 0.05  # lease-wait slice; bounds worker shutdown latency

    def __init__(self, queue: ShardedWorkQueue, run_batch: RunBatch,
                 already_done: AlreadyDone, *, n_workers: int = 2):
        if n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {n_workers}")
        self._queue = queue
        self._run_batch = run_batch
        self._already_done = already_done
        self.n_workers = n_workers
        self._threads: Dict[int, threading.Thread] = {}
        self._killed: Dict[int, threading.Event] = {}
        self._shutdown = threading.Event()

    def start(self) -> "PythiaWorkerPool":
        for wid in range(self.n_workers):
            self._spawn(wid)
        return self

    def _spawn(self, wid: int) -> None:
        self._killed[wid] = threading.Event()
        t = threading.Thread(target=self._loop, args=(wid,),
                             name=f"pythia-worker-{wid}", daemon=True)
        self._threads[wid] = t
        t.start()

    def _loop(self, wid: int) -> None:
        killed = self._killed[wid]
        while not (self._shutdown.is_set() or killed.is_set()):
            try:
                lease = self._queue.lease(wid, timeout=self._POLL)
            except Exception:  # noqa: BLE001 — injected lease fault: the
                log.exception("worker %d lease raised", wid)
                continue      # grant reclaims via its own timeout
            if lease is None:
                continue
            failed = False
            try:
                names = tuple(op["name"] for op in lease.ops)
                with tracing.span("vizier.worker.batch",
                                  trace_id=names) as batch:
                    # a mid-batch worker kill lands here: killed.set() via
                    # the seam's kill callback, checked before dispatch and
                    # by the op_guard below
                    chaos.inject("worker.batch", worker=wid, lease=lease,
                                 kill=killed.set)
                    # idempotent re-run: skip ops a dead predecessor finished
                    ops = [op for op in lease.ops
                           if not self._already_done(op)]
                    batch.add(ops=len(ops), studies=len(
                        {op["study_name"] for op in ops}))
                    if ops and not killed.is_set():
                        self._run_batch(
                            ops,
                            lambda op: (not killed.is_set()
                                        and self._queue.lease_valid(lease)),
                        )
                chaos.inject("queue.ack", lease=lease, kill=killed.set)
            except Exception:  # noqa: BLE001 — the runner fails ops itself
                log.exception("worker %d batch run raised", wid)
                failed = True
            if killed.is_set():
                return  # crashed before ack: reclaim/lease-expiry requeues
            if failed:
                # crash-equivalent: the runner may have died before failing
                # every op — hand the batch back instead of acking it away
                self._queue.release(lease)
                continue
            self._queue.ack(lease)

    # -- fault injection / lifecycle ----------------------------------------
    def alive_workers(self) -> List[int]:
        return sorted(w for w, t in self._threads.items() if t.is_alive())

    def worker_holding(self, study_name: str) -> Optional[int]:
        """Which worker's lease covers this study's shard right now."""
        sid = self._queue.shard_of(study_name)
        for lease in self._queue.active_leases():
            if lease.shard_id == sid:
                return lease.worker_id
        return None

    def stop_worker(self, worker_id: int, *, join_timeout: float = 1.0) -> int:
        """Kill one worker mid-whatever; returns how many ops were requeued."""
        killed = self._killed.get(worker_id)
        if killed is None:
            raise KeyError(f"no worker {worker_id}")
        killed.set()
        t = self._threads[worker_id]
        t.join(timeout=join_timeout)  # may still be stuck in a dispatch
        return self._queue.reclaim_worker(worker_id)

    def restart_worker(self, worker_id: int) -> None:
        old = self._threads.get(worker_id)
        if old is not None and old.is_alive() and not self._killed[worker_id].is_set():
            raise RuntimeError(f"worker {worker_id} is still alive")
        self._spawn(worker_id)

    def shutdown(self, *, join_timeout: float = 1.0) -> None:
        self._shutdown.set()
        self._queue.close()
        for t in self._threads.values():
            t.join(timeout=join_timeout)
