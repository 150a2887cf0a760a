"""The OSS Vizier API servicer (paper §3.2, Figure 2).

Implements the RPC surface with Vertex-Vizier method names:

  CreateStudy / GetStudy / ListStudies / DeleteStudy / SetStudyState
  SuggestTrials -> Operation           (Pythia runs in a server thread)
  BatchSuggestTrials -> [Operation]    (N studies' suggestions, one dispatch)
  GetOperation                         (client polling loop)
  CompleteTrial / AddTrialMeasurement / GetTrial / ListTrials / DeleteTrial
  BatchCompleteTrials                  (N completions, one round trip)
  CheckTrialEarlyStoppingState -> Operation
  StopTrial / ListOptimalTrials / UpdateMetadata / ListAlgorithms

Batched suggestion path: BatchSuggestTrials coalesces the suggestion
operations of many (study, client) pairs arriving in one request into a
single Pythia dispatch — one thread-pool job, one multi-study datastore
prefetch (Datastore.list_trials_multi), one policy construction per study —
instead of one job + per-study query fan-out per call. Fast paths (own
ACTIVE trials, stalled-trial reassignment, idempotent pending ops) are
evaluated per sub-request exactly as in SuggestTrials, so batched and
sequential calls observe identical protocol semantics.

Key semantics reproduced from the paper:
  * client_id trial binding — a SuggestTrials call first returns the caller's
    own ACTIVE trials, so a crashed-and-restarted worker resumes its trial
    (client-side fault tolerance, §5).
  * stalled-trial reassignment — ACTIVE trials bound to a client that has not
    heartbeated within ``reassign_stalled_after`` seconds are re-bound to the
    requesting client (§5 "reassign Trials to other clients to prevent
    stalling").
  * operation persistence + recover_pending_operations() — suggestion work
    interrupted by a server crash restarts on boot (§3.2).
  * Pythia may run in-process or as a separate service (Figure 2) — see
    PythiaConnector implementations.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from repro import tracing
from repro.core.metadata import Metadata, MetadataDelta, Namespace
from repro.core.pareto import pareto_frontier_indices
from repro.core.study import (
    Measurement,
    Study,
    StudyState,
    Trial,
    TrialState,
)
from repro.core.study_config import StudyConfig
from repro.pythia.policy import StudyDescriptor, SuggestRequest, EarlyStopRequest
from repro.pythia.registry import make_policy, registered_algorithms
from repro.pythia.supporter import DatastorePolicySupporter, PrefetchedPolicySupporter
from repro.service import chaos
from repro.service import operations as ops_lib
from repro.service._lockwitness import make_lock
from repro.service.datastore import Datastore, KeyAlreadyExistsError, NotFoundError
from repro.service.rpc import Servicer, StatusCode, VizierRpcError

log = logging.getLogger(__name__)

HEARTBEAT_NS = "system.heartbeat"


class PythiaConnector:
    """How the API server reaches the algorithm (same process or remote)."""

    def suggest(self, study: Study, count: int, client_id: str):
        raise NotImplementedError

    def suggest_batch(self, items: "List[tuple]"):
        """items: [(study, count, client_id)] -> per-item (suggestions, delta)
        or the Exception that item raised (per-item fault isolation).

        Default loops over suggest(); InProcessPythia overrides with a
        shared multi-study prefetch so one coalesced dispatch issues O(1)
        datastore queries instead of O(N).
        """
        out = []
        for study, count, client_id in items:
            try:
                out.append(self.suggest(study, count, client_id))
            except Exception as e:  # noqa: BLE001 — isolate per study
                out.append(e)
        return out

    def early_stop(self, study: Study, trial_ids: List[int]):
        raise NotImplementedError


class InProcessPythia(PythiaConnector):
    """Pythia policy in the API-server process (paper: 'can be the same binary')."""

    def __init__(self, datastore: Datastore):
        self._ds = datastore

    def _descriptor(self, study: Study) -> StudyDescriptor:
        return StudyDescriptor(
            config=study.study_config,
            guid=study.name,
            max_trial_id=self._ds.max_trial_id(study.name),
        )

    def suggest(self, study: Study, count: int, client_id: str):
        supporter = DatastorePolicySupporter(self._ds, study.name)
        policy = make_policy(study.study_config.algorithm, supporter, study.study_config)
        request = SuggestRequest(study_descriptor=self._descriptor(study), count=count)
        decision = policy.suggest(request)
        return decision.suggestions, decision.metadata

    def _prefetch_snapshot(self, study_names: List[str]) -> dict:
        """Two multi-study queries (completed + active). A study deleted
        mid-flight must not poison the whole prefetch: fall back to
        per-study reads and let the missing study's own item fail."""
        try:
            completed = self._ds.list_trials_multi(
                study_names, states=[TrialState.COMPLETED])
            active = self._ds.list_trials_multi(
                study_names, states=[TrialState.ACTIVE])
        except NotFoundError:
            completed, active = {}, {}
            for name in study_names:
                try:
                    completed[name] = self._ds.list_trials(
                        name, states=[TrialState.COMPLETED])
                    active[name] = self._ds.list_trials(
                        name, states=[TrialState.ACTIVE])
                except NotFoundError:
                    pass  # absent from the snapshot; its item raises alone
        return {
            name: {
                TrialState.COMPLETED.value: completed[name],
                TrialState.ACTIVE.value: active[name],
            }
            for name in study_names
            if name in completed and name in active
        }

    def suggest_batch(self, items: "List[tuple]"):
        study_names = list({study.name for study, _, _ in items})
        # transfer learning: fold every batched study's prior studies into
        # the same prefetch so the stacked-GP fit reads them from memory (a
        # deleted prior just stays absent; the policy skips it)
        prior_names = []
        for study, _, _ in items:
            for pn in getattr(study.study_config, "prior_study_names", ()):
                if pn not in study_names and pn not in prior_names:
                    prior_names.append(pn)
        # one multi-study query per state the policies read (completed for
        # the regressor fit, active for pending-trial fantasies)
        snapshot = self._prefetch_snapshot(study_names + prior_names)
        out = []
        for study, count, client_id in items:
            try:
                supporter = PrefetchedPolicySupporter(
                    DatastorePolicySupporter(self._ds, study.name), snapshot
                )
                policy = make_policy(
                    study.study_config.algorithm, supporter, study.study_config
                )
                decision = policy.suggest(
                    SuggestRequest(study_descriptor=self._descriptor(study), count=count)
                )
                out.append((decision.suggestions, decision.metadata))
            except Exception as e:  # noqa: BLE001 — isolate per study
                out.append(e)
        return out

    def early_stop(self, study: Study, trial_ids: List[int]):
        supporter = DatastorePolicySupporter(self._ds, study.name)
        policy = make_policy(study.study_config.algorithm, supporter, study.study_config)
        request = EarlyStopRequest(
            study_descriptor=self._descriptor(study), trial_ids=trial_ids
        )
        return policy.early_stop(request).decisions


class RemotePythia(PythiaConnector):
    """Pythia as a separate service reached over RPC (paper Figure 2).

    suggest_batch dispatches the whole coalesced work-list in ONE
    PythiaBatchSuggest frame: the Pythia service loads every study's
    config/trials once (a single GetTrialsMulti(include_studies) frame back
    to the API server) and returns per-item results with isolated errors —
    the same contract as InProcessPythia.suggest_batch, so the coalesced
    operation runner needs no per-backend branching.
    Against an older Pythia binary without the batch method (UNIMPLEMENTED)
    it falls back to the per-study PythiaSuggest loop.
    """

    def __init__(self, rpc_client, *, coalesce: bool = True):
        self._rpc = rpc_client
        self._coalesce = coalesce

    @staticmethod
    def _parse_suggestions(result: dict):
        from repro.core.study import TrialSuggestion

        suggestions = []
        for p in result["suggestions"]:
            t = Trial.from_proto(p)
            suggestions.append(TrialSuggestion(parameters=t.parameters, metadata=t.metadata))
        return suggestions, MetadataDelta.from_proto(result.get("metadata_delta"))

    def suggest(self, study: Study, count: int, client_id: str):
        result = self._rpc.call(
            "PythiaSuggest",
            {"study_name": study.name, "count": count, "client_id": client_id},
            timeout=600.0,
        )
        return self._parse_suggestions(result)

    def suggest_batch(self, items: "List[tuple]"):
        if not items:
            return []
        if not self._coalesce:
            return super().suggest_batch(items)
        requests = [
            {"study_name": study.name, "count": int(count), "client_id": client_id}
            for study, count, client_id in items
        ]
        try:
            result = self._rpc.call(
                "PythiaBatchSuggest", {"requests": requests}, timeout=600.0
            )
        except VizierRpcError as e:
            if e.code != StatusCode.UNIMPLEMENTED:
                raise
            return super().suggest_batch(items)  # pre-batch Pythia binary
        out = []
        for r in result["results"]:
            err = r.get("error")
            if err:
                out.append(VizierRpcError(
                    err.get("code", StatusCode.INTERNAL),
                    err.get("message", "unknown error"),
                ))
            else:
                out.append(self._parse_suggestions(r))
        return out

    def early_stop(self, study: Study, trial_ids: List[int]):
        from repro.pythia.policy import EarlyStopDecision

        result = self._rpc.call(
            "PythiaEarlyStop", {"study_name": study.name, "trial_ids": trial_ids},
            timeout=600.0,
        )
        return [
            EarlyStopDecision(d["trial_id"], d["should_stop"], d.get("reason", ""))
            for d in result["decisions"]
        ]


class VizierService(Servicer):
    #: server-side cap on one WaitOperation park; clients chunk longer waits
    MAX_WAIT_S = 30.0

    def __init__(
        self,
        datastore: Datastore,
        pythia: Optional[PythiaConnector] = None,
        *,
        reassign_stalled_after: Optional[float] = None,
        max_workers: int = 16,
        n_pythia_workers: int = 0,
        n_shards: int = 8,
        lease_timeout: float = 30.0,
    ):
        """``n_pythia_workers`` > 0 switches suggestion execution from the
        direct thread-pool submit to the scale-out tier: ops enqueue on a
        ``n_shards``-way study-sharded work queue and a pool of Pythia
        workers lease per-shard coalesced batches (see ``work_queue``). The
        thread pool remains for early-stopping ops either way."""
        super().__init__()
        self._ds = datastore
        self._pythia = pythia or InProcessPythia(datastore)
        self._reassign_after = reassign_stalled_after
        self._pool = ThreadPoolExecutor(max_workers=max_workers,
                                        thread_name_prefix="pythia")
        self._study_locks: Dict[str, threading.Lock] = {}
        self._locks_guard = make_lock("VizierService._locks_guard")
        # WaitOperation long-poll: op name -> [Event, waiter refcount]
        self._op_waiters: Dict[str, list] = {}
        self._op_waiters_guard = make_lock("VizierService._op_waiters_guard")
        self._queue = None
        self.worker_pool = None
        if n_pythia_workers > 0:
            from repro.service.work_queue import (
                PythiaWorkerPool,
                ShardedWorkQueue,
            )

            self._queue = ShardedWorkQueue(n_shards,
                                           lease_timeout=lease_timeout)
            self.worker_pool = PythiaWorkerPool(
                self._queue,
                self._run_suggest_ops_coalesced,
                self._op_already_done,
                n_workers=n_pythia_workers,
            ).start()
        for method in (
            "CreateStudy", "GetStudy", "ListStudies", "DeleteStudy", "SetStudyState",
            "SuggestTrials", "BatchSuggestTrials", "GetOperation", "WaitOperation",
            "CompleteTrial", "BatchCompleteTrials", "AddTrialMeasurement",
            "GetTrial", "ListTrials", "GetTrialsMulti", "DeleteTrial", "CreateTrial",
            "CheckTrialEarlyStoppingState", "StopTrial", "ListOptimalTrials",
            "UpdateMetadata", "ListAlgorithms", "Ping",
        ):
            self.expose(method, getattr(self, method))

    # -- helpers ---------------------------------------------------------------
    def _study_lock(self, study_name: str) -> threading.Lock:
        with self._locks_guard:
            return self._study_locks.setdefault(
                study_name, make_lock("VizierService._study_lock"))

    def _put_op(self, op: dict) -> None:
        """Single write path for operations: persists, then wakes any
        WaitOperation long-pollers once the op reaches a terminal state."""
        self._ds.put_operation(op)
        if op.get("done"):
            with self._op_waiters_guard:
                entry = self._op_waiters.pop(op["name"], None)
            if entry is not None:
                entry[0].set()

    def _op_already_done(self, op: dict) -> bool:
        """Requeue idempotency: a dead worker may have finished this op."""
        try:
            return bool(self._ds.get_operation(op["name"]).get("done"))
        except NotFoundError:
            return True  # study (and its ops) deleted mid-flight

    def _dispatch_suggest_op(self, op: dict) -> None:
        """Route a runnable suggest op to the worker-pool queue (scale-out)
        or the legacy direct thread-pool dispatch."""
        if self._queue is not None:
            self._queue.enqueue(op)
        else:
            self._pool.submit(self._run_suggest_op, op)

    def _dispatch_suggest_ops(self, ops: List[dict]) -> None:
        if self._queue is not None:
            for op in ops:
                self._queue.enqueue(op)
        else:
            self._pool.submit(self._run_suggest_ops_coalesced, ops)

    def _get_study_or_rpc_error(self, name: str) -> Study:
        try:
            return self._ds.get_study(name)
        except NotFoundError as e:
            raise VizierRpcError(StatusCode.NOT_FOUND, f"study {name!r}") from e

    @staticmethod
    def _parse_trial_name(name: str):
        if "/trials/" not in name:
            raise VizierRpcError(StatusCode.INVALID_ARGUMENT, f"bad trial name {name!r}")
        study_name, trial_id = name.rsplit("/trials/", 1)
        return study_name, int(trial_id)

    def _touch_heartbeat(self, trial: Trial) -> None:
        trial.metadata.abs_ns(Namespace(HEARTBEAT_NS))["t"] = repr(time.time())

    def _heartbeat_of(self, trial: Trial) -> float:
        raw = trial.metadata.abs_ns(Namespace(HEARTBEAT_NS)).get("t")
        if raw is None:
            return trial.creation_time
        try:
            return float(raw if isinstance(raw, str) else raw.decode())
        except ValueError:
            return trial.creation_time

    # -- studies ------------------------------------------------------------------
    def CreateStudy(self, params: dict) -> dict:
        owner = params.get("owner", "default")
        display_name = params.get("display_name") or f"study-{int(time.time()*1e3)}"
        try:
            config = StudyConfig.from_proto(params["study_spec"])
        except (ValueError, KeyError, TypeError) as e:
            # malformed spec (e.g. duplicate metric ids): permanent client
            # error, not a retryable INTERNAL
            raise VizierRpcError(
                StatusCode.INVALID_ARGUMENT,
                f"invalid study_spec: {type(e).__name__}: {e}") from e
        name = f"owners/{owner}/studies/{display_name}"
        study = Study(name=name, display_name=display_name, study_config=config)
        try:
            self._ds.create_study(study)
        except KeyAlreadyExistsError:
            # load-or-create semantics live in the client; Create returns the
            # existing study (idempotent for identical display names).
            study = self._ds.get_study(name)
        return {"study": study.to_proto()}

    def GetStudy(self, params: dict) -> dict:
        return {"study": self._get_study_or_rpc_error(params["name"]).to_proto()}

    def ListStudies(self, params: dict) -> dict:
        prefix = params.get("parent", "")
        return {"studies": [s.to_proto() for s in self._ds.list_studies(prefix)]}

    def DeleteStudy(self, params: dict) -> dict:
        name = params["name"]
        try:
            self._ds.delete_study(name)
        except NotFoundError as e:
            raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e
        # evict the per-study lock: without this the lock map grows forever
        # under study churn (create/delete workloads leaked one Lock per
        # study for the life of the server)
        with self._locks_guard:
            self._study_locks.pop(name, None)
        return {}

    def SetStudyState(self, params: dict) -> dict:
        # read-modify-write under the study lock: racing a concurrent
        # _apply_delta_locked / UpdateMetadata would resurrect the stale
        # study snapshot and silently drop their writes
        with self._study_lock(params["name"]):
            study = self._get_study_or_rpc_error(params["name"])
            study.state = StudyState(params["state"])
            self._ds.update_study(study)
        return {"study": study.to_proto()}

    # -- suggestion flow -------------------------------------------------------------
    def _prepare_suggest_op(self, study_name: str, client_id: str, count: int):
        """Shared SuggestTrials protocol. Returns (op, needs_computation).

        Fast paths 1-4 return an op that is already done (or already pending
        elsewhere); only path 5 needs a Pythia dispatch. Caller must hold no
        locks; this takes the study lock itself. The work is one
        ``vizier.suggest.prepare`` span, and the op's name becomes the trace
        id of the spans open on this thread (the RPC dispatch).
        """
        with tracing.span("vizier.suggest.prepare"):
            op, needs_run = self._choose_suggest_op(study_name, client_id, count)
            tracing.bind(op["name"])
        return op, needs_run

    def _choose_suggest_op(self, study_name: str, client_id: str, count: int):
        study = self._get_study_or_rpc_error(study_name)

        with tracing.span("vizier.lock.wait") as wait, \
                self._study_lock(study_name):
            wait.end()
            # 1. study no longer active -> empty, done (client loop terminates)
            if study.state != StudyState.ACTIVE:
                op = ops_lib.new_suggest_operation(study_name, client_id, count)
                op = ops_lib.complete_operation(op, {"trials": []})
                self._put_op(op)
                return op, False

            # 2. client already owns ACTIVE trials -> return them immediately
            #    (client-side fault tolerance, paper §5)
            mine = self._ds.list_trials(
                study_name, states=[TrialState.ACTIVE], client_id=client_id
            )
            if mine:
                op = ops_lib.new_suggest_operation(study_name, client_id, count)
                op = ops_lib.complete_operation(
                    op, {"trials": [t.to_proto() for t in mine[:count]]}
                )
                self._put_op(op)
                return op, False

            # 3. reassign stalled trials from dead clients (paper §5)
            if self._reassign_after is not None:
                now = time.time()
                stalled = [
                    t
                    for t in self._ds.list_trials(study_name, states=[TrialState.ACTIVE])
                    if now - self._heartbeat_of(t) > self._reassign_after
                ]
                if stalled:
                    grabbed = []
                    for t in stalled[:count]:
                        t.client_id = client_id
                        self._touch_heartbeat(t)
                        self._ds.update_trial(study_name, t)
                        grabbed.append(t)
                    op = ops_lib.new_suggest_operation(study_name, client_id, count)
                    op = ops_lib.complete_operation(
                        op, {"trials": [t.to_proto() for t in grabbed]}
                    )
                    self._put_op(op)
                    return op, False

            # 4. an identical pending op may already exist (idempotent retry)
            pending = self._ds.list_operations(
                study_name, client_id=client_id, only_pending=True
            )
            for op in pending:
                if op.get("type") == "suggest":
                    return op, False

            # 5. schedule fresh Pythia computation
            op = ops_lib.new_suggest_operation(study_name, client_id, count)
            self._put_op(op)
            return op, True

    def SuggestTrials(self, params: dict) -> dict:
        study_name = params["parent"]
        client_id = params.get("client_id") or "default_client"
        count = int(params.get("suggestion_count", 1))
        op, needs_run = self._prepare_suggest_op(study_name, client_id, count)
        if needs_run:
            self._dispatch_suggest_op(op)
        return {"operation": op}

    def BatchSuggestTrials(self, params: dict) -> dict:
        """N sub-requests -> N operations, at most ONE Pythia dispatch job.

        params: {"requests": [{"parent", "suggestion_count", "client_id"}...]}
        Sub-requests that hit a fast path (own ACTIVE trials, reassignment,
        idempotent retry) complete inline exactly as SuggestTrials would; the
        remainder are coalesced — grouped by study, one policy invocation per
        study with the summed count — into a single pool job. Per-sub-request
        failures (e.g. unknown study) surface as error entries, not a failed
        batch.
        """
        requests = params.get("requests") or []
        operations: List[Optional[dict]] = []
        errors: List[Optional[dict]] = []
        to_run: List[dict] = []
        for r in requests:
            try:
                study_name = r["parent"]
                client_id = r.get("client_id") or "default_client"
                count = int(r.get("suggestion_count", 1))
                op, needs_run = self._prepare_suggest_op(study_name, client_id, count)
            except VizierRpcError as e:
                operations.append(None)
                errors.append({"code": e.code, "message": e.message})
                continue
            except (KeyError, TypeError, ValueError) as e:
                operations.append(None)
                errors.append({
                    "code": StatusCode.INVALID_ARGUMENT,
                    "message": f"malformed sub-request: {type(e).__name__}: {e}",
                })
                continue
            operations.append(op)
            errors.append(None)
            if needs_run:
                to_run.append(op)
        if to_run:
            self._dispatch_suggest_ops(to_run)
        return {"operations": operations, "errors": errors}

    def _apply_delta_locked(self, study_name: str, delta) -> None:
        """Apply policy metadata (algorithm state; paper §6.3). Lock held."""
        if delta is not None and not delta.empty():
            self._ds.apply_metadata_delta(study_name, delta)

    def _create_trials_locked(self, study_name: str, client_id: str,
                              suggestions) -> List[Trial]:
        """Materialize suggestions as ACTIVE trials bound to client. Lock held."""
        trials = []
        for sug in suggestions:
            trial = Trial(
                parameters=sug.parameters,
                metadata=sug.metadata,
                state=TrialState.ACTIVE,
                client_id=client_id,
            )
            self._touch_heartbeat(trial)
            trial = self._ds.create_trial(study_name, trial)
            trials.append(trial)
        return trials

    def _fail_op(self, op: dict, e: Exception) -> None:
        self._put_op(
            ops_lib.fail_operation_from_exception(op, e,
                                                  default_code=StatusCode.INTERNAL)
        )

    def _run_suggest_op(self, op: dict) -> None:
        study_name = op["study_name"]
        client_id = op["client_id"]
        try:
            study = self._ds.get_study(study_name)
            suggestions, delta = self._pythia.suggest(
                study, op["suggestion_count"], client_id
            )
            with tracing.span("vizier.finalize", trace_id=op["name"]), \
                    tracing.span("vizier.lock.wait") as wait, \
                    self._study_lock(study_name):
                wait.end()
                # one durable unit: delta + trials + the done op commit
                # together, so a crash mid-finalize rolls back to a cleanly
                # re-runnable pending op (never trials without their op)
                with self._ds.study_transaction(study_name):
                    self._apply_delta_locked(study_name, delta)
                    trials = self._create_trials_locked(study_name, client_id, suggestions)
                    done = ops_lib.complete_operation(
                        op, {"trials": [t.to_proto() for t in trials]}
                    )
                    self._put_op(done)
        except Exception as e:  # noqa: BLE001 — op must terminate
            log.exception("suggest op %s failed", op["name"])
            self._fail_op(op, e)

    def _run_suggest_ops_coalesced(self, ops: List[dict], op_guard=None) -> None:
        """One job for a whole coalesced dispatch (pool job or worker lease).

        Groups ops by study, asks Pythia for each study's summed count in one
        policy invocation, then splits the suggestion batch across the ops in
        arrival order (each trial bound to its requester's client_id). A
        failed study fails only its own ops.

        ``op_guard`` (worker-pool path): called per op before any state is
        written; returning False means this runner's lease was revoked — the
        op has been requeued to another worker, so a zombie holder must
        neither create trials nor terminate the op. Paired with the
        done-recheck under the study lock, a requeued op is finalized exactly
        once even if the presumed-dead worker is still running.
        """
        by_study: Dict[str, List[dict]] = {}
        for op in ops:
            by_study.setdefault(op["study_name"], []).append(op)

        def fail_group(group, e):
            for op in group:
                if op_guard is not None and not op_guard(op):
                    continue
                self._fail_op(op, e)

        items = []
        for study_name, group in by_study.items():
            try:
                study = self._ds.get_study(study_name)
            except Exception as e:  # noqa: BLE001 — study may be deleted
                fail_group(group, e)
                continue
            total = sum(int(op["suggestion_count"]) for op in group)
            items.append((study, total, group[0]["client_id"]))

        try:
            results = self._pythia.suggest_batch(items)
        except Exception as e:  # noqa: BLE001 — whole dispatch failed
            log.exception("batch suggest dispatch failed")
            for study, _, _ in items:
                fail_group(by_study[study.name], e)
            return

        for (study, _, _), result in zip(items, results):
            group = by_study[study.name]
            if isinstance(result, Exception):
                log.error("batch suggest for %s failed: %s", study.name, result)
                fail_group(group, result)
                continue
            suggestions, delta = result
            shortfalls: List[tuple] = []
            try:
                # injected finalize faults fire before the study lock so a
                # stall here delays, never deadlocks, the finalize path
                chaos.inject("service.finalize", study=study.name)
                with tracing.span("vizier.finalize",
                                  trace_id=tuple(op["name"] for op in group)), \
                        tracing.span("vizier.lock.wait") as wait, \
                        self._study_lock(study.name):
                    wait.end()
                    if op_guard is not None:
                        # zombie-lease finalize races are settled under the
                        # study lock: drop ops whose lease is gone or that a
                        # successor already finalized
                        group = [op for op in group
                                 if op_guard(op) and not self._op_already_done(op)]
                        if not group:
                            continue
                    # one durable unit per study group: delta + every op's
                    # trials + done markers commit together (see
                    # Datastore.study_transaction)
                    with self._ds.study_transaction(study.name):
                        self._apply_delta_locked(study.name, delta)
                        cursor = 0
                        for op in group:
                            want = int(op["suggestion_count"])
                            take = suggestions[cursor:cursor + want]
                            cursor += len(take)
                            if want and not take:
                                # the policy under-delivered and this op got
                                # nothing: an empty *successful* op would make
                                # the client's suggestion loop terminate, so
                                # fail it (client may retry) instead
                                self._fail_op(op, RuntimeError(
                                    f"policy returned {len(suggestions)} suggestions "
                                    f"for a coalesced request; none left for this op"))
                                continue
                            if len(take) < want:
                                # log outside the study lock (logging does I/O)
                                shortfalls.append((op["name"], len(take), want))
                            trials = self._create_trials_locked(
                                study.name, op["client_id"], take
                            )
                            done = ops_lib.complete_operation(
                                op, {"trials": [t.to_proto() for t in trials]}
                            )
                            self._put_op(done)
            except Exception as e:  # noqa: BLE001 — ops must terminate
                log.exception("batch suggest finalize for %s failed", study.name)
                for op in group:
                    try:
                        if self._ds.get_operation(op["name"]).get("done"):
                            continue
                    except NotFoundError:
                        pass
                    if op_guard is not None and not op_guard(op):
                        continue
                    self._fail_op(op, e)
            for op_name, got, want in shortfalls:
                log.warning("coalesced op %s got %d/%d suggestions",
                            op_name, got, want)

    def GetOperation(self, params: dict) -> dict:
        try:
            return {"operation": self._ds.get_operation(params["name"])}
        except NotFoundError as e:
            raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e

    def WaitOperation(self, params: dict) -> dict:
        """Long-poll GetOperation: parks the request on a per-op event until
        the op completes or ``timeout_ms`` lapses (capped at MAX_WAIT_S per
        call; clients chunk longer waits), then returns the current op state.
        Completion latency stops being quantized by the client poll/backoff
        ladder — the response leaves the instant the op finishes.
        """
        name = params["name"]
        tracing.bind(name)
        timeout = min(float(params.get("timeout_ms", 0)) / 1000.0,
                      self.MAX_WAIT_S)
        try:
            op = self._ds.get_operation(name)
        except NotFoundError as e:
            raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e
        if op.get("done") or timeout <= 0:
            return {"operation": op}
        with self._op_waiters_guard:
            entry = self._op_waiters.setdefault(name, [threading.Event(), 0])
            entry[1] += 1
            event = entry[0]
        try:
            with tracing.span("vizier.op.wait"):
                event.wait(timeout)
        finally:
            with self._op_waiters_guard:
                cur = self._op_waiters.get(name)
                if cur is not None and cur[0] is event:
                    cur[1] -= 1
                    if cur[1] <= 0:  # last waiter out evicts the entry
                        del self._op_waiters[name]
        try:
            return {"operation": self._ds.get_operation(name)}
        except NotFoundError as e:  # op's study deleted while parked
            raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e

    def recover_pending_operations(self) -> int:
        """Re-launches computations for not-done ops (crash recovery, §3.2).

        With the worker pool enabled, recovered suggest ops re-enter the
        sharded queue like fresh ones — same-study ops land on the same
        shard and coalesce into one lease."""
        count = 0
        for study in self._ds.list_studies():
            for op in self._ds.list_operations(study.name, only_pending=True):
                if op.get("type") == "suggest":
                    self._dispatch_suggest_op(op)
                elif op.get("type") == "early_stopping":
                    self._pool.submit(self._run_early_stop_op, op)
                count += 1
        return count

    # -- trial lifecycle -----------------------------------------------------------
    def CreateTrial(self, params: dict) -> dict:
        """Registers a user-provided trial (e.g. known baselines / transfer)."""
        study_name = params["parent"]
        self._get_study_or_rpc_error(study_name)
        trial = Trial.from_proto(params["trial"])
        trial.id = 0  # service assigns ids
        trial = self._ds.create_trial(study_name, trial)
        return {"trial": trial.to_proto()}

    def GetTrial(self, params: dict) -> dict:
        study_name, trial_id = self._parse_trial_name(params["name"])
        try:
            return {"trial": self._ds.get_trial(study_name, trial_id).to_proto()}
        except NotFoundError as e:
            raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e

    def ListTrials(self, params: dict) -> dict:
        study_name = params["parent"]
        states = [TrialState(s) for s in params.get("states", [])] or None
        try:
            trials = self._ds.list_trials(
                study_name,
                states=states,
                client_id=params.get("client_id"),
                min_trial_id=params.get("min_trial_id"),
            )
        except NotFoundError as e:
            raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e
        return {"trials": [t.to_proto() for t in trials]}

    def GetTrialsMulti(self, params: dict) -> dict:
        """Many studies' trials in ONE frame (coalesced Pythia prefetch).

        params: {"parents": [study names], "states": [state values]?,
                 "allow_missing": bool?, "include_studies": bool?,
                 "include_priors": bool?}. Strict
        by default (any unknown study is NOT_FOUND, matching ListTrials);
        with allow_missing the unknown names are reported in "missing"
        instead so one deleted study cannot poison a whole batch's prefetch.
        include_studies adds a "studies" map so the coalesced Pythia
        dispatch gets configs + trials for N studies in ONE frame.
        include_priors (requires include_studies) additionally expands each
        requested study's ``prior_study_names`` ONE level deep: the prior
        studies' configs + trials join the same response maps (deleted
        priors land in "missing", never an error), so a transfer-learning
        suggest costs zero extra frames.
        """
        parents = list(params.get("parents") or [])
        states = [TrialState(s) for s in params.get("states", [])] or None
        missing: List[str] = []
        try:
            # raw protos end to end: no Trial materialization server-side
            by_study = self._ds.list_trials_multi_raw(parents, states=states)
        except NotFoundError as e:
            if not params.get("allow_missing"):
                raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e
            by_study = {}
            for name in parents:
                try:
                    by_study[name] = [
                        t.to_proto()
                        for t in self._ds.list_trials(name, states=states)
                    ]
                except NotFoundError:
                    missing.append(name)
        result: dict = {"trials_by_study": by_study, "missing": missing}
        if params.get("include_studies"):
            studies = {}
            for name in list(by_study):
                try:
                    studies[name] = self._ds.get_study(name).to_proto()
                except NotFoundError:  # deleted between the two reads
                    del by_study[name]
                    missing.append(name)
            if params.get("include_priors"):
                # one-level transfer expansion (priors' own priors are NOT
                # chased): a deleted prior is reported, never a failure
                prior_names: List[str] = []
                for sproto in studies.values():
                    spec = sproto.get("study_spec") or {}
                    for pn in spec.get("prior_study_names", ()):
                        if pn not in by_study and pn not in prior_names \
                                and pn not in missing:
                            prior_names.append(pn)
                for pn in prior_names:
                    try:
                        study_proto = self._ds.get_study(pn).to_proto()
                        trials = self._ds.list_trials_multi_raw(
                            [pn], states=states)[pn]
                    except NotFoundError:
                        missing.append(pn)
                        continue
                    studies[pn] = study_proto
                    by_study[pn] = trials
            result["studies"] = studies
        return result

    def AddTrialMeasurement(self, params: dict) -> dict:
        """Intermediate measurement — also acts as the client heartbeat."""
        study_name, trial_id = self._parse_trial_name(params["trial_name"])
        measurement = Measurement.from_proto(params["measurement"])
        with self._study_lock(study_name):
            trial = self._ds.get_trial(study_name, trial_id)
            if trial.state.is_terminal:
                raise VizierRpcError(
                    StatusCode.FAILED_PRECONDITION, f"trial {trial_id} already terminal"
                )
            trial.add_measurement(measurement)
            self._touch_heartbeat(trial)
            self._ds.update_trial(study_name, trial)
        return {"trial": trial.to_proto()}

    def CompleteTrial(self, params: dict) -> dict:
        study_name, trial_id = self._parse_trial_name(params["name"])
        with tracing.span("vizier.complete"), \
                tracing.span("vizier.lock.wait") as wait, \
                self._study_lock(study_name):
            wait.end()
            trial = self._complete_trial_locked(study_name, trial_id, params)
        return {"trial": trial.to_proto()}

    def _complete_trial_locked(self, study_name: str, trial_id: int,
                               params: dict) -> Trial:
        trial = self._ds.get_trial(study_name, trial_id)
        if trial.state.is_terminal:
            raise VizierRpcError(
                StatusCode.FAILED_PRECONDITION, f"trial {trial_id} already terminal"
            )
        if params.get("trial_infeasible"):
            trial.complete(
                infeasibility_reason=params.get("infeasible_reason", "infeasible")
            )
        else:
            fm = Measurement.from_proto(params.get("final_measurement"))
            if fm is None:
                # fall back to the last intermediate measurement
                if not trial.measurements:
                    raise VizierRpcError(
                        StatusCode.INVALID_ARGUMENT,
                        "no final_measurement and no intermediate measurements",
                    )
                fm = trial.measurements[-1]
            trial.complete(fm)
        self._ds.update_trial(study_name, trial)
        return trial

    def BatchCompleteTrials(self, params: dict) -> dict:
        """N CompleteTrial sub-requests in one round trip.

        params: {"requests": [CompleteTrial params...]}. Returns parallel
        "trials"/"errors" lists — a failed completion (unknown trial, already
        terminal) yields an error entry without failing its siblings.
        """
        trials: List[Optional[dict]] = []
        errors: List[Optional[dict]] = []
        with tracing.span("vizier.complete"):
            for r in params.get("requests") or []:
                try:
                    study_name, trial_id = self._parse_trial_name(r["name"])
                    with tracing.span("vizier.lock.wait") as wait, \
                            self._study_lock(study_name):
                        wait.end()
                        trial = self._complete_trial_locked(study_name, trial_id, r)
                    trials.append(trial.to_proto())
                    errors.append(None)
                except VizierRpcError as e:
                    trials.append(None)
                    errors.append({"code": e.code, "message": e.message})
                except NotFoundError as e:
                    trials.append(None)
                    errors.append({"code": StatusCode.NOT_FOUND, "message": str(e)})
                except (KeyError, TypeError, ValueError) as e:
                    trials.append(None)
                    errors.append({
                        "code": StatusCode.INVALID_ARGUMENT,
                        "message": f"malformed sub-request: {type(e).__name__}: {e}",
                    })
        return {"trials": trials, "errors": errors}

    def DeleteTrial(self, params: dict) -> dict:
        study_name, trial_id = self._parse_trial_name(params["name"])
        try:
            self._ds.delete_trial(study_name, trial_id)
        except NotFoundError as e:
            raise VizierRpcError(StatusCode.NOT_FOUND, str(e)) from e
        return {}

    def StopTrial(self, params: dict) -> dict:
        study_name, trial_id = self._parse_trial_name(params["name"])
        with self._study_lock(study_name):
            trial = self._ds.get_trial(study_name, trial_id)
            if not trial.state.is_terminal:
                trial.state = TrialState.STOPPING
                self._ds.update_trial(study_name, trial)
        return {"trial": trial.to_proto()}

    # -- early stopping ----------------------------------------------------------------
    def CheckTrialEarlyStoppingState(self, params: dict) -> dict:
        study_name, trial_id = self._parse_trial_name(params["trial_name"])
        self._get_study_or_rpc_error(study_name)
        op = ops_lib.new_early_stopping_operation(study_name, trial_id)
        self._put_op(op)
        self._pool.submit(self._run_early_stop_op, op)
        return {"operation": op}

    def _run_early_stop_op(self, op: dict) -> None:
        try:
            study = self._ds.get_study(op["study_name"])
            decisions = self._pythia.early_stop(study, [op["trial_id"]])
            should_stop = any(d.should_stop for d in decisions)
            if should_stop:
                with self._study_lock(op["study_name"]):
                    trial = self._ds.get_trial(op["study_name"], op["trial_id"])
                    if not trial.state.is_terminal:
                        trial.state = TrialState.STOPPING
                        self._ds.update_trial(op["study_name"], trial)
            self._put_op(
                ops_lib.complete_operation(op, {"should_stop": bool(should_stop)})
            )
        except Exception as e:  # noqa: BLE001
            log.exception("early-stop op %s failed", op["name"])
            # _fail_op maps the carried code (e.g. PolicyConstructionError ->
            # INVALID_ARGUMENT); hard-coding INTERNAL here made permanent
            # policy-construction failures look retryable
            self._fail_op(op, e)

    # -- optimal trials / metadata ---------------------------------------------------
    def ListOptimalTrials(self, params: dict) -> dict:
        study_name = params["parent"]
        study = self._get_study_or_rpc_error(study_name)
        config: StudyConfig = study.study_config
        completed = self._ds.list_trials(study_name, states=[TrialState.COMPLETED])
        ys, keep = [], []
        for t in completed:
            obj = config.objective_values(t)
            if obj is not None:
                ys.append(obj)
                keep.append(t)
        if not ys:
            return {"optimal_trials": []}
        idx = pareto_frontier_indices(ys)
        return {"optimal_trials": [keep[i].to_proto() for i in idx]}

    def UpdateMetadata(self, params: dict) -> dict:
        study_name = params["name"]
        delta = MetadataDelta.from_proto(params["delta"])
        # the study lock orders this against SetStudyState's read-modify-
        # write (backend atomicity alone can't stop a stale study snapshot
        # from overwriting the delta); per-trial entries naming deleted
        # trials are skipped instead of failing a half-applied delta, and
        # the skipped ids are reported so callers can detect stale targets
        with self._study_lock(study_name):
            self._get_study_or_rpc_error(study_name)
            skipped = self._ds.apply_metadata_delta(study_name, delta)
        return {"skipped_trials": skipped}

    def ListAlgorithms(self, params: dict) -> dict:
        return {"algorithms": registered_algorithms()}

    def Ping(self, params: dict) -> dict:
        return {"time": time.time()}

    def shutdown(self) -> None:
        if self.worker_pool is not None:
            self.worker_pool.shutdown()
        self._pool.shutdown(wait=False, cancel_futures=True)
