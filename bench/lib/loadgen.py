"""The open-loop load generator: worker events against the served path.

Each (study, worker) pair is one worker slot with its own ``VizierClient``
(its own TCP connection and client id). A dispatcher thread sleeps until
each event is due and hands it to a small thread pool; the event takes its
slot's lock (a worker does one thing at a time), completes the trials the
worker holds with ``CompleteTrial``, valued by the study's objective, and
then asks with ``get_suggestions(count)``.

Latency is timed from when the op was due, not from when it was sent: a
suggest op and the event's first ``CompleteTrial`` are due at the event's
due time; a further ``CompleteTrial`` of the same event is due when the one
before it returned. So a stall in the system, or a worker still waiting for
its previous suggestions, shows in every op it delays. ``late_s`` is how
late a generator thread picked each event up, so a starved generator is not
read as a fast server.
"""

from __future__ import annotations

import concurrent.futures as futures
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

SUGGEST_TIMEOUT_S = 120.0


@dataclasses.dataclass
class OpRecord:
    kind: str                 # "suggest" or "complete"
    event: int
    study: int
    worker: int
    due: float                # perf_counter seconds
    sent: float = 0.0
    done: float = 0.0
    ok: bool = False
    error: str = ""
    count: int = 0
    trials: list = dataclasses.field(default_factory=list)
    trial_id: int = 0
    value: float = 0.0


class Slot:
    def __init__(self, client):
        self.lock = threading.Lock()
        self.client = client
        self.held: list = []       # trials handed to this worker, not reported


class LoadGen:
    def __init__(self, address: str, study_names: List[str], configs: list,
                 objectives: list, workers: int, *, threads: int = 32):
        from repro.service import VizierClient

        self.configs = configs
        self.objectives = objectives
        self.slots: Dict[Tuple[int, int], Slot] = {
            (s, w): Slot(VizierClient(address, name, f"w{w}"))
            for s, name in enumerate(study_names) for w in range(workers)}
        self.threads = threads
        self.records: List[OpRecord] = []
        self.late_s: List[float] = []
        self.unfinished_due: List[float] = []
        self._lock = threading.Lock()

    def _add(self, rec: OpRecord) -> None:
        with self._lock:
            self.records.append(rec)

    def _complete(self, slot: Slot, ev_index: int, study: int, worker: int,
                  due: float) -> None:
        while slot.held:
            t = slot.held[0]
            value = self.objectives[study](t.parameters)
            rec = OpRecord("complete", ev_index, study, worker, due,
                           trial_id=t.id, value=value["obj"])
            rec.sent = time.perf_counter()
            try:
                slot.client.complete_trial(value, trial_id=t.id)
                rec.ok = True
            except Exception as e:  # noqa: BLE001 - recorded as a failed op
                rec.error = f"{type(e).__name__}: {e}"
            rec.done = time.perf_counter()
            self._add(rec)
            slot.held.pop(0)
            due = rec.done

    def run_event(self, ev_index: int, study: int, worker: int, count: int,
                  due: float) -> None:
        if ev_index >= 0:
            with self._lock:
                self.late_s.append(max(0.0, time.perf_counter() - due))
        slot = self.slots[(study, worker)]
        with slot.lock:
            self._complete(slot, ev_index, study, worker, due)
            rec = OpRecord("suggest", ev_index, study, worker, due,
                           count=count)
            rec.sent = time.perf_counter()
            try:
                trials = slot.client.get_suggestions(
                    count=count, timeout=SUGGEST_TIMEOUT_S)
                rec.trials = trials
                rec.ok = True
                slot.held = list(trials)
            except Exception as e:  # noqa: BLE001 - recorded as a failed op
                rec.error = f"{type(e).__name__}: {e}"
            rec.done = time.perf_counter()
            self._add(rec)

    def run(self, events, t0: float, grace_s: float) -> float:
        """Drives ``events`` (due_s after ``t0``); waits for each until
        ``grace_s`` past the last due time. Returns the window's close."""
        pool = futures.ThreadPoolExecutor(self.threads,
                                          thread_name_prefix="bench-load")
        pending = {}
        try:
            for ev in events:
                due = t0 + ev.due_s
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                pending[pool.submit(self.run_event, ev.index, ev.study,
                                    ev.worker, ev.count, due)] = due
            close = t0 + max(ev.due_s for ev in events)
            done, not_done = futures.wait(
                pending, timeout=max(0.0, close + grace_s - time.perf_counter()))
            for f in done:
                f.result()
            self.unfinished_due = [pending[f] for f in not_done]
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
        return close

    def complete_held(self) -> None:
        """Reports every trial still held (used after the warm-up)."""
        for (study, worker), slot in self.slots.items():
            with slot.lock:
                self._complete(slot, -1, study, worker, time.perf_counter())

    def close(self) -> None:
        for slot in self.slots.values():
            slot.client.close()
