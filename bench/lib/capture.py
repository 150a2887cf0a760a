"""Spans, counters and captured outputs, taken from around the program's calls.

``install()`` wraps a fixed set of the program's functions (nothing in the
program is edited). While a ``Recorder`` is active the wrappers note, on
the host clock:

* queue: when each suggest op was enqueued, and when the worker batch that
  runs it started (``ShardedWorkQueue.enqueue``,
  ``VizierService._run_suggest_ops_coalesced``);
* datastore: the time a worker batch spends in datastore reads, and the
  time of each ``CompleteTrial`` handler;
* policy: each ``GPBanditPolicy.suggest`` call, its featurization time
  (``trials_to_xy``, ``to_features``, ``to_parameters``) and its Adam step
  count (``last_fit_steps``).

For the correctness check they also keep what the timed path itself
produced, per policy call: the fit's data and starting state, each Adam
step's hyperparameters in and out, its loss and its schedule
(``gp_bandit._fit_step``), the fit's closing loss (``_mll_grad``), the
posterior's design and hyperparameters
(``CholeskyPosterior``/``SparsePosterior``), the candidate pool, the pending
fantasies, every pool UCB vector the count loop scored (``pool_ucb``) and
the picks (``_suggest_engine``). Device values are kept as references and
read back only after the window.

With ``trace=True`` the same wrappers open ``jax.profiler.TraceAnnotation``
spans (``bench.queue_batch``, ``bench.policy``, ``bench.datastore_read``,
``bench.featurize``, ``bench.complete``) so that the trace can label idle
gaps. Installing twice is a no-op; a wrapped name that the program no
longer has raises, since the check could then not see the timed path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

_LOCAL = threading.local()
_INSTALLED: Dict[str, Any] = {}
RECORDER: Optional["Recorder"] = None


@dataclasses.dataclass
class PolicyCall:
    study: str
    count: int
    t0: float
    t1: float = 0.0
    fit_steps: int = 0
    featurize_s: float = 0.0
    sparse: bool = False
    fit: Optional[dict] = None        # first _fit_step: inputs and stats
    # every _fit_step: input and output hyperparameters, stats, schedule
    fit_trace: List[dict] = dataclasses.field(default_factory=list)
    fit_close: Optional[tuple] = None  # the closing loss: (raw, loss)
    posterior: Optional[dict] = None  # raw, x, y, capacity of the top level
    engine: Optional[dict] = None     # pool, fantasies, count, picks
    scores: List[np.ndarray] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Batch:
    t0: float
    ops: List[str]
    t1: float = 0.0
    read_s: float = 0.0
    policy_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    op_study: Dict[str, str] = dataclasses.field(default_factory=dict)


class Recorder:
    """What the wrappers saw while it was active (one per run)."""

    def __init__(self, *, trace: bool = False):
        self.trace = trace
        self.active = False
        self.lock = threading.Lock()
        self.enqueued: Dict[str, float] = {}
        self.batches: List[Batch] = []
        self.calls: List[PolicyCall] = []
        self.complete_s: List[float] = []


def _span(name: str):
    rec = RECORDER
    if rec is None or not rec.trace:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _active() -> Optional[Recorder]:
    rec = RECORDER
    return rec if rec is not None and rec.active else None


def _wrap(owner, name: str, make):
    key = f"{getattr(owner, '__module__', '')}.{owner.__name__}.{name}"
    if key in _INSTALLED:
        return
    orig = getattr(owner, name, None)
    if orig is None:
        raise AttributeError(f"the program has no {key}: cannot capture it")
    _INSTALLED[key] = (owner, name, orig)

    # the wrapper reaches the original through _INSTALLED, so a fault can be
    # planted beneath it (bench/tests/test_faults.py)
    def current(*a, **k):
        return _INSTALLED[key][2](*a, **k)
    setattr(owner, name, make(functools.update_wrapper(current, orig)))


def install() -> None:
    """Wraps the program's functions once per process."""
    from repro.pythia import converters, gp_bandit, posterior, sparse_posterior
    from repro.service import vizier_service, work_queue

    def enqueue(orig):
        @functools.wraps(orig)
        def w(self, op):
            rec = _active()
            if rec is not None:
                rec.enqueued[op["name"]] = time.perf_counter()
            return orig(self, op)
        return w

    def run_batch(orig):
        @functools.wraps(orig)
        def w(self, ops, op_guard=None):
            rec = _active()
            if rec is None:
                return orig(self, ops, op_guard)
            b = Batch(time.perf_counter(), [op["name"] for op in ops],
                      op_study={op["name"]: op["study_name"] for op in ops})
            _LOCAL.batch = b
            try:
                with _span("bench.queue_batch"):
                    return orig(self, ops, op_guard)
            finally:
                _LOCAL.batch = None
                b.t1 = time.perf_counter()
                with rec.lock:
                    rec.batches.append(b)
        return w

    def complete(orig):
        @functools.wraps(orig)
        def w(self, params):
            rec = _active()
            if rec is None:
                return orig(self, params)
            t0 = time.perf_counter()
            try:
                with _span("bench.complete"):
                    return orig(self, params)
            finally:
                dt = time.perf_counter() - t0
                with rec.lock:
                    rec.complete_s.append(dt)
        return w

    def suggest(orig):
        @functools.wraps(orig)
        def w(self, request):
            rec = _active()
            if rec is None:
                return orig(self, request)
            call = PolicyCall(request.study_guid, int(request.count), time.perf_counter())
            _LOCAL.call = call
            try:
                with _span("bench.policy"):
                    return orig(self, request)
            finally:
                _LOCAL.call = None
                call.t1 = time.perf_counter()
                call.fit_steps = int(self.last_fit_steps)
                call.sparse = bool(self.last_sparse)
                b = getattr(_LOCAL, "batch", None)
                if b is not None:
                    b.policy_s[call.study] = (b.policy_s.get(call.study, 0.0)
                                              + call.t1 - call.t0)
                with rec.lock:
                    rec.calls.append(call)
        return w

    def featurize(orig):
        @functools.wraps(orig)
        def w(*a, **k):
            call = getattr(_LOCAL, "call", None)
            if call is None or getattr(_LOCAL, "in_featurize", False):
                return orig(*a, **k)
            _LOCAL.in_featurize = True
            t0 = time.perf_counter()
            try:
                with _span("bench.featurize"):
                    return orig(*a, **k)
            finally:
                call.featurize_s += time.perf_counter() - t0
                _LOCAL.in_featurize = False
        return w

    def fit_step(orig):
        @functools.wraps(orig)
        def w(raw, m, v, x, y, mask, bc1, bc2, lr_t):
            out = orig(raw, m, v, x, y, mask, bc1, bc2, lr_t)
            call = getattr(_LOCAL, "call", None)
            if call is not None:
                if call.fit is None:
                    call.fit = {"raw": raw, "m": m, "v": v, "x": x, "y": y,
                                "mask": mask, "stats": out[3]}
                call.fit_trace.append({"raw": raw, "new_raw": out[0],
                                       "stats": out[3], "bc1": float(bc1),
                                       "bc2": float(bc2),
                                       "lr_t": float(lr_t)})
            return out
        return w

    def mll_grad(orig):
        @functools.wraps(orig)
        def w(raw, x, y, mask):
            out = orig(raw, x, y, mask)
            call = getattr(_LOCAL, "call", None)
            if call is not None and call.fit is not None:
                call.fit_close = (raw, out[0])
            return out
        return w

    def posterior_init(kind):
        def make(orig):
            @functools.wraps(orig)
            def w(self, raw, x, y, *a, **k):
                orig(self, raw, x, y, *a, **k)
                call = getattr(_LOCAL, "call", None)
                if call is not None:
                    call.posterior = {"kind": kind, "raw": self.raw,
                                      "x": np.asarray(x, np.float32),
                                      "y": np.asarray(y, np.float32)}
            return w
        return make

    def pool_ucb(orig):
        @functools.wraps(orig)
        def w(self, beta):
            out = orig(self, beta)
            call = getattr(_LOCAL, "call", None)
            if call is not None:
                call.scores.append(out)
            return out
        return w

    def engine(orig):
        @functools.wraps(orig)
        def w(self, post, pool, pool_mu, beta, fantasy_x, y_pend, count):
            picks = orig(self, post, pool, pool_mu, beta, fantasy_x, y_pend,
                         count)
            call = getattr(_LOCAL, "call", None)
            if call is not None:
                pool = np.asarray(pool)
                idx = [int(np.flatnonzero(np.all(pool == p, axis=1))[0])
                       for p in picks]
                call.engine = {
                    "pool": pool, "pool_mu": np.asarray(pool_mu),
                    "beta": float(beta), "count": int(count),
                    "fantasy_x": None if fantasy_x is None
                    else np.asarray(fantasy_x),
                    "y_pend": None if y_pend is None else np.asarray(y_pend),
                    "picks": idx}
            return picks
        return w

    _wrap(work_queue.ShardedWorkQueue, "enqueue", enqueue)
    _wrap(vizier_service.VizierService, "_run_suggest_ops_coalesced", run_batch)
    _wrap(vizier_service.VizierService, "CompleteTrial", complete)
    _wrap(gp_bandit.GPBanditPolicy, "suggest", suggest)
    _wrap(gp_bandit, "trials_to_xy", featurize)
    _wrap(converters.TrialToArrayConverter, "to_features", featurize)
    _wrap(converters.TrialToArrayConverter, "to_parameters", featurize)
    _wrap(gp_bandit, "_fit_step", fit_step)
    _wrap(gp_bandit, "_mll_grad", mll_grad)
    _wrap(posterior.CholeskyPosterior, "__init__", posterior_init("dense"))
    _wrap(sparse_posterior.SparsePosterior, "__init__",
          posterior_init("sparse"))
    _wrap(posterior.CholeskyPosterior, "pool_ucb", pool_ucb)
    _wrap(sparse_posterior.SparsePosterior, "pool_ucb", pool_ucb)
    _wrap(gp_bandit.GPBanditPolicy, "_suggest_engine", engine)


def install_datastore_reads(datastore_cls) -> None:
    """Times the reads a worker batch makes through ``datastore_cls``."""
    def make(orig):
        return _datastore_read(orig)
    for name in ("get_study", "list_trials", "list_trials_multi",
                 "max_trial_id", "get_operation", "list_operations"):
        _wrap(datastore_cls, name, make)


def _datastore_read(orig):
    @functools.wraps(orig)
    def w(self, *a, **k):
        b = getattr(_LOCAL, "batch", None)
        if b is None or getattr(_LOCAL, "in_read", False):
            return orig(self, *a, **k)
        _LOCAL.in_read = True
        t0 = time.perf_counter()
        try:
            with _span("bench.datastore_read"):
                return orig(self, *a, **k)
        finally:
            b.read_s += time.perf_counter() - t0
            _LOCAL.in_read = False
    return w


def start(recorder: Recorder) -> None:
    global RECORDER
    RECORDER = recorder
    recorder.active = True


def stop() -> None:
    if RECORDER is not None:
        RECORDER.active = False
