"""RPC transport (paper §3.1-3.2).

The paper's infrastructure is gRPC + protobuf; this container has neither, so
we reproduce the *protocol semantics* over a small, robust transport:

* Frames: 4-byte big-endian length prefix + msgpack body.
* Request:  {"id", "method", "params", "deadline_ms"}
* Response: {"id", "ok", "result"} or {"id", "ok": False,
             "error": {"code", "message"}}
* Server: threaded TCP server; one thread per connection, sequential frames
  per connection (clients pool connections for concurrency).
* Client: lazy connect, automatic reconnect, exponential-backoff retries for
  UNAVAILABLE/connection errors, per-call deadlines. Retry semantics mirror
  gRPC: only idempotent failures (transport-level) are retried; application
  errors surface as VizierRpcError.
* Batching: ``RpcClient.call_many`` pipelines N requests over one connection
  (send all frames, then read all responses in order — the server processes
  frames sequentially per connection), collapsing N network round-trips into
  one. The batched service methods (BatchSuggestTrials / BatchCompleteTrials)
  ride on top of single frames carrying request lists; call_many is the
  transport-level complement used e.g. to poll many operations at once.

A LocalTransport dispatches in-process — the paper notes the server may run
in the same process as the client when evaluation is cheap (§3.2).
"""

from __future__ import annotations

import logging
import random
import socket
import socketserver
import struct
import threading
import time
import uuid
from typing import Any, Callable, Dict, Optional

import msgpack

from repro import tracing
from repro.service import chaos
from repro.service._lockwitness import make_lock

log = logging.getLogger(__name__)

MAX_FRAME = 256 * 1024 * 1024  # 256 MiB


class StatusCode:
    OK = 0
    UNAVAILABLE = 14
    DEADLINE_EXCEEDED = 4
    NOT_FOUND = 5
    INVALID_ARGUMENT = 3
    ALREADY_EXISTS = 6
    FAILED_PRECONDITION = 9
    INTERNAL = 13
    UNIMPLEMENTED = 12


class VizierRpcError(Exception):
    def __init__(self, code: int, message: str):
        super().__init__(f"[code={code}] {message}")
        self.code = code
        self.message = message


def _pack(obj: dict) -> bytes:
    body = msgpack.packb(obj, use_bin_type=True)
    if len(body) > MAX_FRAME:
        raise VizierRpcError(StatusCode.INVALID_ARGUMENT, "frame too large")
    return struct.pack(">I", len(body)) + body


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed connection")
        buf += chunk
    return buf


def _read_frame(sock: socket.socket) -> dict:
    (length,) = struct.unpack(">I", _read_exact(sock, 4))
    if length > MAX_FRAME:
        raise VizierRpcError(StatusCode.INVALID_ARGUMENT, "frame too large")
    return msgpack.unpackb(_read_exact(sock, length), raw=False, strict_map_key=False)


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------


class Transport:
    """Abstract: issue a single request dict, get a response dict."""

    def call_raw(self, request: dict, timeout: float) -> dict:
        raise NotImplementedError

    def call_raw_many(self, requests: "list[dict]", timeout: float) -> "list[dict]":
        """Issue N requests, responses in request order. Default: sequential.

        On a transport error the responses already read are attached to the
        raised VizierRpcError as ``delivered`` so RpcClient.call_many can
        resend only the undelivered sub-requests.
        """
        out: "list[dict]" = []
        for r in requests:
            try:
                out.append(self.call_raw(r, timeout))
            except VizierRpcError as e:
                e.delivered = list(out)
                raise
        return out

    def close(self) -> None:
        pass


class LocalTransport(Transport):
    """In-process dispatch straight into a servicer (no sockets)."""

    def __init__(self, servicer: "Servicer"):
        self._servicer = servicer

    def call_raw(self, request: dict, timeout: float) -> dict:
        return self._servicer.dispatch(request)


class TcpTransport(Transport):
    """Socket transport with reconnect-on-failure."""

    def __init__(self, address: str):
        host, port = address.rsplit(":", 1)
        self._addr = (host, int(port))
        self._sock: Optional[socket.socket] = None
        self._lock = make_lock("TcpTransport._lock")

    def _connect(self, timeout: float) -> socket.socket:
        sock = socket.create_connection(self._addr, timeout=timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock

    def call_raw(self, request: dict, timeout: float) -> dict:
        with self._lock:  # one in-flight request per transport
            try:
                if self._sock is None:
                    self._sock = self._connect(timeout)
                self._sock.settimeout(timeout)
                # archlint: disable=chaos-call-under-lock — the transport lock
                # IS the per-frame serializer: an injected sever must tear
                # *this* connection's frame, so it has to fire inside it
                chaos.inject("transport.send", method=request.get("method"))
                # archlint: disable=lock-blocking-call — this lock IS the
                # per-connection request serializer; blocking socket I/O under
                # it is the design (one in-flight frame per transport)
                self._sock.sendall(_pack(request))
                # archlint: disable=chaos-call-under-lock — a drop models the
                # response frame lost after the server applied the request;
                # only this point in the serializer has that meaning
                chaos.inject("transport.recv", method=request.get("method"))
                return _read_frame(self._sock)
            except (OSError, ConnectionError, struct.error) as e:
                self._drop()
                raise VizierRpcError(StatusCode.UNAVAILABLE, f"transport: {e}") from e

    def call_raw_many(self, requests: "list[dict]", timeout: float) -> "list[dict]":
        """Pipelined: all frames go out, then all responses are read in order.

        Correct because the server handler loop reads/serves/replies one frame
        at a time per connection, so response order == request order. On a
        transport error the responses already read are attached to the raised
        VizierRpcError as ``delivered`` (see Transport.call_raw_many).
        """
        with self._lock:
            delivered: "list[dict]" = []
            try:
                if self._sock is None:
                    self._sock = self._connect(timeout)
                self._sock.settimeout(timeout)
                # archlint: disable=chaos-call-under-lock — the transport lock
                # IS the per-frame serializer; a batch sever must tear this
                # connection's pipelined frames, so it fires inside it
                chaos.inject("transport.send", method=requests[0].get("method"))
                # archlint: disable=lock-blocking-call — pipelined frames ride
                # the same per-connection serializer lock by design
                self._sock.sendall(b"".join(_pack(r) for r in requests))
                for i in range(len(requests)):
                    # archlint: disable=chaos-call-under-lock — a drop at
                    # index i loses response i after the server applied it;
                    # only this point in the serializer has that meaning
                    chaos.inject("transport.recv",
                                 method=requests[i].get("method"), index=i)
                    delivered.append(_read_frame(self._sock))
                return delivered
            except (OSError, ConnectionError, struct.error) as e:
                self._drop()
                err = VizierRpcError(StatusCode.UNAVAILABLE, f"transport: {e}")
                err.delivered = delivered
                raise err from e

    def _drop(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def close(self) -> None:
        with self._lock:
            self._drop()


# ---------------------------------------------------------------------------
# Client with retries/deadlines (gRPC-style fault tolerance)
# ---------------------------------------------------------------------------


class RetryBudget:
    """Token-bucket retry budget shared by every call on one client.

    Each retry spends a token; the bucket refills at ``refill_per_s`` and
    every success refunds ``success_credit``. When the bucket runs dry the
    client stops retrying and surfaces the UNAVAILABLE immediately, so an
    injected (or real) outage costs a caller one failed attempt instead of
    ``max_retries`` backoff cycles — retries track the *success* rate of the
    backend rather than amplifying its failure rate into a retry storm
    (gRPC retryThrottling semantics).
    """

    def __init__(self, capacity: float = 32.0, refill_per_s: float = 2.0,
                 success_credit: float = 1.0):
        self.capacity = float(capacity)
        self.refill_per_s = float(refill_per_s)
        self.success_credit = float(success_credit)
        self._tokens = self.capacity
        self._stamp = time.monotonic()
        self._lock = make_lock("RetryBudget._lock")

    def _refill_locked(self) -> None:
        now = time.monotonic()
        self._tokens = min(
            self.capacity,
            self._tokens + (now - self._stamp) * self.refill_per_s)
        self._stamp = now

    def try_spend(self, cost: float = 1.0) -> bool:
        with self._lock:
            self._refill_locked()
            if self._tokens < cost:
                return False
            self._tokens -= cost
            return True

    def record_success(self) -> None:
        with self._lock:
            self._refill_locked()
            self._tokens = min(self.capacity,
                               self._tokens + self.success_credit)

    @property
    def tokens(self) -> float:
        with self._lock:
            self._refill_locked()
            return self._tokens


class CircuitBreaker:
    """Consecutive-transport-failure breaker: closed → open → half-open.

    ``failure_threshold`` consecutive transport failures open the breaker;
    while open, ``allow()`` is False so the client backs off without touching
    the socket (no reconnect storm against a dead or drowning server). After
    ``cooldown_s`` exactly one probe is let through: success closes the
    breaker, failure re-opens it for another cooldown. Only transport-level
    failures count — an application error proves the server is up.
    """

    def __init__(self, failure_threshold: int = 16, cooldown_s: float = 1.0):
        self.failure_threshold = int(failure_threshold)
        self.cooldown_s = float(cooldown_s)
        self._failures = 0
        self._opened_at: Optional[float] = None
        self._probing = False
        self._lock = make_lock("CircuitBreaker._lock")

    def allow(self) -> bool:
        with self._lock:
            if self._opened_at is None:
                return True
            if time.monotonic() - self._opened_at < self.cooldown_s:
                return False
            if self._probing:
                return False
            self._probing = True  # half-open: single probe in flight
            return True

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._opened_at = None
            self._probing = False

    def record_failure(self) -> None:
        with self._lock:
            self._failures += 1
            self._probing = False
            if self._failures >= self.failure_threshold:
                self._opened_at = time.monotonic()

    @property
    def is_open(self) -> bool:
        with self._lock:
            return (self._opened_at is not None
                    and time.monotonic() - self._opened_at < self.cooldown_s)


class RpcClient:
    def __init__(
        self,
        target: "str | Servicer",
        *,
        default_timeout: float = 30.0,
        max_retries: int = 5,
        backoff_base: float = 0.05,
        backoff_cap: float = 2.0,
        retry_budget: Optional[RetryBudget] = None,
        circuit_breaker: Optional[CircuitBreaker] = None,
    ):
        if isinstance(target, str):
            self._transport: Transport = TcpTransport(target)
        else:
            self._transport = LocalTransport(target)
        self.default_timeout = default_timeout
        self.max_retries = max_retries
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self.retry_budget = (retry_budget if retry_budget is not None
                             else RetryBudget())
        self.circuit_breaker = (circuit_breaker if circuit_breaker is not None
                                else CircuitBreaker())

    def _backoff_sleep(self, attempt: int, deadline: float) -> None:
        """Jittered exponential backoff, clamped to the request deadline.

        Unclamped, the last retry could sleep a full backoff (up to
        1.5 * backoff_cap) *past* the deadline before the next loop
        iteration noticed and raised — callers saw DEADLINE_EXCEEDED
        seconds after their deadline. Clamping the sleep to the remaining
        budget makes the error surface at the deadline, not after it.
        """
        delay = min(self.backoff_cap, self.backoff_base * (2**attempt))
        delay *= 0.5 + random.random()
        remaining = deadline - time.monotonic()
        if remaining > 0:
            time.sleep(min(delay, remaining))

    def call(self, method: str, params: dict, *, timeout: Optional[float] = None) -> Any:
        timeout = timeout if timeout is not None else self.default_timeout
        deadline = time.monotonic() + timeout
        request = {
            "id": uuid.uuid4().hex,
            "method": method,
            "params": params,
            "deadline_ms": int(timeout * 1000),
        }
        with tracing.span("vizier.rpc.call", method=method, rid=request["id"]):
            return self._call(method, request, deadline)

    def _call(self, method: str, request: dict, deadline: float) -> Any:
        attempt = 0
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise VizierRpcError(StatusCode.DEADLINE_EXCEEDED, f"{method} deadline")
            if not self.circuit_breaker.allow():
                # open breaker: back off without touching the socket; keep
                # retrying (within budget) so a recovering server is re-probed
                if attempt >= self.max_retries or not self.retry_budget.try_spend():
                    raise VizierRpcError(
                        StatusCode.UNAVAILABLE, f"{method}: circuit breaker open")
                attempt += 1
                self._backoff_sleep(attempt, deadline)
                continue
            try:
                resp = self._transport.call_raw(request, remaining)
            except VizierRpcError as e:
                if e.code != StatusCode.UNAVAILABLE:
                    raise
                self.circuit_breaker.record_failure()
                if attempt >= self.max_retries or not self.retry_budget.try_spend():
                    raise
                attempt += 1
                self._backoff_sleep(attempt, deadline)
                continue
            self.circuit_breaker.record_success()
            if resp.get("ok"):
                self.retry_budget.record_success()
                return resp.get("result")
            err = resp.get("error") or {}
            code = err.get("code", StatusCode.INTERNAL)
            if (code == StatusCode.UNAVAILABLE and attempt < self.max_retries
                    and self.retry_budget.try_spend()):
                attempt += 1
                self._backoff_sleep(attempt, deadline)
                continue
            raise VizierRpcError(code, err.get("message", "unknown error"))

    def call_many(
        self,
        method: str,
        params_list: "list[dict]",
        *,
        timeout: Optional[float] = None,
        return_exceptions: bool = False,
    ) -> "list[Any]":
        """N calls of one method, pipelined over a single connection.

        Results come back in params order. On a mid-batch transport failure
        the responses already read are kept and only the *undelivered*
        sub-requests are resent — a sub-request whose response was read is
        never re-sent, so batching non-idempotent methods cannot double-apply
        work the server already acknowledged. (A sub-request whose response
        was lost in flight is still at-least-once, same as any single call:
        services dedupe those via client-chosen operation ids.) The first
        application error is raised after all responses are read, so the
        connection stays frame-aligned. With ``return_exceptions=True``
        application errors are returned in-place as VizierRpcError objects
        instead — per-item fault isolation for pipelined reads where one bad
        key must not fail its siblings.
        """
        if not params_list:
            return []
        timeout = timeout if timeout is not None else self.default_timeout
        deadline = time.monotonic() + timeout
        requests = [
            {
                "id": uuid.uuid4().hex,
                "method": method,
                "params": params,
                "deadline_ms": int(timeout * 1000),
            }
            for params in params_list
        ]
        responses_by_id: Dict[str, dict] = {}

        def _absorb(resps: "list[dict]") -> None:
            for resp in resps:
                rid = resp.get("id")
                if rid is not None:
                    responses_by_id[rid] = resp

        pending = list(requests)
        attempt = 0
        while pending:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise VizierRpcError(StatusCode.DEADLINE_EXCEEDED, f"{method} deadline")
            if not self.circuit_breaker.allow():
                if attempt >= self.max_retries or not self.retry_budget.try_spend():
                    raise VizierRpcError(
                        StatusCode.UNAVAILABLE, f"{method}: circuit breaker open")
                attempt += 1
                self._backoff_sleep(attempt, deadline)
                continue
            try:
                _absorb(self._transport.call_raw_many(pending, remaining))
            except VizierRpcError as e:
                _absorb(getattr(e, "delivered", None) or [])
                pending = [r for r in pending if r["id"] not in responses_by_id]
                if e.code != StatusCode.UNAVAILABLE:
                    raise
                self.circuit_breaker.record_failure()
                if attempt >= self.max_retries or not self.retry_budget.try_spend():
                    raise
                attempt += 1
                self._backoff_sleep(attempt, deadline)
                continue
            self.circuit_breaker.record_success()
            self.retry_budget.record_success()
            pending = [r for r in pending if r["id"] not in responses_by_id]
        results = []
        first_error: Optional[VizierRpcError] = None
        for req in requests:
            resp = responses_by_id.get(req["id"]) or {}
            if resp.get("ok"):
                results.append(resp.get("result"))
                continue
            err = resp.get("error") or {}
            error = VizierRpcError(
                err.get("code", StatusCode.INTERNAL),
                err.get("message", "unknown error"),
            )
            if first_error is None:
                first_error = error
            results.append(error if return_exceptions else None)
        if first_error is not None and not return_exceptions:
            raise first_error
        return results

    def close(self) -> None:
        self._transport.close()


class PooledRpcClient:
    """Thread-affine RpcClient pool: one connection per calling thread.

    A single RpcClient over TCP serializes concurrent callers on its
    transport lock — fine for one client thread, a bottleneck for the
    Pythia worker pool, where N workers dispatch coalesced batches
    concurrently to the same Pythia service. Each thread lazily gets its own
    RpcClient (same retry/deadline semantics); close() tears down every
    connection ever created.
    """

    def __init__(self, target: "str | Servicer", **client_kwargs):
        self._target = target
        self._kwargs = client_kwargs
        self._local = threading.local()
        self._all: "list[RpcClient]" = []
        self._all_lock = make_lock("PooledRpcClient._all_lock")

    def _client(self) -> RpcClient:
        client = getattr(self._local, "client", None)
        if client is None:
            client = RpcClient(self._target, **self._kwargs)
            self._local.client = client
            with self._all_lock:
                self._all.append(client)
        return client

    def call(self, method: str, params: dict, *, timeout: Optional[float] = None) -> Any:
        return self._client().call(method, params, timeout=timeout)

    def call_many(self, method: str, params_list: "list[dict]", **kwargs) -> "list[Any]":
        return self._client().call_many(method, params_list, **kwargs)

    def close(self) -> None:
        with self._all_lock:
            clients, self._all = self._all, []
        for c in clients:
            c.close()


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------


class Servicer:
    """Registry of method handlers. Subclasses register via expose().

    Every dispatched frame is tallied in ``method_counts`` — the
    frame-counting regression tests assert the coalesced suggestion path
    really does collapse to one GetTrialsMulti + one PythiaBatchSuggest
    frame per batch.
    """

    def __init__(self):
        self._methods: Dict[str, Callable[[dict], Any]] = {}
        self._counts: Dict[str, int] = {}
        self._counts_lock = make_lock("Servicer._counts_lock")

    def expose(self, name: str, fn: Callable[[dict], Any]) -> None:
        self._methods[name] = fn

    def method_counts(self) -> Dict[str, int]:
        """Frames dispatched per method since construction (or last reset)."""
        with self._counts_lock:
            return dict(self._counts)

    def reset_method_counts(self) -> None:
        with self._counts_lock:
            self._counts.clear()

    def dispatch(self, request: dict) -> dict:
        rid = request.get("id")
        method = request.get("method", "")
        with self._counts_lock:
            self._counts[method] = self._counts.get(method, 0) + 1
        fn = self._methods.get(method)
        if fn is None:
            return {
                "id": rid,
                "ok": False,
                "error": {"code": StatusCode.UNIMPLEMENTED, "message": f"no method {method!r}"},
            }
        with tracing.span("vizier.rpc.dispatch", method=method, rid=rid):
            return self._handle(fn, method, rid, request)

    @staticmethod
    def _handle(fn, method: str, rid, request: dict) -> dict:
        try:
            result = fn(request.get("params") or {})
            return {"id": rid, "ok": True, "result": result}
        except VizierRpcError as e:
            return {"id": rid, "ok": False, "error": {"code": e.code, "message": e.message}}
        except Exception as e:  # noqa: BLE001 - server must not die on handler bugs
            log.exception("handler %s failed", method)
            # duck-type a carried status code so exceptions like
            # PolicyConstructionError keep INVALID_ARGUMENT over the wire
            code = getattr(e, "code", None)
            if not isinstance(code, int):
                code = StatusCode.INTERNAL
            return {
                "id": rid,
                "ok": False,
                "error": {"code": code, "message": f"{type(e).__name__}: {e}"},
            }


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        sock: socket.socket = self.request
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        servicer: Servicer = self.server.servicer  # type: ignore[attr-defined]
        while True:
            try:
                request = _read_frame(sock)
            except (ConnectionError, OSError, struct.error):
                return  # client went away
            response = servicer.dispatch(request)
            try:
                sock.sendall(_pack(response))
            except (OSError, ConnectionError):
                return


class _ThreadingTCPServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    # the socketserver default backlog of 5 drops SYNs when hundreds of
    # clients dial at once (the scale-out benchmark's 256-client storm
    # surfaced as DEADLINE_EXCEEDED on first calls); match a production
    # listen queue instead
    request_queue_size = 1024


class RpcServer:
    """Threaded TCP server wrapping a Servicer (paper Code Block 4)."""

    def __init__(self, servicer: Servicer, host: str = "127.0.0.1", port: int = 0):
        self._server = _ThreadingTCPServer((host, port), _Handler)
        self._server.servicer = servicer  # type: ignore[attr-defined]
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> str:
        host, port = self._server.server_address[:2]
        return f"{host}:{port}"

    def start(self) -> "RpcServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever, kwargs={"poll_interval": 0.05}, daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
