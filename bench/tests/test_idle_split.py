"""The device-idle split by program spans (bench/idle_split.py), and the
trace reduction it sits beside, unchanged on the first recorded trace."""

import json
import pathlib
import types

import pytest

from bench import idle_split, trace_reduce

DATA = pathlib.Path(__file__).parent / "data"


def _ev(name, a, b, **stats):
    return types.SimpleNamespace(name=name, start_ns=float(a),
                                 end_ns=float(b), duration_ns=float(b - a),
                                 stats=list(stats.items()))


def _plane(name, lines=(), stats=()):
    return types.SimpleNamespace(
        name=name, stats=list(stats),
        lines=[types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


def test_idle_split_on_a_synthetic_plane():
    """Window [0, 100] ns, device busy [10, 20] and [50, 60]: 80 ns idle.
    Thread a works in a batch with a query inside it, thread b waits for a
    lease, thread c works in a policy call. Thread d is a client blocked
    in a WaitOperation call, whose dispatch on thread e works only before
    and after its long-poll park."""
    pdata = types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", [
            ("XLA Ops", [_ev("%fusion.1 = f32[8]", 10, 20),
                         _ev("%fusion.2 = f32[8]", 50, 60)]),
            ("XLA Modules", [_ev("jit_f(1)", 10, 60)])]),
        _plane("/host:CPU", [
            ("a", [_ev("vizier.worker.batch", 5, 45),
                   _ev("vizier.datastore.query", 25, 35),
                   _ev("PjitFunction(f)", 11, 12)]),
            ("b", [_ev("vizier.lease.wait", 0, 70)]),
            ("c", [_ev("vizier.policy.suggest", 40, 55)]),
            ("d", [_ev("vizier.rpc.call", 61, 84,
                       method="WaitOperation", rid="r1")]),
            ("e", [_ev("vizier.rpc.dispatch", 62, 82,
                       method="WaitOperation", rid="r1"),
                   _ev("vizier.op.wait", 65, 80)])]),
    ])
    s = idle_split.split(pdata, 0.0, 100.0)
    assert s.window_s == pytest.approx(100e-9)
    assert s.idle_s == pytest.approx(80e-9)
    assert s.working_s == pytest.approx(40e-9)
    assert s.waiting_s == pytest.approx(24e-9)
    assert s.no_span_s == pytest.approx(16e-9)
    assert s.working_s + s.waiting_s + s.no_span_s == pytest.approx(s.idle_s)
    assert [n for n, _ in s.by_span] == [
        "vizier.worker.batch", "vizier.datastore.query",
        "vizier.policy.suggest", "vizier.rpc.dispatch"]
    assert [v for _, v in s.by_span] == pytest.approx(
        [15e-9, 10e-9, 10e-9, 5e-9])

    # the dispatch's recorded start puts the host window on the trace clock
    rec = types.SimpleNamespace(name="vizier.rpc.dispatch", start_ns=1062,
                                counts={"method": "WaitOperation",
                                        "rid": "r1"})
    lo, hi = idle_split.trace_window(pdata, [rec], (1000e-9, 1100e-9))
    assert (lo, hi) == (pytest.approx(0.0, abs=1e-6),
                        pytest.approx(100.0, abs=1e-6))
    assert idle_split.trace_window(pdata, [], (0.0, 1.0)) is None


def test_span_names_lose_their_metadata():
    assert idle_split.span_name("vizier.rpc.dispatch#method=X,rid=1#") == \
        "vizier.rpc.dispatch"
    assert idle_split.span_name("vizier.finalize") == "vizier.finalize"
    assert idle_split.span_meta(_ev("vizier.rpc.dispatch", 0, 1, method="X",
                                    rid="ab")) == {"method": "X", "rid": "ab"}
    assert idle_split.span_meta(_ev("vizier.finalize", 0, 1)) == {}


def test_reduction_of_the_first_trace_reads_as_before():
    from jax.profiler import ProfileData

    red = trace_reduce.reduce(
        ProfileData.from_file(str(DATA / "small_trace.xplane.pb")), 1.0)
    assert (red.busy_s, red.window_s, red.chips) == (0.000431736, 1.0, 1)
    assert red.module_s == {"_lambda": 0.00043182300000000007}
    assert red.unshaped == {}
    assert {k: [c.seconds for c in v] for k, v in red.kernels.items()} == {
        "gram": [1.4672000000000001e-05, 1.4668000000000001e-05, 1.4671e-05],
        "tri_solve": [9.015000000000001e-05, 9.014400000000001e-05,
                      9.0167e-05],
        "cholupdate": [3.4648000000000004e-05, 3.4646e-05, 3.4646e-05]}
    assert json.dumps(red.breakdown()) == (
        '{"device_ops": [["_lambda:tri_solve_pallas", 0.000270461], '
        '["_lambda:cholupdate_pallas", 0.00010394000000000001], '
        '["_lambda:matern52_gram_pallas", 4.4011e-05], '
        '["_lambda:copy", 1.2509e-05], ["_lambda:pad", 7.91e-07], '
        '["_lambda:copy-start", 1.6e-08], ["_lambda:copy-done", 8e-09]], '
        '"idle_gaps": [["idle", 0.000723813], ["idle", 0.0006964730000000001], '
        '["idle", 0.000601016], ["idle", 0.0005833790000000001], '
        '["idle", 0.000569843], ["bench.policy", 0.000557747], '
        '["bench.policy", 0.0005200760000000001], '
        '["bench.policy", 0.000454275], ["idle", 2e-09], ["idle", 2e-09]]}')


def test_idle_split_of_a_trace_with_program_spans():
    """``data/span_trace.xplane.pb`` was written on one v5e chip by
    ``bench/tools/record_span_trace.py``: three kernel rounds, the first
    two inside a worker batch, then 3 ms of device idle in a decode span,
    3 ms in a long-poll park only, and 3 ms in no span."""
    from jax.profiler import ProfileData

    pdata = ProfileData.from_file(str(DATA / "span_trace.xplane.pb"))
    red = trace_reduce.reduce(pdata, 1.0)
    assert {k: len(v) for k, v in red.kernels.items()} == {
        "gram": 3, "tri_solve": 3, "cholupdate": 3}
    assert {n for n, _ in red.breakdown()["idle_gaps"]} == {"idle"}
    assert {n for _, _, n, _ in idle_split.host_spans(pdata)} == {
        "vizier.worker.batch", "vizier.policy.suggest",
        "vizier.datastore.decode", "vizier.policy.acquire", "vizier.op.wait"}

    # the profile's own window: event times are relative to its start
    (env,) = [p for p in pdata.planes if p.name == "Task Environment"]
    stats = dict(env.stats)
    s = idle_split.split(pdata, 0.0, float(
        stats["profile_stop_time"] - stats["profile_start_time"]))
    assert s.idle_s == pytest.approx(s.window_s - red.busy_s, abs=1e-9)
    assert s.working_s + s.waiting_s + s.no_span_s == pytest.approx(
        s.idle_s, rel=1e-9)
    idle = 0.9 * 0.003
    assert s.by_span[0][0] == "vizier.datastore.decode"
    assert s.by_span[0][1] > idle
    assert s.waiting_s > idle and s.no_span_s > idle
    # the gaps between kernel calls inside the policy span are working time
    assert dict(s.by_span)["vizier.policy.suggest"] > 0
