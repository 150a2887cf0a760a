#!/usr/bin/env python3
"""What the program's spans say about one traced run of a cell.

    python3 bench/tools/span_report.py --workload <cell> --seed <n> \
        [--seconds <s>] [--out <file>]

One process, one chip. Runs the cell as ``bench/run.py --trace 1`` does and
prints one JSON line (also written to ``--out``) with:

* ``result``: the run's own result line (end-to-end and per-layer metrics);
* ``idle_split``: the device-idle split of the traced window by program
  spans (``bench/idle_split.py``), over the reduction's window, beside the
  reduction's idle time;
* ``spans_per_op``: program spans in the window per suggest op served, all
  of them and those carrying a suggest op's trace id;
* ``coverage``: for the suggest op of median latency, and as quantiles over
  all ops, the share of its send-to-done interval that no span of its trace
  covers, other than those in which it only waits: the client's
  ``vizier.rpc.call``, the server's ``WaitOperation`` dispatch and its
  ``vizier.op.wait`` park; and the median op's three longest uncovered
  gaps (ms after the send, ms long, the spans before and after);
* ``cpu_by_span``: per span name, over the spans that started in the
  window, wall and thread-CPU time per suggest op served and their ratio
  (a span that is slow for want of the CPU reads a low ratio);
* ``profiler_on_off``: per suggest op, the wall time of the worker batches
  that started while the profiler recorded, and of those that started
  after it stopped;
* ``run_s``: the run's wall time from the harness's start to its result,
  to set against the same cell's untraced run;
* ``span_ns``: the cost of one span on this host with the profiler off and
  with a trace recording, and of a thread-CPU clock reading and a bare
  annotation (measured after the run, in the same process).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import idle_split, run, trace_reduce  # noqa: E402
from bench.lib import cells, spans  # noqa: E402

COST_SPANS = 100_000


def _waits(r) -> bool:
    """A span in which the op only waits for its server or for itself."""
    return r.name in ("vizier.rpc.call", "vizier.op.wait") or (
        r.name == "vizier.rpc.dispatch"
        and r.counts.get("method") == "WaitOperation")


def _gaps(intervals, a, b) -> list:
    """The parts of ``[a, b]`` that no ``(start, end, name)`` interval
    covers, each as ``(start, end, name before, name after)``."""
    gaps, t, before = [], a, "send"
    for s, e, name in sorted(intervals):
        if e <= t or s >= b:
            continue
        if s > t:
            gaps.append((t, s, before, name))
        if e > t:
            t, before = e, name
        if t >= b:
            break
    if t < b:
        gaps.append((t, b, before, "done"))
    return gaps


def _op_of_call(dispatch: dict, call):
    d = dispatch.get(call.counts.get("rid"))
    return d.trace_id if d is not None else None


def coverage(ctx, w: spans.Window) -> dict:
    """Uncovered share of each window suggest op's send-to-done interval.

    A client op is matched to its server op by thread: its SuggestTrials
    call is the first one that started after the op was sent on a thread
    whose last call before the op's end waited on the same server op."""
    dispatch = {r.counts.get("rid"): r for r in w.spans
                if r.name == "vizier.rpc.dispatch"}
    suggests = sorted((r for r in w.spans if r.name == "vizier.rpc.call"
                       and r.counts.get("method") == "SuggestTrials"),
                      key=lambda r: r.start_ns)
    calls_on: dict = {}
    for r in w.spans:
        if r.name == "vizier.rpc.call":
            calls_on.setdefault(r.thread_id, []).append(r)
    for calls in calls_on.values():
        calls.sort(key=lambda r: r.end_ns)
    by_trace: dict = {}
    for r in w.spans:
        if _waits(r) or r.trace_id is None:
            continue
        for tid in (r.trace_id if isinstance(r.trace_id, tuple)
                    else (r.trace_id,)):
            by_trace.setdefault(tid, []).append((r.start_ns, r.end_ns, r.name))

    def server_op(sent: int, done: int):
        for c in suggests:
            if c.start_ns < sent:
                continue
            if c.start_ns > done:
                return None
            op = _op_of_call(dispatch, c)
            last = [x for x in calls_on[c.thread_id]
                    if c.start_ns <= x.end_ns <= done]
            if op is not None and last and _op_of_call(
                    dispatch, last[-1]) == op:
                return op
        return None

    rows = []
    for rec in ctx.suggest_ops():
        sent, done = int(rec.sent * 1e9), int(rec.done * 1e9)
        if not w.t0_ns <= sent <= w.t1_ns:
            continue
        op = server_op(sent, done)
        if op is None:
            continue
        gaps = _gaps(by_trace.get(op, []), sent, done)
        rows.append((done - sent, sum(g[1] - g[0] for g in gaps)
                     / (done - sent), sent, gaps))
    if not rows:
        return {}
    rows.sort(key=lambda r: r[0])
    shares = sorted(r[1] for r in rows)
    q = statistics.quantiles(shares, n=10) if len(shares) > 1 else shares * 9
    wall, share, sent, gaps = rows[len(rows) // 2]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:3]
    return {"ops": len(rows), "median_op_ms": wall * 1e-6,
            "median_op_uncovered": share,
            "median_op_gaps": [[(a - sent) * 1e-6, (b - a) * 1e-6, x, y]
                               for a, b, x, y in longest],
            "uncovered_p10_p50_p90": [q[0], q[4], q[8]],
            "uncovered_max": shares[-1]}


def cpu_by_span(w: spans.Window) -> dict:
    """Wall and thread-CPU ms per served op, by span name."""
    wall, cpu, n = {}, {}, {}
    for r in w.spans:
        if r.cpu_ns is None or not w.t0_ns <= r.start_ns <= w.t1_ns:
            continue
        wall[r.name] = wall.get(r.name, 0) + r.wall_ns
        cpu[r.name] = cpu.get(r.name, 0) + r.cpu_ns
        n[r.name] = n.get(r.name, 0) + 1
    served = w.served_ops()
    return {name: {"spans": n[name], "wall_ms": wall[name] * 1e-6 / served,
                   "cpu_ms": cpu[name] * 1e-6 / served,
                   "cpu_share": cpu[name] / wall[name] if wall[name] else None}
            for name in sorted(wall, key=lambda k: -wall[k])}


def profiler_on_off(ctx, w: spans.Window) -> dict:
    """Worker-batch wall ms per op, profiler recording against stopped."""
    on_until = int(ctx.trace_span[1] * 1e9)
    out = {}
    for key, batches in (
            ("on", [r for r in w.batches if r.start_ns <= on_until]),
            ("off", [r for r in w.batches if r.start_ns > on_until])):
        ops = sum(int(r.counts.get("ops", 0)) for r in batches)
        out[key] = {"ops": ops, "batch_ms_per_op": (
            sum(r.wall_ns for r in batches) * 1e-6 / ops if ops else None)}
    return out


def span_cost_ns() -> dict:
    """ns per span, and per call of its two costliest parts (profiler off)."""
    import jax

    from repro import tracing

    def per_call(fn):
        t = time.perf_counter_ns()
        for _ in range(COST_SPANS):
            fn()
        return (time.perf_counter_ns() - t) / COST_SPANS

    def one_span():
        with tracing.span("vizier.cost", ops=1):
            pass

    def one_annotation():
        with jax.profiler.TraceAnnotation("vizier.cost", ops=1):
            pass

    parts = {"thread_time_ns": per_call(time.thread_time_ns),
             "annotation": per_call(one_annotation)}
    off = per_call(one_span)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        on = per_call(one_span)
    finally:
        jax.profiler.stop_trace()
    return dict(parts, profiler_off=off, profiler_on=on)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--out")
    args = parser.parse_args()
    cell = cells.load_cell(args.workload)
    seconds = args.seconds or cells.benchmark()["run_seconds"]
    run.CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    device = run.device_info(cell.chips)

    # the run deletes its trace directory: keep the trace it reduces
    traces = []
    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_keep(trace_dir, window_s):
        from jax.profiler import ProfileData

        red = reduce_dir(trace_dir, window_s)
        traces.append((ProfileData.from_file(
            trace_reduce.find_xplane(trace_dir)), red))
        return red

    trace_reduce.reduce_dir = reduce_and_keep
    kept = []
    result = run.run_cell(cell, args.seed, seconds, True, device,
                          contexts=kept)
    run_s = time.perf_counter() - run.T_PROC
    ctx = kept[0]
    w = spans.window(ctx)
    report = {"workload": args.workload, "seed": args.seed, "result": result,
              "run_s": run_s}
    bounds = None
    if traces:
        from repro import tracing

        pdata, red = traces[0]
        bounds = idle_split.trace_window(pdata, tracing.snapshot(),
                                         ctx.trace_span)
    if bounds is not None:
        s = idle_split.split(pdata, *bounds)
        report["idle_split"] = dict(
            s.as_dict(), by_span=s.by_span[:8],
            reduction_idle_s=red.window_s - red.busy_s,
            reduction_window_s=red.window_s)
    if w is not None:
        served = w.served_ops()
        ops = set(w.served_op_names())
        traced = [r for r in w.spans if r.trace_id is not None and (
            r.trace_id in ops if isinstance(r.trace_id, str)
            else any(t in ops for t in r.trace_id))]
        report["spans_per_op"] = {"all": len(w.spans) / served,
                                  "op_traced": len(traced) / served,
                                  "served_ops": served}
        report["coverage"] = coverage(ctx, w)
        report["cpu_by_span"] = cpu_by_span(w)
        report["profiler_on_off"] = profiler_on_off(ctx, w)
    report["span_ns"] = span_cost_ns()
    line = json.dumps(report)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
