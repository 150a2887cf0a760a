"""One reduction from a profiler trace to the device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes (``ProfileData``, with
nothing but JAX) and keeps, for the TPU planes (``/device:TPU:<n>``):

* busy time: the union of the intervals of the ``XLA Ops`` line, averaged
  over the chips; the window is the traced window's host-clock length;
* device time per XLA module (``XLA Modules`` line), keyed by the jitted
  function's name with JAX's ``jit_`` prefix and any ``(<id>)`` suffix
  stripped, so a metric file can list the programs of its layer by name;
* each Pallas kernel call with its device duration and its operand and
  result shapes. An ``XLA Ops`` event is named by its HLO instruction text,
  ``%matern52_gram_pallas.1 = f32[1024,1024]{...} custom-call(f32[1024,128]
  {...} %pad.5, ...), custom_call_target="tpu_custom_call", ...``: the
  instruction name (without its ``.<n>``) is the jitted Pallas wrapper's
  name, which ``bench/roofline/<kernel>.py`` lists, and the shapes are the
  ones the kernel was launched with;
* for the breakdown: device time by (module, op kind), the kind being a
  custom call's target or the instruction's name without its index, most
  first; and the longest idle gaps, each labelled with the innermost
  ``bench.*`` host span open at its midpoint (``idle`` when none was).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench.lib import cells

_SUFFIX = re.compile(r"\(\d+\)$")
_SHAPE = re.compile(r"[a-z]+\d*\[([0-9,]*)\]")
_OP_INDEX = re.compile(r"\.\d+$")


def module_key(name: str) -> str:
    """``jit__fit_step(123)`` -> ``_fit_step``."""
    name = _SUFFIX.sub("", name.strip())
    return name[4:] if name.startswith("jit_") else name


def _shapes(text: str) -> List[Tuple[int, ...]]:
    return [tuple(int(v) for v in m.group(1).split(",") if v)
            for m in _SHAPE.finditer(text)]


def op_name(text: str) -> str:
    """``%tri_solve_pallas.7 = ...`` -> ``tri_solve_pallas``."""
    return _OP_INDEX.sub("", text.split(" = ", 1)[0].strip().lstrip("%"))


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_kind(text: str) -> str:
    """A short kind for an ``XLA Ops`` event: the custom call's target (a
    Pallas kernel by its wrapper's name), else the instruction name."""
    name = op_name(text)
    if name.startswith("custom-call"):
        m = _TARGET.search(text)
        return m.group(1) if m else name
    return re.sub(r"[._]\d+$", "", name)


def hlo_shapes(text: str):
    """(operand shapes, result shape) of one custom call's HLO text:
    ``%x = f32[8,4]{1,0} custom-call(f32[8,2]{1,0} %a, ...), ...``."""
    head, sep, rest = text.partition("custom-call(")
    if not sep:
        return None
    result = _shapes(head.split(" = ", 1)[-1])
    operands = _shapes(rest.split("), custom_call_target=", 1)[0])
    if not result or not operands:
        return None
    return operands, result[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


@dataclasses.dataclass
class KernelCall:
    seconds: float
    operands: List[Tuple[int, ...]]
    result: Tuple[int, ...]


@dataclasses.dataclass
class Reduction:
    busy_s: float
    window_s: float
    chips: int
    module_s: Dict[str, float]
    kernels: Dict[str, List[KernelCall]]
    unshaped: Dict[str, int]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def module_seconds(self, names: Iterable[str]) -> float:
        names = set(names)
        return sum(s for k, s in self.module_s.items() if k in names)

    def roofline_pct(self, kernel: str, peaks: dict) -> Optional[float]:
        calls = self.kernels.get(kernel)
        if not calls:
            return None
        mod = cells.roofline(kernel)
        least = 0.0
        for c in calls:
            flops, nbytes = mod.cost(c.operands, c.result)
            least += max(flops / peaks["flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
        spent = sum(c.seconds for c in calls)
        return 100.0 * least / spent if spent > 0 else None

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def reduce(pdata, window_s: float, top: int = 10) -> Reduction:
    kernel_of = {name: k for k in cells.roofline_kernels()
                 for name in cells.roofline(k).NAMES}
    busy_ns = 0
    chips = 0
    module_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    kernels: Dict[str, List[KernelCall]] = {}
    unshaped: Dict[str, int] = {}
    busy_all: List[Tuple[int, int]] = []
    host_spans: List[Tuple[int, int, str]] = []
    for plane in pdata.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns, ev.end_ns, ev.name))
            continue
        if not plane.name.startswith("/device:TPU:"):
            continue
        chips += 1
        modules: List[Tuple[int, int, str]] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    key = module_key(ev.name)
                    module_s[key] = module_s.get(key, 0.0) + ev.duration_ns * 1e-9
                    modules.append((ev.start_ns, ev.end_ns, key))
        modules.sort()
        starts = [m[0] for m in modules]
        parsed: Dict[str, tuple] = {}   # an op's text is parsed once
        for line in plane.lines:
            if line.name == "XLA Ops":
                intervals = []
                for ev in line.events:
                    name, start, dur = ev.name, ev.start_ns, ev.duration_ns
                    intervals.append((start, start + dur))
                    if name not in parsed:
                        kernel = (kernel_of.get(op_name(name))
                                  if "tpu_custom_call" in name else None)
                        parsed[name] = (op_kind(name), kernel,
                                        hlo_shapes(name) if kernel else None)
                    kind, kernel, shapes = parsed[name]
                    i = bisect.bisect_right(starts, start) - 1
                    module = (modules[i][2] if i >= 0
                              and modules[i][1] >= start else "?")
                    key = f"{module}:{kind}"
                    op_s[key] = op_s.get(key, 0.0) + dur * 1e-9
                    if kernel is None:
                        continue
                    if shapes is None:
                        unshaped[kernel] = unshaped.get(kernel, 0) + 1
                        continue
                    kernels.setdefault(kernel, []).append(KernelCall(
                        dur * 1e-9, shapes[0], shapes[1]))
                merged = _union(intervals)
                busy_ns += sum(e - s for s, e in merged)
                busy_all.extend(merged)
    merged = _union(busy_all)
    gaps = []
    for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
        if s1 > e0:
            mid = (e0 + s1) // 2
            label = "idle"
            best = None
            for hs, he, name in host_spans:
                if hs <= mid <= he and (best is None or he - hs < best):
                    best, label = he - hs, name
            gaps.append((label, (s1 - e0) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    tops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        busy_s=busy_ns * 1e-9 / max(chips, 1), window_s=window_s, chips=chips,
        module_s=module_s, kernels=kernels, unshaped=unshaped,
        top_ops=tops, idle_gaps=gaps[:top])


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: str, window_s: float) -> Reduction:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(trace_dir)), window_s)
