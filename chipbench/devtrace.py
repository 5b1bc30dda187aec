"""Reduction of a JAX profiler trace to device busy time, module time and
labelled idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events as ``(name, start_ns, end_ns)`` on the profiler's
clock: device operations, device module executions (one per launch of a
compiled program) and the harness's host annotations.  The functions below
it are pure and are what the per-layer metrics read.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]            # (name, start_ns, end_ns)

# the harness wraps each study of a traced window in this annotation
STUDY = "chipbench.study"

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class DeviceTrace:
    ops: Dict[str, List[Event]]         # device plane -> operation events
    modules: Dict[str, List[Event]]     # device plane -> module events
    studies: List[Event]                # host annotations, in order


def _op_name(name: str) -> str:
    """An operation event is named by its whole HLO instruction
    (``%fusion.3 = (...) fusion(...)``); keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> DeviceTrace:
    """Read ``path``, an ``.xplane.pb`` (or one compressed with gzip)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    studies: List[Event] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (_op_name(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
                elif line.name == _MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                studies.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name == STUDY)
    studies.sort(key=lambda e: e[1])
    return DeviceTrace(ops=ops, modules=modules, studies=studies)


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of half-open intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, w0: int, w1: int) -> List[Tuple[int, int]]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def window(trace: DeviceTrace) -> Optional[Tuple[int, int]]:
    """From the start of the first traced study to the end of the last."""
    if not trace.studies:
        return None
    return trace.studies[0][1], max(e for _, _, e in trace.studies)


def busy(trace: DeviceTrace, w0: int, w1: int) -> Dict[str, List]:
    """Per device plane, the merged intervals in which an operation ran
    (modules where a plane records no operations)."""
    out = {}
    for plane in sorted(set(trace.ops) | set(trace.modules)):
        evs = trace.ops.get(plane) or trace.modules.get(plane, [])
        out[plane] = merge(clip([(s, e) for _, s, e in evs], w0, w1))
    return out


def busy_seconds(trace: DeviceTrace, w0: int, w1: int) -> float:
    """Busy time averaged over the device planes."""
    per = busy(trace, w0, w1)
    if not per:
        return 0.0
    return sum(sum(e - s for s, e in iv) for iv in per.values()) \
        / len(per) / 1e9


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def module_seconds(trace: DeviceTrace, pattern: str, w0: int,
                   w1: int) -> float:
    """Device time of the modules whose name matches ``pattern`` (a
    regular expression, searched): the time in which an operation ran
    inside one of their executions, summed over the device planes.  An
    execution's span also holds time in which the device waited; where a
    plane records no operations, the spans are all there is."""
    rx = re.compile(pattern)
    per = busy(trace, w0, w1)
    total = 0
    for plane, evs in trace.modules.items():
        spans = merge(clip([(s, e) for n, s, e in evs if rx.search(n)],
                           w0, w1))
        total += _overlap(spans, per[plane])
    return total / 1e9


def top_ops(trace: DeviceTrace, w0: int, w1: int,
            k: int = 10) -> List[List]:
    """The ``k`` device operations that took the most time, as
    ``[name, seconds]`` averaged over the device planes."""
    tot: Dict[str, int] = {}
    planes = trace.ops or trace.modules
    for evs in planes.values():
        for n, s, e in evs:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                tot[n] = tot.get(n, 0) + (e - s)
    n_planes = max(1, len(planes))
    return [[n, t / n_planes / 1e9]
            for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: DeviceTrace, w0: int, w1: int,
              spans: Sequence[Event], k: int = 10) -> List[List]:
    """The ``k`` longest stretches of the window in which the first device
    ran nothing, as ``[label, seconds]``.  The label is the innermost
    host span (``spans``, on the profiler's clock) open at the middle of
    the gap; ``harness`` inside a study but outside every span,
    ``between_studies`` outside the studies."""
    per = busy(trace, w0, w1)
    if not per:
        return []
    iv = per[sorted(per)[0]]
    gaps, t = [], w0
    for s, e in iv + [(w1, w1)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) // 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        if open_:
            label = min(open_, key=lambda sp: sp[2] - sp[1])[0]
        elif any(st[1] <= mid < st[2] for st in trace.studies):
            label = "harness"
        else:
            label = "between_studies"
        out.append([label, (e - s) / 1e9])
    return out
