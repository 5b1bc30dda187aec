"""Retrace sentinel: one facade over both engines' jit-cache accounting.

Every compiled-engine execution reports here via :func:`engine_run` with a
*fingerprint* — the static engine key plus the vmap batch width, i.e. the
exact unit the jit cache compiles — and whether that call traced (compiled)
or hit the cache.  The accounting is always on (two dict operations per
engine call, against multi-millisecond engine work), so the sentinel works
with the ledger disabled.

:func:`assert_no_retrace` is the public invariant: inside the block, no
fingerprint that was already warm at entry may compile again.  Fresh
fingerprints (new static structure, new batch width) compile freely — cold
benchmark phases pass — but a warm engine silently re-tracing (a runtime
scalar accidentally promoted to static, a dropped cache) raises
:class:`RetraceError` with the offending fingerprints.

:func:`reset` is the one blessed way to throw compiled state away (it also
forgets the matching run history, so deliberate cold re-timing inside an
``assert_no_retrace`` block does not false-positive).
"""

from __future__ import annotations

from typing import Dict, List, Optional


class RetraceError(AssertionError):
    """A warm engine re-compiled inside an ``assert_no_retrace`` block."""


class _Stat:
    __slots__ = ("runs", "compiles")

    def __init__(self):
        self.runs = 0
        self.compiles = 0


_RUNS: Dict[str, _Stat] = {}


def engine_run(fingerprint: str, compiled: bool) -> None:
    """Account one engine execution (called by the engines themselves)."""
    s = _RUNS.get(fingerprint)
    if s is None:
        s = _RUNS[fingerprint] = _Stat()
    s.runs += 1
    if compiled:
        s.compiles += 1


def engine_runs() -> Dict[str, Dict[str, int]]:
    """Per-fingerprint run/compile counts since the last :func:`reset`."""
    return {fp: {"runs": s.runs, "compiles": s.compiles}
            for fp, s in _RUNS.items()}


def _forget(prefix: str) -> None:
    for fp in [fp for fp in _RUNS if fp.startswith(prefix)]:
        del _RUNS[fp]


def cache_stats() -> Dict[str, int]:
    """One view over both engines' jit caches and the run accounting:

    ``hms_engines``                        compiled HMS entries
    ``hms_traces``                         total HMS Python traces
    ``um_engines`` / ``um_traces``         same for the paging engine
    ``um_results_cached``                  memoized UM results (all traces)
    ``um_lanes_run``                       cumulative engine lanes executed
    ``engine_runs`` / ``engine_compiles``  sentinel totals since reset()
    """
    from repro.core import simulator as _sim
    from repro.um import engine as _um

    return {
        "hms_engines": len(_sim._ENGINE_CACHE),
        "hms_traces": sum(_sim._TRACE_COUNTS.values()),
        "um_engines": len(_um._UM_ENGINE_CACHE),
        "um_traces": sum(_um._UM_TRACE_COUNTS.values()),
        "um_results_cached": sum(len(d) for d in
                                 _um._RESULT_CACHE.values()),
        "um_lanes_run": _um._LANES_RUN,
        "engine_runs": sum(s.runs for s in _RUNS.values()),
        "engine_compiles": sum(s.compiles for s in _RUNS.values()),
    }


def reset(*, hms: bool = True, um: bool = True,
          keep_compiled: bool = False) -> None:
    """Throw engine state away, on purpose.

    ``keep_compiled=True`` drops only memoized results (today: the UM
    per-trace result cache) and keeps compiled engines — the warm
    re-timing split benchmarks use.  Otherwise compiled engines, trace
    counts, and the matching sentinel history go too, so the recompiles
    that follow are *expected* and ``assert_no_retrace`` stays quiet.
    ``hms=False`` / ``um=False`` scope the reset to one engine.
    """
    from repro.core import simulator as _sim
    from repro.um import engine as _um

    if um:
        _um._RESULT_CACHE.clear()
        if not keep_compiled:
            _um._UM_ENGINE_CACHE.clear()
            _um._UM_TRACE_COUNTS.clear()
            _forget("um:")
    if hms and not keep_compiled:
        _sim._ENGINE_CACHE.clear()
        _sim._TRACE_COUNTS.clear()
        _forget("hms:")


class assert_no_retrace:
    """Context manager asserting no warm engine recompiles inside the block.

    Fingerprints first seen inside the block may compile (once or many
    times — a cold sweep is free to build new engines); fingerprints that
    had already run before entry must be served from the jit cache.  Use
    :func:`reset` for deliberate cache invalidation — it forgets the
    history this check compares against.
    """

    def __enter__(self) -> "assert_no_retrace":
        self._snap = {fp: s.compiles for fp, s in _RUNS.items()}
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            return False
        bad: List[str] = []
        for fp, compiles in self._snap.items():
            s = _RUNS.get(fp)
            if s is not None and s.compiles > compiles:
                bad.append(f"{fp} (+{s.compiles - compiles})")
        if bad:
            raise RetraceError(
                "engines recompiled while warm: " + "; ".join(sorted(bad)))
        return False

    # convenience: how many compile events (warm or cold) the block saw
    def compiles_during(self) -> Optional[int]:
        total = sum(s.compiles for s in _RUNS.values())
        return total - sum(self._snap.values())
