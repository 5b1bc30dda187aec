"""Host + repo identity for run records and benchmark artifacts.

``git_info`` answers the question cross-run comparison could not answer
before this subsystem: *which commit produced this artifact, and was the
working tree clean when it did?*  It is resolved once per process (the
ledger stamps every record with it) and degrades to ``None`` outside a git
checkout — e.g. an installed wheel — rather than failing.
"""

from __future__ import annotations

import functools
import os
import subprocess
from typing import Dict, Optional


@functools.lru_cache(maxsize=1)
def git_info() -> Dict[str, Optional[object]]:
    """``{"git_sha": <40-hex or None>, "git_dirty": <bool or None>}`` for
    the checkout this package runs from."""
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(
            ["git", "-C", here, "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
        if sha is None:
            return {"git_sha": None, "git_dirty": None}
        dirty = bool(subprocess.run(
            ["git", "-C", here, "status", "--porcelain"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip())
        return {"git_sha": sha, "git_dirty": dirty}
    except (OSError, subprocess.SubprocessError):
        return {"git_sha": None, "git_dirty": None}


@functools.lru_cache(maxsize=1)
def host_metadata() -> Dict[str, object]:
    """Process-stable host descriptor: platform, Python/JAX versions, the
    default device as JAX reports it (platform, kind, count), and the git
    identity.  Benchmark artifacts extend this with engine tuning
    constants (``benchmarks.common.host_metadata``)."""
    import platform

    import jax

    devices = jax.devices()
    return {
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "jax_backend": jax.default_backend(),
        "device_platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        **git_info(),
    }
