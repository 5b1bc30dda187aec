"""SCM-aware DRAM-cache bypass policy (§III-C), as pure JAX functions.

The policy collapses three access dimensions into one score:

  * spatial locality   — columns accessed per row activation amortize SCM's
                         long tRCD (Eq. 1 numerator is divided by them);
  * write intensity    — writes add the tWR gap between SCM and DRAM;
  * hotness            — per-page activation counters multiply the penalty
                         into the *DRAM-affinity* score.

Scores are discretized to ``n_levels`` between 0 and the maximum observed so
far, compared first against a discretized moving average (level-1 filter, no
DRAM traffic), then against the victim line's stored affinity level (level-2,
one metadata access), with probabilistic decay ``p_dec`` of the victim's
level when the fill is rejected.
"""

from __future__ import annotations

import jax.numpy as jnp

from .timing import DeviceTiming


def scm_penalty_score(ncols, has_write, dram: DeviceTiming, scm: DeviceTiming,
                      xp=jnp):
    """Eq. 1, using the static pre-computation of §III-C1.

    Because column-access latency is identical between SCM and DRAM, the
    numerator collapses to (tRCD_scm - tRCD_dram) for read-only activations
    plus (tWR_scm - tWR_dram) when the activation includes a write.

    ``xp`` is the array module: ``jnp`` in the frozen reference scan,
    ``numpy`` where the simulator evaluates the policy on the host.
    """
    ncols = xp.maximum(xp.asarray(ncols, dtype=xp.float32), 1.0)
    num = (scm.rcd - dram.rcd) + xp.asarray(has_write, xp.float32) * (
        scm.wr - dram.wr
    )
    return num / ncols


def discretize(score, max_seen, n_levels: int, xp=jnp):
    """Discretize ``score`` into ``n_levels`` fixed intervals of [0, max]."""
    max_seen = xp.maximum(xp.asarray(max_seen, xp.float32), 1e-6)
    lvl = xp.floor(
        xp.asarray(score, xp.float32) / max_seen * n_levels
    ).astype(xp.int32)
    return xp.clip(lvl, 0, n_levels - 1)


def ema_update(avg, value, weight: float):
    """Moving average; a new value has weight ``weight`` (1% in the paper)."""
    return (1.0 - weight) * avg + weight * value


def affinity_score(penalty, act_count, use_counter: bool, xp=jnp):
    """DRAM-affinity score = SCM-penalty x per-page activation counter.

    §IV-A disables the counter "for simplicity" (constant 1); we keep both
    modes behind ``use_counter``.
    """
    act = xp.asarray(act_count, xp.float32)
    return penalty * xp.where(use_counter, xp.maximum(act, 1.0), 1.0)


def p_dec(act_count, max_act, xp=jnp):
    """Victim decay probability: page activations / max activations seen."""
    max_act = xp.maximum(xp.asarray(max_act, xp.float32), 1.0)
    return xp.clip(xp.asarray(act_count, xp.float32) / max_act, 0.0, 1.0)


def xorshift32(state):
    """Cheap stateless PRNG step for the scan-carried decay dice."""
    state = jnp.asarray(state, jnp.uint32)
    state = state ^ (state << jnp.uint32(13))
    state = state ^ (state >> jnp.uint32(17))
    state = state ^ (state << jnp.uint32(5))
    return state


def uniform01(state):
    """Map a uint32 PRNG state to [0, 1)."""
    return state.astype(jnp.float32) * (1.0 / 4294967296.0)
