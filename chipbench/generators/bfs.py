"""The ``bfs`` trace generator of the benchmark's traffic mixes.

``gen_bfs`` is a copy of the program's ``gen_bfs`` and ``_powerlaw_nodes``
(``repro.core.traces``), kept here so that a change to the program cannot
move the benchmark's inputs.  ``generate`` takes the ``params`` of a
traffic file and a seed, and returns the request stream as plain arrays:
``col`` (int64 global 32 B column index) and ``is_write`` (bool).

The set of node visits and of write flags is the one ``gen_bfs`` draws
from the traffic's ``base_seed``; ``seed`` draws the order in which the
visits arrive (a BFS frontier's order is arbitrary) and the order of the
write flags.  So every seed gives the same multiset of addresses — the
same per-set loads, hence the same shard plan and engine shapes — and a
different request stream for the simulator to get right.
"""

from __future__ import annotations

import numpy as np

COLUMN_BYTES = 32


def _powerlaw_nodes(rng, n_nodes, n, alpha=1.1):
    """Zipf-ish node sampling typical of scale-free graph frontiers."""
    ranks = rng.zipf(alpha, size=4 * n)
    ranks = ranks[ranks <= n_nodes][:n]
    while ranks.shape[0] < n:
        extra = rng.zipf(alpha, size=2 * n)
        extra = extra[extra <= n_nodes]
        ranks = np.concatenate([ranks, extra])[:n]
    # Pseudo-random node permutation via an affine map (avoids a huge perm).
    a = 2 * rng.integers(1, n_nodes // 2, dtype=np.int64) + 1
    b = rng.integers(0, n_nodes, dtype=np.int64)
    return (a * ranks.astype(np.int64) + b) % n_nodes


def gen_bfs(seed, footprint, n, write_frac, burst, alpha):
    """BFS: random frontier expansion over a CSR graph.

    Reads of a node's adjacency list are short sequential bursts at a
    random base; visited[] updates are sparse random writes."""
    rng = np.random.default_rng(seed)
    total = footprint // COLUMN_BYTES
    n_nodes = total // burst
    nodes = _powerlaw_nodes(rng, n_nodes, -(-n // burst), alpha)
    base = nodes * burst
    col = (base[:, None] + np.arange(burst)[None, :]).reshape(-1) % total
    col = col[:n]
    wr = rng.random(col.shape[0]) < write_frac
    return col.astype(np.int64), wr


def generate(seed, base_seed, footprint, n, write_frac, burst, alpha):
    col, wr = gen_bfs(base_seed, footprint, n, write_frac, burst, alpha)
    rng = np.random.default_rng(int(seed) % 2**64)
    whole = n // burst                   # a cut last visit stays last
    visits = col[:whole * burst].reshape(whole, burst)
    col = np.concatenate([visits[rng.permutation(whole)].reshape(-1),
                          col[whole * burst:]])
    return col, wr[rng.permutation(n)]
