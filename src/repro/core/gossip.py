"""Decentralized gossip transport: neighbor averaging instead of a server.

The paper's Algorithm 1 assumes a star topology — every worker commits its
``(delta_alpha, delta_b)`` to one parameter server that owns the coupled
state ``W = X diag(alpha) Sigma / lam``.  arXiv:2410.03403 (Distributed
Networked Multi-task Learning) analyzes the serverless regime the ROADMAP
names as the top open item: each node keeps a *replica* of the shared
state and averages it with graph neighbors under a doubly-stochastic
mixing matrix.  This module is that regime, shaped so the rest of the
stack cannot tell the difference:

  * ``GossipTransport`` registers as the ``gossip`` member of the
    ``core.transport`` registry and exposes the exact
    ``gate/snapshot/commit/install_sigma`` surface — all three engines,
    the cross-transport parity tests, and the serving fleet's model
    subscribers work unchanged.
  * Topologies: ``ring`` / ``torus`` / ``complete`` / an explicit
    adjacency matrix (``AsyncOptions.topology``); the mixing matrix is the
    Metropolis–Hastings weighting, symmetric and doubly stochastic by
    construction, with ``spectral_gap`` introspection (the 1 - |lambda_2|
    quantity that rates how fast consensus contracts).

Protocol (why it matches the server member)
-------------------------------------------
Node ``g`` owns task rows ``rows_g`` and holds a full replica
``W_nodes[g]`` of the coupled state.  A commit applies the **G-scaled**
local update

    W_nodes[g] += G * Sigma[:, rows_g] @ delta_b_g / lam

so the replica *mean* moves by exactly the server's update.  At every
round boundary (SSP floor advance) one synchronous gossip exchange runs:

    W_nodes <- M @ W_nodes

and because M is doubly stochastic the exchange preserves the replica
mean exactly.  Invariant: ``mean_g W_nodes[g]`` equals the server's ``W``
trajectory at every round boundary (up to float association).  On a
complete graph the Metropolis weights degenerate to uniform ``1/G``, one
exchange reaches exact consensus, and every node serves the same boundary
state the ``threaded`` server would — the acceptance anchor (final
objective within 1e-5 of ``threaded`` on the parity fixture).  On sparser
graphs nodes solve against *locally averaged* state whose disagreement
contracts at rate ``1 - spectral_gap`` per exchange — the bounded
perturbation of the paper's fixed point that arXiv:1609.09563's analysis
tolerates.

Sigma stays driver-installed (the Omega-step is a centralized spectral
update over ``w_true()``, the replica mean); a Sigma install recomputes
``W`` from the exact global dual state and broadcasts it, resetting
consensus.  Decentralizing the Omega-step itself is a ROADMAP follow-up.

Wire accounting: the neighbor exchanges are the gossip wire.  Each node
ships its (codec-encoded, error-feedback-corrected — ``core.wire``)
replica to each neighbor per exchange; ``wire_stats['mix_bytes']``
/ ``raw_mix_bytes`` make the compression measurable, and under lossy
codecs each node keeps its own replica exact (only neighbor contributions
are quantized).  Per-edge staleness (``|completed[g] - completed[h]|`` at
each exchange) lands in the event history (``e_src/e_dst/e_stal/e_tick``)
and is summarized by ``convergence.staleness_summary``.
"""
from __future__ import annotations

import logging
import time
from typing import List, Tuple, Union

import jax.numpy as jnp
import numpy as np

from .sigma_view import SigmaView
from .transport import (
    CommitReceipt,
    Snapshot,
    ThreadedTransport,
    TransportSpec,
    record_receipt,
    register_transport,
)
from .wire import ErrorFeedback
from ..obs.metrics import get_registry
from ..obs.trace import span

logger = logging.getLogger(__name__)

__all__ = [
    "GossipTransport",
    "build_adjacency",
    "mixing_matrix",
    "spectral_gap",
]

Topology = Union[str, tuple, list, np.ndarray]


# ---------------------------------------------------------------------------
# topology -> adjacency -> mixing matrix
# ---------------------------------------------------------------------------
def _torus_sides(G: int) -> Tuple[int, int]:
    """Largest a <= sqrt(G) with a | G; (a, G // a).  a == 1 degenerates
    to a ring (every G has the trivial divisor)."""
    a = 1
    for c in range(2, int(np.sqrt(G)) + 1):
        if G % c == 0:
            a = c
    return a, G // a


def build_adjacency(topology: Topology, G: int) -> np.ndarray:
    """(G, G) symmetric 0/1 adjacency, zero diagonal, connected.

    ``ring``     node i <-> i +- 1 (mod G).
    ``torus``    a x b wrap-around grid with a the largest divisor of G
                 not above sqrt(G); degenerates to a ring for prime G.
    ``complete`` all pairs — the server-equivalent anchor.
    explicit     any square 0/1 array-like; symmetrized view is checked
                 for symmetry, zero diagonal, and connectivity.
    """
    if G < 1:
        raise ValueError(f"need G >= 1 nodes, got {G}")
    adj = np.zeros((G, G), dtype=np.int64)
    if isinstance(topology, str):
        if topology == "complete":
            adj[:] = 1
            np.fill_diagonal(adj, 0)
        elif topology == "ring":
            for i in range(G):
                adj[i, (i + 1) % G] = adj[(i + 1) % G, i] = 1
            np.fill_diagonal(adj, 0)  # G <= 2 self-loops
        elif topology == "torus":
            a, b = _torus_sides(G)
            if a == 1:
                return build_adjacency("ring", G)
            for i in range(G):
                r, c = divmod(i, b)
                for rr, cc in (
                    (r, (c + 1) % b),
                    (r, (c - 1) % b),
                    ((r + 1) % a, c),
                    ((r - 1) % a, c),
                ):
                    j = rr * b + cc
                    if j != i:
                        adj[i, j] = adj[j, i] = 1
        else:
            raise ValueError(
                f"unknown gossip topology {topology!r}; have "
                "'ring' | 'torus' | 'complete' | explicit adjacency matrix"
            )
    else:
        A = np.asarray(topology)
        if A.shape != (G, G):
            raise ValueError(
                f"explicit adjacency must be ({G}, {G}) for {G} workers; "
                f"got shape {A.shape}"
            )
        if not np.array_equal(A, A.T):
            raise ValueError("explicit adjacency must be symmetric")
        if not np.all((A == 0) | (A == 1)):
            raise ValueError("explicit adjacency entries must be 0/1")
        if np.any(np.diag(A) != 0):
            raise ValueError("explicit adjacency must have a zero diagonal")
        adj = A.astype(np.int64)
    if G > 1:
        # BFS connectivity: gossip on a disconnected graph never reaches
        # consensus, so fail loudly at setup, not as silent divergence
        seen = {0}
        frontier = [0]
        while frontier:
            i = frontier.pop()
            for j in np.flatnonzero(adj[i]):
                if int(j) not in seen:
                    seen.add(int(j))
                    frontier.append(int(j))
        if len(seen) != G:
            raise ValueError(
                f"gossip topology is disconnected: reachable component "
                f"from node 0 has {len(seen)} of {G} nodes"
            )
    return adj


def mixing_matrix(adj: np.ndarray) -> np.ndarray:
    """Metropolis–Hastings weights: symmetric, doubly stochastic.

    M[g, h] = 1 / (1 + max(deg_g, deg_h)) on edges, diagonal takes the
    slack.  Doubly stochastic => the gossip exchange preserves the replica
    mean exactly; symmetric => real eigenvalues, so the spectral gap below
    is well defined.  On a complete graph every weight is exactly 1/G.
    """
    G = adj.shape[0]
    deg = adj.sum(axis=1)
    M = np.zeros((G, G), dtype=np.float64)
    for g in range(G):
        for h in np.flatnonzero(adj[g]):
            M[g, h] = 1.0 / (1.0 + max(deg[g], deg[h]))
    np.fill_diagonal(M, 1.0 - M.sum(axis=1))
    return M


def spectral_gap(M: np.ndarray) -> float:
    """1 - |lambda_2(M)|: the per-exchange contraction rate of the
    disagreement (consensus error shrinks by (1 - gap) each exchange).
    1.0 for a complete graph (one exchange = exact consensus), -> 0 for
    long rings."""
    ev = np.sort(np.abs(np.linalg.eigvalsh(M)))[::-1]
    if ev.size < 2:
        return 1.0
    return float(1.0 - ev[1])


# ---------------------------------------------------------------------------
# the transport member
# ---------------------------------------------------------------------------
class GossipTransport(ThreadedTransport):
    """Serverless neighbor-averaging transport (see module docstring).

    Subclasses the threaded member for its worker fan-out, SSP gate, and
    tau machinery; replaces the shared server ``W`` with per-node replicas
    ``W_nodes`` mixed at every round boundary.
    """

    name = "gossip"

    def setup(self, cfg, raw, *, mesh, axes, reg, init, track, options):
        super().setup(
            cfg, raw, mesh=mesh, axes=axes, reg=reg, init=init, track=track,
            options=options,
        )
        topology = options.topology
        self.adjacency = build_adjacency(topology, self.G)
        self.M = mixing_matrix(self.adjacency)
        self.spectral_gap = spectral_gap(self.M)
        self._deg = self.adjacency.sum(axis=1).astype(int)
        self._edges: List[Tuple[int, int]] = [
            (g, h)
            for g in range(self.G)
            for h in range(g + 1, self.G)
            if self.adjacency[g, h]
        ]
        dtype = self.W.dtype
        # split M into diagonal + off-diagonal: a node's own replica never
        # rides the wire, so under lossy codecs only the neighbor terms
        # see quantization
        self._M_diag = jnp.asarray(np.diag(self.M), dtype)
        self._M_off = jnp.asarray(self.M - np.diag(np.diag(self.M)), dtype)
        self._mix_ef = ErrorFeedback(self.codec)
        self.W_nodes = jnp.asarray(
            jnp.broadcast_to(self.W, (self.G,) + self.W.shape)
        )
        self._boundary_nodes = self.W_nodes
        # gossip-only event-history keys (per-edge staleness at each
        # exchange); staleness_summary picks them up when present
        for k in ("e_src", "e_dst", "e_stal", "e_tick"):
            self.hist[k] = []
        self.wire_stats["topology"] = (
            topology if isinstance(topology, str) else "explicit"
        )
        self.wire_stats["spectral_gap"] = self.spectral_gap
        logger.info(
            "gossip transport: %d nodes, topology %s (%d edges), "
            "spectral gap %.4f, codec %s",
            self.G,
            self.wire_stats["topology"],
            len(self._edges),
            self.spectral_gap,
            self.codec.name,
        )
        get_registry().gauge(
            "repro_gossip_spectral_gap",
            "1 - |lambda_2| of the mixing matrix (consensus contraction "
            "per exchange)",
            labels=("topology",),
        ).set(self.spectral_gap, topology=self.wire_stats["topology"])

    # -- consensus ----------------------------------------------------------
    def _consensus_w(self):
        return jnp.mean(self.W_nodes, axis=0)

    def _mix(self, tick: float) -> None:
        """One synchronous gossip exchange (called under the lock at a
        round boundary): record per-edge staleness, ship each replica to
        its neighbors through the codec, contract with M."""
        with span(
            "mix",
            cat="gossip",
            n_edges=len(self._edges),
            exchange=self.wire_stats["n_exchanges"],
        ):
            self._mix_locked(tick)

    def _mix_locked(self, tick: float) -> None:
        for g, h in self._edges:
            self.hist["e_src"].append(g)
            self.hist["e_dst"].append(h)
            self.hist["e_stal"].append(
                abs(self.completed[g] - self.completed[h])
            )
            self.hist["e_tick"].append(tick)
        per_node_raw = int(
            np.prod(self.W_nodes.shape[1:])
        ) * self.W_nodes.dtype.itemsize
        if self.codec.name == "none" or not self._edges:
            q = self.W_nodes
            enc_nbytes = [per_node_raw] * self.G
        else:
            qs, enc_nbytes = [], []
            for g in range(self.G):
                enc = self._mix_ef.encode(g, np.asarray(self.W_nodes[g]))
                qs.append(self.codec.decode(enc))
                enc_nbytes.append(enc.nbytes)
            q = jnp.asarray(np.stack(qs), self.W_nodes.dtype)
        self.wire_stats["n_exchanges"] += 1
        self.wire_stats["mix_bytes"] += sum(
            enc_nbytes[g] * int(self._deg[g]) for g in range(self.G)
        )
        self.wire_stats["raw_mix_bytes"] += per_node_raw * int(
            self._deg.sum()
        )
        self.W_nodes = (
            self._M_diag[:, None, None] * self.W_nodes
            + jnp.einsum("gh,hmd->gmd", self._M_off, q)
        )
        self.W = self._consensus_w()

    # -- protocol overrides (all under the server condition variable) -------
    def snapshot(self, worker):
        with span("snapshot", cat="transport", worker=worker), self.cond:
            self._check_abort()
            self._maybe_install(worker)
            rows = self._rows(worker)
            self._snap_version[worker] = self._boundary_version
            self._snap_lag[worker] = self.completed[worker] - min(
                self.completed
            )
            _W_b, sigma_b = self._boundary
            W_b = self._boundary_nodes[worker]  # node-LOCAL replica
            if isinstance(sigma_b, SigmaView):
                return Snapshot(
                    W_rows=W_b[rows],
                    sigma_rows=None,
                    alpha_rows=self.alpha[rows],
                    version=self._boundary_version,
                    sigma_diag=sigma_b.diag()[rows],
                )
            return Snapshot(
                W_rows=W_b[rows],
                sigma_rows=sigma_b[rows],
                alpha_rows=self.alpha[rows],
                version=self._boundary_version,
            )

    def commit(self, worker, rnd, delta):
        dalpha, db = delta
        with span("commit", cat="transport", worker=worker, round=rnd), self.cond:
            self._check_abort()
            self._maybe_install(worker)
            cfg = self.cfg
            rows = self._rows(worker)
            # alpha rows are node-owned dual state, identical to the server
            self.alpha = self.alpha.at[rows].add(cfg.eta * dalpha)
            if isinstance(self.sigma, SigmaView):
                upd = self.sigma.col_block_matvec(rows.start, db) / cfg.lam
            else:
                upd = (jnp.swapaxes(self.sigma[rows], 0, 1) @ db) / cfg.lam
            # G-scaled LOCAL apply: the replica mean moves by exactly the
            # server's W update (module docstring invariant)
            self.W_nodes = self.W_nodes.at[worker].add(self.G * upd)
            stal = self.commits_total - self._snap_version[worker]
            self.commits_total += 1
            self.commits_outer += 1
            floor_before = min(self.completed)
            self.completed[worker] += 1
            tick = time.monotonic() - self._t0
            if min(self.completed) > floor_before:
                # round boundary: one gossip exchange, then freeze the
                # per-node boundary replicas later starters will read
                self._mix(tick)
                self._boundary = (self.W, self.sigma)
                self._boundary_nodes = self.W_nodes
                self._boundary_version = self.commits_total
            receipt = CommitReceipt(
                worker=worker,
                round=self.p * self.R + rnd,
                staleness=stal,
                lag=self._snap_lag[worker],
                tick=tick,
                version=self.commits_total,
                tau=self.tau,
            )
            record_receipt(self.hist, receipt)
            self._after_commit_event(tick, self.alpha, self.sigma)
            self.cond.notify_all()
            return receipt

    def _install(self, sig, om):
        with span("install_sigma", cat="transport", transport=self.name):
            self.sigma, self.omega = sig, om
            # consensus reset: W is recomputed from the exact global dual
            # state and broadcast, so all replicas agree and any accumulated
            # quantization residual refers to dead state
            self.W = self._w_from_alpha(self.alpha, self.sigma)
            self.W_nodes = jnp.asarray(
                jnp.broadcast_to(self.W, (self.G,) + self.W.shape)
            )
            self._commit_ef.reset()
            self._mix_ef.reset()
            self._boundary = (self.W, self.sigma)
            self._boundary_nodes = self.W_nodes
            self._boundary_version = self.commits_total
            if isinstance(self.sigma, SigmaView):
                sigma_raw = self.sigma.unpad(self.raw.m)
            else:
                sigma_raw = self.sigma[: self.raw.m, : self.raw.m]
            self._notify_model(self.W[: self.raw.m, : self.raw.d], sigma_raw)

    # -- driver lifecycle ---------------------------------------------------
    def _begin_w_step(self, p):
        with self.cond:
            self.W = self._consensus_w()
            super()._begin_w_step(p)
            self._boundary_nodes = self.W_nodes

    def w_true(self):
        with self.lock:
            return self._consensus_w()[: self.raw.m]

    def result(self):
        with self.lock:
            self.W = self._consensus_w()
        return super().result()


register_transport(
    TransportSpec(
        name="gossip",
        description="serverless neighbor averaging over a configurable "
        "topology (ring/torus/complete/explicit): per-node W replicas, "
        "Metropolis mixing at round boundaries; complete graph matches "
        "the threaded server",
        needs_mesh=False,
        factory=GossipTransport,
    )
)
