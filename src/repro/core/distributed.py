"""Distributed DMTRL — the paper's parameter-server W-step on a JAX mesh.

Mapping (docs/DESIGN.md §2):
  * ``data`` mesh axis  = the paper's workers; tasks are sharded over it.
  * ``model`` mesh axis = feature-dimension sharding (wide phi); the
    block-Gram solver psums its three d-contractions over this axis.
  * ``pod`` mesh axis   = intra-task sample partitioning (the paper's
    "further distribute data of one task over several local workers").
    Each pod owns a contiguous slice of every task's samples and the
    corresponding dual coordinates; delta_b is psum'ed over pods.

One communication round lowers to exactly:
    all_gather(delta_b, 'data')            -- the worker->server "send"
    local  dW = Sigma_rows @ dB / lambda   -- the server reduce, sharded
  (+ psum over 'pod' when present, + the block-Gram psums over 'model')
which is the paper's m*d-floats-per-round communication pattern.

Packed task storage (core/mtl_data.py:PackedMTLData) shards over the
``data`` axis alone: each worker holds its tasks' rows back to back,
padded only to the largest worker's row total, and its local round reads
task i's sample j at row offset_i + j (``make_local_solve``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..launch.mesh import require_auto_axes
from ..obs.metrics import get_registry
from ..obs.trace import span
from . import omega as omega_mod
from . import omega_regularizers as omega_reg
from .dmtrl import (
    DMTRLConfig,
    WarmStart,
    _rho_value,
    driver_program,
    make_data_fns,
)
from .losses import get_loss
from .mtl_data import MTLData, PackedMTLData, row_tasks, worker_layout
from .sigma_view import LowRankDiagSigma, SigmaView
from .solver_backends import get_backend

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    data: str = "data"  # tasks
    model: Optional[str] = None  # feature dim
    pod: Optional[str] = None  # intra-task samples


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    return mesh.shape[name] if name is not None else 1


def pad_to_multiple(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


def shard_mtl_data(
    data: MTLData, mesh: Mesh, axes: MeshAxes
) -> Tuple[MTLData, int, int]:
    """Pad task count / feature dim / sample dim and device_put with shardings.

    Returns (sharded data, m_padded, d_padded). Packed data goes through
    ``_shard_packed_data``.
    """
    require_auto_axes(mesh)
    if data.layout == "packed":
        return _shard_packed_data(data, mesh, axes)
    dsz = _axis_size(mesh, axes.data)
    msz = _axis_size(mesh, axes.model)
    psz = _axis_size(mesh, axes.pod)

    m_pad = pad_to_multiple(data.m, dsz)
    d_pad = pad_to_multiple(data.d, msz)
    n_pad = pad_to_multiple(data.n_max, psz)

    d = data.pad_tasks(m_pad)
    x = jnp.zeros((m_pad, n_pad, d_pad), d.x.dtype)
    x = x.at[:, : d.n_max, : d.d].set(d.x)
    y = jnp.zeros((m_pad, n_pad), d.y.dtype).at[:, : d.n_max].set(d.y)
    mask = jnp.zeros((m_pad, n_pad), d.mask.dtype).at[:, : d.n_max].set(d.mask)

    sx = NamedSharding(mesh, P(axes.data, axes.pod, axes.model))
    sv = NamedSharding(mesh, P(axes.data, axes.pod))
    sn = NamedSharding(mesh, P(axes.data))
    out = MTLData(
        jax.device_put(x, sx),
        jax.device_put(y, sv),
        jax.device_put(mask, sv),
        jax.device_put(d.n, sn),
    )
    return out, m_pad, d_pad


def _shard_packed_data(
    data: PackedMTLData, mesh: Mesh, axes: MeshAxes
) -> Tuple[PackedMTLData, int, int]:
    """Place packed rows on the mesh, tasks dealt to the ``data`` workers in
    contiguous ranges: each worker's tasks' rows back to back, padded only
    to the largest worker's row total (``mtl_data.worker_layout``). On one
    worker the rows are already in place, and nothing is copied.

    Returns (sharded data, m_padded, d).
    """
    if axes.model is not None or axes.pod is not None:
        raise ValueError(
            "packed task storage shards tasks over the data axis only; "
            f"got model={axes.model!r}, pod={axes.pod!r}"
        )
    workers = _axis_size(mesh, axes.data)
    x, y, mask, n = data.x, data.y, data.mask, data.n
    if workers > 1:
        dst, rows, n = worker_layout(np.asarray(data.n), workers)

        def place(a):
            out = jnp.zeros((workers * rows,) + a.shape[1:], a.dtype)
            return out.at[dst].set(a[: dst.shape[0]])

        x, y, mask = place(x), place(y), place(mask)
    sr = NamedSharding(mesh, P(axes.data, None))
    sv = NamedSharding(mesh, P(axes.data))
    out = PackedMTLData(
        jax.device_put(x, sr), jax.device_put(y, sv), jax.device_put(mask, sv),
        jax.device_put(n, sv), data.n_max, workers,
    )
    return out, out.m, data.d


def round_in_specs(axes: MeshAxes):
    """in_specs shared by the sync round and the async tick (first 7 args):
    (x, y, mask, n, alpha, W-like, sigma_rows)."""
    return (
        P(axes.data, axes.pod, axes.model),  # x
        P(axes.data, axes.pod),  # y
        P(axes.data, axes.pod),  # mask
        P(axes.data),  # n  (global per-task counts)
        P(axes.data, axes.pod),  # alpha
        P(axes.data, axes.model),  # W (or a stale snapshot of it)
        P(axes.data, None),  # sigma rows
    )


def round_out_specs(axes: MeshAxes):
    return (P(axes.data, axes.pod), P(axes.data, axes.model))


def make_local_solve(
    cfg: DMTRLConfig,
    mesh: Mesh,
    axes: MeshAxes,
    m: int,
    n_max: int,
    d: int,
    rho: float,
    sigma_input: str = "rows",
    packed: bool = False,
):
    """The worker half of one communication round, as a shard_map body.

    Returns ``local_solve(x, y, n, alpha, W_read, sigma_rows, key) ->
    (dalpha, db)`` where ``W_read`` is the (possibly stale) weight snapshot
    the worker solves against and ``db`` is this shard's delta_b rows
    (pod-psum'ed, eta/n-normalized) ready for the server reduce. The sync
    path passes the live ``W``; the async engine passes each worker group's
    bounded-staleness snapshot — the math is identical by construction.

    ``sigma_input`` names what the sigma argument carries: ``"rows"`` the
    dense (m_loc, m) owned Sigma rows (the historical layout — sigma_ii is
    extracted by global task id), ``"diag"`` just the local (m_loc,)
    diagonal (the structured-Sigma layout: workers never see full rows).

    With ``packed=True`` x is this worker's packed rows (R_loc, d), and y,
    alpha and dalpha are (R_loc,) in the rows' order (``_shard_packed_data``):
    task i's sample j is row offset_i + j, offsets the exclusive cumulative
    sum of n. The coordinates and keys are the padded layout's, so the
    iterates are too, one for one. Only backends with ``packed`` run there:
    the Pallas ones run over a task's padded rows.

    Every regime builds its solver with one ``backend.make`` call: with a
    ``model`` axis the backend psums its d-contractions over it, and the
    Pallas backends, which cannot, refuse it.
    """
    if sigma_input not in ("rows", "diag"):
        raise ValueError(f"sigma_input must be 'rows' or 'diag', got {sigma_input!r}")
    loss = get_loss(cfg.loss)
    dsz = _axis_size(mesh, axes.data)
    psz = _axis_size(mesh, axes.pod)
    m_loc = m // dsz
    n_loc = n_max // psz
    backend = get_backend(cfg.solver)
    if packed and not backend.packed:
        raise ValueError(
            f"the {cfg.solver} backend runs its Pallas kernel over a task's "
            "padded rows and refuses packed task storage; use "
            'solver="block_gram"'
        )
    H = backend.round_local_iters(cfg.local_iters or n_loc, cfg.block_size)
    # with a model axis the backend psums its d-contractions over it; the
    # Pallas makers refuse axis_name (docs/DESIGN.md §5)
    packed_kw = {"n_cap": n_max} if packed else {}
    solver = backend.make(
        loss, rho, cfg.lam, H, block=cfg.block_size, axis_name=axes.model,
        **packed_kw,
    )

    def local_solve(x, y, n, alpha, W_read, sigma_rows, key):
        di = jax.lax.axis_index(axes.data)
        pi = jax.lax.axis_index(axes.pod) if axes.pod else 0
        # global task ids of this shard + per-(task, pod, round) RNG
        tids = di * m_loc + jnp.arange(m_loc, dtype=jnp.int32)
        keys = jax.vmap(lambda t: jax.random.fold_in(jax.random.fold_in(key, t), pi))(
            tids
        )
        if sigma_input == "diag":
            sigma_ii = sigma_rows  # already the local (m_loc,) diagonal
        else:
            sigma_ii = jnp.take_along_axis(sigma_rows, tids[:, None], axis=1)[:, 0]
        # local valid sample count in this pod's contiguous slice
        n_local = jnp.clip(n - pi * n_loc, 0, n_loc).astype(jnp.int32)
        if packed:  # x, y and alpha are shared by the tasks' solves
            offsets = jnp.cumsum(n) - n
            dalpha, r = jax.vmap(solver, in_axes=(None, None, None, 0, 0, 0, 0, 0))(
                x, y, alpha, W_read, n, sigma_ii, keys, offsets
            )  # dalpha (m_loc, n_max), by sample: back to the rows' order
            t = row_tasks(n, x.shape[0])
            j = jnp.arange(x.shape[0], dtype=jnp.int32) - offsets[t]
            dalpha = jnp.where(j < n[t], dalpha[t, jnp.minimum(j, n_max - 1)], 0.0)
        else:
            dalpha, r = jax.vmap(solver)(
                x, y, alpha, W_read, n_local, sigma_ii, keys
            )
        if axes.pod is not None:
            r = jax.lax.psum(r, axes.pod)
        # delta_b_i = (eta / n_i_global) * sum over ALL of task i's samples
        db = cfg.eta * r / jnp.maximum(n, 1)[:, None].astype(r.dtype)
        return dalpha, db

    return local_solve


def pad_sigma_blocks(sigma_t, omega_t, m: int, m_true: int, jitter: float):
    """Embed the real-task Sigma/Omega into padded (m, m) matrices. Padded
    tasks get an inert jitter-scaled identity block so they stay decoupled.
    Shared by the sync and async engines (their Omega-steps must agree for
    the tau=0 bit-parity anchor)."""
    pad = m - m_true
    if not pad:
        return sigma_t, omega_t
    sigma = jnp.zeros((m, m), sigma_t.dtype)
    sigma = sigma.at[:m_true, :m_true].set(sigma_t)
    sigma = sigma.at[m_true:, m_true:].set(jnp.eye(pad) * jitter)
    omega = jnp.zeros((m, m), omega_t.dtype)
    omega = omega.at[:m_true, :m_true].set(omega_t)
    omega = omega.at[m_true:, m_true:].set(jnp.eye(pad) / jitter)
    return sigma, omega


def pad_sigma_any(sigma_t, omega_t, m: int, m_true: int, jitter: float):
    """pad_sigma_blocks generalized to SigmaView / missing-omega inputs.
    Dense (array, array) pairs go through pad_sigma_blocks unchanged (the
    bit-parity anchor); views pad via their own factor-level embedding."""
    if isinstance(sigma_t, SigmaView):
        sigma = sigma_t.pad(m, jitter)
        omega = omega_t.pad(m, 1.0 / jitter) if isinstance(omega_t, SigmaView) else None
        return sigma, omega
    if omega_t is None:
        sigma, _ = pad_sigma_blocks(sigma_t, sigma_t, m, m_true, jitter)
        return sigma, None
    return pad_sigma_blocks(sigma_t, omega_t, m, m_true, jitter)


def device_put_sigma(sigma, mesh: Mesh, axes: MeshAxes):
    """Shard a padded Sigma onto the mesh: dense rows get the historical
    P(data, None) row-sharding; a LowRankDiagSigma shards its task-indexed
    leaves (U rows / d) over the data axis with the r x r core replicated.
    SparseSigma has no mesh-native round yet — it densifies here (the
    documented small-m fallback; host transports keep it structured)."""
    if isinstance(sigma, LowRankDiagSigma):
        return LowRankDiagSigma(
            U=jax.device_put(sigma.U, NamedSharding(mesh, P(axes.data, None))),
            core=jax.device_put(sigma.core, NamedSharding(mesh, P())),
            d=jax.device_put(sigma.d, NamedSharding(mesh, P(axes.data))),
        )
    if isinstance(sigma, SigmaView):
        sigma = sigma.dense()
    return jax.device_put(sigma, NamedSharding(mesh, P(axes.data, None)))


def device_put_omega(omega, mesh: Mesh, axes: MeshAxes):
    if omega is None:
        return None
    return device_put_sigma(omega, mesh, axes)


def install_initial_state(
    state: "DistributedState",
    raw: MTLData,
    data: MTLData,
    m: int,
    cfg: DMTRLConfig,
    mesh: Mesh,
    axes: MeshAxes,
    reg,
    init,
    w_from_alpha,
) -> "DistributedState":
    """Install a warm start (``init``), a custom-init regularizer's Sigma, or
    the real tasks' initial Sigma when the task axis was padded, into
    freshly padded mesh state, rederiving W(alpha). (``init_state``'s
    I/m Sigma counts the padded tasks; the paper's init is I/m over the
    real ones.) Shared by the sync and async engines so their tau=0
    bit-parity anchor cannot drift."""
    padded = m != raw.m
    if init is None and not reg.custom_init and not reg.structured and not padded:
        return state
    if init is not None:
        if isinstance(init.sigma, SigmaView):
            sigma_t = init.sigma
        else:
            sigma_t = jnp.asarray(init.sigma, data.x.dtype)
        omega_t = init.omega
        if omega_t is not None and not isinstance(omega_t, SigmaView):
            omega_t = jnp.asarray(omega_t, data.x.dtype)
    else:
        sigma_t, omega_t = reg.init(raw.m, data.x.dtype)
    sig, om = pad_sigma_any(sigma_t, omega_t, m, raw.m, cfg.omega_jitter)
    state = dataclasses.replace(
        state,
        sigma=device_put_sigma(sig, mesh, axes),
        omega=device_put_omega(om, mesh, axes),
    )
    if init is not None:
        alpha_t = jnp.asarray(init.alpha, data.x.dtype)
        alpha0 = jnp.zeros(data.mask.shape, data.x.dtype)  # alpha follows the mask
        if data.layout == "padded":
            alpha0 = alpha0.at[: raw.m, : raw.n_max].set(alpha_t)
        elif state.rows is not None:
            alpha0 = alpha0.at[state.rows].set(alpha_t[: state.rows.shape[0]])
        else:
            alpha0 = alpha_t
        state = dataclasses.replace(
            state, alpha=jax.device_put(alpha0, alpha_sharding(data, mesh, axes))
        )
        state = dataclasses.replace(
            state, W=w_from_alpha(state.alpha, state.sigma)
        )
    return state


def server_reduce(cfg: DMTRLConfig, axes: MeshAxes, sigma_rows, db):
    """The server half of one round, as a shard_map body fragment:
    all_gather the workers' delta_b rows and apply the Sigma-coupled
    reduce for this shard's W rows. ``db`` may be pre-masked by the async
    engine so that only arrived contributions enter the gather."""
    dB = jax.lax.all_gather(db, axes.data, axis=0, tiled=True)  # (m, d_loc)
    return sigma_rows @ dB / cfg.lam  # (m_loc, d_loc)


def round_shard_map(cfg: DMTRLConfig, axes: MeshAxes, body, mesh, in_specs, out_specs):
    """shard_map a round/tick body, disabling the replication check only
    when the configured backend traces a pallas_call into the body (jax
    has no replication rule for pallas_call)."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=not get_backend(cfg.solver).uses_pallas,
    )


# the DMTRLConfig fields a round program's trace reads: with the mesh, the
# axes, the shapes and the builders below, its static key
_ROUND_FIELDS = ("loss", "lam", "eta", "solver", "block_size", "local_iters")


@dataclasses.dataclass(frozen=True)
class RhoRound:
    """A cached round program with one outer iteration's ``rho`` bound:
    ``round(x, y, mask, n, alpha, W, sigma, key) -> (alpha, W)``."""

    program: Callable
    rho: float

    def __call__(self, *args):
        return self.program(*args, self.rho)

    def lower(self, *args):
        return self.program.lower(*args, self.rho)


def make_distributed_round(
    cfg: DMTRLConfig,
    mesh: Mesh,
    axes: MeshAxes,
    m: int,
    n_max: int,
    d: int,
    rho: float,
    structured: bool = False,
    packed: bool = False,
):
    """The jitted one-round function over sharded global arrays, at ``rho``.

    round(x, y, mask, n, alpha, W, sigma, key) -> (alpha, W)

    ``rho`` is a runtime operand of the program, which is built once per
    static key (the config fields in ``_ROUND_FIELDS``, mesh, axes, shapes,
    ``structured`` and the ``make_local_solve`` / ``server_reduce`` it is
    built from) and reused for every ``rho`` and every fit.

    With ``structured=True`` the sigma argument is a LowRankDiagSigma pytree
    (U/d row-sharded, core replicated) and the server reduce is factored:
    instead of all-gathering the (m, d) delta_b block, each shard psums its
    (r, d) projection U_rows^T db — O(r d) collective bytes per round
    instead of O(m d), the communication win at large m — then applies
    dW_rows = U_rows (C psum) + d_rows * db locally. The dense and factored
    reduces agree to float tolerance (parity-tested).

    With ``packed=True`` the data, alpha and the returned alpha are packed
    rows (``_shard_packed_data``), ``n_max`` is the largest n_i, and the
    worker half is ``make_local_solve(..., packed=True)``.
    """
    round_cfg = DMTRLConfig(**{f: getattr(cfg, f) for f in _ROUND_FIELDS})
    program = _round_program(
        round_cfg, mesh, axes, m, n_max, d, structured, packed,
        make_local_solve, server_reduce,
    )
    return RhoRound(program, float(rho))


@driver_program("round")
def _round_program(
    cfg, mesh, axes, m, n_max, d, structured, packed, local_solve_fn, reduce_fn
):
    structured_specs = LowRankDiagSigma(
        U=P(axes.data, None), core=P(), d=P(axes.data)
    )
    if packed:  # rows (x, y, mask, alpha) and tasks (n, W, sigma) over data
        vec, mat = P(axes.data), P(axes.data, None)
        base_specs = (mat, vec, vec, vec, vec, mat, mat)
        out_specs = (vec, mat)
    else:
        base_specs = round_in_specs(axes)
        out_specs = round_out_specs(axes)
    if structured:
        base_specs = base_specs[:-1] + (structured_specs,)
    in_specs = base_specs + (P(), P())  # + key, rho (replicated)

    def local_solve(rho, *args):
        return local_solve_fn(
            cfg, mesh, axes, m, n_max, d, rho,
            sigma_input="diag" if structured else "rows",
            **({"packed": True} if packed else {}),
        )(*args)

    if structured:

        def round_body(x, y, mask, n, alpha, W, sv, key, rho):
            dalpha, db = local_solve(rho, x, y, n, alpha, W, sv.diag(), key)
            proj = jax.lax.psum(sv.U.T @ db, axes.data)  # (r, d_loc)
            dW = (sv.U @ (sv.core @ proj) + sv.d[:, None] * db) / cfg.lam
            return alpha + cfg.eta * dalpha, W + dW

    else:

        def round_body(x, y, mask, n, alpha, W, sigma_rows, key, rho):
            dalpha, db = local_solve(rho, x, y, n, alpha, W, sigma_rows, key)
            dW = reduce_fn(cfg, axes, sigma_rows, db)
            return alpha + cfg.eta * dalpha, W + dW

    shmapped = round_shard_map(cfg, axes, round_body, mesh, in_specs, out_specs)
    return jax.jit(shmapped)


def _count_rows(raw, data, layout: str) -> None:
    """Gauge ``repro_engine_data_rows{layout, kind}``: the rows of task
    storage on the mesh (``stored``, padding included) and the tasks' real
    samples (``real``, the sum of n_i)."""
    gauge = get_registry().gauge(
        "repro_engine_data_rows",
        "rows of task storage on the mesh, padding included (stored) and "
        "the tasks' samples (real)",
        labels=("layout", "kind"),
    )
    stored = data.x.shape[0] * (1 if layout == "packed" else data.x.shape[1])
    gauge.set(stored, layout=layout, kind="stored")
    gauge.set(int(np.asarray(raw.n).sum()), layout=layout, kind="real")


@dataclasses.dataclass
class DistributedState:
    alpha: Array
    W: Array
    # dense row-sharded (m, m) array or a mesh-sharded SigmaView pytree
    sigma: Array
    # precision; None for structured members without a cheap inverse
    omega: Optional[Array]
    # packed rows on more than one worker: where each of the raw
    # container's rows lies in ``alpha`` (mtl_data.worker_layout)
    rows: Optional[np.ndarray] = None


def alpha_sharding(data, mesh: Mesh, axes: MeshAxes) -> NamedSharding:
    """alpha has the mask's shape: (m, n_max) over (data, pod), or the
    packed rows over data."""
    if data.layout == "packed":
        return NamedSharding(mesh, P(axes.data))
    return NamedSharding(mesh, P(axes.data, axes.pod))


def init_state(
    data: MTLData, mesh: Mesh, axes: MeshAxes, m: int, d: int
) -> DistributedState:
    sw = NamedSharding(mesh, P(axes.data, axes.model))
    sr = NamedSharding(mesh, P(axes.data, None))
    alpha = jax.device_put(
        jnp.zeros(data.mask.shape, data.x.dtype), alpha_sharding(data, mesh, axes)
    )
    W = jax.device_put(jnp.zeros((m, d), data.x.dtype), sw)
    sigma, omega = omega_mod.init_sigma(m, data.x.dtype)
    return DistributedState(
        alpha, W, jax.device_put(sigma, sr), jax.device_put(omega, sr)
    )


def fit_distributed(
    cfg: DMTRLConfig,
    raw: MTLData,
    mesh: Mesh,
    axes: Optional[MeshAxes] = None,
    track: bool = True,
    *,
    init: Optional[WarmStart] = None,
    regularizer=None,
):
    """Full Algorithm 1 on a mesh. Semantically equal to dmtrl.fit when
    pod axis is absent (tested); with pods the CoCoA block structure is finer
    (m*pods blocks) so iterates differ but convergence is preserved.

    ``init`` warm-starts from raw-shaped (alpha, sigma, omega);
    ``regularizer`` overrides the Omega family member (see
    core.omega_regularizers).
    """
    if axes is None:
        axes = MeshAxes()
    reg = omega_reg.resolve_regularizer(cfg, regularizer, m=raw.m)
    # driver spans (obs): shard / rho / round / objectives / omega_step /
    # result, on the profiler's clock while a profiler session records.
    # They add no host sync: each readback below was there before, but
    # for the row count of the gauge in shard.
    layout = raw.layout
    packed = layout == "packed"
    with span("shard", cat="driver", layout=layout):
        data, m, d = shard_mtl_data(raw, mesh, axes)
        state = init_state(data, mesh, axes, m, d)
        if packed and data.workers > 1:
            rows = worker_layout(np.asarray(raw.n), data.workers)[0]
            state = dataclasses.replace(state, rows=rows)
        _count_rows(raw, data, layout)
        objectives, w_from_alpha = make_data_fns(cfg, data)
        state = install_initial_state(
            state, raw, data, m, cfg, mesh, axes, reg, init, w_from_alpha
        )
    key = jax.random.PRNGKey(cfg.seed)

    # the synchronous engine IS the degenerate tau=0 transport: every round
    # commits all G workers as one barriered event with zero staleness/lag,
    # accounted through the same CommitReceipt path as the async transports
    # (core/transport.py) so convergence.staleness_summary reads one stream.
    from .transport import CommitReceipt, new_event_history, record_receipt

    n_pods = _axis_size(mesh, axes.pod)
    n_workers = _axis_size(mesh, axes.data)
    hist = new_event_history()
    rounds_seen = 0

    for p in range(cfg.outer_iters):
        with span("rho", cat="driver"):
            rho = _rho_value(
                cfg, state.sigma, n_blocks_scale=float(n_pods), reg=reg
            )
        round_fn = make_distributed_round(
            cfg, mesh, axes, m, data.n_max, d, rho,
            structured=isinstance(state.sigma, LowRankDiagSigma),
            packed=packed,
        )
        # same key schedule as dmtrl.fit/w_step => bit-equal coordinate draws
        key, outer_key = jax.random.split(key)
        round_keys = jax.random.split(outer_key, cfg.rounds)
        for t in range(cfg.rounds):
            with span("round", cat="driver", outer=p, round=t):
                alpha, W = round_fn(
                    data.x,
                    data.y,
                    data.mask,
                    data.n,
                    state.alpha,
                    state.W,
                    state.sigma,
                    round_keys[t],
                )
                state = dataclasses.replace(state, alpha=alpha, W=W)
                commit = rounds_seen + t + 1
                for g in range(n_workers):
                    record_receipt(
                        hist,
                        CommitReceipt(
                            worker=g, round=rounds_seen + t, staleness=0,
                            lag=0, tick=commit, version=commit, tau=0,
                        ),
                    )
                hist["tau_trace"].append(0)
                hist["gate_refusals"].append(0)
            if track:
                with span("objectives", cat="driver", outer=p, round=t):
                    dd, pp = objectives(state.alpha, state.sigma)
                    hist["round"].append(commit)
                    hist["tick"].append(commit)
                    hist["dual"].append(float(dd))
                    hist["primal"].append(float(pp))
                    hist["gap"].append(float(pp - dd))
                    hist["min_round"].append(rounds_seen + t + 1)
        rounds_seen += cfg.rounds
        if reg.learns:
            with span("omega_step", cat="driver", outer=p):
                # Omega-step must see only the REAL tasks: padded (inert)
                # tasks would otherwise distort the trace-1 normalization.
                W_true = state.W[: raw.m]
                sigma_t, omega_t = reg.step(W_true, cfg.omega_jitter)
                sigma, omega = pad_sigma_any(
                    sigma_t, omega_t, m, raw.m, cfg.omega_jitter
                )
                state = dataclasses.replace(
                    state,
                    sigma=device_put_sigma(sigma, mesh, axes),
                    omega=device_put_omega(omega, mesh, axes),
                )
                state = dataclasses.replace(
                    state, W=w_from_alpha(state.alpha, state.sigma)
                )

    with span("result", cat="driver"):
        hist_np = {k: np.asarray(v) for k, v in hist.items()}
        # un-pad the task axis before returning
        W = np.asarray(state.W)[: raw.m, : raw.d]
        if isinstance(state.sigma, SigmaView):
            from .sigma_view import maybe_dense

            sigma = maybe_dense(state.sigma.unpad(raw.m))
        else:
            sigma = np.asarray(state.sigma)[: raw.m, : raw.m]
    return W, sigma, state, hist_np
