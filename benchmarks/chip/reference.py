"""The benchmark's own float64 reference: filter models and the tracker's
frame semantics, in plain numpy.

It imports nothing of the program. The models are built from the
numbers in a configuration file; the tracker follows the semantics the
program documents for one lane of its stream front end:

  predict every slot (IMM: mix the K hypotheses first), gate each
  (slot, measurement) pair by the squared Mahalanobis distance against
  the chi-square 99 % quantile times the tier's gate scale (IMM: the
  cbar-weighted sum over models), globally greedy assignment, Kalman
  update of assigned slots, hit/miss/age counters, spawn of unassigned
  measurements into free slots in order, prune after ``max_misses``
  consecutive misses, confirmation after ``min_hits`` hits.

The live comparison is teacher-forced: the reference follows the
association the program chose in each frame (as a served model's
reference follows its served tokens) and judges that choice by
``assoc_gap``, the widest margin by which it departs from a globally
greedy assignment under the reference's own float64 costs. Everything
downstream of the association (ids, counters, states, mode
probabilities) is then compared directly.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# chi-square 99 % quantiles by degrees of freedom
CHI2_99 = {1: 6.63, 2: 9.21, 3: 11.34, 4: 13.28, 5: 15.09, 6: 16.81}
LOG_2PI = float(np.log(2.0 * np.pi))


@dataclass(frozen=True)
class Model:
    """K linear motion hypotheses with a shared position-selector H
    (K = 1 is a plain Kalman filter: its mixing and mode posterior are
    the identity)."""

    F: np.ndarray      # (K, n, n)
    Q: np.ndarray      # (K, n, n)
    R: np.ndarray      # (m, m)
    obs: tuple         # state index observed by each measurement row
    x0: np.ndarray     # (n,)
    P0: np.ndarray     # (n, n)
    trans: np.ndarray  # (K, K) row-stochastic mode transitions
    mu0: np.ndarray    # (K,)

    @property
    def K(self) -> int:
        return self.F.shape[0]

    @property
    def n(self) -> int:
        return self.F.shape[1]

    @property
    def m(self) -> int:
        return len(self.obs)


def cv6(dt: float, q: float, r: float, p0: float) -> Model:
    """3-D constant velocity, state [p, v], position measured."""
    F = np.eye(6)
    F[:3, 3:] = dt * np.eye(3)
    G = np.zeros((6, 3))
    G[:3] = 0.5 * dt * dt * np.eye(3)
    G[3:] = dt * np.eye(3)
    Q = q * G @ G.T + 1e-9 * np.eye(6)
    return Model(F[None], Q[None], r * np.eye(3), (0, 1, 2), np.zeros(6),
                 p0 * np.eye(6), np.ones((1, 1)), np.ones(1))


def _cv9(dt, q):
    F = np.zeros((9, 9))
    F[:6, :6] = np.eye(6)
    F[:3, 3:6] = dt * np.eye(3)
    G = np.zeros((9, 3))
    G[:3] = 0.5 * dt * dt * np.eye(3)
    G[3:6] = dt * np.eye(3)
    return F, q * G @ G.T + 1e-9 * np.eye(9)


def _ca9(dt, q):
    F = np.eye(9)
    F[:3, 3:6] = dt * np.eye(3)
    F[:3, 6:9] = 0.5 * dt * dt * np.eye(3)
    F[3:6, 6:9] = dt * np.eye(3)
    G = np.zeros((9, 3))
    G[:3] = (dt ** 3 / 6.0) * np.eye(3)
    G[3:6] = 0.5 * dt * dt * np.eye(3)
    G[6:9] = dt * np.eye(3)
    return F, q * G @ G.T + 1e-9 * np.eye(9)


def _ct9(dt, q, w):
    s, c = np.sin(w * dt), np.cos(w * dt)
    F = np.zeros((9, 9))
    F[:3, :3] = np.eye(3)
    F[0, 3], F[0, 4] = s / w, -(1 - c) / w
    F[1, 3], F[1, 4] = (1 - c) / w, s / w
    F[2, 5] = dt
    F[3, 3], F[3, 4] = c, -s
    F[4, 3], F[4, 4] = s, c
    F[5, 5] = 1.0
    G = np.zeros((9, 3))
    G[:3] = 0.5 * dt * dt * np.eye(3)
    G[3:6] = dt * np.eye(3)
    return F, q * G @ G.T + 1e-9 * np.eye(9)


def imm4(dt: float, q_cv: float, q_ca: float, omega: float, p_stay: float,
         r: float, p0: float) -> Model:
    """IMM over [p, v, a]: constant velocity, constant acceleration, and
    coordinated turns at +omega and -omega about z."""
    parts = [_cv9(dt, q_cv), _ca9(dt, q_ca), _ct9(dt, q_cv, omega),
             _ct9(dt, q_cv, -omega)]
    K = len(parts)
    trans = np.full((K, K), (1.0 - p_stay) / (K - 1))
    np.fill_diagonal(trans, p_stay)
    return Model(np.stack([f for f, _ in parts]),
                 np.stack([q for _, q in parts]), r * np.eye(3), (0, 1, 2),
                 np.zeros(9), p0 * np.eye(9), trans, np.full(K, 1.0 / K))


def model_from_config(cfg: dict) -> Model:
    f = cfg["filter"]
    dt = 1.0 / cfg["fps"]
    if f["kind"] == "lkf-cv6":
        return cv6(dt, f["q"], f["r"], f["p0"])
    if f["kind"] == "imm-cv-ca-ct9":
        return imm4(dt, f["q"], f["q_ca"], f["omega"], f["p_stay"], f["r"],
                    f["p0"])
    raise KeyError(f"no reference for filter kind {f['kind']!r}")


# ---------------------------------------------------------------- algebra

def _sym(P):
    return 0.5 * (P + np.swapaxes(P, -1, -2))


def _inv(S):
    """Inverse and determinant of (..., 3, 3) matrices by cofactors
    (closed form: no per-matrix LAPACK call)."""
    if S.shape[-1] != 3:
        return np.linalg.inv(S), np.linalg.det(S)
    a, b, c = S[..., 0, 0], S[..., 0, 1], S[..., 0, 2]
    d, e, f = S[..., 1, 0], S[..., 1, 1], S[..., 1, 2]
    g, h, i = S[..., 2, 0], S[..., 2, 1], S[..., 2, 2]
    cof = np.stack([np.stack([e * i - f * h, c * h - b * i, b * f - c * e],
                             -1),
                    np.stack([f * g - d * i, a * i - c * g, c * d - a * f],
                             -1),
                    np.stack([d * h - e * g, b * g - a * h, a * e - b * d],
                             -1)], -2)
    det = a * cof[..., 0, 0] + b * cof[..., 1, 0] + c * cof[..., 2, 0]
    return cof / det[..., None, None], det


def _predict(model: Model, x, P, mu):
    """IMM interaction then K time updates, batched over a leading axis.

    x: (N, K, n); P: (N, K, n, n); mu: (N, K). Returns predicted (x, P)
    and cbar (N, K), the Markov-predicted mode probabilities."""
    Pi = model.trans
    N, K, n = x.shape
    if K == 1:
        return (np.einsum("kab,Nkb->Nka", model.F, x),
                model.F @ P @ np.swapaxes(model.F, -1, -2) + model.Q, mu)
    cbar = mu @ Pi                                    # (N, K)
    w = Pi * mu[:, :, None] / cbar[:, None, :]        # w[i, j] = P(i | j)
    wT = np.swapaxes(w, 1, 2)                         # (N, j, i)
    x_mix = wT @ x                                    # (N, j, n)
    dx = x[:, None, :, :] - x_mix[:, :, None, :]      # (N, j, i, n)
    P_mix = ((wT @ P.reshape(N, K, n * n)).reshape(N, K, n, n)
             + np.swapaxes(dx * wT[..., None], 2, 3) @ dx)
    xp = np.einsum("kab,Nkb->Nka", model.F, x_mix)
    Pp = model.F @ P_mix @ np.swapaxes(model.F, -1, -2) + model.Q
    return xp, Pp, cbar


def _innovation(model: Model, xp, Pp):
    obs = list(model.obs)
    z_pred = xp[..., obs]                               # (..., K, m)
    S = Pp[..., obs, :][..., :, obs] + model.R          # (..., K, m, m)
    Sinv, det = _inv(S)
    return z_pred, det, Sinv


def _update(model: Model, xp, Pp, z_pred, det, Sinv, z):
    """Kalman update of every hypothesis with z (..., m), and the
    Gaussian log-likelihood of z under each."""
    obs = list(model.obs)
    y = z[..., None, :] - z_pred                        # (..., K, m)
    PHt = Pp[..., :, obs]                               # (..., K, n, m)
    G = PHt @ Sinv
    x = xp + (G @ y[..., None])[..., 0]
    P = _sym(Pp - G @ np.swapaxes(PHt, -1, -2))
    maha = (y[..., None, :] @ Sinv @ y[..., None])[..., 0, 0]
    ll = -0.5 * (maha + np.log(det) + model.m * LOG_2PI)
    return x, P, ll


def _posterior(cbar, ll):
    w = cbar * np.exp(ll - ll.max(axis=-1, keepdims=True))
    return w / w.sum(axis=-1, keepdims=True)


def gate_cost(z_pred, Sinv, cbar, z, lane):
    """Gating cost of N slot rows against their lane's measurements:
    sum_k cbar_k (z - z_pred_k)^T S_k^-1 (z - z_pred_k), (N, M).
    z_pred: (N, K, m); Sinv: (N, K, m, m); cbar: (N, K); z: (B, M, m);
    lane: (N,) sorted lane index of each row."""
    N, K, m = z_pred.shape
    M = z.shape[1]
    out = np.empty((N, M))
    # y^T A y with y = z - z_pred, expanded into matrix products (float64
    # keeps the cancellation far below what is compared)
    Azp = (Sinv @ z_pred[..., None])[..., 0]                 # (N, K, m)
    q = (Azp * z_pred).sum(-1)                               # (N, K)
    edges = np.searchsorted(lane, np.arange(z.shape[0] + 1))
    for b_, (lo, hi) in enumerate(zip(edges, edges[1:])):
        if lo == hi:
            continue
        zl = z[b_]
        zz = (zl[:, :, None] * zl[:, None, :]).reshape(M, m * m)
        d = (Sinv[lo:hi].reshape(-1, m * m) @ zz.T
             - 2.0 * Azp[lo:hi].reshape(-1, m) @ zl.T
             + q[lo:hi].reshape(-1, 1)).reshape(hi - lo, K, M)
        out[lo:hi] = np.einsum("rk,rkM->rM", cbar[lo:hi], d)
    return out


def assoc_gap(cost, lane, chosen_m, zvalid, gate, bad):
    """Widest margin by which the program's association departs from the
    globally greedy assignment under ``cost``, per lane.

    cost: (N, M) costs of the lanes' active slots (rows sorted by
    ``lane``); chosen_m: (N,) the measurement each row was given, or -1;
    zvalid: (B, M); gate: (B,); bad: (B,) lanes whose choice is
    impossible (an inactive slot, an out-of-range, invalid or twice-used
    measurement), which read infinite.

    With a single global order of pairs the greedy assignment is the
    unique matching in which every pair left out is blocked by a chosen
    pair at its slot or its measurement that is no dearer (an unmatched
    end blocks at the gate), and every chosen pair is gated. The gap is
    the largest violation: a chosen pair's cost above the gate, or a
    pair left out whose cost lies below both of its blockers. 0 means
    the program chose exactly the greedy assignment."""
    N, M = cost.shape
    B = zvalid.shape[0]
    gate = np.broadcast_to(np.asarray(gate, np.float64), (B,))
    chosen = chosen_m >= 0
    safe = np.where(chosen, chosen_m, 0)
    c_row = np.where(chosen, cost[np.arange(N), safe], np.inf)
    gap = np.zeros(B)
    np.maximum.at(gap, lane[chosen], c_row[chosen] - gate[lane[chosen]])
    block_row = np.where(chosen, c_row, gate[lane])          # (N,)
    block_col = np.broadcast_to(gate[:, None], (B, M)).copy()
    np.minimum.at(block_col, (lane[chosen], safe[chosen]), c_row[chosen])
    margin = np.minimum(block_row[:, None], block_col[lane]) - cost
    margin[~zvalid[lane]] = -np.inf
    margin[np.flatnonzero(chosen), safe[chosen]] = -np.inf
    np.maximum.at(gap, lane, margin.max(axis=1, initial=-np.inf))
    return np.where(bad, np.inf, np.maximum(gap, 0.0))


# ------------------------------------------------------------- the tracker

class Tracker:
    """B independent lanes (one per tenant) of the tracker, teacher-forced
    by the program's association."""

    def __init__(self, model: Model, capacity: int, max_meas: int,
                 lanes: int, gate: float, max_misses: int, min_hits: int):
        K, n = model.K, model.n
        self.model, self.C, self.M = model, capacity, max_meas
        self.gate, self.max_misses, self.min_hits = gate, max_misses, min_hits
        self.x = np.zeros((lanes, capacity, K, n))
        self.P = np.broadcast_to(model.P0, (lanes, capacity, K, n, n)).copy()
        self.mu = np.broadcast_to(model.mu0, (lanes, capacity, K)).copy()
        self.active = np.zeros((lanes, capacity), bool)
        self.hits = np.zeros((lanes, capacity), np.int64)
        self.misses = np.zeros((lanes, capacity), np.int64)
        self.age = np.zeros((lanes, capacity), np.int64)
        self.track_id = np.full((lanes, capacity), -1, np.int64)
        self.next_id = np.zeros(lanes, np.int64)

    def step(self, lanes, z, zvalid, assoc, gate_scale):
        """Advance ``lanes`` (index array) by one frame along the
        program's ``assoc`` (len(lanes), C). z: (len(lanes), M, m);
        zvalid: (len(lanes), M). Returns the per-lane assoc_gap.

        Only active slots are filtered: a free slot's state is never
        read before a spawn overwrites it."""
        md = self.model
        b, C, M = len(lanes), self.C, self.M
        active = self.active[lanes]
        li, si = np.nonzero(active)
        gl = lanes[li]
        xp, Pp, cbar = _predict(md, self.x[gl, si], self.P[gl, si],
                                self.mu[gl, si])
        z_pred, det, Sinv = _innovation(md, xp, Pp)
        a_row = assoc[li, si]
        # an impossible choice: an inactive slot, or a measurement out of
        # range, invalid, or given twice
        chosen = assoc >= 0
        bad = (chosen & ~active).any(axis=1) | (assoc >= M).any(axis=1)
        m_ok = np.where(chosen & active & (assoc < M), assoc, M)
        rows = np.broadcast_to(np.arange(b)[:, None], assoc.shape)
        used = np.zeros((b, M + 1), np.int64)
        np.add.at(used, (rows, m_ok), 1)
        bad |= ((used[:, :M] > 1) | ((used[:, :M] > 0) & ~zvalid)).any(axis=1)
        gap = assoc_gap(gate_cost(z_pred, Sinv, cbar, z, li), li,
                        np.where(a_row < M, a_row, -1), zvalid,
                        self.gate * np.asarray(gate_scale), bad)
        u = np.flatnonzero(a_row >= 0)
        mu = cbar.copy()
        if len(u):
            zk = z[li[u], np.minimum(a_row[u], M - 1)]
            xu, Pu, ll = _update(md, xp[u], Pp[u], z_pred[u], det[u],
                                 Sinv[u], zk)
            xp[u], Pp[u], mu[u] = xu, Pu, _posterior(cbar[u], ll)
        self.x[gl, si], self.P[gl, si], self.mu[gl, si] = xp, Pp, mu
        upd = (assoc >= 0) & active
        hits = np.where(upd, self.hits[lanes] + 1, self.hits[lanes])
        misses = np.where(upd, 0, np.where(active, self.misses[lanes] + 1,
                                           self.misses[lanes]))
        age = np.where(active, self.age[lanes] + 1, self.age[lanes])
        # spawn: the j-th unassigned measurement takes the j-th free slot
        taken = np.zeros(zvalid.shape, bool)
        taken[rows[upd], np.minimum(assoc[upd], M - 1)] = True
        fresh = zvalid & ~taken
        free = ~active
        free_rank = np.cumsum(free, axis=1) - 1
        spawn = free & (free_rank < fresh.sum(axis=1)[:, None])
        # measurement index of the k-th fresh measurement, per lane
        order = np.argsort(~fresh, axis=1, kind="stable")
        ls, ss = np.nonzero(spawn)
        src = order[ls, free_rank[ls, ss]]
        seed = np.broadcast_to(md.x0, (len(ls), md.n)).copy()
        seed[:, list(md.obs)] = z[ls, src]
        gs = lanes[ls]
        self.x[gs, ss] = seed[:, None, :]
        self.P[gs, ss] = md.P0
        self.mu[gs, ss] = md.mu0
        track_id = np.where(spawn, self.next_id[lanes][:, None] + free_rank,
                            self.track_id[lanes])
        self.next_id[lanes] += spawn.sum(axis=1)
        active = active | spawn
        hits = np.where(spawn, 1, hits)
        misses = np.where(spawn, 0, misses)
        age = np.where(spawn, 0, age)
        dead = active & (misses > self.max_misses)
        active &= ~dead
        track_id = np.where(dead, -1, track_id)
        hits = np.where(dead, 0, hits)
        misses = np.where(dead, 0, misses)
        (self.active[lanes], self.hits[lanes], self.misses[lanes],
         self.age[lanes], self.track_id[lanes]) = (active, hits, misses, age,
                                                   track_id)
        return gap

    def confirmed(self, lane: int):
        """(slots, ids, combined states, hits, ages, mode probabilities)
        of the lane's confirmed tracks, in slot order."""
        conf = self.active[lane] & (self.hits[lane] >= self.min_hits)
        idx = np.flatnonzero(conf)
        mu = self.mu[lane, idx]
        x_c = np.einsum("sk,skn->sn", mu, self.x[lane, idx])
        return (idx, self.track_id[lane, idx], x_c, self.hits[lane, idx],
                self.age[lane, idx], mu)
