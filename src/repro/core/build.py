"""Batch-synchronous JAG construction (Insert, Algorithm 3).

Points are inserted in batches of B:
  1. For every threshold t in T (or weight w): GreedySearch from the entry
     point under D_A(t) (resp. D_A^w); union the visited logs (Alg. 3 l.4-7).
  2. Dedup/self-mask the candidate pool, keep the C-best by vector distance.
  3. JointRobustPrune -> out-neighbors of each inserted point (l.8).
  4. Reverse edges (l.9-13): proposals (v -> p) are grouped by destination via
     a sort + in-group rank, written at slot degree[v]+rank into an adjacency
     buffer with EX spare columns; destinations whose degree exceeds R are
     re-pruned in a second vectorized pass (fill factor 0.9, paper D.3).

The graph buffer is ``int32[N, R+EX]``; rows hold -1 sentinels beyond their
degree. Searches read the full buffer (spare columns are -1 except transiently
for rows awaiting a future overflow re-prune).
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import span
from .beam_search import greedy_search
from .distances import (INF, build_threshold_key_fn, build_weight_key_fn,
                        dist_a, sq_norms)
from .filters import AttrTable
from .prune import joint_robust_prune, select_to_rows


@dataclasses.dataclass(frozen=True)
class BuildConfig:
    degree: int = 32                 # R: max out-degree
    ls_build: int = 64               # l_b: build beam width
    alpha: float = 1.2
    mode: str = "threshold"          # "threshold" | "weight"
    thresholds: tuple = (jnp.inf, 0.1, 0.0)  # absolute dist_A caps
    weights: tuple = (0.0, 1.0)
    batch_size: int = 128
    cand_pool: int = 192             # C: prune candidate pool size
    max_iters: int = 0               # 0 -> 2*ls_build
    ex_slots: int = 16               # EX spare adjacency columns
    ov_max: int = 256                # max overflow vertices re-pruned / batch
    fill: float = 0.9                # overflow re-prune fill factor
    n_passes: int = 2                # DiskANN-style build passes

    @property
    def iters(self) -> int:
        return self.max_iters or 2 * self.ls_build

    @property
    def row_width(self) -> int:
        return self.degree + self.ex_slots

    @property
    def bucket_vals(self):
        return self.thresholds if self.mode == "threshold" else self.weights


# ---------------------------------------------------------------------------
# candidate pool assembly
# ---------------------------------------------------------------------------

def _dedup_pool(ids: jnp.ndarray, self_ids: jnp.ndarray) -> jnp.ndarray:
    """Mark -1 for duplicates / self / sentinel; keep first occurrence."""
    ids = jnp.where(ids == self_ids[:, None], -1, ids)
    order = jnp.argsort(ids, axis=1)
    s = jnp.take_along_axis(ids, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(s[:, :1], jnp.bool_), s[:, 1:] == s[:, :-1]], axis=1)
    s = jnp.where(dup, -1, s)
    out = jnp.full_like(ids, -1)
    return out.at[jnp.arange(ids.shape[0])[:, None], order].set(s)


def _top_c(ids: jnp.ndarray, d2: jnp.ndarray, c: int):
    """Keep the c candidates with smallest vector distance."""
    key = jnp.where(ids >= 0, d2, INF)
    _, sids = jax.lax.sort((key, ids), num_keys=1)
    return sids[:, :c]


# ---------------------------------------------------------------------------
# one jitted insertion step
# ---------------------------------------------------------------------------

def make_insert_step(cfg: BuildConfig):
    """Returns insert(graph, degree, xb, xb_norm, attr, batch_ids, entry)."""

    @partial(jax.jit, donate_argnums=(0, 1))
    def insert(graph, degree, xb, xb_norm, attr: AttrTable, batch_ids, entry):
        B = batch_ids.shape[0]
        p_vec = jnp.take(xb, batch_ids, axis=0)
        p_attr = attr.gather(batch_ids)

        # --- 1. per-bucket greedy searches, union visited logs -----------
        logs = []
        for bval in cfg.bucket_vals:
            if cfg.mode == "threshold":
                kf = build_threshold_key_fn(attr.kind, p_attr,
                                            jnp.float32(bval))
            else:
                kf = build_weight_key_fn(attr.kind, p_attr, jnp.float32(bval))
            res = greedy_search(graph, xb, xb_norm, attr, p_vec, entry, kf,
                                ls=cfg.ls_build, k=1, max_iters=cfg.iters)
            logs.append(res.vlog)
        pool = jnp.concatenate(logs, axis=1)

        # --- 2. dedup + keep best C by vector distance --------------------
        pool = _dedup_pool(pool, batch_ids)
        pn = jnp.sum(p_vec.astype(jnp.float32) ** 2, axis=-1)
        pool_d2 = _pool_d2(xb, xb_norm, pool, p_vec, pn)
        cand = _top_c(pool, pool_d2, cfg.cand_pool)          # [B, C]
        cvalid = cand >= 0
        cc = jnp.maximum(cand, 0)
        d2_p = _pool_d2(xb, xb_norm, cc, p_vec, pn)
        da_p = dist_a(attr.kind, p_attr, attr.gather(cc))
        cvec = jnp.take(xb, cc, axis=0).astype(jnp.float32)  # [B, C, d]
        cnorm = jnp.take(xb_norm, cc, axis=0)
        pair_d2 = (cnorm[:, :, None] + cnorm[:, None, :]
                   - 2.0 * jnp.einsum("bcd,bed->bce", cvec, cvec))
        pair_d2 = jnp.maximum(pair_d2, 0.0)

        # --- 3. prune -> out-neighbors of p -------------------------------
        kw = (dict(thresholds=cfg.thresholds) if cfg.mode == "threshold"
              else dict(weights=cfg.weights))
        selected = joint_robust_prune(cvalid, d2_p, da_p, pair_d2,
                                      degree=cfg.degree, alpha=cfg.alpha,
                                      **kw)
        out_rows = select_to_rows(selected, cand, d2_p, cfg.degree)
        pad = jnp.full((B, cfg.ex_slots), -1, jnp.int32)
        graph = graph.at[batch_ids].set(
            jnp.concatenate([out_rows, pad], axis=1))
        degree = degree.at[batch_ids].set(
            jnp.sum(out_rows >= 0, axis=1, dtype=jnp.int32))

        # --- 4. reverse edges ---------------------------------------------
        graph, degree, overflow_v = _reverse_edges(
            graph, degree, out_rows, batch_ids, cfg)

        # --- 5. overflow re-prune -----------------------------------------
        graph, degree = _overflow_reprune(graph, degree, xb, xb_norm, attr,
                                          overflow_v, cfg)
        return graph, degree

    return insert


def _pool_d2(xb, xb_norm, ids, p_vec, p_norm):
    rows = jnp.take(xb, ids, axis=0, mode="clip").astype(jnp.float32)
    dots = jnp.einsum("bcd,bd->bc", rows, p_vec.astype(jnp.float32))
    return jnp.maximum(
        jnp.take(xb_norm, ids, mode="clip") - 2.0 * dots + p_norm[:, None],
        0.0)


def _reverse_edges(graph, degree, out_rows, batch_ids, cfg: BuildConfig):
    """Scatter (v -> p) proposals grouped by destination v.

    Duplicate-edge guards: (a) mutual selection within the batch — if v is
    also being inserted and already chose p as an out-neighbor, the (v -> p)
    proposal is dropped; (b) identical (v, p) pairs (padded tail batches).
    """
    B, R = out_rows.shape
    N = degree.shape[0]
    W = cfg.row_width
    # (a) mutual-selection mask: M[b, c] = batch_ids[c] in out_rows[b]
    is_batch = out_rows[:, :, None] == batch_ids[None, None, :]  # [B, R, B]
    M = jnp.any(is_batch, axis=1)                             # [B, B]
    # proposal (b, j) duplicates iff its target is batch point c whose own
    # out-row already contains batch_ids[b]:  is_batch[b,j,c] & M[c,b]
    mutual = jnp.any(is_batch & M.T[:, None, :], axis=-1)     # [B, R]
    v = out_rows.reshape(-1)                                  # [B*R]
    p = jnp.repeat(batch_ids, R)
    valid = (v >= 0) & ~mutual.reshape(-1)
    v_s = jnp.where(valid, v, N)                              # sentinel last
    # (b) dedup identical (v, p) pairs
    v_s, p_s = jax.lax.sort((v_s, p), num_keys=2)
    dup = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                           (v_s[1:] == v_s[:-1]) & (p_s[1:] == p_s[:-1])])
    v_s = jnp.where(dup, N, v_s)
    # (c) drop proposals already present in v's row (re-insertion passes)
    exists = jnp.any(
        jnp.take(graph, jnp.minimum(v_s, N - 1), axis=0) == p_s[:, None],
        axis=1)
    v_s = jnp.where(exists, N, v_s)
    v_s, p_s = jax.lax.sort((v_s, p_s), num_keys=1)
    ar = jnp.arange(v_s.shape[0], dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), v_s[1:] != v_s[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, ar, 0))
    rank = ar - group_start
    deg_v = jnp.take(degree, jnp.minimum(v_s, N - 1))
    slot = deg_v + rank
    ok = (v_s < N) & (slot < W)
    graph = graph.at[jnp.where(ok, v_s, N), jnp.where(ok, slot, 0)].set(
        p_s, mode="drop")
    # per-group counts at group-end positions -> new degrees
    is_end = jnp.concatenate([v_s[1:] != v_s[:-1],
                              jnp.ones((1,), jnp.bool_)])
    cnt = rank + 1
    newdeg = jnp.minimum(deg_v + cnt, W)
    degree = degree.at[jnp.where(is_end & (v_s < N), v_s, N)].set(
        newdeg, mode="drop")
    # overflow vertices: degree now beyond R -> need re-prune
    over = is_end & (v_s < N) & (newdeg > cfg.degree)
    okey = jnp.where(over, ar, jnp.int32(2 ** 30))
    _, ov_pos = jax.lax.sort((okey, ar), num_keys=1)
    ov_pos = ov_pos[:cfg.ov_max]
    overflow_v = jnp.where(
        jnp.take(over, ov_pos), jnp.take(v_s, ov_pos), -1)    # [ov_max]
    return graph, degree, overflow_v


def _overflow_reprune(graph, degree, xb, xb_norm, attr, ov: jnp.ndarray,
                      cfg: BuildConfig):
    """Re-prune rows whose degree exceeded R (Alg. 3 l.11-12)."""
    W = cfg.row_width
    OV = ov.shape[0]
    vvalid = ov >= 0
    vc = jnp.maximum(ov, 0)
    cand = jnp.take(graph, vc, axis=0)                        # [OV, W]
    cvalid = (cand >= 0) & vvalid[:, None]
    cand = jnp.where(cvalid, cand, -1)
    cand = _dedup_pool(cand, vc)
    cvalid = cand >= 0
    cc = jnp.maximum(cand, 0)

    p_vec = jnp.take(xb, vc, axis=0)
    pn = jnp.take(xb_norm, vc)
    d2_p = _pool_d2(xb, xb_norm, cc, p_vec, pn)
    da_p = dist_a(attr.kind, attr.gather(vc), attr.gather(cc))
    cvec = jnp.take(xb, cc, axis=0).astype(jnp.float32)
    cnorm = jnp.take(xb_norm, cc, axis=0)
    pair_d2 = jnp.maximum(
        cnorm[:, :, None] + cnorm[:, None, :]
        - 2.0 * jnp.einsum("bcd,bed->bce", cvec, cvec), 0.0)

    kw = (dict(thresholds=cfg.thresholds) if cfg.mode == "threshold"
          else dict(weights=cfg.weights))
    selected = joint_robust_prune(cvalid, d2_p, da_p, pair_d2,
                                  degree=cfg.degree, alpha=cfg.alpha,
                                  fill=cfg.fill, **kw)
    new_rows = select_to_rows(selected, cand, d2_p, cfg.degree)
    new_rows = jnp.concatenate(
        [new_rows, jnp.full((OV, W - cfg.degree), -1, jnp.int32)], axis=1)
    graph = graph.at[jnp.where(vvalid, vc, graph.shape[0])].set(
        new_rows, mode="drop")
    degree = degree.at[jnp.where(vvalid, vc, graph.shape[0])].set(
        jnp.sum(new_rows >= 0, axis=1, dtype=jnp.int32), mode="drop")
    return graph, degree


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def medoid(xb: jnp.ndarray) -> jnp.ndarray:
    """Point closest to the dataset mean (entry vertex s)."""
    x = xb.astype(jnp.float32)
    mu = jnp.mean(x, axis=0, keepdims=True)
    return jnp.argmin(jnp.sum((x - mu) ** 2, axis=-1)).astype(jnp.int32)


def make_seeds(xb: jnp.ndarray, n_seeds: int, seed: int = 0) -> jnp.ndarray:
    """Entry set = medoid + stratified random seeds (multi-seed beam init).

    A single-medoid entry can strand well-separated clusters behind pruned
    highways; seeding the beam with a small stratified sample restores
    reachability at negligible cost (beyond-paper robustness fix, DESIGN §2).
    """
    n = xb.shape[0]
    m = int(medoid(xb))
    if n_seeds <= 1 or n <= n_seeds:
        return jnp.asarray([m], jnp.int32)
    rng = np.random.default_rng(seed + 7919)
    strata = np.linspace(0, n, n_seeds, endpoint=False).astype(np.int64)
    extra = (strata + rng.integers(0, max(1, n // n_seeds),
                                   n_seeds)) % n
    ids = np.unique(np.concatenate([[m], extra]))[:n_seeds]
    return jnp.asarray(ids, jnp.int32)


def finalize_graph(graph, degree, xb, xb_norm, attr, cfg: BuildConfig):
    """Drain the overflow backlog: re-prune every row with degree > R.

    Each pass reads the degrees to the host (span ``sync:finalize``); the
    first re-prune call traces the program (``jit:reprune``)."""
    @jax.jit
    def reprune(graph, degree, xb, xb_norm, attr, ov):
        return _overflow_reprune(graph, degree, xb, xb_norm, attr, ov, cfg)

    for i in range(64):  # bounded; each pass fixes up to ov_max rows
        with span("sync:finalize"):
            over = np.flatnonzero(np.asarray(degree) > cfg.degree)
        if over.size == 0:
            break
        chunk = np.full(cfg.ov_max, -1, np.int32)
        chunk[:min(over.size, cfg.ov_max)] = over[:cfg.ov_max]
        with span("compact.reprune", rows=int(min(over.size, cfg.ov_max))), \
                (span("jit:reprune") if i == 0 else nullcontext()):
            graph, degree = reprune(graph, degree, xb, xb_norm, attr,
                                    jnp.asarray(chunk))
    return graph, degree


def build_graph(xb: jnp.ndarray, attr: AttrTable, cfg: BuildConfig,
                seed: int = 0, entry: jnp.ndarray | None = None,
                verbose: bool = False):
    """Full index build. Returns (graph int32[N, R+EX], degree, entry)."""
    N = xb.shape[0]
    xb = jnp.asarray(xb)
    xb_norm = sq_norms(xb)
    if entry is None:
        entry = make_seeds(xb, n_seeds=8, seed=seed)
    graph = jnp.full((N, cfg.row_width), -1, jnp.int32)
    degree = jnp.zeros((N,), jnp.int32)
    insert = make_insert_step(cfg)

    rng = np.random.default_rng(seed)
    Bsz = cfg.batch_size
    n_batches = (N + Bsz - 1) // Bsz
    for pass_i in range(cfg.n_passes):
        order = rng.permutation(N)
        for i in range(n_batches):
            ids = order[i * Bsz:(i + 1) * Bsz]
            if len(ids) < Bsz:  # pad final batch cyclically (dup-tolerant)
                ids = np.resize(ids, Bsz)
            graph, degree = insert(graph, degree, xb, xb_norm, attr,
                                   jnp.asarray(ids, jnp.int32), entry)
            if verbose and (i % 20 == 0 or i == n_batches - 1):
                print(f"  pass {pass_i + 1}/{cfg.n_passes} "
                      f"batch {i + 1}/{n_batches}")
        graph, degree = finalize_graph(graph, degree, xb, xb_norm, attr, cfg)
    return graph, degree, entry
