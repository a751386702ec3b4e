"""The plain reference and the comparison that decides ``correct``.

NumPy only, and independent of the program: filtered k-nearest neighbours
by brute force in float64 over every row that passes the query's filter,
ties to the lower id, ``-1`` where fewer than k rows pass. Recall follows
the filtered-ANN convention (``repro.core.recall.recall_at_k``): a query's
recall is |served ∩ reference| / |reference|, a query that no row passes
counts 1.

:func:`compare` turns served answers into the numbers that are held
against the configuration's limits (``guarantees.limits``):

- ``dist_err``: the widest gap between a distance the program reports
  and the float64 distance of the row it names, over every served row,
  as a share of the median k-th reference distance. The program computes
  distances in float32; a lower precision reads several times higher.
- ``rank_gap``: on queries an exact route served, the widest gap between
  the float64 distance of the j-th served row and of the j-th reference
  row, as the same share. An exact scan reads 0 up to float32 ties.
- ``short``: exact-route queries that return fewer rows than the
  reference holds.
- ``violations``: served rows that fail their query's filter, repeat
  within a query, lie outside the database, or carry no finite distance.
- ``approx_recall``: recall@k over the queries an approximate route
  served; its limit is the configuration's stated recall floor.
- ``replays``: window answers that differ from the first answer to the
  same batch (``replay_mismatch``, counted by the harness).
"""
from __future__ import annotations

import numpy as np


def passes(spec: dict, attr: np.ndarray, filt: dict, i: int) -> np.ndarray:
    """Boolean [N]: rows whose attribute passes query ``i``'s filter."""
    if spec["kind"] == "range":
        return (attr >= filt["lo"][i]) & (attr <= filt["hi"][i])
    return attr == filt["label"][i]


class Reference:
    """Brute-force filtered top-k over one database, in float64.

    Rows are kept in attribute order, so the rows that pass a range or a
    label are one contiguous slice. Queries whose slice holds more than a
    quarter of the rows share one matrix product over every row; the
    others are grouped by slice, one product per group."""

    def __init__(self, xb: np.ndarray, attr: np.ndarray, spec: dict):
        self.order = np.argsort(attr, kind="stable")
        self.sattr = attr[self.order]
        self.xs = xb[self.order].astype(np.float64)
        self.xns = np.einsum("nd,nd->n", self.xs, self.xs)
        self.spec = spec

    def bounds(self, filt: dict):
        """Slice [a, b) of the attribute order that each query passes."""
        if self.spec["kind"] == "range":
            lo, hi = filt["lo"], filt["hi"]
        else:
            lo = hi = filt["label"]
        return (np.searchsorted(self.sattr, lo, "left"),
                np.searchsorted(self.sattr, hi, "right"))

    def topk(self, batch, k: int):
        """Reference ids int64 [B, k] and float64 squared distances
        [B, k] (``inf`` on padding) for one batch."""
        B = batch.queries.shape[0]
        ids = np.full((B, k), -1, np.int64)
        dist = np.full((B, k), np.inf)
        q64 = batch.queries.astype(np.float64)
        qn = np.einsum("bd,bd->b", q64, q64)
        a, b = self.bounds(batch.filt)

        def pick(i, d2, lo):
            rows = self.order[lo:lo + d2.size]
            if rows.size > k:
                keep = d2 <= np.partition(d2, k - 1)[k - 1]
                rows, d2 = rows[keep], d2[keep]
            top = np.lexsort((rows, d2))[:k]
            ids[i, :top.size] = rows[top]
            dist[i, :top.size] = d2[top]

        wide = (b - a) > self.xs.shape[0] // 4
        if wide.any():
            w = np.flatnonzero(wide)
            d2 = q64[w] @ self.xs.T
            d2 *= -2.0
            d2 += self.xns
            d2 += qn[w, None]
            for j, i in enumerate(w):
                pick(i, d2[j, a[i]:b[i]], a[i])
        narrow = np.flatnonzero(~wide & (b > a))
        groups = {}
        for i in narrow:
            groups.setdefault((a[i], b[i]), []).append(i)
        for (lo, hi), members in groups.items():
            d2 = q64[members] @ self.xs[lo:hi].T
            d2 *= -2.0
            d2 += self.xns[lo:hi]
            d2 += qn[members, None]
            for j, i in enumerate(members):
                pick(i, d2[j], lo)
        return ids, dist


def recall(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-query recall float64 [B] of served ids against reference ids."""
    out = np.ones(served.shape[0])
    for i in range(served.shape[0]):
        want = set(ref[i][ref[i] >= 0].tolist())
        if want:
            out[i] = len(want & set(served[i].tolist())) / len(want)
    return out


class Tally:
    """Accumulates the compared numbers over the batches of a check."""

    def __init__(self, exact_routes):
        self.exact_routes = set(exact_routes)
        self.scale = []         # k-th reference distance per query
        self.dist_abs = 0.0     # widest |reported - float64| distance
        self.rank_abs = 0.0     # widest served-vs-reference gap, exact
        self.short = 0
        self.violations = 0
        self.approx_hits = 0.0
        self.approx_n = 0
        self.recalls = []
        self.routes = {}

    def add(self, xb, attr, spec, batch, served_ids, served_d2, routes,
            ref_ids, ref_d2) -> None:
        N = xb.shape[0]
        q64 = batch.queries.astype(np.float64)
        self.recalls.append(recall(served_ids, ref_ids))
        for i in range(served_ids.shape[0]):
            route = routes[i].split("+")[0]
            self.routes[route] = self.routes.get(route, 0) + 1
            n_ref = int((ref_ids[i] >= 0).sum())
            if n_ref:
                self.scale.append(ref_d2[i, n_ref - 1])
            got = served_ids[i]
            valid = got >= 0
            ids = got[valid].astype(np.int64)
            bad = (ids >= N).sum() + (ids.size - np.unique(ids).size)
            ids = ids[ids < N]
            ok = passes(spec, attr[ids], batch.filt, i) if ids.size else \
                np.zeros(0, bool)
            self.violations += int(bad + (~ok).sum())
            if ids.size:
                diff = xb[ids].astype(np.float64) - q64[i]
                true = np.einsum("nd,nd->n", diff, diff)
                rep = served_d2[i][valid][:ids.size].astype(np.float64)
                # a returned row without a finite distance is a violation
                fin = np.isfinite(rep)
                self.violations += int((~fin).sum())
                if fin.any():
                    self.dist_abs = max(self.dist_abs, float(
                        np.abs(rep[fin] - true[fin]).max()))
            else:
                true = np.zeros(0)
            if route in self.exact_routes:
                if ids.size < n_ref:
                    self.short += 1
                m = min(ids.size, n_ref)
                if m:
                    gap = np.sort(true)[:m] - ref_d2[i, :m]
                    self.rank_abs = max(self.rank_abs, float(gap.max()))
            else:
                self.approx_hits += self.recalls[-1][i]
                self.approx_n += 1

    def numbers(self, replays: int) -> dict:
        scale = float(np.median(self.scale)) if self.scale else 1.0
        return {
            "dist_err": self.dist_abs / scale,
            "rank_gap": self.rank_abs / scale,
            "short": self.short,
            "violations": self.violations,
            "approx_recall": (float(self.approx_hits) / self.approx_n
                              if self.approx_n else 1.0),
            "replays": replays,
        }

    def recall_at_k(self) -> float:
        return float(np.concatenate(self.recalls).mean())


def judge(numbers: dict, limits: dict) -> dict:
    """Each number beside its limit; ``approx_recall`` is a floor, every
    other number a ceiling."""
    out = {}
    for name, value in numbers.items():
        lim = limits[name]
        ok = value >= lim if name == "approx_recall" else value <= lim
        out[name] = {"value": value, "limit": lim, "ok": bool(ok)}
    return out
