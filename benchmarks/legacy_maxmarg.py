"""Pre-engine host-loop MAXMARG baselines (benchmark + differential oracle).

These are the MAXMARG protocols exactly as they executed before the batched
engine's MAXMARG selector landed: host-side Python loops over rounds, one
``fit_max_margin`` device call per round, numpy control plane.  Kept for two
reasons only:

* ``benchmarks/maxmarg_sweep.py`` measures the engine's speedup against the
  execution model it replaced (this one);
* ``kparty_maxmarg_hostloop`` doubles as a differential-testing oracle for
  the engine's protocol logic (same selector, same support/violation
  shipping, host-side control flow) — ``tests/test_engine_maxmarg.py``
  asserts identical comm-byte totals across a grid.

One normalization relative to the retired ``src`` code, noted for the
record: per-node error counts are exact integer sums
(``int(np.sum(pred != y))``) rather than ``int(error_rate * n)`` — the
float64 round-trip in the latter could truncate an exact count by one ulp,
which is an artifact of the old accounting, not protocol behavior.

Production code paths must use :mod:`repro.engine` — do not import this
from ``src/``.
"""

from __future__ import annotations

import numpy as np

from repro.core import classifiers as clf
from repro.core.comm import make_nodes
from repro.core.protocols.one_way import ProtocolResult


def _errors(h: clf.LinearSeparator, X: np.ndarray, y: np.ndarray) -> int:
    return int(np.sum(h.predict(X) != y))


def kparty_maxmarg_hostloop(
    shards,
    eps: float = 0.05,
    max_epochs: int = 48,
    max_support: int = 4,
    fit=clf.fit_max_margin,
) -> ProtocolResult:
    """The retired k-party MAXMARG host loop (paper §7 variant): the epoch
    coordinator fits on everything it knows, broadcasts support points, and
    the others reply with their own most-violated points.  ``fit`` is the
    max-margin solver, ``(X, y) -> LinearSeparator``."""
    nodes, log = make_nodes(shards)
    k = len(nodes)
    n_total = sum(nd.n for nd in nodes)
    budget = int(np.floor(eps * n_total))

    h = None
    for epoch in range(max_epochs):
        for ci in range(k):
            log.new_round()
            coord = nodes[ci]
            X, y = coord.all_known()
            h = fit(X, y)
            sidx = clf.support_points(h, X, y, max_support=max_support)
            errs = []
            for nd in nodes:
                if nd is coord:
                    errs.append(_errors(h, nd.X, nd.y))
                    continue
                coord.send_points(nd, X[sidx], y[sidx],
                                  tag="kparty-maxmarg-support")
                e = _errors(h, nd.X, nd.y)
                errs.append(e)
                nd.send_bit(coord, int(e == 0), tag="kparty-maxmarg-ok")
                if e > 0:
                    # reply with the most-violated points (stable: margin
                    # ties break by index, matching the engine's ranking)
                    m = nd.y * (nd.X @ h.w + h.b)
                    worst = np.argsort(m, kind="stable")[:2]
                    nd.send_points(coord, nd.X[worst], nd.y[worst],
                                   tag="kparty-maxmarg-viol")
            if sum(errs) <= budget:
                return ProtocolResult(h, log.summary(), rounds=epoch + 1,
                                      converged=True)
    return ProtocolResult(h, log.summary(), rounds=max_epochs,
                          converged=False)


def two_party_maxmarg_hostloop(
    shards,
    eps: float = 0.05,
    max_rounds: int = 64,
    max_support: int = 6,
) -> ProtocolResult:
    """The retired asymmetric two-party MAXMARG host loop (alternating
    senders, value-level dedup of reshipped support points).  Benchmark
    reference only — the public two-party API is now the k=2 instance of the
    k-party support-exchange protocol on the engine."""
    nodes, log = make_nodes(shards[:2])
    A, B = nodes
    n_total = A.n + B.n
    budget = int(np.floor(eps * n_total))

    sent_ids = {A.name: set(), B.name: set()}
    h = None
    for rnd in range(max_rounds):
        log.new_round()
        src, dst = (A, B) if rnd % 2 == 0 else (B, A)
        Xk, yk = src.all_known()
        h = clf.fit_max_margin(Xk, yk)
        sidx = clf.support_points(h, Xk, yk, max_support=max_support)
        # ship only points the peer has not seen from us (dedup by value)
        new_pts, new_labs = [], []
        for i in sidx:
            if i >= src.n:  # a received point — the peer may already know it
                key = (round(float(Xk[i, 0]), 9),
                       round(float(Xk[i, 1] if Xk.shape[1] > 1 else 0.0), 9),
                       int(yk[i]))
            else:
                key = (int(i), int(yk[i]), "own")
            if key in sent_ids[src.name]:
                continue
            sent_ids[src.name].add(key)
            new_pts.append(Xk[i])
            new_labs.append(yk[i])
        if new_pts:
            src.send_points(dst, np.stack(new_pts),
                            np.asarray(new_labs, dtype=np.int32),
                            tag="maxmarg-support")
        err = _errors(h, src.X, src.y) + _errors(h, dst.X, dst.y)
        dst.send_bit(src, int(err <= budget), tag="accept")
        if err <= budget:
            return ProtocolResult(h, log.summary(), rounds=rnd + 1,
                                  converged=True)
    return ProtocolResult(h, log.summary(), rounds=max_rounds,
                          converged=False)
