"""The comparison that decides ``correct`` for served sessions.

Each sampled session's inputs (recomputed from the seed, through the
reference reservoir for streamed sessions) run through the plain
reference of its protocol.  These numbers are compared with the limits
in the configuration's ``check.limits``:

* ``unfinished_sessions``: sessions due in the window with no result a
  minute after it closed (limit 0);
* ``guarantee_violations``: sampled sessions reported converged whose
  separator errs on more than ``floor(eps * n)`` of the session's points,
  counted in float64 (limit 0);
* ``mismatched_sessions``: sampled sessions whose convergence, rounds or
  communication record (points, scalars, bits, messages, rounds, bytes)
  differ from the reference's in any way (limit 0: exact);
* ``separator_gap``: the widest gap between a sampled session's
  separator and the reference's: for MEDIAN ``max(|w - w_ref|_inf,
  |b - b_ref|)``, for MAXMARG the distance between the unit vectors of
  ``(w, b)`` and ``(w_ref, b_ref)``;
* ``inconsistent_records``: sampled sessions whose communication record
  breaks the protocol's own accounting, whatever path the session took
  (limit 0): the bytes are the float32 wire size of the points, scalars
  and bits; a converged session's epochs are its turns over k, rounded
  up, and an unconverged one ran ``max_epochs`` epochs; and for MAXMARG,
  each turn sends every peer one bit, one message of support points (the
  same to each) and at most one message of two violators back.

For MAXMARG the reference runs every sampled session whole.  A session
that the reference or the program ends at its first turn, which decides
convergence from error counts alone, is compared exactly
(``mismatched_sessions``) and its separator within ``separator_gap``.
Past the first turn, the support points a session ships turn on margin
orderings at the scale to which the solver settles a separator (about
1e-4), so two sound float32 solvers can lead a session onto different
later turns (PERF.md, section 4).  Those sessions are held to
``multi_turn_mismatch_pct``: the share of the sampled sessions that go
past their first turn whose whole-session decisions differ from the
reference's, with a limit set between what the program reads and what
the control and a planted later-turn fault read.

A ``mode`` other than ``"highest"`` runs the reference at the lower
precision of a control (see ``bench/tools/control.py``).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.common import Check


def reference(protocol: str, pool: dict, inputs: List, mode: str,
              **kw) -> List[dict]:
    """The reference's result of every session, run whole."""
    if protocol == "median":
        from bench.reference import median
        proj = {"highest": median.proj_f64, "high": median.proj_bf16x3}[mode]
        return [median.run(s, eps=pool["eps"], max_epochs=pool["max_epochs"],
                           n_angles=pool["n_angles"], proj=proj)
                for s in inputs]
    from bench.reference import maxmarg
    return maxmarg.run_batch(
        inputs, eps=pool["eps"], max_epochs=pool["max_epochs"],
        max_support=pool["max_support"], lam0=pool.get("lam0", 1e-3),
        steps=pool["svm_steps"], stages=pool["svm_stages"], mode=mode, **kw)


def separator_gap(protocol: str, got: dict, ref: dict) -> float:
    w, wr = np.asarray(got["w"], np.float64), np.asarray(ref["w"], np.float64)
    if protocol == "median":
        return float(max(np.max(np.abs(w - wr)), abs(got["b"] - ref["b"])))
    u, v = np.append(w, got["b"]), np.append(wr, ref["b"])
    return float(np.linalg.norm(u / np.linalg.norm(u) - v / np.linalg.norm(v)))


def decisions(r: dict) -> tuple:
    return (bool(r["converged"]), int(r["rounds"]),
            tuple(sorted((k, int(v)) for k, v in r["comm"].items())))


def violates(r: dict, shards, eps: float) -> bool:
    """A converged result whose separator errs on more than its budget."""
    if not r["converged"]:
        return False
    w = np.asarray(r["w"], np.float64)
    n = sum(len(y) for _X, y in shards)
    errs = sum(int(np.sum(np.where(np.asarray(X, np.float64) @ w + r["b"]
                                   > 0, 1, -1) != y)) for X, y in shards)
    return errs > int(np.floor(eps * n))


def consistent(protocol: str, r: dict, pool: dict) -> bool:
    """Whether a result's communication record keeps the protocol's
    accounting identities (see the module's docstring)."""
    c = {key: int(v) for key, v in r["comm"].items()}
    k, d, turns = pool["k"], pool["d"], c["rounds"]
    wire = (c["points"] * (d + 1) + c["scalars"]) * 32 + c["bits"]
    ok = c["bytes"] == -(-wire // 8) and turns >= 1
    if r["converged"]:
        ok &= int(r["rounds"]) == -(-turns // k)
    else:
        ok &= (int(r["rounds"]) == pool["max_epochs"]
               and turns == pool["max_epochs"] * k)
    if protocol == "maxmarg":
        peers = (k - 1) * turns
        back = c["messages"] - 2 * peers          # violator messages
        band = c["points"] - 2 * back             # support points sent
        ok &= (c["scalars"] == 0 and c["bits"] == peers
               and 0 <= back <= peers and band % (k - 1) == 0
               and 0 <= band <= pool["max_support"] * peers)
    return bool(ok)


def turns(r: dict) -> int:
    return int(r["comm"]["rounds"])


def compare(protocol: str, got: List[dict], ref: List[dict]) -> Dict:
    """Every sampled session against the reference's: for MEDIAN, and for
    a MAXMARG session that either side ends at its first turn, the
    decisions exactly and the separators' gap; for the other MAXMARG
    sessions, the share whose decisions differ."""
    bad, gaps, multi, apart = [], [], 0, 0
    for i, (g, r) in enumerate(zip(got, ref)):
        if protocol == "maxmarg" and turns(g) > 1 and turns(r) > 1:
            multi += 1
            apart += decisions(g) != decisions(r)
            continue
        if decisions(g) != decisions(r):
            bad.append(f"session {i}: got {decisions(g)} reference "
                       f"{decisions(r)}")
        gaps.append(separator_gap(protocol, g, r))
    return {"mismatched": len(bad), "gap": max(gaps) if gaps else 0.0,
            "compared": len(gaps), "lines": bad, "multi_turn": multi,
            "multi_turn_pct": 100.0 * apart / multi if multi else 0.0}


def numbers(protocol: str, pool: dict, inputs: List, got: List[dict],
            ref: List[dict], failed: int) -> Dict:
    """Every number compared, for results ``got`` of sessions with
    ``inputs`` against the reference's ``ref``."""
    cmp = compare(protocol, got, ref)
    eps = pool["eps"]
    out = {
        "unfinished_sessions": float(failed),
        "guarantee_violations": float(sum(
            violates(g, s, eps) for g, s in zip(got, inputs))),
        "mismatched_sessions": float(cmp["mismatched"]),
        "separator_gap": cmp["gap"],
        "inconsistent_records": float(sum(
            not consistent(protocol, g, pool) for g in got)),
    }
    if protocol == "maxmarg":
        out["multi_turn_mismatch_pct"] = cmp["multi_turn_pct"]
    return dict(out, _cmp=cmp)


def run(entry, config: dict) -> tuple:
    """``(checks, notes)`` for a served run whose window has closed and
    whose program state the entry has released."""
    protocol = config["protocol"]
    limits = config["check"]["limits"]
    sids = entry.sample()
    inputs = [entry.inputs(s) for s in sids]
    ref = reference(protocol, config["pool"], inputs, "highest")
    got = [entry.results[s] for s in sids]
    got_n = numbers(protocol, config["pool"], inputs, got, ref,
                    entry.counts()[1])
    cmp = got_n.pop("_cmp")
    checks = [Check(name, value, float(limits[name]))
              for name, value in got_n.items()]
    notes = [f"checked {len(sids)} sessions, separators of "
             f"{cmp['compared']}, {cmp['multi_turn']} past their first "
             "turn on both sides"] + [
        f"sid {sids[int(line.split()[1][:-1])]}: {line}"
        for line in cmp["lines"][:5]]
    return checks, notes
