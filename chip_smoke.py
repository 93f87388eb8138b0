#!/usr/bin/env python3
"""Smoke test of the served protocol path on a TPU.

Drives ``ProtocolService`` -> ``SessionPool`` -> the pinned pool turn (and,
inside it, the Pallas Pegasos solver kernel) once, through the entry points
a caller uses, at deployment size, and checks every finished session
against plain host references that do not run the engine:

  (a) MEDIAN pool, k=2, d=2;
  (b) MAXMARG pool, k=4, d=10 (paper Table 3's d=10 lift, Table 4's k=4);
  (c) unified pool, k=4, d=2, interleaving MEDIAN, MAXMARG and SAMPLING;

each with 64 sessions whose nodes stream 2048 points apiece in batches
through ``open``/``feed``/``close`` into 32 slots padded to n_pad=2048.
MEDIAN sessions are checked against ``benchmarks/legacy_median.py`` and
MAXMARG sessions against ``benchmarks/legacy_maxmarg.py`` (convergence,
rounds and the full communication record identical, separators within
the tolerances stated below); SAMPLING sessions must ship exactly their
reservoir and separate their sample.  Pools (b) and (c) must carry the
Pegasos kernel (``tpu_custom_call``) in their lowered dispatch.

``--chips 4`` runs only the sharded sweeps instead: a MEDIAN and a MAXMARG
``run_instances`` over a 4-device ("data",) mesh against the same sweeps
on one device (MEDIAN bit-exact, MAXMARG decision-exact), printing which
device holds each shard.

Observations (compile seconds, sessions finished, wall seconds, peak device
memory) go to earlier lines; the last line is one JSON object with the
device.  Exits non-zero, with no result line, when JAX finds no TPU or any
check fails.  Usage::

    python chip_smoke.py [--seed N] [--chips 1|4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from benchmarks.legacy_maxmarg import kparty_maxmarg_hostloop  # noqa: E402
from benchmarks.legacy_median import kparty_median_hostloop  # noqa: E402
from repro import engine  # noqa: E402
from repro.core import classifiers as clf  # noqa: E402
from repro.core import datasets  # noqa: E402
from repro.core.sampling import epsilon_net_size  # noqa: E402
from repro.engine.session_pool import PoolConfig  # noqa: E402
from repro.engine.state import ProtocolInstance  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.serve.service import ProtocolService  # noqa: E402

N_POINTS = 2048          # points streamed into each node
SESSIONS = 64            # sessions per pool
SLOTS = 32
FEED_BATCH = 512

# MEDIAN separators: the direction is the same grid vector on both sides;
# the offset is a band midpoint the pool takes in f32 and the reference in
# f64, from the same f32 points of norm < 10, so the two differ by a few
# f32 ulps of the projections.
MEDIAN_B_ATOL = 1e-5
# MAXMARG separators: both sides run the same annealed Pegasos schedule on
# the same fit set, but the pool fits 32 padded slots at the pinned
# transcript width and the reference one unpadded set, so point sums
# reassociate and 3 x 2000 projected-subgradient steps carry those
# ulp-level differences into the iterate.  The check is on the angle
# between the (w, b) vectors.
MAXMARG_COS_TOL = 1e-4


def _shards(i: int, k: int, d: int, n: int, seed: int):
    gen = (datasets.data1, datasets.data2, datasets.data3)[i % 3]
    s = gen(n_per_node=n, k=k, seed=seed * 1000 + i)
    if d > 2:
        s = datasets.lift_dim(s, d, seed=seed * 1000 + i)
    return [(X.astype(np.float32), y.astype(np.int32)) for X, y in s]


def _vitter_sample(shards, seed: int, cap: int, n_pad: int):
    """The SAMPLING chain's reservoir, recomputed on the host: hop i
    ingests node i's shard with the session's i-th hop key (Vitter's
    algorithm: positional fill, then slot ``randint(0, t)`` for the t-th
    point, later points winning collisions)."""
    k, d = len(shards), shards[0][0].shape[1]
    keys = jax.random.split(jax.random.PRNGKey(seed), k - 1)
    resX = np.zeros((cap, d), np.float32)
    resy = np.zeros((cap,), np.int32)
    seen, shipped = 0, 0
    for i in range(k - 1):
        X = np.zeros((n_pad, d), np.float32)
        y = np.zeros((n_pad,), np.int32)
        X[:len(shards[i][1])], y[:len(shards[i][1])] = shards[i]
        valid = y != 0
        t = seen + np.cumsum(valid).astype(np.int32)
        draw = np.asarray(jax.random.randint(
            keys[i], (n_pad,), 0, jnp.asarray(np.maximum(t, 1), jnp.int32)))
        j = np.where(t <= cap, t - 1, draw)
        for r in np.flatnonzero(valid & (j < cap)):
            resX[j[r]], resy[j[r]] = X[r], y[r]
        seen += int(valid.sum())
        shipped += min(seen, cap)
    fill = min(seen, cap)
    return resX[:fill], resy[:fill], shipped


def _fit_padded(X, y):
    """``fit_max_margin`` with the fit set padded by inert label-0 rows to
    a multiple of 512 before the solve.  The solver kernel pads its point
    axis to its tile anyway, so the separator is the same bit for bit;
    the padding only keeps the host loop's growing fit sets to a handful
    of compiled shapes."""
    pad = -X.shape[0] % 512
    Xp = np.concatenate([X, np.zeros((pad, X.shape[1]))])
    yp = np.concatenate([y, np.zeros((pad,), y.dtype)])
    w, b, _ = clf.anneal_hard_margin(Xp, yp, lam=1e-3, steps=2000, stages=3)
    return clf.LinearSeparator(w, float(b))


def _check(cfg, sel, res, shards, seed):
    """``(mismatch description or None, separator gap)`` for one finished
    session; the gap is |Δb| for MEDIAN, 1 - cosine for MAXMARG."""
    w = np.asarray(res.classifier.w, np.float64)
    b = float(res.classifier.b)
    if sel == "sampling":
        cap = epsilon_net_size(cfg.eps, cfg.d + 1)
        Xs, ys, shipped = _vitter_sample(shards, seed, cap, cfg.n_pad)
        if res.comm["points"] != shipped:
            return (f"shipped {res.comm['points']} points, reservoir "
                    f"{shipped}", None)
        errs = int(np.sum(res.classifier.predict(Xs) != ys))
        return (f"{errs} errors on its sample" if errs else None), None
    if sel == "median":
        ref = kparty_median_hostloop(shards, eps=cfg.eps,
                                     max_epochs=cfg.max_epochs,
                                     n_angles=cfg.n_angles)
    else:
        ref = kparty_maxmarg_hostloop(shards, eps=cfg.eps,
                                      max_epochs=cfg.max_epochs,
                                      max_support=cfg.max_support,
                                      fit=_fit_padded)
    if (res.converged, res.rounds, res.comm) != (ref.converged, ref.rounds,
                                                 ref.comm):
        return (f"decisions: pool {(res.converged, res.rounds, res.comm)} "
                f"reference {(ref.converged, ref.rounds, ref.comm)}"), None
    wr = np.asarray(ref.classifier.w, np.float64)
    br = float(ref.classifier.b)
    if sel == "median":
        gap = abs(b - br)
        ok = np.array_equal(w, wr) and gap <= MEDIAN_B_ATOL
    else:
        u, v = np.append(w, b), np.append(wr, br)
        gap = 1.0 - float(u @ v / (np.linalg.norm(u) * np.linalg.norm(v)))
        ok = gap <= MAXMARG_COS_TOL
    return (None if ok else f"separator: pool {(w, b)} reference "
            f"{(wr, br)}"), gap


def serve_pool(cfg, selectors, *, seed: int, n_points: int, sessions: int,
               feed_batch: int):
    """Stream ``sessions`` sessions through one ``ProtocolService`` and
    check each finished one.  Returns a dict of observations, the lowered
    pinned dispatch, and the list of mismatches."""
    svc = ProtocolService(cfg, ingest_seed=seed)
    fn, args, kw = svc.pool.turn_call(np.arange(cfg.slots))
    t0 = time.perf_counter()
    lowered = fn.lower(*args, **kw)
    lowered.compile()
    compile_s = time.perf_counter() - t0

    entries = []
    t0 = time.perf_counter()
    for i in range(sessions):
        sel = selectors[i % len(selectors)]
        shards = _shards(i, cfg.k, cfg.d, n_points, seed)
        h = svc.open(selector=sel if cfg.selector == "unified" else None,
                     seed=seed * 1000 + i)
        for node, (X, y) in enumerate(shards):
            for s in range(0, n_points, feed_batch):
                svc.feed(h, node, X[s:s + feed_batch], y[s:s + feed_batch])
        entries.append((svc.close(h), sel, shards, seed * 1000 + i))
        if (i + 1) % max(1, cfg.slots // 2) == 0:
            svc.step()          # admission interleaves with live sessions
    svc.run()
    wall_s = time.perf_counter() - t0

    mismatches, finished, gaps = [], 0, {}
    t0 = time.perf_counter()
    for sid, sel, shards, sseed in entries:
        res = svc.result(sid)
        if res is None:
            mismatches.append(f"sid {sid} ({sel}): {svc.status(sid)}")
            continue
        finished += 1
        why, gap = _check(cfg, sel, res, shards, sseed)
        if why:
            mismatches.append(f"sid {sid} ({sel}): {why}")
        if gap is not None:
            gaps[sel] = max(gaps.get(sel, 0.0), gap)
    return {"compile_s": compile_s, "finished": finished, "wall_s": wall_s,
            "reference_s": time.perf_counter() - t0,
            "pool_turns": svc.stats["pool_turns"],
            "converged": svc.stats["evicted_converged"],
            "separator_gap": gaps}, lowered, mismatches


def pools_phase(seed: int, n_points=N_POINTS, sessions=SESSIONS,
                slots=SLOTS, feed_batch=FEED_BATCH, need_kernel=True):
    """The one-chip phase: the three pools.  Returns True when every
    session finished and matched its reference, and pools (b) and (c)
    carry the Pegasos kernel."""
    n_pad = max(8, n_points)
    pools = [
        ("a-median", PoolConfig(selector="median", k=2, d=2, n_pad=n_pad,
                                slots=slots), ("median",), False),
        ("b-maxmarg", PoolConfig(selector="maxmarg", k=4, d=10,
                                 n_pad=n_pad, slots=slots),
         ("maxmarg",), True),
        ("c-unified", PoolConfig(selector="unified", k=4, d=2, n_pad=n_pad,
                                 slots=slots),
         ("median", "maxmarg", "sampling"), True),
    ]
    ok = True
    for name, cfg, selectors, kernel in pools:
        try:
            obs, lowered, bad = serve_pool(
                cfg, selectors, seed=seed, n_points=n_points,
                sessions=sessions, feed_batch=feed_batch)
        except Exception:  # noqa: BLE001 — report, then try the next pool
            traceback.print_exc(file=sys.stdout)
            print(f"pool {name}: FAILED", flush=True)
            ok = False
            continue
        has_kernel = "tpu_custom_call" in lowered.as_text()
        print(f"pool {name}: k={cfg.k} d={cfg.d} n_pad={cfg.n_pad} "
              f"slots={cfg.slots} compile_s={obs['compile_s']!r} "
              f"sessions_finished={obs['finished']}/{sessions} "
              f"converged={obs['converged']} wall_s={obs['wall_s']!r} "
              f"pool_turns={obs['pool_turns']} "
              f"reference_s={obs['reference_s']!r} "
              f"max_separator_gap={obs['separator_gap']} "
              f"pegasos_kernel={has_kernel} mismatches={len(bad)}",
              flush=True)
        for line in bad[:10]:
            print(f"  mismatch {line}", flush=True)
        if need_kernel and kernel and not has_kernel:
            print(f"  pool {name}: no tpu_custom_call in its lowered "
                  "dispatch", flush=True)
            ok = False
        ok = ok and not bad and obs["finished"] == sessions
    return ok


def sharded_phase(n_dev: int, seed: int, n_points=N_POINTS, n_median=32,
                  n_maxmarg=16, n_angles=1024, max_epochs=16):
    """MEDIAN and MAXMARG sweeps over an ``n_dev``-device ("data",) mesh
    against the same sweeps on one device."""
    mesh = make_data_mesh(n_dev)
    med = [ProtocolInstance(_shards(i, 2, 2, n_points, seed),
                            0.05) for i in range(n_median)]
    mm = [ProtocolInstance(_shards(i, 4, 10, n_points, seed),
                           0.05, "maxmarg") for i in range(n_maxmarg)]
    data, *_ = engine.pack_instances(med, n_angles=n_angles,
                                     max_epochs=max_epochs, mesh=mesh)
    held = sorted({s.device.id for s in data.X.addressable_shards})
    for s in data.X.addressable_shards:
        print(f"shard rows {s.index[0]} on device {s.device.id} "
              f"({s.device.device_kind})", flush=True)
    ok = held == sorted(d.id for d in mesh.devices.flat)
    if not ok:
        print(f"shards landed on devices {held} only", flush=True)

    def timed(f, *a, **k):
        t0 = time.perf_counter()
        out = f(*a, **k)
        return out, time.perf_counter() - t0

    sh, t_sh = timed(engine.run_instances, med, n_angles=n_angles,
                     max_epochs=max_epochs, mesh=mesh)
    one, t_one = timed(engine.run_instances, med, n_angles=n_angles,
                       max_epochs=max_epochs)
    bad_med = [i for i, (a, b) in enumerate(zip(sh, one))
               if (a.comm, a.rounds, a.converged, float(a.classifier.b))
               != (b.comm, b.rounds, b.converged, float(b.classifier.b))
               or not np.array_equal(a.classifier.w, b.classifier.w)]
    print(f"median sweep: B={n_median} {n_dev}-device wall_s={t_sh!r} "
          f"1-device wall_s={t_one!r} converged="
          f"{sum(r.converged for r in sh)} bit_mismatches={bad_med}",
          flush=True)

    sh, t_sh = timed(engine.maxmarg.run_instances, mm,
                     max_epochs=max_epochs, mesh=mesh)
    one, t_one = timed(engine.maxmarg.run_instances, mm,
                       max_epochs=max_epochs)
    bad_mm = []
    for i, (inst, a, b) in enumerate(zip(mm, sh, one)):
        X = np.concatenate([s[0] for s in inst.shards])
        if ((a.comm, a.rounds, a.converged) != (b.comm, b.rounds, b.converged)
                or not np.array_equal(a.classifier.predict(X),
                                      b.classifier.predict(X))):
            bad_mm.append(i)
    print(f"maxmarg sweep: B={n_maxmarg} {n_dev}-device wall_s={t_sh!r} "
          f"1-device wall_s={t_one!r} converged="
          f"{sum(r.converged for r in sh)} decision_mismatches={bad_mm}",
          flush=True)
    return ok and not bad_med and not bad_mm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1
    print(f"compile cache: {compile_cache.enable()}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        ok = sharded_phase(4, args.seed)
    else:
        ok = pools_phase(args.seed)
    stats = devices[0].memory_stats() or {}
    print(f"total_s={time.perf_counter() - t0!r} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
