"""The MAXMARG sweep over a ("data",) mesh against the plain host loop.

``run_sweep(mesh=make_data_mesh(n))`` at k=4, d=10 over twelve instances
(lifted Data1/2/3 alternating with mixed-hardness) goes through
``shard_map``, ``hotloop.balanced_index``, donation and the
double-buffered host loop even on one device, so these tests run in any
process; the four-device case skips only where the process has fewer
than four devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``
before jax starts gives them).

The oracle is ``benchmarks/legacy_maxmarg.kparty_maxmarg_hostloop``, whose
coordinator refits cold every turn.  A sweep that refits cold too
(``warm=False``) must agree with it instance by instance: converged flag,
rounds and the whole communication record exactly, the separator within
``chip_smoke.MAXMARG_COS_TOL`` (1 - cos <= 1e-4).  The sweep's default
warm polish settles the separator elsewhere at the 1e-4 scale, and past a
session's first turn the support points turn on margin orderings at that
scale, so a warm session that runs several turns may take other (equally
valid) turns than the cold host loop; the warm sweep is held exactly to
the host loop where either side ends at the first turn, and bit for bit
to the unsharded warm hot path everywhere.

The sweep's counters (``stats``) and its ``sweep.*`` spans are pinned too.
"""

import collections
import glob
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro import engine  # noqa: E402
from repro.core import classifiers as clf  # noqa: E402
from repro.core import datasets  # noqa: E402
from repro.engine import hotloop  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402

from benchmarks.legacy_maxmarg import kparty_maxmarg_hostloop  # noqa: E402

K, D, N, EPS, EPOCHS, STEPS = 4, 10, 200, 0.05, 16, 2000
COS_TOL = 1e-4          # chip_smoke.MAXMARG_COS_TOL
OPTS = dict(max_epochs=EPOCHS, max_support=4, steps=STEPS, stages=3,
            lam=1e-3)
_GENS = (datasets.data1, datasets.data2, datasets.data3)


def _instances():
    """Twelve k=4 instances lifted to d=10: Data1/2/3 alternating with
    mixed-hardness (gap 0.15), as the benchmark's bank alternates them."""
    out = []
    for i in range(12):
        if i % 2:
            s = datasets.data_mixed_hardness(n_per_node=N, k=K, seed=i,
                                             gap=0.15)
        else:
            s = _GENS[(i // 2) % 3](n_per_node=N, k=K, seed=i)
        s = [(X.astype(np.float32), y.astype(np.int32))
             for X, y in datasets.lift_dim(s, D, seed=i)]
        out.append(engine.ProtocolInstance(s, EPS, "maxmarg"))
    return out


def _fit(X, y):
    """The host loop's cold refit, its fit set padded with inert label-0
    rows to a multiple of 256 so that few programs compile."""
    pad = -X.shape[0] % 256
    Xp = np.concatenate([X, np.zeros((pad, X.shape[1]), X.dtype)])
    yp = np.concatenate([y, np.zeros((pad,), y.dtype)])
    w, b, _ = clf.anneal_hard_margin(Xp, yp, lam=1e-3, steps=STEPS,
                                     stages=3)
    return clf.LinearSeparator(w, float(b))


@pytest.fixture(scope="module")
def insts():
    return _instances()


@pytest.fixture(scope="module")
def host_loop(insts):
    return [kparty_maxmarg_hostloop(i.shards, eps=EPS, max_epochs=EPOCHS,
                                    max_support=4, fit=_fit) for i in insts]


@pytest.fixture(scope="module")
def warm_sweep(insts):
    """The cell's path on a one-device mesh, with its counters."""
    stats = {}
    n0 = len(hotloop.KEY_LOG)
    res = engine.run_sweep(insts, mesh=make_data_mesh(1), stats=stats,
                           **OPTS)
    return res, stats, len(hotloop.KEY_LOG) - n0


def _mesh(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices, the process has "
                    f"{len(jax.devices())}")
    return make_data_mesh(n)


def _decisions(r):
    return r.converged, r.rounds, r.comm


def _cos_gap(a, b):
    u = np.append(np.asarray(a.classifier.w, np.float64), a.classifier.b)
    v = np.append(np.asarray(b.classifier.w, np.float64), b.classifier.b)
    return 1.0 - float(u @ v / (np.linalg.norm(u) * np.linalg.norm(v)))


def _errors(r, inst):
    return sum(int(np.sum(r.classifier.predict(X) != y))
               for X, y in inst.shards)


@pytest.mark.parametrize("devices", [1, 4])
def test_cold_sweep_matches_host_loop(insts, host_loop, devices):
    res = engine.run_sweep(insts, mesh=_mesh(devices), warm=False, **OPTS)
    assert any(r.comm["rounds"] > K for r in res)    # multi-epoch sessions
    for i, (r, ref) in enumerate(zip(res, host_loop)):
        assert _decisions(r) == _decisions(ref), i
        assert _cos_gap(r, ref) <= COS_TOL, i
        assert r.extra["devices"] == devices


def test_warm_sweep_against_host_loop(insts, host_loop, warm_sweep):
    res, _stats, _n = warm_sweep
    first_turn = 0
    for i, (inst, r, ref) in enumerate(zip(insts, res, host_loop)):
        assert r.converged, i
        n = sum(len(y) for _X, y in inst.shards)
        assert _errors(r, inst) <= int(np.floor(EPS * n)), i
        if min(r.comm["rounds"], ref.comm["rounds"]) == 1:
            first_turn += 1
            assert _decisions(r) == _decisions(ref), i
            assert _cos_gap(r, ref) <= COS_TOL, i
    assert first_turn >= 1


def test_warm_sweep_is_the_unsharded_hot_path(insts, warm_sweep):
    """Sharding, donation and the stale view change no decision and no
    bit of a separator: the one-device mesh gives what the unsharded hot
    path gives."""
    res, _stats, _n = warm_sweep
    plain = engine.run_sweep(insts, **OPTS)
    for i, (a, b) in enumerate(zip(res, plain)):
        assert _decisions(a) == _decisions(b), i
        np.testing.assert_array_equal(a.classifier.w, b.classifier.w)
        assert a.classifier.b == b.classifier.b, i


@pytest.mark.parametrize("devices", [1, 4])
def test_sweep_counters(insts, warm_sweep, devices):
    if devices == 1:
        res, stats, logged = warm_sweep
    else:
        stats = {}
        n0 = len(hotloop.KEY_LOG)
        res = engine.run_sweep(insts, mesh=_mesh(devices), stats=stats,
                               **OPTS)
        logged = len(hotloop.KEY_LOG) - n0
    longest = max(r.comm["rounds"] for r in res)
    # one dispatch a turn; every instance runs in lock-step, and the
    # double-buffered loop may run one all-done turn past the longest
    assert stats["turns"] == logged
    assert longest <= stats["turns"] <= longest + 1
    assert 0 < stats["live_rows"] <= stats["dispatched_rows"]
    shapes = stats["stage_shapes"]
    assert sum(shapes.values()) == stats["turns"]
    assert sum(n * L * devices for (L, _w, _warm), n in shapes.items()) \
        == stats["dispatched_rows"]
    full = len(insts) // devices            # a full-batch turn's rows
    assert (full, 0, False) in shapes                     # the first turn
    assert all(L == full or L % hotloop.BATCH_MULT == 0
               for L, _w, _warm in shapes)
    assert stats["view_wait_s"] > 0.0
    assert 1 <= stats["shard_dispatches"] < stats["turns"]


def test_unsharded_counters_count_n_pad(insts):
    """Without a mesh, a sub-batch turn's dispatched rows are its
    ``n_pad``: the live count rounded up to ``BATCH_MULT``."""
    stats = {}
    n0 = len(hotloop.KEY_LOG)
    engine.run_sweep(insts, stats=stats, **OPTS)
    keys = hotloop.KEY_LOG[n0:]
    assert stats["turns"] == len(keys)
    assert stats["dispatched_rows"] == sum(n for n, *_ in keys)
    assert stats["live_rows"] <= stats["dispatched_rows"]
    assert "shard_dispatches" not in stats


def _read_spans(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sweep."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_sweep_spans(insts, warm_sweep, tmp_path):
    stats = {}
    with jax.profiler.trace(str(tmp_path)):
        traced = engine.run_sweep(insts, mesh=make_data_mesh(1),
                                  stats=stats, **OPTS)
    spans = _read_spans(str(tmp_path))
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert set(by) == {"sweep.pack", "sweep.assemble", "sweep.turn",
                       "sweep.dispatch", "sweep.view", "sweep.collect"}
    assert len(by["sweep.pack"]) == len(by["sweep.collect"]) == 1
    (_n, pack_start, pack_end, _a), = by["sweep.pack"]
    (_n, s, e, a), = by["sweep.assemble"]
    assert pack_start <= s and e <= pack_end
    assert a["direct"] + a["copied"] == len(insts) * K
    assert (a["direct"], a["copied"]) == (stats["upload_shards_direct"],
                                          stats["upload_shards_copied"])
    (_n, collect_start, _e, _a), = by["sweep.collect"]
    turns = by["sweep.turn"]
    assert len(turns) == len(by["sweep.dispatch"]) == stats["turns"]
    for _n, s, e, _a in by["sweep.dispatch"]:
        assert any(ts <= s and e <= te for _m, ts, te, _b in turns)
    for _n, s, e, _a in turns + by["sweep.view"]:
        assert pack_end <= s and e <= collect_start
    # the seed view and at least one decode a turn pair
    assert len(by["sweep.view"]) >= 1 + stats["turns"] // 2
    assert sum(a["live"] for *_x, a in turns) == stats["live_rows"]
    assert sum(a["rows"] for *_x, a in turns) == stats["dispatched_rows"]
    shapes = collections.Counter()
    for (L, w, _warm), n in stats["stage_shapes"].items():
        shapes[(L, w)] += n                   # one device: rows == L
    assert collections.Counter((a["rows"], a["width"])
                               for *_x, a in turns) == shapes

    # tracing changes nothing the sweep decides or counts
    res, plain_stats, _n = warm_sweep
    for a, b in zip(traced, res):
        assert _decisions(a) == _decisions(b)
        np.testing.assert_array_equal(a.classifier.w, b.classifier.w)
    for key in ("turns", "live_rows", "dispatched_rows", "stage_shapes"):
        assert stats[key] == plain_stats[key], key

