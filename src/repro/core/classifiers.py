"""Hypothesis classes from the paper.

Thresholds (R^1), intervals (R^1), axis-aligned rectangles (R^d), and linear
separators (R^d).  Each provides ``fit`` (0-error learner under the noiseless
assumption), ``predict`` and ``error``.  The linear-separator max-margin
solver is a jit'd JAX routine (Pegasos-style projected subgradient on the
hard-margin objective with margin renormalization); support points are the
active-margin points — exactly what the protocols ship.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as _kernel_ops


# ---------------------------------------------------------------------------
# Thresholds (predict +1 iff x < t)  — paper Lemma 3.1
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Threshold:
    t: float

    def predict(self, X: np.ndarray) -> np.ndarray:
        x = np.asarray(X).reshape(-1)
        return np.where(x < self.t, 1, -1)

    def error(self, X: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(X) != y)) if len(y) else 0.0

    @staticmethod
    def fit(X: np.ndarray, y: np.ndarray) -> "Threshold":
        """Any 0-error threshold on (X, y); assumes separability."""
        x = np.asarray(X).reshape(-1)
        pos = x[y == 1]
        neg = x[y == -1]
        lo = pos.max() if len(pos) else -np.inf  # t must exceed all positives
        hi = neg.min() if len(neg) else np.inf   # and be below all negatives
        if not lo < hi:
            raise ValueError("not separable by a threshold")
        if np.isinf(lo) and np.isinf(hi):
            t = 0.0
        elif np.isinf(lo):
            t = hi - 1.0
        elif np.isinf(hi):
            t = lo + 1.0
        else:
            t = 0.5 * (lo + hi)
        return Threshold(float(t))


# ---------------------------------------------------------------------------
# Intervals (predict +1 iff a <= x <= b) — paper Lemma 3.2
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Interval:
    a: float
    b: float

    def predict(self, X: np.ndarray) -> np.ndarray:
        x = np.asarray(X).reshape(-1)
        return np.where((x >= self.a) & (x <= self.b), 1, -1)

    def error(self, X: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(X) != y)) if len(y) else 0.0

    @staticmethod
    def fit(X: np.ndarray, y: np.ndarray) -> "Interval":
        """Minimal enclosing interval of the positives (paper's choice: 'as
        small as possible'); assumes noiseless separability."""
        x = np.asarray(X).reshape(-1)
        pos = x[y == 1]
        if len(pos) == 0:
            return Interval(0.0, -1.0)  # empty interval
        a, b = float(pos.min()), float(pos.max())
        neg = x[y == -1]
        if len(neg) and np.any((neg >= a) & (neg <= b)):
            raise ValueError("not separable by an interval")
        return Interval(a, b)


# ---------------------------------------------------------------------------
# Axis-aligned rectangles in R^d — paper Theorem 3.2
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AxisAlignedRectangle:
    lo: np.ndarray  # (d,)
    hi: np.ndarray  # (d,)
    positive_inside: bool = True

    def predict(self, X: np.ndarray) -> np.ndarray:
        X = np.atleast_2d(X)
        inside = np.all((X >= self.lo) & (X <= self.hi), axis=1)
        lab = np.where(inside, 1, -1)
        return lab if self.positive_inside else -lab

    def error(self, X: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(X) != y)) if len(y) else 0.0

    @staticmethod
    def minimal(X: np.ndarray) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Minimum enclosing rectangle (the 2d values A ships, Thm 3.2);
        None plays the paper's ∅ sentinel."""
        X = np.atleast_2d(X)
        if X.shape[0] == 0:
            return None
        return X.min(axis=0), X.max(axis=0)

    @staticmethod
    def merge(
        r1: Optional[Tuple[np.ndarray, np.ndarray]],
        r2: Optional[Tuple[np.ndarray, np.ndarray]],
    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Coordinate-wise merge: R^+_{A∪B} from R^+_A and R^+_B."""
        if r1 is None:
            return r2
        if r2 is None:
            return r1
        return np.minimum(r1[0], r2[0]), np.maximum(r1[1], r2[1])

    @staticmethod
    def from_bounds(
        rect: Tuple[np.ndarray, np.ndarray], positive_inside: bool = True
    ) -> "AxisAlignedRectangle":
        return AxisAlignedRectangle(np.asarray(rect[0]), np.asarray(rect[1]), positive_inside)


# ---------------------------------------------------------------------------
# Linear separators — jit'd max-margin solver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LinearSeparator:
    w: np.ndarray  # (d,)
    b: float
    margin: float = 0.0  # geometric margin on the fit set

    def predict(self, X: np.ndarray) -> np.ndarray:
        return np.where(np.atleast_2d(X) @ self.w + self.b > 0, 1, -1)

    def decision(self, X: np.ndarray) -> np.ndarray:
        return np.atleast_2d(X) @ self.w + self.b

    def error(self, X: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(X) != y)) if len(y) else 0.0


@functools.partial(jax.jit, static_argnames=("steps",))
def _svm_solve(X: jnp.ndarray, y: jnp.ndarray, lam: jnp.ndarray, steps: int = 2000):
    """Pegasos projected subgradient on  λ/2 ||w||² + mean hinge(w·x+b)."""
    n, d = X.shape

    def body(i, carry):
        w, b = carry
        eta = 1.0 / (lam * (i + 2.0))
        m = y * (X @ w + b)
        viol = (m < 1.0).astype(X.dtype)
        gw = lam * w - (viol * y) @ X / n
        gb = -jnp.sum(viol * y) / n
        w = w - eta * gw
        b = b - eta * gb
        # pegasos projection onto ball of radius 1/sqrt(lam)
        nrm = jnp.linalg.norm(w)
        scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / (nrm + 1e-12))
        return w * scale, b * scale

    w0 = jnp.zeros((d,), X.dtype)
    b0 = jnp.zeros((), X.dtype)
    w, b = jax.lax.fori_loop(0, steps, body, (w0, b0))
    return w, b


# Warm-polish defaults: a quarter of a stage's step budget refines the
# carried separator, and the eta schedule starts as if WARM_OFFSET steps had
# already elapsed, so the first polish steps are gentle refinements instead
# of the stage-restart kicks that would wipe out the warm iterate.
WARM_STEPS = 500
WARM_OFFSET = 1024.0


@functools.partial(jax.jit, static_argnames=("steps", "stages", "warm_steps",
                                             "warm_offset", "return_gate",
                                             "kernel"))
def _svm_solve_batch(
    X: jnp.ndarray,                # (B, N, d) f32; rows with label 0 are padding
    y: jnp.ndarray,                # (B, N) f32 in {+1, -1, 0}
    lam0: jnp.ndarray,             # scalar f32 — stage-0 λ
    steps: int = 2000,
    stages: int = 3,
    w0: Optional[jnp.ndarray] = None,    # (B, d) warm-init separator
    b0: Optional[jnp.ndarray] = None,    # (B,)
    warm_ok: Optional[jnp.ndarray] = None,   # (B,) bool — init is trustworthy
    warm_steps: int = WARM_STEPS,
    warm_offset: float = WARM_OFFSET,
    return_gate: bool = False,
    kernel: Optional[bool] = None,
):
    """Batched hard-margin-annealed Pegasos: B independent fits in lock-step.

    The λ schedule (λ0, λ0/10, …) runs as one compiled loop over stages
    (``lax.while_loop`` that exits as soon as *every* instance separates —
    skipped stages could only have touched latched slots, so the early exit
    is results-identical); each stage warm-starts from the previous stage's
    (w, b) instead of re-initializing at zero, so later stages only have to
    *tighten* an already-separating direction (fewer total steps for the
    same margin — regression-tested in tests/test_svm_batch.py).  Per
    instance, the result is latched at the first stage that reaches 0
    training error (exactly the legacy early-break); instances that never
    separate keep the last stage's iterate.  Label-0 rows are inert: they
    contribute no hinge violations and the gradient normalizes by the
    per-instance *valid* row count.

    **Warm entry** (``w0``/``b0`` given, e.g. the previous MAXMARG turn's
    separator): before the anneal, a short *polish* stage runs ``warm_steps``
    Pegasos steps from (w0, b0) at the stage-0 λ — the λ whose optimum the
    first-0-error latch keys to whenever separation is easy, so polish and
    cold approximate the same fixed point — with the eta schedule
    offset by ``warm_offset`` so the early steps refine instead of
    re-initializing.  A polished instance that reaches 0 training error is
    latched through the existing first-0-error latch — for it, every
    annealing stage is skipped (the stage loop exits immediately once all
    instances latch).  Instances whose polish does not separate fall through
    to the cold anneal from zeros, bit-identically to the cold entry.  With
    ``w0=None`` the computation is exactly the cold path.  Warm vs cold can
    differ at the float level (two approximations of the same
    transcript-determined hard-margin optimum), never at the decision level
    on the tested grids — enforced by tests/test_maxmarg_warm.py.

    Returns ``(w, b, converged)`` with shapes (B, d), (B,), (B,) — already
    canonicalized to functional margin 1 at the support points (a positive
    rescale, so every margin-order/sign decision downstream is unaffected by
    whether canonicalization happened).  ``return_gate=True`` (static)
    additionally returns the polish gate bits — the carried separator
    classified the fit set cleanly (all-False on the cold entry) — so
    callers instrumenting latch behaviour read the solver's own gate
    instead of recomputing the margin scan.

    ``kernel`` (static) selects the solver's inner loop: ``True`` routes
    every λ stage through ``kernels.ops.pegasos_stage`` — the tiled Pallas
    kernel on TPU, its jnp twin elsewhere — with the
    first-0-error latch fused into the stage launch; ``False`` keeps the
    classic d-unrolled vmapped-XLA loop below, bit-identical to before
    this flag existed.  ``None`` (default) resolves to the backend: Pallas
    kernels are TPU-default, so the kernel path is chosen exactly when
    running on TPU.  The two paths are two float approximations of the
    same transcript-determined optimum — decision-level agreement on the
    tested grids is enforced by the kernel-parity gates, not bit equality
    (same contract as warm vs cold).

    Compile-key contract: this function is jitted with static
    ``steps``/``stages``/``warm_steps``/``warm_offset``/``return_gate``/
    ``kernel`` — plus, implicitly, the (B, N, d) shapes of ``X``/``y``
    and whether ``w0``/``warm_ok`` are present.  Everything else
    (data, λ, warm iterates) is traced.  Engine callers pin B and N via
    their own padding/quantization so repeated refits hit one cache
    entry; calling this directly with ragged batch shapes recompiles
    per shape.
    """
    B, N, d = X.shape
    valid = y != 0.0
    nv = jnp.maximum(jnp.sum(valid, axis=1), 1).astype(X.dtype)  # (B,)
    use_kernel = _kernel_ops.on_tpu() if kernel is None else bool(kernel)

    # the d-contractions are spelled as broadcast multiply-adds: XLA:CPU
    # lowers the K=d (=2..10) dot through a generic GEMM path that is ~5×
    # slower than the fused elementwise form, and these two run `steps`
    # times per stage (cf. the same note on engine/median._proj_grid)
    def decide(w, b):
        return sum(X[:, :, i] * w[:, None, i] for i in range(d)) + b[:, None]

    def margins_min(w, b):
        m = y * decide(w, b)
        return jnp.min(jnp.where(valid, m, jnp.inf), axis=1)

    def pegasos_stage(w, b, lam, nsteps, t0=0.0):
        def body(i, carry):
            w, b = carry
            eta = 1.0 / (lam * (i + 2.0 + t0))                  # (B,)
            m = y * decide(w, b)
            viol = ((m < 1.0) & valid).astype(X.dtype)          # (B, N)
            vy = viol * y
            gsum = jnp.stack([jnp.sum(vy * X[:, :, i], axis=1)
                              for i in range(d)], axis=1)       # (B, d)
            gw = lam[:, None] * w - gsum / nv[:, None]
            gb = -jnp.sum(vy, axis=1) / nv
            w = w - eta[:, None] * gw
            b = b - eta * gb
            nrm = jnp.sqrt(jnp.sum(w * w, axis=1))
            scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / (nrm + 1e-12))
            return w * scale[:, None], b * scale

        # unroll=2 shaves the XLA:CPU loop-machinery overhead off the hot
        # 2000-iteration dispatch; the op sequence (and so every float
        # result) is bit-identical to the rolled loop
        return jax.lax.fori_loop(0, nsteps, body, (w, b), unroll=2)

    zeros_w = jnp.zeros((B, d), X.dtype)
    zeros_b = jnp.zeros((B,), X.dtype)
    if w0 is not None:
        # polish: refine the carried separator at the *stage-0* λ — the
        # stage the first-0-error latch keys to whenever separation is easy,
        # so polish and cold approximate the same regularized optimum.  The
        # latch is gated on the *carried* separator already classifying the
        # fit set cleanly: only then is the refit optimum a small
        # perturbation the short polish reliably tracks — an init with
        # training errors falls through to the cold anneal instead (a
        # half-converged cold iterate's decisions are not reproducible from
        # a different starting point).
        ok0 = margins_min(w0.astype(X.dtype), b0.astype(X.dtype)) > 0.0
        if warm_ok is not None:
            ok0 = ok0 & warm_ok
        gate = ok0
        lam_p = jnp.full((B,), lam0, X.dtype)
        if use_kernel:
            # polish runs un-latched (found=False in): the gate below is
            # the composition ok0 & (polished margin > 0), not the
            # kernel's own latch — same formula as the classic branch
            w_p, b_p, mm_p, _f, _wb, _bb = _kernel_ops.pegasos_stage(
                X, y, nv, w0.astype(X.dtype), b0.astype(X.dtype), lam_p,
                jnp.zeros((B,), bool), zeros_w, zeros_b,
                nsteps=warm_steps, t0=float(warm_offset))
            ok_p = ok0 & (mm_p > 0.0)
        else:
            w_p, b_p = pegasos_stage(w0.astype(X.dtype), b0.astype(X.dtype),
                                     lam_p, warm_steps,
                                     jnp.float32(warm_offset))
            ok_p = ok0 & (margins_min(w_p, b_p) > 0.0)
        found0 = ok_p
        w_best0 = jnp.where(ok_p[:, None], w_p, zeros_w)
        b_best0 = jnp.where(ok_p, b_p, zeros_b)
    else:
        found0 = jnp.zeros((B,), bool)
        gate = jnp.zeros((B,), bool)
        w_best0, b_best0 = zeros_w, zeros_b

    def stage_cond(carry):
        s, _w, _b, _wb, _bb, found = carry
        # once every instance separates, later stages can only touch latched
        # slots — exit early (identical results, none of the arithmetic)
        return (s < stages) & ~jnp.all(found)

    def stage(carry):
        s, w, b, w_best, b_best, found = carry
        lam_s = lam0 * 0.1 ** s.astype(X.dtype)
        lam_v = jnp.full((B,), lam_s, X.dtype)
        if use_kernel:
            # whole stage + first-0-error latch in one fused launch
            w, b, _mm, found, w_best, b_best = _kernel_ops.pegasos_stage(
                X, y, nv, w, b, lam_v, found, w_best, b_best, nsteps=steps)
            return (s + 1, w, b, w_best, b_best, found)
        w, b = pegasos_stage(w, b, lam_v, steps)
        ok = margins_min(w, b) > 0.0
        take = ok & ~found
        w_best = jnp.where(take[:, None], w, w_best)
        b_best = jnp.where(take, b, b_best)
        return (s + 1, w, b, w_best, b_best, found | ok)

    _, w, b, w_best, b_best, found = jax.lax.while_loop(
        stage_cond, stage,
        (jnp.zeros((), jnp.int32), zeros_w, zeros_b, w_best0, b_best0,
         found0))
    w = jnp.where(found[:, None], w_best, w)
    b = jnp.where(found, b_best, b)

    # canonicalize: functional margin 1 at the support points
    mmin = margins_min(w, b)
    can = found & jnp.isfinite(mmin) & (mmin > 0.0)
    scale = jnp.where(can, 1.0 / jnp.where(can, mmin, 1.0), 1.0)
    if return_gate:
        return w * scale[:, None], b * scale, found, gate
    return w * scale[:, None], b * scale, found


def anneal_hard_margin(
    X: np.ndarray,
    y: np.ndarray,
    lam: float = 1e-3,
    steps: int = 2000,
    stages: int = 3,
    kernel: Optional[bool] = None,
) -> Tuple[np.ndarray, float, bool]:
    """Single-instance entry to the warm-started annealed solver (B=1).

    Returns ``(w, b, converged)`` in float64/bool host types.  This *is* the
    batched engine's per-turn fit at B=1 — the engine's MAXMARG selector and
    the host API share one solver, so batched-vs-sequential parity is a
    property of the program, not of tolerances.  ``kernel`` follows
    ``_svm_solve_batch``'s solver-path contract (None = TPU-default).
    """
    Xj = jnp.asarray(np.atleast_2d(X), dtype=jnp.float32)[None]
    yj = jnp.asarray(y, dtype=jnp.float32)[None]
    w, b, ok = _svm_solve_batch(Xj, yj, jnp.float32(lam), steps, stages,
                                kernel=kernel)
    return (np.asarray(w[0], dtype=np.float64), float(b[0]), bool(ok[0]))


def fit_max_margin(
    X: np.ndarray,
    y: np.ndarray,
    steps: int = 2000,
    lam: float = 1e-3,
    refine: int = 2,
) -> LinearSeparator:
    """Approximate hard-margin SVM.

    Pegasos with decreasing λ (hard-margin annealing): the paper's protocols
    need a 0-training-error max-margin separator on separable data.  Stages
    run warm-started on device (``_svm_solve_batch`` at B=1, ``refine + 1``
    λ stages) and the first 0-error stage wins; the result is canonicalized
    so that min functional margin = 1.
    """
    w, b, _ = anneal_hard_margin(X, y, lam=lam, steps=steps, stages=refine + 1)
    geo = (y * (X @ w + b)).min() / (np.linalg.norm(w) + 1e-30)
    return LinearSeparator(w, float(b), margin=float(geo))


def support_points(
    clf: LinearSeparator, X: np.ndarray, y: np.ndarray, rtol: float = 0.15, max_support: int = 8
) -> np.ndarray:
    """Indices of active-margin points (functional margin within (1+rtol) of
    the minimum).  These are the points MAXMARG ships each round."""
    m = y * (X @ clf.w + clf.b)
    mmin = max(m.min(), 1e-12)
    idx = np.where(m <= mmin * (1.0 + rtol))[0]
    if len(idx) > max_support:  # keep the tightest ones from each class
        # stable: exact margin ties truncate by ascending index, the same
        # (margin, index) order the batched engine's selection ranks by —
        # an unstable sort here could make host and engine ship different
        # tied points and break the exact-parity gates
        order = np.argsort(m[idx], kind="stable")
        keep = []
        for i in order:
            keep.append(idx[i])
            if len(keep) >= max_support:
                break
        idx = np.asarray(sorted(keep))
    return idx
