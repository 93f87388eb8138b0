"""Operations and bytes of the Pegasos solver stage, from the algorithm's
shapes at the call, and the least time a chip could take for them.

One stage over a batch of ``B`` fit sets of ``N`` rows in R^d runs
``nsteps`` projected subgradient steps and one closing margin scan.  Per
row and step: the margin ``y (x.w + b)`` (d multiplies, d adds, 1
multiply), the hinge mask and its label product (1 multiply), the
gradient sum (d multiplies, d adds) and the offset sum (1 add): 4d + 3
operations.  Per instance and step the update and the ball projection
take 5d + 8.  The scan takes 2d + 2 per row.  The bytes are the fit sets
and labels read once (4 bytes a value) and the per-instance vectors read
and written once: no implementation can move less.  The count is the
same whatever runs the stage (the Pallas kernel or its jnp twin) and
leaves out any padding an implementation adds.
"""

from __future__ import annotations

from typing import Tuple


def maxmarg_transcript_rows(k: int, max_epochs: int, max_support: int) -> int:
    """Rows a MAXMARG node can receive in a session: per epoch
    ``max_support`` points on each of the k-1 turns it does not
    coordinate and 2 from each of the k-1 others on the turn it does,
    plus 8 rows of slack, rounded up to 8 (the program's pinned width)."""
    return -(-(max_epochs * (max_support + 2) * (k - 1) + 8) // 8) * 8


def pegasos_stage(B: int, N: int, d: int, nsteps: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one stage."""
    ops = B * (nsteps * (N * (4 * d + 3) + 5 * d + 8) + N * (2 * d + 2))
    vectors = 3 * d + 6               # w, b, lambda, count, latch in/out
    nbytes = 4 * B * (N * (d + 1) + vectors)
    return float(ops), float(nbytes)


def pool_stage_shape(pool: dict) -> Tuple[int, int, int, int]:
    """``(B, N, d, nsteps)`` of every stage a MAXMARG pool turn launches:
    all ``slots`` rows (rounded up to 4), each fitting its own node's
    ``n_pad`` rows and the full pinned transcript."""
    B = -(-pool["slots"] // 4) * 4
    N = pool["n_pad"] + maxmarg_transcript_rows(
        pool["k"], pool["max_epochs"], pool["max_support"])
    return B, N, pool["d"], pool["svm_steps"]


def least_time(ops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The larger of ops over peak FLOP/s and bytes over peak HBM
    bandwidth, and which of the two it is."""
    t_c, t_m = ops / peaks["flops_bf16"], nbytes / peaks["hbm_bw"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
