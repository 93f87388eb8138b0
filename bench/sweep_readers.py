"""The program's own counters of the window's sweeps, as the ``.sweep``
metrics read them (``run.host["sweeps"]``, one record a sweep, written by
``bench/entries/sweep.py``).  A program without a counter (a commit
before the sweep's counters) gives ``None``.

The sweep's hot loop counts its dispatches per ``(L, width, warm)``
(``stats["stage_shapes"]``): every device ran its solver stages on ``L``
rows (padded to the kernel's 8-row blocks), each fitting its node's
``n_pad`` rows and ``width`` transcript rows.  The least time of each
shape is ``bench/costs.py``'s; weighed by the counts it gives the least
time of a mean launch.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from bench import costs

SUBLANES = 8          # the Pegasos kernel's instance block


def counter_sums(run, keys: Sequence[str]) -> Optional[Dict[str, float]]:
    """Each of ``keys`` summed over the window's sweeps: a sweep's own
    ``sweep_s``, else the program's counter of that name."""
    sweeps = run.host.get("sweeps")
    if not sweeps:
        return None
    sums = dict.fromkeys(keys, 0.0)
    for s in sweeps:
        for key in keys:
            value = s.get(key, s["stats"].get(key))
            if value is None:
                return None
            sums[key] += value
    return sums


def per_turn_ms(run, key: str) -> Optional[float]:
    """Σ ``key`` over Σ turns of the window's sweeps, ms."""
    sums = counter_sums(run, (key, "turns"))
    if sums is None or not sums["turns"]:
        return None
    return 1e3 * sums[key] / sums["turns"]


def stage_shapes(run) -> Dict[tuple, int]:
    """Dispatch counts per ``(L, width, warm)`` over the window's sweeps;
    empty without the counter."""
    out: Dict[tuple, int] = {}
    for s in run.host.get("sweeps") or ():
        for key, n in s["stats"].get("stage_shapes", {}).items():
            out[key] = out.get(key, 0) + n
    return out


def stage_shape(pool: dict, L: int, width: int) -> Tuple[int, int, int, int]:
    """``(B, N, d, nsteps)`` of a stage a device ran at ``(L, width)``."""
    B = -(-L // SUBLANES) * SUBLANES
    return B, pool["n_pad"] + width, pool["d"], pool["svm_steps"]


def mean_least_time(pool: dict, shapes: Dict[tuple, int],
                    peaks: dict) -> Optional[float]:
    """The least time of a launch, s, weighed by the dispatch counts of
    ``shapes`` (``{(L, width, warm): count}``); ``None`` with no count."""
    total, n = 0.0, 0
    for (L, width, _warm), count in shapes.items():
        ops, nbytes = costs.pegasos_stage(*stage_shape(pool, L, width))
        total += count * costs.least_time(ops, nbytes, peaks)[0]
        n += count
    return total / n if n else None
