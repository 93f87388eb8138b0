"""Readings shared by per-layer metrics that a suffix splits by the
end-to-end metric they move (``<name>.batch`` / ``<name>.open``): each
metric file in ``bench/metrics`` is ``read = <one of these>``.  A reading
with nothing to read returns ``None``."""

from __future__ import annotations

from bench import common, costs

PEGASOS_KERNEL = "pegasos_stage_batched"
TURN_MODULE = "jit__hot_turn_impl"


def device_idle_pct(run):
    """Share of the traced window in which no program ran on the device,
    % (the mean over the chips used)."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def pool_step_ms(run):
    """Host time of one pool step (``ProtocolService.step``, which ends on
    the blocking read of the supervision view), ms: total over steps."""
    xs = run.host.get("step_s")
    return 1e3 * sum(xs) / len(xs) if xs else None


def turn_device_ms(run):
    """Device time of one pool turn, ms: the trace's executions of the turn
    program, total over their count."""
    n, secs = run.trace.module(TURN_MODULE) if run.trace else (0, 0)
    return 1e3 * secs / n if n else None


def pegasos_roofline(run):
    """Share of its roofline that the Pegasos stage kernel reached, %: the
    least time of every launch in the trace (``bench/costs.py``, the
    device's peaks from ``bench/peaks.json``) over the kernel's device
    time."""
    if run.trace is None:
        return None
    n, secs = run.trace.op(PEGASOS_KERNEL)
    if not n or secs <= 0:
        return None
    ops, nbytes = costs.pegasos_stage(*costs.pool_stage_shape(
        run.config["pool"]))
    least, bound = costs.least_time(ops, nbytes,
                                    common.load_peaks(run.device_kind))
    print(f"pegasos_roofline: {n} launches, {secs!r} s, least {least!r} s "
          f"a launch, {bound} bound", flush=True)
    return 100.0 * n * least / secs
