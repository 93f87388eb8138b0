"""Share of its roofline that the Pegasos stage kernel reached in the
sharded sweep, %: the least time of a launch at the shapes the window's
sweeps ran (``bench/sweep_readers.py``, weighed by the program's
``stats["stage_shapes"]``; the device's peaks from ``bench/peaks.json``)
over the trace's mean time a launch of ``pegasos_stage_batched``."""

from bench import common
from bench.readers import PEGASOS_KERNEL
from bench.sweep_readers import mean_least_time, stage_shapes


def read(run):
    shapes = stage_shapes(run)
    if run.trace is None or not shapes:
        return None
    n, secs = run.trace.op(PEGASOS_KERNEL)
    if not n or secs <= 0:
        return None
    least = mean_least_time(run.config["pool"], shapes,
                            common.load_peaks(run.device_kind))
    print(f"pegasos_roofline.sweep: {n} launches, {secs!r} s, least "
          f"{least!r} s a launch over {len(shapes)} shapes", flush=True)
    return 100.0 * least * n / secs
