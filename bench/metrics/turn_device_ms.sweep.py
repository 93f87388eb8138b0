"""Device time of one sharded sweep turn, ms: the trace's executions of
``jit__sharded_full_turn`` and ``jit__sharded_sub_turn``, total over
their count on the four chips."""

MODULES = ("jit__sharded_full_turn", "jit__sharded_sub_turn")


def read(run):
    if run.trace is None:
        return None
    n = secs = 0
    for name in MODULES:
        c, s = run.trace.module(name)
        n, secs = n + c, secs + s
    return 1e3 * secs / n if n else None
