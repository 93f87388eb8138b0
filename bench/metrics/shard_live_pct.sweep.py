"""Share of the rows the chips ran that were live, %: the program's
``stats["live_rows"]`` over ``stats["dispatched_rows"]`` (S x L a
sharded turn, every shard padded to the fullest shard's L), summed over
the window's sweeps."""

from bench.sweep_readers import counter_sums


def read(run):
    sums = counter_sums(run, ("live_rows", "dispatched_rows"))
    if sums is None or not sums["dispatched_rows"]:
        return None
    return 100.0 * sums["live_rows"] / sums["dispatched_rows"]
