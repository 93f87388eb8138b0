"""Host time of one sweep turn, ms: the window's sweeps' host time
(``run_sweep``, pack and read-back included) over their turns (the
program's ``stats["turns"]``)."""

from bench.sweep_readers import per_turn_ms


def read(run):
    return per_turn_ms(run, "sweep_s")
