"""Host time blocked on the host view of one sweep turn, ms: the
program's ``stats["view_wait_s"]`` over ``stats["turns"]``, summed over
the window's sweeps."""

from bench.sweep_readers import per_turn_ms


def read(run):
    return per_turn_ms(run, "view_wait_s")
