"""Sessions in flight at each pool step's entry (submitted, no result
yet; at most ``slots``) over ``slots``, %: the share of the full-block
dispatch that serves a session, averaged over steps."""


def read(run):
    xs = run.host.get("occupancy")
    return 100.0 * sum(xs) / len(xs) if xs else None
