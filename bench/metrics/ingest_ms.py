"""Host time of ingest (``open``, every ``feed``, ``close``) per session,
ms: the mean over the window's sessions."""


def read(run):
    xs = run.host.get("ingest_s")
    return 1e3 * sum(xs) / len(xs) if xs else None
