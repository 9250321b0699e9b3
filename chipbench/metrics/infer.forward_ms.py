"""Device time of one served forward: the summed device time of the
server's forward program in the traced window, over its launches (ms)."""


def _forwards(run):
    """The server's launches, where they are the window's forwards: the
    program of its name launched once per call, and no other."""
    ts = run.trace.module_seconds(run.ctx["programs"]["infer"])
    return ts if ts and len(ts) == run.ctx["infer_calls"] else None


def read(run):
    ts = _forwards(run)
    return 1e3 * sum(ts) / len(ts) if ts else None
