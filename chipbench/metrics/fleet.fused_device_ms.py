"""Device time of the fused fleet window per control window: the summed
device time of the fused program's launches in the traced window, over the
control windows the timed call served (ms)."""


def read(run):
    ts = run.trace.module_seconds(run.ctx["programs"]["fleet"])
    n = run.ctx.get("windows", 0)
    return 1e3 * sum(ts) / n if ts and n else None
