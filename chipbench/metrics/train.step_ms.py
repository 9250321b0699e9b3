"""Device time of one training step: the summed device time of the step
program's launches in the traced window, over their number (ms)."""


def read(run):
    ts = run.trace.module_seconds(run.ctx["programs"]["train"])
    return 1e3 * sum(ts) / len(ts) if ts else None
