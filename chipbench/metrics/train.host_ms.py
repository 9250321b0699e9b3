"""Host time around one training step: the mean of the program's
``train.step`` spans (next batch, launch, wait for the step) less the mean
device time of the step program's launches, in the traced window (ms).
None for a program without its own spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    steps = [s.seconds for s in spans("train.step")]
    dev = run.trace.module_seconds(run.ctx["programs"]["train"])
    if not steps or not dev:
        return None
    return 1e3 * (sum(steps) / len(steps) - sum(dev) / len(dev))
