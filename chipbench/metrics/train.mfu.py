"""The whole training step's share of the chip's bf16 peak: the step's
model FLOPs (from the configuration's shapes) over the step program's mean
device time, over the peak (%)."""


def read(run):
    ts = run.trace.module_seconds(run.ctx["programs"]["train"])
    if not ts:
        return None
    return 100.0 * run.ctx["flops"]["train"] / (sum(ts) / len(ts)) \
        / run.peak["bf16_flops"]
