"""The whole served forward's share of the chip's bf16 peak: its model
FLOPs (from the configuration's shapes) over the forward program's mean
device time, over the peak (%)."""


def _forwards(run):
    """The server's launches, where they are the window's forwards: the
    program of its name launched once per call, and no other."""
    ts = run.trace.module_seconds(run.ctx["programs"]["infer"])
    return ts if ts and len(ts) == run.ctx["infer_calls"] else None


def read(run):
    ts = _forwards(run)
    if not ts:
        return None
    return 100.0 * run.ctx["flops"]["infer"] / (sum(ts) / len(ts)) \
        / run.peak["bf16_flops"]
