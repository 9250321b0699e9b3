"""Host time of the fleet loop's reports of one control window: the mean
of the program's ``fleet.report`` spans in the traced window (plans and
execution reports rebuilt, controller state observed, the fleet's report)
(ms). None for a program without its own spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    ts = [s.seconds for s in spans("fleet.report")]
    return 1e3 * sum(ts) / len(ts) if ts else None
