"""Host time of the fleet loop's preparation of one control window: the
mean of the program's ``fleet.prepare`` spans in the traced window
(arrivals, dispatch, plan rates, burst quantiles, budgets, carry-in) (ms).
None for a program without its own spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    ts = [s.seconds for s in spans("fleet.prepare")]
    return 1e3 * sum(ts) / len(ts) if ts else None
