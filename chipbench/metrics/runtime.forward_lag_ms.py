"""Mean time from a batch's ready time to the launch of its forward, as the
interleaving runtime sees it: the ``lag_s`` of the program's
``runtime.serve`` spans, on the runtime's clock (ms). None for a program
without its own spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    lags = [s.attrs["lag_s"] for s in spans("runtime.serve")]
    return 1e3 * sum(lags) / len(lags) if lags else None
