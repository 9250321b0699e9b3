"""Mean time from a batch's ready time (its last request's due time) to the
start of its forward, from the benchmark's host spans around the server
(ms): how long served work waited behind training or the previous
forward."""


def read(run):
    lags = run.ctx.get("infer_lag_s")
    return 1e3 * sum(lags) / len(lags) if lags else None
