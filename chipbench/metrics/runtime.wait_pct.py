"""Share of the traced window in which the interleaving runtime waited for
a batch to form because no training step fitted before it: the
``wait_s`` of the program's ``runtime.wait`` spans, on the runtime's
clock, over the window (%). None for a program without its own spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    ws = spans("runtime.wait")
    return 100.0 * sum(s.attrs["wait_s"] for s in ws) / run.trace.window_s \
        if ws else None
