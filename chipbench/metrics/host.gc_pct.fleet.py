"""Share of the traced window the host spent in cyclic garbage collection:
the program's ``host.gc`` spans over the window (%). None for a program
without its own spans; 0 when it recorded spans and no collection."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    if not spans():
        return None
    return 100.0 * sum(s.seconds for s in spans("host.gc")) \
        / run.trace.window_s
