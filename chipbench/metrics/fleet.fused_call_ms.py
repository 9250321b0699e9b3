"""Host time of one call of the fused window: the mean of the program's
``fleet.fused`` spans in the traced window (padding, uploads and launch,
the wait for the device and the fetch of its results) (ms). None for a
program without its own spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    ts = [s.seconds for s in spans("fleet.fused")]
    return 1e3 * sum(ts) / len(ts) if ts else None
