"""Share of the fused window's padded event slots that hold a request:
the arrival events of every ``fleet.fused`` span in the traced window over
their slots, K_pad x T_pad each (%). None for a program without its own
spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    fs = spans("fleet.fused")
    slots = sum(s.attrs["slots"] for s in fs)
    return 100.0 * sum(s.attrs["events"] for s in fs) / slots if slots \
        else None
