"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the window
(%)."""


def read(run):
    return 100.0 * run.trace.idle_share
