"""The training step time the interleaving runtime planned the window with
(its profiled ``t_tr``): the ``planned_step_s`` of the program's
``runtime.run`` span (ms). None for a program without its own spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    rs = spans("runtime.run")
    return 1e3 * rs[-1].attrs["planned_step_s"] if rs else None
