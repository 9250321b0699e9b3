"""Share of the expert layers' token-choices that land on the experts this
chip holds: the ``moe_held`` over the ``moe_choices`` of the program's
``train.step`` spans, summed over the traced window's steps (%). None for a
program without those spans or attrs."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    steps = [s.attrs for s in spans("train.step") if "moe_choices" in s.attrs]
    if not steps:
        return None
    return 100.0 * sum(a["moe_held"] for a in steps) \
        / sum(a["moe_choices"] for a in steps)
