"""Share of the grouped expert products' rows that are padding: the static
rows (``moe_rows``) less the token-choices on held experts (``moe_held``),
over the rows, from the program's ``train.step`` spans, summed over the
traced window's steps (%). 0 for a layout with no padding. None for a
program without those spans or attrs."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    steps = [s.attrs for s in spans("train.step") if "moe_rows" in s.attrs]
    rows = sum(a["moe_rows"] for a in steps)
    if not rows:
        return None
    return 100.0 * (rows - sum(a["moe_held"] for a in steps)) / rows
