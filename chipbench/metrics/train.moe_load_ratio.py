"""Load of the busiest held expert over the mean held expert's: the
``moe_load_max`` (each expert layer's busiest held expert, summed over the
layers) over ``moe_held`` / ``moe_experts`` (the token-choices on held
experts, summed over the layers, over the experts a layer holds), from the
program's ``train.step`` spans, summed over the traced window's steps. 1
is an even load. None for a program without those spans or attrs."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    steps = [s.attrs for s in spans("train.step") if "moe_load_max" in s.attrs]
    held = sum(a["moe_held"] for a in steps)
    if not held:
        return None
    return sum(a["moe_load_max"] * a["moe_experts"] for a in steps) / held
