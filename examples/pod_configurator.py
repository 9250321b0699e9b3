"""Beyond-paper example: Fulcrum's GMD as a TPU-pod auto-configurator.

For each assigned architecture, search (tensor-parallel width, microbatches,
remat) for the train_4k shape with ~11 roofline "profiles" — the TPU analogue
of profiling ~11 power modes on a Jetson — and compare against the exhaustive
oracle over the knob grid. The HBM budget (16 GiB/chip) plays the paper's
power budget; step time plays minibatch time.

Run: PYTHONPATH=src python examples/pod_configurator.py [--chips 256]
"""
import argparse

from repro.configs import ARCH_IDS, get_config
from repro.core.tpu_adapter import (GMDForTPU, RooflineTPUModel, TPUKnobSpace,
                                    exhaustive_best)
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()
    enable_compile_cache()

    print(f"{'arch':16s} {'GMD config':>20s} {'t_step':>8s} {'HBM':>7s} "
          f"{'probes':>6s} {'vs oracle':>9s}")
    for arch in ARCH_IDS:
        model = RooflineTPUModel(get_config(arch), args.seq, args.batch,
                                 "train", chips=args.chips)
        space = TPUKnobSpace(args.chips)
        gmd = GMDForTPU(model, space)
        sol = gmd.solve()
        opt = exhaustive_best(model, space)
        if sol is None:
            note = ("does not fit 16 GiB/chip at any config — needs "
                    "multi-pod (--chips 512) or 8-bit optimizer state"
                    if opt is None else "search failed")
            print(f"{arch:16s} {'-':>20s} {'-':>8s} {'-':>7s} "
                  f"{gmd.num_profiles:6d} {note}")
            continue
        exc = 100 * (sol.time - opt[1]) / opt[1]
        print(f"{arch:16s} {str(sol.pm):>20s} {sol.time*1e3:7.0f}m "
              f"{sol.power/2**30:6.1f}G {gmd.num_profiles:6d} {exc:+8.1f}%")


if __name__ == "__main__":
    main()
