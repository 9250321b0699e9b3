"""Fleet-scale serving (``Scenario.FLEET``): one aggregate request stream
served on K heterogeneous edge devices, every window stepped as ONE batched
program — one weighted round-robin dispatch pass, one batched grid solve
per planning-ladder rung, one multi-lane engine call (devices are lanes).

Each device is the base Orin model with deterministic per-device time/power
multipliers (``fleet_device``), governed by its own closed-loop controller
state (EWMA rate estimate, latency feedback, backlog carryover). The
batched step is bitwise-identical on NumPy to serving the K devices one by
one with the existing single-device loop — ``--sequential`` runs that
reference instead so the two can be diffed. ``--fused`` (jax/pallas only)
collapses each window further: the whole plan ladder + admission + engine
runs as ONE compiled launch per window (``core.fused_window``), and the
per-window host-dispatch count is printed from the backend counters.

Run: PYTHONPATH=src python examples/fleet_serving.py [--devices 8]
     [--dispatch least-backlog] [--backend jax] [--sequential] [--fused]
"""
import argparse

import numpy as np

from repro.core import fleet as F
from repro.core.controller import ControllerConfig
from repro.core.device_model import INFER_WORKLOADS
from repro.launch.compile_cache import enable_compile_cache

POWER, LATENCY = 30.0, 0.1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--dnn", default="mobilenet",
                    choices=sorted(INFER_WORKLOADS))
    ap.add_argument("--dispatch", default="capacity",
                    choices=["capacity", "least-backlog"])
    ap.add_argument("--backend", default=None,
                    help="engine backend (numpy/jax/pallas; default env)")
    ap.add_argument("--sequential", action="store_true",
                    help="run the K-sequential-loops reference instead of "
                         "the batched fleet step")
    ap.add_argument("--fused", action="store_true",
                    help="run each window as ONE compiled solve+simulate "
                         "launch (jax/pallas backends only)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.fused and args.sequential:
        ap.error("--fused fuses the batched step; drop --sequential")

    spec = F.FleetSpec(args.devices, seed=3, dispatch=args.dispatch)
    cfg = ControllerConfig(rate_estimator="ewma", rate_margin=1.5,
                           feedback=True, carry_backlog=True,
                           mode_switch_s=0.25)
    # aggregate offered rate per window: cruise, surge, recover
    rates = [30.0 * args.devices * m for m in (0.9, 1.5, 0.8, 1.1)]
    serve = F.serve_fleet_sequential if args.sequential else F.serve_fleet
    kw = {"fused": True} if args.fused else {}
    from repro.core.backend import dispatch_count
    d0 = dispatch_count()
    wins = serve(INFER_WORKLOADS[args.dnn], POWER, LATENCY, rates, spec,
                 window_duration=5.0, arrivals="poisson", seed=11,
                 backend=args.backend, controller=cfg, **kw)
    d1 = dispatch_count()

    kind = ("fused" if args.fused
            else "sequential" if args.sequential else "batched")
    print(f"{kind} fleet of "
          f"{args.devices} devices, dispatch={args.dispatch}")
    if d1 > d0:
        print(f"compiled-program launches: {d1 - d0} "
              f"({(d1 - d0) / len(rates):.1f} per window)")
    ts = [d.time_scale for d in spec.devices()]
    print(f"device time scales: min={min(ts):.3f} max={max(ts):.3f}")
    print(f"{'win':>3} {'rate':>7} {'offered':>8} {'goodput':>8} "
          f"{'power_w':>8} {'served_devs':>11}  dispatch")
    for i, wr in enumerate(wins):
        served = sum(d.solution is not None for d in wr.devices)
        counts = np.asarray(wr.dispatch_counts)
        print(f"{i:>3} {wr.rate:>7.1f} {wr.offered_requests:>8} "
              f"{wr.goodput:>8.3f} {wr.attributed_power:>8.1f} "
              f"{served:>4}/{len(wr.devices):<4}  "
              f"min={counts.min()} max={counts.max()}")


if __name__ == "__main__":
    main()
