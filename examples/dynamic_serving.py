"""Dynamic arrival rates (paper §5.4/§7.4): serve an inference workload whose
request rate changes every window, replanning with GMD only when the current
plan stops satisfying the new rate — profiled modes are reused across
windows. Each window is then *executed* by the trace-driven engine
(core.simulate) over a uniform or seeded-Poisson arrival trace.

``--closed-loop`` flips the serving loop from open loop (oracle rates, each
window independent) to the feedback controller (core.controller): the rate
is *estimated* from the observed arrivals (EWMA over inter-arrival gaps,
1.5x planning margin), the previous window's executed violation rate scales
the next effective latency budget, backlogged requests carry across window
boundaries, and power-mode switches cost 0.5 wall seconds.

``--admission`` (implies --closed-loop) adds burst survival: requests the
committed plan provably cannot serve within budget are shed (dropped) or
deferred (re-offered at the next window start), the plan's service headroom
is sized at the window's p95 Poisson arrival-count quantile, and the report
gains goodput / shed / deferred columns.

Run: PYTHONPATH=src:. python examples/dynamic_serving.py [--trace azure]
     [--arrivals poisson] [--strategy rnd150] [--closed-loop]
     [--admission shed]
"""
import argparse

from benchmarks.bench_dynamic import make_traces
from repro.core.controller import ControllerConfig
from repro.core.device_model import DeviceModel, INFER_WORKLOADS
from repro.core.scheduler import Fulcrum
from repro.launch.compile_cache import enable_compile_cache

POWER, LATENCY = 40.0, 0.1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="azure",
                    choices=["azure", "alibaba", "poisson"])
    ap.add_argument("--dnn", default="resnet50")
    ap.add_argument("--strategy", default="gmd")
    ap.add_argument("--arrivals", default="uniform",
                    choices=["uniform", "poisson"])
    ap.add_argument("--closed-loop", action="store_true",
                    help="EWMA-estimated rates + executed-latency feedback "
                         "+ backlog carryover + mode-switch cost")
    ap.add_argument("--admission", default="none",
                    choices=["none", "shed", "defer"],
                    help="SLO-aware admission control (implies "
                         "--closed-loop): shed drops requests the plan "
                         "cannot serve in budget, defer re-offers them at "
                         "the next window start")
    args = ap.parse_args()
    enable_compile_cache()
    closed = args.closed_loop or args.admission != "none"

    rates = make_traces()[args.trace]
    dev = DeviceModel()
    w = INFER_WORKLOADS[args.dnn]
    f = Fulcrum(dev)
    controller = ControllerConfig(
        rate_estimator="ewma", rate_margin=1.5, feedback=True,
        carry_backlog=True, mode_switch_s=0.5,
        admission=args.admission,
        burst_quantile=0.95 if args.admission != "none" else 0.0,
        defer_cap=1000 if args.admission == "defer" else None) \
        if closed else None
    windows = f.serve_dynamic(w, POWER, LATENCY, rates,
                              strategy=args.strategy, window_duration=30.0,
                              arrivals=args.arrivals, controller=controller)

    loop = "closed loop" if closed else "open loop"
    if args.admission != "none":
        loop += f", admission={args.admission}"
    print(f"{args.dnn} on {args.trace} trace ({args.arrivals} arrivals, "
          f"{args.strategy}, {loop}): {len(rates)} x 5-min windows, "
          f"power<={POWER:.0f} W, latency<={LATENCY*1e3:.0f} ms")
    print(f"{'win':>3} {'rate':>6} {'est':>6} {'pm':>18} {'bs':>3} "
          f"{'p95_ms':>7} {'viol%':>5} {'pow_W':>6} {'sw_s':>4} {'carry':>5} "
          f"{'good%':>5} {'shed':>5} {'defer':>5}")
    found = 0
    for i, wr in enumerate(windows):
        est = f"{wr.estimated_rate:6.1f}" if wr.estimated_rate is not None \
            else " " * 6
        if wr.solution is None:
            print(f"{i:3d} {wr.rate:6.1f} {est} {'(no solution)':>18}")
            continue
        found += 1
        sol, rep = wr.solution, wr.report
        gp = f"{100*wr.goodput:5.1f}" if wr.goodput is not None else " " * 5
        print(f"{i:3d} {wr.rate:6.1f} {est} {str(sol.pm):>18} {sol.bs:3d} "
              f"{rep.latency_quantile(0.95)*1e3:7.1f} "
              f"{100*rep.violation_rate(LATENCY):5.1f} {sol.power:6.1f} "
              f"{wr.mode_switch_s:4.1f} {wr.carried_requests:5d} "
              f"{gp} {wr.shed_requests:5d} {wr.deferred_requests:5d}")
    print(f"solutions found: {found}/{len(rates)}")
    if closed:
        sat = sum(wr.report is not None
                  and wr.report.violation_rate(LATENCY) <= 0.05
                  for wr in windows)
        print(f"windows meeting the budget (p95 <= {LATENCY*1e3:.0f} ms): "
              f"{sat}/{len(windows)}")
    if args.admission != "none":
        gps = [wr.goodput for wr in windows if wr.goodput is not None]
        print(f"mean goodput {100*sum(gps)/max(1, len(gps)):.1f}% | "
              f"shed {sum(wr.shed_requests for wr in windows)} | "
              f"deferred {sum(wr.deferred_requests for wr in windows)}")


if __name__ == "__main__":
    main()
