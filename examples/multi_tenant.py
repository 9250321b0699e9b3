"""Multi-tenant serving demo: 3 heterogeneous inference streams share one
edge accelerator with a training job under a single power budget.

 1. Fulcrum (GMD) solves the N-stream problem: one power mode, one minibatch
    size per tenant, and the training interleave factor.
 2. The N-stream managed engine executes the plan over per-tenant Poisson
    arrival traces (merged with stream provenance) and reports per-tenant
    latency quantiles and violation rates plus the realized training
    throughput.

Run: PYTHONPATH=src python examples/multi_tenant.py \
         [--power-budget 45 --duration 60 --arrivals poisson --backend jax]

The ``--backend`` flag picks the execution-engine implementation (NumPy
reference or the jax max-plus scan), so this example doubles as a smoke test
for the on-accelerator engine path; the backend that actually ran is printed
with the execution report.
"""
import argparse

from repro.core import problem as P
from repro.core.backend import resolve_backend
from repro.core.device_model import (DeviceModel, INFER_WORKLOADS,
                                     TRAIN_WORKLOADS)
from repro.core.scheduler import Fulcrum
from repro.launch.compile_cache import enable_compile_cache

TENANTS = [
    # (infer workload, arrival rate req/s, peak-latency budget s)
    ("mobilenet", 40.0, 0.8),     # camera feed classifier
    ("lstm", 60.0, 0.5),          # sensor-stream scorer
    ("resnet50", 20.0, 1.5),      # periodic quality inspection
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--train", default="resnet18",
                    choices=sorted(TRAIN_WORKLOADS))
    ap.add_argument("--power-budget", type=float, default=45.0)
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--arrivals", default="poisson",
                    choices=["uniform", "poisson"])
    ap.add_argument("--strategy", default="gmd")
    ap.add_argument("--backend", default=None, choices=["numpy", "jax"],
                    help="execution-engine backend (default: resolve via "
                         "FULCRUM_ENGINE_BACKEND, falling back to numpy)")
    args = ap.parse_args()
    enable_compile_cache()
    backend = resolve_backend(args.backend)

    dev = DeviceModel()
    w_tr = TRAIN_WORKLOADS[args.train]
    specs = tuple(P.StreamSpec(rate, lat, INFER_WORKLOADS[name])
                  for name, rate, lat in TENANTS)
    prob = P.MultiTenantProblem(args.power_budget, specs)

    f = Fulcrum(dev)
    plan = f.solve_multi_tenant(w_tr, prob, args.strategy)
    if plan is None:
        print("Fulcrum: no feasible multi-tenant plan under the budgets")
        return
    s = plan.solution
    print(f"plan ({args.strategy}): pm={s.pm}  power={s.power:.1f} W "
          f"(budget {args.power_budget:.0f} W), {plan.profiling_runs} "
          f"modes profiled")
    for (name, rate, lat), bs, lam in zip(TENANTS, s.bss, s.times):
        print(f"  {name:<10} rate={rate:>5.1f}/s  bs={bs:<3} "
              f"planned peak latency {lam*1e3:6.0f} ms (budget {lat*1e3:.0f})")
    print(f"  train      tau_tr={s.tau_tr}/cycle -> "
          f"{s.throughput:.2f} minibatches/s planned")

    rep = f.execute_multi_tenant(plan, prob, w_tr, duration=args.duration,
                                 arrivals=args.arrivals, backend=backend)
    print(f"\nexecuted {args.duration:.0f} s of {args.arrivals} arrivals "
          f"({len(rep.trace)} requests merged across {len(specs)} tenants) "
          f"on the {backend} engine backend:")
    viols = rep.violation_rates([sp.latency_budget for sp in specs])
    for (name, _, lat), r, v in zip(TENANTS, rep.streams, viols):
        print(f"  {name:<10} served {len(r.latencies):>5} reqs  "
              f"q50 {r.latency_quantile(0.5)*1e3:6.0f} ms  "
              f"q95 {r.latency_quantile(0.95)*1e3:6.0f} ms  "
              f"violations {100*v:4.1f} %")
    print(f"  train      {rep.train_minibatches} minibatches "
          f"({rep.train_throughput:.2f}/s) at {rep.power:.1f} W")


if __name__ == "__main__":
    main()
