"""End-to-end driver: concurrent DNN training + inference serving under
Fulcrum, with REAL JAX execution (the paper's headline scenario).

 1. GMD solves the concurrent problem on the edge-device model -> plan
    (power mode, inference minibatch size bs, interleave factor).
 2. The plan's bs drives the real managed-interleave runtime: one process
    owns the accelerator, alternating jitted train minibatches of one
    model with jitted inference minibatches of another, switching only at
    minibatch boundaries; requests arrive at a constant rate and
    per-request latency is measured wall-clock.

Run: PYTHONPATH=src python examples/concurrent_edge.py --reduced \
         [--train-arch stablelm-1.6b --infer-arch internvl2-1b --duration 15]

``--reduced`` runs the 2-layer, d_model-256 variants of both models (what
fits a CPU run). Without it both run at their published sizes, which needs
an accelerator that holds the trainer's weights, gradients and AdamW state
next to the server's weights (``chip_smoke.py`` runs such a pair on one
TPU v5e).
"""
import argparse

from repro.configs import get_config, reduced
from repro.core import problem as P
from repro.core.device_model import DeviceModel, workload_from_model_config
from repro.core.scheduler import Fulcrum
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.interleave_runtime import (InterleaveConfig,
                                              ManagedInterleaveRuntime)
from repro.runtime.serving import BatchInferenceServer
from repro.runtime.train_loop import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-arch", default="stablelm-1.6b")
    ap.add_argument("--infer-arch", default="internvl2-1b")
    ap.add_argument("--duration", type=float, default=15.0)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--latency-budget", type=float, default=4.0)
    ap.add_argument("--power-budget", type=float, default=35.0)
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer smoke variants (default: published sizes)")
    args = ap.parse_args()
    enable_compile_cache()

    # 1. plan on the device model
    dev = DeviceModel()
    w_tr = workload_from_model_config(get_config(args.train_arch), "train")
    w_in = workload_from_model_config(get_config(args.infer_arch), "infer")
    prob = P.ConcurrentProblem(args.power_budget, args.latency_budget, args.rate)
    plan = Fulcrum(dev).solve_concurrent(w_tr, w_in, prob, strategy="gmd")
    if plan is None:
        print("Fulcrum: no feasible plan under the budgets"); return
    s = plan.solution
    print(f"Fulcrum plan: pm={s.pm} bs={s.bs} tau_tr={s.tau_tr} "
          f"(predicted latency {s.time*1e3:.0f} ms, power {s.power:.1f} W, "
          f"{plan.profiling_runs} modes profiled)")

    # 2. execute for real on the default device
    size = reduced if args.reduced else (lambda cfg: cfg)
    cfg_tr = size(get_config(args.train_arch))
    cfg_in = size(get_config(args.infer_arch))
    print(f"building models + compiling steps ({cfg_tr.num_layers}L "
          f"d={cfg_tr.d_model} trainer, {cfg_in.num_layers}L "
          f"d={cfg_in.d_model} server) ...")
    trainer = Trainer(cfg_tr, batch=4, seq_len=64)
    # a VLM request carries its patch embeddings ahead of 64 text tokens
    seq_in = 64 + (cfg_in.n_patches if cfg_in.arch_type == "vlm" else 0)
    server = BatchInferenceServer(cfg_in, seq_len=seq_in, bs=s.bs or 4)
    runtime = ManagedInterleaveRuntime(
        trainer, server,
        InterleaveConfig(arrival_rate=args.rate, infer_bs=s.bs or 4,
                         latency_budget=args.latency_budget,
                         duration=args.duration))
    print(f"running managed interleaving for {args.duration:.0f} s wall ...")
    rep = runtime.run()
    print(f"requests served: {len(rep.latencies)}  "
          f"median latency {rep.latency_quantile(0.5)*1e3:.0f} ms  "
          f"p95 {rep.latency_quantile(0.95)*1e3:.0f} ms  "
          f"violations {100*rep.violation_rate(args.latency_budget):.1f}%")
    print(f"training minibatches completed concurrently: {rep.train_minibatches} "
          f"({rep.train_throughput:.2f}/s)")


if __name__ == "__main__":
    main()
