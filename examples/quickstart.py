"""Quickstart: the three layers of the repro in one script.

 1. Train a reduced assigned-architecture model for a few steps (real JAX).
 2. Serve it with batched requests (prefill + decode, real JAX).
 3. Ask Fulcrum (GMD) for a power-mode plan for the same workload on the
    edge-device model, under power + latency budgets.

Run: PYTHONPATH=src python examples/quickstart.py [--arch stablelm-1.6b]
"""
import argparse

from repro.configs import get_config, make_batch, reduced
from repro.core import problem as P
from repro.core.device_model import DeviceModel, workload_from_model_config
from repro.core.scheduler import Fulcrum
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.serving import GenerationServer
from repro.runtime.train_loop import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config(args.arch))
    print(f"== 1. training reduced {cfg.name} ({cfg.arch_type}) ==")
    trainer = Trainer(cfg, batch=4, seq_len=64)
    report = trainer.train(args.steps, log_every=5)
    print(f"loss {report.losses[0]:.3f} -> {report.final_loss:.3f} "
          f"({report.mean_step_time*1e3:.0f} ms/step)")

    print("== 2. serving with batched requests ==")
    server = GenerationServer(cfg, max_seq=96, bs=2)
    prompt = make_batch(cfg, 32, 2, "prefill")
    tokens = server.generate(prompt, steps=8, prompt_len=32)
    print(f"generated {tokens.shape[1]} tokens/seq: {tokens[0].tolist()}")

    print("== 3. Fulcrum plan for this workload on the edge device ==")
    dev = DeviceModel()
    w = workload_from_model_config(get_config(args.arch), "infer")
    fulcrum = Fulcrum(dev)
    prob = P.InferProblem(power_budget=30.0, latency_budget=5.0, arrival_rate=2.0)
    plan = fulcrum.solve_infer(w, prob, strategy="gmd")
    if plan is None:
        print("no feasible power mode under the budgets")
    else:
        s = plan.solution
        print(f"power mode {s.pm}  bs={s.bs}  latency {s.time*1e3:.0f} ms "
              f"power {s.power:.1f} W  ({plan.profiling_runs} modes profiled, "
              f"{plan.profiling_cost_s/60:.1f} simulated-min profiling)")


if __name__ == "__main__":
    main()
