"""Benchmark harness: one module per paper table/figure + the roofline
deliverable. Prints ``name,value,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig2,fig9,...]

Default grids are strided for CPU wall-time; --full uses the paper's exact
grids (273k+ problem configurations). A suite that raises prints a
``<suite>/ERROR`` row, the others still run, and the command exits non-zero.
"""
import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (bench_arch_fulcrum, bench_concurrent,
                        bench_concurrent_inference, bench_dynamic,
                        bench_infer, bench_interleave_engine,
                        bench_interleaving, bench_multi_tenant,
                        bench_roofline, bench_solver, bench_table1,
                        bench_train)

SUITES = {
    "fig2_interleaving": bench_interleaving.run,
    "fig9_train": bench_train.run,
    "fig10_infer": bench_infer.run,
    "fig11_concurrent": bench_concurrent.run,
    "fig12_dynamic": bench_dynamic.run,
    "fig14_concurrent_infer": bench_concurrent_inference.run,
    "multi_tenant": bench_multi_tenant.run,
    "table1_practitioner": bench_table1.run,
    "arch_fulcrum": bench_arch_fulcrum.run,
    "roofline": bench_roofline.run,
    "solver_microbench": bench_solver.run,
    "interleave_engine": bench_interleave_engine.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (273k+ configs)")
    ap.add_argument("--only", default="",
                    help="comma-separated suite names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    print("name,value,derived")
    failed = []
    for name, fn in SUITES.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn(full=args.full):
                print(row, flush=True)
        except Exception as e:  # keep the harness running
            print(f"{name}/ERROR,1,{type(e).__name__}: {e}", flush=True)
            failed.append(name)
        print(f"{name}/wall_s,{time.time()-t0:.1f},", flush=True)
    if failed:
        sys.exit(f"failed suites: {','.join(failed)}")


if __name__ == "__main__":
    main()
