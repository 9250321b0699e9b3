"""Readings of a cell's control and planted faults, for setting its limits.

  python3 chipbench/control.py --workload <cell> --seeds 1,2,3

For each seed, the plain reference is put in the system's place at the
cell's own sizes, once in the next lower precision (the control) and once
with each planted fault, and compared with the float32 reference exactly as
a run's output is, and judged against the cell's limits as a run is
(``correct`` of each, which the control and each fault must fail). One
JSON line per seed. The benchmark's own runs never run this; it needs the
accelerator the cell names.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from run import Benchmark, accelerator, log  # noqa: E402  (sets sys.path)
from chipbench.checks import Readings  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    bench = Benchmark.load()
    cell = bench.cell(a.workload)
    if accelerator(cell["chips"]) is None:
        return 3
    config = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    kind = bench.kind(config)
    seconds = bench.spec["run_seconds"]
    for seed in (int(x) for x in a.seeds.split(",")):
        out = kind.control(config, mix, seed, seconds, log=log)
        correct = {k: Readings(v, config["limits"]).correct
                   for k, v in out.items()}
        print(json.dumps({"workload": a.workload, "seed": seed, **out,
                          "correct": correct}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
