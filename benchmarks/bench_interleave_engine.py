"""Execution-engine microbenchmark: the vectorized trace-driven simulators
(core.simulate) vs the seed's scalar per-request loops, over the *full*
Fig. 2 interleaving sweep (10 GMD-planned configs x 3 approaches at 120 s),
plus the NumPy-vs-jax *engine backend* comparison: the same managed sweep
run lane-by-lane on NumPy vs as one batched max-plus-scan program on jax.

The managed outputs of both paths are asserted identical before timing (the
engine's exactness contract); the jax engine is cross-checked against NumPy
within the documented tolerance (atol=1e-8 s, rtol=1e-9 — see
``docs/exactness.md``). Speedups are printed as CSV rows and snapshotted to
``benchmarks/results/BENCH_interleave.json`` so they are tracked across PRs,
mirroring bench_solver's BENCH_solver.json.

The ``lane_scaling`` section sweeps the lane axis — 10 to 100k concurrent
managed lanes sharing one short trace — through ``simulate_batch`` on every
engine backend (numpy / jax), recording configs/s per backend so
the NumPy-vs-accelerator crossover is a measured curve, not folklore.
``--quick`` caps the sweep at 1k lanes and snapshots to
``BENCH_interleave_partial.json`` (the committed full snapshot stays
canonical); ``--check`` gates the result: jax must beat NumPy at 1k lanes
and every pre-existing snapshot key must still be present."""
from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from repro.core import simulate as S
from repro.core.backend import jax_available

from benchmarks.bench_interleaving import solve_configs
from benchmarks.common import DEV, row, snapshot

SNAPSHOT = Path(__file__).parent / "results" / "BENCH_interleave.json"
QUICK_SNAPSHOT = SNAPSHOT.with_name("BENCH_interleave_partial.json")

LANE_COUNTS = (10, 100, 1000, 10000, 100000)
QUICK_LANE_COUNTS = (10, 100, 1000)
# the lane-count at which the --check gate requires jax >= NumPy configs/s
GATE_LANES = 1000

SCALAR = {"managed": S.managed_scalar,
          "native": lambda *a: S.native_scalar(*a, seed=0),
          "streams": lambda *a: S.streams_scalar(*a, seed=0)}
VECTOR = {"managed": lambda *a: S.simulate(*a, approach="managed"),
          "native": lambda *a: S.simulate(*a, approach="native", seed=0),
          "streams": lambda *a: S.simulate(*a, approach="streams", seed=0)}


def _time(sims, repeats: int) -> float:
    t0 = time.perf_counter()
    for _ in range(repeats):
        for fn, args in sims:
            fn(*args)
    return (time.perf_counter() - t0) / repeats


def _lane_scaling(w_tr, w_in, solved, lane_counts) -> dict:
    """Sweep the lane axis through simulate_batch on every engine backend.

    All lanes share ONE short trace object (~128 arrivals) so the 100k-lane
    point measures engine throughput, not trace-generation memory; (pm, bs)
    cycle through the GMD-planned configs so event shapes stay realistic."""
    trace = S.ArrivalTrace.poisson(32.0, 4.0, seed=7)
    pms = [p.pm for _, p, _ in solved]
    bss = [p.bs for _, p, _ in solved]
    backends = ["numpy"]
    if jax_available():
        backends.append("jax")
    rows = []
    for lanes in lane_counts:
        pml = [pms[i % len(pms)] for i in range(lanes)]
        bsl = [bss[i % len(bss)] for i in range(lanes)]
        traces = [trace] * lanes
        args = (DEV, w_tr, w_in, pml, bsl, traces)
        rec = {"lanes": lanes, "configs": lanes}
        for bk in backends:
            S.simulate_batch(*args, backend=bk)          # warm jit / caches
            t0 = time.perf_counter()
            S.simulate_batch(*args, backend=bk)
            rec[f"{bk}_configs_per_s"] = lanes / (time.perf_counter() - t0)
        rows.append(rec)
    return {"trace_arrivals": len(trace), "backends": backends,
            "lane_counts": list(lane_counts), "rows": rows}


# top-level snapshot keys every run must produce — the --check gate's
# byte-identity floor for pre-existing BENCH structure
_REQUIRED_KEYS = ("configs", "duration_s", "requests_total", "approaches",
                  "scalar_s", "vector_s", "speedup")
_APPROACH_KEYS = ("configs", "scalar_s", "vector_s", "speedup")


def check(results: dict) -> None:
    """--check gate: pre-existing snapshot structure intact, and the jax
    engine at least matches NumPy throughput at the 1k-lane point."""
    for key in _REQUIRED_KEYS:
        assert key in results, f"missing snapshot key {key!r}"
    for name in ("managed", "native", "streams"):
        app = results["approaches"][name]
        for key in _APPROACH_KEYS:
            assert key in app, f"missing approaches.{name}.{key}"
    if jax_available():
        for key in ("configs", "numpy_s", "jax_s", "speedup",
                    "max_abs_latency_diff"):
            assert key in results["engine_backends"], \
                f"missing engine_backends.{key}"
        gate = [r for r in results["lane_scaling"]["rows"]
                if r["lanes"] == GATE_LANES]
        assert gate, f"lane_scaling has no {GATE_LANES}-lane row"
        np_cps = gate[0]["numpy_configs_per_s"]
        jax_cps = gate[0]["jax_configs_per_s"]
        assert jax_cps >= np_cps, (
            f"jax engine lost to NumPy at {GATE_LANES} lanes: "
            f"{jax_cps:.0f} vs {np_cps:.0f} configs/s")


def run(full: bool = False, quick: bool = False) -> list[str]:
    # always measure the full Fig. 2 sweep: the point is paper-scale traces
    w_tr, w_in, configs = solve_configs(duration=120.0)
    solved = [(prob, plan, trace) for _, prob, plan, trace in configs
              if plan is not None]

    # exactness gate: vectorized managed == scalar reference on every config
    for prob, plan, trace in solved:
        a = S.simulate(DEV, w_tr, w_in, plan.pm, plan.bs, trace, "managed")
        b = S.managed_scalar(DEV, w_tr, w_in, plan.pm, plan.bs, trace)
        assert a.latencies.tolist() == b.latencies, "managed engine diverged"
        assert a.train_minibatches == b.train_minibatches
        assert a.power == b.power

    repeats = 3 if full else 1
    results: dict = {"configs": len(solved), "duration_s": 120.0,
                     "requests_total": sum(len(t) for _, _, t in solved),
                     "approaches": {}}
    rows: list[str] = []
    total_scalar = total_vector = 0.0
    for name in ("managed", "native", "streams"):
        sims_s = [(SCALAR[name], (DEV, w_tr, w_in, p.pm, p.bs, t))
                  for _, p, t in solved]
        sims_v = [(VECTOR[name], (DEV, w_tr, w_in, p.pm, p.bs, t))
                  for _, p, t in solved]
        _time(sims_v, 1)                       # warm allocator / caches
        scalar_s = _time(sims_s, repeats)
        vector_s = _time(sims_v, repeats)
        total_scalar += scalar_s
        total_vector += vector_s
        speedup = scalar_s / vector_s
        results["approaches"][name] = {
            "configs": len(solved), "scalar_s": scalar_s,
            "vector_s": vector_s, "speedup": speedup}
        rows.append(row(f"interleave_engine/{name}/speedup", speedup,
                        f"scalar={scalar_s*1e3:.1f}ms;"
                        f"vector={vector_s*1e3:.1f}ms;n={len(solved)}"))
    results["scalar_s"] = total_scalar
    results["vector_s"] = total_vector
    results["speedup"] = total_scalar / total_vector
    rows.append(row("interleave_engine/full_sweep/speedup",
                    results["speedup"],
                    f"requests={results['requests_total']};"
                    f"configs={len(solved)}x3"))

    # -- engine backends: NumPy lane loop vs one batched jax scan program ----
    if jax_available():
        pms = [p.pm for _, p, _ in solved]
        bss = [p.bs for _, p, _ in solved]
        traces = [t for _, _, t in solved]
        args = (DEV, w_tr, w_in, pms, bss, traces)
        ref = S.simulate_batch(*args, backend="numpy")
        got = S.simulate_batch(*args, backend="jax")   # also warms the jit
        for a, b in zip(ref, got):
            np.testing.assert_allclose(b.latencies, a.latencies,
                                       rtol=1e-9, atol=1e-8,
                                       err_msg="jax engine out of tolerance")
            assert abs(a.train_minibatches - b.train_minibatches) <= 2
        numpy_s = _time([(lambda: S.simulate_batch(*args, backend="numpy"),
                          ())], repeats)
        jax_s = _time([(lambda: S.simulate_batch(*args, backend="jax"),
                        ())], repeats)
        results["engine_backends"] = {
            "configs": len(solved), "numpy_s": numpy_s, "jax_s": jax_s,
            "speedup": numpy_s / jax_s,
            "max_abs_latency_diff": max(
                float(np.abs(np.asarray(b.latencies)
                             - np.asarray(a.latencies)).max(initial=0.0))
                for a, b in zip(ref, got))}
        rows.append(row("interleave_engine/managed_batch/jax_vs_numpy",
                        numpy_s / jax_s,
                        f"numpy={numpy_s*1e3:.1f}ms;jax={jax_s*1e3:.1f}ms;"
                        f"n={len(solved)}"))

    # -- lane scaling: the NumPy-vs-jax crossover curve ----------------------
    lane_counts = QUICK_LANE_COUNTS if quick else LANE_COUNTS
    results["lane_scaling"] = _lane_scaling(w_tr, w_in, solved, lane_counts)
    for rec in results["lane_scaling"]["rows"]:
        parts = [f"{bk}={rec[f'{bk}_configs_per_s']:.0f}cfg_s"
                 for bk in results["lane_scaling"]["backends"]]
        rows.append(row(f"interleave_engine/lane_scaling/{rec['lanes']}",
                        rec.get("jax_configs_per_s",
                                rec["numpy_configs_per_s"]),
                        ";".join(parts)))

    snapshot(QUICK_SNAPSHOT if quick else SNAPSHOT, results,
             configs=len(solved) * 3)
    run.last_results = results          # for --check / tests
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="more timing repeats")
    ap.add_argument("--quick", action="store_true",
                    help="cap the lane sweep at 1k lanes; snapshot to "
                         "BENCH_interleave_partial.json")
    ap.add_argument("--check", action="store_true",
                    help="assert snapshot structure + jax>=NumPy at 1k lanes")
    cli = ap.parse_args()
    for r in run(full=cli.full, quick=cli.quick):
        print(r)
    if cli.check:
        check(run.last_results)
        print("interleave_engine/check,1,"
              f"jax_ge_numpy_at_{GATE_LANES}_lanes;keys_ok")
