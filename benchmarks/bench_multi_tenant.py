"""Multi-tenant benchmark: N∈{2,3,4} inference streams concurrent with
training under one power budget, swept across the 15 (train workload x N)
combinations drawn from the paper's 5 train + 5 infer DNNs.

Per combination: the oracle solves the whole problem grid with the batched
multi-tenant grid solver on the NumPy *and* jax backends (both timed, results
cross-checked), GMD plans the median solvable problem, and the N-stream
managed engine executes it — per-tenant violation rates and training
throughput are reported. The executed plan is replayed on both *engine*
backends too (NumPy reference vs the jax max-plus scan) and cross-checked
within the documented tolerance (``docs/exactness.md``). Rows are printed as
CSV and snapshotted to ``benchmarks/results/BENCH_multi_tenant.json``.

The ``lane_scaling`` section mirrors bench_interleave_engine's: 10 to 100k
two-stream multi-tenant lanes (shared traces) through
``simulate_multi_tenant_batch`` on every engine backend, recording the
NumPy-vs-jax configs/s crossover on the N-stream path.
"""
from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from repro.core import problem as P
from repro.core import simulate as S
from repro.core.backend import jax_available
from repro.core.device_model import INFER_WORKLOADS, TRAIN_WORKLOADS
from repro.core.scheduler import Fulcrum

from benchmarks.common import DEV, ORACLE, SPACE, loss_pct, median, row, \
    snapshot

SNAPSHOT = Path(__file__).parent / "results" / "BENCH_multi_tenant.json"

LANE_COUNTS = (10, 100, 1000, 10000, 100000)

# per-stream (rate, latency budget) matched to each DNN's service time scale
STREAM_DEFAULTS = {
    "mobilenet": (40.0, 0.8),
    "lstm": (60.0, 0.5),
    "resnet50": (25.0, 1.2),
    "yolov8n": (20.0, 1.5),
    "bert": (2.0, 4.0),
}
INFER_ORDER = ["mobilenet", "lstm", "resnet50", "yolov8n", "bert"]
TRAIN_ORDER = ["resnet18", "mobilenet", "yolov8n", "bert", "lstm"]


def _streams(train_idx: int, n: int) -> tuple:
    """N heterogeneous streams: rotate the infer pool per train workload so
    the 15 combos cover every pairing."""
    names = [INFER_ORDER[(train_idx + k) % len(INFER_ORDER)]
             for k in range(n)]
    specs = []
    for name in names:
        rate, lat = STREAM_DEFAULTS[name]
        specs.append(P.StreamSpec(rate, lat, INFER_WORKLOADS[name]))
    return tuple(specs), names


def _problem_grid(specs: tuple, full: bool) -> list:
    """(power budget, latency scale, rate scale) sweep around the per-stream
    defaults."""
    pows = range(20, 56, 5) if full else (25, 35, 45, 55)
    lat_scales = (0.75, 1.0, 1.5, 2.0) if full else (1.0, 1.5)
    rate_scales = (0.5, 0.75, 1.0) if full else (0.5, 1.0)
    probs = []
    for pb in pows:
        for ls in lat_scales:
            for rs in rate_scales:
                streams = tuple(
                    P.StreamSpec(s.arrival_rate * rs, s.latency_budget * ls,
                                 s.workload)
                    for s in specs)
                probs.append(P.MultiTenantProblem(float(pb), streams))
    return probs


def _lane_scaling(lane_counts=LANE_COUNTS) -> dict:
    """Lane-axis sweep of the N-stream engine: every lane is the same
    2-stream (mobilenet + lstm) scenario over two shared short traces, with
    (pm, per-stream bs) cycling so event shapes vary realistically."""
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    streams = [INFER_WORKLOADS["mobilenet"], INFER_WORKLOADS["lstm"]]
    tr_a = S.ArrivalTrace.poisson(20.0, 4.0, seed=11)
    tr_b = S.ArrivalTrace.poisson(12.0, 4.0, seed=13)
    modes = SPACE.all_modes()
    bs_cycle = [[4, 8], [8, 16], [16, 4], [32, 8]]
    backends = ["numpy"]
    if jax_available():
        backends.append("jax")
    rows = []
    for lanes in lane_counts:
        args = (DEV, w_tr, [streams] * lanes,
                [modes[(7 * i) % len(modes)] for i in range(lanes)],
                [bs_cycle[i % len(bs_cycle)] for i in range(lanes)],
                [[tr_a, tr_b]] * lanes)
        rec = {"lanes": lanes, "configs": lanes}
        for bk in backends:
            S.simulate_multi_tenant_batch(*args, backend=bk)   # warm
            t0 = time.perf_counter()
            S.simulate_multi_tenant_batch(*args, backend=bk)
            rec[f"{bk}_configs_per_s"] = lanes / (time.perf_counter() - t0)
        rows.append(rec)
    return {"trace_arrivals": len(tr_a) + len(tr_b), "n_streams": 2,
            "backends": backends, "lane_counts": list(lane_counts),
            "rows": rows}


def run(full: bool = False) -> list[str]:
    rows: list[str] = []
    results: dict = {"rows": []}
    for n in (2, 3, 4):
        for ti, tr_name in enumerate(TRAIN_ORDER):
            w_tr = TRAIN_WORKLOADS[tr_name]
            specs, stream_names = _streams(ti, n)
            probs = _problem_grid(specs, full)
            label = f"multi_tenant/{tr_name}+{n}x"

            t0 = time.perf_counter()
            opts_np = ORACLE.solve_multi_tenant_batch(w_tr, probs, "numpy")
            numpy_s = time.perf_counter() - t0
            try:
                ORACLE.solve_multi_tenant_batch(w_tr, probs[:2], "jax")
                t0 = time.perf_counter()
                opts_jax = ORACLE.solve_multi_tenant_batch(w_tr, probs, "jax")
                jax_s = time.perf_counter() - t0
            except RuntimeError:          # jax unavailable: record honestly
                opts_jax, jax_s = None, None
            if opts_jax is not None:
                for a, b in zip(opts_np, opts_jax):
                    assert (a is None) == (b is None), "backend divergence"
                    assert a is None or (a.pm, a.bss, a.tau_tr) == \
                        (b.pm, b.bss, b.tau_tr), "backend divergence"

            solvable = [(pr, opt) for pr, opt in zip(probs, opts_np)
                        if opt is not None]
            rec = {"n_streams": n, "train": tr_name,
                   "streams": stream_names, "configs": len(probs),
                   "solvable": len(solvable),
                   "numpy_configs_per_s": len(probs) / numpy_s}
            if jax_s is not None:
                rec["jax_configs_per_s"] = len(probs) / jax_s
            rows.append(row(f"{label}/solvable_pct",
                            100.0 * len(solvable) / len(probs),
                            f"streams={'+'.join(stream_names)};"
                            f"configs={len(probs)}"))

            if solvable:
                # GMD on the median solvable problem + engine execution
                prob, opt = solvable[len(solvable) // 2]
                f = Fulcrum(DEV, SPACE)
                plan = f.solve_multi_tenant(w_tr, prob, "gmd")
                if plan is not None:
                    sol = plan.solution
                    rec["gmd"] = {
                        "tput_loss_pct": loss_pct(opt.throughput,
                                                  sol.throughput),
                        "profiling_runs": plan.profiling_runs}
                    rep = f.execute_multi_tenant(plan, prob, w_tr,
                                                 duration=30.0)
                    if jax_available():
                        # engine-backend cross-check: jax scan vs reference
                        rj = f.execute_multi_tenant(plan, prob, w_tr,
                                                    duration=30.0,
                                                    backend="jax")
                        diff = 0.0
                        for ra, rb in zip(rep.streams, rj.streams):
                            np.testing.assert_allclose(
                                rb.latencies, ra.latencies,
                                rtol=1e-9, atol=1e-8,
                                err_msg="jax engine out of tolerance")
                            if len(ra.latencies):
                                diff = max(diff, float(np.abs(
                                    np.asarray(rb.latencies)
                                    - np.asarray(ra.latencies)).max()))
                        assert abs(rep.train_minibatches
                                   - rj.train_minibatches) <= 2
                        rec["engine_backend_max_abs_diff"] = diff
                    viols = rep.violation_rates(
                        [s.latency_budget for s in prob.streams])
                    rec["executed"] = {
                        "configs": 1,
                        "train_mb_per_s": rep.train_throughput,
                        "power": rep.power,
                        "per_tenant_violation_pct":
                            [100.0 * v for v in viols],
                        "worst_q95_ms":
                            rep.worst_latency_quantile(0.95) * 1e3}
                    rows.append(row(
                        f"{label}/gmd/executed_worst_q95_ms",
                        rep.worst_latency_quantile(0.95) * 1e3,
                        f"viol_max_pct={100.0 * max(viols):.1f};"
                        f"tput={rep.train_throughput:.2f}mb_s"))
                oracle_tputs = [o.throughput for _, o in solvable]
                rows.append(row(f"{label}/oracle/median_tput_mb_s",
                                median(oracle_tputs),
                                f"solvable={len(solvable)}"))
            results["rows"].append(rec)

    total = sum(r["configs"] for r in results["rows"])
    results["configs"] = total
    rows.append(row("multi_tenant/total_configs", total,
                    f"combos={len(results['rows'])}"))

    # -- lane scaling: N-stream engine crossover curve -----------------------
    results["lane_scaling"] = _lane_scaling()
    for rec in results["lane_scaling"]["rows"]:
        parts = [f"{bk}={rec[f'{bk}_configs_per_s']:.0f}cfg_s"
                 for bk in results["lane_scaling"]["backends"]]
        rows.append(row(f"multi_tenant/lane_scaling/{rec['lanes']}",
                        rec.get("jax_configs_per_s",
                                rec["numpy_configs_per_s"]),
                        ";".join(parts)))

    snapshot(SNAPSHOT, results, configs=total)
    rows.append(row("multi_tenant/snapshot", 1, str(SNAPSHOT)))
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale problem grids")
    cli = ap.parse_args()
    for r in run(full=cli.full):
        print(r)
