"""Bring-up smoke on one TPU chip: the main paths, through the entry points
users call, at real sizes.

  python3 chip_smoke.py

Phases, each printing its own lines:
  (a) device   - JAX must see a TPU; anything else exits non-zero at once.
  (b) planner  - grid_eval.solve_concurrent_batch(backend="jax") over the
                 paper's full concurrent grid (41 power x 16 latency x 10
                 rate budgets) for the mobilenet train/infer pair over every
                 power mode; selections must equal backend="numpy".
  (c) fleet    - fleet.serve_fleet(fused=True, backend="jax") at K=512 with
                 the settings of benchmarks/bench_fleet.py and shed
                 admission; executed latencies within JAX_TOL of the NumPy
                 reference, host dispatches per window, first-window and
                 warm-window times.
  (d) trainer  - Trainer steps of mamba2-780m at its published widths and
                 depth, sequence 128; the loss must be finite.
  (e) runtime  - ManagedInterleaveRuntime: internvl2-1b served at full size
                 through BatchInferenceServer while mamba2-780m trains at
                 full size beside it; requests served, p95 against the
                 budget, training minibatches.

Models are built from random weights (seeded). The last line of standard
output is one JSON object naming the device; the command exits 0 only when
every phase passed. The persistent compilation cache is on
(``repro.launch.compile_cache``); a second run reports its cache hits.
"""
from __future__ import annotations

import gc
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# (c) fleet size; (d)/(e) trainer sequence length, the one
# benchmarks/bench_arch_fulcrum.py plans the pair for
FLEET_K = 512
SEQ_LEN = 128
# (e) the pair and its problem (bench_arch_fulcrum: 45 W, 4 s, 2 req/s).
# Both run at full depth: on a 16 GB v5e the 48-layer trainer fits beside
# the server (10.59 GiB held between steps), so no layer is cut
TRAIN_ARCH, SERVE_ARCH = "mamba2-780m", "internvl2-1b"
PAIR_BUDGETS = (45.0, 4.0, 2.0)        # power W, latency s, rate req/s
TRACE_S = 10.0

_cache_hits = 0


def _count_cache_hits(event: str, **_) -> None:
    global _cache_hits
    if event == "/jax/compilation_cache/cache_hits":
        _cache_hits += 1


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _on_tpu(tree) -> bool:
    import jax
    return {d.platform for x in jax.tree.leaves(tree)
            for d in x.devices()} == {"tpu"}


def _gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def _memory_line(tag: str) -> None:
    # buffers held between steps; the runtime's peak counter does not see a
    # program's temporaries, so it is not printed
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if stats:
        print(f"[{tag}] device memory: in use "
              f"{_gib(stats.get('bytes_in_use', 0))} of "
              f"{_gib(stats.get('bytes_limit', 0))}", flush=True)


def phase_planner() -> None:
    from benchmarks.common import concurrent_problem_grid
    from repro.core import grid_eval as G
    from repro.core import problem as P
    from repro.core.device_model import (DeviceModel, INFER_WORKLOADS,
                                         TRAIN_WORKLOADS)
    from repro.core.powermode import PowerModeSpace

    problems = concurrent_problem_grid(full=True)
    space = PowerModeSpace()
    tg = G.materialize(DeviceModel(), TRAIN_WORKLOADS["mobilenet"], space)
    ig = G.materialize(DeviceModel(), INFER_WORKLOADS["mobilenet"], space,
                       P.INFER_BATCH_SIZES)

    def solve(backend):
        return G.solve_concurrent_batch(problems, tg, ig, backend=backend)

    ref, t_np = _timed(lambda: solve("numpy"))
    _, t_cold = _timed(lambda: solve("jax"))
    got, t_warm = _timed(lambda: solve("jax"))
    key = lambda s: None if s is None else (s.pm, s.bs, s.tau_tr)
    mismatch = sum(key(a) != key(b) for a, b in zip(got, ref))
    both = [(a, b) for a, b in zip(got, ref) if a is not None and b is not None]
    d_time = max((abs(a.time - b.time) for a, b in both), default=0.0)
    d_tput = max((abs(a.throughput - b.throughput) for a, b in both),
                 default=0.0)
    print(f"[b] planner: problems={len(problems)} grid={len(ig)} "
          f"solved={sum(s is not None for s in ref)} "
          f"selection_mismatches={mismatch} "
          f"max_abs_diff_time={d_time!r} max_abs_diff_tput={d_tput!r}")
    print(f"[b] planner: jax first call {t_cold:.3f} s (compile included), "
          f"warm {t_warm:.3f} s ({len(problems) / t_warm:.0f} problems/s); "
          f"numpy {t_np:.3f} s")
    if len(problems) != 41 * 16 * 10 or mismatch:
        raise AssertionError(f"{mismatch} selections differ from numpy")


def phase_fleet(K: int = FLEET_K) -> None:
    from benchmarks.bench_fleet import (JAX_TOL, LATENCY, POWER,
                                        RATE_PER_DEVICE, WINDOW_S, _adm_cfg,
                                        parity_diff)
    from repro.core import fleet as F
    from repro.core.backend import dispatch_count
    from repro.core.device_model import INFER_WORKLOADS

    rates = [RATE_PER_DEVICE * m * K for m in (0.9, 1.4, 0.7, 1.1)]
    spec = F.FleetSpec(K, seed=3, dispatch="least-backlog")

    def serve(backend, rs, fused=False):
        return F.serve_fleet(INFER_WORKLOADS["mobilenet"], POWER, LATENCY,
                             rs, spec, window_duration=WINDOW_S,
                             arrivals="poisson", seed=11, backend=backend,
                             controller=_adm_cfg("shed"), fused=fused)

    _, t_first = _timed(lambda: serve("jax", rates[:1], fused=True))
    serve("jax", rates, fused=True)        # compile every (K, T) bucket
    d0 = dispatch_count()
    wins, t_warm = _timed(lambda: serve("jax", rates, fused=True))
    per_window = (dispatch_count() - d0) / len(rates)
    ref = serve("numpy", rates)
    diff = parity_diff(wins, ref)
    served = sum(len(d.report.latencies) for w in wins for d in w.devices
                 if d.report is not None)
    print(f"[c] fleet: K={K} windows={len(rates)} "
          f"offered={sum(w.offered_requests for w in wins)} served={served} "
          f"shed={sum(w.shed_requests for w in wins)} "
          f"parity_max_abs_diff={diff!r} (tol {JAX_TOL})")
    print(f"[c] fleet: host dispatches per window {per_window}; first window "
          f"{t_first:.3f} s (compile included); warm window mean "
          f"{t_warm / len(rates):.4f} s")
    if not diff <= JAX_TOL or per_window != 1.0 or not served:
        raise AssertionError(f"parity {diff!r}, {per_window} dispatches "
                             f"per window, {served} served")


def _trainer(cfg, batch: int):
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.train_loop import Trainer
    return Trainer(cfg, batch, SEQ_LEN, AdamWConfig(warmup_steps=10))


def _train_batch(cfg) -> int:
    """The plan's training minibatch for this model (its workload profile)."""
    from repro.core.device_model import workload_from_model_config
    return workload_from_model_config(cfg, "train",
                                      tokens_per_sample=SEQ_LEN).train_bs


def phase_trainer(steps: int = 4) -> None:
    from repro.configs import get_config
    cfg = get_config(TRAIN_ARCH)
    batch = _train_batch(cfg)
    trainer = _trainer(cfg, batch)
    if not _on_tpu(trainer.params):
        raise AssertionError("trainer parameters are not on the TPU")
    rep = trainer.train(steps, log_every=0)
    print(f"[d] trainer: {cfg.name} {cfg.num_layers}L d={cfg.d_model} "
          f"params={cfg.param_count() / 1e6:.1f}M batch={batch} "
          f"seq={SEQ_LEN} steps={rep.steps}")
    print(f"[d] trainer: losses {[round(x, 4) for x in rep.losses]}; step "
          f"times {[round(t, 4) for t in rep.step_times]} s (first includes "
          f"compile)")
    _memory_line("d")
    if not all(math.isfinite(x) for x in rep.losses):
        raise AssertionError(f"non-finite loss: {rep.losses}")


def phase_runtime() -> None:
    import numpy as np
    from repro.configs import get_config
    from repro.core import problem as P
    from repro.core.device_model import DeviceModel, workload_from_model_config
    from repro.core.scheduler import Fulcrum
    from repro.runtime.interleave_runtime import (InterleaveConfig,
                                                  ManagedInterleaveRuntime)
    from repro.runtime.serving import BatchInferenceServer

    cfg_tr, cfg_in = get_config(TRAIN_ARCH), get_config(SERVE_ARCH)
    w_tr = workload_from_model_config(cfg_tr, "train",
                                      tokens_per_sample=SEQ_LEN)
    w_in = workload_from_model_config(cfg_in, "infer",
                                      tokens_per_sample=SEQ_LEN)
    power, budget, rate = PAIR_BUDGETS
    plan = Fulcrum(DeviceModel()).solve_concurrent(
        w_tr, w_in, P.ConcurrentProblem(power, budget, rate))
    if plan is None:
        raise AssertionError("no feasible plan for the pair")
    bs = plan.solution.bs
    print(f"[e] runtime: plan pm={plan.solution.pm} bs={bs} "
          f"tau_tr={plan.solution.tau_tr}; trainer depth cut: none "
          f"({cfg_tr.num_layers} layers, the published depth)")
    # a VLM request is its patch embeddings followed by SEQ_LEN text tokens
    seq_in = cfg_in.n_patches + SEQ_LEN
    server = BatchInferenceServer(cfg_in, seq_len=seq_in, bs=bs)
    trainer = _trainer(cfg_tr, _train_batch(cfg_tr))
    if not (_on_tpu(server.params) and _on_tpu(trainer.params)):
        raise AssertionError("model parameters are not on the TPU")
    runtime = ManagedInterleaveRuntime(
        trainer, server, InterleaveConfig(arrival_rate=rate, infer_bs=bs,
                                          latency_budget=budget,
                                          duration=TRACE_S))
    rep = runtime.run()
    logits = np.asarray(server.infer(), np.float32)
    lat = np.asarray(rep.latencies)
    p95 = rep.latency_quantile(0.95)
    print(f"[e] runtime: {cfg_in.name} {cfg_in.num_layers}L "
          f"d={cfg_in.d_model} seq={seq_in} served beside {cfg_tr.name} "
          f"{cfg_tr.num_layers}L d={cfg_tr.d_model} batch={trainer.batch}")
    print(f"[e] runtime: trace {TRACE_S:.0f} s uniform at {rate} req/s; "
          f"served {lat.size}/{len(runtime.trace)} requests; p95 "
          f"{p95 * 1e3:.1f} ms against a {budget * 1e3:.0f} ms budget; "
          f"training minibatches {rep.train_minibatches} "
          f"(measured step {runtime.t_tr * 1e3:.1f} ms)")
    _memory_line("e")
    want = (bs, seq_in, cfg_in.padded_vocab)
    if logits.shape != want or not np.isfinite(logits).all():
        raise AssertionError(f"server output {logits.shape} (want {want}) "
                             f"or non-finite")
    if lat.size != len(runtime.trace) or not np.isfinite(lat).all() \
            or p95 > budget or rep.train_minibatches <= 0:
        raise AssertionError("runtime missed its contract")


def main() -> int:
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"[a] device: no TPU: JAX found {device}", file=sys.stderr)
        return 2
    print(f"[a] device: {device}", flush=True)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_listener(_count_cache_hits)
    entries = len(list(Path(cache_dir).glob("*"))) \
        if Path(cache_dir).is_dir() else 0
    print(f"[a] compile cache: {cache_dir} ({entries} entries at start)")

    failed = []
    for name, phase in (("b", phase_planner), ("c", phase_fleet),
                        ("d", phase_trainer), ("e", phase_runtime)):
        try:
            _, secs = _timed(phase)
            print(f"[{name}] ok in {secs:.1f} s", flush=True)
        except Exception:
            traceback.print_exc()
            print(f"[{name}] FAILED", flush=True)
            failed.append(name)
        # free one phase's device buffers before the next allocates its own
        gc.collect()
    print(f"[a] compile cache hits: {_cache_hits}")
    if failed:
        print(f"failed phases: {','.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
