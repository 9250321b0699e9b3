"""Fleet tier (``Scenario.FLEET``): the batched K-device serving loop must be
*bitwise* identical on NumPy to K sequential single-device closed loops over
the same split traces (the PR's correctness contract), the weighted
round-robin dispatch must match its greedy definition and round-trip
provenance, the per-device perturbation draws must be collision-free at
K=512, the batched fleet solver must replay the scalar solver over scaled
grids, and priority weighting must default (None / all-equal) to the
unweighted solver bitwise."""
import dataclasses

import numpy as np
import pytest

from repro.core import fleet as F
from repro.core import grid_eval as G
from repro.core import problem as P
from repro.core import simulate as S
from repro.core.backend import jax_available
from repro.core.controller import ControllerConfig
from repro.core.device_model import (DeviceModel, INFER_WORKLOADS,
                                     TRAIN_WORKLOADS, _device_pert,
                                     fleet_device)
from repro.core.powermode import PowerModeSpace
from repro.core.scheduler import Fulcrum, Scenario

DEV = DeviceModel()
SPACE = PowerModeSpace()
W_IN = INFER_WORKLOADS["mobilenet"]


def _fused_backend(backend):
    """Skip guard for the fused-window cases: the fused program is
    jax-tier."""
    if not jax_available():
        pytest.skip("jax unavailable")
    return backend


# ---------------------------------------------------------------------------
# (a) heterogeneity: collision-free deterministic perturbations
# ---------------------------------------------------------------------------

def test_device_perturbations_collision_free_at_k512():
    # the _poisson_seed trap: arithmetic seed mixing collides distinct
    # (index, field) pairs; the delimited-string key must not. 512 devices
    # x 2 fields = 1024 draws, all distinct.
    draws = [_device_pert(0, d, f, 0.10)
             for d in range(512) for f in ("time", "power")]
    assert len(set(draws)) == len(draws)
    assert all(0.90 <= x <= 1.10 for x in draws)
    # different seeds name different fleets; same seed is reproducible
    assert _device_pert(1, 7, "time", 0.1) != _device_pert(2, 7, "time", 0.1)
    assert _device_pert(3, 7, "time", 0.1) == _device_pert(3, 7, "time", 0.1)


def test_fleet_device_scales_grid_elementwise():
    d = fleet_device(5, seed=9)
    for pm in SPACE.all_modes()[:8]:
        for bs in (1, 32):
            t0, p0 = DEV.time_power(W_IN, pm, bs)
            t1, p1 = d.time_power(W_IN, pm, bs)
            assert t1 == t0 * d.time_scale and p1 == p0 * d.power_scale


def test_fleet_spec_validation():
    with pytest.raises(ValueError):
        F.FleetSpec(0)
    with pytest.raises(ValueError):
        F.FleetSpec(4, time_spread=1.5)
    with pytest.raises(ValueError):
        F.FleetSpec(4, dispatch="round-trip")
    assert len(F.FleetSpec(4).devices()) == 4


# ---------------------------------------------------------------------------
# (b) dispatch: greedy definition, vectorized merge, provenance round-trip
# ---------------------------------------------------------------------------

def _greedy_dispatch(n, weights, counts0=None):
    counts = (np.zeros(len(weights), np.int64) if counts0 is None
              else np.asarray(counts0, np.int64).copy())
    out = np.empty(n, np.int64)
    for k in range(n):
        out[k] = int(np.argmin((counts + 1.0) / weights))
        counts[out[k]] += 1
    return out


@pytest.mark.parametrize("seed", range(6))
def test_dispatch_matches_greedy_reference(seed):
    rng = np.random.default_rng(seed)
    K = int(rng.integers(1, 12))
    n = int(rng.integers(0, 400))
    wts = rng.uniform(0.5, 2.0, K)
    c0 = rng.integers(0, 30, K) if rng.random() < 0.5 else None
    got = F.dispatch_arrivals(np.zeros(n), wts, c0)
    assert np.array_equal(got, _greedy_dispatch(n, wts, c0))


def test_dispatch_proportional_to_capacity():
    wts = np.array([1.0, 1.0, 2.0])        # device 2 is twice as fast
    sid = F.dispatch_arrivals(np.zeros(400), wts)
    counts = np.bincount(sid, minlength=3)
    assert counts[2] == 200 and counts[0] == counts[1] == 100


def test_dispatch_provenance_round_trips():
    agg = S.ArrivalTrace.poisson(80.0, 5.0, seed=3)
    wts = np.array([1.0, 1.3, 0.8, 1.1])
    sid = F.dispatch_arrivals(agg.times, wts)
    merged, per_dev = F.split_window(agg, sid, 4)
    assert merged.n_streams == 4 and len(merged) == len(agg)
    # split(K) recovers exactly the per-device arrival times, in order
    re_split = merged.split(4)
    for tr, tr2, d in zip(per_dev, re_split, range(4)):
        assert np.array_equal(tr.times, agg.times[sid == d])
        assert np.array_equal(tr.times, tr2.times)
        assert tr.duration == agg.duration


# ---------------------------------------------------------------------------
# (c) the batched fleet solver == per-device scalar solves over scaled grids
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_fleet_solver_matches_scalar_over_scaled_grids(backend):
    if backend == "jax" and not jax_available():
        pytest.skip("jax unavailable")
    rng = np.random.default_rng(11)
    grid = G.materialize(DEV, W_IN, SPACE, P.INFER_BATCH_SIZES)
    base = grid.to_dict()
    n = 40
    ts = rng.uniform(0.9, 1.1, n)
    ps = rng.uniform(0.95, 1.05, n)
    probs = [P.InferProblem(float(rng.uniform(10, 55)),
                            float(rng.uniform(0.05, 1.5)),
                            float(rng.uniform(5, 150))) for _ in range(n)]
    his = np.array([p.arrival_rate * float(rng.uniform(1.0, 1.6))
                    for p in probs])
    got = G.solve_infer_fleet_batch(probs, his, grid, ts, ps,
                                    backend=backend)
    for k, (pr, sol) in enumerate(zip(probs, got)):
        obs = {key: (t * ts[k], p * ps[k]) for key, (t, p) in base.items()}
        ref = P.solve_infer_interval(pr, float(his[k]), obs)
        assert (sol is None) == (ref is None)
        if ref is not None:
            assert (sol.pm, sol.bs) == (ref.pm, ref.bs)
            if backend == "numpy":
                assert sol.time == ref.time and sol.power == ref.power
            else:
                np.testing.assert_allclose([sol.time, sol.power],
                                           [ref.time, ref.power],
                                           atol=1e-8, rtol=1e-9)


def test_fleet_solver_validates_alignment():
    grid = G.materialize(DEV, W_IN, SPACE, P.INFER_BATCH_SIZES)
    probs = [P.InferProblem(30.0, 0.5, 50.0)] * 2
    with pytest.raises(ValueError):
        G.solve_infer_fleet_batch(probs, [60.0], grid, [1.0, 1.0],
                                  [1.0, 1.0])


# ---------------------------------------------------------------------------
# (d) THE contract: batched fleet == K sequential single-device loops
# ---------------------------------------------------------------------------

def _assert_fleet_equal(a, b, exact=True):
    assert len(a) == len(b)
    for wa, wb in zip(a, b):
        assert np.array_equal(wa.dispatch_counts, wb.dispatch_counts)
        assert wa.offered_requests == wb.offered_requests
        assert np.array_equal(wa.trace.stream_ids, wb.trace.stream_ids)
        if exact:
            assert wa.goodput == wb.goodput
        for da, db in zip(wa.devices, wb.devices):
            assert (da.solution is None) == (db.solution is None)
            assert da.carried_requests == db.carried_requests
            assert da.replanned == db.replanned
            assert da.offered_requests == db.offered_requests
            if exact:
                assert da.rate == db.rate
                assert da.estimated_rate == db.estimated_rate
                assert da.goodput == db.goodput
            if da.solution is None:
                continue
            assert (da.solution.pm, da.solution.bs) \
                == (db.solution.pm, db.solution.bs)
            if exact:
                assert da.solution == db.solution
                assert da.report.latencies.tolist() \
                    == db.report.latencies.tolist()
                assert da.report.power == db.report.power
                assert da.report.attributed_power \
                    == db.report.attributed_power
                assert da.report.queue_state.pending.tolist() \
                    == db.report.queue_state.pending.tolist()
                assert da.report.queue_state.clock \
                    == db.report.queue_state.clock
            else:
                np.testing.assert_allclose(da.report.latencies,
                                           db.report.latencies,
                                           atol=1e-8, rtol=1e-9)


@pytest.mark.parametrize("seed", range(4))
def test_batched_fleet_bitwise_equals_sequential_numpy(seed):
    rng = np.random.default_rng(seed)
    spec = F.FleetSpec(int(rng.integers(2, 9)), seed=seed,
                       dispatch=("capacity", "least-backlog")[seed % 2])
    cfg = ControllerConfig(rate_estimator="ewma",
                           feedback=bool(seed % 2),
                           carry_backlog=True,
                           mode_switch_s=0.25 * (seed % 2),
                           burst_quantile=0.9 if seed == 1 else 0.0)
    rates = [float(r) for r in rng.uniform(20.0, 500.0, 4)]
    kw = dict(window_duration=3.0, arrivals="poisson", seed=seed + 100,
              backend="numpy", controller=cfg)
    a = F.serve_fleet(W_IN, 30.0, 0.2, rates, spec, **kw)
    b = F.serve_fleet_sequential(W_IN, 30.0, 0.2, rates, spec, **kw)
    _assert_fleet_equal(a, b, exact=True)


def test_batched_fleet_with_idle_devices_matches_sequential():
    # aggregate rate so low a window dispatches nothing to some devices —
    # idle lanes must still observe (rate estimate decays) and report
    # goodput 1.0 on zero offered
    spec = F.FleetSpec(8, seed=1)
    cfg = ControllerConfig(rate_estimator="ewma", carry_backlog=True)
    kw = dict(window_duration=2.0, arrivals="poisson", seed=5,
              backend="numpy", controller=cfg)
    a = F.serve_fleet(W_IN, 30.0, 0.2, [2.0, 1.0], spec, **kw)
    b = F.serve_fleet_sequential(W_IN, 30.0, 0.2, [2.0, 1.0], spec, **kw)
    _assert_fleet_equal(a, b, exact=True)
    idle = [d for d, c in enumerate(a[0].dispatch_counts) if c == 0]
    assert idle                              # the setup really idles devices
    for d in idle:
        assert a[0].devices[d].goodput == 1.0
        assert a[0].devices[d].offered_requests == 0


def test_batched_fleet_jax_matches_numpy_within_tolerance():
    if not jax_available():
        pytest.skip("jax unavailable")
    spec = F.FleetSpec(4, seed=2)
    cfg = ControllerConfig(rate_estimator="ewma", carry_backlog=True)
    kw = dict(window_duration=3.0, arrivals="poisson", seed=7,
              controller=cfg)
    a = F.serve_fleet(W_IN, 30.0, 0.2, [200.0, 400.0, 80.0], spec,
                      backend="jax", **kw)
    b = F.serve_fleet(W_IN, 30.0, 0.2, [200.0, 400.0, 80.0], spec,
                      backend="numpy", **kw)
    _assert_fleet_equal(a, b, exact=False)


def test_fleet_rejects_single_device_refinements():
    # admission is fleet-batched since the global-admission PR; the one
    # remaining single-device refinement is mid-window re-entry
    with pytest.raises(ValueError, match="split_backlog"):
        F.serve_fleet(W_IN, 30.0, 0.2, [50.0], F.FleetSpec(2),
                      controller=ControllerConfig(split_backlog=1))
    out = F.serve_fleet(W_IN, 30.0, 0.2, [50.0], F.FleetSpec(2),
                        controller=ControllerConfig(admission="shed"),
                        backend="numpy")
    assert len(out) == 1


def test_scenario_fleet_and_scheduler_facade():
    assert Scenario.FLEET.canonical is Scenario.INFER
    ful = Fulcrum(DEV, SPACE)
    out = ful.serve_fleet(W_IN, 30.0, 0.2, [100.0, 150.0], 4,
                          window_duration=2.0, backend="numpy")
    assert len(out) == 2 and len(out[0].devices) == 4
    assert out[0].attributed_power > 0.0
    # an int fleet arg names the default-spec fleet of that size
    spec = F.FleetSpec(4)
    ref = F.serve_fleet(W_IN, 30.0, 0.2, [100.0, 150.0], spec,
                        window_duration=2.0, backend="numpy",
                        space=SPACE)
    _assert_fleet_equal(out, ref, exact=True)


# ---------------------------------------------------------------------------
# (e) satellites: power attribution and priority-weighted objectives
# ---------------------------------------------------------------------------

def test_single_stream_attribution_equals_power():
    rep = S.simulate(DEV, None, W_IN, SPACE.maxn(), 16,
                     S.ArrivalTrace.uniform(50.0, 5.0))
    assert rep.attributed_power == rep.power   # sole busy share takes all
    idle = S.simulate(DEV, None, W_IN, SPACE.maxn(), 16,
                      S.ArrivalTrace.uniform(0.0, 5.0))
    assert idle.attributed_power == 0.0        # nothing ran, nothing billed


def test_multi_tenant_attribution_sums_to_device_power():
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    ws = [INFER_WORKLOADS["mobilenet"], INFER_WORKLOADS["resnet50"]]
    traces = [S.ArrivalTrace.uniform(40.0, 10.0),
              S.ArrivalTrace.uniform(15.0, 10.0)]
    rep = S.simulate_multi_tenant(DEV, w_tr, ws, SPACE.maxn(), [16, 4],
                                  traces)
    shares = [s.attributed_power for s in rep.streams] \
        + [rep.train_attributed_power]
    assert all(s >= 0.0 for s in shares)
    assert np.isclose(sum(shares), rep.power)
    # time-weighted: the busier stream is billed more per unit time served
    assert rep.streams[0].attributed_power > 0.0


def test_priorities_none_and_uniform_are_bitwise_default():
    rng = np.random.default_rng(4)
    sub = SPACE.all_modes()[::12]
    w_tr = TRAIN_WORKLOADS["resnet18"]
    tobs = {pm: DEV.time_power(w_tr, pm) for pm in sub}
    iobs = {(pm, bs): DEV.time_power(W_IN, pm, bs)
            for pm in sub for bs in P.INFER_BATCH_SIZES}
    for _ in range(25):
        streams = tuple(
            P.StreamSpec(float(rng.uniform(5, 60)),
                         float(rng.uniform(0.1, 1.0)), W_IN)
            for _ in range(2))
        prob = P.MultiTenantProblem(float(rng.uniform(15, 55)), streams,
                                    train=w_tr)
        ref = P.solve_multi_tenant(prob, tobs, [iobs, iobs])
        for pri in ((1.0, 1.0), (7.0, 7.0)):
            got = P.solve_multi_tenant(
                dataclasses.replace(prob, priorities=pri),
                tobs, [iobs, iobs])
            assert (ref is None) == (got is None)
            if ref is not None:
                assert ref.pm == got.pm and ref.bss == got.bss
                assert ref.times == got.times    # bitwise
                assert ref.power == got.power


def test_priorities_skew_the_latency_objective():
    # two identical streams; the solver breaks ties on the worst *weighted*
    # latency, so any skew must weakly improve the favored stream's latency
    sub = SPACE.all_modes()[::6]
    iobs = {(pm, bs): DEV.time_power(W_IN, pm, bs)
            for pm in sub for bs in P.INFER_BATCH_SIZES}
    streams = tuple(P.StreamSpec(40.0, 1.0, W_IN) for _ in range(2))
    base = P.MultiTenantProblem(40.0, streams, train=False)
    ref = P.solve_multi_tenant(base, None, [iobs, iobs])
    skew = P.solve_multi_tenant(
        dataclasses.replace(base, priorities=(100.0, 1.0)),
        None, [iobs, iobs])
    assert ref is not None and skew is not None
    assert skew.times[0] <= ref.times[0] + 1e-12
    # weights normalize to priority/max; validation rejects bad shapes
    assert base.priority_weights() is None
    w = dataclasses.replace(base, priorities=(2.0, 1.0)).priority_weights()
    assert w == (1.0, 0.5)
    with pytest.raises(ValueError):
        P.MultiTenantProblem(40.0, streams, train=False,
                             priorities=(1.0,))
    with pytest.raises(ValueError):
        P.MultiTenantProblem(40.0, streams, train=False,
                             priorities=(1.0, -2.0))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_priority_batch_solver_matches_scalar(backend):
    if backend == "jax" and not jax_available():
        pytest.skip("jax unavailable")
    sub = SPACE.all_modes()[::8]
    iobs = {(pm, bs): DEV.time_power(W_IN, pm, bs)
            for pm in sub for bs in P.INFER_BATCH_SIZES}
    ig = G.ObservationGrid.from_infer_dict(iobs)
    streams = tuple(P.StreamSpec(30.0, 0.6, W_IN) for _ in range(2))
    # the batch solver requires uniform priorities per batch: one batch
    # call per priority vector, each checked against the scalar solver
    for pri in (None, (1.0, 1.0), (10.0, 1.0), (1.0, 10.0)):
        probs = [P.MultiTenantProblem(float(pb), streams, train=False,
                                      priorities=pri)
                 for pb in (20.0, 35.0, 55.0)]
        got = G.solve_multi_tenant_batch(probs, None, [ig, ig],
                                         backend=backend)
        for pr, sol in zip(probs, got):
            ref = P.solve_multi_tenant(pr, None, [iobs, iobs])
            assert (sol is None) == (ref is None)
            if ref is not None:
                assert sol.pm == ref.pm and sol.bss == ref.bss
                if backend == "numpy":
                    assert sol.times == ref.times and sol.power == ref.power


# ---------------------------------------------------------------------------
# (f) the fused window: solve + admit + simulate as ONE launch per window
# ---------------------------------------------------------------------------

_LIGHT = [60.0, 110.0, 25.0, 80.0]
# congested: an EWMA estimate lags each jump, so plans under-provision and
# admission rejects, and the window after the 2400 req/s burst leaves
# devices with no feasible plan (unserved)
_HEAVY = [300.0, 2400.0, 500.0, 3000.0]
_EWMA = dict(rate_estimator="ewma")
# the fleet-k512-shed cell's controller (chipbench/traffic/fleet-30rps-shed)
_CELL = dict(rate_estimator="ewma", rate_margin=1.5, feedback=True,
             carry_backlog=True, mode_switch_s=0.25, burst_quantile=0.95,
             admission="shed", tighten=0.5, relax=0.5, target_violation=0.0,
             tail_quantile=0.95, min_budget_scale=0.2)


def _shed(fus, _rung4):
    return any(w.shed_requests for w in fus)


def _redeferred(fus, _rung4):
    # an unserved device drew re-offers (dispatch counts include them)
    return any(d.solution is None and n > d.offered_requests
               for w in fus for d, n in zip(w.devices, w.dispatch_counts))


_FUSED_MATRIX = {
    # name -> (FleetSpec kwargs, ControllerConfig kwargs, serve kwargs,
    # what the run must show to have taken its branch, or None): every
    # fleet feature the fused program claims to cover, and each branch of
    # the shared prepare / settle stages, including combinations
    "heterogeneous": (dict(time_spread=0.25, power_spread=0.15), dict(),
                      dict(rates=[2.0, 0.0, 1.0]), None),   # idle devices
    "carried-backlog": (dict(), dict(rate_estimator="ewma",
                                     carry_backlog=True,
                                     mode_switch_s=0.25), {}, None),
    "shed": (dict(), dict(admission="shed", carry_backlog=True,
                          mode_switch_s=0.25), {}, None),
    "defer": (dict(dispatch="least-backlog"),
              dict(admission="defer", defer_cap=25, carry_backlog=True,
                   rate_estimator="ewma", rate_margin=1.5, feedback=True,
                   mode_switch_s=0.25), {}, None),
    "water-filled": (dict(migrate_backlog=True, fleet_power_budget=80.0),
                     dict(carry_backlog=True, feedback=True), {}, None),
    "fleet-cell": (
        dict(time_spread=0.1, power_spread=0.05, dispatch="least-backlog"),
        _CELL, dict(rates=_HEAVY),
        lambda fus, _: _shed(fus, _) and any(
            d.mode_switch_s and d.carried_requests
            for w in fus for d in w.devices)),
    "defer-idle": (dict(), dict(admission="defer", carry_backlog=True,
                                **_EWMA),
                   dict(rates=[300.0, 3000.0, 0.0, 300.0]), _redeferred),
    "defer-cap-drops": (dict(), dict(admission="defer", defer_cap=2,
                                     carry_backlog=True, **_EWMA),
                        dict(rates=_HEAVY), _shed),
    "least-backlog-open": (
        dict(dispatch="least-backlog"), dict(carry_backlog=True, **_EWMA),
        dict(rates=_HEAVY),
        lambda fus, _: any(d.carried_requests
                           for w in fus for d in w.devices)),
    "migrate-shed": (
        dict(migrate_backlog=True, time_spread=0.25),
        dict(admission="shed", carry_backlog=True, **_EWMA),
        dict(rates=_HEAVY),
        lambda fus, _: _shed(fus, _) and any(w.migrated_requests
                                             for w in fus)),
    "power-cap-defer": (
        dict(fleet_power_budget=150.0),
        dict(admission="defer", carry_backlog=True, **_EWMA),
        dict(rates=_HEAVY),
        lambda fus, _: any(w.deferred_requests for w in fus)
        and all(w.power_budgets is not None for w in fus)),
    "burst-no-margin": (dict(), dict(burst_quantile=0.95, **_EWMA),
                        dict(rates=_HEAVY), None),
    "feedback-rung4": (dict(), dict(feedback=True, tighten=1.0,
                                    min_budget_scale=0.05, **_EWMA),
                       dict(rates=[300.0, 3000.0, 2400.0, 3000.0]),
                       lambda _, rung4: rung4),
    "uniform-arrivals": (dict(), dict(admission="shed", carry_backlog=True,
                                      **_EWMA),
                         dict(rates=_HEAVY, arrivals="uniform"), _shed),
    "shed-headroom": (dict(), dict(admission="shed", admission_headroom=0.7,
                                   **_EWMA),
                      dict(rates=_HEAVY), _shed),
    "defer-no-carry": (dict(), dict(admission="defer", **_EWMA),
                       dict(rates=_HEAVY),
                       lambda fus, _: any(w.deferred_requests for w in fus)),
    "switch-no-carry": (dict(), dict(mode_switch_s=0.25, **_EWMA),
                        dict(rates=_HEAVY),
                        lambda fus, _: any(d.mode_switch_s for w in fus
                                           for d in w.devices)),
}


@pytest.mark.parametrize("case", sorted(_FUSED_MATRIX))
@pytest.mark.parametrize("backend", ["jax"])
def test_fused_fleet_matches_unfused(case, backend, monkeypatch):
    _fused_backend(backend)
    spec_kw, cfg_kw, serve_kw, takes = _FUSED_MATRIX[case]
    spec = F.FleetSpec(6, seed=3, **spec_kw)
    cfg = ControllerConfig(**cfg_kw)
    rates = serve_kw.get("rates", _LIGHT)
    kw = dict(window_duration=3.0, arrivals=serve_kw.get("arrivals",
                                                         "poisson"),
              seed=17, controller=cfg)
    # rung 4 fired for a device iff its plan misses the tightened budget:
    # rungs 1-3 only accept plans within it
    rung4 = []
    ladder = F._solve_ladder

    def spy(grid, ts, ps, pbud, bud, *rest):
        sols = ladder(grid, ts, ps, pbud, bud, *rest)
        rung4.extend(s is not None and s.time > b for s, b in zip(sols, bud))
        return sols

    monkeypatch.setattr(F, "_solve_ladder", spy)
    fus = F.serve_fleet(W_IN, 30.0, 0.15, rates, spec, backend=backend,
                        fused=True, **kw)
    unf = F.serve_fleet(W_IN, 30.0, 0.15, rates, spec, backend=backend,
                        **kw)
    seq = F.serve_fleet_sequential(W_IN, 30.0, 0.15, rates, spec,
                                   backend="numpy", **kw)
    if takes is not None:
        assert takes(fus, any(rung4))
    # same jax tier: only the associative scan's padded tree shape differs
    _assert_fleet_equal(fus, unf, exact=False)
    # and the exactness ladder back to the bitwise NumPy reference
    _assert_fleet_equal(fus, seq, exact=False)
    for wf, wu in zip(fus, unf):
        assert wf.shed_requests == wu.shed_requests
        assert wf.deferred_requests == wu.deferred_requests
        assert wf.migrated_requests == wu.migrated_requests
        for df, du in zip(wf.devices, wu.devices):
            assert df.shed_requests == du.shed_requests
            assert df.deferred_requests == du.deferred_requests
            assert df.mode_switch_s == du.mode_switch_s
            if df.report is not None:
                np.testing.assert_allclose(
                    df.report.queue_state.pending,
                    du.report.queue_state.pending, atol=1e-8, rtol=1e-9)
                np.testing.assert_allclose(
                    df.report.queue_state.clock,
                    du.report.queue_state.clock, atol=1e-8, rtol=1e-9)
                np.testing.assert_allclose(
                    df.report.attributed_power,
                    du.report.attributed_power, atol=1e-8, rtol=1e-9)


def test_fused_fleet_no_retrace_across_windows():
    _fused_backend("jax")
    from repro.core.fused_window import fleet_trace_count
    spec = F.FleetSpec(6, seed=3)
    cfg = ControllerConfig(rate_estimator="ewma", carry_backlog=True)
    kw = dict(window_duration=3.0, arrivals="poisson", seed=17,
              backend="jax", controller=cfg, fused=True)
    rates = [80.0] * 3
    F.serve_fleet(W_IN, 30.0, 0.15, rates, spec, **kw)   # warm the buckets
    before = fleet_trace_count()
    F.serve_fleet(W_IN, 30.0, 0.15, rates + [75.0, 85.0], spec, **kw)
    # steady state: same pow2 (K, event) buckets -> zero new compilations
    assert fleet_trace_count() == before


def test_fused_fleet_one_dispatch_per_window():
    _fused_backend("jax")
    from repro.core.backend import dispatch_count
    spec = F.FleetSpec(4, seed=3)
    kw = dict(window_duration=3.0, arrivals="poisson", seed=17,
              backend="jax", fused=True,
              controller=ControllerConfig(admission="shed"))
    rates = [60.0, 90.0, 40.0]
    F.serve_fleet(W_IN, 30.0, 0.15, rates, spec, **kw)   # warm compile
    before = dispatch_count()
    F.serve_fleet(W_IN, 30.0, 0.15, rates, spec, **kw)
    assert dispatch_count() - before == len(rates)       # ONE launch each


def test_fused_fleet_rejects_unfusable_configs():
    # the fused window is a jax program; the NumPy tier has no fused form
    with pytest.raises(ValueError, match="jax"):
        F.serve_fleet(W_IN, 30.0, 0.15, [50.0], F.FleetSpec(2),
                      backend="numpy", fused=True)
    # degrade-bs re-plans on the host mid-window: unfusable by design
    if jax_available():
        with pytest.raises(ValueError, match="degrade-bs"):
            F.serve_fleet(W_IN, 30.0, 0.15, [50.0], F.FleetSpec(2),
                          backend="jax", fused=True,
                          controller=ControllerConfig(
                              admission="degrade-bs", carry_backlog=True))


def test_grid_mode_ids_injective_and_memoized():
    from repro.core.fused_window import grid_mode_ids
    grid = G.materialize(DEV, W_IN, SPACE, P.INFER_BATCH_SIZES)
    ids = grid_mode_ids(grid)
    assert ids.shape == (len(grid),)
    # id equality must be PowerMode equality — the in-program mode-switch
    # charge depends on it
    by_id: dict = {}
    for pm, i in zip(grid.modes, ids):
        assert by_id.setdefault(int(i), pm) == pm
    n_modes = len({pm for pm in grid.modes})
    assert len(by_id) == n_modes
    assert grid_mode_ids(grid) is ids            # memoized on the grid
