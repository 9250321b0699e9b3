"""Burst survival: SLO-aware admission control, deferral, and mid-window
re-planning (paper §5.4 under overload).

The load-bearing property is the admission gate's exactness: the deadline-
drop mask runs the managed engine's own batching recurrence (identical
float64 ops) over the admitted subsequence, so whatever it admits replays
through the engine with *zero* nominal-budget violations by construction —
no tolerance, no predictor slack. The second contract is PR-5's carryover
exactness extended to splitting: clipping a window at an arrival timestamp
and chaining ``QueueState`` reproduces the unsplit run bitwise on NumPy,
which is what makes mid-window re-planning a pure control decision.
``AdmissionPolicy("none")`` must leave the closed loop byte-identical to
the PR-5 controller (fingerprint regression below).

Bitwise assertions pin ``backend="numpy"`` so they still check the
reference contract when ``FULCRUM_ENGINE_BACKEND=jax`` (CI does).
"""
import math

import numpy as np
import pytest

from repro.core import problem as P
from repro.core import simulate as S
from repro.core.controller import (AdmissionPolicy, ControllerConfig,
                                   ControllerState, _admit_mask_multi)
from repro.core.device_model import DeviceModel, INFER_WORKLOADS
from repro.core.powermode import PowerModeSpace
from repro.core.scheduler import Fulcrum
from repro.runtime.clock import FakeClock
from repro.runtime.interleave_runtime import (InterleaveConfig,
                                              ManagedInterleaveRuntime)

DEV = DeviceModel()
SPACE = PowerModeSpace()
MODES = SPACE.all_modes()


# ---------------------------------------------------------------------------
# burst quantiles (problem.poisson_quantile / burst_rate)
# ---------------------------------------------------------------------------

def _brute_poisson_quantile(mean, q):
    p = math.exp(-mean)
    cdf, k = p, 0
    while cdf < q:
        k += 1
        p *= mean / k
        cdf += p
    return k


@pytest.mark.parametrize("mean", [0.5, 3.0, 20.0, 200.0])
@pytest.mark.parametrize("q", [0.0, 0.5, 0.9, 0.95, 0.999])
def test_poisson_quantile_matches_brute_cdf(mean, q):
    assert P.poisson_quantile(mean, q) == _brute_poisson_quantile(mean, q)


def test_poisson_quantile_tail_regime_sane():
    """Above the exact-pmf regime (mean > 700) the Cornish-Fisher branch
    must stay monotone in q and bracket the mean."""
    qs = [0.5, 0.9, 0.95, 0.99, 0.999]
    ks = [P.poisson_quantile(2000.0, q) for q in qs]
    assert ks == sorted(ks)
    assert ks[0] >= 1990 and ks[-1] <= 2200   # ~mean + 4.4*sqrt(mean)


def test_poisson_quantile_validation_and_edges():
    with pytest.raises(ValueError, match="quantile"):
        P.poisson_quantile(10.0, 1.0)
    with pytest.raises(ValueError, match="quantile"):
        P.poisson_quantile(10.0, -0.1)
    assert P.poisson_quantile(0.0, 0.95) == 0
    assert P.poisson_quantile(5.0, 0.0) == 0


def test_burst_rate_never_below_mean_and_off_switch():
    assert P.burst_rate(40.0, 10.0, 0.95) >= 40.0
    assert P.burst_rate(40.0, 10.0, 0.0) == 40.0      # quantile planning off
    assert P.burst_rate(0.0, 10.0, 0.95) == 0.0
    # longer windows concentrate: the quantile rate approaches the mean
    assert P.burst_rate(40.0, 300.0, 0.95) < P.burst_rate(40.0, 5.0, 0.95)


# ---------------------------------------------------------------------------
# drainability / minimal shed set
# ---------------------------------------------------------------------------

def test_drain_capacity_full_minibatches_only():
    # 30 s / 0.05 s = 600 batches of 4 — a trailing partial batch never runs
    assert P.drain_capacity(4, 0.05, 30.0) == 2400
    assert P.drain_capacity(4, 0.05, 0.0) == 0
    assert P.drain_capacity(4, 0.0, 30.0) >= int(1e18)


def test_min_shed_and_drainable():
    assert P.min_shed(2400, 4, 0.05, 30.0) == 0
    assert P.min_shed(2500, 4, 0.05, 30.0) == 100
    assert P.drainable(0, 80.0, 4, 0.05, 30.0)         # 2400 demand, exact
    assert not P.drainable(1, 80.0, 4, 0.05, 30.0)     # one carried too many
    assert not P.drainable(0, 81.0, 4, 0.05, 30.0)


# ---------------------------------------------------------------------------
# interval solve, N-stream path (satellite: solve_multi_tenant_interval)
# ---------------------------------------------------------------------------

OBS = {("pmA", 1): (0.010, 20.0), ("pmA", 4): (0.030, 22.0),
       ("pmB", 4): (0.020, 30.0), ("pmB", 8): (0.036, 33.0)}


def test_interval_solve_n1_replays_single_stream_bitwise():
    """With one stream, solve_multi_tenant_interval must replay
    solve_infer_interval op-for-op: same plan, bitwise-equal latency."""
    for rate, hi, bud in [(30.0, 90.0, 0.1), (100.0, 180.0, 0.08),
                          (50.0, 50.0, 0.2), (200.0, 400.0, 0.5)]:
        single = P.solve_infer_interval(P.InferProblem(32.0, bud, rate),
                                        hi, OBS)
        multi = P.solve_multi_tenant_interval(
            P.MultiTenantProblem(32.0, (P.StreamSpec(rate, bud),),
                                 train=False), [hi], None, [OBS])
        if single is None:
            assert multi is None
            continue
        assert (multi.pm, multi.bss[0]) == (single.pm, single.bs)
        assert multi.times[0] == single.time          # bitwise
        assert multi.power == single.power


def test_interval_solve_sustains_high_rate_judges_latency_low():
    # at hi=210 only pmB/bs=8 sustains (8/0.036 = 222 rps; pmB/4 = 200);
    # latency is judged at the low rate: (8-1)/30 + 0.036 = 0.269
    s = P.solve_infer_interval(P.InferProblem(40.0, 0.5, 30.0), 210.0, OBS)
    assert (s.pm, s.bs) == ("pmB", 8)
    assert s.time == pytest.approx(7 / 30.0 + 0.036)
    # same interval, tight budget: the fill wait at the low rate kills it
    assert P.solve_infer_interval(P.InferProblem(40.0, 0.1, 30.0),
                                  210.0, OBS) is None
    # at hi=180 pmB/4 still sustains and wins on low-rate latency
    s4 = P.solve_infer_interval(P.InferProblem(40.0, 0.5, 30.0), 180.0, OBS)
    assert (s4.pm, s4.bs) == ("pmB", 4)


def test_interval_solve_rejects_rate_his_length_mismatch():
    prob = P.MultiTenantProblem(40.0, (P.StreamSpec(30.0, 0.2),
                                       P.StreamSpec(40.0, 0.2)), train=False)
    with pytest.raises(ValueError, match="high rates"):
        P.solve_multi_tenant_interval(prob, [90.0], None, [OBS, OBS])


def test_solve_infer_capacity_max_service_rate():
    # max bs/t under power alone: pmA/1=100, pmA/4=133, pmB/4=200, pmB/8=222
    assert (P.solve_infer_capacity(40.0, OBS).pm,
            P.solve_infer_capacity(40.0, OBS).bs) == ("pmB", 8)
    # power 25 leaves only pmA candidates
    assert (P.solve_infer_capacity(25.0, OBS).pm,
            P.solve_infer_capacity(25.0, OBS).bs) == ("pmA", 4)
    assert P.solve_infer_capacity(5.0, OBS) is None


# ---------------------------------------------------------------------------
# the admission mask: exactness is the whole point
# ---------------------------------------------------------------------------

def test_admit_mask_uncongested_admits_everything():
    trace = S.ArrivalTrace.uniform(20.0, 10.0)
    pol = AdmissionPolicy("shed")
    mask = pol.admit(trace.times, 0.5, 4, 0.01, 0.0)
    assert mask.all()


@pytest.mark.parametrize("seed", range(4))
def test_admitted_subsequence_replays_with_zero_violations(seed):
    """The key property: the gate runs the engine's own recurrence, so the
    admitted requests — simulated for real under the same plan — meet the
    nominal budget exactly, while the flood guarantees sheds happened."""
    rng = np.random.default_rng(seed)
    w = list(INFER_WORKLOADS.values())[rng.integers(5)]
    pm = MODES[rng.integers(len(MODES))]
    bs = [2, 4, 8][rng.integers(3)]
    t_in = DEV.time_power(w, pm, bs)[0]
    budget = float(rng.uniform(2.5, 6.0)) * t_in
    rate = 3.0 * bs / t_in                        # 3x sustainable: a flood
    trace = S.ArrivalTrace.poisson(rate, 5.0, seed=seed)
    pol = AdmissionPolicy("shed")
    mask = pol.admit(trace.times, budget, bs, t_in, 0.0)
    assert not mask.all() and mask.any()
    admitted = S.ArrivalTrace(trace.times[mask], trace.duration, trace.kind)
    rep = S.simulate(DEV, None, w, pm, bs, admitted, "managed",
                     backend="numpy")
    assert rep.violation_rate(budget) == 0.0
    # and dropping the gate would have violated: the flood is real
    raw = S.simulate(DEV, None, w, pm, bs, trace, "managed",
                     backend="numpy")
    assert raw.violation_rate(budget) > 0.0


def test_admit_mask_sheds_stale_carry_first():
    """Carried backlog already past its deadline (device clock far ahead)
    is shed; fresh arrivals still admit."""
    times = np.concatenate([np.zeros(4),                 # stale carry
                            5.0 + np.arange(8) * 0.01])  # fresh, fast
    budgets = np.full(times.size, 0.2)
    mask = _admit_mask_multi(times, np.zeros(times.size, np.int64), [4],
                             [0.01], budgets, clock=5.0)
    assert not mask[:4].any()
    assert mask[4:].all()


def test_admit_mask_trailing_partial_batch_admitted():
    # 3 requests, bs=4: the batch never fills, nothing can be judged — the
    # engine carries it to the next window where admission re-judges it
    mask = _admit_mask_multi(np.array([0.0, 0.1, 0.2]), np.zeros(3, np.int64),
                             [4], [10.0], np.full(3, 1e-6), 0.0)
    assert mask.all()


def test_admit_mask_empty():
    assert _admit_mask_multi(np.empty(0), np.empty(0, np.int64), [4], [0.01],
                             np.empty(0), 0.0).size == 0
    assert AdmissionPolicy("shed").admit(np.empty(0), 0.1, 4, 0.01,
                                         0.0).size == 0


def test_admit_multi_priorities_shed_low_priority_first():
    """Two identical flood streams sharing the device: the low-priority
    stream's scaled budget makes it shed strictly more."""
    n = 400
    t = np.repeat(np.arange(n) * 0.004, 2)        # 500 rps merged, paired
    sids = np.tile([0, 1], n)
    pol = AdmissionPolicy("shed", priorities=(1.0, 0.25))
    mask = pol.admit_multi(t, sids, [4, 4], [0.02, 0.02], [0.15, 0.15], 0.0)
    shed0 = int(np.count_nonzero(~mask[sids == 0]))
    shed1 = int(np.count_nonzero(~mask[sids == 1]))
    assert shed1 > shed0
    # equal priorities restore symmetry of budgets (not necessarily of
    # sheds — device-order ties break by arrival order)
    even = AdmissionPolicy("shed").stream_budget_scales(2)
    assert np.array_equal(even, np.ones(2))


def test_admit_multi_matches_single_stream_degenerate():
    trace = S.ArrivalTrace.poisson(300.0, 3.0, seed=7)
    pol = AdmissionPolicy("shed")
    single = pol.admit(trace.times, 0.12, 4, 0.02, 0.0)
    merged = pol.admit_multi(trace.times, np.zeros(len(trace), np.int64),
                             [4], [0.02], [0.12], 0.0)
    assert np.array_equal(single, merged)


def test_admission_policy_validation():
    with pytest.raises(ValueError, match="admission mode"):
        AdmissionPolicy("drop-tail")
    with pytest.raises(ValueError, match="headroom"):
        AdmissionPolicy("shed", headroom=0.0)
    with pytest.raises(ValueError, match="priorities"):
        AdmissionPolicy("shed", priorities=(1.0,)).stream_budget_scales(2)
    assert not AdmissionPolicy("none").active
    assert AdmissionPolicy("defer").trims
    assert not AdmissionPolicy("degrade-bs").trims


def test_controller_config_admission_validation():
    with pytest.raises(ValueError, match="admission"):
        ControllerConfig(admission="magic")
    with pytest.raises(ValueError, match="burst_quantile"):
        ControllerConfig(burst_quantile=1.0)
    with pytest.raises(ValueError, match="split_backlog"):
        ControllerConfig(split_backlog=0)
    # admission alone flips the loop closed
    assert ControllerConfig(admission="shed").closed_loop
    assert ControllerConfig(split_backlog=64).closed_loop
    assert ControllerConfig(burst_quantile=0.95).closed_loop
    assert not ControllerConfig(admission="none").closed_loop


# ---------------------------------------------------------------------------
# deferral state
# ---------------------------------------------------------------------------

def test_push_pop_deferred_retimestamps_at_window_start():
    state = ControllerState(ControllerConfig(admission="defer"), n_streams=2)
    assert state.push_deferred([3, 5]) == 0
    arrs = state.pop_deferred(12.5)
    assert [a.size for a in arrs] == [3, 5]
    assert all((a == 12.5).all() for a in arrs)        # clock restarts
    assert [a.size for a in state.pop_deferred(0.0)] == [0, 0]  # drained


def test_defer_cap_overflow_is_shed_largest_first():
    cfg = ControllerConfig(admission="defer", defer_cap=6)
    state = ControllerState(cfg, n_streams=2)
    dropped = state.push_deferred([5, 4])              # 9 > cap=6
    assert dropped == 3
    assert sum(a.size for a in state.pop_deferred(1.0)) == 6


# ---------------------------------------------------------------------------
# mid-window re-planning: backlog crossing + exact split replay
# ---------------------------------------------------------------------------

def test_first_backlog_crossing_counts_uncompleted():
    times = np.arange(8, dtype=np.float64)             # one per second
    comps = np.array([2.5, 4.5])                       # two bs=2 batches
    # backlog after each arrival: 1 2 3 2 3 2 3 4
    assert S.first_backlog_crossing(times, comps, 2, 3) == 7
    assert S.first_backlog_crossing(times, comps, 2, 2) == 2
    assert S.first_backlog_crossing(times, comps, 2, 99) is None
    assert S.first_backlog_crossing(np.empty(0), comps, 2, 0) is None


@pytest.mark.parametrize("seed", range(4))
def test_split_at_arrival_timestamp_replays_bitwise(seed):
    """The contract the split driver leans on: clip a window at any arrival
    timestamp, chain the QueueState, and the two halves reproduce the
    unsplit run bitwise on NumPy — so splitting is purely a chance to
    re-plan, never a numerical perturbation."""
    rng = np.random.default_rng(seed)
    w = list(INFER_WORKLOADS.values())[rng.integers(5)]
    pm = MODES[rng.integers(len(MODES))]
    bs = [1, 4, 8][rng.integers(3)]
    trace = S.ArrivalTrace.poisson(float(rng.uniform(30, 120)), 8.0,
                                   seed=seed)
    split_t = float(trace.times[rng.integers(1, len(trace) - 1)])
    whole = S.simulate(DEV, None, w, pm, bs, trace, "managed",
                       backend="numpy")
    head = S.simulate(DEV, None, w, pm, bs, trace.clip(0.0, split_t),
                      "managed", backend="numpy")
    tail = S.simulate(DEV, None, w, pm, bs, trace.clip(split_t, 9.0),
                      "managed", carry_in=head.queue_state, backend="numpy")
    lats = np.concatenate([np.asarray(head.latencies, np.float64),
                           np.asarray(tail.latencies, np.float64)])
    assert np.array_equal(lats, np.asarray(whole.latencies, np.float64))


def test_closed_loop_splits_on_backlog_crossing():
    """A rate jump the EWMA estimator lags behind floods the second window;
    with split_backlog set the loop re-enters the controller mid-window
    (splits recorded), without admission trimming anything."""
    f = Fulcrum(DEV)
    cfg = ControllerConfig(rate_estimator="ewma", rate_margin=1.0,
                           carry_backlog=True, admission="none",
                           split_backlog=24, max_splits=2)
    wins = f.serve_dynamic(INFER_WORKLOADS["mobilenet"], 40.0, 0.1,
                           [20.0, 120.0, 120.0], "gmd",
                           window_duration=10.0, arrivals="poisson", seed=5,
                           controller=cfg, backend="numpy")
    assert sum(wr.splits for wr in wins) >= 2
    assert all(wr.splits <= 2 for wr in wins)
    assert all(wr.shed_requests == 0 for wr in wins)
    # every offered request is still accounted for across the splits
    for wr in wins:
        assert wr.offered_requests > 0
        assert wr.report is not None


# ---------------------------------------------------------------------------
# closed loop end to end
# ---------------------------------------------------------------------------

_PR5_CFG = dict(rate_estimator="ewma", rate_margin=1.5, feedback=True,
                carry_backlog=True, mode_switch_s=0.5)

# serve_dynamic(mobilenet, 40 W, 0.1 s, [60, 80, 45, 70], gmd, 10 s windows,
# poisson seed 3, ewma+feedback+carry+switch) on the NumPy reference —
# recorded from the PR-5 loop; the admission-aware loop must reproduce it.
_PR5_FINGERPRINT = [
    ("12c/2201/1300/3199", 4, 572, 27.243475908860727,
     10.014343123258966, 0.0, 0),
    ("12c/2201/1300/3199", 4, 772, 31.45140962804028,
     20.007964592759695, 0.0, 1),
    ("12c/2201/1300/3199", 4, 476, 25.13270795253002,
     30.01919892420535, 0.0, 0),
    ("12c/2201/1300/3199", 4, 732, 30.85720815969366,
     40.01063504205469, 0.0, 0),
]


def _pr5_run(**extra):
    f = Fulcrum(DEV)
    return f.serve_dynamic(INFER_WORKLOADS["mobilenet"], 40.0, 0.1,
                           [60.0, 80.0, 45.0, 70.0], "gmd",
                           window_duration=10.0, arrivals="poisson", seed=3,
                           backend="numpy",
                           controller=ControllerConfig(**_PR5_CFG, **extra))


def _fingerprint(wins):
    out = []
    for wr in wins:
        lats = np.asarray(wr.report.latencies, np.float64)
        out.append((str(wr.solution.pm), wr.solution.bs, lats.size,
                    float(lats.sum()), float(wr.report.queue_state.clock),
                    wr.mode_switch_s, wr.carried_requests))
    return out


def test_pr5_closed_loop_fingerprint_regression():
    """The admission-aware rewrite of the closed loop reproduces the PR-5
    controller bitwise when admission is off (recorded fingerprint)."""
    assert _fingerprint(_pr5_run()) == _PR5_FINGERPRINT


def test_admission_none_byte_identical_to_plain_closed_loop():
    base = _pr5_run()
    none = _pr5_run(admission="none")
    assert _fingerprint(none) == _fingerprint(base)
    for a, b in zip(base, none):
        assert np.array_equal(np.asarray(a.report.latencies),
                              np.asarray(b.report.latencies))
        assert a.shed_requests == b.shed_requests == 0
        assert a.deferred_requests == b.deferred_requests == 0


def test_shed_closed_loop_zero_admitted_violations():
    """Overload the PR-5 scenario: shedding keeps every *admitted* request
    inside the nominal budget while recording goodput and sheds."""
    f = Fulcrum(DEV)
    cfg = ControllerConfig(**_PR5_CFG, admission="shed",
                           burst_quantile=0.95)
    wins = f.serve_dynamic(INFER_WORKLOADS["mobilenet"], 40.0, 0.1,
                           [300.0, 300.0, 300.0], "gmd",
                           window_duration=10.0, arrivals="poisson", seed=3,
                           controller=cfg, backend="numpy")
    assert sum(wr.shed_requests for wr in wins) > 0
    for wr in wins:
        assert wr.report is not None
        assert wr.report.violation_rate(0.1) == 0.0
        assert wr.goodput is not None and 0.0 < wr.goodput <= 1.0 + 1e-12
        assert wr.report.shed_requests == wr.shed_requests
        assert wr.offered_requests > 0


def test_defer_closed_loop_records_and_reoffers():
    f = Fulcrum(DEV)
    cfg = ControllerConfig(**_PR5_CFG, admission="defer",
                           burst_quantile=0.95, defer_cap=2000)
    wins = f.serve_dynamic(INFER_WORKLOADS["mobilenet"], 40.0, 0.1,
                           [300.0, 60.0, 60.0], "gmd",
                           window_duration=10.0, arrivals="poisson", seed=3,
                           controller=cfg, backend="numpy")
    assert wins[0].deferred_requests > 0
    assert all(wr.shed_requests == 0 or wr.deferred_requests >= 0
               for wr in wins)
    # re-offered requests land in later windows: drain goodput can top 1,
    # and admitted service still meets the nominal budget everywhere
    for wr in wins:
        assert wr.report.violation_rate(0.1) == 0.0


def test_degrade_bs_sheds_nothing():
    f = Fulcrum(DEV)
    cfg = ControllerConfig(**_PR5_CFG, admission="degrade-bs",
                           burst_quantile=0.95)
    wins = f.serve_dynamic(INFER_WORKLOADS["mobilenet"], 40.0, 0.1,
                           [300.0, 500.0], "gmd", window_duration=10.0,
                           arrivals="poisson", seed=3, controller=cfg,
                           backend="numpy")
    assert all(wr.shed_requests == 0 and wr.deferred_requests == 0
               for wr in wins)
    assert all(wr.goodput is not None for wr in wins)


def test_multi_tenant_shed_keeps_admitted_in_budget():
    f = Fulcrum(DEV)
    streams = (P.StreamSpec(100.0, 0.1, INFER_WORKLOADS["mobilenet"]),
               P.StreamSpec(60.0, 0.2, INFER_WORKLOADS["lstm"]))
    cfg = ControllerConfig(rate_estimator="ewma", carry_backlog=True,
                           admission="shed", burst_quantile=0.95,
                           priorities=(1.0, 0.5))
    wins = f.serve_dynamic(streams, 55.0, None,
                           [(100.0, 60.0), (130.0, 78.0)], "gmd",
                           window_duration=10.0, arrivals="poisson", seed=2,
                           controller=cfg, backend="numpy")
    assert sum(wr.shed_requests for wr in wins) > 0
    for wr in wins:
        assert wr.solution is not None
        for rep, spec in zip(wr.report.streams, streams):
            assert rep.violation_rate(spec.latency_budget) == 0.0
        assert wr.goodput is not None


# ---------------------------------------------------------------------------
# open-loop goodput + runtime gate parity
# ---------------------------------------------------------------------------

def test_open_loop_reports_goodput():
    f = Fulcrum(DEV)
    wins = f.serve_dynamic(INFER_WORKLOADS["resnet50"], 40.0, 0.1,
                           [40.0, 60.0], "gmd", window_duration=5.0,
                           backend="numpy")
    for wr in wins:
        assert wr.goodput is not None
        assert wr.offered_requests == len(wr.report.trace)
        assert wr.shed_requests == 0


def test_runtime_gate_parity_with_engine_mask():
    """The runtime-side admission gate sheds the identical request set as
    the engine-side mask, and the gated runtime run under a FakeClock
    replays the engine on the admitted trace bitwise."""
    w = INFER_WORKLOADS["mobilenet"]
    pm = SPACE.maxn()
    bs = 4
    t_in = DEV.time_power(w, pm, bs)[0]
    budget = 4.0 * t_in
    trace = S.ArrivalTrace.poisson(3.0 * bs / t_in, 4.0, seed=11)
    pol = AdmissionPolicy("shed")
    mask = pol.admit(trace.times, budget, bs, t_in, 0.0)
    admitted = S.ArrivalTrace(trace.times[mask], trace.duration, trace.kind)

    clock = FakeClock()

    class _Server:
        def infer(self):
            clock.advance(t_in)

    rt = ManagedInterleaveRuntime(
        None, _Server(),
        InterleaveConfig(arrival_rate=0.0, infer_bs=bs,
                         latency_budget=budget),
        trace=trace, clock=clock, admission=pol.gate(bs, t_in, budget))
    rep = rt.run()
    assert rep.shed_requests == int(np.count_nonzero(~mask))
    ref = S.simulate(DEV, None, w, pm, bs, admitted, "managed",
                     backend="numpy")
    assert np.array_equal(np.asarray(rep.latencies, np.float64),
                          np.asarray(ref.latencies, np.float64))
    assert rep.violation_rate(budget) == 0.0


def test_runtime_gate_rejects_multi_stream_trace():
    merged = S.ArrivalTrace.merge([S.ArrivalTrace.uniform(10.0, 2.0),
                                   S.ArrivalTrace.uniform(10.0, 2.0)])
    pol = AdmissionPolicy("shed")
    with pytest.raises(ValueError, match="single-stream"):
        ManagedInterleaveRuntime(
            None, None,
            InterleaveConfig(arrival_rate=0.0, infer_bs=4,
                             latency_budget=0.1),
            trace=merged, admission=pol.gate(4, 0.01, 0.1))
