"""Fleet-wide resource control (global admission, backlog migration, shared
power budget): the batched K-device loop must stay *bitwise* identical on
NumPy to K sequential single-device loops for every admission mode x
migration x shared-budget combination (tolerance-identical on jax), the
admitted subsequences must replay with zero nominal-budget violations at
fleet scale (the PR-6 exactness property, per device), migration must
conserve requests, water-filled grants must sum within the fleet cap, and
the default ``FleetSpec`` must reproduce the PR-8 loop byte-for-byte — the
features are provably opt-in."""
import numpy as np
import pytest

from repro.core import fleet as F
from repro.core import problem as P
from repro.core import simulate as S
from repro.core.backend import jax_available
from repro.core.controller import ControllerConfig, ControllerState
from repro.core.device_model import DeviceModel, INFER_WORKLOADS

try:                                   # hypothesis is optional: the random-
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                    # scenario property tests degrade to
    HAVE_HYPOTHESIS = False            # skips; seeded ones always run

DEV = DeviceModel()
W_IN = INFER_WORKLOADS["mobilenet"]

# the PR-6 closed-loop config the admission benches use, fleet-sized windows
_CL = dict(rate_estimator="ewma", rate_margin=1.5, feedback=True,
           carry_backlog=True, mode_switch_s=0.25, burst_quantile=0.95)


def _cfg(mode, **over):
    kw = dict(_CL, admission=mode)
    if mode == "defer":
        kw["defer_cap"] = 500
    kw.update(over)
    return ControllerConfig(**kw)


def _assert_fleet_equal(a, b, exact=True):
    """Field-by-field equality of two fleet runs, the new resource-control
    accounts included (extends test_fleet's checker)."""
    assert len(a) == len(b)
    for wa, wb in zip(a, b):
        assert np.array_equal(wa.dispatch_counts, wb.dispatch_counts)
        assert wa.offered_requests == wb.offered_requests
        assert np.array_equal(wa.trace.stream_ids, wb.trace.stream_ids)
        assert wa.shed_requests == wb.shed_requests
        assert wa.deferred_requests == wb.deferred_requests
        assert wa.migrated_requests == wb.migrated_requests
        assert (wa.power_budgets is None) == (wb.power_budgets is None)
        if wa.power_budgets is not None:
            assert wa.power_budgets.tolist() == wb.power_budgets.tolist()
        if exact:
            assert wa.goodput == wb.goodput
        for da, db in zip(wa.devices, wb.devices):
            assert (da.solution is None) == (db.solution is None)
            assert da.carried_requests == db.carried_requests
            assert da.offered_requests == db.offered_requests
            assert da.shed_requests == db.shed_requests
            assert da.deferred_requests == db.deferred_requests
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
                assert da.report.queue_state.pending.tolist() \
                    == db.report.queue_state.pending.tolist()
                assert da.report.queue_state.clock \
                    == db.report.queue_state.clock
            else:
                np.testing.assert_allclose(da.report.latencies,
                                           db.report.latencies,
                                           atol=1e-8, rtol=1e-9)


def _run_pair(spec, cfg, rates, backend="numpy", latency=0.05, power=30.0,
              wd=2.0, seed=11):
    kw = dict(window_duration=wd, arrivals="poisson", seed=seed,
              backend=backend, controller=cfg)
    a = F.serve_fleet(W_IN, power, latency, rates, spec, **kw)
    b = F.serve_fleet_sequential(W_IN, power, latency, rates, spec, **kw)
    return a, b


# ---------------------------------------------------------------------------
# (a) THE contract, extended: batched == sequential for every feature combo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["shed", "defer", "degrade-bs"])
@pytest.mark.parametrize("mig,fleet_budget", [(False, None), (True, None),
                                              (False, 130.0), (True, 130.0)])
def test_admission_fleet_bitwise_equals_sequential_numpy(mode, mig,
                                                         fleet_budget):
    spec = F.FleetSpec(5, seed=3, time_spread=0.3, dispatch="least-backlog",
                       migrate_backlog=mig, fleet_power_budget=fleet_budget)
    rates = [400.0, 800.0, 120.0, 600.0]     # overload: the gates must act
    a, b = _run_pair(spec, _cfg(mode), rates)
    _assert_fleet_equal(a, b, exact=True)
    if mode in ("shed", "defer") and fleet_budget is None:
        assert sum(w.shed_requests + w.deferred_requests for w in a) > 0
    if mig and fleet_budget is None:
        assert sum(w.migrated_requests for w in a) > 0


@pytest.mark.parametrize("mode", ["shed", "defer"])
def test_admission_fleet_jax_matches_sequential_within_tolerance(mode):
    if not jax_available():
        pytest.skip("jax unavailable")
    spec = F.FleetSpec(4, seed=2, time_spread=0.25, migrate_backlog=True,
                       fleet_power_budget=110.0)
    a, b = _run_pair(spec, _cfg(mode), [300.0, 700.0, 150.0], backend="jax")
    _assert_fleet_equal(a, b, exact=False)


@pytest.mark.parametrize("seed", range(4))
def test_admission_fleet_parity_random_scenarios(seed):
    """Seeded random K / heterogeneity / burst mixes — always runs, even
    without hypothesis installed."""
    rng = np.random.default_rng(seed)
    mode = ("shed", "defer", "degrade-bs")[seed % 3]
    spec = F.FleetSpec(int(rng.integers(1, 7)), seed=seed,
                       time_spread=float(rng.uniform(0.0, 0.4)),
                       dispatch=("capacity", "least-backlog")[seed % 2],
                       migrate_backlog=bool(seed % 2),
                       fleet_power_budget=(None, 80.0)[(seed // 2) % 2])
    rates = [float(r) for r in rng.uniform(20.0, 900.0, 4)]
    a, b = _run_pair(spec, _cfg(mode), rates, seed=seed + 50)
    _assert_fleet_equal(a, b, exact=True)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 10**6),
           k=st.integers(1, 6),
           spread=st.floats(0.0, 0.4),
           mode=st.sampled_from(["shed", "defer", "degrade-bs"]),
           mig=st.booleans(),
           budget=st.sampled_from([None, 60.0, 100.0]),
           dispatch=st.sampled_from(["capacity", "least-backlog"]),
           burst=st.floats(100.0, 1200.0))
    def test_admission_fleet_parity_property(seed, k, spread, mode, mig,
                                             budget, dispatch, burst):
        rng = np.random.default_rng(seed)
        spec = F.FleetSpec(k, seed=seed % 97, time_spread=spread,
                           dispatch=dispatch, migrate_backlog=mig,
                           fleet_power_budget=budget)
        rates = [float(r) for r in rng.uniform(10.0, burst, 3)]
        a, b = _run_pair(spec, _cfg(mode), rates, seed=seed % 1013)
        _assert_fleet_equal(a, b, exact=True)


# ---------------------------------------------------------------------------
# (b) flood admission at fleet scale: admitted subsequences replay clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["shed", "defer"])
def test_fleet_flood_admitted_requests_meet_budget(mode):
    """The PR-6 exactness property per device: the admission mask runs each
    device's own engine recurrence (its own t_in, its carried clock), so
    every admitted request — simulated for real through ``simulate_batch``
    — meets the nominal budget, while the flood guarantees rejections
    happened on several devices."""
    spec = F.FleetSpec(4, seed=3, time_spread=0.3)
    rates = [1200.0, 1200.0, 1200.0]            # ~3x sustainable per device
    wins = F.serve_fleet(W_IN, 40.0, 0.1, rates, spec, window_duration=2.0,
                         arrivals="poisson", seed=7, backend="numpy",
                         controller=_cfg(mode, defer_cap=200))
    trimmed_devices = set()
    for fw in wins:
        assert fw.shed_requests + fw.deferred_requests > 0
        for d, wr in enumerate(fw.devices):
            if wr.report is not None:
                assert wr.report.violation_rate(0.1) == 0.0
            if wr.shed_requests + wr.deferred_requests > 0:
                trimmed_devices.add(d)
    assert len(trimmed_devices) > 1             # fleet-wide, not one lane
    # dropping the gate makes the same flood violate: the overload is real
    raw = F.serve_fleet(W_IN, 40.0, 0.1, rates, spec, window_duration=2.0,
                        arrivals="poisson", seed=7, backend="numpy",
                        controller=ControllerConfig(**_CL))
    assert any(wr.report is not None and wr.report.violation_rate(0.1) > 0.0
               for fw in raw for wr in fw.devices)


def test_fleet_deferred_reenter_the_dispatcher():
    """Deferred requests re-enter the *dispatcher* at the next window start
    (re-timestamped at t0, sorted first), not the device that bounced them:
    the next window's merged trace carries exactly the previous window's
    deferral count as extra leading arrivals."""
    spec = F.FleetSpec(3, seed=3, time_spread=0.3)
    wins = F.serve_fleet(W_IN, 40.0, 0.1, [900.0, 300.0, 100.0], spec,
                         window_duration=2.0, arrivals="poisson", seed=7,
                         backend="numpy", controller=_cfg("defer"))
    assert wins[0].deferred_requests > 0
    for prev, cur, i in zip(wins, wins[1:], range(1, len(wins))):
        extra = len(cur.trace) - cur.offered_requests
        assert extra == prev.deferred_requests
        t0 = i * 2.0
        assert np.all(cur.trace.times[:extra] == t0)
        # the re-offers were dispatched across devices like any arrival
        assert int(cur.dispatch_counts.sum()) == len(cur.trace)


def test_fleet_k512_device_traces_equal_dispatch_masks(monkeypatch):
    """At the benchmark's fleet size, the per-device traces a shed-admission
    window runs are exactly the dispatch masks ``agg.times[sid == d]``:
    the one-pass grouping pinned at K=512, not only at a handful of
    devices (2 req/s per device keeps the three windows small)."""
    K = 512
    seen = []
    split_window = F.split_window

    def spy(agg, sid, n_devices):
        merged, dtr = split_window(agg, sid, n_devices)
        seen.append((agg.times.copy(), np.asarray(sid).copy(), dtr))
        return merged, dtr

    monkeypatch.setattr(F, "split_window", spy)
    wins = F.serve_fleet(W_IN, 30.0, 0.1, [2.0 * K, 3.0 * K, 1.5 * K],
                         F.FleetSpec(K, seed=3), window_duration=2.0,
                         arrivals="poisson", seed=5, backend="numpy",
                         controller=_cfg("shed"))
    assert len(seen) == len(wins) == 3
    for (times, sid, dtr), fw in zip(seen, wins):
        assert len(dtr) == K and np.array_equal(fw.trace.stream_ids, sid)
        for d in range(K):
            assert dtr[d].times.dtype == np.float64
            assert np.array_equal(dtr[d].times, times[sid == d])
            assert dtr[d].duration == fw.trace.duration
            assert fw.devices[d].offered_requests == len(dtr[d])


# ---------------------------------------------------------------------------
# (c) conservation: migration moves requests, never loses or mints them
# ---------------------------------------------------------------------------

def _states_with_backlog(pendings, clocks, cfg):
    states = []
    for pend, clock in zip(pendings, clocks):
        stt = ControllerState(cfg, 1)
        if pend is not None:
            stt.carry = S.QueueState(np.asarray(pend, np.float64),
                                     float(clock))
        states.append(stt)
    return states


def test_migrate_backlog_conserves_and_retimestamps():
    cfg = ControllerConfig(carry_backlog=True)
    # device 0 is flooded, 1 idle, 2 lightly loaded, 3 has no carry at all
    pendings = [np.linspace(0.0, 1.8, 40), np.empty(0), [1.0, 1.5], None]
    clocks = [2.4, 2.0, 2.1, 0.0]
    states = _states_with_backlog(pendings, clocks, cfg)
    before = sum(len(s.carry) for s in states if s.carry is not None)
    moved = F._migrate_backlog(states, np.ones(4), t0=2.0)
    assert moved > 0
    after = sum(len(s.carry) for s in states if s.carry is not None)
    assert after == before                       # nothing lost or minted
    sizes = [len(s.carry) for s in states]
    assert max(sizes) - min(sizes) <= 1          # equal-weight equalization
    for d, s in enumerate(states):
        pend = s.carry.pending
        assert np.all(np.diff(pend) >= 0.0)      # replayable: nondecreasing
        assert np.all(pend <= 2.0 + 1e-12)       # moved requests land at t0
        # clocks never migrate: a busy device stays busy
        expect = clocks[d] if pendings[d] is not None else 2.0
        assert s.carry.clock == expect
    # stayed requests keep their original timestamps (bitwise replay);
    # moved ones are re-timestamped at exactly t0
    orig = set(np.concatenate([np.asarray(p, np.float64)
                               for p in pendings if p is not None]))
    for s in states:
        for t in s.carry.pending:
            assert float(t) in orig or float(t) == 2.0


def test_migrate_noop_when_nothing_moves():
    cfg = ControllerConfig(carry_backlog=True)
    states = _states_with_backlog([[0.5], [0.6]], [1.0, 1.0], cfg)
    carries = [s.carry for s in states]
    assert F._migrate_backlog(states, np.ones(2), t0=1.0) == 0
    assert all(s.carry is c for s, c in zip(states, carries))


def test_migration_rebalances_toward_idle_devices():
    """End to end: with least-backlog dispatch off (capacity dispatch pins
    arrivals proportionally), migration drains a hot device's carry into
    idle ones between windows."""
    spec_off = F.FleetSpec(4, seed=5, time_spread=0.35)
    spec_on = F.FleetSpec(4, seed=5, time_spread=0.35, migrate_backlog=True)
    kw = dict(window_duration=2.0, arrivals="poisson", seed=9,
              backend="numpy", controller=ControllerConfig(**_CL))
    rates = [1000.0, 50.0, 50.0, 50.0]          # burst, then drain windows
    off = F.serve_fleet(W_IN, 30.0, 0.05, rates, spec_off, **kw)
    on = F.serve_fleet(W_IN, 30.0, 0.05, rates, spec_on, **kw)
    assert sum(w.migrated_requests for w in on) > 0
    assert all(w.migrated_requests == 0 for w in off)
    # post-burst carried backlog is spread flatter with migration on
    def worst_carry(wins):
        return max(max(wr.carried_requests for wr in w.devices)
                   for w in wins[1:])
    assert worst_carry(on) <= worst_carry(off)


# ---------------------------------------------------------------------------
# (d) shared power budget: water-filling grants within the fleet cap
# ---------------------------------------------------------------------------

def test_water_fill_closed_form():
    np.testing.assert_allclose(P.water_fill(np.array([1.0, 2.0, 3.0]), 9.0),
                               [2.0, 3.0, 4.0])          # slack split evenly
    got = P.water_fill(np.array([1.0, 5.0, 10.0]), 8.0)
    np.testing.assert_allclose(got, [1.0, 3.5, 3.5])     # level allocation
    assert float(got.sum()) == pytest.approx(8.0)
    np.testing.assert_allclose(P.water_fill(np.array([4.0]), 2.0), [2.0])
    with pytest.raises(ValueError):
        P.water_fill(np.array([-1.0, 2.0]), 5.0)
    with pytest.raises(ValueError):
        P.water_fill(np.empty(0), 5.0)


def test_fleet_power_budget_bounds_attributed_power():
    fb = 120.0
    spec = F.FleetSpec(5, seed=3, time_spread=0.3, fleet_power_budget=fb)
    wins = F.serve_fleet(W_IN, 30.0, 0.05, [400.0, 800.0, 300.0], spec,
                         window_duration=2.0, arrivals="poisson", seed=11,
                         backend="numpy", controller=_cfg("shed"))
    served = 0
    for fw in wins:
        assert fw.power_budgets is not None
        assert float(fw.power_budgets.sum()) <= fb + 1e-9
        assert np.all(fw.power_budgets > 0.0)
        assert np.all(fw.power_budgets <= 30.0 + 1e-12)  # per-device cap
        assert fw.attributed_power <= fb + 1e-9
        for d, wr in enumerate(fw.devices):
            if wr.report is None:
                continue
            served += 1
            # the committed plan respects the device's water-filled grant
            assert wr.solution.power <= fw.power_budgets[d] + 1e-12
    assert served > 0


def test_fleet_power_budget_follows_demand():
    """Water-filling is demand-driven: after a window where only some
    devices attribute power, the next grants skew toward them (floored so
    idle devices can re-enter)."""
    fb = 120.0
    spec = F.FleetSpec(5, seed=3, time_spread=0.3, fleet_power_budget=fb)
    wins = F.serve_fleet(W_IN, 30.0, 0.05, [400.0, 800.0, 300.0], spec,
                         window_duration=2.0, arrivals="poisson", seed=11,
                         backend="numpy", controller=_cfg("shed"))
    K = 5
    floor = fb / (4.0 * K)
    for prev, cur in zip(wins, wins[1:]):
        attr = np.array([(wr.report.attributed_power or 0.0)
                         if wr.report is not None else 0.0
                         for wr in prev.devices])
        assert np.all(cur.power_budgets >= floor - 1e-12)
        if attr.max() > attr.min():              # skewed demand last window
            assert cur.power_budgets[int(attr.argmax())] \
                >= cur.power_budgets[int(attr.argmin())]


# ---------------------------------------------------------------------------
# (e) the features are provably opt-in: PR-8 defaults, byte-for-byte
# ---------------------------------------------------------------------------

# serve_fleet(mobilenet, 30.0, 0.1, [60, 90, 45], FleetSpec(3, seed=2,
# dispatch="least-backlog"), wd=5.0, poisson seed 9, numpy) under the PR-5
# closed-loop config — captured on the PR-8 code before this PR's features
_PR8_FINGERPRINT = [
    ([95, 108, 95], 298, 1.0, 88.97228327172972,
     [('8c/1958/1300/3199', 1, 95, 1.7360800077866878, 5.015016350494625),
      ('8c/2201/1300/3199', 1, 108, 1.694506938705475, 4.995914466680478),
      ('8c/2201/1300/3199', 1, 95, 1.7007526113773164, 4.951472835322781)]),
    ([146, 167, 147], 460, 1.0, 88.97228327172972,
     [('8c/1958/1300/3199', 1, 146, 2.7869726480459205, 10.009887555913291),
      ('8c/2201/1300/3199', 1, 167, 2.679105710393067, 10.011081786031085),
      ('8c/2201/1300/3199', 1, 147, 2.7030223667635243, 9.993803171137206)]),
    ([71, 80, 71], 222, 1.0, 88.97228327172972,
     [('8c/1958/1300/3199', 1, 71, 1.2997414865362735, 14.979021337146028),
      ('8c/2201/1300/3199', 1, 80, 1.2702685196490382, 14.93161267356513),
      ('8c/2201/1300/3199', 1, 71, 1.2654876022190038, 14.950858563553371)])]


def test_fleet_defaults_reproduce_pr8_byte_identically():
    spec = F.FleetSpec(3, seed=2, dispatch="least-backlog")
    cfg = ControllerConfig(rate_estimator="ewma", rate_margin=1.5,
                           feedback=True, carry_backlog=True,
                           mode_switch_s=0.25)
    wins = F.serve_fleet(W_IN, 30.0, 0.1, [60.0, 90.0, 45.0], spec,
                         window_duration=5.0, arrivals="poisson", seed=9,
                         backend="numpy", controller=cfg)
    got = [(list(map(int, fw.dispatch_counts)), fw.offered_requests,
            fw.goodput, fw.attributed_power,
            [(str(wr.solution.pm), wr.solution.bs,
              len(wr.report.latencies),
              float(np.sum(wr.report.latencies)),
              float(wr.report.queue_state.clock))
             for wr in fw.devices]) for fw in wins]
    assert got == _PR8_FINGERPRINT
    for fw in wins:                              # and the new accounts stay
        assert fw.shed_requests == 0             # inert at the defaults
        assert fw.deferred_requests == 0
        assert fw.migrated_requests == 0
        assert fw.power_budgets is None


def test_fused_run_leaves_default_path_byte_identical():
    """The fused-window PR's opt-in proof: running the fused jax program
    first (caches warmed, grid device columns uploaded, counters bumped)
    must leave the default unfused NumPy run reproducing the PR-8
    fingerprint byte-for-byte."""
    from repro.core.backend import jax_available
    if not jax_available():
        pytest.skip("jax unavailable")
    spec = F.FleetSpec(3, seed=2, dispatch="least-backlog")
    cfg = ControllerConfig(rate_estimator="ewma", rate_margin=1.5,
                           feedback=True, carry_backlog=True,
                           mode_switch_s=0.25)
    kw = dict(window_duration=5.0, arrivals="poisson", seed=9,
              controller=cfg)
    F.serve_fleet(W_IN, 30.0, 0.1, [60.0, 90.0, 45.0], spec,
                  backend="jax", fused=True, **kw)
    wins = F.serve_fleet(W_IN, 30.0, 0.1, [60.0, 90.0, 45.0], spec,
                         backend="numpy", **kw)
    got = [(list(map(int, fw.dispatch_counts)), fw.offered_requests,
            fw.goodput, fw.attributed_power,
            [(str(wr.solution.pm), wr.solution.bs,
              len(wr.report.latencies),
              float(np.sum(wr.report.latencies)),
              float(wr.report.queue_state.clock))
             for wr in fw.devices]) for fw in wins]
    assert got == _PR8_FINGERPRINT


# ---------------------------------------------------------------------------
# (f) per-feature capability checks: one clear error per unsupported combo
# ---------------------------------------------------------------------------

def test_split_backlog_still_rejected_with_clear_message():
    with pytest.raises(ValueError, match="split_backlog"):
        F.serve_fleet(W_IN, 30.0, 0.2, [50.0], F.FleetSpec(2),
                      controller=ControllerConfig(split_backlog=1))
    with pytest.raises(ValueError, match="split_backlog"):
        F.serve_fleet_sequential(W_IN, 30.0, 0.2, [50.0], F.FleetSpec(2),
                                 controller=ControllerConfig(split_backlog=1))


def test_migration_requires_carry_backlog_with_clear_message():
    spec = F.FleetSpec(2, migrate_backlog=True)
    with pytest.raises(ValueError, match="carry_backlog"):
        F.serve_fleet(W_IN, 30.0, 0.2, [50.0], spec,
                      controller=ControllerConfig())
    with pytest.raises(ValueError, match="carry_backlog"):
        F.serve_fleet_sequential(W_IN, 30.0, 0.2, [50.0], spec,
                                 controller=ControllerConfig())


def test_fleet_spec_validates_power_budget():
    with pytest.raises(ValueError, match="fleet_power_budget"):
        F.FleetSpec(2, fleet_power_budget=0.0)
    with pytest.raises(ValueError, match="fleet_power_budget"):
        F.FleetSpec(2, fleet_power_budget=-5.0)
    assert F.FleetSpec(2, fleet_power_budget=60.0).fleet_power_budget == 60.0
