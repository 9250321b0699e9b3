"""Multi-tenant core: the N=1 problem/solver/engine paths must be *bitwise*
identical to the pair-shaped originals (the refactor's exactness contract),
the N>1 vectorized paths must equal their scalar references, and
ArrivalTrace.merge must round-trip stream provenance."""
import numpy as np
import pytest

from repro.core import grid_eval as G
from repro.core import problem as P
from repro.core import simulate as S
from repro.core.device_model import (DeviceModel, INFER_WORKLOADS,
                                     TRAIN_WORKLOADS)
from repro.core.powermode import PowerModeSpace

try:                                   # hypothesis is optional: the merge
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                    # property tests degrade to skips, the
    HAVE_HYPOTHESIS = False            # numpy-randomized ones always run

DEV = DeviceModel()
SPACE = PowerModeSpace()
MODES = SPACE.all_modes()

TRAIN_WS = list(TRAIN_WORKLOADS.values())
INFER_WS = list(INFER_WORKLOADS.values())


def _random_obs(rng, n_modes=40):
    sub = [MODES[i] for i in rng.choice(len(MODES), n_modes, replace=False)]
    w_tr = TRAIN_WS[rng.integers(len(TRAIN_WS))]
    w_in = INFER_WS[rng.integers(len(INFER_WS))]
    tobs = {pm: DEV.time_power(w_tr, pm) for pm in sub}
    iobs = {(pm, bs): DEV.time_power(w_in, pm, bs)
            for pm in sub for bs in P.INFER_BATCH_SIZES}
    return tobs, iobs


def _assert_pair_equal(sol, msol):
    assert (sol is None) == (msol is None)
    if sol is None:
        return
    assert sol.pm == msol.pm
    assert sol.bs == msol.bss[0]
    assert sol.tau_tr == msol.tau_tr
    assert sol.time == msol.times[0]        # bitwise float equality
    assert sol.power == msol.power
    assert sol.throughput == msol.throughput


# ---------------------------------------------------------------------------
# (a) one stream == the existing pair solver / kernel, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_single_stream_solver_bitwise_identical_to_concurrent(seed):
    rng = np.random.default_rng(seed)
    tobs, iobs = _random_obs(rng)
    for _ in range(20):
        prob = P.ConcurrentProblem(float(rng.uniform(10, 55)),
                                   float(rng.uniform(0.05, 2.0)),
                                   float(rng.uniform(5, 120)))
        ref = P.solve_concurrent(prob, tobs, iobs)
        got = P.solve_multi_tenant(prob.as_multi_tenant(), tobs, [iobs])
        _assert_pair_equal(ref, got)


@pytest.mark.parametrize("seed", range(4))
def test_single_stream_solver_bitwise_identical_to_infer(seed):
    rng = np.random.default_rng(seed)
    _, iobs = _random_obs(rng)
    for _ in range(20):
        prob = P.InferProblem(float(rng.uniform(10, 55)),
                              float(rng.uniform(0.05, 2.0)),
                              float(rng.uniform(5, 120)))
        ref = P.solve_infer(prob, iobs)
        got = P.solve_multi_tenant(prob.as_multi_tenant(), None, [iobs])
        assert (ref is None) == (got is None)
        if ref is not None:
            assert (ref.pm, ref.bs) == (got.pm, got.bss[0])
            assert ref.time == got.times[0] and ref.power == got.power


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_single_stream_batch_bitwise_identical_to_concurrent_batch(backend):
    rng = np.random.default_rng(7)
    tobs, iobs = _random_obs(rng, n_modes=60)
    tg = G.ObservationGrid.from_train_dict(tobs)
    ig = G.ObservationGrid.from_infer_dict(iobs)
    probs = [P.ConcurrentProblem(float(pb), float(lb), float(ar))
             for pb in (12, 25, 40, 55) for lb in (0.1, 0.6, 1.5)
             for ar in (20, 60, 110)]
    ref = G.solve_concurrent_batch(probs, tg, ig)
    got = G.solve_multi_tenant_batch([p.as_multi_tenant() for p in probs],
                                     tg, [ig], backend=backend)
    for r, g in zip(ref, got):
        _assert_pair_equal(r, g)


@pytest.mark.parametrize("seed", range(5))
def test_single_stream_kernel_bitwise_identical_to_managed_scalar(seed):
    rng = np.random.default_rng(seed)
    for _ in range(15):
        w_tr = TRAIN_WS[rng.integers(len(TRAIN_WS))] \
            if rng.random() < 0.8 else None
        w_in = INFER_WS[rng.integers(len(INFER_WS))]
        pm = MODES[rng.integers(len(MODES))]
        bs = [1, 4, 16, 32, 64][rng.integers(5)]
        rate = float(rng.uniform(1.0, 120.0))
        dur = float(rng.uniform(5.0, 40.0))
        trace = (S.ArrivalTrace.uniform(rate, dur) if rng.random() < 0.5
                 else S.ArrivalTrace.poisson(rate, dur, seed=seed))
        cap = None if rng.random() < 0.7 else int(rng.integers(0, 4))
        ref = S.managed_scalar(DEV, w_tr, w_in, pm, bs, trace, tau_cap=cap)
        got = S.simulate_multi_tenant(DEV, w_tr, [w_in], pm, [bs], [trace],
                                      tau_cap=cap)
        assert got.streams[0].latencies.tolist() == ref.latencies
        assert got.train_minibatches == ref.train_minibatches
        assert got.power == ref.power
        assert got.duration == ref.duration


# ---------------------------------------------------------------------------
# N > 1: vectorized == scalar reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_multi_stream_kernel_identical_to_scalar(seed):
    rng = np.random.default_rng(seed + 50)
    for _ in range(8):
        n = int(rng.integers(2, 5))
        w_tr = TRAIN_WS[rng.integers(len(TRAIN_WS))] \
            if rng.random() < 0.8 else None
        ws = [INFER_WS[rng.integers(len(INFER_WS))] for _ in range(n)]
        pm = MODES[rng.integers(len(MODES))]
        bss = [int([1, 4, 16, 32][rng.integers(4)]) for _ in range(n)]
        traces = [S.ArrivalTrace.uniform(float(rng.uniform(1, 60)),
                                         float(rng.uniform(5, 25)))
                  if rng.random() < 0.5 else
                  S.ArrivalTrace.poisson(float(rng.uniform(1, 60)),
                                         float(rng.uniform(5, 25)),
                                         seed=seed * 31 + j)
                  for j in range(n)]
        cap = None if rng.random() < 0.7 else int(rng.integers(0, 4))
        a = S.simulate_multi_tenant(DEV, w_tr, ws, pm, bss, traces,
                                    tau_cap=cap)
        b = S.multi_tenant_scalar(DEV, w_tr, ws, pm, bss, traces, tau_cap=cap)
        for ra, rb in zip(a.streams, b.streams):
            assert ra.latencies.tolist() == rb.latencies
        assert a.train_minibatches == b.train_minibatches
        assert a.power == b.power


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_multi_stream_batch_equals_scalar_solver(backend):
    rng = np.random.default_rng(11)
    tobs, iobs1 = _random_obs(rng, n_modes=30)
    _, iobs2 = _random_obs(rng, n_modes=30)
    # align mode sets: stream obs must share modes with the train grid
    iobs2 = {(pm, bs): DEV.time_power(INFER_WS[2], pm, bs)
             for pm in tobs for bs in P.INFER_BATCH_SIZES}
    probs = [P.MultiTenantProblem(
        float(pb), (P.StreamSpec(40.0, float(l)), P.StreamSpec(60.0, 0.7 * l)))
        for pb in (18, 30, 45) for l in (0.3, 0.8, 1.6)]
    got = G.solve_multi_tenant_batch(probs, tobs, [iobs1, iobs2],
                                     backend=backend)
    for prob, g in zip(probs, got):
        ref = P.solve_multi_tenant(prob, tobs, [iobs1, iobs2])
        assert (ref is None) == (g is None)
        if ref is None:
            continue
        assert (ref.pm, ref.bss, ref.tau_tr) == (g.pm, g.bss, g.tau_tr)
        assert ref.times == g.times
        assert ref.power == g.power and ref.throughput == g.throughput


def test_stream_batch_size_restriction_honoured():
    rng = np.random.default_rng(3)
    tobs, iobs = _random_obs(rng)
    spec = P.StreamSpec(60.0, 1.0, batch_sizes=(4, 16))
    prob = P.MultiTenantProblem(50.0, (spec,))
    sol = P.solve_multi_tenant(prob, tobs, [iobs])
    assert sol is not None and sol.bss[0] in (4, 16)
    batch = G.solve_multi_tenant_batch([prob], tobs, [iobs])[0]
    assert batch.bss == sol.bss and batch.times == sol.times


# ---------------------------------------------------------------------------
# (b) ArrivalTrace.merge: provenance + order, Hypothesis-randomized
# ---------------------------------------------------------------------------

def _check_merge_round_trip(stream_times):
    traces = [S.ArrivalTrace(np.asarray(ts, np.float64), 10.0 + j)
              for j, ts in enumerate(stream_times)]
    merged = S.ArrivalTrace.merge(traces)
    assert len(merged) == sum(len(t) for t in traces)
    assert merged.duration == max(t.duration for t in traces)
    assert np.all(np.diff(merged.times) >= 0)          # sorted
    # provenance round-trip (this also pins the stable tie order: equal
    # times must come back to their source streams intact)
    back = merged.split(len(traces))
    for orig, rt in zip(traces, back):
        assert rt.times.tolist() == orig.times.tolist()
    for j, tr in enumerate(traces):
        sel = merged.times[merged.stream_ids == j]
        assert sel.tolist() == tr.times.tolist()


if HAVE_HYPOTHESIS:
    sorted_times = st.lists(
        st.floats(min_value=0.0, max_value=1e4, allow_nan=False,
                  allow_infinity=False, width=64),
        min_size=0, max_size=60).map(sorted)

    @settings(max_examples=80, deadline=None)
    @given(st.lists(sorted_times, min_size=1, max_size=5))
    def test_merge_round_trips_provenance_and_sorted_order(stream_times):
        _check_merge_round_trip(stream_times)


@pytest.mark.parametrize("seed", range(10))
def test_merge_round_trip_randomized(seed):
    """numpy-randomized fallback of the Hypothesis property (always runs):
    duplicated timestamps across and within streams included."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 6))
    streams = []
    for _ in range(n):
        k = int(rng.integers(0, 50))
        ts = np.sort(np.round(rng.uniform(0, 20, k), 2))  # rounding => ties
        streams.append(ts.tolist())
    _check_merge_round_trip(streams)


def test_split_requires_provenance():
    with pytest.raises(ValueError, match="provenance"):
        S.ArrivalTrace.uniform(10.0, 1.0).split()


def test_merge_split_keeps_idle_tenants():
    """A rate-0 tenant (empty trace) must survive the round-trip — the
    stream count is recorded on the merged trace, not inferred."""
    traces = [S.ArrivalTrace.uniform(10.0, 2.0),
              S.ArrivalTrace.poisson(0.0, 2.0, seed=1),   # idle tenant
              S.ArrivalTrace.uniform(5.0, 2.0)]
    merged = S.ArrivalTrace.merge(traces)
    assert merged.n_streams == 3
    back = merged.split()
    assert len(back) == 3 and len(back[1]) == 0
    assert back[0].times.tolist() == traces[0].times.tolist()
    assert back[2].times.tolist() == traces[2].times.tolist()


_SPLIT_CASES = ("n=1", "n=3", "n=512", "wide-ids", "empty", "idle-streams",
                "tied-times", "ids-outside")


def _split_case(case):
    """(times, ids, n_streams) for one grouping case of ``split``."""
    rng = np.random.default_rng(_SPLIT_CASES.index(case))
    sorted_times = lambda k: np.sort(rng.uniform(0.0, 5.0, k))
    if case.startswith("n="):
        n = int(case[2:])
        return sorted_times(2000), rng.integers(0, n, 2000), n
    if case == "wide-ids":      # n past 16 bits: ids that wrap mod 2**16
        ids = rng.integers(0, 70_000, 300)    # must not collide with 3, 4
        ids[:4] = [3, 3 + (1 << 16), 4, 4 + (1 << 16)]
        return sorted_times(300), rng.permutation(ids), 70_000
    if case == "empty":
        return np.empty(0), np.empty(0, np.int64), 4
    if case == "idle-streams":  # only streams 1 and 5 of 8 draw arrivals
        return sorted_times(200), rng.choice([1, 5], 200), 8
    if case == "tied-times":    # equal times across (and within) streams
        return np.sort(np.round(rng.uniform(0, 1, 400), 1)), \
            rng.integers(0, 3, 400), 3
    if case == "ids-outside":   # ids < 0 or >= n are dropped
        return sorted_times(500), rng.integers(-2, 9, 500), 6
    raise ValueError(case)


@pytest.mark.parametrize("case", _SPLIT_CASES)
def test_split_equals_per_stream_masks(case):
    """``split`` groups in one pass; it must give exactly the per-stream
    mask definition ``times[stream_ids == j]``: the same float64 values in
    the same order (pinned through arrival positions, which ties cannot
    hide), the same duration and kind, empty idle streams, and nothing of
    an id outside ``[0, n)``."""
    times, ids, n = _split_case(case)
    trace = S.ArrivalTrace(times, 5.0, "merged", ids, n)
    pos = S.ArrivalTrace(np.arange(len(ids), dtype=np.float64), 5.0,
                         "merged", ids, n)
    back, back_pos = trace.split(), pos.split()
    assert len(back) == len(back_pos) == n
    for j, (tr, tp) in enumerate(zip(back, back_pos)):
        want = trace.times[trace.stream_ids == j]
        assert tr.times.dtype == np.float64
        assert np.array_equal(tr.times, want)
        assert np.array_equal(tp.times, np.flatnonzero(ids == j))
        assert tr.duration == 5.0 and tr.kind == "merged"
        assert tr.stream_ids is None and tr.n_streams is None


def test_batch_rejects_mixed_stream_workloads():
    """A problem batch shares one observation set per stream, so mixing
    stream workloads across the batch must be an error, not a silent solve
    against the wrong grid."""
    rng = np.random.default_rng(5)
    tobs, iobs = _random_obs(rng)
    p1 = P.MultiTenantProblem(
        40.0, (P.StreamSpec(40.0, 1.0, INFER_WS[0]),))
    p2 = P.MultiTenantProblem(
        40.0, (P.StreamSpec(40.0, 1.0, INFER_WS[1]),))
    with pytest.raises(ValueError, match="uniform"):
        G.solve_multi_tenant_batch([p1, p2], tobs, [iobs])
