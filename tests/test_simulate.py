"""Trace-driven execution engine: the vectorized managed simulator must be
*identical* (latencies, minibatch counts, power) to the seed's scalar loop
across randomized (workload, pm, bs, rate) configs and every trace kind;
native/streams are seeded-deterministic with the same queueing skeleton.
The jax backend (max-plus associative scan) is cross-checked against the
NumPy reference within the tolerance documented in docs/exactness.md."""
import numpy as np
import pytest

from repro.core import backend as B
from repro.core import problem as P
from repro.core import simulate as S
from repro.core.device_model import (DeviceModel, INFER_WORKLOADS,
                                     TRAIN_WORKLOADS)
from repro.core.interleave import (simulate_managed, simulate_native,
                                   simulate_streams)
from repro.core.powermode import PowerModeSpace

DEV = DeviceModel()
SPACE = PowerModeSpace()
MODES = SPACE.all_modes()


def _random_config(rng):
    w_tr = (list(TRAIN_WORKLOADS.values())[rng.integers(5)]
            if rng.random() < 0.8 else None)
    w_in = list(INFER_WORKLOADS.values())[rng.integers(5)]
    pm = MODES[rng.integers(len(MODES))]
    bs = [1, 4, 16, 32, 64][rng.integers(5)]
    rate = float(rng.uniform(1.0, 120.0))
    duration = float(rng.uniform(5.0, 60.0))
    kind = int(rng.integers(3))
    if kind == 0:
        trace = S.ArrivalTrace.uniform(rate, duration)
    elif kind == 1:
        trace = S.ArrivalTrace.poisson(rate, duration,
                                       seed=int(rng.integers(1000)))
    else:
        trace = S.ArrivalTrace.piecewise(
            [float(rng.uniform(1.0, 100.0)) for _ in range(4)], duration / 4)
    tau_cap = None if rng.random() < 0.7 else int(rng.integers(0, 4))
    return w_tr, w_in, pm, bs, trace, tau_cap


# ---------------------------------------------------------------------------
# managed: vectorized kernel == scalar reference, exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_managed_identical_to_scalar_randomized(seed):
    rng = np.random.default_rng(seed)
    for _ in range(25):
        w_tr, w_in, pm, bs, trace, tau_cap = _random_config(rng)
        vec = S.simulate(DEV, w_tr, w_in, pm, bs, trace, "managed",
                         tau_cap=tau_cap)
        ref = S.managed_scalar(DEV, w_tr, w_in, pm, bs, trace,
                               tau_cap=tau_cap)
        assert vec.latencies.tolist() == ref.latencies
        assert vec.train_minibatches == ref.train_minibatches
        assert vec.power == ref.power
        assert vec.duration == ref.duration


def test_managed_backlogged_identical_to_scalar():
    """Unsustainable config (t_in > bs/rate): the backlog-resolve path must
    still match the scalar recurrence exactly."""
    w_in = INFER_WORKLOADS["bert"]          # slow inference
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    pm = MODES[0]                           # slowest mode
    trace = S.ArrivalTrace.uniform(60.0, 20.0)
    vec = S.simulate(DEV, w_tr, w_in, pm, 16, trace, "managed")
    ref = S.managed_scalar(DEV, w_tr, w_in, pm, 16, trace)
    t_in, _ = DEV.time_power(w_in, pm, 16)
    assert not P.sustainable(16, 60.0, t_in)     # backlog really happens
    assert vec.latencies.tolist() == ref.latencies
    assert vec.train_minibatches == ref.train_minibatches


def test_managed_wrapper_matches_seed_signature():
    """The interleave.simulate_managed wrapper over a uniform trace equals
    the scalar reference driven by the seed's arrival list."""
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    w_in = INFER_WORKLOADS["mobilenet"]
    pm = SPACE.maxn()
    rep = simulate_managed(DEV, w_tr, w_in, pm, 16, 60.0, duration=30.0)
    arrivals = [i / 60.0 for i in range(int(60.0 * 30.0))]   # seed loop
    assert rep.trace.times.tolist() == arrivals
    ref = S.managed_scalar(DEV, w_tr, w_in, pm, 16, rep.trace)
    assert rep.latencies.tolist() == ref.latencies
    assert rep.train_minibatches == ref.train_minibatches


def test_managed_tau_cap_bounds_training_only():
    """Threading the plan's tau_tr caps slack-fill without touching the
    latency trajectory (training never delays inference)."""
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    w_in = INFER_WORKLOADS["mobilenet"]
    pm = SPACE.maxn()
    trace = S.ArrivalTrace.uniform(60.0, 30.0)
    free = S.simulate(DEV, w_tr, w_in, pm, 16, trace, "managed")
    capped = S.simulate(DEV, w_tr, w_in, pm, 16, trace, "managed", tau_cap=1)
    n_batches = len(trace) // 16
    assert capped.train_minibatches <= n_batches
    assert capped.train_minibatches <= free.train_minibatches
    assert capped.latencies.tolist() == free.latencies.tolist()


# ---------------------------------------------------------------------------
# arrival traces
# ---------------------------------------------------------------------------

def test_uniform_trace_bitwise_matches_seed_arrivals():
    for rate, duration in [(60.0, 30.0), (37.3, 17.9), (1.5, 120.0)]:
        trace = S.ArrivalTrace.uniform(rate, duration)
        assert trace.times.tolist() == \
            [i / rate for i in range(int(rate * duration))]


def test_poisson_trace_seeded_and_bounded():
    a = S.ArrivalTrace.poisson(60.0, 30.0, seed=3)
    b = S.ArrivalTrace.poisson(60.0, 30.0, seed=3)
    c = S.ArrivalTrace.poisson(60.0, 30.0, seed=4)
    assert np.array_equal(a.times, b.times)
    assert not np.array_equal(a.times, c.times)
    assert np.all(np.diff(a.times) > 0)
    assert a.times[-1] < 30.0 and a.times[0] > 0.0
    # ~rate*duration arrivals (Poisson concentration)
    assert 0.7 * 1800 < len(a) < 1.3 * 1800


def test_poisson_trace_idle_window_is_empty():
    trace = S.ArrivalTrace.poisson(0.0, 30.0, seed=1)
    assert len(trace) == 0 and trace.duration == 30.0
    rep = S.simulate(DEV, None, INFER_WORKLOADS["lstm"], SPACE.maxn(), 4,
                     trace, "managed")
    assert len(rep.latencies) == 0 and rep.train_minibatches == 0


def test_piecewise_trace_window_structure():
    rates = [10.0, 0.0, 40.0]
    trace = S.ArrivalTrace.piecewise(rates, 5.0)
    assert trace.duration == 15.0
    w0 = trace.times[trace.times < 5.0]
    w1 = trace.times[(trace.times >= 5.0) & (trace.times < 10.0)]
    w2 = trace.times[trace.times >= 10.0]
    assert len(w0) == 50 and len(w1) == 0 and len(w2) == 200
    assert np.all(np.diff(trace.times) >= 0)


# ---------------------------------------------------------------------------
# report statistics
# ---------------------------------------------------------------------------

def test_latency_quantile_nearest_rank():
    rep = S.ExecutionReport("managed", [4.0, 1.0, 3.0, 2.0], 0, 1.0, 0.0)
    assert rep.latency_quantile(0.75) == 3.0     # ceil(0.75*4)=3rd, not max
    assert rep.latency_quantile(0.5) == 2.0
    assert rep.latency_quantile(1.0) == 4.0
    assert rep.latency_quantile(0.01) == 1.0
    assert S.ExecutionReport("m", [], 0, 1.0, 0.0).latency_quantile(0.5) == 0.0


def test_violation_rate_matches_loop():
    xs = [0.1, 0.5, 0.2, 0.9]
    rep = S.ExecutionReport("managed", np.asarray(xs), 0, 1.0, 0.0)
    assert rep.violation_rate(0.3) == sum(1 for x in xs if x > 0.3) / len(xs)


# ---------------------------------------------------------------------------
# native / streams: seeded determinism + queueing skeleton
# ---------------------------------------------------------------------------

def test_native_streams_deterministic_per_seed():
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    w_in = INFER_WORKLOADS["mobilenet"]
    pm = SPACE.maxn()
    for sim in (simulate_native, simulate_streams):
        a = sim(DEV, w_tr, w_in, pm, 16, 60.0, duration=20.0, seed=1)
        b = sim(DEV, w_tr, w_in, pm, 16, 60.0, duration=20.0, seed=1)
        c = sim(DEV, w_tr, w_in, pm, 16, 60.0, duration=20.0, seed=2)
        assert a.latencies.tolist() == b.latencies.tolist()
        assert a.latencies.tolist() != c.latencies.tolist()


def test_queue_completions_matches_sequential_recurrence():
    rng = np.random.default_rng(0)
    for _ in range(20):
        K = int(rng.integers(1, 200))
        ready = np.sort(rng.uniform(0, 50, K))
        exec_t = rng.uniform(0.01, 2.0, K)
        got = S._queue_completions(ready, exec_t)
        now, want = 0.0, []
        for r, e in zip(ready, exec_t):
            now = max(now, r) + e
            want.append(now)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-12)


def test_managed_dominates_native_and_streams_tails():
    """Fig. 2 shape is preserved by the vectorized engines."""
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    w_in = INFER_WORKLOADS["mobilenet"]
    pm = SPACE.maxn()
    man = simulate_managed(DEV, w_tr, w_in, pm, 16, 60.0, duration=30.0)
    nat = simulate_native(DEV, w_tr, w_in, pm, 16, 60.0, duration=30.0)
    stc = simulate_streams(DEV, w_tr, w_in, pm, 16, 60.0, duration=30.0)
    assert nat.latency_quantile(0.75) > man.latency_quantile(0.75)
    assert stc.latency_quantile(0.95) > man.latency_quantile(0.95)
    for rep in (man, nat, stc):
        assert rep.trace is not None and len(rep.trace) == 1800


def test_unknown_approach_raises():
    with pytest.raises(ValueError, match="unknown approach"):
        S.simulate(DEV, None, INFER_WORKLOADS["lstm"], SPACE.maxn(), 1,
                   S.ArrivalTrace.uniform(10.0, 1.0), approach="magic")


# ---------------------------------------------------------------------------
# jax backend: max-plus scan engine vs the NumPy reference, within the
# tolerance documented in docs/exactness.md (the scan reassociates adds and
# skips the fill-count boundary replay, so it is NOT bitwise)
# ---------------------------------------------------------------------------

needs_jax = pytest.mark.skipif(not B.jax_available(),
                               reason="jax unavailable")
TOL = dict(rtol=1e-9, atol=1e-8)
TRAIN_WS = list(TRAIN_WORKLOADS.values())
INFER_WS = list(INFER_WORKLOADS.values())


def _assert_engine_close(ref, got):
    np.testing.assert_allclose(np.asarray(got.latencies, np.float64),
                               np.asarray(ref.latencies, np.float64), **TOL)
    # fill counts may flip only on quotient-boundary cases (floor vs replay)
    assert abs(ref.train_minibatches - got.train_minibatches) <= 2
    if bool(ref.train_minibatches) == bool(got.train_minibatches):
        assert ref.power == got.power
    assert ref.duration == got.duration


@needs_jax
@pytest.mark.parametrize("seed", range(4))
def test_jax_engine_matches_numpy_randomized(seed):
    rng = np.random.default_rng(100 + seed)
    w_tr = TRAIN_WS[seed % len(TRAIN_WS)] if seed % 2 == 0 else None
    w_in = INFER_WS[seed % len(INFER_WS)]
    pms, bss, traces, caps = [], [], [], []
    for _ in range(8):
        _, _, pm, bs, trace, cap = _random_config(rng)
        pms.append(pm), bss.append(bs), traces.append(trace), caps.append(cap)
    ref = S.simulate_batch(DEV, w_tr, w_in, pms, bss, traces,
                           tau_caps=caps, backend="numpy")
    got = S.simulate_batch(DEV, w_tr, w_in, pms, bss, traces,
                           tau_caps=caps, backend="jax")
    for a, b in zip(ref, got):
        _assert_engine_close(a, b)


@needs_jax
def test_jax_single_simulate_matches_numpy():
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    w_in = INFER_WORKLOADS["mobilenet"]
    trace = S.ArrivalTrace.poisson(60.0, 30.0, seed=7)
    ref = S.simulate(DEV, w_tr, w_in, SPACE.maxn(), 16, trace, "managed")
    got = S.simulate(DEV, w_tr, w_in, SPACE.maxn(), 16, trace, "managed",
                     backend="jax")
    _assert_engine_close(ref, got)


@needs_jax
def test_jax_engine_backlogged_within_tolerance():
    """Unsustainable config: the scan must track the queue buildup too."""
    trace = S.ArrivalTrace.uniform(60.0, 20.0)
    ref = S.simulate(DEV, TRAIN_WORKLOADS["mobilenet"],
                     INFER_WORKLOADS["bert"], MODES[0], 16, trace, "managed")
    got = S.simulate(DEV, TRAIN_WORKLOADS["mobilenet"],
                     INFER_WORKLOADS["bert"], MODES[0], 16, trace, "managed",
                     backend="jax")
    _assert_engine_close(ref, got)


@needs_jax
@pytest.mark.parametrize("seed", range(3))
def test_jax_multi_tenant_matches_numpy_randomized(seed):
    """Ragged tenant counts across lanes (padded stream axes), including
    idle tenants whose trace is empty."""
    rng = np.random.default_rng(200 + seed)
    w_tr = TRAIN_WS[seed % len(TRAIN_WS)] if seed != 1 else None
    wss, pms, bsss, tracess, caps = [], [], [], [], []
    for lane in range(4):
        n = int(rng.integers(1, 4))
        wss.append([INFER_WS[rng.integers(len(INFER_WS))] for _ in range(n)])
        pms.append(MODES[rng.integers(len(MODES))])
        bsss.append([int([1, 4, 16, 32][rng.integers(4)]) for _ in range(n)])
        duration = float(rng.uniform(5.0, 25.0))
        tracess.append([S.ArrivalTrace.poisson(
            0.0 if (lane == 0 and j == 0) or rng.random() < 0.15
            else float(rng.uniform(5.0, 60.0)),
            duration, seed=int(rng.integers(1000))) for j in range(n)])
        caps.append(None if rng.random() < 0.7 else int(rng.integers(0, 4)))
    ref = S.simulate_multi_tenant_batch(DEV, w_tr, wss, pms, bsss, tracess,
                                        tau_caps=caps, backend="numpy")
    got = S.simulate_multi_tenant_batch(DEV, w_tr, wss, pms, bsss, tracess,
                                        tau_caps=caps, backend="jax")
    assert tracess[0][0].times.size == 0           # an idle lane really ran
    for a, b in zip(ref, got):
        assert abs(a.train_minibatches - b.train_minibatches) <= 2
        assert len(a.streams) == len(b.streams)
        for ra, rb in zip(a.streams, b.streams):
            np.testing.assert_allclose(
                np.asarray(rb.latencies, np.float64),
                np.asarray(ra.latencies, np.float64), **TOL)


@needs_jax
def test_jax_multi_tenant_single_call_matches_numpy():
    ws = [INFER_WORKLOADS["mobilenet"], INFER_WORKLOADS["lstm"]]
    traces = [S.ArrivalTrace.poisson(30.0, 20.0, seed=1),
              S.ArrivalTrace.uniform(50.0, 20.0)]
    ref = S.simulate_multi_tenant(DEV, TRAIN_WORKLOADS["resnet18"], ws,
                                  SPACE.maxn(), [4, 16], traces)
    got = S.simulate_multi_tenant(DEV, TRAIN_WORKLOADS["resnet18"], ws,
                                  SPACE.maxn(), [4, 16], traces,
                                  backend="jax")
    assert abs(ref.train_minibatches - got.train_minibatches) <= 2
    for ra, rb in zip(ref.streams, got.streams):
        np.testing.assert_allclose(np.asarray(rb.latencies, np.float64),
                                   np.asarray(ra.latencies, np.float64),
                                   **TOL)


# ---------------------------------------------------------------------------
# backend selection + batched report builder
# ---------------------------------------------------------------------------

def test_backend_probe_reports_only_a_missing_module_as_absent(tmp_path,
                                                              monkeypatch):
    """A module that is not installed probes False; one that is installed
    but breaks while importing (an API that moved) raises its own error,
    not "requires jax"."""
    (tmp_path / "moved_api_mod.py").write_text(
        "from jax.experimental import no_such_name\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    assert B._installed("jax")
    assert not B._installed("no_such_module_anywhere")
    with pytest.raises(ImportError, match="no_such_name"):
        B._installed("moved_api_mod")
    jax, jnp, enable_x64 = B.require_jax()
    with enable_x64():
        assert jnp.ones(1).dtype == jnp.float64


def test_jax_backend_selection_defaults_to_numpy_when_unavailable(monkeypatch):
    """Regression: with jax absent the default path must degrade to the
    NumPy reference — env-var requests included — while an *explicit*
    backend='jax' argument raises."""
    monkeypatch.setattr(B, "_JAX_OK", False)
    monkeypatch.setenv(B.ENGINE_BACKEND_ENV, "jax")
    assert B.resolve_backend(None) == "numpy"
    monkeypatch.delenv(B.ENGINE_BACKEND_ENV)
    assert B.resolve_backend(None) == "numpy"
    with pytest.raises(RuntimeError, match="requires jax"):
        B.resolve_backend("jax")
    # the engine default still runs, on the reference backend
    trace = S.ArrivalTrace.uniform(20.0, 2.0)
    rep = S.simulate(DEV, None, INFER_WORKLOADS["lstm"], SPACE.maxn(), 4,
                     trace)
    ref = S.managed_scalar(DEV, None, INFER_WORKLOADS["lstm"], SPACE.maxn(),
                           4, trace)
    assert rep.latencies.tolist() == ref.latencies


def test_explicit_numpy_backend_wins_over_env_jax(monkeypatch):
    """Regression: backend='numpy' must run the reference engine even when
    FULCRUM_ENGINE_BACKEND=jax — the batch paths' per-lane delegation must
    not re-resolve the backend from the environment."""
    monkeypatch.setenv(B.ENGINE_BACKEND_ENV, "jax")
    monkeypatch.setitem(
        S._JAX_ENGINE_CACHE, "managed",
        lambda *a: pytest.fail("jax engine ran despite backend='numpy'"))
    w_in = INFER_WORKLOADS["mobilenet"]
    trace = S.ArrivalTrace.uniform(40.0, 5.0)
    S.simulate_batch(DEV, None, w_in, [SPACE.maxn()], [16], [trace],
                     backend="numpy")
    S.simulate_multi_tenant_batch(DEV, None, [[w_in]], [SPACE.maxn()],
                                  [[16]], [[trace]], backend="numpy")
    S.simulate(DEV, None, w_in, SPACE.maxn(), 16, trace, "managed",
               backend="numpy")


def test_backend_env_var_selects_jax(monkeypatch):
    if not B.jax_available():
        pytest.skip("jax unavailable")
    monkeypatch.setenv(B.ENGINE_BACKEND_ENV, "jax")
    assert B.resolve_backend(None) == "jax"
    with pytest.raises(ValueError, match="unknown backend"):
        B.resolve_backend("torch")


def test_batched_report_builder_matches_per_report_statistics():
    """The presorted quantile/violation caches must change nothing about
    the statistics themselves."""
    rng = np.random.default_rng(5)
    w_in = INFER_WORKLOADS["mobilenet"]
    pms = [MODES[int(rng.integers(len(MODES)))] for _ in range(4)]
    traces = [S.ArrivalTrace.poisson(float(rng.uniform(10, 60)), 15.0,
                                     seed=i) for i in range(4)]
    reps = S.simulate_batch(DEV, None, w_in, pms, [4, 16, 1, 32], traces)
    for rep in reps:
        assert rep._sorted is not None         # builder pre-filled the cache
        xs = np.asarray(rep.latencies, np.float64)
        for q in (0.01, 0.5, 0.75, 0.95, 1.0):
            fresh = S.ExecutionReport("managed", xs.tolist(), 0, 1.0, 0.0)
            assert rep.latency_quantile(q) == fresh.latency_quantile(q)
        for budget in (0.0, float(np.median(xs)) if xs.size else 0.5, 10.0):
            want = (float(np.count_nonzero(xs > budget)) / xs.size
                    if xs.size else 0.0)
            assert rep.violation_rate(budget) == want


# ---------------------------------------------------------------------------
# pallas backend tier: the hand-written kernels behind the same entry points
# ---------------------------------------------------------------------------

needs_pallas = pytest.mark.skipif(not B.pallas_available(),
                                  reason="pallas unavailable")


@needs_pallas
@pytest.mark.parametrize("seed", range(2))
def test_pallas_engine_matches_numpy_randomized(seed):
    rng = np.random.default_rng(300 + seed)
    w_tr = TRAIN_WS[seed % len(TRAIN_WS)] if seed % 2 == 0 else None
    w_in = INFER_WS[seed % len(INFER_WS)]
    pms, bss, traces, caps = [], [], [], []
    for _ in range(6):
        _, _, pm, bs, trace, cap = _random_config(rng)
        pms.append(pm), bss.append(bs), traces.append(trace), caps.append(cap)
    ref = S.simulate_batch(DEV, w_tr, w_in, pms, bss, traces,
                           tau_caps=caps, backend="numpy")
    got = S.simulate_batch(DEV, w_tr, w_in, pms, bss, traces,
                           tau_caps=caps, backend="pallas")
    for a, b in zip(ref, got):
        _assert_engine_close(a, b)
        # the pallas report builder sorts with the bitonic kernel: sorting
        # permutes values, so the cache must EQUAL sorting its own latencies
        assert b._sorted is not None
        np.testing.assert_array_equal(
            b._sorted, np.sort(np.asarray(b.latencies, np.float64)))


@needs_pallas
def test_pallas_single_simulate_matches_numpy():
    w_tr = TRAIN_WORKLOADS["mobilenet"]
    w_in = INFER_WORKLOADS["mobilenet"]
    trace = S.ArrivalTrace.poisson(60.0, 20.0, seed=7)
    ref = S.simulate(DEV, w_tr, w_in, SPACE.maxn(), 16, trace, "managed")
    got = S.simulate(DEV, w_tr, w_in, SPACE.maxn(), 16, trace, "managed",
                     backend="pallas")
    _assert_engine_close(ref, got)


@needs_pallas
def test_pallas_multi_tenant_matches_numpy():
    ws = [INFER_WORKLOADS["mobilenet"], INFER_WORKLOADS["lstm"]]
    traces = [S.ArrivalTrace.poisson(30.0, 15.0, seed=1),
              S.ArrivalTrace.uniform(50.0, 15.0)]
    ref = S.simulate_multi_tenant(DEV, TRAIN_WORKLOADS["resnet18"], ws,
                                  SPACE.maxn(), [4, 16], traces)
    got = S.simulate_multi_tenant(DEV, TRAIN_WORKLOADS["resnet18"], ws,
                                  SPACE.maxn(), [4, 16], traces,
                                  backend="pallas")
    assert abs(ref.train_minibatches - got.train_minibatches) <= 2
    for ra, rb in zip(ref.streams, got.streams):
        np.testing.assert_allclose(np.asarray(rb.latencies, np.float64),
                                   np.asarray(ra.latencies, np.float64),
                                   **TOL)


def test_env_pallas_degrades_down_tiers(monkeypatch):
    """An environment-level 'pallas' request degrades pallas -> jax -> numpy
    as capabilities vanish; an *explicit* backend='pallas' argument raises."""
    monkeypatch.setenv(B.ENGINE_BACKEND_ENV, "pallas")
    if B.pallas_available():
        assert B.resolve_backend(None) == "pallas"
    monkeypatch.setattr(B, "_PALLAS_OK", False)
    if B.jax_available():
        assert B.resolve_backend(None) == "jax"
    monkeypatch.setattr(B, "_JAX_OK", False)
    assert B.resolve_backend(None) == "numpy"
    with pytest.raises(RuntimeError, match="pallas"):
        B.resolve_backend("pallas")


def test_grid_solvers_reject_pallas_backend():
    """The 'pallas' tier is engine-only: the grid solvers must refuse it
    loudly instead of silently falling back to the NumPy branch."""
    from repro.core import grid_eval as G
    with pytest.raises(ValueError, match="unknown backend"):
        G.solve_train_batch([], {}, backend="pallas")
    with pytest.raises(ValueError, match="unknown backend"):
        G.solve_infer_batch([], {}, backend="pallas")
    with pytest.raises(ValueError, match="unknown backend"):
        G.solve_concurrent_batch([], {}, {}, backend="pallas")


# ---------------------------------------------------------------------------
# jit-cache stability: shape bucketing must keep retraces flat across calls
# ---------------------------------------------------------------------------

@needs_jax
def test_engine_trace_count_stable_within_shape_bucket():
    """Lane counts inside one power-of-two bucket (and identical padded
    event counts) must reuse the compiled scan — no per-call retracing."""
    w_in = INFER_WORKLOADS["mobilenet"]
    trace = S.ArrivalTrace.poisson(30.0, 4.0, seed=3)

    def batch(n):
        S.simulate_batch(DEV, None, w_in, [SPACE.maxn()] * n, [8] * n,
                         [trace] * n, backend="jax")

    batch(5)                           # compile (or reuse a prior test's)
    n0 = S.engine_trace_count()
    batch(5)                           # identical shapes
    batch(6)                           # same pow2 lane bucket (8)
    batch(3)                           # floor bucket is 8 as well
    assert S.engine_trace_count() == n0


@needs_pallas
def test_pallas_trace_count_stable_within_shape_bucket():
    w_in = INFER_WORKLOADS["lstm"]
    trace = S.ArrivalTrace.poisson(25.0, 4.0, seed=5)

    def batch(n):
        S.simulate_batch(DEV, None, w_in, [SPACE.maxn()] * n, [4] * n,
                         [trace] * n, backend="pallas")

    batch(4)
    n0 = S.engine_trace_count()
    batch(4)
    batch(7)
    assert S.engine_trace_count() == n0


# ---------------------------------------------------------------------------
# chunked report builder: chunking must be invisible (bitwise)
# ---------------------------------------------------------------------------

def test_presort_chunking_bitwise_identical(monkeypatch):
    """Force tiny sort chunks: the per-report sorted caches must be bitwise
    identical to one unchunked NumPy sort per report."""
    rng = np.random.default_rng(9)
    reports = []
    for _ in range(13):
        xs = rng.uniform(0.0, 3.0, int(rng.integers(0, 40))).tolist()
        reports.append(S.ExecutionReport("managed", xs, 0, 1.0, 0.0))
    want = [np.sort(np.asarray(r.latencies, np.float64)) for r in reports]
    monkeypatch.setattr(S, "_SORT_CHUNK_ELEMS", 64)
    S._presort_reports(reports)
    for rep, w in zip(reports, want):
        assert rep._sorted is not None
        np.testing.assert_array_equal(rep._sorted, w)


@needs_pallas
def test_presort_pallas_backend_equals_numpy_sort(monkeypatch):
    rng = np.random.default_rng(11)
    reports = [S.ExecutionReport(
        "managed", rng.uniform(0.0, 3.0, int(rng.integers(1, 30))).tolist(),
        0, 1.0, 0.0) for _ in range(9)]
    want = [np.sort(np.asarray(r.latencies, np.float64)) for r in reports]
    monkeypatch.setattr(S, "_SORT_CHUNK_ELEMS", 128)   # exercise chunk loop
    S._presort_reports(reports, backend="pallas")
    for rep, w in zip(reports, want):
        np.testing.assert_array_equal(rep._sorted, w)
