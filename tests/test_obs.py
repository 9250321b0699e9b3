"""The program's own instrumentation (``repro.obs``): counters always on;
spans recorded only inside a profiler session, on the trace's host plane,
with the fleet loop's and the interleaving runtime's spans carrying the
counts their metrics read."""
import gc
import glob
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro import obs
from repro.core import simulate as S
from repro.core.backend import dispatch_count, jax_available, record_dispatch

pytestmark = pytest.mark.skipif(not jax_available(), reason="needs jax")


class session:
    """A profiler session that records host events (no Python tracer),
    written to ``path``."""

    def __init__(self, path):
        self.path = str(path)

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.path, profiler_options=opts)
        obs.span("session.open")      # a span that sees the profiler on
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        obs.span("session.closed")    # and one that sees it off again


# -- counters ---------------------------------------------------------------

def test_dispatch_counts_keep_their_meaning():
    layers = ("solver", "engine", "fused")
    before = {k: dispatch_count(k) for k in layers}
    total = dispatch_count()
    assert total == sum(before.values())
    record_dispatch("engine")
    record_dispatch("fused")
    record_dispatch("fused")
    assert [dispatch_count(k) - before[k] for k in layers] == [0, 1, 2]
    assert dispatch_count() == total + 3
    assert dispatch_count("no-such-layer") == 0


def test_trace_counters_count_compilations_not_calls():
    from repro.core import grid_eval as G
    from repro.core.fused_window import fleet_trace_count
    assert G.solver_trace_count() == obs.counted("trace.solver")
    assert S.engine_trace_count() == obs.counted("trace.engine")
    assert fleet_trace_count() == obs.counted("trace.fleet")
    ready = [np.array([0.1, 0.2, 0.3, 0.9, 1.7])]
    execs = [np.full(5, 0.05)]
    n0, d0 = S.engine_trace_count(), dispatch_count("engine")
    args = (ready, execs, np.full(1, np.inf), np.full(1, np.inf),
            np.zeros(1))
    S._run_engine(*args)
    n1 = S.engine_trace_count()
    S._run_engine(*args)
    assert S.engine_trace_count() == n1 >= n0
    assert dispatch_count("engine") - d0 == 2


# -- spans off --------------------------------------------------------------

def test_off_records_nothing_and_builds_no_annotation(monkeypatch):
    built, before = [], obs.spans()
    monkeypatch.setattr(obs, "_annotation",
                        lambda name: built.append(name))
    with obs.span("fleet.window", i=0) as sp:
        assert not sp and sp is obs.OFF
        with obs.span("fleet.prepare") as inner:
            assert inner is obs.OFF
    assert built == []
    assert obs.spans() == before


def test_untraced_process_records_nothing_and_installs_no_gc_hook():
    """In a process never traced, a whole runtime run leaves no record
    and no collection hook."""
    code = textwrap.dedent("""
        import gc
        from repro import obs
        from repro.core.simulate import ArrivalTrace
        from repro.runtime.clock import FakeClock
        from repro.runtime.interleave_runtime import (
            InterleaveConfig, ManagedInterleaveRuntime)

        class Step:
            def __init__(self, clock, dt):
                self.clock, self.dt = clock, dt
            def train_minibatch_time(self):
                return self.dt
            def step_minibatch(self):
                self.clock.advance(self.dt)
            def infer(self):
                self.clock.advance(self.dt)

        clock = FakeClock()
        rt = ManagedInterleaveRuntime(
            Step(clock, 0.3), Step(clock, 0.05),
            InterleaveConfig(arrival_rate=8.0, infer_bs=4,
                             latency_budget=1.0),
            trace=ArrivalTrace.poisson(8.0, 5.0, seed=1), clock=clock)
        rt.run()
        gc.collect()
        assert obs.spans() == [], obs.spans()
        assert obs._on_gc not in gc.callbacks
        print("ok")
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "ok"


# -- spans on ---------------------------------------------------------------

def test_spans_nest_under_a_session_and_clear_at_the_next(tmp_path):
    with session(tmp_path / "a"):
        with obs.span("outer", i=3) as a:
            assert a and a.attrs == {"i": 3}
            with obs.span("inner") as b:
                b.attrs["events"] = 7
            with obs.span("inner"):
                pass
    got = [(s.name, s.parent.name if s.parent else None)
           for s in obs.spans() if s.name != "host.gc"]
    assert got == [("outer", None), ("inner", "outer"), ("inner", "outer")]
    outer, inner = obs.spans("outer")[0], obs.spans("inner")[0]
    assert inner.attrs == {"events": 7}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    # the records outlive the session, until the next one records
    assert len(obs.spans("inner")) == 2
    with session(tmp_path / "b"):
        with obs.span("other"):
            pass
    assert [s.name for s in obs.spans() if s.name != "host.gc"] == \
        ["other"]


def test_spans_land_on_the_trace_host_plane(tmp_path):
    from jax.profiler import ProfileData
    with session(tmp_path):
        with obs.span("fleet.window"):
            with obs.span("fleet.prepare"):
                time.sleep(0.004)
            with obs.span("fleet.report"):
                gc.collect()
                time.sleep(0.002)
        with obs.span("fleet.window"):
            time.sleep(0.003)
    rec = obs.spans()
    assert any(g.attrs == {"generation": 2} and g.parent.name ==
               "fleet.report" for g in obs.spans("host.gc"))
    names = {s.name for s in rec}
    pd = ProfileData.from_file(glob.glob(f"{tmp_path}/**/*.xplane.pb",
                                         recursive=True)[0])
    host = sorted(((e.start_ns, e.name, e.duration_ns)
                   for plane in pd.planes if plane.name.startswith("/host:")
                   for ln in plane.lines for e in ln.events
                   if e.name in names), key=lambda x: x[0])
    assert [n for _, n, _ in host] == [s.name for s in rec]
    for (_, _, dur), s in zip(host, rec):
        assert abs(dur - (s.end_ns - s.start_ns)) < 1e6


# -- where the program records them ------------------------------------------

def test_fused_fleet_windows_are_spanned_with_their_event_fill(tmp_path):
    from repro.core import fleet as F
    from repro.core.controller import ControllerConfig
    from repro.core.device_model import INFER_WORKLOADS
    from repro.core.simulate import _pow2
    spec = F.FleetSpec(8, seed=3)
    cfg = ControllerConfig(rate_estimator="ewma", carry_backlog=True,
                           admission="shed", burst_quantile=0.95)
    kw = dict(window_duration=1.0, arrivals="poisson", seed=17,
              backend="jax", controller=cfg, fused=True)
    rates = [800.0, 1200.0, 600.0]       # bs 4, with requests carried
    w = INFER_WORKLOADS["mobilenet"]
    F.serve_fleet(w, 30.0, 0.1, rates, spec, **kw)        # compiled here
    with session(tmp_path):
        reports = F.serve_fleet(w, 30.0, 0.1, rates, spec, **kw)
    wins = obs.spans("fleet.window")
    assert [s.attrs["i"] for s in wins] == [0, 1, 2]
    kids = {}
    for s in obs.spans():
        if s.parent is not None and s.name != "host.gc":
            kids.setdefault(id(s.parent), []).append(s.name)
    for win in wins:
        assert win.parent is None
        assert kids[id(win)] == ["fleet.prepare", "fleet.fused",
                                 "fleet.report"]
    fused = obs.spans("fleet.fused")
    for f in fused:
        assert kids[id(f)] == ["fleet.pack", "fleet.launch", "fleet.fetch"]
    assert any(d.carried_requests for rep in reports for d in rep.devices)
    for f, rep in zip(fused, reports):
        eff = [int(d.carried_requests) + int(c)
               for d, c in zip(rep.devices, rep.dispatch_counts)]
        assert f.attrs["events"] == sum(eff)
        assert f.attrs["k_pad"] == _pow2(8)
        assert f.attrs["t_pad"] == _pow2(max(eff))
        assert f.attrs["slots"] == f.attrs["k_pad"] * f.attrs["t_pad"]


class _Step:
    """Training or serving stub: advances the fake clock by its time."""

    def __init__(self, clock, dt):
        self.clock, self.dt = clock, dt

    def train_minibatch_time(self):
        return self.dt

    def step_minibatch(self):
        self.clock.advance(self.dt)

    def infer(self):
        self.clock.advance(self.dt)


def _engine_waits_and_lags(arrivals, bs, t_tr, t_in):
    """The engine's scalar replay (``managed_scalar``'s arithmetic): per
    batch, the slack left after the training that fits, and the start's
    lateness past the batch's ready time."""
    waits, lags, now = [], [], 0.0
    for i in range(0, len(arrivals) - bs + 1, bs):
        ready = arrivals[i + bs - 1]
        while now + t_tr <= ready:
            now += t_tr
        start = max(now, ready)
        waits.append(start - now)
        lags.append(start - ready)
        now = start + t_in
    return waits, lags


@pytest.mark.parametrize("rate", [6.0, 40.0])
def test_runtime_wait_and_lag_equal_the_engine_replay(tmp_path, rate):
    from repro.runtime.clock import FakeClock
    from repro.runtime.interleave_runtime import (InterleaveConfig,
                                                  ManagedInterleaveRuntime)
    t_tr, t_in, bs = 0.31, 0.09, 4
    trace = S.ArrivalTrace.poisson(rate, 20.0, seed=5)
    clock = FakeClock()
    rt = ManagedInterleaveRuntime(
        _Step(clock, t_tr), _Step(clock, t_in),
        InterleaveConfig(arrival_rate=rate, infer_bs=bs,
                         latency_budget=1.0), trace=trace, clock=clock)
    with session(tmp_path):
        rt.run()
    waits, lags = _engine_waits_and_lags(trace.times.tolist(), bs, t_tr,
                                         t_in)
    run, = obs.spans("runtime.run")
    assert run.attrs == {"planned_step_s": t_tr}
    serve = obs.spans("runtime.serve")
    assert [s.attrs["wait_s"] for s in obs.spans("runtime.wait")] == waits
    assert [s.attrs["lag_s"] for s in serve] == lags
    assert [s.attrs["k"] for s in serve] == list(range(len(lags)))
    assert all(s.parent is run for s in serve)
    assert any(w > 0 for w in waits)
    if rate > 20:
        assert any(x > 0 for x in lags)
