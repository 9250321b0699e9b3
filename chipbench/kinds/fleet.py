"""A fleet of simulated edge devices served as one program per control
window: ``serve_fleet(fused=True, backend="jax")``, dispatch -> plan ->
admit -> simulate -> report, one compiled launch per window.

The timed call serves as many windows as the mix gives for the run's
seconds. ``correct`` replays a sample of those windows, drawn from the
seed, through the plain reference: the window's arrivals drawn again, the
dispatch rule, each device's plan (power mode and batch size) chosen again
by the planning ladder from its stated rate estimate, deadline-drop
admission and the batch queue, from the state the previous window left.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import traffic
from chipbench.checks import Readings
from chipbench.reference import jetson_fleet as ref

SAMPLE_WINDOWS = 6


def controller(mix: dict):
    from repro.core.controller import ControllerConfig
    return ControllerConfig(**mix["controller"])


def workload(config: dict):
    from repro.core.device_model import WorkloadProfile
    w = dict(config["workload"])
    return WorkloadProfile(kind="infer", **w)


class Runner:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 log=print):
        self.c, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.log = log

    def _serve(self, rates, seed):
        from repro.core import fleet as F
        c = self.c
        spec = F.FleetSpec(c["devices"], seed=c["fleet_seed"],
                           time_spread=c["time_spread"],
                           power_spread=c["power_spread"],
                           dispatch=c["dispatch"])
        return F.serve_fleet(workload(c), c["power_w"], c["latency_s"],
                             rates, spec, window_duration=c["window_s"],
                             arrivals="poisson", seed=seed, backend="jax",
                             controller=controller(self.mix), fused=True)

    def setup(self) -> None:
        K = self.c["devices"]
        self.rates = traffic.fleet_rates(self.mix, self.seconds, K)
        base = self.mix["rate_per_device"] * K
        # one window at each load the schedule's Poisson counts can reach:
        # every (device, arrival) bucket compiles here, not in the window
        self._serve([base * m for m in self.mix["warm_multipliers"]],
                    self.seed + 7919)

    def window(self) -> None:
        import jax
        from repro.core.backend import dispatch_count
        from repro.core.fused_window import fleet_trace_count
        d0, c0 = dispatch_count("fused"), fleet_trace_count()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve_fleet"):
            self.reports = self._serve(self.rates, self.seed)
        self.window_s = time.perf_counter() - t0
        self.launches = (dispatch_count("fused") - d0) / len(self.rates)
        self.retraces = fleet_trace_count() - c0

    def end_to_end(self) -> tuple[dict, int, int]:
        n = len(self.reports)
        offered = sum(w.offered_requests for w in self.reports)
        served = sum(len(d.report.latencies) for w in self.reports
                     for d in w.devices if d.report is not None)
        shed = sum(w.shed_requests for w in self.reports)
        self.log(f"fleet: {n} windows in {self.window_s:.3f} s; launches per "
                 f"window {self.launches}; fused programs traced in the "
                 f"window {self.retraces}; offered {offered}, served {served},"
                 f" shed {shed}")
        return ({"fleet_window_ms": 1e3 * self.window_s / n}, offered, shed)

    def layer_context(self) -> dict:
        return {"programs": {"fleet": "jit_window"},
                "windows": len(self.reports)}

    def free(self) -> None:
        pass

    def check(self, dtype=np.float64) -> Readings:
        vals = replay(self.c, self.mix, self.seed, self.rates, self.reports,
                      sample_windows(self.seed, len(self.rates)), dtype)
        return Readings(vals, dict(self.c["limits"]))


def sample_windows(seed: int, n: int) -> list[int]:
    rng = np.random.default_rng([seed, 11])
    return sorted(int(i) for i in rng.choice(n, min(SAMPLE_WINDOWS, n),
                                             replace=False))


def budget_scales(reports, windows, nominal: float, ctl: dict) -> dict:
    """Each device's feedback scale of the latency budget at the start of
    each sampled window, folded from the served windows before it."""
    K = len(reports[0].devices)
    scale, out = np.ones(K), {}
    for i in range(max(windows) + 1):
        if i in windows:
            out[i] = scale.copy()
        for d, wr in enumerate(reports[i].devices):
            if wr.report is not None:
                scale[d] = ref.feedback(scale[d], wr.report.latencies,
                                        nominal, ctl)
    return out


def replay(c: dict, mix: dict, seed: int, rates, reports, windows,
           dtype=np.float64) -> dict:
    """Replay the sampled windows; returns the numbers compared."""
    K, W = c["devices"], c["window_s"]
    w = dict(c["workload"])
    ts = np.array([ref.device_scale(c["fleet_seed"], d, "time",
                                    c["time_spread"]) for d in range(K)])
    ps = np.array([ref.device_scale(c["fleet_seed"], d, "power",
                                    c["power_spread"]) for d in range(K)])
    keys, t_grid, p_grid, bs_grid = ref.grid(w, c["power_modes"],
                                             c["batch_sizes"])
    ctl = mix["controller"]
    trims = ctl.get("admission", "none") in ("shed", "defer")
    nominal = c["latency_s"]
    budget = ctl.get("admission_headroom", 1.0) * nominal
    scales = budget_scales(reports, windows, nominal, ctl)
    out = {"arrival_gap_s": 0.0, "dispatch_errors": 0, "plan_errors": 0,
           "shed_errors": 0, "carry_errors": 0, "latency_gap_s": 0.0}
    checked = 0
    for i in windows:
        rep, prev = reports[i], reports[i - 1] if i else None
        t0 = i * W
        agg = traffic.poisson_window(rates[i], W, seed + i) + t0
        got = np.sort(rep.trace.times)
        if got.size != agg.size:
            out["arrival_gap_s"] = float("inf")
            continue
        out["arrival_gap_s"] = max(out["arrival_gap_s"],
                                   float(np.max(np.abs(got - agg), initial=0)))
        carry, clock, prev_pm = [], [], []
        for d in range(K):
            pr = prev.devices[d] if prev is not None else None
            if pr is not None and pr.report is not None:
                carry.append(np.asarray(pr.report.queue_state.pending))
                clock.append(float(pr.report.queue_state.clock))
                prev_pm.append(pr.solution.pm)
            else:
                carry.append(None if pr is not None else np.empty(0))
                clock.append(None)
                prev_pm.append(None)
        counts0 = np.array([len(x) if x is not None
                            else rep.devices[d].carried_requests
                            for d, x in enumerate(carry)])
        if c["dispatch"] == "least-backlog":
            sid = ref.dispatch(agg.size, 1.0 / ts, counts0)
        else:
            sid = ref.dispatch(agg.size, 1.0 / ts, np.zeros(K, np.int64))
        order = np.argsort(rep.trace.times, kind="stable")
        out["dispatch_errors"] += int(np.count_nonzero(
            np.asarray(rep.trace.stream_ids)[order] != sid))
        for d in range(K):
            wr = rep.devices[d]
            if carry[d] is None:     # after a window the device left unserved
                continue
            checked += 1
            est = float(wr.estimated_rate)
            hi = ref.high_rate(est, ctl, W, t0, len(carry[d]), clock[d])
            want = ref.plan(t_grid * ts[d], p_grid * ps[d], bs_grid,
                            c["power_w"], est, hi, nominal * scales[i][d],
                            nominal)
            sol = wr.solution
            have = None if sol is None else (
                tuple(getattr(sol.pm, k) for k in ("cores", "cpuf", "gpuf",
                                                   "memf")), int(sol.bs))
            if (want is None) != (have is None) \
                    or (want is not None and keys[want] != have):
                out["plan_errors"] += 1
            if wr.report is None or have is None:
                continue
            pm, bs = dict(zip(("cores", "cpuf", "gpuf", "memf"), have[0])), \
                have[1]
            t_base, _ = ref.time_power(w, pm, bs)
            switch = ctl["mode_switch_s"] if prev_pm[d] is not None \
                and prev_pm[d] != sol.pm else 0.0
            clk = max(clock[d], t0) if clock[d] is not None else t0
            eff = np.concatenate([carry[d], agg[sid == d]])
            r = ref.run_device(eff, bs, t_base * ts[d], clk + switch, budget,
                               trims, dtype)
            out["shed_errors"] += abs(int(np.count_nonzero(~r["admit"]))
                                      - int(wr.shed_requests))
            q = wr.report.queue_state
            lat = np.asarray(wr.report.latencies, np.float64)
            if lat.size != r["latencies"].size \
                    or len(q.pending) != r["carry"].size:
                out["carry_errors"] += 1
                continue
            if lat.size:
                out["latency_gap_s"] = max(out["latency_gap_s"], float(
                    np.max(np.abs(lat - r["latencies"].astype(np.float64)))))
    out["device_windows_checked"] = checked
    return out


def control(config: dict, mix: dict, seed: int, seconds: float,
            log=print) -> dict:
    """The control: the system's run of the cell replayed by the reference
    in float32 instead of float64."""
    r = Runner(config, mix, seed, seconds, log=log)
    r.setup()
    r.window()
    r.end_to_end()
    return {"control_f32": r.check(np.float32).values,
            "sound": r.check().values}
