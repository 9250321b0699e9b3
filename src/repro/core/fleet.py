"""Fleet-scale serving (``Scenario.FLEET``): K devices as one batched program.

PR 7 scaled the *engine* to 10^5 lanes; this tier scales the whole serving
loop. A fleet is K heterogeneous edge devices (``fleet_device`` — the base
Orin model with deterministic per-device time/power multipliers) fed by one
aggregate arrival stream. Each window:

 1. **dispatch** — the window's aggregate arrivals are split across devices
    by deterministic weighted round-robin: each arrival, in time order, goes
    to the device minimizing ``(n_d + 1) / w_d`` (ties to the lowest index),
    where ``n_d`` counts this window's assignments so far. ``"capacity"``
    starts every window's counts at zero with ``w_d = 1 / time_scale_d``
    (faster devices take proportionally more); ``"least-backlog"`` seeds the
    counts with each device's carried backlog (join-the-shortest-queue
    flavor). The dispatched window keeps provenance: the merged trace's
    ``stream_ids`` are device indices, so ``ArrivalTrace.split`` recovers
    exactly the per-device traces that ran.
 2. **plan** — the K per-device closed-loop controller windows run the PR-5/6
    ladder (EWMA rate estimate, feedback-scaled budget, burst quantile,
    interval solve -> high-rate fallback -> estimate -> nominal-budget retry),
    but each rung is ONE ``grid_eval.solve_infer_fleet_batch`` call over the
    still-unsolved devices: every device's observation grid is the shared
    base grid scaled by its (time, power) multipliers, so the K problems
    stack into one masked-argmin array program per rung.
 3. **execute** — all solved devices run as one ``simulate_batch`` call
    (devices ARE lanes; PR 7's chunked max-plus dispatch does the rest),
    each with its own carried ``QueueState``; reports fold back into the
    per-device controller states.

Fleet-wide resource control rides on top of the same three passes (all
opt-in; with the knobs at their defaults every step below is skipped and the
loop is byte-identical to the PR-8 form — pinned by the fingerprint test in
``tests/test_fleet_admission.py``):

 * **global admission** (``ControllerConfig.admission``) — each solved
    device runs the PR-6 exact deadline-drop mask (``AdmissionPolicy.admit``
    over ``[carried pending, dispatched arrivals]`` with the *device's own*
    ``t_in``, so the admitted subsequence replays through that device's
    engine with zero nominal-budget violations by construction). ``"shed"``
    drops rejections; ``"defer"`` pushes them into a single fleet-level
    re-offer queue — at the next window start they re-enter the
    *dispatcher*, re-timestamped, and may land on any device, not the one
    they bounced off (``FleetControllerState.push_fleet_deferred`` /
    ``pop_fleet_deferred``, ``defer_cap`` overflow shed); ``"degrade-bs"``
    swaps a non-drainable device's plan for its max-service-rate plan
    (``problem.solve_infer_capacity``), trimming nothing.
 * **backlog migration** (``FleetSpec.migrate_backlog``) — between windows,
    every device's carried ``QueueState`` backlog is pooled and re-dispatched
    by the same capped key-merge as arrivals (``dispatch_arrivals`` with no
    seed counts == least-backlog equalization after pooling). A request that
    stays keeps its timestamp and replays bitwise; a request that moves is
    re-timestamped at the window start (re-submission semantics, the defer
    contract) so the receiving device's trace is still a valid nondecreasing
    replay. Device clocks never migrate — a busy device stays busy.
 * **shared power budget** (``FleetSpec.fleet_power_budget``) — one fleet
    cap allocated per window by water-filling (``problem.water_fill``) over
    the previous window's per-device ``attributed_power`` (the PR-8
    measurement side), floored so idle devices can re-enter and capped at
    the per-device ``power_budget``. The per-device grants thread into
    ``solve_infer_fleet_batch`` as its per-problem power-budget column.

Correctness contract (enforced by ``tests/test_fleet.py`` and
``tests/test_fleet_admission.py``): ``serve_fleet`` is **bitwise identical
on NumPy** (tolerance-identical on jax, like the engine itself) to
``serve_fleet_sequential`` — K independent single-device closed loops run
one after another over the same split traces — for every combination of
admission mode, migration, and shared budget. The cross-device decisions
(dispatch, deferral, migration, water-filling, admission masks) are shared
helper functions called identically by both drivers, so their floats cannot
diverge; the batched solver rungs replay the scalar solvers' float ops over
per-device scaled grids (``solve_infer_fleet_batch``'s contract), and the
batched engine's NumPy path runs the identical per-lane kernel — the fleet
tier adds speed, never drift.

The one remaining single-device refinement is mid-window re-entry
(``split_backlog``): configs requesting it are rejected rather than
silently ignored.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from repro import obs
from repro.core import problem as P
from repro.core.backend import resolve_backend
from repro.core.controller import (AdmissionPolicy, ControllerConfig,
                                   ControllerState, FleetControllerState)
from repro.core.device_model import (DeviceModel, PerturbedDeviceModel,
                                     WorkloadProfile, fleet_device)
from repro.core.fused_window import fused_fleet_window
from repro.core.grid_eval import materialize, solve_infer_fleet_batch
from repro.core.powermode import PowerModeSpace
from repro.core.simulate import (ArrivalTrace, ExecutionReport, QueueState,
                                 _presort_reports, simulate, simulate_batch)

_DISPATCHES = ("capacity", "least-backlog")


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """One fleet: how many devices, how they differ, how arrivals are
    dispatched, and which fleet-wide resource controls are on. Heterogeneity
    is sampled deterministically per (seed, index) via collision-free draws
    (``device_model._device_pert``), so a spec names the same fleet in every
    process. ``migrate_backlog`` and ``fleet_power_budget`` default off —
    the default spec reproduces the PR-8 K-isolated-loops behavior
    byte-for-byte."""
    n_devices: int
    seed: int = 0
    time_spread: float = 0.10     # per-device service-time spread (+-)
    power_spread: float = 0.05    # per-device power spread (+-)
    dispatch: str = "capacity"    # "capacity" | "least-backlog"
    migrate_backlog: bool = False  # re-dispatch carried backlog each window
    fleet_power_budget: Optional[float] = None   # shared cap, water-filled
    #   across devices per window (None = one per-device cap each)

    def __post_init__(self):
        if self.n_devices <= 0:
            raise ValueError("a fleet needs at least one device")
        if not 0.0 <= self.time_spread < 1.0 \
                or not 0.0 <= self.power_spread < 1.0:
            raise ValueError("spreads must be in [0, 1)")
        if self.dispatch not in _DISPATCHES:
            raise ValueError(f"unknown dispatch policy {self.dispatch!r}; "
                             f"use {_DISPATCHES}")
        if self.fleet_power_budget is not None \
                and self.fleet_power_budget <= 0.0:
            raise ValueError("fleet_power_budget must be positive (or None)")

    def devices(self) -> list[PerturbedDeviceModel]:
        return [fleet_device(d, self.seed, self.time_spread,
                             self.power_spread)
                for d in range(self.n_devices)]


@dataclasses.dataclass
class FleetWindowReport:
    """One fleet window: the per-device ``WindowReport``s (scheduler-shaped,
    index = device) plus the fleet-level dispatch and goodput account.
    ``trace`` is the dispatched aggregate window — ``trace.split(K)``
    recovers each device's arrivals (provenance round-trip). With admission
    ``"defer"`` the dispatched trace also carries the re-offered requests
    (re-timestamped at the window start), so ``len(trace)`` can exceed
    ``offered_requests`` — the window's own arrivals."""
    rate: float                       # aggregate announced rate
    devices: list                     # one WindowReport per device
    trace: ArrivalTrace               # merged; stream_ids = device indices
    dispatch_counts: np.ndarray       # arrivals dispatched per device
    offered_requests: int
    goodput: float                    # fleet-wide in-budget served / offered
    shed_requests: int = 0            # admission-rejected, dropped
    deferred_requests: int = 0        # admission-rejected, re-offered
    migrated_requests: int = 0        # backlog moved between devices
    power_budgets: Optional[np.ndarray] = None   # per-device water-filled
    #   grants (None unless FleetSpec.fleet_power_budget is set)

    @property
    def attributed_power(self) -> float:
        """Summed per-device attributed power (satellite of the per-tenant
        attribution account): each executed report's time-weighted share —
        idle devices attribute 0, so this is the fleet's busy power."""
        return float(sum(wr.report.attributed_power or 0.0
                         for wr in self.devices if wr.report is not None))


def dispatch_arrivals(times: np.ndarray, weights: np.ndarray,
                      counts0: Optional[np.ndarray] = None) -> np.ndarray:
    """Deterministic weighted round-robin dispatch: arrival k (time order)
    goes to the device minimizing ``(counts0_d + n_d + 1) / w_d`` over the
    running assignment counts ``n_d``, ties to the lowest device index.
    Returns the per-arrival device index vector.

    Implemented as a merge, not a loop: device d's j-th assignment has key
    ``(counts0_d + j + 1) / w_d`` — strictly increasing per device — and the
    greedy order is exactly the first N keys in (key, device) order. Each
    device can own at most ``(N + C + K) * w_d / W - counts0_d`` of the
    first N keys (the N-th smallest key is at most ``(N + C + K) / W``
    with ``C = sum(counts0)``, ``W = sum(w)``), so only ~N + O(K) candidate
    keys are materialized however large K * N is."""
    weights = np.asarray(weights, np.float64)
    K = weights.size
    n = int(np.asarray(times).size)
    if K <= 0:
        raise ValueError("dispatch needs at least one device")
    if np.any(weights <= 0.0):
        raise ValueError("dispatch weights must be positive")
    c0 = np.zeros(K, np.int64) if counts0 is None \
        else np.asarray(counts0, np.int64)
    if c0.size != K:
        raise ValueError("counts0 must align with the weights")
    if n == 0:
        return np.empty(0, np.int64)
    W = float(weights.sum())
    C = int(c0.sum())
    caps = np.ceil((n + C + K) * weights / W).astype(np.int64) - c0 + 2
    caps = np.clip(caps, 0, n)
    keys, devs = [], []
    for d in range(K):
        m = int(caps[d])
        if m <= 0:
            continue
        keys.append((c0[d] + 1.0 + np.arange(m)) / weights[d])
        devs.append(np.full(m, d, np.int64))
    keys = np.concatenate(keys)
    devs = np.concatenate(devs)
    order = np.argsort(keys, kind="stable")   # stable: device-major input,
    return devs[order[:n]]                    # equal keys -> lowest index


def split_window(agg: ArrivalTrace, sid: np.ndarray, n_devices: int,
                 ) -> tuple[ArrivalTrace, list[ArrivalTrace]]:
    """The dispatched forms of one aggregate window: the merged trace with
    device provenance, and the per-device traces (absolute times, so the
    carryover replay contract applies per device)."""
    merged = ArrivalTrace(agg.times, agg.duration, agg.kind,
                          np.asarray(sid, np.int64), int(n_devices))
    return merged, merged.split(n_devices)


def _check_fleet_features(spec: FleetSpec, cfg: ControllerConfig) -> None:
    """Per-feature capability checks (PR-8's blanket admission rejection is
    gone — shed / defer / degrade-bs are fleet-batched now)."""
    if cfg.split_backlog is not None:
        raise ValueError(
            "fleet serving batches whole controller windows; mid-window "
            "backlog splits (split_backlog) are a single-device refinement "
            "(serve them per device via Fulcrum.serve_dynamic)")
    if spec.migrate_backlog and not cfg.carry_backlog:
        raise ValueError(
            "backlog migration re-dispatches carried QueueState backlog "
            "between windows; it needs controller carry_backlog=True "
            "(or turn FleetSpec.migrate_backlog off)")


def _fleet_scales(spec: FleetSpec) -> tuple[list, np.ndarray, np.ndarray,
                                            np.ndarray, np.ndarray]:
    """(devices, time_scales, power_scales, weights, shares): dispatch
    weight = 1 / time_scale (a device's service capacity), announced-rate
    share = normalized weight."""
    devs = spec.devices()
    ts = np.array([d.time_scale for d in devs])
    ps = np.array([d.power_scale for d in devs])
    wts = 1.0 / ts
    return devs, ts, ps, wts, wts / wts.sum()


def _window_trace(rate: float, i: int, window_duration: float,
                  arrivals: str, seed: int) -> ArrivalTrace:
    t0 = i * window_duration
    win = (ArrivalTrace.uniform(rate, window_duration)
           if arrivals == "uniform"
           else ArrivalTrace.poisson(rate, window_duration, seed + i))
    return win.shifted(t0)


def _backlog_counts(states: Sequence[ControllerState],
                    cfg: ControllerConfig) -> np.ndarray:
    """Per-device carried-backlog counts (0 with carryover off) — both the
    ``carried_requests`` account and the ``"least-backlog"`` dispatch seed."""
    return np.array([len(st.carry)
                     if cfg.carry_backlog and st.carry is not None else 0
                     for st in states], np.int64)


def _dispatch_fleet_window(agg: ArrivalTrace, n_deferred: int, t0: float,
                           weights: np.ndarray,
                           counts0: Optional[np.ndarray], K: int):
    """One window's dispatch pass, deferred re-offers included: the
    ``n_deferred`` fleet-level re-offers are re-timestamped at the window
    start and prepended to the aggregate arrivals (they sort first — the
    defer contract says they re-enter at the start), then the whole vector
    is dispatched by the capped key-merge. Returns ``(merged, dtr, own_dtr,
    deferred_counts, counts)``: the provenance-tagged merged trace, the
    per-device traces that run, the per-device *own-arrival* traces (the
    window's arrivals minus re-offers — what estimators observe and what
    ``offered_requests`` counts), how many re-offers each device drew, and
    the full dispatch counts."""
    if n_deferred:
        eff = ArrivalTrace(
            np.concatenate([np.full(n_deferred, float(t0)), agg.times]),
            agg.duration, agg.kind)
    else:
        eff = agg
    sid = dispatch_arrivals(eff.times, weights, counts0)
    merged, dtr = split_window(eff, sid, K)
    counts = np.bincount(sid, minlength=K).astype(np.int64)
    def_counts = np.bincount(sid[:n_deferred], minlength=K).astype(np.int64)
    if n_deferred:
        own = ArrivalTrace(agg.times, agg.duration, agg.kind,
                           np.asarray(sid[n_deferred:], np.int64), K)
        own_dtr = own.split(K)
    else:
        own_dtr = dtr
    return merged, dtr, own_dtr, def_counts, counts


def _migrate_backlog(states: Sequence[ControllerState], weights: np.ndarray,
                     t0: float) -> int:
    """Between-window backlog migration: pool every device's carried pending
    requests (time order, home-device-major on ties) and re-dispatch the
    pool through the same capped key-merge as arrivals — with no seed
    counts, the greedy ``(j + 1) / w_d`` keys equalize the queues, i.e.
    least-backlog placement over the pooled backlog. A request that stays on
    its home device keeps its original timestamp (its replay is bitwise the
    no-migration one); a request that moves is re-timestamped at the window
    start ``t0`` — re-submission semantics, exactly the defer contract — so
    the receiving device's ``[pending, window arrivals]`` vector stays
    nondecreasing and replays exactly. Device clocks never move: a busy
    device stays busy until its own clock. Returns how many requests moved
    (0 leaves every ``QueueState`` untouched)."""
    pend, home = [], []
    for d, st in enumerate(states):
        if st.carry is not None and len(st.carry):
            pend.append(np.asarray(st.carry.pending, np.float64))
            home.append(np.full(len(st.carry), d, np.int64))
    if not pend:
        return 0
    times = np.concatenate(pend)
    homes = np.concatenate(home)
    order = np.argsort(times, kind="stable")
    times, homes = times[order], homes[order]
    sid = dispatch_arrivals(times, weights)
    moved = sid != homes
    n_moved = int(np.count_nonzero(moved))
    if n_moved == 0:
        return 0
    new_times = np.where(moved, float(t0), times)
    for d, st in enumerate(states):
        pend_d = np.sort(new_times[sid == d], kind="stable")
        if st.carry is None and pend_d.size == 0:
            continue
        clock = float(st.carry.clock) if st.carry is not None else float(t0)
        st.carry = QueueState(pend_d, clock)
    return n_moved


def _fleet_power_budgets(spec: FleetSpec, power_budget: float,
                         prev_attr: np.ndarray, K: int) -> np.ndarray:
    """Per-device power budgets for one window. Without a fleet budget,
    every device keeps the per-device cap. With one, the shared cap is
    water-filled (``problem.water_fill``) over demand = the previous
    window's per-device attributed power — the PR-8 measurement side —
    floored at ``fleet_budget / 4K`` (an idle device must keep enough budget
    to serve again, or a zero-demand fixed point would starve it forever)
    and capped at the per-device ``power_budget`` (a grant the device's own
    envelope cannot use is forfeited, never redistributed — keeps the grant
    sum <= the fleet budget)."""
    if spec.fleet_power_budget is None:
        return np.full(K, float(power_budget))
    total = float(spec.fleet_power_budget)
    demands = np.maximum(np.asarray(prev_attr, np.float64),
                         total / (4.0 * K))
    return np.minimum(P.water_fill(demands, total), float(power_budget))


def _attributed_by_device(device_reports: Sequence) -> np.ndarray:
    """The per-device attributed power of one executed window — next
    window's water-filling demand vector (0 for unserved devices)."""
    return np.array([(wr.report.attributed_power or 0.0)
                     if wr is not None and wr.report is not None else 0.0
                     for wr in device_reports], np.float64)


def _admit_fleet_device(adm: AdmissionPolicy, latency_budget: float, sol,
                        t_in: float, carry_in: QueueState,
                        trace: ArrivalTrace,
                        ) -> tuple[ArrivalTrace, QueueState, int]:
    """One device's admission pass, exactly the single-device
    ``_closed_loop_window`` sequence: the deadline-drop mask runs over
    ``[carried pending, dispatched arrivals]`` from the carried clock with
    the device's own ``t_in`` (the engine's own recurrence — the admitted
    subsequence replays with zero nominal-budget violations by
    construction). Returns the trimmed ``(trace, carry_in, n_rejected)``;
    untouched inputs when everything admits."""
    k0 = len(carry_in)
    all_times = np.concatenate([np.asarray(carry_in.pending, np.float64),
                                trace.times])
    mask = adm.admit(all_times, latency_budget, sol.bs, t_in,
                     carry_in.clock)
    if mask.all():
        return trace, carry_in, 0
    run_carry = QueueState(carry_in.pending[mask[:k0]], carry_in.clock)
    run_trace = ArrivalTrace(trace.times[mask[k0:]], trace.duration,
                             trace.kind)
    return run_trace, run_carry, int(np.count_nonzero(~mask))


def _degrade_fleet_plan(sol, est: float, n_waiting: int, duration: float,
                        power_budget: float, obs: dict):
    """The ``degrade-bs`` admission mode per device (the fleet form of the
    scheduler's ``_degrade_plan``): when the device's demand — carried
    backlog + re-offers dispatched to it + estimated arrivals — is not
    drainable under the committed plan, swap in its max-service-rate plan
    under its (possibly water-filled) power budget and accept the
    violations."""
    t_in = obs[(sol.pm, sol.bs)][0]
    if P.drainable(n_waiting, est, sol.bs, t_in, duration):
        return sol
    cand = P.solve_infer_capacity(float(power_budget), obs)
    if cand is None:
        return sol
    c_t = obs[(cand.pm, cand.bs)][0]
    return cand if cand.bs / c_t > sol.bs / t_in else sol


def _goodput(rep, latency_budget: float, offered: int) -> float:
    good = int(np.count_nonzero(
        np.asarray(rep.latencies, np.float64) <= latency_budget))
    return good / offered if offered else 1.0


def _fleet_report(rate, device_reports, merged, counts, latency_budget,
                  offered, shed, deferred, migrated,
                  power_budgets) -> FleetWindowReport:
    good = sum(int(np.count_nonzero(
        np.asarray(wr.report.latencies, np.float64) <= latency_budget))
        for wr in device_reports if wr.report is not None)
    return FleetWindowReport(float(rate), device_reports, merged,
                             counts, int(offered),
                             good / offered if offered else 1.0,
                             shed_requests=int(shed),
                             deferred_requests=int(deferred),
                             migrated_requests=int(migrated),
                             power_budgets=power_budgets)


def serve_fleet(w: WorkloadProfile, power_budget: float,
                latency_budget: float, rates: Sequence[float],
                spec: FleetSpec, window_duration: float = 30.0,
                arrivals: str = "uniform", seed: int = 0,
                backend: Optional[str] = None,
                controller: Optional[ControllerConfig] = None,
                space: Optional[PowerModeSpace] = None,
                fused: Optional[bool] = None,
                ) -> list[FleetWindowReport]:
    """Serve a dynamic aggregate trace on a K-device fleet, stepping all K
    per-device closed-loop windows as one batched program per window: one
    dispatch pass (deferred re-offers re-entering first), one batched solve
    per ladder rung (per-device water-filled power budgets when the spec
    sets a fleet cap), one admission pass over the solved lanes, one
    ``simulate_batch`` over the admitted traces. Bitwise-identical on NumPy
    to ``serve_fleet_sequential`` (the K independent scalar loops).

    ``fused=True`` (jax/pallas backends only) runs each window through the
    fused solve+simulate program instead — ONE compiled launch per window
    (``core.fused_window``), tolerance-identical to this per-rung path.
    The default (``None``/False) keeps the unfused loop, so the NumPy
    reference path stays byte-identical."""
    cfg = controller if controller is not None else ControllerConfig()
    _check_fleet_features(spec, cfg)
    adm = cfg.admission_policy()
    if fused:
        eng = resolve_backend(backend)
        if eng == "numpy":
            raise ValueError(
                "the fused fleet window is a jax program; request "
                "backend='jax' (or 'pallas'), or leave fused off for the "
                "NumPy reference path")
        if adm.mode == "degrade-bs":
            raise ValueError(
                "admission mode 'degrade-bs' re-plans on the host between "
                "solve and simulate (problem.solve_infer_capacity over the "
                "device dict); serve it unfused — the fused window supports "
                "admission none/shed/defer")
        return _serve_fleet_fused(w, power_budget, latency_budget, rates,
                                  spec, window_duration, arrivals, seed,
                                  cfg, adm, space)
    K = spec.n_devices
    devs, ts, ps, wts, shares = _fleet_scales(spec)
    grid = materialize(DeviceModel(), w, space or PowerModeSpace(),
                       P.INFER_BATCH_SIZES)
    eng_backend = resolve_backend(backend)
    sol_backend = "numpy" if eng_backend == "numpy" else "jax"
    state = FleetControllerState(cfg, K)
    obs_cache: dict[int, dict] = {}     # degrade-bs only: per-device grids
    base_obs: list = []                 # the shared base dict, converted at
    #   most once per serve_fleet call (not once per device)

    def device_obs(d: int) -> dict:
        if d not in obs_cache:
            if not base_obs:
                base_obs.append(grid.to_dict())
            obs_cache[d] = {k: (t * ts[d], p * ps[d])
                            for k, (t, p) in base_obs[0].items()}
        return obs_cache[d]

    prev_keys: list = [None] * K
    prev_attr = np.full(K, float(power_budget))
    out: list[FleetWindowReport] = []
    from repro.core.scheduler import WindowReport
    for i, rate in enumerate(rates):
        t0 = i * window_duration
        agg = _window_trace(float(rate), i, window_duration, arrivals, seed)
        n_mig = _migrate_backlog(state.devices, wts, t0) \
            if spec.migrate_backlog else 0
        n_def = state.pop_fleet_deferred() if adm.active else 0
        carried = _backlog_counts(state.devices, cfg)
        counts0 = carried if spec.dispatch == "least-backlog" else None
        merged, dtr, own_dtr, def_counts, counts = _dispatch_fleet_window(
            agg, n_def, t0, wts, counts0, K)
        announced = float(rate) * shares
        pbud = _fleet_power_budgets(spec, power_budget, prev_attr, K)
        # the PR-5 ladder, vectorized over the device axis: every rung is
        # one batched fleet solve over the still-unsolved devices
        hi = state.plan_rates(announced, t0, window_duration)
        est = state.plan_rates(announced, t0, window_duration,
                               margin=1.0, pressure=False)
        if cfg.burst_quantile > 0.0:
            hi = np.maximum(hi, [P.burst_rate(e, window_duration,
                                              cfg.burst_quantile)
                                 for e in est])
        bud = state.plan_budgets([latency_budget] * K)
        sols: list[Optional[P.Solution]] = [None] * K
        live = est > 0.0            # a zero estimate has no rate to plan at
        unsolved = np.ones(K, bool)

        def rung(mask, rates_lo, budgets, rate_his):
            sel = np.flatnonzero(mask)
            if not sel.size:
                return
            probs = [P.InferProblem(float(pbud[d]), float(budgets[d]),
                                    float(rates_lo[d])) for d in sel]
            res = solve_infer_fleet_batch(probs, rate_his[sel], grid,
                                          ts[sel], ps[sel],
                                          backend=sol_backend)
            for d, s in zip(sel, res):
                sols[d] = s
                unsolved[d] = s is None

        # 1. margin headroom: sustainable up to hi, budget held at est
        rung(live & (hi > est), est, bud, hi)
        # 2. dead zone: prefer the high end (see _closed_loop_window)
        rung(live & (hi > est) & unsolved, hi, bud, hi)
        # 3. the point plan at the estimate
        rung(live & unsolved, est, bud, est)
        # 4. feedback tightened into infeasibility: retry at nominal
        nominal = np.full(K, float(latency_budget))
        rung(live & unsolved & (bud < nominal), est, nominal, est)
        lanes = []              # (device, sol, switch_s, run_trace, carry)
        shed_d = np.zeros(K, np.int64)
        def_out_d = np.zeros(K, np.int64)
        for d in range(K):
            sol = sols[d]
            if sol is not None and adm.mode == "degrade-bs":
                sol = _degrade_fleet_plan(
                    sol, float(est[d]), int(carried[d] + def_counts[d]),
                    window_duration, float(pbud[d]), device_obs(d))
                sols[d] = sol
            if sol is None:
                if def_counts[d]:
                    # nothing serves here: re-defer this device's re-offers
                    shed_d[d] += state.push_fleet_deferred(
                        int(def_counts[d]))
                state.observe_unserved(d, own_dtr[d], window_duration)
                continue
            switch_s = state.mode_switch(d, sol.pm)
            carry_in = state.window_carry_in(d, t0, switch_s)
            run_trace, run_carry = dtr[d], carry_in
            if adm.trims:
                t_in = devs[d].time_power(w, sol.pm, sol.bs)[0]
                run_trace, run_carry, n_rej = _admit_fleet_device(
                    adm, latency_budget, sol, t_in, carry_in, dtr[d])
                if n_rej:
                    if adm.mode == "defer":
                        dropped = state.push_fleet_deferred(n_rej)
                        def_out_d[d] = n_rej - dropped
                        shed_d[d] = dropped
                    else:
                        shed_d[d] = n_rej
            lanes.append((d, sol, switch_s, run_trace, run_carry))
        reps = simulate_batch(
            DeviceModel(), None, w,
            [sol.pm for _, sol, _, _, _ in lanes],
            [sol.bs for _, sol, _, _, _ in lanes],
            [rt for _, _, _, rt, _ in lanes],
            tau_caps=[sol.tau_tr for _, sol, _, _, _ in lanes],
            backend=eng_backend,
            carry_ins=[rc for _, _, _, _, rc in lanes],
            devices=[devs[d] for d, _, _, _, _ in lanes])
        device_reports: list = [None] * K
        for (d, sol, switch_s, _, _), rep in zip(lanes, reps):
            offered = len(own_dtr[d])
            gp = _goodput(rep, latency_budget, offered)
            rep.goodput = gp
            rep.shed_requests = int(shed_d[d])
            rep.deferred_requests = int(def_out_d[d])
            state.observe(d, own_dtr[d], rep, latency_budget,
                          window_duration, rep.queue_state)
            key = (sol.pm, sol.bs, sol.tau_tr)
            device_reports[d] = WindowReport(
                float(announced[d]), sol, rep,
                estimated_rate=float(est[d]),
                replanned=key != prev_keys[d], mode_switch_s=switch_s,
                carried_requests=int(carried[d]),
                shed_requests=int(shed_d[d]),
                deferred_requests=int(def_out_d[d]), goodput=gp,
                offered_requests=offered)
            prev_keys[d] = key
        for d in range(K):
            if device_reports[d] is None:
                offered = len(own_dtr[d])
                device_reports[d] = WindowReport(
                    float(announced[d]), None, None,
                    estimated_rate=float(est[d]),
                    carried_requests=int(carried[d]),
                    shed_requests=int(shed_d[d]),
                    goodput=0.0 if offered else 1.0,
                    offered_requests=offered)
        out.append(_fleet_report(
            rate, device_reports, merged, counts, latency_budget,
            offered=len(agg), shed=int(shed_d.sum()),
            deferred=int(def_out_d.sum()), migrated=n_mig,
            power_budgets=pbud.copy()
            if spec.fleet_power_budget is not None else None))
        prev_attr = _attributed_by_device(device_reports)
    return out


def _serve_fleet_fused(w: WorkloadProfile, power_budget: float,
                       latency_budget: float, rates: Sequence[float],
                       spec: FleetSpec, window_duration: float,
                       arrivals: str, seed: int, cfg: ControllerConfig,
                       adm: AdmissionPolicy,
                       space: Optional[PowerModeSpace],
                       ) -> list[FleetWindowReport]:
    """The fused driver behind ``serve_fleet(fused=True)``: identical
    host-side bookkeeping (dispatch, deferral, migration, water-filling,
    controller states) to the unfused loop, but the per-window plan ladder,
    admission recurrence, and engine run as ONE compiled launch
    (``core.fused_window.fused_fleet_window``) instead of up to four solver
    rungs + a host admission pass + an engine launch. Reports are
    reconstructed from the fetched arrays with the same float ops
    ``simulate_batch`` would apply, so results match the unfused jax path
    within the associative-scan tolerance (the padded tree shape is the
    only difference) and the unfused NumPy reference within the ladder's
    documented jax tolerance."""
    K = spec.n_devices
    devs, ts, ps, wts, shares = _fleet_scales(spec)
    grid = materialize(DeviceModel(), w, space or PowerModeSpace(),
                       P.INFER_BATCH_SIZES)
    state = FleetControllerState(cfg, K)
    prev_keys: list = [None] * K
    prev_mode = np.full(K, -1, np.int32)    # committed mode ids; -1 = none
    prev_attr = np.full(K, float(power_budget))
    adm_budget = adm.headroom * float(latency_budget)
    out: list[FleetWindowReport] = []
    from repro.core.scheduler import WindowReport
    for i, rate in enumerate(rates):
        with obs.span("fleet.window", i=i):
            with obs.span("fleet.prepare"):
                t0 = i * window_duration
                agg = _window_trace(float(rate), i, window_duration,
                                    arrivals, seed)
                n_mig = _migrate_backlog(state.devices, wts, t0) \
                    if spec.migrate_backlog else 0
                n_def = state.pop_fleet_deferred() if adm.active else 0
                carried = _backlog_counts(state.devices, cfg)
                counts0 = carried if spec.dispatch == "least-backlog" else None
                merged, dtr, own_dtr, def_counts, counts = \
                    _dispatch_fleet_window(agg, n_def, t0, wts, counts0, K)
                announced = float(rate) * shares
                pbud = _fleet_power_budgets(spec, power_budget, prev_attr, K)
                hi = state.plan_rates(announced, t0, window_duration)
                est = state.plan_rates(announced, t0, window_duration,
                                       margin=1.0, pressure=False)
                if cfg.burst_quantile > 0.0:
                    hi = np.maximum(hi, [P.burst_rate(e, window_duration,
                                                      cfg.burst_quantile)
                                         for e in est])
                bud = state.plan_budgets([latency_budget] * K)
                nominal = np.full(K, float(latency_budget))
                live = est > 0.0
                # the engine-side carry-in, flattened: device d's effective
                # arrival vector [carried pending, dispatched arrivals] and
                # its pre-switch clock max(carried clock, t0) —
                # window_carry_in minus the switch cost, which the program
                # charges in-line from prev_mode
                eff: list[np.ndarray] = []
                n_carry = np.zeros(K, np.int64)
                clock0 = np.full(K, float(t0))
                for d in range(K):
                    st = state.devices[d]
                    if cfg.carry_backlog and st.carry is not None:
                        pend = np.asarray(st.carry.pending, np.float64)
                        clock0[d] = max(float(st.carry.clock), float(t0))
                        n_carry[d] = pend.size
                        eff.append(np.concatenate([pend, dtr[d].times])
                                   if pend.size else dtr[d].times)
                    else:
                        eff.append(dtr[d].times)
            res = fused_fleet_window(grid, ts, ps, pbud, bud, nominal, est, hi,
                                     live, prev_mode, eff, n_carry, clock0,
                                     float(cfg.mode_switch_s), adm_budget,
                                     adm.trims)
            with obs.span("fleet.report"):
                shed_d = np.zeros(K, np.int64)
                def_out_d = np.zeros(K, np.int64)
                sols: list = [None] * K
                switches = np.zeros(K)
                reps: list = [None] * K
                for d in range(K):
                    if not res["solved"][d]:
                        if def_counts[d]:
                            shed_d[d] += state.push_fleet_deferred(
                                int(def_counts[d]))
                        state.observe_unserved(d, own_dtr[d], window_duration)
                        continue
                    sel = int(res["sel"][d])
                    sol = P.Solution(pm=grid.modes[sel], bs=int(grid.bs[sel]),
                                     time=float(res["lam"][d]),
                                     power=float(res["power"][d]))
                    sols[d] = sol
                    # == res["switch"]
                    switches[d] = state.mode_switch(d, sol.pm)
                    n_rej = int(res["n_rej"][d])
                    if n_rej:
                        if adm.mode == "defer":
                            dropped = state.push_fleet_deferred(n_rej)
                            def_out_d[d] = n_rej - dropped
                            shed_d[d] = dropped
                        else:
                            shed_d[d] = n_rej
                    bs = sol.bs
                    n_adm = int(res["n_adm"][d])
                    nb = int(res["n_batches"][d])
                    ctv = np.asarray(res["adm_times"][d][:n_adm], np.float64)
                    if adm.trims and n_rej:
                        # rebuilt exactly as _admit_fleet_device does: the
                        # admitted window arrivals follow the admitted carry
                        # prefix
                        nca = int(n_carry[d]) - int(res["n_carry_rej"][d])
                        run_tr = ArrivalTrace(ctv[nca:].copy(),
                                              dtr[d].duration, dtr[d].kind)
                    else:
                        run_tr = dtr[d]
                    power = float(res["power"][d])
                    reps[d] = ExecutionReport(
                        "managed",
                        np.asarray(res["latencies"][d][:nb * bs],
                                   np.float64).copy(),
                        0, run_tr.duration, power, run_tr,
                        queue_state=QueueState(ctv[nb * bs:].copy(),
                                               float(res["clock_out"][d])),
                        attributed_power=power if nb else 0.0)
                    prev_mode[d] = int(res["mode_id"][d])
                _presort_reports([r for r in reps if r is not None])
                device_reports: list = [None] * K
                for d in range(K):
                    rep = reps[d]
                    offered = len(own_dtr[d])
                    if rep is None:
                        device_reports[d] = WindowReport(
                            float(announced[d]), None, None,
                            estimated_rate=float(est[d]),
                            carried_requests=int(carried[d]),
                            shed_requests=int(shed_d[d]),
                            goodput=0.0 if offered else 1.0,
                            offered_requests=offered)
                        continue
                    sol = sols[d]
                    gp = _goodput(rep, latency_budget, offered)
                    rep.goodput = gp
                    rep.shed_requests = int(shed_d[d])
                    rep.deferred_requests = int(def_out_d[d])
                    state.observe(d, own_dtr[d], rep, latency_budget,
                                  window_duration, rep.queue_state)
                    key = (sol.pm, sol.bs, sol.tau_tr)
                    device_reports[d] = WindowReport(
                        float(announced[d]), sol, rep,
                        estimated_rate=float(est[d]),
                        replanned=key != prev_keys[d],
                        mode_switch_s=float(switches[d]),
                        carried_requests=int(carried[d]),
                        shed_requests=int(shed_d[d]),
                        deferred_requests=int(def_out_d[d]), goodput=gp,
                        offered_requests=offered)
                    prev_keys[d] = key
                out.append(_fleet_report(
                    rate, device_reports, merged, counts, latency_budget,
                    offered=len(agg), shed=int(shed_d.sum()),
                    deferred=int(def_out_d.sum()), migrated=n_mig,
                    power_budgets=pbud.copy()
                    if spec.fleet_power_budget is not None else None))
                prev_attr = _attributed_by_device(device_reports)
    return out


def serve_fleet_sequential(w: WorkloadProfile, power_budget: float,
                           latency_budget: float, rates: Sequence[float],
                           spec: FleetSpec, window_duration: float = 30.0,
                           arrivals: str = "uniform", seed: int = 0,
                           backend: Optional[str] = None,
                           controller: Optional[ControllerConfig] = None,
                           space: Optional[PowerModeSpace] = None,
                           ) -> list[FleetWindowReport]:
    """The reference: the SAME fleet served as K independent single-device
    closed loops run sequentially — scalar solvers over each device's own
    observation dict, one single-lane engine call per device per window.
    The cross-device steps (dispatch, fleet deferral, migration,
    water-filling, admission) are the same shared helpers ``serve_fleet``
    calls, in the same device order, so the contract extends to every
    admission/migration/shared-budget combination: ``serve_fleet`` must
    match this bitwise on NumPy; benchmarks measure the batched speedup
    against it."""
    cfg = controller if controller is not None else ControllerConfig()
    _check_fleet_features(spec, cfg)
    adm = cfg.admission_policy()
    K = spec.n_devices
    devs, ts, ps, wts, shares = _fleet_scales(spec)
    base = materialize(DeviceModel(), w, space or PowerModeSpace(),
                       P.INFER_BATCH_SIZES).to_dict()
    # device d's observation dict: the base grid rescaled entrywise — the
    # same floats a per-device profile of PerturbedDeviceModel would yield
    obs = [{k: (t * ts[d], p * ps[d]) for k, (t, p) in base.items()}
           for d in range(K)]
    fstate = FleetControllerState(cfg, K)
    states = fstate.devices
    prev_keys: list = [None] * K
    prev_attr = np.full(K, float(power_budget))
    out: list[FleetWindowReport] = []
    from repro.core.scheduler import WindowReport
    for i, rate in enumerate(rates):
        t0 = i * window_duration
        agg = _window_trace(float(rate), i, window_duration, arrivals, seed)
        n_mig = _migrate_backlog(states, wts, t0) \
            if spec.migrate_backlog else 0
        n_def = fstate.pop_fleet_deferred() if adm.active else 0
        carried = _backlog_counts(states, cfg)
        counts0 = carried if spec.dispatch == "least-backlog" else None
        merged, dtr, own_dtr, def_counts, counts = _dispatch_fleet_window(
            agg, n_def, t0, wts, counts0, K)
        announced = float(rate) * shares
        pbud = _fleet_power_budgets(spec, power_budget, prev_attr, K)
        shed_d = np.zeros(K, np.int64)
        def_out_d = np.zeros(K, np.int64)
        device_reports: list = []
        for d in range(K):
            st = states[d]
            hi = st.plan_rates([announced[d]], t0, window_duration)[0]
            est = st.plan_rates([announced[d]], t0, window_duration,
                                margin=1.0, pressure=False)[0]
            if cfg.burst_quantile > 0.0:
                hi = max(hi, P.burst_rate(est, window_duration,
                                          cfg.burst_quantile))
            bud = st.plan_budgets([latency_budget])[0]
            pb = float(pbud[d])
            sol = None
            if est > 0.0:
                if hi > est:
                    sol = P.solve_infer_interval(
                        P.InferProblem(pb, bud, est), hi, obs[d])
                    if sol is None:
                        sol = P.solve_infer(
                            P.InferProblem(pb, bud, hi), obs[d])
                if sol is None:
                    sol = P.solve_infer(
                        P.InferProblem(pb, bud, est), obs[d])
                if sol is None and bud < latency_budget:
                    sol = P.solve_infer(
                        P.InferProblem(pb, float(latency_budget), est),
                        obs[d])
            if sol is not None and adm.mode == "degrade-bs":
                sol = _degrade_fleet_plan(
                    sol, float(est), int(carried[d] + def_counts[d]),
                    window_duration, pb, obs[d])
            offered = len(own_dtr[d])
            if sol is None:
                if def_counts[d]:
                    shed_d[d] += fstate.push_fleet_deferred(
                        int(def_counts[d]))
                st.observe_unserved([own_dtr[d]], window_duration)
                device_reports.append(WindowReport(
                    float(announced[d]), None, None,
                    estimated_rate=float(est),
                    carried_requests=int(carried[d]),
                    shed_requests=int(shed_d[d]),
                    goodput=0.0 if offered else 1.0,
                    offered_requests=offered))
                continue
            switch_s = st.mode_switch(sol.pm)
            carry_in = st.window_carry_in(t0, switch_s)
            run_trace, run_carry = dtr[d], carry_in
            if adm.trims:
                t_in = devs[d].time_power(w, sol.pm, sol.bs)[0]
                run_trace, run_carry, n_rej = _admit_fleet_device(
                    adm, latency_budget, sol, t_in, carry_in, dtr[d])
                if n_rej:
                    if adm.mode == "defer":
                        dropped = fstate.push_fleet_deferred(n_rej)
                        def_out_d[d] = n_rej - dropped
                        shed_d[d] = dropped
                    else:
                        shed_d[d] = n_rej
            rep = simulate(devs[d], None, w, sol.pm, sol.bs, run_trace,
                           "managed", tau_cap=sol.tau_tr, backend=backend,
                           carry_in=run_carry)
            gp = _goodput(rep, latency_budget, offered)
            rep.goodput = gp
            rep.shed_requests = int(shed_d[d])
            rep.deferred_requests = int(def_out_d[d])
            st.observe([own_dtr[d]], [rep], [latency_budget],
                       window_duration, rep.queue_state)
            key = (sol.pm, sol.bs, sol.tau_tr)
            device_reports.append(WindowReport(
                float(announced[d]), sol, rep, estimated_rate=float(est),
                replanned=key != prev_keys[d], mode_switch_s=switch_s,
                carried_requests=int(carried[d]),
                shed_requests=int(shed_d[d]),
                deferred_requests=int(def_out_d[d]), goodput=gp,
                offered_requests=offered))
            prev_keys[d] = key
        out.append(_fleet_report(
            rate, device_reports, merged, counts, latency_budget,
            offered=len(agg), shed=int(shed_d.sum()),
            deferred=int(def_out_d.sum()), migrated=n_mig,
            power_budgets=pbud.copy()
            if spec.fleet_power_budget is not None else None))
        prev_attr = _attributed_by_device(device_reports)
    return out
