"""Plain reference of one control window of a fleet of simulated Jetson
devices: the device model, the dispatch rule, deadline-drop admission and
the batch queue, written from their stated semantics in float64 (or, for
the control, float32) with Python loops.

Device model (the paper's calibrated Orin AGX, arXiv:2509.20205 Table 4):
a minibatch of ``bs`` takes ``t = t_gpu + t_cpu + t_mem``, each term
``(fixed + per_sample * bs) / speed`` with the resource's speed from the
power mode's frequencies, and a deterministic per-(workload, dimension,
value) perturbation of at most 5% from an MD5 hash; device ``d`` of a fleet
multiplies time and power by its own hashed factor within the spreads.

Planning (the closed-loop controller's ladder): each device picks, from
every (power mode, batch size) of the grid, the first of least peak latency
``(bs - 1) / rate + t`` among those within its power cap, sustainable at
the high rate (``t <= bs / rate_hi``) and within the latency budget. The
rungs, in order until one finds a plan: (estimate, budget, high rate) and
(high rate, budget, high rate) when the high rate exceeds the estimate;
(estimate, budget, estimate); and (estimate, nominal budget, estimate) when
feedback has cut the budget below nominal. The high rate is the margined
estimate, raised to drain the carried backlog within what the window has
left after the carried clock's overrun, and never under the window's
Poisson arrival-count quantile. Feedback scales the budget after each
served window: down by ``tighten`` x the severity of a violation, else back
toward 1 by ``relax``.

Dispatch: arrivals in time order each go to the device minimising
``(carried_d + assigned_d + 1) / w_d``, ``w_d = 1 / time_scale_d``, ties to
the lowest index.

Admission (deadline drop): requests join a forming batch; when it fills,
its completion ``max(clock, last arrival) + t_in`` is judged and the oldest
members whose wait would exceed the budget are dropped until the rest meet
it; the batch commits only when full. Execution: every full batch of the
admitted sequence runs in order from the device's clock; a trailing
partial batch carries to the next window.
"""
from __future__ import annotations

import hashlib
import heapq
import itertools
import math

import numpy as np

MAX = {"cpuf": 2201.0, "gpuf": 1300.0, "memf": 3199.0, "cores": 12.0}


def _pert(key: str, scale: float) -> float:
    h = hashlib.md5(key.encode()).digest()
    u = int.from_bytes(h[:4], "little") / 2 ** 32
    return 1.0 + scale * (2.0 * u - 1.0)


def device_scale(seed: int, index: int, field: str, spread: float) -> float:
    return _pert(f"fleet|{seed}|{index}|{field}", spread)


def time_power(w: dict, pm: dict, bs: int) -> tuple[float, float]:
    """Minibatch time (s) and power (W) of the base device."""
    n = w["name"]
    pert = lambda dim, v, s=0.05: _pert(f"{n}|{dim}|{v}", s)
    gpu_s = (pm["gpuf"] / MAX["gpuf"]) * pert("gpuf", pm["gpuf"])
    cores = min(pm["cores"], w["cpu_parallelism"]) / w["cpu_parallelism"]
    cpu_s = ((pm["cpuf"] / MAX["cpuf"]) ** 0.9) * (cores ** 0.7) \
        * pert("cpuf", pm["cpuf"]) * pert("cores", pm["cores"])
    mem_s = (pm["memf"] / MAX["memf"]) * pert("memf", pm["memf"])
    b = float(bs)
    t_gpu = (w["gpu_fixed"] + w["gpu_per_sample"] * b) / gpu_s
    t_cpu = (w["cpu_fixed"] + w["cpu_per_sample"] * b) / cpu_s
    t_mem = (w["mem_fixed"] + w["mem_per_sample"] * b) / mem_s
    t = t_gpu + t_cpu + t_mem
    util = b / (b + w["util_half_bs"])
    gpu_pow = (pm["gpuf"] / MAX["gpuf"]) ** 1.3
    cpu_pow = (pm["cores"] / MAX["cores"]) ** 0.8 \
        * (pm["cpuf"] / MAX["cpuf"]) ** 1.3
    p = (w["p_idle"]
         + w["p_gpu"] * (0.35 + 0.65 * util) * gpu_pow
         * (0.4 + 0.6 * t_gpu / t)
         + w["p_cpu"] * cpu_pow * (0.5 + 0.5 * t_cpu / t)
         + w["p_mem"] * (pm["memf"] / MAX["memf"]) ** 1.1
         * (0.5 + 0.5 * t_mem / t))
    p *= pert("power", pm["gpuf"] * 31 + pm["cpuf"] * 7 + pm["memf"], 0.015)
    return t, p


def grid(w: dict, modes: dict, batch_sizes) -> tuple:
    """Every (power mode, batch size) of the base device, mode-major in the
    order cores, cpuf, gpuf, memf, each ascending: the entries' keys and
    their times, powers and batch sizes as arrays."""
    dims = ("cores", "cpuf", "gpuf", "memf")
    keys, t, p = [], [], []
    for combo in itertools.product(*(sorted(modes[k]) for k in dims)):
        pm = dict(zip(dims, combo))
        for bs in batch_sizes:
            tt, pp = time_power(w, pm, bs)
            keys.append((combo, int(bs)))
            t.append(tt)
            p.append(pp)
    bs = np.array([float(b) for _, b in keys])
    return keys, np.array(t), np.array(p), bs


def poisson_quantile(mean: float, q: float) -> int:
    """Smallest k with P[N <= k] >= q for N ~ Poisson(mean)."""
    if mean <= 0.0:
        return 0
    if mean > 700.0:
        raise ValueError("the pmf underflows past a mean of 700")
    pk = math.exp(-mean)
    cdf, k = pk, 0
    while cdf < q:
        k += 1
        pk *= mean / k
        cdf += pk
    return k


def high_rate(est: float, ctl: dict, window: float, t0: float,
              pending: int, clock) -> float:
    """The rate a device's service is sized for: see the module's text.
    ``clock`` is the carried clock, None before the first served window."""
    hi = ctl["rate_margin"] * est
    if ctl.get("carry_backlog") and clock is not None:
        overrun = max(0.0, min(0.9 * window, clock - t0))
        hi = (hi * window + pending) / (window - overrun)
    q = ctl.get("burst_quantile", 0.0)
    if q > 0.0 and est > 0.0:
        hi = max(hi, est, poisson_quantile(est * window, q) / window)
    return hi


def feedback(scale: float, lat: np.ndarray, nominal: float,
             ctl: dict) -> float:
    """The budget's scale after a served window with these latencies."""
    if not ctl.get("feedback"):
        return scale
    lat = np.sort(np.asarray(lat, np.float64))
    n = lat.size
    vr = float(np.count_nonzero(lat > nominal)) / n if n else 0.0
    if vr > ctl["target_violation"]:
        k = min(n - 1, max(0, math.ceil(ctl["tail_quantile"] * n) - 1))
        over = float(lat[k]) / max(nominal, 1e-12) - 1.0
        sev = min(1.0, max(vr, min(1.0, max(0.0, over))))
        return max(ctl["min_budget_scale"],
                   scale * (1.0 - ctl["tighten"] * sev))
    return min(1.0, scale + ctl["relax"] * (1.0 - scale))


def select(t, p, bs, cap: float, rate: float, rate_hi: float,
           budget: float):
    """Index of the first entry of least peak latency at ``rate`` among
    those within ``cap``, sustainable at ``rate_hi`` and within ``budget``;
    None when there is none."""
    lam = (bs - 1.0) / rate + t
    ok = (p <= cap) & (t <= bs / max(rate_hi, rate)) & (lam <= budget)
    if not ok.any():
        return None
    return int(np.argmin(np.where(ok, lam, np.inf)))


def plan(t, p, bs, cap: float, est: float, hi: float, budget: float,
         nominal: float):
    """The ladder over one device's grid (times ``t``, powers ``p``)."""
    if est <= 0.0:
        return None
    rungs = [(est, budget, hi), (hi, budget, hi)] if hi > est else []
    rungs.append((est, budget, est))
    if budget < nominal:
        rungs.append((est, nominal, est))
    for rate, bud, rate_hi in rungs:
        i = select(t, p, bs, cap, rate, rate_hi, bud)
        if i is not None:
            return i
    return None


def dispatch(n: int, weights: np.ndarray, carried: np.ndarray) -> np.ndarray:
    """Device of each of ``n`` time-ordered arrivals."""
    heap = [((int(c) + 1) / float(w), d)
            for d, (w, c) in enumerate(zip(weights, carried))]
    heapq.heapify(heap)
    count = np.asarray(carried, np.int64).copy()
    out = np.empty(n, np.int64)
    for i in range(n):
        _, d = heapq.heappop(heap)
        out[i] = d
        count[d] += 1
        heapq.heappush(heap, ((int(count[d]) + 1) / float(weights[d]), d))
    return out


def run_device(times: np.ndarray, bs: int, t_in: float, clock: float,
               budget: float, trims: bool, dtype=np.float64) -> dict:
    """One device's window over its effective arrivals (carried pending
    first): admission when ``trims``, then the batch queue. Returns the
    admitted mask, the latencies of the served requests in order, the
    requests carried out and the clock after the last batch."""
    times = np.asarray(times, dtype)
    t_in, clock, budget = dtype(t_in), dtype(clock), dtype(budget)
    admit = np.ones(times.size, bool)
    if trims:
        c, batch = clock, []
        for i in range(times.size):
            batch.append(i)
            if len(batch) < bs:
                continue
            comp = max(c, times[i]) + t_in
            while batch and comp - times[batch[0]] > budget + dtype(1e-12):
                admit[batch.pop(0)] = False
            if len(batch) == bs:
                c, batch = comp, []
    adm = times[admit]
    nb = adm.size // bs
    lat = np.empty(nb * bs, dtype)
    c = clock
    for k in range(nb):
        c = max(c, adm[k * bs + bs - 1]) + t_in
        lat[k * bs:(k + 1) * bs] = c - adm[k * bs:(k + 1) * bs]
    return {"admit": admit, "latencies": lat, "carry": adm[nb * bs:],
            "clock": c}
