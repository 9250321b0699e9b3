"""Vectorized grid-evaluation engine (the 273k-config sweep substrate).

The paper's evaluation solves every (power budget, latency budget, arrival
rate) triple against the observed 441-mode x 5-batch-size profile grid. The
scalar reference (`problem.solve_*`) re-scans all observations per problem in
pure Python, and `DeviceModel.time_power` re-hashes its deterministic
perturbations on every call; at paper scale that is hours of interpreter time.

This module replaces both hot paths with dense array programs:

 * ``materialize`` builds the device model as dense ``(cores, cpuf, gpuf,
   memf[, bs])`` time/power tensors per workload — perturbations are computed
   once per axis value (and once per mode for the power term), never in the
   evaluation loop;
 * ``ObservationGrid`` is a flat columnar view of an observation set (dense
   grid or any ``{pm: (t, p)}`` / ``{(pm, bs): (t, p)}`` dict, e.g. a partial
   RND sample or an NN-predicted surface);
 * ``solve_train_batch`` / ``solve_infer_batch`` / ``solve_concurrent_batch``
   solve a whole *batch* of problem configurations as masked argmin/argmax
   reductions, chunked to bound memory, with a NumPy baseline and an optional
   ``backend="jax"`` path (jit + vmap over the problem axis) that runs the
   reduction on-accelerator.

Exactness contract: the NumPy path is **bitwise identical** to the scalar
reference — it is the authoritative backend this module is judged against.
The tensors replay the exact IEEE-754 expression tree of
``DeviceModel.time_power`` elementwise, flattening in observation-dict
iteration order, and the reductions reproduce the scalar loops'
first-strict-improvement rule (NumPy's argmin/argmax return the first
occurrence of the extremum). ``tests/test_grid_eval.py`` enforces this
against randomized grids and the full 441 x 5 sweep. The jax backend runs
the same reductions under ``enable_x64`` (masked argmin/argmax are
reassociation-free, so it stays bitwise-equal too — unlike the execution
engine's scan, see ``docs/exactness.md``). Backend names are validated by
the shared ``core.backend`` plumbing, also used by ``core.simulate`` — the
solvers accept only the "numpy"/"jax" tiers (the "pallas" tier is an
execution-engine backend; there is no Pallas solver kernel, so asking for
it here is a ``ValueError`` rather than a silent NumPy fallback). Ragged
final problem chunks are padded to power-of-two row buckets before hitting
the jit kernels, so sweeping many batch sizes reuses a handful of
compilations — ``solver_trace_count()`` exposes the retrace counter.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np

from repro import obs
from repro.core import problem as P
from repro.core.backend import (check_backend, record_dispatch, require_jax,
                                with_program)
from repro.core.device_model import (MAX_CORES, MAX_CPUF, MAX_GPUF, MAX_MEMF,
                                     DeviceModel, WorkloadProfile, _pert)
from repro.core.powermode import PowerMode, PowerModeSpace

# Cap on problems x observations elements held per solver chunk. Each chunk
# materializes a handful of float64 (K, N) temporaries, so 4M elements keeps
# peak memory in the low hundreds of MB even for the concurrent solver.
CHUNK_ELEMS = 4 << 20


# ---------------------------------------------------------------------------
# columnar observation sets
# ---------------------------------------------------------------------------

class ObservationGrid:
    """Flat columnar view of an observation set, in iteration order.

    ``bs`` is None for training-style grids ({pm: (t, p)}) and an int array
    for inference-style grids ({(pm, bs): (t, p)}).
    """

    def __init__(self, modes: list, t: np.ndarray, p: np.ndarray,
                 bs: Optional[np.ndarray] = None):
        self.modes = modes
        self.t = np.ascontiguousarray(t, dtype=np.float64)
        self.p = np.ascontiguousarray(p, dtype=np.float64)
        self.bs = None if bs is None else np.ascontiguousarray(bs, np.int64)
        self._index: Optional[dict] = None
        self._stairs: dict = {}     # memoized Pareto staircases (per rate)

    def __len__(self) -> int:
        return len(self.modes)

    def key(self, i: int):
        if self.bs is None:
            return self.modes[i]
        return (self.modes[i], int(self.bs[i]))

    @property
    def index(self) -> dict:
        """{key: flat position}; first occurrence wins on duplicates."""
        if self._index is None:
            idx: dict = {}
            for i in range(len(self.modes)):
                idx.setdefault(self.key(i), i)
            self._index = idx
        return self._index

    def lookup(self, pm: PowerMode, bs: Optional[int] = None) -> tuple[float, float]:
        i = self.index[pm if self.bs is None else (pm, bs)]
        return float(self.t[i]), float(self.p[i])

    def to_dict(self) -> dict:
        return {self.key(i): (float(self.t[i]), float(self.p[i]))
                for i in range(len(self.modes))}

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_train_dict(cls, obs: dict) -> "ObservationGrid":
        modes = list(obs)
        t = np.fromiter((obs[k][0] for k in modes), np.float64, len(modes))
        p = np.fromiter((obs[k][1] for k in modes), np.float64, len(modes))
        return cls(modes, t, p)

    @classmethod
    def from_infer_dict(cls, obs: dict) -> "ObservationGrid":
        keys = list(obs)
        modes = [pm for pm, _ in keys]
        bs = np.fromiter((b for _, b in keys), np.int64, len(keys))
        t = np.fromiter((obs[k][0] for k in keys), np.float64, len(keys))
        p = np.fromiter((obs[k][1] for k in keys), np.float64, len(keys))
        return cls(modes, t, p, bs)


def as_train_grid(obs: Union[dict, ObservationGrid]) -> ObservationGrid:
    return obs if isinstance(obs, ObservationGrid) else \
        ObservationGrid.from_train_dict(obs)


def cached_grid(owner, attr: str, obs: dict, kind: str) -> ObservationGrid:
    """Memoize the columnar view of ``obs`` on ``owner.<attr>`` so repeated
    queries against a fitted strategy reuse the flattening and the grid's
    staircase memos. Invalidated when the observation count changes —
    sufficient for profiler-backed strategies, whose caches only grow; a
    strategy that *replaces* observations wholesale (the NN baselines'
    predicted surfaces) must also reset ``owner.<attr>`` to None on refit."""
    cache = getattr(owner, attr, None)
    if cache is None or cache[0] != len(obs):
        grid = (ObservationGrid.from_train_dict(obs) if kind == "train"
                else ObservationGrid.from_infer_dict(obs))
        cache = (len(obs), grid)
        setattr(owner, attr, cache)
    return cache[1]


def as_infer_grid(obs: Union[dict, ObservationGrid]) -> ObservationGrid:
    return obs if isinstance(obs, ObservationGrid) else \
        ObservationGrid.from_infer_dict(obs)


# ---------------------------------------------------------------------------
# dense device-model tensors
# ---------------------------------------------------------------------------

def _axis_pert(name: str, dim: str, values: Sequence[int],
               scale: float = 0.05) -> np.ndarray:
    return np.array([_pert(name, dim, v, scale) for v in values])


def _dense_closed_form(w: WorkloadProfile, space: PowerModeSpace,
                       bs_eff: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Replay DeviceModel.time_power's expression tree over the full
    (cores, cpuf, gpuf, memf, bs) grid. Elementwise ops on float64 are the
    same IEEE-754 operations the scalar path performs, so the result is
    bitwise identical per grid point."""
    cores_i = space.values["cores"]
    cpuf_i = space.values["cpuf"]
    gpuf_i = space.values["gpuf"]
    memf_i = space.values["memf"]
    gpuf = np.asarray(gpuf_i, np.float64)[None, None, :, None]
    memf = np.asarray(memf_i, np.float64)[None, None, None, :]

    pert_gpuf = _axis_pert(w.name, "gpuf", gpuf_i)[None, None, :, None]
    pert_cpuf = _axis_pert(w.name, "cpuf", cpuf_i)[None, :, None, None]
    pert_cores = _axis_pert(w.name, "cores", cores_i)[:, None, None, None]
    pert_memf = _axis_pert(w.name, "memf", memf_i)[None, None, None, :]
    # power perturbation keys mix (gpuf, cpuf, memf): one hash per combination
    pert_power = np.empty((1, len(cpuf_i), len(gpuf_i), len(memf_i)))
    for j, cf in enumerate(cpuf_i):
        for k, gf in enumerate(gpuf_i):
            for m, mf in enumerate(memf_i):
                pert_power[0, j, k, m] = _pert(
                    w.name, "power", gf * 31 + cf * 7 + mf, 0.015)

    # pow() per axis value with Python scalar math: NumPy's SIMD pow can
    # differ from libm by 1 ulp, which would break bitwise identity with the
    # scalar path. The remaining +,*,/ are correctly rounded either way.
    cpuf_pow = np.array([(v / MAX_CPUF) ** 0.9 for v in cpuf_i])[None, :, None, None]
    cores_pow = np.array([(min(c, w.cpu_parallelism) / w.cpu_parallelism) ** 0.7
                          for c in cores_i])[:, None, None, None]
    gpu_s = (gpuf / MAX_GPUF) * pert_gpuf
    cpu_s = cpuf_pow * cores_pow * pert_cpuf * pert_cores
    mem_s = (memf / MAX_MEMF) * pert_memf

    # trailing bs axis
    t_gpu = (w.gpu_fixed + w.gpu_per_sample * bs_eff) / gpu_s[..., None]
    t_cpu = (w.cpu_fixed + w.cpu_per_sample * bs_eff) / cpu_s[..., None]
    t_mem = (w.mem_fixed + w.mem_per_sample * bs_eff) / mem_s[..., None]
    t = t_gpu + t_cpu + t_mem

    util = bs_eff / (bs_eff + w.util_half_bs)
    f_gpu, f_cpu, f_mem = t_gpu / t, t_cpu / t, t_mem / t
    f_gpu_power = np.array([(v / MAX_GPUF) ** 1.3
                            for v in gpuf_i])[None, None, :, None]
    f_cpu_power = (np.array([(c / MAX_CORES) ** 0.8
                             for c in cores_i])[:, None, None, None]
                   * np.array([(v / MAX_CPUF) ** 1.3
                               for v in cpuf_i])[None, :, None, None])
    mem_power = np.array([(v / MAX_MEMF) ** 1.1
                          for v in memf_i])[None, None, None, :]
    p = (w.p_idle
         + w.p_gpu * (0.35 + 0.65 * util) * f_gpu_power[..., None] * (0.4 + 0.6 * f_gpu)
         + w.p_cpu * f_cpu_power[..., None] * (0.5 + 0.5 * f_cpu)
         + w.p_mem * mem_power[..., None] * (0.5 + 0.5 * f_mem))
    p = p * pert_power[..., None]
    return t, p


def materialize(device: DeviceModel, w: WorkloadProfile, space: PowerModeSpace,
                batch_sizes: Optional[Sequence[int]] = None) -> ObservationGrid:
    """Dense ground-truth grid for one workload: every mode in ``space``
    (x every batch size, for inference grids). Flattening follows
    ``space.all_modes()`` mode-major / bs-minor order — exactly the insertion
    order of the scalar oracle's observation dicts."""
    modes = space.all_modes()
    if type(device) is DeviceModel and isinstance(modes[0], PowerMode):
        if batch_sizes is None:
            bs_eff = np.array([float(w.train_bs)])
        else:
            bs_eff = np.array([float(b) for b in batch_sizes])
        t, p = _dense_closed_form(w, space, bs_eff)
        t = t.reshape(len(modes), -1)
        p = p.reshape(len(modes), -1)
    else:
        # exotic device model (subclass / TPU adapter): fall back to one
        # scalar call per grid point — still a one-off, amortized over every
        # problem configuration solved against the grid.
        bss = [None] if batch_sizes is None else list(batch_sizes)
        t = np.empty((len(modes), len(bss)))
        p = np.empty((len(modes), len(bss)))
        for i, pm in enumerate(modes):
            for j, b in enumerate(bss):
                t[i, j], p[i, j] = device.time_power(w, pm, b)
    if batch_sizes is None:
        return ObservationGrid(modes, t[:, 0], p[:, 0])
    B = t.shape[1]
    flat_modes = [pm for pm in modes for _ in range(B)]
    bs = np.tile(np.asarray(batch_sizes, np.int64), len(modes))
    return ObservationGrid(flat_modes, t.reshape(-1), p.reshape(-1), bs)


# ---------------------------------------------------------------------------
# batched solvers (NumPy baseline)
# ---------------------------------------------------------------------------

def _chunks(n_problems: int, n_obs: int):
    step = max(1, CHUNK_ELEMS // max(n_obs, 1))
    for s in range(0, n_problems, step):
        yield s, min(n_problems, s + step)


def _pad_problems(*arrs: np.ndarray) -> tuple[np.ndarray, ...]:
    """Pad problem-axis arrays to a power-of-two row count (floor 8) by
    repeating the last row. Full chunks share one jit compilation already;
    this buckets the ragged *final* chunk of each sweep too, so the jax
    kernels compile O(log) distinct shapes instead of one per sweep size.
    Padded rows are duplicated real problems — callers slice kernel outputs
    back to the true row count and never read the padding's answers."""
    m = arrs[0].shape[0]
    m_pad = max(8, 1 << max(0, m - 1).bit_length())
    if m_pad == m:
        return arrs
    return tuple(np.concatenate([a, np.repeat(a[-1:], m_pad - m, axis=0)])
                 for a in arrs)


def _problem_cols(problems, *fields) -> list[np.ndarray]:
    return [np.fromiter((getattr(pr, f) for pr in problems),
                        np.float64, len(problems)) for f in fields]


def _staircase(obj: np.ndarray, p: np.ndarray,
               subset: Optional[np.ndarray] = None):
    """Pareto staircase of (objective, power): entries sorted by (obj,
    original index) whose power strictly improves the running minimum.

    The scalar solvers pick the min-objective entry among those with
    p <= budget, first occurrence on ties; any such entry is on the
    staircase (a dominated entry has an earlier-ordered entry with power
    <= its own, hence also feasible with smaller-or-equal objective). Since
    staircase power is strictly decreasing, the answer for a budget is the
    *first* staircase entry with p <= budget — one binary search.
    Returns (flat indices, staircase powers, staircase objectives)."""
    idx = np.arange(len(obj)) if subset is None else subset
    if idx.size == 0:
        return idx, np.empty(0), np.empty(0)
    order = idx[np.argsort(obj[idx], kind="stable")]
    ps = p[order]
    keep = np.empty(ps.size, dtype=bool)
    keep[0] = True
    keep[1:] = ps[1:] < np.minimum.accumulate(ps)[:-1]
    sidx = order[keep]
    return sidx, ps[keep], obj[sidx]


def solve_train_batch(problems: Sequence[P.TrainProblem],
                      obs: Union[dict, ObservationGrid],
                      backend: str = "numpy") -> list[Optional[P.Solution]]:
    """Batched ``problem.solve_train``: argmax theta_tr s.t. p <= p-hat for
    every problem at once. Returns one Optional[Solution] per problem,
    bitwise identical to the scalar loop."""
    check_backend(backend, ("numpy", "jax"))
    grid = as_train_grid(obs)
    out: list[Optional[P.Solution]] = [None] * len(problems)
    if not len(grid) or not len(problems):
        return out
    budgets, = _problem_cols(problems, "power_budget")
    if backend == "jax":
        kern = _jax_kernels()["train"]
        for s, e in _chunks(len(problems), len(grid)):
            bud, = _pad_problems(budgets[s:e])
            idx, ok = kern(grid.t, grid.p, bud)
            for k in np.flatnonzero(ok[:e - s]):
                i = int(idx[k])
                t = float(grid.t[i])
                out[s + k] = P.Solution(pm=grid.modes[i], time=t,
                                        power=float(grid.p[i]),
                                        throughput=1.0 / t)
        return out
    if "train" not in grid._stairs:
        sidx, sp, _ = _staircase(grid.t, grid.p)
        grid._stairs["train"] = (sidx, sp)
    sidx, sp = grid._stairs["train"]
    pos = np.searchsorted(-sp, -budgets, side="left")
    for k in np.flatnonzero(pos < sidx.size):
        i = int(sidx[pos[k]])
        t = float(grid.t[i])
        out[k] = P.Solution(pm=grid.modes[i], time=t, power=float(grid.p[i]),
                            throughput=1.0 / t)
    return out


def solve_infer_batch(problems: Sequence[P.InferProblem],
                      obs: Union[dict, ObservationGrid],
                      backend: str = "numpy") -> list[Optional[P.Solution]]:
    """Batched ``problem.solve_infer``: argmin peak latency s.t. power,
    latency, and sustainability constraints, over a batch of problems."""
    check_backend(backend, ("numpy", "jax"))
    grid = as_infer_grid(obs)
    out: list[Optional[P.Solution]] = [None] * len(problems)
    if not len(grid) or not len(problems):
        return out
    pb, lb, ar = _problem_cols(problems, "power_budget", "latency_budget",
                               "arrival_rate")
    bsf = grid.bs.astype(np.float64)
    if backend == "jax":
        kern = _jax_kernels()["infer"]
        for s, e in _chunks(len(problems), len(grid)):
            pbc, lbc, arc = _pad_problems(pb[s:e], lb[s:e], ar[s:e])
            idx, ok, lam_sel = kern(grid.t, grid.p, bsf, pbc, lbc, arc)
            for k in np.flatnonzero(ok[:e - s]):
                i = int(idx[k])
                out[s + k] = P.Solution(pm=grid.modes[i], bs=int(grid.bs[i]),
                                        time=float(lam_sel[k, i]),
                                        power=float(grid.p[i]))
        return out
    # group problems by arrival rate: peak latency and sustainability depend
    # on the rate alone, so each distinct rate needs one staircase over the
    # sustainable entries and each problem one binary search.
    rates, inverse = np.unique(ar, return_inverse=True)
    for ri in range(rates.size):
        rate = rates[ri]
        sel = np.flatnonzero(inverse == ri)
        key = ("infer", float(rate))
        if key not in grid._stairs:
            if len(grid._stairs) > 256:     # bound memoization growth
                grid._stairs.clear()
            lam_all = (bsf - 1.0) / rate + grid.t
            sustainable = np.flatnonzero(grid.t <= bsf / rate)
            grid._stairs[key] = (*_staircase(lam_all, grid.p, sustainable),
                                 lam_all)
        sidx, sp, slam, lam = grid._stairs[key]
        if not sidx.size:
            continue
        pos = np.searchsorted(-sp, -pb[sel], side="left")
        safe = np.minimum(pos, sidx.size - 1)
        ok = (pos < sidx.size) & (slam[safe] <= lb[sel])
        for j in np.flatnonzero(ok):
            i = int(sidx[pos[j]])
            out[sel[j]] = P.Solution(pm=grid.modes[i], bs=int(grid.bs[i]),
                                     time=float(lam[i]),
                                     power=float(grid.p[i]))
    return out


def solve_infer_fleet_batch(problems: Sequence[P.InferProblem],
                            rate_his: Sequence[float],
                            obs: Union[dict, ObservationGrid],
                            time_scales: Sequence[float],
                            power_scales: Sequence[float],
                            backend: str = "numpy"
                            ) -> list[Optional[P.Solution]]:
    """Batched ``problem.solve_infer_interval`` across K heterogeneous
    devices sharing one *base* observation grid: device k's grid is the base
    grid scaled elementwise by its ``(time_scales[k], power_scales[k])``
    (the ``PerturbedDeviceModel`` law — same IEEE multiply as profiling the
    device point by point, so results are bitwise equal to the scalar solve
    over each device's own dict). Row k solves ``problems[k]`` against
    device k: sustainability at ``max(rate_his[k], arrival_rate)``, latency
    budget and objective at the problem's (low-end) rate. Every problem
    column — including ``power_budget`` — is per-row, which is how the
    fleet's shared power cap threads through: ``FleetSpec.fleet_power_budget``
    water-fills one cap into per-device budgets and each device's grant
    lands in its problem row. The fleet planner solves all K per-device
    windows with one call per window."""
    check_backend(backend, ("numpy", "jax"))
    grid = as_infer_grid(obs)
    out: list[Optional[P.Solution]] = [None] * len(problems)
    if not len(grid) or not len(problems):
        return out
    n = len(problems)
    if not (len(rate_his) == len(time_scales) == len(power_scales) == n):
        raise ValueError("rate_his / time_scales / power_scales must align "
                         "with the problems")
    pb, lb, ar = _problem_cols(problems, "power_budget", "latency_budget",
                               "arrival_rate")
    hi = np.maximum(np.asarray(rate_his, np.float64), ar)
    ts = np.asarray(time_scales, np.float64)
    ps = np.asarray(power_scales, np.float64)
    bsf = grid.bs.astype(np.float64)
    if backend == "jax":
        kern = _jax_kernels()["fleet"]
        t_dev, p_dev, bsf_dev = device_grid_arrays(grid)
        for s, e in _chunks(n, len(grid)):
            pbc, lbc, arc, hic, tsc, psc = _pad_problems(
                pb[s:e], lb[s:e], ar[s:e], hi[s:e], ts[s:e], ps[s:e])
            idx, ok, lam_sel = kern(t_dev, p_dev, bsf_dev, pbc, lbc, arc,
                                    hic, tsc, psc)
            for k in np.flatnonzero(ok[:e - s]):
                i = int(idx[k])
                out[s + k] = P.Solution(pm=grid.modes[i], bs=int(grid.bs[i]),
                                        time=float(lam_sel[k, i]),
                                        power=float(grid.p[i] * ps[s + k]))
        return out
    # the rate-grouped staircase trick does not survive per-device time
    # scales (each device reorders the Pareto set); a chunked dense masked
    # argmin is still one array program per window for the whole fleet.
    for s, e in _chunks(n, len(grid)):
        t_k = grid.t[None, :] * ts[s:e, None]
        p_k = grid.p[None, :] * ps[s:e, None]
        lam = (bsf[None, :] - 1.0) / ar[s:e, None] + t_k
        feas = ((p_k <= pb[s:e, None])
                & (t_k <= bsf[None, :] / hi[s:e, None])
                & (lam <= lb[s:e, None]))
        lam_sel = np.where(feas, lam, np.inf)
        idx = np.argmin(lam_sel, axis=1)
        for k in np.flatnonzero(feas.any(axis=1)):
            i = int(idx[k])
            out[s + k] = P.Solution(pm=grid.modes[i], bs=int(grid.bs[i]),
                                    time=float(lam[k, i]),
                                    power=float(p_k[k, i]))
    return out


def _align_train(infer_grid: ObservationGrid, train_grid: ObservationGrid):
    """Per-infer-entry train observations; entries whose mode is absent from
    the train grid are masked out (the scalar loop skips them)."""
    tindex = train_grid.index
    pos = np.fromiter((tindex.get(pm, -1) for pm in infer_grid.modes),
                      np.int64, len(infer_grid))
    valid = pos >= 0
    safe = np.maximum(pos, 0)
    t_tr = np.where(valid, train_grid.t[safe], np.nan)
    p_tr = np.where(valid, train_grid.p[safe], np.nan)
    return t_tr, p_tr, valid


def solve_concurrent_batch(problems: Sequence[P.ConcurrentProblem],
                           train_obs: Union[dict, ObservationGrid],
                           infer_obs: Union[dict, ObservationGrid],
                           backend: str = "numpy") -> list[Optional[P.Solution]]:
    """Batched ``problem.solve_concurrent``: lexicographic argmax of
    (training throughput, -peak latency) under the interleaving feasibility
    mask, for every problem at once."""
    check_backend(backend, ("numpy", "jax"))
    tg = as_train_grid(train_obs)
    ig = as_infer_grid(infer_obs)
    out: list[Optional[P.Solution]] = [None] * len(problems)
    if not len(tg) or not len(ig) or not len(problems):
        return out
    pb, lb, ar = _problem_cols(problems, "power_budget", "latency_budget",
                               "arrival_rate")
    t_tr, p_tr, valid = _align_train(ig, tg)
    with np.errstate(invalid="ignore"):
        pmax = np.maximum(ig.p, p_tr)
    bsf = ig.bs.astype(np.float64)
    if backend == "jax":
        kern = _jax_kernels()["concurrent"]
        for s, e in _chunks(len(problems), len(ig)):
            pbc, lbc, arc = _pad_problems(pb[s:e], lb[s:e], ar[s:e])
            idx, ok, tau_c, theta_c, lam_c = kern(
                ig.t, bsf, t_tr, pmax, valid, pbc, lbc, arc)
            for k in np.flatnonzero(ok[:e - s]):
                i = int(idx[k])
                out[s + k] = P.Solution(
                    pm=ig.modes[i], bs=int(ig.bs[i]), tau_tr=int(tau_c[k, i]),
                    time=float(lam_c[k, i]), power=float(pmax[i]),
                    throughput=float(theta_c[k, i]))
        return out
    # group by arrival rate: tau/theta/lam and sustainability depend only on
    # the rate, so compute them once per distinct rate over the (compressed)
    # sustainable candidate set; only the power/latency mask is per problem.
    rates, inverse = np.unique(ar, return_inverse=True)
    for ri in range(rates.size):
        rate = rates[ri]
        sel = np.flatnonzero(inverse == ri)
        cycle = bsf / rate
        cand = np.flatnonzero(valid & (ig.t <= cycle))  # original order kept
        if not cand.size:
            continue
        cyc = cycle[cand]
        lam = (bsf[cand] - 1.0) / rate + ig.t[cand]
        tau = np.maximum(np.floor((cyc - ig.t[cand]) / t_tr[cand]), 0.0)
        theta = tau / cyc
        pm_c = pmax[cand]
        for s, e in _chunks(sel.size, cand.size):
            rows = sel[s:e]
            feas = ((pm_c[None, :] <= pb[rows, None])
                    & (lam[None, :] <= lb[rows, None]))
            th = np.where(feas, theta[None, :], -np.inf)
            best = th.max(axis=1)
            lam_masked = np.where(feas & (th >= best[:, None]), lam, np.inf)
            idx = np.argmin(lam_masked, axis=1)
            for k in np.flatnonzero(feas.any(axis=1)):
                j = int(idx[k])
                i = int(cand[j])
                out[rows[k]] = P.Solution(
                    pm=ig.modes[i], bs=int(ig.bs[i]), tau_tr=int(tau[j]),
                    time=float(lam[j]), power=float(pmax[i]),
                    throughput=float(theta[j]))
    return out


# ---------------------------------------------------------------------------
# multi-tenant: N inference streams + optional training fill (problem.
# solve_multi_tenant batched). Candidates are the cross-product of per-stream
# (pm, bs) grid entries sharing one mode, enumerated stream-0-major in grid
# order — the scalar reference's exact scan (and tie-break) order.
# ---------------------------------------------------------------------------

_MISS = object()


class _MultiCandidates:
    """Columnar joint candidate set for one (stream grids, specs) tuple."""

    def __init__(self, grids: Sequence[ObservationGrid],
                 train_grid: Optional[ObservationGrid],
                 specs: Sequence) -> None:
        n = len(grids)
        masks = []
        for g, spec in zip(grids, specs):
            if spec.batch_sizes is None:
                masks.append(None)
            else:
                allowed = set(int(b) for b in spec.batch_sizes)
                masks.append(np.fromiter((int(b) in allowed for b in g.bs),
                                         bool, len(g)))
        # streams 1..n-1: {pm: [flat indices]} in grid order
        by_pm: list[dict] = []
        for g, m in zip(grids[1:], masks[1:]):
            d: dict = {}
            for i in range(len(g)):
                if m is None or m[i]:
                    d.setdefault(g.modes[i], []).append(i)
            by_pm.append(d)
        tindex = None if train_grid is None else train_grid.index
        inner_cache: dict = {}
        cols: list[list] = [[] for _ in range(n)]
        g0, m0 = grids[0], masks[0]
        for i in range(len(g0)):
            if m0 is not None and not m0[i]:
                continue
            pm = g0.modes[i]
            if tindex is not None and pm not in tindex:
                continue
            blk = inner_cache.get(pm, _MISS)
            if blk is _MISS:
                lists = [d.get(pm) for d in by_pm]
                if any(ls is None for ls in lists):
                    blk = None
                else:
                    mesh = np.meshgrid(*[np.asarray(ls, np.int64)
                                         for ls in lists], indexing="ij") \
                        if lists else []
                    blk = [mg.ravel() for mg in mesh]
                inner_cache[pm] = blk
            if blk is None:
                continue
            width = blk[0].size if blk else 1
            cols[0].append(np.full(width, i, np.int64))
            for j, b in enumerate(blk):
                cols[j + 1].append(b)
        if cols[0]:
            self.idx = [np.concatenate(c) for c in cols]
        else:
            self.idx = [np.empty(0, np.int64) for _ in range(n)]
        K = self.idx[0].size
        self.K, self.n = K, n
        self.modes = [grids[0].modes[int(i)] for i in self.idx[0]]
        self.t_in = np.empty((K, n))
        self.bsf = np.empty((K, n))
        self.bss = np.empty((K, n), np.int64)
        pmax = np.full(K, -np.inf)
        for j, g in enumerate(grids):
            ix = self.idx[j]
            self.t_in[:, j] = g.t[ix]
            self.bss[:, j] = g.bs[ix]
            self.bsf[:, j] = self.bss[:, j].astype(np.float64)
            pmax = np.maximum(pmax, g.p[ix])
        if train_grid is not None:
            tpos = np.fromiter((tindex[pm] for pm in self.modes), np.int64, K)
            self.t_tr = train_grid.t[tpos]
            pmax = np.maximum(pmax, train_grid.p[tpos])
        else:
            self.t_tr = None
        self.pmax = pmax


def _multi_spec_key(specs) -> tuple:
    """The per-stream structure that must be uniform across a problem batch:
    the observation sets are shared, so workloads and allowed batch sizes
    must match (rates and budgets may vary)."""
    return tuple((getattr(s.workload, "name", s.workload),
                  None if s.batch_sizes is None else tuple(s.batch_sizes))
                 for s in specs)


def _multi_rate_arrays(cand: "_MultiCandidates", rates: np.ndarray):
    """(sustainable candidate subset, per-candidate lam/tau/theta) for one
    per-stream rate vector — the rate-independent part of the reduction.
    Replays problem.multi_* op-for-op (single stream = the pair exprs)."""
    t_in, bsf, n = cand.t_in, cand.bsf, cand.n
    cycle = bsf / rates[None, :]
    sus = (t_in <= cycle).all(axis=1)
    if n == 1:
        base = cycle[:, 0]
        slack = base - t_in[:, 0]
        lam = (bsf - 1.0) / rates[None, :] + t_in
    else:
        base = cycle.min(axis=1)
        busy = np.zeros(cand.K)
        for j in range(n):
            busy += t_in[:, j] * (base * rates[j] / bsf[:, j])
        slack = base - busy
        sus &= slack >= 0.0
        total = np.zeros(cand.K)
        for j in range(n):
            total += t_in[:, j]
        lam = (bsf - 1.0) / rates[None, :] + t_in
        lam = lam + (total[:, None] - t_in)
    keep = np.flatnonzero(sus)
    if cand.t_tr is not None:
        tau = np.maximum(np.floor(slack[keep] / cand.t_tr[keep]), 0.0)
        theta = tau / base[keep]
    else:
        tau = theta = None
    return keep, lam[keep], tau, theta


def solve_multi_tenant_batch(problems: Sequence["P.MultiTenantProblem"],
                             train_obs: Optional[Union[dict, ObservationGrid]],
                             infer_obs: Sequence[Union[dict, ObservationGrid]],
                             backend: str = "numpy"
                             ) -> list[Optional["P.MultiTenantSolution"]]:
    """Batched ``problem.solve_multi_tenant``: every problem must share the
    stream count, train flag, and per-stream batch-size restrictions; rates,
    latency budgets, and power budgets vary per problem."""
    check_backend(backend, ("numpy", "jax"))
    out: list[Optional[P.MultiTenantSolution]] = [None] * len(problems)
    if not len(problems):
        return out
    p0 = problems[0]
    n = p0.n_streams
    if len(infer_obs) != n:
        raise ValueError(f"expected {n} observation sets, got {len(infer_obs)}")
    skey = _multi_spec_key(p0.streams)
    for pr in problems:
        if pr.n_streams != n or pr.train != p0.train \
                or _multi_spec_key(pr.streams) != skey \
                or pr.priorities != p0.priorities:
            raise ValueError("solve_multi_tenant_batch needs a uniform "
                             "stream shape (count, train flag, workloads, "
                             "batch sizes, priorities) across the problem "
                             "batch")
    weights = p0.priority_weights()
    grids = [as_infer_grid(o) for o in infer_obs]
    tg = as_train_grid(train_obs) if p0.train else None
    if any(not len(g) for g in grids) or (tg is not None and not len(tg)):
        return out
    cand = _MultiCandidates(grids, tg, p0.streams)
    if not cand.K:
        return out
    pb = np.fromiter((pr.power_budget for pr in problems), np.float64,
                     len(problems))
    ar = np.array([[s.arrival_rate for s in pr.streams] for pr in problems])
    lb = np.array([[s.latency_budget for s in pr.streams] for pr in problems])
    if backend == "jax":
        return _solve_multi_jax(problems, cand, pb, ar, lb, out, weights)
    rates, inverse = np.unique(ar, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    for ri in range(rates.shape[0]):
        sel = np.flatnonzero(inverse == ri)
        keep, lam, tau, theta = _multi_rate_arrays(cand, rates[ri])
        if not keep.size:
            continue
        pm_c = cand.pmax[keep]
        # the priority-weighted worst-latency secondary objective (scalar:
        # max_j(w_j * lam_j)); unset priorities apply no multiplication at
        # all — the bitwise-default contract
        worst = lam.max(axis=1) if weights is None \
            else (lam * np.asarray(weights, np.float64)[None, :]).max(axis=1)
        for s, e in _chunks(sel.size, keep.size * n):
            rows = sel[s:e]
            feas = ((pm_c[None, :] <= pb[rows, None])
                    & (lam[None, :, :] <= lb[rows, None, :]).all(axis=2))
            if theta is not None:
                th = np.where(feas, theta[None, :], -np.inf)
                best = th.max(axis=1)
                masked = np.where(feas & (th >= best[:, None]), worst, np.inf)
            else:
                masked = np.where(feas, worst, np.inf)
            idx = np.argmin(masked, axis=1)
            for k in np.flatnonzero(feas.any(axis=1)):
                j = int(idx[k])
                i = int(keep[j])
                out[rows[k]] = P.MultiTenantSolution(
                    pm=cand.modes[i], bss=tuple(int(b) for b in cand.bss[i]),
                    tau_tr=None if tau is None else int(tau[j]),
                    times=tuple(float(x) for x in lam[j]),
                    power=float(cand.pmax[i]),
                    throughput=0.0 if theta is None else float(theta[j]))
    return out


def _solve_multi_jax(problems, cand: "_MultiCandidates", pb, ar, lb, out,
                     weights=None):
    kern = _jax_kernels()["multi_train" if cand.t_tr is not None
                         else "multi_infer"]
    # unit weights reproduce the unweighted objective bitwise (1.0 * x == x
    # in IEEE-754), so the kernel always takes a weight vector
    wts = np.ones(cand.n) if weights is None \
        else np.asarray(weights, np.float64)
    args = (cand.t_in, cand.bsf, cand.pmax) + (
        (cand.t_tr,) if cand.t_tr is not None else ()) + (wts,)
    for s, e in _chunks(len(problems), cand.K * cand.n):
        pbc, arc, lbc = _pad_problems(pb[s:e], ar[s:e], lb[s:e])
        idx, ok, tau_s, theta_s, lam_s = kern(*args, pbc, arc, lbc)
        for k in np.flatnonzero(ok[:e - s]):
            i = int(idx[k])
            out[s + k] = P.MultiTenantSolution(
                pm=cand.modes[i], bss=tuple(int(b) for b in cand.bss[i]),
                tau_tr=None if cand.t_tr is None else int(tau_s[k]),
                times=tuple(float(x) for x in lam_s[k]),
                power=float(cand.pmax[i]),
                throughput=float(theta_s[k]))
    return out


# ---------------------------------------------------------------------------
# jax backend: jit + vmap over the problem axis, float64 via enable_x64 so
# the on-accelerator reduction keeps the NumPy path's exactness
# ---------------------------------------------------------------------------

_JAX_CACHE: dict = {}


def solver_trace_count() -> int:
    """Number of solver-kernel (re)traces since import (all six kernels)."""
    return obs.counted("trace.solver")


def device_grid_arrays(grid: ObservationGrid) -> tuple:
    """Device-resident copies of a grid's ``(t, p, bs-as-float64)`` columns,
    uploaded once per grid instance and memoized on it (the cache dies with
    the grid, like ``_stairs``). Before this, every jax fleet-solver call —
    four per fleet window — re-transferred the same NumPy columns through
    ``jnp.asarray``; passing these committed arrays makes that a no-op, and
    the fused fleet-window program keys its per-window launches on them."""
    cache = grid.__dict__.get("_device_cols")
    if cache is None:
        _jax, jnp, enable_x64 = require_jax()
        with enable_x64():
            cache = (jnp.asarray(grid.t), jnp.asarray(grid.p),
                     jnp.asarray(grid.bs.astype(np.float64)))
        grid.__dict__["_device_cols"] = cache
    return cache


def _jax_kernels() -> dict:
    if _JAX_CACHE:
        return _JAX_CACHE
    jax, jnp, enable_x64 = require_jax()

    @jax.jit
    def train_kernel(t, p, budgets):
        obs.count("trace.solver")              # fires at trace time only
        def one(b):
            feas = p <= b
            masked = jnp.where(feas, t, jnp.inf)
            return jnp.argmin(masked), feas.any()
        return jax.vmap(one)(budgets)

    @jax.jit
    def infer_kernel(t, p, bsf, pb, lb, ar):
        obs.count("trace.solver")
        def one(b_p, b_l, b_a):
            lam = (bsf - 1.0) / b_a + t
            feas = (p <= b_p) & (t <= bsf / b_a) & (lam <= b_l)
            lam_sel = jnp.where(feas, lam, jnp.inf)
            return jnp.argmin(lam_sel), feas.any(), lam_sel
        return jax.vmap(one)(pb, lb, ar)

    @jax.jit
    def concurrent_kernel(t_in, bsf, t_tr, pmax, valid, pb, lb, ar):
        obs.count("trace.solver")
        def one(b_p, b_l, b_a):
            cycle = bsf / b_a
            lam = (bsf - 1.0) / b_a + t_in
            feas = (valid & (pmax <= b_p) & (t_in <= cycle) & (lam <= b_l))
            tau = jnp.where(
                feas, jnp.maximum(jnp.floor((cycle - t_in) / t_tr), 0.0), 0.0)
            theta = jnp.where(feas, tau / cycle, -jnp.inf)
            best = theta.max()
            lam_masked = jnp.where(feas & (theta >= best), lam, jnp.inf)
            return jnp.argmin(lam_masked), feas.any(), tau, theta, lam
        return jax.vmap(one)(pb, lb, ar)

    @jax.jit
    def fleet_kernel(t, p, bsf, pb, lb, ar, hi, ts, ps):
        obs.count("trace.solver")
        def one(b_p, b_l, b_a, b_h, k_t, k_p):
            # device row: the base grid scaled by this device's (time,
            # power) factors — the PerturbedDeviceModel law
            tk = t * k_t
            pk = p * k_p
            lam = (bsf - 1.0) / b_a + tk
            feas = (pk <= b_p) & (tk <= bsf / b_h) & (lam <= b_l)
            lam_sel = jnp.where(feas, lam, jnp.inf)
            return jnp.argmin(lam_sel), feas.any(), lam_sel
        return jax.vmap(one)(pb, lb, ar, hi, ts, ps)

    def _multi_one(t_in, bsf, pmax, t_tr, wts, b_p, b_a, b_l):
        n = t_in.shape[1]
        cycle = bsf / b_a[None, :]
        sus = (t_in <= cycle).all(axis=1)
        if n == 1:
            base = cycle[:, 0]
            slack = base - t_in[:, 0]
            lam = (bsf - 1.0) / b_a[None, :] + t_in
        else:
            base = cycle.min(axis=1)
            busy = jnp.zeros(t_in.shape[0])
            total = jnp.zeros(t_in.shape[0])
            for j in range(n):        # stream order, as the scalar reference
                busy = busy + t_in[:, j] * (base * b_a[j] / bsf[:, j])
                total = total + t_in[:, j]
            slack = base - busy
            sus = sus & (slack >= 0.0)
            lam = (bsf - 1.0) / b_a[None, :] + t_in
            lam = lam + (total[:, None] - t_in)
        feas = sus & (pmax <= b_p) & (lam <= b_l[None, :]).all(axis=1)
        worst = (lam * wts[None, :]).max(axis=1)
        if t_tr is None:
            tau = jnp.zeros(t_in.shape[0])
            theta = jnp.zeros(t_in.shape[0])
            masked = jnp.where(feas, worst, jnp.inf)
        else:
            tau = jnp.where(
                feas, jnp.maximum(jnp.floor(slack / t_tr), 0.0), 0.0)
            theta = jnp.where(feas, tau / base, -jnp.inf)
            best = theta.max()
            masked = jnp.where(feas & (theta >= best), worst, jnp.inf)
        i = jnp.argmin(masked)
        return i, feas.any(), tau[i], theta[i], lam[i]

    @jax.jit
    def multi_train_kernel(t_in, bsf, pmax, t_tr, wts, pb, ar, lb):
        obs.count("trace.solver")
        return jax.vmap(lambda p, a, l: _multi_one(
            t_in, bsf, pmax, t_tr, wts, p, a, l))(pb, ar, lb)

    @jax.jit
    def multi_infer_kernel(t_in, bsf, pmax, wts, pb, ar, lb):
        obs.count("trace.solver")
        return jax.vmap(lambda p, a, l: _multi_one(
            t_in, bsf, pmax, None, wts, p, a, l))(pb, ar, lb)

    def x64(fn):
        def wrapped(*args):
            record_dispatch("solver")
            with enable_x64():
                res = fn(*[jnp.asarray(a) for a in args])
            return tuple(np.asarray(r) for r in res)
        return with_program(wrapped, fn)

    _JAX_CACHE.update({"train": x64(train_kernel),
                       "infer": x64(infer_kernel),
                       "fleet": x64(fleet_kernel),
                       "concurrent": x64(concurrent_kernel),
                       "multi_train": x64(multi_train_kernel),
                       "multi_infer": x64(multi_infer_kernel)})
    return _JAX_CACHE
