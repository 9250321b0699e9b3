"""Vectorized trace-driven execution engine (paper §3 Fig. 2, §5.4).

The seed simulators in ``core.interleave`` replayed every request in a Python
loop; after PR 1 made the solvers batched, execution dominated benchmark wall
time. This module replaces the per-request loops with NumPy array kernels
over arrival-time vectors:

 * ``ArrivalTrace`` — the workload input: a sorted vector of request arrival
   timestamps. Constructors cover the paper's scenarios: ``uniform`` (the
   seed's fixed-rate ticks), ``poisson`` (seeded stochastic arrivals), and
   ``piecewise`` (per-window rates, the §5.4 dynamic traces produced by
   ``bench_dynamic.make_traces``-style rate lists).
 * ``simulate`` — one entry point dispatching to the managed / native /
   streams kernels; ``core.interleave.simulate_*`` remain as thin wrappers.

Exactness contract (mirrors ``core.grid_eval``): the managed path is
*deterministic* and the vectorized kernel reproduces the scalar reference
loop exactly — identical latency lists, training-minibatch counts, and power.
The kernel exploits the loop's structure: training slack-fill never pushes
``now`` past the batch-ready time, so completion times obey the max-plus
recurrence ``c_k = fl(max(c_{k-1}, ready_k) + t_in)`` independent of
training. The no-backlog candidate ``ready + t_in`` is vectorized; backlogged
runs (rare under sustainable plans) are resolved with the exact scalar
recurrence. Slack-fill counts come from a vectorized floor division, with an
exact replay of the reference's repeated-addition loop on the (measure-zero)
boundary cases where floating-point accumulation could flip the count —
``tests/test_simulate.py`` enforces equality property-style.

The native / streams paths are stochastic by design (contention jitter); they
use seeded NumPy generators and a cumulative-sum service-time kernel
(``c = max-accumulate(ready - cumsum_prev) + cumsum``), deterministic per
seed but not bitwise-coupled to the seed's ``random.Random`` streams.

Backlog carryover (§5.4 closed loop): the managed engines accept a
``carry_in`` ``QueueState`` — the previous window's unserved requests
(original arrival times) plus the engine clock — and every managed report
returns the end-of-window ``queue_state``. Replaying one long trace as K
windows chained through queue states is *bitwise identical* on NumPy to
replaying it in one call (the carried floats re-enter the identical
recurrence; see ``docs/exactness.md``), and tolerance-identical on jax.

Backends (contract; see ``docs/exactness.md`` for the full ladder):

 * ``backend="numpy"`` (default) — the **reference**: managed results are
   bitwise-equal to the scalar loops above; this is what the identity tests
   pin and what every other backend is judged against.
 * ``backend="jax"`` — the managed kernel expressed as a max-plus
   ``jax.lax.associative_scan`` (``c_k = max(c_{k-1}, ready_k) + e_k`` is the
   composition of affine max-plus maps ``x -> max(x + e_k, ready_k + e_k)``),
   jit + vmap'd over a *lane* axis so many (power mode, batch size, trace)
   simulations — including multi-tenant lanes with padded event axes — run as
   one on-accelerator program (``simulate_batch`` /
   ``simulate_multi_tenant_batch``). The scan reassociates float adds and
   skips the boundary replay of ``_fill_counts``, so jax results are
   *tolerance-checked* against NumPy (|Δlatency| ≲ K·eps·T, enforced at
   atol=1e-8 s / rtol=1e-9 by ``tests/test_simulate.py``; train-minibatch
   counts may differ only on quotient-boundary cases), **not** bitwise.

Backend selection follows ``core.backend.resolve_backend``: ``None`` defers
to ``FULCRUM_ENGINE_BACKEND`` and degrades jax → numpy when jax is
unavailable. Reports from the batched paths are built by one vectorized
report builder: a chunked padded sort fills every lane's quantile /
violation-rate cache.

Lane scaling (10⁴–10⁵ lanes): the accelerator paths never materialize one
giant padded matrix — lanes are dispatched in ``_LANE_CHUNK``-sized chunks
padded to power-of-two lane buckets and one *global* power-of-two event
count, so every chunk of a sweep (and of the next sweep) hits the same
compiled program. The compiled kernel lives in a module-level cache (jit
itself caches per padded shape); ``engine_trace_count()``
exposes a retrace counter so tests can pin the no-retrace contract. Scan
input buffers are donated (``donate_argnums``) — they are per-call padded
copies, never reused host-side.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import random
import warnings
from typing import Callable, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.backend import (record_dispatch, require_jax, resolve_backend,
                                with_program)
from repro.core.device_model import DeviceModel, WorkloadProfile
from repro.core.powermode import PowerMode

_EPS = float(np.finfo(np.float64).eps)

# Exact slack-fill replay is O(count); past this the floor estimate stands
# (its error bound is still astronomically below the decision boundary).
_MAX_EXACT_FILL = 2_000_000


# ---------------------------------------------------------------------------
# arrival traces
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class ArrivalTrace:
    """Sorted request-arrival timestamps (seconds) driving one simulation.
    ``stream_ids`` (multi-tenant traces) records which tenant each request
    belongs to; ``merge``/``split`` round-trip that provenance."""
    times: np.ndarray
    duration: float
    kind: str = "uniform"
    stream_ids: Optional[np.ndarray] = None
    n_streams: Optional[int] = None   # tenant count of a merged trace

    def __post_init__(self):
        object.__setattr__(self, "times",
                           np.ascontiguousarray(self.times, np.float64))
        if self.stream_ids is not None:
            object.__setattr__(self, "stream_ids",
                               np.ascontiguousarray(self.stream_ids, np.int64))

    def __len__(self) -> int:
        return int(self.times.size)

    @property
    def mean_rate(self) -> float:
        return len(self) / self.duration if self.duration > 0 else 0.0

    def shifted(self, t0: float) -> "ArrivalTrace":
        return ArrivalTrace(self.times + t0, self.duration, self.kind,
                            self.stream_ids, self.n_streams)

    def clip(self, t0: float, t1: float, rebase: bool = False) -> "ArrivalTrace":
        """The [t0, t1) window view of this trace. Times stay absolute —
        the carryover convention, so slicing a long trace into windows and
        replaying them with ``QueueState`` chaining reproduces the long run
        bitwise — unless ``rebase`` shifts them to the window origin."""
        if t1 < t0:
            raise ValueError(f"empty window: t1={t1} < t0={t0}")
        m = (self.times >= t0) & (self.times < t1)
        ids = self.stream_ids[m] if self.stream_ids is not None else None
        return ArrivalTrace(self.times[m] - (t0 if rebase else 0.0),
                            t1 - t0, self.kind, ids, self.n_streams)

    @staticmethod
    def concat(traces: Sequence["ArrivalTrace"],
               duration: Optional[float] = None) -> "ArrivalTrace":
        """Concatenate traces whose times are already in nondecreasing order
        (e.g. carried-over pending requests followed by the next window's
        arrivals). ``duration`` defaults to the longest piece's."""
        if not traces:
            return ArrivalTrace(np.empty(0), float(duration or 0.0))
        times = np.concatenate([t.times for t in traces])
        if times.size > 1 and np.any(np.diff(times) < 0):
            raise ValueError("concat needs nondecreasing times across pieces;"
                             " use merge() for interleaved streams")
        ids = None
        if all(t.stream_ids is not None for t in traces):
            ids = np.concatenate([t.stream_ids for t in traces])
        n_streams = max((t.n_streams for t in traces
                         if t.n_streams is not None), default=None)
        if duration is None:
            duration = max(t.duration for t in traces)
        return ArrivalTrace(times, float(duration), traces[0].kind,
                            ids, n_streams)

    @staticmethod
    def merge(traces: Sequence["ArrivalTrace"]) -> "ArrivalTrace":
        """Merge per-stream traces into one multi-tenant trace. Stream ``j``
        of the result is ``traces[j]``; arrival order is a stable sort on
        time, so simultaneous arrivals keep stream order. ``split`` recovers
        the per-stream traces (idle tenants included — the stream count is
        recorded, not inferred from the ids)."""
        if not traces:
            return ArrivalTrace(np.empty(0), 0.0, "merged",
                                np.empty(0, np.int64), 0)
        times = np.concatenate([t.times for t in traces])
        ids = np.concatenate([np.full(len(t), j, np.int64)
                              for j, t in enumerate(traces)])
        order = np.argsort(times, kind="stable")
        duration = max(t.duration for t in traces)
        return ArrivalTrace(times[order], float(duration), "merged",
                            ids[order], len(traces))

    def split(self, n_streams: Optional[int] = None) -> list["ArrivalTrace"]:
        """Per-stream traces of a merged trace (provenance round-trip)."""
        if self.stream_ids is None:
            raise ValueError("trace has no stream provenance; use merge()")
        n = n_streams if n_streams is not None else self.n_streams
        if n is None:       # foreign ids without a recorded count: infer
            n = int(self.stream_ids.max() + 1) if len(self) else 0
        return [ArrivalTrace(self.times[self.stream_ids == j], self.duration,
                             self.kind) for j in range(int(n))]

    @classmethod
    def uniform(cls, rate: float, duration: float) -> "ArrivalTrace":
        """Fixed-rate ticks at i/rate — bitwise identical to the seed's
        ``[i / arrival_rate for i in range(int(rate * duration))]``."""
        n = int(rate * duration)
        return cls(np.arange(n, dtype=np.float64) / rate, float(duration))

    @classmethod
    def poisson(cls, rate: float, duration: float, seed: int = 0) -> "ArrivalTrace":
        """Seeded Poisson process: exponential inter-arrival gaps."""
        if rate <= 0.0:                       # idle window: no arrivals
            return cls(np.empty(0), float(duration), "poisson")
        rng = np.random.default_rng(seed)
        mean = rate * duration
        n = max(8, int(mean + 6.0 * math.sqrt(mean) + 8))
        t = np.cumsum(rng.exponential(1.0 / rate, n))
        while t.size and t[-1] < duration:        # undershoot: extend (rare)
            t = np.concatenate([t, t[-1] + np.cumsum(
                rng.exponential(1.0 / rate, n))])
        return cls(t[t < duration], float(duration), "poisson")

    @classmethod
    def piecewise(cls, rates: Sequence[float], window_duration: float,
                  seed: Optional[int] = None) -> "ArrivalTrace":
        """Piecewise-rate trace: one window per rate (the §5.4 dynamic
        scenario; ``bench_dynamic.make_traces`` emits such rate lists).
        Uniform ticks within each window, Poisson when ``seed`` is given."""
        parts, t0 = [], 0.0
        for i, r in enumerate(rates):
            if r > 0:
                w = (cls.uniform(r, window_duration) if seed is None
                     else cls.poisson(r, window_duration, seed + i))
                parts.append(t0 + w.times)
            t0 += window_duration
        times = np.concatenate(parts) if parts else np.empty(0)
        return cls(times, t0, "piecewise")


# ---------------------------------------------------------------------------
# window-boundary queue state (backlog carryover)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class QueueState:
    """Managed-engine state at a window boundary, enabling backlog carryover
    across re-planning windows (§5.4 closed loop).

    ``pending`` holds the *original* arrival timestamps of requests that were
    never served (the trailing partial minibatch — every full minibatch is
    always executed, even if its completion overruns the window). ``clock``
    is the completion time of the last executed minibatch: the engine may not
    start work before it, so an overrunning window delays the next one.
    ``stream_ids`` aligns with ``pending`` for multi-tenant windows.

    Contract (enforced by ``tests/test_controller.py``): replaying a long
    trace as K windows chained through ``QueueState`` is bitwise identical on
    NumPy to replaying it in one call — the carried floats re-enter the same
    recurrence at the same positions (boundary-replay style,
    ``docs/exactness.md``). Fleet backlog migration
    (``fleet._migrate_backlog``) re-dispatches these pending vectors across
    devices between windows: a request that stays keeps its timestamp (its
    replay is bitwise this contract), one that moves is re-timestamped at
    the window start so the receiving device's pending vector stays
    nondecreasing — the migration-replay corollary in
    ``docs/exactness.md``."""
    pending: np.ndarray
    clock: float = 0.0
    stream_ids: Optional[np.ndarray] = None

    def __post_init__(self):
        object.__setattr__(self, "pending",
                           np.ascontiguousarray(self.pending, np.float64))
        if self.stream_ids is not None:
            object.__setattr__(self, "stream_ids",
                               np.ascontiguousarray(self.stream_ids, np.int64))

    def __len__(self) -> int:
        return int(self.pending.size)

    def pending_for(self, j: int) -> np.ndarray:
        """Pending arrivals of stream ``j`` of a multi-tenant state."""
        if self.stream_ids is None:
            return self.pending if j == 0 else np.empty(0)
        return self.pending[self.stream_ids == j]


# ---------------------------------------------------------------------------
# execution report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ExecutionReport:
    approach: str
    latencies: Sequence[float]        # per-request latency (s), queue + exec;
    train_minibatches: int            # a list (scalar refs) or float64 array
    duration: float
    power: float
    trace: Optional[ArrivalTrace] = None   # the arrivals that were executed
    queue_state: Optional[QueueState] = dataclasses.field(   # end-of-window
        default=None, repr=False, compare=False)             # engine state
    drift_s: Optional[float] = None   # runtime-vs-engine max |Δlatency| (s),
    #                                   filled by runtime.attach_drift
    # graceful-degradation accounting (§5.4 burst survival), filled by the
    # serving drivers / runtime admission gate — 0 / None when no admission
    # control ran
    shed_requests: int = 0            # offered requests dropped at admission
    deferred_requests: int = 0        # offered requests pushed to next window
    goodput: Optional[float] = None   # in-budget served / offered fraction
    # fleet / tenant power accounting: this report's time-weighted share of
    # the device's interleaved-window power (busy time of this stream over
    # total busy time; the training share lives on the parent multi-tenant
    # report). Shares across a window sum to the device power; an idle
    # window (nothing ran) attributes 0.
    attributed_power: Optional[float] = dataclasses.field(
        default=None, compare=False)
    _sorted: Optional[np.ndarray] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    @property
    def train_throughput(self) -> float:
        return self.train_minibatches / self.duration

    @property
    def sorted_latencies(self) -> np.ndarray:
        """Ascending latencies; the cache behind every quantile / violation
        query. The batched report builder (``_presort_reports``) fills it
        with one vectorized sort across all lanes of a batch."""
        if self._sorted is None:
            self._sorted = np.sort(np.asarray(self.latencies, np.float64))
        return self._sorted

    def latency_quantile(self, q: float) -> float:
        """Nearest-rank quantile: the smallest sample with at least a q
        fraction of the distribution at or below it (ceil(q*n)-th order
        statistic), so q=0.75 over 4 samples is the 3rd, not the max."""
        n = len(self.latencies)
        if n == 0:
            return 0.0
        xs = self.sorted_latencies
        return float(xs[min(n - 1, max(0, math.ceil(q * n) - 1))])

    def violation_rate(self, latency_budget: float) -> float:
        n = len(self.latencies)
        if n == 0:
            return 0.0
        xs = self.sorted_latencies
        return float(n - np.searchsorted(xs, latency_budget, side="right")) / n


# ---------------------------------------------------------------------------
# array kernels
# ---------------------------------------------------------------------------

def _batch_ready(times: np.ndarray, bs: int) -> np.ndarray:
    """Arrival time of the bs-th request of each full minibatch; a trailing
    partial batch never runs (as in the scalar loops)."""
    return times[bs - 1::bs]


def _managed_completions_var(ready: np.ndarray, exec_t: np.ndarray,
                             clock: float = 0.0) -> np.ndarray:
    """Exact batch completion times for the per-event-service recurrence
    c_k = fl(max(c_{k-1}, ready_k) + e_k), started from c_0 = ``clock`` (a
    carried-over window boundary; 0.0 for a fresh run): the vectorized
    no-backlog candidate everywhere, with backlogged runs (candidate
    finishing after the next batch is ready — including a carry-in clock
    overrunning the first batches) replayed by the scalar recurrence —
    identical float ops, so bitwise-equal results."""
    c = ready + exec_t
    K = c.size
    if K and clock > ready[0]:
        prev, k = float(clock), 0
        while k < K and prev > ready[k]:
            prev = prev + float(exec_t[k])
            c[k] = prev
            k += 1
    if K <= 1:
        return c
    bad = np.flatnonzero(c[:-1] > ready[1:])
    i, K = 0, c.size
    while i < bad.size:
        k = int(bad[i]) + 1
        prev = float(c[k - 1])
        while k < K and prev > ready[k]:
            prev = prev + float(exec_t[k])
            c[k] = prev
            k += 1
        while i < bad.size and bad[i] < k:
            i += 1
    return c


def _managed_completions(ready: np.ndarray, t_in: float,
                         clock: float = 0.0) -> np.ndarray:
    """Constant-service special case (the pair engine's kernel)."""
    return _managed_completions_var(
        ready, np.broadcast_to(np.float64(t_in), ready.shape), clock)


def _fill_count_exact(start: float, ready: float, t_tr: float) -> int:
    now, m = start, 0
    while now + t_tr <= ready and m < _MAX_EXACT_FILL:
        now += t_tr
        m += 1
    return m


def _fill_counts(ready: np.ndarray, completions: np.ndarray,
                 t_tr: float, clock: float = 0.0) -> np.ndarray:
    """Training minibatches filled into each batch's slack, matching the
    reference's repeated-addition loop exactly. The vectorized estimate is
    floor(slack / t_tr); only entries whose quotient sits within the
    floating-point error bound of an integer boundary — where repeated
    addition could round the other way — are replayed exactly. ``clock`` is
    the fill start before the first batch (a carried window boundary)."""
    if not math.isfinite(t_tr) or t_tr <= 0.0:
        return np.zeros(ready.size, np.int64)
    start = np.empty_like(ready)
    if ready.size:
        start[0] = clock
        start[1:] = completions[:-1]
    slack = ready - start
    q = slack / t_tr
    m = np.maximum(np.floor(q), 0.0)
    # |accumulated error| <= m*eps*max|s| and |division rounding| <= eps*q,
    # both covered (generously) by this threshold in quotient units
    thr = _EPS * (m + 4.0) * (2.0 + (np.abs(start) + np.abs(ready)) / t_tr)
    suspicious = np.flatnonzero((slack > 0) & (np.abs(q - np.rint(q)) <= thr)
                                & (m < _MAX_EXACT_FILL))
    m = m.astype(np.int64)
    for k in suspicious:
        m[k] = _fill_count_exact(float(start[k]), float(ready[k]), t_tr)
    return m


def first_backlog_crossing(times: np.ndarray, completions: np.ndarray,
                           bs: int, threshold: int) -> Optional[int]:
    """Index of the first arrival at which the backlog — requests arrived
    but not yet completed, counting the arriving request itself — exceeds
    ``threshold``, given the run's batch completion times (each completion
    retires one ``bs``-sized minibatch). ``None`` when the backlog never
    crosses. ``times`` must be the *effective* arrival vector of the run
    (carried pending requests first, as the managed engine sees them).

    The mid-window re-planning driver splits the window at the returned
    arrival's timestamp via ``ArrivalTrace.clip`` + ``QueueState`` chaining;
    the carryover replay contract (windowed == long trace, bitwise on NumPy)
    makes the split exact by construction — this function only has to pick
    the split point deterministically."""
    times = np.asarray(times, np.float64)
    if times.size == 0:
        return None
    comps = np.asarray(completions, np.float64)
    done = int(bs) * np.searchsorted(comps, times, side="right")
    backlog = np.arange(1, times.size + 1) - done
    idx = np.flatnonzero(backlog > int(threshold))
    return int(idx[0]) if idx.size else None


def _queue_completions(ready: np.ndarray, exec_t: np.ndarray) -> np.ndarray:
    """c_k = max(c_{k-1}, ready_k) + exec_k as one array program:
    c_k = max_{j<=k}(ready_j - E_{j-1}) + E_k with E = cumsum(exec)."""
    if ready.size == 0:
        return ready.copy()
    E = np.cumsum(exec_t)
    offset = np.concatenate(([0.0], E[:-1]))
    return np.maximum.accumulate(ready - offset) + E


def _latencies(completions: np.ndarray, times: np.ndarray,
               bs: int) -> np.ndarray:
    return np.repeat(completions, bs) - times[:completions.size * bs]


# Cap on lanes x requests elements per padded sort matrix: ~32 MB float64.
# One full-batch matrix at 10^5 ragged lanes would not survive; chunking
# keeps peak memory flat and lets each chunk pad to its OWN max length.
_SORT_CHUNK_ELEMS = 4 << 20


def _sort_lane_chunk(lats: list[np.ndarray], reports) -> None:
    """Sort one chunk of lanes through a padded (lane, request) matrix.
    Sorting permutes values — the sorted arrays are identical float64
    multisets to one ``np.sort`` per report, so the cache stays bitwise."""
    R = max(a.size for a in lats)
    total = sum(a.size for a in lats)
    if len(lats) * R > 4 * total:      # highly ragged: padding would cost
        for r, a in zip(reports, lats):        # far more than it batches
            r._sorted = np.sort(a)
        return
    mat = np.full((len(lats), R), np.inf)
    for i, a in enumerate(lats):
        mat[i, :a.size] = a
    mat.sort(axis=1)
    for i, (r, a) in enumerate(zip(reports, lats)):
        # copy: a view would pin the whole padded matrix per report
        r._sorted = mat[i, :a.size].copy()


def _presort_reports(reports: Sequence[ExecutionReport]) -> None:
    """Batched report builder: fill every report's quantile/violation cache
    with chunked vectorized sorts over padded (lane, request) matrices, so
    per-lane statistics of a batch are computed vectorized rather than one
    Python-level sort per report. +inf padding keeps each lane's real
    latencies as the leading prefix after the sort; chunks are cut so no
    padded matrix exceeds ``_SORT_CHUNK_ELEMS`` elements (each chunk pads to
    its own max length, so one long lane cannot inflate the whole batch)."""
    lats = [np.asarray(r.latencies, np.float64) for r in reports]
    if max((a.size for a in lats), default=0) == 0:
        for r in reports:
            r._sorted = np.empty(0)
        return
    i = 0
    while i < len(lats):
        j, width = i + 1, max(lats[i].size, 1)
        while j < len(lats):
            width = max(width, lats[j].size)
            if (j + 1 - i) * width > _SORT_CHUNK_ELEMS:
                break
            j += 1
        _sort_lane_chunk(lats[i:j], reports[i:j])
        i = j


def _time_power(device: DeviceModel, w: WorkloadProfile, pm: PowerMode,
                bs: Optional[int]) -> tuple[float, float]:
    """Device timings are pure functions of (workload, mode, bs); memoize
    them on the device instance so repeated executions (per-window
    re-planning, benchmark sweeps) pay the deterministic-perturbation
    hashing once, as the Profiler does. The cache dies with the device."""
    cache = device.__dict__.setdefault("_simulate_time_power_cache", {})
    key = (w, pm, bs)
    out = cache.get(key)
    if out is None:
        out = cache[key] = device.time_power(w, pm, bs)
    return out


def _attribute_power(power: float, busys: Sequence[float]) -> list[float]:
    """Time-weighted power attribution: split a device's interleaved-window
    power across its consumers proportionally to busy time. The managed
    engine runs one DNN at a time, so busy time IS the fraction of the
    window each consumer held the device; the shares sum to ``power`` by
    construction. An idle window (no work ran) attributes 0 to everyone —
    the plan's static power belongs to no tenant."""
    total = float(sum(busys))
    if total <= 0.0:
        return [0.0 for _ in busys]
    return [power * (b / total) for b in busys]


# ---------------------------------------------------------------------------
# the three execution approaches
# ---------------------------------------------------------------------------

def _carry_times(trace: ArrivalTrace,
                 carry_in: Optional[QueueState]) -> tuple[np.ndarray, float]:
    """A window's effective arrival vector and starting clock: carried
    pending requests (original timestamps) re-enter ahead of the window's
    own arrivals, and the engine resumes from the carried clock."""
    if carry_in is None:
        return trace.times, 0.0
    times = trace.times if not len(carry_in) \
        else np.concatenate([carry_in.pending, trace.times])
    return times, float(carry_in.clock)


def _managed_engine(device: DeviceModel, w_tr: Optional[WorkloadProfile],
                    w_in: WorkloadProfile, pm: PowerMode, bs: int,
                    trace: ArrivalTrace, seed: int = 0,
                    tau_cap: Optional[int] = None,
                    carry_in: Optional[QueueState] = None) -> ExecutionReport:
    """Fulcrum managed interleaving: one DNN at a time, switched at minibatch
    boundaries; training fills slack conservatively (never delaying the next
    inference batch). ``tau_cap`` bounds slack-fill at the plan's committed
    tau_tr minibatches per cycle. ``carry_in`` resumes from a previous
    window's queue state; the report's ``queue_state`` carries the trailing
    partial minibatch and the engine clock out for the next window."""
    t_in, p_in = _time_power(device, w_in, pm, bs)
    t_tr, p_tr = _time_power(device, w_tr, pm, None) if w_tr \
        else (float("inf"), 0.0)
    times, clock = _carry_times(trace, carry_in)
    ready = _batch_ready(times, bs)
    c = _managed_completions(ready, t_in, clock)
    trained = 0
    if w_tr:
        fills = _fill_counts(ready, c, t_tr, clock)
        if tau_cap is not None:
            fills = np.minimum(fills, max(0, int(tau_cap)))
        trained = int(fills.sum())
    power = max(p_in, p_tr if trained else 0.0)
    state = QueueState(times[ready.size * bs:],
                       float(c[-1]) if c.size else clock)
    attr = _attribute_power(power, [c.size * t_in,
                                    trained * t_tr if trained else 0.0])
    return ExecutionReport("managed", _latencies(c, times, bs), trained,
                           trace.duration, power, trace, queue_state=state,
                           attributed_power=attr[0])


def _native_engine(device: DeviceModel, w_tr: WorkloadProfile,
                   w_in: WorkloadProfile, pm: PowerMode, bs: int,
                   trace: ArrivalTrace, seed: int = 0,
                   tau_cap: Optional[int] = None) -> ExecutionReport:
    """Native kernel-level time-sharing: inference contends with training
    (~2x slowdown +- jitter); training gets the leftover GPU share."""
    rng = np.random.default_rng(seed)
    t_in, p_in = _time_power(device, w_in, pm, bs)
    t_tr, p_tr = _time_power(device, w_tr, pm, None)
    ready = _batch_ready(trace.times, bs)
    exec_t = t_in * (1.0 + rng.uniform(0.5, 1.6, ready.size))
    c = _queue_completions(ready, exec_t)
    train_share = max(0.0, trace.duration - float(exec_t.sum())) \
        * float(rng.uniform(0.85, 0.95))
    trained = int(train_share / t_tr)
    return ExecutionReport("native", _latencies(c, trace.times, bs), trained,
                           trace.duration, max(p_in, p_tr), trace)


def _streams_engine(device: DeviceModel, w_tr: WorkloadProfile,
                    w_in: WorkloadProfile, pm: PowerMode, bs: int,
                    trace: ArrivalTrace, seed: int = 0,
                    tau_cap: Optional[int] = None) -> ExecutionReport:
    """CUDA-streams space sharing, inference on the high-priority stream:
    throughput-friendly, but non-deterministic block-level resource blocking
    fattens the tail."""
    rng = np.random.default_rng(seed)
    t_in, p_in = _time_power(device, w_in, pm, bs)
    t_tr, p_tr = _time_power(device, w_tr, pm, None)
    ready = _batch_ready(trace.times, bs)
    K = ready.size
    slowdown = 1.0 + rng.uniform(0.05, 0.45, K)
    blocked = rng.random(K) < 0.18
    extra = rng.uniform(0.5, 2.0, K) * (t_tr / max(t_in, 1e-6))
    exec_t = t_in * (slowdown + np.where(blocked, extra, 0.0))
    c = _queue_completions(ready, exec_t)
    trained = int(trace.duration * float(rng.uniform(0.75, 0.9)) / t_tr)
    return ExecutionReport("streams", _latencies(c, trace.times, bs), trained,
                           trace.duration, max(p_in, p_tr) * 1.03, trace)


ENGINES: dict[str, Callable[..., ExecutionReport]] = {
    "managed": _managed_engine,
    "native": _native_engine,
    "streams": _streams_engine,
}


# ---------------------------------------------------------------------------
# jax backend: the managed kernel as a vmapped max-plus scan.
# c_k = max(c_{k-1}, ready_k) + e_k is the composition of affine max-plus
# maps f_k(x) = max(x + a_k, b_k) with a_k = e_k, b_k = ready_k + e_k;
# (f_r . f_l) keeps that form with (a, b) = (a_l + a_r, max(b_l + a_r, b_r)),
# so an associative scan over the (a, b) pairs yields every prefix
# composition, and c_k = prefix_k applied to c_0 = 0 = max(A_k, B_k).
# Lanes are padded with ready = +inf, exec = 0 (absorbing for both ops).
# ---------------------------------------------------------------------------

# the compiled scan runner, under "managed" (tests monkeypatch that key)
_JAX_ENGINE_CACHE: dict = {}

# lanes dispatched per compiled call: bounds the padded chunk matrix to
# _LANE_CHUNK x K_pad floats (~4 MB at K=64) however many lanes a sweep has
_LANE_CHUNK = 8192


def engine_trace_count() -> int:
    """Number of scan-kernel (re)traces since import: it lets tests pin the
    shape-bucketing no-retrace contract."""
    return obs.counted("trace.engine")


@contextlib.contextmanager
def _quiet_donation():
    # donation is best-effort: on CPU XLA may decline a buffer and warn.
    # The fallback (a copy) is exactly the pre-donation behavior.
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _maxplus_combine(left, right):
    """Compose two max-plus affine maps given as ``(a, b)`` pairs: the
    associative operator of the engine's scan (and the fused window's)."""
    import jax.numpy as jnp
    a_l, b_l = left
    a_r, b_r = right
    return a_l + a_r, jnp.maximum(b_l + a_r, b_r)


def _jax_engine() -> Callable:
    if "managed" in _JAX_ENGINE_CACHE:
        return _JAX_ENGINE_CACHE["managed"]
    jax, jnp, enable_x64 = require_jax()

    def one_lane(ready, exec_t, t_tr, tau_cap, clock):
        a, b = jax.lax.associative_scan(_maxplus_combine,
                                        (exec_t, ready + exec_t))
        # prefix compositions applied to c_0 = clock (the carried window
        # boundary; 0 for a fresh run): c_k = max(clock + A_k, B_k)
        c = jnp.maximum(clock + a, b)
        start = jnp.concatenate([jnp.full(1, clock), c[:-1]])
        # floor estimate only — no boundary replay on-accelerator, hence the
        # jax backend's tolerance (not bitwise) contract for trained counts
        fills = jnp.clip(jnp.floor((ready - start) / t_tr), 0.0, tau_cap)
        fills = jnp.where(jnp.isfinite(ready), fills, 0.0)
        return c, fills.sum()

    def batch(ready, exec_t, t_tr, tau_cap, clock):
        obs.count("trace.engine")              # fires at trace time only
        return jax.vmap(one_lane)(ready, exec_t, t_tr, tau_cap, clock)

    # the padded event buffers are fresh per-call copies — donate them so
    # XLA reuses the allocation instead of holding both live
    kernel = jax.jit(batch, donate_argnums=(0, 1))

    def run(ready, exec_t, t_tr, tau_cap, clock):
        record_dispatch("engine")
        with enable_x64(), _quiet_donation():
            c, trained = kernel(jnp.asarray(ready), jnp.asarray(exec_t),
                                jnp.asarray(t_tr), jnp.asarray(tau_cap),
                                jnp.asarray(clock))
        return np.asarray(c), np.asarray(trained)

    _JAX_ENGINE_CACHE["managed"] = with_program(run, kernel)
    return run


def _pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << max(0, n - 1).bit_length())


def _pad_lanes(readies: Sequence[np.ndarray], execs: Sequence[np.ndarray],
               lanes_pad: Optional[int] = None,
               k_pad: Optional[int] = None) -> tuple[np.ndarray, np.ndarray]:
    """Stack ragged per-lane event vectors into (lanes_pad, k_pad) arrays.
    Both axes default to the next power of two so trace-length and
    lane-count jitter across calls reuses a handful of jit compilations
    instead of one per distinct shape. Padding lanes/events are absorbing
    (ready = +inf, exec = 0)."""
    if k_pad is None:
        k_pad = _pow2(max((r.size for r in readies), default=0))
    if lanes_pad is None:
        lanes_pad = _pow2(len(readies))
    ready = np.full((lanes_pad, k_pad), np.inf)
    exec_t = np.zeros((lanes_pad, k_pad))
    for i, (r, e) in enumerate(zip(readies, execs)):
        ready[i, :r.size] = r
        exec_t[i, :e.size] = e
    return ready, exec_t


def _run_engine(readies: Sequence[np.ndarray],
                execs: Sequence[np.ndarray], t_trs: np.ndarray,
                tau_caps: np.ndarray, clocks: np.ndarray,
                ) -> tuple[list[np.ndarray], np.ndarray]:
    """Chunked lane dispatch for the jax scan.

    Lanes run in ``_LANE_CHUNK``-sized chunks so 10^5-lane sweeps never
    materialize one giant padded matrix; every chunk is padded to a
    power-of-two lane bucket and ONE global power-of-two event count
    (computed over *all* lanes), so all full chunks — and the same-shaped
    chunks of the next sweep — hit the same compiled program. Padding lanes
    are absorbing (+inf ready, 0 exec, +inf t_tr, clock 0). Returns each
    lane's trimmed completion vector plus the per-lane fill sums."""
    run = _jax_engine()
    n = len(readies)
    k_pad = _pow2(max((r.size for r in readies), default=0))
    comps: list[np.ndarray] = []
    trained = np.empty(n)
    for s in range(0, n, _LANE_CHUNK):
        e = min(n, s + _LANE_CHUNK)
        m = e - s
        lanes_pad = min(_LANE_CHUNK, _pow2(m))
        ready, exec_t = _pad_lanes(readies[s:e], execs[s:e],
                                   lanes_pad=lanes_pad, k_pad=k_pad)
        ttr = np.full(lanes_pad, np.inf)
        ttr[:m] = t_trs[s:e]
        cap = np.full(lanes_pad, np.inf)
        cap[:m] = tau_caps[s:e]
        clk = np.zeros(lanes_pad)
        clk[:m] = clocks[s:e]
        c, f = run(ready, exec_t, ttr, cap, clk)
        comps.extend(c[i, :readies[s + i].size] for i in range(m))
        trained[s:e] = f[:m]
    return comps, trained


def _tau_array(tau_caps: Sequence[Optional[int]]) -> np.ndarray:
    return np.array([np.inf if c is None else float(max(0, int(c)))
                     for c in tau_caps])


# ---------------------------------------------------------------------------
# multi-tenant managed interleaving: N inference streams + training fill
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MultiTenantReport:
    """Per-tenant execution reports plus the shared training/power account
    of one N-stream managed run."""
    streams: list                     # one ExecutionReport per tenant
    train_minibatches: int
    duration: float
    power: float
    trace: Optional[ArrivalTrace] = None   # the merged trace that was run
    queue_state: Optional[QueueState] = dataclasses.field(  # end-of-window
        default=None, repr=False, compare=False)            # engine state
    # graceful-degradation accounting (§5.4 burst survival) across all
    # tenants, filled by the serving drivers / runtime admission gate
    shed_requests: int = 0
    deferred_requests: int = 0
    goodput: Optional[float] = None
    # the training job's time-weighted share of the device power; each
    # tenant's share is on its stream report — together they sum to
    # ``power`` (0 everywhere for an idle window)
    train_attributed_power: Optional[float] = None

    @property
    def train_throughput(self) -> float:
        return self.train_minibatches / self.duration

    def worst_latency_quantile(self, q: float) -> float:
        return max((r.latency_quantile(q) for r in self.streams), default=0.0)

    def violation_rates(self, budgets: Sequence[float]) -> list:
        return [r.violation_rate(b) for r, b in zip(self.streams, budgets)]


def _carry_stream_traces(traces: Sequence[ArrivalTrace],
                         carry_in: Optional[QueueState],
                         ) -> tuple[list[ArrivalTrace], float]:
    """Per-stream effective traces of a multi-tenant window: each stream's
    carried pending requests re-enter ahead of its window arrivals."""
    if carry_in is None:
        return list(traces), 0.0
    out = []
    for j, tr in enumerate(traces):
        pend = carry_in.pending_for(j)
        times = tr.times if pend.size == 0 \
            else np.concatenate([pend, tr.times])
        out.append(ArrivalTrace(times, tr.duration, tr.kind))
    return out, float(carry_in.clock)


def _multi_tenant_state(times_by_stream: Sequence[np.ndarray],
                        bss: Sequence[int], completions: np.ndarray,
                        clock: float) -> QueueState:
    """End-of-window queue state of an N-stream run: each stream's trailing
    partial minibatch, merged back into (time, stream) order."""
    pend = [t[(t.size // int(b)) * int(b):]
            for t, b in zip(times_by_stream, bss)]
    times = np.concatenate(pend) if pend else np.empty(0)
    ids = np.concatenate([np.full(p.size, j, np.int64)
                          for j, p in enumerate(pend)]) \
        if pend else np.empty(0, np.int64)
    order = np.argsort(times, kind="stable")
    out_clock = float(completions[-1]) if completions.size else clock
    return QueueState(times[order], out_clock, ids[order])


def _merge_events(traces: Sequence[ArrivalTrace], bss: Sequence[int],
                  t_ins: Sequence[float]):
    """Batch-ready events of all streams merged into device order: a stable
    sort on ready time, ties by stream index (the scalar loop's order).
    Returns (ready, exec_t, stream_of_event)."""
    readies = [_batch_ready(tr.times, int(b)) for tr, b in zip(traces, bss)]
    ready = np.concatenate(readies) if readies else np.empty(0)
    sid = np.concatenate([np.full(r.size, j, np.int64)
                          for j, r in enumerate(readies)]) \
        if readies else np.empty(0, np.int64)
    order = np.argsort(ready, kind="stable")
    ready, sid = ready[order], sid[order]
    exec_t = np.asarray(t_ins, np.float64)[sid] if ready.size \
        else np.empty(0)
    return ready, exec_t, sid


def simulate_multi_tenant(device: DeviceModel,
                          w_tr: Optional[WorkloadProfile],
                          stream_workloads: Sequence[WorkloadProfile],
                          pm: PowerMode, bss: Sequence[int],
                          traces: Sequence[ArrivalTrace],
                          tau_cap: Optional[int] = None,
                          backend: Optional[str] = None,
                          carry_in: Optional[QueueState] = None,
                          ) -> MultiTenantReport:
    """N-stream managed interleaving on one device: streams' minibatches are
    served in ready order (one DNN at a time), training fills the remaining
    slack conservatively. With one stream this is exactly the pair managed
    engine (and the seed scalar loop) — the engine's exactness contract.
    ``backend="jax"`` routes through the batched scan engine (one lane).
    ``carry_in`` resumes from a previous window's per-stream queue state."""
    n = len(stream_workloads)
    if not (len(bss) == len(traces) == n):
        raise ValueError("stream workloads / batch sizes / traces must align")
    backend = resolve_backend(backend)
    if backend != "numpy":
        return simulate_multi_tenant_batch(
            device, w_tr, [stream_workloads], [pm], [bss], [traces],
            tau_caps=[tau_cap], carry_ins=[carry_in], backend=backend)[0]
    tps = [_time_power(device, w, pm, int(b))
           for w, b in zip(stream_workloads, bss)]
    t_ins = [t for t, _ in tps]
    t_tr, p_tr = _time_power(device, w_tr, pm, None) if w_tr \
        else (float("inf"), 0.0)
    eff_traces, clock = _carry_stream_traces(traces, carry_in)
    ready, exec_t, sid = _merge_events(eff_traces, bss, t_ins)
    c = _managed_completions_var(ready, exec_t, clock)
    trained = 0
    if w_tr:
        fills = _fill_counts(ready, c, t_tr, clock)
        if tau_cap is not None:
            fills = np.minimum(fills, max(0, int(tau_cap)))
        trained = int(fills.sum())
    power = p_tr if trained else 0.0
    for _, p_in in tps:
        power = max(power, p_in)
    duration = max((tr.duration for tr in traces), default=0.0)
    reports, busys = [], []
    for j, (tr, b) in enumerate(zip(eff_traces, bss)):
        comp_j = c[sid == j]
        lat = np.repeat(comp_j, int(b)) - tr.times[:comp_j.size * int(b)]
        busys.append(comp_j.size * t_ins[j])
        reports.append(ExecutionReport("managed", lat, 0, tr.duration,
                                       power, tr))
    attr = _attribute_power(power,
                            busys + [trained * t_tr if trained else 0.0])
    for rep, a in zip(reports, attr):
        rep.attributed_power = a
    state = _multi_tenant_state([tr.times for tr in eff_traces], bss, c,
                                clock)
    return MultiTenantReport(reports, trained, duration, power,
                             ArrivalTrace.merge(eff_traces),
                             queue_state=state,
                             train_attributed_power=attr[-1])


def simulate_multi_tenant_batch(
        device: DeviceModel, w_tr: Optional[WorkloadProfile],
        stream_workloads: Sequence[Sequence[WorkloadProfile]],
        pms: Sequence[PowerMode], bsss: Sequence[Sequence[int]],
        tracess: Sequence[Sequence[ArrivalTrace]],
        tau_caps: Optional[Sequence[Optional[int]]] = None,
        backend: Optional[str] = None,
        carry_ins: Optional[Sequence[Optional[QueueState]]] = None,
        ) -> list[MultiTenantReport]:
    """Run many N-stream managed simulations as one batch (one lane per
    multi-tenant run; lanes may have *different* tenant counts — the merged
    event axis is padded per lane, so a 2-tenant and a 4-tenant run share
    one vmapped program). Per-stream event merging (stable time sort, ties
    by stream index) happens host-side exactly as the NumPy engine does;
    only the scan arithmetic differs on jax. All reports across all lanes
    and streams share one vectorized report-builder pass. ``carry_ins``
    gives each lane a carried per-stream ``QueueState``."""
    n = len(pms)
    if not (len(stream_workloads) == len(bsss) == len(tracess) == n):
        raise ValueError("stream_workloads / pms / bsss / tracess must align")
    caps = list(tau_caps) if tau_caps is not None else [None] * n
    if len(caps) != n:
        raise ValueError("tau_caps must align with the lanes")
    carries = list(carry_ins) if carry_ins is not None else [None] * n
    if len(carries) != n:
        raise ValueError("carry_ins must align with the lanes")
    if n == 0:
        return []
    backend = resolve_backend(backend)
    if backend == "numpy":
        # pass the resolved backend through: a default (env-var) jax
        # request must not bounce each lane back into the jax path
        reports = [simulate_multi_tenant(device, w_tr, ws, pm, bss, traces,
                                         tau_cap=cap, backend="numpy",
                                         carry_in=ci)
                   for ws, pm, bss, traces, cap, ci
                   in zip(stream_workloads, pms, bsss, tracess, caps,
                          carries)]
        _presort_reports([r for mt in reports for r in mt.streams])
        return reports
    lanes = []
    for ws, pm, bss, traces, cap, ci in zip(stream_workloads, pms, bsss,
                                            tracess, caps, carries):
        if not (len(ws) == len(bss) == len(traces)):
            raise ValueError("stream workloads / batch sizes / traces "
                             "must align")
        tps = [_time_power(device, w, pm, int(b)) for w, b in zip(ws, bss)]
        ttr = _time_power(device, w_tr, pm, None) if w_tr else (np.inf, 0.0)
        eff, clock = _carry_stream_traces(traces, ci)
        ready, exec_t, sid = _merge_events(eff, bss, [t for t, _ in tps])
        lanes.append((tps, ttr, ready, exec_t, sid, eff, clock))
    comps, trained_f = _run_engine([ln[2] for ln in lanes],
                                   [ln[3] for ln in lanes],
                                   np.array([ln[1][0] for ln in lanes]),
                                   _tau_array(caps),
                                   np.array([ln[6] for ln in lanes]))
    out, flat = [], []
    for i, (tps, ttr, ready_i, _, sid, eff, clock) in enumerate(lanes):
        comp = comps[i]
        trained = int(round(float(trained_f[i]))) if w_tr else 0
        power = ttr[1] if trained else 0.0
        for _, p_in in tps:
            power = max(power, p_in)
        duration = max((tr.duration for tr in tracess[i]), default=0.0)
        streams, busys = [], []
        for j, (tr, b) in enumerate(zip(eff, bsss[i])):
            comp_j = comp[sid == j]
            lat = np.repeat(comp_j, int(b)) - tr.times[:comp_j.size * int(b)]
            busys.append(comp_j.size * tps[j][0])
            streams.append(ExecutionReport("managed", lat, 0, tr.duration,
                                           power, tr))
        attr = _attribute_power(power,
                                busys + [trained * ttr[0] if trained
                                         else 0.0])
        for rep, a in zip(streams, attr):
            rep.attributed_power = a
        flat.extend(streams)
        state = _multi_tenant_state([tr.times for tr in eff], bsss[i], comp,
                                    clock)
        out.append(MultiTenantReport(streams, trained, duration, power,
                                     ArrivalTrace.merge(eff),
                                     queue_state=state,
                                     train_attributed_power=attr[-1]))
    _presort_reports(flat)
    return out


def simulate(device: DeviceModel, w_tr: Optional[WorkloadProfile],
             w_in: WorkloadProfile, pm: PowerMode, bs: int,
             trace: ArrivalTrace, approach: str = "managed", seed: int = 0,
             tau_cap: Optional[int] = None,
             backend: Optional[str] = None,
             carry_in: Optional[QueueState] = None) -> ExecutionReport:
    """Run one execution approach over an arrival trace.

    ``backend`` selects the engine implementation for the deterministic
    managed kernel: ``"numpy"`` (the reference) or ``"jax"`` (max-plus scan);
    ``None`` resolves via ``core.backend.resolve_backend``. The stochastic
    native/streams models always run on NumPy. ``carry_in`` (managed only)
    resumes from a previous window's ``QueueState``."""
    try:
        engine = ENGINES[approach]
    except KeyError:
        raise ValueError(f"unknown approach {approach!r}; "
                         f"use one of {sorted(ENGINES)}") from None
    if carry_in is not None and approach != "managed":
        raise ValueError("carry-in backlog is only defined for the "
                         "deterministic managed approach")
    backend = resolve_backend(backend)
    if backend != "numpy" and approach == "managed":
        return simulate_batch(device, w_tr, w_in, [pm], [bs], [trace],
                              tau_caps=[tau_cap], carry_ins=[carry_in],
                              backend=backend)[0]
    if approach == "managed":
        return engine(device, w_tr, w_in, pm, bs, trace, seed, tau_cap,
                      carry_in)
    return engine(device, w_tr, w_in, pm, bs, trace, seed, tau_cap)


def simulate_batch(device: DeviceModel, w_tr: Optional[WorkloadProfile],
                   w_in: WorkloadProfile, pms: Sequence[PowerMode],
                   bss: Sequence[int], traces: Sequence[ArrivalTrace],
                   tau_caps: Optional[Sequence[Optional[int]]] = None,
                   approach: str = "managed", seed: int = 0,
                   backend: Optional[str] = None,
                   carry_ins: Optional[Sequence[Optional[QueueState]]] = None,
                   devices: Optional[Sequence[DeviceModel]] = None,
                   ) -> list[ExecutionReport]:
    """Run many (power mode, batch size, trace) simulations as one batch.

    One report per lane. On ``backend="jax"`` all managed lanes run as a
    single jit + vmap max-plus-scan program (lanes padded to a shared event
    count); on NumPy the per-lane kernels run in a loop. Either way the
    reports' quantile/violation caches are filled by the vectorized report
    builder. Only the managed approach is deterministic enough to batch on
    jax; native/streams lanes always use the seeded NumPy models.
    ``carry_ins`` (managed only) gives each lane a carried ``QueueState``.
    ``devices`` gives each lane its own device model (the fleet tier: lanes
    ARE devices); the scan arithmetic is unchanged — heterogeneity enters
    only through each lane's (t, p) timings."""
    n = len(pms)
    if not (len(bss) == len(traces) == n):
        raise ValueError("pms / bss / traces must align")
    caps = list(tau_caps) if tau_caps is not None else [None] * n
    if len(caps) != n:
        raise ValueError("tau_caps must align with the lanes")
    carries = list(carry_ins) if carry_ins is not None else [None] * n
    if len(carries) != n:
        raise ValueError("carry_ins must align with the lanes")
    devs = list(devices) if devices is not None else [device] * n
    if len(devs) != n:
        raise ValueError("devices must align with the lanes")
    if approach != "managed" and any(ci is not None for ci in carries):
        raise ValueError("carry-in backlog is only defined for the "
                         "deterministic managed approach")
    if n == 0:
        return []
    backend = resolve_backend(backend)
    if backend == "numpy" or approach != "managed":
        engine = ENGINES[approach]
        if approach == "managed":
            reports = [engine(dv, w_tr, w_in, pm, int(bs), tr, seed, cap,
                              ci)
                       for dv, pm, bs, tr, cap, ci
                       in zip(devs, pms, bss, traces, caps, carries)]
        else:
            reports = [engine(dv, w_tr, w_in, pm, int(bs), tr, seed, cap)
                       for dv, pm, bs, tr, cap
                       in zip(devs, pms, bss, traces, caps)]
        _presort_reports(reports)
        return reports
    tps = [_time_power(dv, w_in, pm, int(bs))
           for dv, pm, bs in zip(devs, pms, bss)]
    ttr = [_time_power(dv, w_tr, pm, None) if w_tr else (np.inf, 0.0)
           for dv, pm in zip(devs, pms)]
    lane_times = [_carry_times(tr, ci) for tr, ci in zip(traces, carries)]
    readies = [_batch_ready(times, int(bs))
               for (times, _), bs in zip(lane_times, bss)]
    execs = [np.broadcast_to(np.float64(t), r.shape)
             for (t, _), r in zip(tps, readies)]
    comps, trained_f = _run_engine(readies, execs,
                                   np.array([t for t, _ in ttr]),
                                   _tau_array(caps),
                                   np.array([cl for _, cl in lane_times]))
    reports = []
    for i, (tr, bs) in enumerate(zip(traces, bss)):
        comp = comps[i]
        times, clock = lane_times[i]
        trained = int(round(float(trained_f[i]))) if w_tr else 0
        power = max(tps[i][1], ttr[i][1] if trained else 0.0)
        state = QueueState(times[comp.size * int(bs):],
                           float(comp[-1]) if comp.size else clock)
        attr = _attribute_power(power, [comp.size * tps[i][0],
                                        trained * ttr[i][0] if trained
                                        else 0.0])
        reports.append(ExecutionReport(
            "managed", _latencies(comp, times, int(bs)), trained,
            tr.duration, power, tr, queue_state=state,
            attributed_power=attr[0]))
    _presort_reports(reports)
    return reports


# ---------------------------------------------------------------------------
# scalar reference loops (the seed implementations, generalized to traces).
# Kept as the verification oracle for the identity tests and the baseline
# for benchmarks/bench_interleave_engine.py — not for production use.
# ---------------------------------------------------------------------------

def managed_scalar(device: DeviceModel, w_tr: Optional[WorkloadProfile],
                   w_in: WorkloadProfile, pm: PowerMode, bs: int,
                   trace: ArrivalTrace, tau_cap: Optional[int] = None,
                   carry_in: Optional[QueueState] = None) -> ExecutionReport:
    t_in, p_in = device.time_power(w_in, pm, bs)
    t_tr, p_tr = device.time_power(w_tr, pm) if w_tr else (float("inf"), 0.0)
    times, clock = _carry_times(trace, carry_in)
    arrivals = times.tolist()
    latencies: list[float] = []
    now, trained, i = clock, 0, 0
    while i + bs <= len(arrivals):
        batch_ready = arrivals[i + bs - 1]
        filled = 0
        while w_tr and now + t_tr <= batch_ready \
                and (tau_cap is None or filled < tau_cap):
            now += t_tr
            trained += 1
            filled += 1
        now = max(now, batch_ready)
        now += t_in
        latencies.extend(now - arrivals[j] for j in range(i, i + bs))
        i += bs
    power = max(p_in, p_tr if trained else 0.0)
    attr = _attribute_power(power, [(i // bs) * t_in,
                                    trained * t_tr if trained else 0.0])
    return ExecutionReport("managed", latencies, trained, trace.duration,
                           power, trace,
                           queue_state=QueueState(times[i:], now),
                           attributed_power=attr[0])


def batch_ready_events(arrivals: Sequence[Sequence[float]],
                       bss: Sequence[int]) -> list[tuple]:
    """Per-stream batch-ready events merged into device order: one
    ``(ready time, stream index, start request index)`` tuple per full
    minibatch, sorted by ready time with ties broken by stream then
    position — the managed engines' merge order. Shared by the scalar
    reference and the real runtime so their replay order cannot drift."""
    events = []
    for j, (arr, b) in enumerate(zip(arrivals, bss)):
        b = int(b)
        for k in range(len(arr) // b):
            events.append((arr[k * b + b - 1], j, k * b))
    events.sort()
    return events


def multi_tenant_scalar(device: DeviceModel, w_tr: Optional[WorkloadProfile],
                        stream_workloads: Sequence[WorkloadProfile],
                        pm: PowerMode, bss: Sequence[int],
                        traces: Sequence[ArrivalTrace],
                        tau_cap: Optional[int] = None,
                        carry_in: Optional[QueueState] = None,
                        ) -> MultiTenantReport:
    """Scalar reference for the N-stream managed engine: replay every
    batch-ready event in (time, stream) order with the seed loop's float
    ops. One stream degenerates to ``managed_scalar``."""
    tps = [device.time_power(w, pm, int(b))
           for w, b in zip(stream_workloads, bss)]
    t_tr, p_tr = device.time_power(w_tr, pm) if w_tr else (float("inf"), 0.0)
    eff_traces, clock = _carry_stream_traces(traces, carry_in)
    arrivals = [tr.times.tolist() for tr in eff_traces]
    events = batch_ready_events(arrivals, bss)
    latencies: list[list[float]] = [[] for _ in stream_workloads]
    now, trained = clock, 0
    for ready, j, start in events:
        filled = 0
        while w_tr and now + t_tr <= ready \
                and (tau_cap is None or filled < tau_cap):
            now += t_tr
            trained += 1
            filled += 1
        now = max(now, ready)
        now += tps[j][0]
        latencies[j].extend(now - arrivals[j][i]
                            for i in range(start, start + int(bss[j])))
    power = p_tr if trained else 0.0
    for _, p_in in tps:
        power = max(power, p_in)
    duration = max((tr.duration for tr in traces), default=0.0)
    reports = [ExecutionReport("managed", lat, 0, tr.duration, power, tr)
               for lat, tr in zip(latencies, eff_traces)]
    attr = _attribute_power(
        power, [(len(lat) // int(b)) * tps[j][0]
                for j, (lat, b) in enumerate(zip(latencies, bss))]
        + [trained * t_tr if trained else 0.0])
    for rep, a in zip(reports, attr):
        rep.attributed_power = a
    state = _multi_tenant_state(
        [tr.times for tr in eff_traces], bss,
        np.asarray([now] if events else [], np.float64), clock)
    return MultiTenantReport(reports, trained, duration, power,
                             ArrivalTrace.merge(eff_traces),
                             queue_state=state,
                             train_attributed_power=attr[-1])


def native_scalar(device: DeviceModel, w_tr: WorkloadProfile,
                  w_in: WorkloadProfile, pm: PowerMode, bs: int,
                  trace: ArrivalTrace, seed: int = 0) -> ExecutionReport:
    rng = random.Random(seed)
    t_in, p_in = device.time_power(w_in, pm, bs)
    t_tr, p_tr = device.time_power(w_tr, pm)
    arrivals = trace.times.tolist()
    latencies: list[float] = []
    now, i, infer_busy = 0.0, 0, 0.0
    while i + bs <= len(arrivals):
        now = max(now, arrivals[i + bs - 1])
        exec_t = t_in * (1.0 + rng.uniform(0.5, 1.6))
        now += exec_t
        infer_busy += exec_t
        latencies.extend(now - arrivals[j] for j in range(i, i + bs))
        i += bs
    train_share = max(0.0, trace.duration - infer_busy) * rng.uniform(0.85, 0.95)
    trained = int(train_share / t_tr)
    return ExecutionReport("native", latencies, trained, trace.duration,
                           max(p_in, p_tr), trace)


def streams_scalar(device: DeviceModel, w_tr: WorkloadProfile,
                   w_in: WorkloadProfile, pm: PowerMode, bs: int,
                   trace: ArrivalTrace, seed: int = 0) -> ExecutionReport:
    rng = random.Random(seed)
    t_in, p_in = device.time_power(w_in, pm, bs)
    t_tr, p_tr = device.time_power(w_tr, pm)
    arrivals = trace.times.tolist()
    latencies: list[float] = []
    now, i = 0.0, 0
    while i + bs <= len(arrivals):
        now = max(now, arrivals[i + bs - 1])
        slowdown = 1.0 + rng.uniform(0.05, 0.45)
        if rng.random() < 0.18:
            slowdown += rng.uniform(0.5, 2.0) * t_tr / max(t_in, 1e-6)
        now += t_in * slowdown
        latencies.extend(now - arrivals[j] for j in range(i, i + bs))
        i += bs
    trained = int(trace.duration * rng.uniform(0.75, 0.9) / t_tr)
    return ExecutionReport("streams", latencies, trained, trace.duration,
                           max(p_in, p_tr) * 1.03, trace)
