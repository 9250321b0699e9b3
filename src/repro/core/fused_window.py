"""The fused fleet window: solve + simulate as ONE jitted program.

The PR-8/9 fleet loop is dispatch-bound on jax: every window pays up to four
host-synchronized ``solve_infer_fleet_batch`` rungs (each with Python-side
``InferProblem`` construction and ``Solution`` materialization), an optional
per-device admission loop, and a separate ``simulate_batch`` launch. At
K=512 that overhead leaves the batched jax path ~4x *behind* NumPy
(BENCH_fleet.json) — the accelerator idles between launches.

This module collapses the whole window into one persistent-jitted program
per (K-bucket, event-bucket) shape:

 * **masked ladder rungs** — the PR-5 planning ladder (interval solve ->
   dead-zone high end -> point estimate -> nominal-budget retry) runs as
   four unconditional masked argmins over the (device, grid-entry) plane,
   combined by ``jnp.where`` gates over the device axis that replicate the
   host loop's "only still-unsolved devices" masks. Each rung replays
   ``grid_eval``'s fleet-kernel row math exactly (same elementwise IEEE
   ops, same first-occurrence argmin), so the selected entries match the
   per-rung path bitwise; computing a rung for an already-solved device is
   free parallel work whose result the gate discards.
 * **in-program admission** — the exact deadline-drop recurrence
   (``controller._admit_mask_multi`` at one stream) expressed as a
   ``lax.scan`` over arrivals with a ``max_bs`` ring buffer of
   forming-batch members and a bounded ``while_loop`` for the
   drop-from-front rule (total drops across a window are <= the arrival
   count). Rejected requests are compacted out by a stable sort against
   +inf — admitted times are a nondecreasing subsequence, so the sort
   yields exactly the trimmed vector the unfused path would rebuild on the
   host.
 * **fused execution** — the selected ``(bs, t_in)`` lanes feed straight
   into the max-plus associative scan (the PR-4 engine kernel, same
   ``simulate._maxplus_combine``), with batch-ready events gathered by
   traced-``bs`` indexing instead of host-side strided slicing. Mode-switch
   costs are charged in-program from the previous window's committed mode
   ids.

Solve -> admit -> simulate never crosses the host boundary: one launch per
window (``backend.dispatch_count("fused")`` tracks it; the legacy path pays
up to five). The grid tensors ride along as device-resident arrays
(``grid_eval.device_grid_arrays``), uploaded once per grid instance.

Exactness: this is a jax-tier program — the ladder/admission arithmetic is
bitwise the reference's (reassociation-free elementwise ops and compares),
while completion times inherit the associative scan's tolerance rung
(``docs/exactness.md``); the unfused NumPy path remains the authoritative
reference and the default. Shapes are pow2-bucketed on both the device and
event axes, so steady-state serving hits one compilation —
``fleet_trace_count()`` pins the no-retrace contract like the solver and
engine counters. The (K_pad x grid) rung temporaries are materialized
unchunked (one program is the point); at the 441x5 grid that is ~9 MB per
temporary at K=512 — callers far beyond K~4096 should stay on the chunked
per-rung path.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro import obs
from repro.core.backend import record_dispatch, require_jax, with_program
from repro.core.grid_eval import ObservationGrid, device_grid_arrays
from repro.core.simulate import _maxplus_combine, _pow2

# one compiled program per (trims, max_bs) variant x jit shape bucket
_FUSED_CACHE: dict = {}

# the admission slack, exactly controller._admit_mask_multi's
_ADMIT_EPS = 1e-12


def fleet_trace_count() -> int:
    """Number of fused fleet-window (re)traces since import."""
    return obs.counted("trace.fleet")


def grid_mode_ids(grid: ObservationGrid) -> np.ndarray:
    """Per-grid-entry power-mode ids (first-appearance order), memoized on
    the grid: the fused program compares these ints to charge mode-switch
    costs in-program (``PowerMode`` equality == id equality)."""
    ids = grid.__dict__.get("_mode_ids")
    if ids is None:
        first: dict = {}
        ids = np.array([first.setdefault(pm, len(first))
                        for pm in grid.modes], np.int32)
        grid.__dict__["_mode_ids"] = ids
    return ids


def _device_mode_ids(grid: ObservationGrid):
    """Device-resident copy of ``grid_mode_ids``, cached like the columns."""
    dev = grid.__dict__.get("_device_mode_ids")
    if dev is None:
        _jax, jnp, enable_x64 = require_jax()
        with enable_x64():
            dev = jnp.asarray(grid_mode_ids(grid))
        grid.__dict__["_device_mode_ids"] = dev
    return dev


def _fused_kernel(trims: bool, max_bs: int):
    """The compiled window program for one (admission-on, ring-size)
    variant; jit handles the per-shape-bucket caching underneath."""
    key = (trims, max_bs)
    if key in _FUSED_CACHE:
        return _FUSED_CACHE[key]
    jax, jnp, enable_x64 = require_jax()

    def admit_lane(tv, n, bs, t_in, budget, clock):
        # controller._admit_mask_multi at one stream, as a scan: ring
        # buffer of forming-batch member indices (members are a window
        # [h, h+m) mod max_bs), one bounded drop loop per filled batch.
        # Same float ops, same 1e-12 slack, so the mask matches the host
        # recurrence bitwise.
        T = tv.shape[0]

        def step(carry, i):
            admit, c, buf, h, m = carry
            valid = i < n
            pos = (h + m) % max_bs
            buf = buf.at[pos].set(jnp.where(valid, i, buf[pos]))
            m = jnp.where(valid, m + 1, m)
            full = valid & (m == bs)
            comp = jnp.maximum(c, tv[i]) + t_in

            def cond(s):
                _a, h_, m_ = s
                j = buf[h_ % max_bs]
                return full & (m_ > 0) & (comp - tv[j] > budget + _ADMIT_EPS)

            def body(s):
                a_, h_, m_ = s
                j = buf[h_ % max_bs]
                return a_.at[j].set(False), h_ + 1, m_ - 1

            admit, h, m = jax.lax.while_loop(cond, body, (admit, h, m))
            commit = full & (m == bs)
            c = jnp.where(commit, comp, c)
            m = jnp.where(commit, 0, m)
            return (admit, c, buf, h, m), None

        init = (jnp.ones(T, bool), clock, jnp.zeros(max_bs, jnp.int32),
                jnp.int32(0), jnp.int32(0))
        (admit, _, _, _, _), _ = jax.lax.scan(
            step, init, jnp.arange(T, dtype=jnp.int32))
        return admit

    def window(t, p, bsf, mode_ids, ts, ps, pb, bud, nom, est, hi, live,
               prev_mode, times, n_times, n_carry, clock0, switch_cost,
               adm_budget):
        obs.count("trace.fleet")           # fires at trace time only
        inf = jnp.inf
        with jax.named_scope("fleet_ladder"):
            tk = t[None, :] * ts[:, None]      # per-device scaled grid rows —
            pk = p[None, :] * ps[:, None]      # the PerturbedDeviceModel law

            def rung(need, ar, b_h, b_l):
                # one masked-argmin solve per device row, replaying
                # grid_eval's fleet kernel (sustainable at b_h, objective and
                # latency budget at the low rate ar). ``need`` — "some device
                # is still unsolved at this rung" — wraps the whole (K x grid)
                # plane in a lax.cond, so the program pays for exactly the
                # rungs the host-masked loop would have launched: in steady
                # state (rung 1 solves everyone) rungs 2-4 cost one branch
                # predicate, not three dense solves
                K_pad = ts.shape[0]

                def solve(_):
                    lam = (bsf[None, :] - 1.0) / ar[:, None] + tk
                    feas = ((pk <= pb[:, None])
                            & (tk <= bsf[None, :] / b_h[:, None])
                            & (lam <= b_l[:, None]))
                    lam_sel = jnp.where(feas, lam, inf)
                    idx = jnp.argmin(lam_sel, axis=1)
                    lam_i = jnp.take_along_axis(lam_sel, idx[:, None],
                                                axis=1)[:, 0]
                    return idx.astype(jnp.int32), feas.any(axis=1), lam_i

                def skip(_):
                    return (jnp.zeros(K_pad, jnp.int32),
                            jnp.zeros(K_pad, bool), jnp.full(K_pad, inf))

                return jax.lax.cond(need, solve, skip, operand=None)

            # the PR-5 ladder: rung r's gate reproduces the host loop's
            # "live & still-unsolved" mask at rung r; a rung nobody needs is
            # skipped at runtime (its gate is identically False either way)
            interval = live & (hi > est)
            idx1, ok1, lam1 = rung(interval.any(), est, jnp.maximum(hi, est),
                                   bud)
            g1 = interval & ok1
            idx2, ok2, lam2 = rung((interval & ~g1).any(), hi, hi, bud)
            g2 = interval & ~g1 & ok2
            un12 = live & ~g1 & ~g2
            idx3, ok3, lam3 = rung(un12.any(), est, est, bud)
            g3 = un12 & ok3
            idx4, ok4, lam4 = rung((un12 & ~g3 & (bud < nom)).any(), est, est,
                                   nom)
            g4 = un12 & ~g3 & (bud < nom) & ok4
            solved = g1 | g2 | g3 | g4
            sel = jnp.where(g1, idx1, jnp.where(g2, idx2,
                            jnp.where(g3, idx3, idx4)))
            lam_sel = jnp.where(g1, lam1, jnp.where(g2, lam2,
                                jnp.where(g3, lam3, lam4)))

            bs_i = bsf[sel].astype(jnp.int32)
            t_in = t[sel] * ts
            p_out = p[sel] * ps
            msel = mode_ids[sel]
            switch = jnp.where(solved & (prev_mode >= 0) & (msel != prev_mode),
                               switch_cost, 0.0)
            clock_in = clock0 + switch

        T = times.shape[1]
        iota = jnp.arange(T, dtype=jnp.int32)
        with jax.named_scope("fleet_admission"):
            if trims:
                admit = jax.vmap(admit_lane)(times, n_times, bs_i, t_in,
                                             adm_budget, clock_in)
                admit = admit | ~solved[:, None]    # unsolved lanes: untouched
                in_range = iota[None, :] < n_times[:, None]
                rej = (~admit) & in_range
                n_rej = rej.sum(axis=1, dtype=jnp.int32)
                n_carry_rej = (rej & (iota[None, :] < n_carry[:, None])
                               ).sum(axis=1, dtype=jnp.int32)
                # admitted times are a nondecreasing subsequence: a stable sort
                # against +inf IS the compaction the host path rebuilds
                ctimes = jnp.sort(jnp.where(admit, times, inf), axis=1,
                                  stable=True)
                n_adm = n_times - n_rej
            else:
                n_rej = jnp.zeros_like(n_times)
                n_carry_rej = n_rej
                ctimes = times
                n_adm = n_times

        with jax.named_scope("fleet_engine"):
            # batch-ready gather with a traced bs, then the max-plus scan —
            # solve feeds simulate without leaving the program
            bs_c = jnp.maximum(bs_i, 1)
            nb = n_adm // bs_c
            last = (iota[None, :] + 1) * bs_c[:, None] - 1
            validb = iota[None, :] < nb[:, None]
            ready = jnp.where(
                validb,
                jnp.take_along_axis(ctimes, jnp.clip(last, 0, T - 1), axis=1),
                inf)
            ex = jnp.where(validb, t_in[:, None], 0.0)
            a, b = jax.lax.associative_scan(_maxplus_combine, (ex, ready + ex),
                                            axis=1)
            comp = jnp.maximum(clock_in[:, None] + a, b)
            bidx = jnp.clip(iota[None, :] // bs_c[:, None], 0, T - 1)
            served = iota[None, :] < (nb * bs_c)[:, None]
            lat = jnp.where(served,
                            jnp.take_along_axis(comp, bidx, axis=1) - ctimes,
                            inf)
            clock_out = jnp.where(
                nb > 0,
                jnp.take_along_axis(comp, jnp.clip(nb - 1, 0, T - 1)[:, None],
                                    axis=1)[:, 0],
                clock_in)
        return (solved, sel, lam_sel, p_out, switch, clock_in, n_rej,
                n_carry_rej, ctimes, n_adm, nb, lat, clock_out)

    kernel = jax.jit(window)

    def run(grid_cols, mode_ids, *host_args):
        record_dispatch("fused")
        with enable_x64():
            with obs.span("fleet.launch"):     # uploads and enqueue only
                res = kernel(*grid_cols, mode_ids,
                             *[jnp.asarray(a) for a in host_args])
            with obs.span("fleet.fetch"):      # waits for the device
                return tuple(np.asarray(r) for r in res)

    _FUSED_CACHE[key] = with_program(run, kernel)
    return run


def fused_fleet_window(grid: ObservationGrid, ts: np.ndarray, ps: np.ndarray,
                       pbud: np.ndarray, bud: np.ndarray, nominal: np.ndarray,
                       est: np.ndarray, hi: np.ndarray, live: np.ndarray,
                       prev_mode: np.ndarray,
                       eff_times: Sequence[np.ndarray],
                       n_carry: np.ndarray, clock0: np.ndarray,
                       switch_cost: float, adm_budget: float,
                       trims: bool) -> dict:
    """Run one fleet window fused: plan ladder + admission + engine in a
    single compiled launch over pow2-padded (device, event) buckets.

    ``eff_times[d]`` is device d's effective arrival vector ``[carried
    pending, dispatched window arrivals]`` (the ``_carry_times`` order),
    ``n_carry[d]`` its pending prefix length, ``clock0[d]`` the pre-switch
    engine clock ``max(carry clock, t0)``. Returns per-device NumPy arrays
    (rows sliced back to K): the selection (``solved``/``sel``/``lam``/
    ``power``/``mode_id``), the in-program mode-switch charge and resulting
    clocks, the admission account (``n_rej``/``n_carry_rej``), and the
    execution results over the admitted compaction (``adm_times`` padded
    with +inf, ``n_adm``, ``n_batches``, ``latencies`` padded, and
    ``clock_out``)."""
    K = len(eff_times)
    K_pad = _pow2(K)
    T_pad = _pow2(max((len(v) for v in eff_times), default=0))
    with obs.span("fleet.fused") as sp:
        if sp:
            sp.attrs.update(events=sum(len(v) for v in eff_times),
                            slots=K_pad * T_pad, t_pad=T_pad, k_pad=K_pad)
        with obs.span("fleet.pack"):
            times = np.full((K_pad, T_pad), np.inf)
            n_times = np.zeros(K_pad, np.int32)
            for d, v in enumerate(eff_times):
                times[d, :len(v)] = v
                n_times[d] = len(v)

            def pad1(v, fill, dtype=np.float64):
                out = np.full(K_pad, fill, dtype)
                out[:K] = v
                return out

            args = (pad1(ts, 1.0), pad1(ps, 1.0), pad1(pbud, 0.0),
                    pad1(bud, np.inf), pad1(nominal, np.inf),
                    pad1(est, 0.0), pad1(hi, 0.0), pad1(live, False, bool),
                    pad1(prev_mode, -1, np.int32), times, n_times,
                    pad1(n_carry, 0, np.int32), pad1(clock0, 0.0),
                    np.float64(switch_cost),
                    pad1(np.full(K, float(adm_budget)), 0.0))
        run = _fused_kernel(bool(trims), _grid_max_bs(grid))
        (solved, sel, lam, power, switch, clock_in, n_rej, n_carry_rej,
         ctimes, n_adm, nb, lat, clock_out) = run(
            device_grid_arrays(grid), _device_mode_ids(grid), *args)
        mode_ids = grid_mode_ids(grid)
        return {"solved": solved[:K], "sel": sel[:K], "lam": lam[:K],
                "power": power[:K], "mode_id": mode_ids[sel[:K]],
                "switch": switch[:K], "clock_in": clock_in[:K],
                "n_rej": n_rej[:K], "n_carry_rej": n_carry_rej[:K],
                "adm_times": ctimes[:K], "n_adm": n_adm[:K],
                "n_batches": nb[:K], "latencies": lat[:K],
                "clock_out": clock_out[:K]}


def _grid_max_bs(grid: ObservationGrid) -> int:
    """The admission ring-buffer size: the grid's largest batch size (a
    forming batch never holds more members than its bs)."""
    return int(grid.bs.max()) if grid.bs is not None and len(grid) else 1
