"""XLA evaluator: the lockstep timeline state machine under jax.jit + vmap.

This is the third interpretation of the lowered
:class:`~repro.core.lowering.ProblemSpec` IR (after the authoritative
scalar simulator and the NumPy lockstep loop): one candidate's Eq. 2-8
event machine is written as a ``lax.while_loop`` over a fixed-shape state
pytree, ``jax.vmap`` batches it across the candidate population, and
``jax.jit`` compiles the whole sweep into a single XLA executable — so
candidate evaluation scales with the accelerator instead of the Python
interpreter, and populations far beyond the Table-8 sweep's 137k
candidates can stay device-resident.

Key differences from :mod:`repro.core.simulate_batch`:

  * **finished-candidate masking instead of compaction** — a vmapped
    ``while_loop`` keeps every lane's state fixed once its own condition
    goes false; no dynamic shapes anywhere.  The host shards large
    populations into power-of-two chunks so each chunk's loop terminates
    at its *own* deepest candidate (the masking analogue of the NumPy
    path's compaction) and solver chunk-size jitter reuses a handful of
    compiled executables.
  * **scatter-free waves** — FIFO claims are resolved by per-rank argmin
    over (ready, index) and all accelerator-indexed accumulations go
    through one-hot contractions; the only gathers are group-table reads.
  * **surface-parameterized contention** — slowdowns are computed from the
    spec's lowered :class:`~repro.core.lowering.SlowdownSurface` parameters
    (proportional closed form in jnp; the PCCS piecewise surface through
    :mod:`repro.kernels.slowdown`, whose Pallas kernel engages for large
    flat batches on TPU and whose XLA contraction fuses into the loop body
    elsewhere).  A model with no lowered surface cannot run here — lower it
    (``repro.core.lowering.register_surface_lowering``) or use the
    ``batch``/``scalar`` evaluators, whose Python fallbacks accept any
    object with a scalar ``slowdown``.
  * **error codes, not exceptions** — a traced loop cannot raise;
    deadlock / unmodeled contention / guard exhaustion set per-candidate
    flags that are re-raised host-side after the run, matching the scalar
    simulator's exceptions.

By default the evaluator runs in float64 via the scoped
``jax.enable_x64(True)`` context (bit-compatible with the NumPy
path to ~1e-9 and differentially pinned at 1e-5 by
``tests/test_simulate_differential.py``); ``precision="float32"`` halves
memory traffic for accelerator-resident search where ranking, not exact
latency, is consumed (event tolerances scale with the dtype).

The scalar simulator remains authoritative: ``evaluator="jax"`` call sites
inherit the same contract as the NumPy batch path — solvers re-simulate
their final incumbent through :func:`repro.core.simulate.simulate`.
"""
from __future__ import annotations

import functools
from typing import Any, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .accelerators import Platform
from .contention import ContentionModel
from .graph import DNNGraph
from .lowering import (ProblemSpec, TOL as _TOL, lower_assignments,
                       lower_workloads)
from .simulate import Workload
from .simulate_batch import BatchTimeline, _empty_batch

#: host-side error codes surfaced by the traced loop.
_ERR_DEADLOCK = 1
_ERR_UNMODELED = 2
_ERR_GUARD = 4

#: default candidate-axis shard; chunks pad to the next power of two, so a
#: sweep of any size runs through ~log2 distinct compiled shapes.  16k is
#: the empirical sweet spot on the 2-core CPU reference box (see
#: BENCH_simulate.json); accelerator deployments may prefer larger shards.
DEFAULT_CHUNK = 16384


def _surface_params(surface) -> dict:
    """One surface's parameters as a jnp pytree (traced jit inputs).

    No explicit dtypes: the ambient precision context (``enable_x64`` or
    the process default) decides float64 vs float32.
    """
    p: dict[str, Any] = {"factor": jnp.asarray(float(surface.factor))}
    if surface.kind == "proportional":
        p["capacity"] = jnp.asarray(float(surface.capacity))
        p["sensitivity"] = jnp.asarray(float(surface.sensitivity))
    elif surface.kind == "piecewise":
        p["own_knots"] = jnp.asarray(np.asarray(surface.own_knots, float))
        p["ext_knots"] = jnp.asarray(np.asarray(surface.ext_knots, float))
        p["table"] = jnp.asarray(np.asarray(surface.table, float))
    else:
        raise ValueError(f"unknown surface kind {surface.kind!r}")
    return p


def _surface_eval(kind: str, params: Mapping[str, Any], own, ext):
    """jnp evaluation of one lowered surface (mirrors
    ``repro.core.lowering.surface_slowdown``)."""
    if kind == "proportional":
        cap = params["capacity"].astype(own.dtype)
        own_ = jnp.maximum(0.0, own)
        ext_ = jnp.maximum(0.0, ext)
        total = own_ + ext_
        s = 1.0 + params["sensitivity"].astype(own.dtype) \
            * jnp.minimum(1.0, own_ / cap) * (total / cap - 1.0)
        s = jnp.where((own_ == 0.0) | (total <= cap),
                      jnp.ones((), own.dtype), s)
    else:  # piecewise — the PCCS surface kernel (Pallas on TPU, XLA here)
        from repro.kernels.slowdown import piecewise_slowdown
        s = piecewise_slowdown(own, ext,
                               params["own_knots"].astype(own.dtype),
                               params["ext_knots"].astype(own.dtype),
                               params["table"].astype(own.dtype),
                               backend="auto")
    f = params["factor"].astype(own.dtype)
    return jnp.where(f == 1.0, s, 1.0 + f * (s - 1.0))


def make_event_machine(kinds: tuple[str, ...], max_it: int,
                       record: bool = True):
    """Build one candidate's Eq. 2-8 event machine as a traceable function.

    Returns ``one(acc, dur, dem, tau, ngroups, iters, dep, arrival,
    domshare, model_of_acc, surf_params)``.  With ``record=True`` (the
    evaluator path) it returns ``(finish, lat, contention, busy, err)``;
    with ``record=False`` it carries only the state the control flow needs
    and returns ``(finish, err)`` — the lean variant the device-resident
    search (:mod:`repro.core.search_jax`) evaluates millions of mutants
    through, where every objective derives from finish times alone.

    ``kinds`` (surface kinds, control flow) and ``max_it`` (iteration-
    latency depth / guard budget shape) must be static; shapes and dtypes
    re-specialize through jit as usual.
    """

    def one(acc, dur, dem, tau, ngroups, iters, dep, arrival,
            domshare, model_of_acc, surf_params):
        W = acc.shape[0]
        A = domshare.shape[0]
        dt = dur.dtype
        i32 = jnp.int32
        idx = jnp.arange(W)
        arange_a = jnp.arange(A)
        inf = jnp.asarray(jnp.inf, dt)
        zero = jnp.zeros((), dt)
        one_ = jnp.ones((), dt)
        # event tolerance scales with the working precision: lowering.TOL
        # matches the scalar/NumPy paths exactly; float32 cannot resolve
        # that, so completions/boundaries coalesce at ~1e-5 (ranking-grade).
        tol = jnp.asarray(_TOL if dt == jnp.dtype("float64") else 1e-5, dt)
        ngroups32 = ngroups.astype(i32)
        iters32 = iters.astype(i32)
        dep32 = dep.astype(i32)
        dep_row = jnp.clip(dep32, 0, W - 1)
        macc_of = model_of_acc.astype(i32)
        domshare_t = domshare.astype(dt)
        # scalar-simulator guard, per candidate.
        max_waves = (200000 + 200 * jnp.sum(ngroups32 * iters32)).astype(i32)

        def claim(t, cur_oh, group, ready, it, started, done, is_run,
                  it_start):
            """One FIFO claim sweep: eligible waiting workloads in
            (ready, index) order take their accelerator if free.  Pure
            recomputation — idempotent when nothing changed since the last
            sweep, which is what lets the idle jump re-claim in-wave.
            ``cur_oh`` is the (W, A) accelerator one-hot of ``cur_acc``,
            hoisted by the caller (it only changes at completions, so one
            wave's claims and slowdown step share a single build)."""
            dep_ok = (dep32 < 0) | done[dep_row] | (it[dep_row] > it)
            eligible = ~done & ~is_run & dep_ok & (ready <= t + tol)
            acc_busy = (cur_oh & is_run[:, None]).any(0)        # (A,)
            left = eligible
            for _ in range(W):   # static unroll: rank-r claim by argmin
                key = jnp.where(left, ready, inf)
                wr = jnp.argmin(key)            # first min -> FIFO tie by idx
                sel = idx == wr
                my_busy = (cur_oh & acc_busy[None, :]).any(1)   # (W,)
                claim_v = sel & left & ~my_busy  # at most one entry true
                is_run = is_run | claim_v
                acc_busy = acc_busy | (cur_oh & claim_v[:, None]).any(0)
                if record:   # iteration-start bookkeeping feeds lat only
                    fresh = claim_v & (group == 0) & ~started
                    it_start = jnp.where(fresh, t, it_start)
                    started = started | fresh
                left = left & ~sel
            return is_run, started, it_start

        state = dict(
            t=jnp.zeros((), dt),
            guard=jnp.zeros((), i32),
            group=jnp.zeros(W, i32),
            cur_acc=acc[:, 0].astype(i32),
            own=dem[:, 0].astype(dt),
            remaining=dur[:, 0].astype(dt),
            ready=arrival.astype(dt),
            it=jnp.zeros(W, i32),
            done=jnp.zeros(W, bool),
            is_run=jnp.zeros(W, bool),
            finish=jnp.zeros(W, dt),
            err=jnp.zeros((), i32),
        )
        if record:   # observability state the search ranking never reads
            state.update(
                it_start=arrival.astype(dt),
                started=jnp.zeros(W, bool),
                lat=jnp.full((W, max_it), jnp.nan, dt),
                contention=jnp.zeros((), dt),
                busy=jnp.zeros(A, dt),
            )

        def cond(s):
            return (~s["done"].all()) & (s["guard"] < max_waves)

        def body(s):
            t = s["t"]
            group, cur_acc, own = s["group"], s["cur_acc"], s["own"]
            remaining, ready = s["remaining"], s["ready"]
            it = s["it"]
            it_start, started = s.get("it_start"), s.get("started")
            done, is_run = s["done"], s["is_run"]
            err = s["err"]
            # accelerator one-hot of the wave; cur_acc only changes at
            # completions (step 5), so both claims and the slowdown step
            # share one build.
            cur_oh = cur_acc[:, None] == arange_a[None, :]      # (W, A)

            # 1) FIFO claims at the current time.
            is_run, started, it_start = claim(
                t, cur_oh, group, ready, it, started, done, is_run,
                it_start)
            any_run = is_run.any()

            # idle gap: jump to the next pending boundary and re-claim in
            # the same wave (the scalar simulator's `continue`, fused).
            pend = jnp.where(~done & (ready > t + tol), ready, inf)
            tmin = pend.min()
            idle = ~any_run
            dead = idle & ~jnp.isfinite(tmin)
            err = err | jnp.where(dead, _ERR_DEADLOCK, 0)
            done = done | dead      # poison-exit the lane; host re-raises
            t = jnp.where(idle & ~dead, tmin, t)
            is_run, started, it_start = claim(
                t, cur_oh, group, ready, it, started, done, is_run,
                it_start)
            any_run = is_run.any()

            # 2) per-interval slowdowns from the lowered surfaces.
            cur_ohf = cur_oh.astype(dt)
            own_eff = jnp.where(is_run, own, zero)
            acc_dem = (cur_ohf * own_eff[:, None]).sum(0)       # (A,)
            ext = (cur_ohf * (domshare_t @ acc_dem)[None, :]).sum(1)
            contended = is_run & (own > 0.0) & (ext > 0.0)
            macc = (cur_ohf * macc_of[None, :].astype(dt)).sum(1).astype(i32)
            slow = jnp.ones(W, dt)
            for mid, kind in enumerate(kinds):   # static unroll over models
                sv = _surface_eval(kind, surf_params[mid], own, ext)
                slow = jnp.where(contended & (macc == mid),
                                 jnp.maximum(one_, sv), slow)
            unmod = (contended & (macc < 0)).any()
            err = err | jnp.where(unmod, _ERR_UNMODELED, 0)
            done = done | unmod

            # 3) next event horizon: earliest running completion, capped by
            # ready boundaries strictly inside the interval.
            run_rem = jnp.where(is_run, remaining * slow, inf)
            horizon = t + run_rem.min()
            cap = jnp.where(~done & ~is_run & (ready > t + tol)
                            & (ready < horizon - tol), ready, inf).min()
            horizon = jnp.minimum(horizon, cap)
            horizon = jnp.where(any_run, horizon, t)
            span = horizon - t

            # 4) integrate the contention interval.
            prog = jnp.where(is_run, span / slow, zero)
            remaining = remaining - prog
            if record:
                contention = s["contention"] + jnp.sum(
                    jnp.where(is_run, span * (1.0 - 1.0 / slow), zero))
                busy = s["busy"] + (cur_ohf * prog[:, None]).sum(0)
            t = jnp.where(any_run, horizon, t)

            # 5) process completions.
            fin = is_run & (remaining <= tol)
            is_run = is_run & ~fin
            tau_cur = tau[idx, group].astype(dt)
            has_next = fin & (group + 1 < ngroups32)
            last = fin & ~has_next
            if record:
                lat = jnp.where(
                    last[:, None]
                    & (jnp.arange(max_it)[None, :] == it[:, None]),
                    (t - it_start)[:, None], s["lat"])
            it2 = it + last.astype(i32)
            if record:
                started = started & ~last
            fin_wl = last & (it2 >= iters32)
            done = done | fin_wl
            finish = jnp.where(fin_wl, t, s["finish"])
            restart = last & ~fin_wl
            new_group = jnp.where(has_next, group + 1,
                                  jnp.where(restart, 0, group))
            refresh = has_next | restart
            cur_acc = jnp.where(refresh, acc[idx, new_group].astype(i32),
                                cur_acc)
            own = jnp.where(refresh, dem[idx, new_group].astype(dt), own)
            remaining = jnp.where(refresh, dur[idx, new_group].astype(dt),
                                  remaining)
            ready = jnp.where(has_next, t + tau_cur,
                              jnp.where(restart, t, ready))

            nxt = dict(t=t, guard=s["guard"] + 1, group=new_group,
                       cur_acc=cur_acc, own=own, remaining=remaining,
                       ready=ready, it=it2, done=done, is_run=is_run,
                       finish=finish, err=err)
            if record:
                nxt.update(it_start=it_start, started=started, lat=lat,
                           contention=contention, busy=busy)
            return nxt

        out = jax.lax.while_loop(cond, body, state)
        err = out["err"] | jnp.where(out["done"].all(), 0, _ERR_GUARD)
        if record:
            return (out["finish"], out["lat"], out["contention"],
                    out["busy"], err)
        return out["finish"], err

    return one


@functools.lru_cache(maxsize=None)
def _compiled_run(kinds: tuple[str, ...], max_it: int):
    """Jitted population evaluator for one surface-kind layout: the full
    (recording) event machine under ``jax.vmap`` + ``jax.jit``."""
    one = make_event_machine(kinds, max_it, record=True)

    @jax.jit
    def run(acc, dur, dem, tau, ngroups, iters, dep, arrival,
            domshare, model_of_acc, surf_params):
        mapped = jax.vmap(
            lambda a, du, de, ta, ng, itr, dp, ar: one(
                a, du, de, ta, ng, itr, dp, ar,
                domshare, model_of_acc, surf_params))
        return mapped(acc, dur, dem, tau, ngroups, iters, dep, arrival)

    return run


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _pad_rows(arr: np.ndarray, n_to: int) -> np.ndarray:
    if arr.shape[0] == n_to:
        return arr
    reps = np.repeat(arr[:1], n_to - arr.shape[0], axis=0)
    return np.concatenate([arr, reps], axis=0)


def unlowerable_models(spec: ProblemSpec) -> tuple[str, ...]:
    """Type names of the spec's contention models with no array-IR surface."""
    return tuple(type(m).__name__
                 for m, s in zip(spec.models, spec.surfaces) if s is None)


def simulate_spec(spec: ProblemSpec, *, precision: str = "x64",
                  chunk: int = DEFAULT_CHUNK) -> BatchTimeline:
    """Evaluate a lowered problem spec through the XLA event loop.

    ``precision="x64"`` (default) runs float64 inside a scoped
    ``enable_x64`` context; ``"float32"`` runs the process-default single
    precision (ranking-grade, cheaper on accelerators).  ``chunk`` shards
    the candidate axis: each shard's while_loop stops at its own deepest
    candidate instead of the global maximum, and shards pad to powers of
    two so arbitrary population sizes share compiled executables.
    """
    bad = unlowerable_models(spec)
    if bad:
        raise ValueError(
            f"evaluator 'jax' needs lowerable contention surfaces, but "
            f"{', '.join(sorted(set(bad)))} has no registered surface "
            f"lowering (repro.core.lowering.register_surface_lowering); "
            f"use evaluator='batch' or 'scalar' for this model")
    if precision not in ("x64", "float32"):
        raise ValueError(f"unknown precision {precision!r} "
                         f"(expected 'x64' or 'float32')")
    n = spec.n
    max_it = int(spec.iters.max())
    run = _compiled_run(tuple(s.kind for s in spec.surfaces), max_it)

    finish = np.zeros((n, spec.w))
    lat = np.full((n, spec.w, max_it), np.nan)
    contention = np.zeros(n)
    busy = np.zeros((n, spec.amax))
    err = np.zeros(n, dtype=np.int64)

    def call():
        surf = tuple(_surface_params(s) for s in spec.surfaces)
        domshare = jnp.asarray(spec.domshare)
        model_of_acc = jnp.asarray(spec.model_of_acc)
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            nb = _next_pow2(hi - lo)
            args = [jnp.asarray(_pad_rows(np.asarray(a[lo:hi]), nb))
                    for a in (spec.acc, spec.dur, spec.dem, spec.tau,
                              spec.ngroups, spec.iters, spec.dep,
                              spec.arrival)]
            fin, la, con, bu, er = run(*args, domshare, model_of_acc, surf)
            m = hi - lo
            finish[lo:hi] = np.asarray(fin)[:m]
            lat[lo:hi] = np.asarray(la)[:m]
            contention[lo:hi] = np.asarray(con)[:m]
            busy[lo:hi] = np.asarray(bu)[:m]
            err[lo:hi] = np.asarray(er)[:m]

    if precision == "x64":
        with jax.enable_x64(True):
            call()
    else:
        call()

    if err.any():
        code = int(np.bitwise_or.reduce(err))
        if code & _ERR_UNMODELED:
            uncovered = [a for a, m in zip(spec.acc_names, spec.model_of_acc)
                         if m < 0]
            raise KeyError(f"no contention model covers accelerator(s) "
                           f"{uncovered!r}")
        if code & _ERR_DEADLOCK:
            raise RuntimeError("deadlock: nothing running, nothing pending")
        raise RuntimeError("jax simulator did not converge (event storm)")

    return BatchTimeline(
        makespan=finish.max(axis=1),
        finish_times=finish,
        iteration_latencies=lat,
        iterations=spec.iters.copy(),
        contention_ms=contention,
        busy_ms=busy,
        acc_names=spec.acc_names,
    )


# ---------------------------------------------------------------------------
# registry-shaped wrappers (the evaluator entry points)
# ---------------------------------------------------------------------------

def simulate_batch(
    platform: Platform,
    workloads_batch: Sequence[Sequence[Workload]],
    model: ContentionModel | Mapping[str, ContentionModel],
    validate: bool = True,
    precision: str = "x64",
) -> BatchTimeline:
    """Lower per-candidate Workload lists and evaluate them under XLA."""
    if len(workloads_batch) == 0:
        return _empty_batch(platform)
    return simulate_spec(lower_workloads(platform, workloads_batch, model,
                                         validate), precision=precision)


def simulate_assignments(
    platform: Platform,
    graphs: Sequence[DNNGraph],
    assignments_batch: Sequence[Sequence[Sequence[str]]],
    model: ContentionModel | Mapping[str, ContentionModel],
    iterations: Sequence[int] | None = None,
    depends_on: Sequence[int | None] | None = None,
    validate: bool = True,
    precision: str = "x64",
) -> BatchTimeline:
    """Lower fixed-graph assignment vectors and evaluate them under XLA."""
    if len(assignments_batch) == 0:
        return _empty_batch(platform)
    return simulate_spec(lower_assignments(
        platform, graphs, assignments_batch, model, iterations=iterations,
        depends_on=depends_on, validate=validate), precision=precision)
