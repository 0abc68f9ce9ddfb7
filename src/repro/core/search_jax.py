"""Device-resident schedule search: annealing over the lowered array IR.

PR 4 made candidate *evaluation* device-resident
(:mod:`repro.core.simulate_jax`); the solver loop itself still generated
candidates on the host and round-tripped one population per batch.  This
module closes the loop: mutation, evaluation and selection all run inside
one ``lax.while_loop`` over frozen per-graph lookup tables, so the only
host<->device traffic per search is the initial tables down and the
per-chain incumbents back.

Structure:

* :class:`SearchTables` — the frozen device-side problem: per-graph
  (group, accelerator) duration/demand tables, legality masks, transition
  costs and the platform contention layout, built once from the same
  :func:`repro.core.lowering.graph_tables` the assignment lowering uses.
* :func:`anneal_search` — a population of chains walks the assignment
  space.  Each step every chain mutates one (workload, group) site to a
  random allowed accelerator (proposals that break transition legality
  revert to the current state), scores the mutant through the *lean*
  event machine (``make_event_machine(record=False)`` — identical event
  semantics to the jax evaluator, minus the observability state no
  ranking reads), and the population is selected by the Metropolis +
  incumbent kernel (:mod:`repro.kernels.search`).  Every
  ``exchange_every`` steps each island's best incumbent replaces its
  worst current member — the genetic/elitist migration that keeps deep
  islands from stagnating.

Determinism is by construction, not by luck:

* per-chain RNG streams are ``fold_in(fold_in(PRNGKey(seed),
  global_chain_index), step)`` — a chain's stream depends only on its
  global index, never on how the population was chunked across device
  calls;
* islands are fixed ``island``-sized slices of the global chain order and
  chunk boundaries must align to them (``chunk % island == 0``), so
  migration sees the same members regardless of chunking;
* uniform draws are taken in float32 in *both* precision modes, so the
  accept decisions of ``precision="float32"`` and ``"x64"`` diverge only
  where the objectives themselves do;
* the global winner is the (objective, chain index) lexicographic min —
  first-found wins ties.

The scalar simulator stays authoritative: this module reports the device
incumbent and its device objective; :mod:`repro.core.solver_anneal`
re-simulates the winner on the host scalar path before any
:class:`~repro.core.plan.Plan` is minted.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh as _Mesh
from jax.sharding import PartitionSpec as _PSpec

from .accelerators import Platform
from .contention import ContentionModel
from .graph import DNNGraph
from .lowering import _platform_tables, graph_tables
from .simulate_jax import _next_pow2, _surface_params, make_event_machine
from ..obs import get_registry, get_tracer

OBJECTIVES = ("latency", "throughput", "sum_inverse")
MIGRATIONS = ("auto", "island", "ring")
FANOUTS = ("auto", "shard_map", "pmap")

#: chains per island — the migration neighborhood.  Must divide both the
#: population and the chunk so islands never straddle a device call.
DEFAULT_ISLAND = 32
#: chains per device call; population shards into island-aligned chunks.
DEFAULT_CHUNK = 8192


# ---------------------------------------------------------------------------
# SearchTables: the frozen device-side problem
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SearchTables:
    """Per-(workload, group, accelerator) lookup tables for one problem.

    ``gmax`` is padded to the next power of two so nearby graph depths
    share compiled executables; rows at ``i >= ngroups[m]`` are dead
    (``allowed`` all-False, never reached by the event machine).
    """

    acc_names: tuple[str, ...]
    w: int
    gmax: int
    amax: int
    dur_t: np.ndarray          # (w, gmax, A) ms; 0 where not allowed
    dem_t: np.ndarray          # (w, gmax, A) demand fraction
    allowed: np.ndarray        # (w, gmax, A) bool
    n_allowed: np.ndarray      # (w, gmax) int
    legal_after: np.ndarray    # (w, gmax) bool
    move_ms: np.ndarray        # (w, gmax) output move cost
    tau_pair: np.ndarray       # (A, A) fixed in+out transition cost
    ngroups: np.ndarray        # (w,) live groups per workload
    iters: np.ndarray          # (w,)
    dep: np.ndarray            # (w,) -1 = no dependency
    arrival: np.ndarray        # (w,) ms
    domshare: np.ndarray       # (A, A) contention-domain sharing
    model_of_acc: np.ndarray   # (A,) surface index, -1 = unmodeled
    models: tuple
    surfaces: tuple
    max_transitions: int

    @property
    def kinds(self) -> tuple[str, ...]:
        return tuple(s.kind for s in self.surfaces)

    def decode(self, asg: np.ndarray) -> tuple[tuple[str, ...], ...]:
        """(w, gmax) index row -> per-workload accelerator-name tuples."""
        return tuple(
            tuple(self.acc_names[int(asg[m, i])]
                  for i in range(int(self.ngroups[m])))
            for m in range(self.w))

    def encode(self, assignments: Sequence[Sequence[str]]) -> np.ndarray:
        """Per-workload accelerator names -> a (w, gmax) index row."""
        idx = {a: j for j, a in enumerate(self.acc_names)}
        out = np.zeros((self.w, self.gmax), dtype=np.int32)
        for m, asg in enumerate(assignments):
            ng = int(self.ngroups[m])
            if len(asg) != ng:
                raise ValueError(
                    f"workload {m}: assignment has {len(asg)} groups, "
                    f"graph has {ng}")
            for i, a in enumerate(asg):
                out[m, i] = idx[a]
            if ng < self.gmax:
                out[m, ng:] = out[m, ng - 1]   # dead rows: repeat last acc
        return out

    def legal(self, asg: np.ndarray) -> bool:
        """Host mirror of the device legality predicate for one row."""
        for m in range(self.w):
            ng = int(self.ngroups[m])
            trans = 0
            for i in range(ng):
                if not self.allowed[m, i, int(asg[m, i])]:
                    return False
                if i + 1 < ng and asg[m, i] != asg[m, i + 1]:
                    if not self.legal_after[m, i]:
                        return False
                    trans += 1
            if trans > self.max_transitions:
                return False
        return True


def build_tables(
    platform: Platform,
    graphs: Sequence[DNNGraph],
    model: ContentionModel | Mapping[str, ContentionModel],
    max_transitions: int,
    iterations: Sequence[int] | None = None,
    depends_on: Sequence[int | None] | None = None,
    arrival_ms: Sequence[float] | None = None,
) -> SearchTables:
    """Freeze one scheduling problem into device-search lookup tables."""
    acc_names, domshare, model_of_acc, models, surfaces = _platform_tables(
        platform, model)
    if any(s is None for s in surfaces):
        bad = sorted({type(m).__name__
                      for m, s in zip(models, surfaces) if s is None})
        raise ValueError(
            f"solver 'anneal' needs lowerable contention surfaces, but "
            f"{', '.join(bad)} has no registered surface lowering "
            f"(repro.core.lowering.register_surface_lowering); use "
            f"solver='bb' or 'greedy' for this model")
    w = len(graphs)
    if w == 0:
        raise ValueError("cannot search an empty problem")
    amax = len(acc_names)
    gmax = _next_pow2(max(len(g) for g in graphs))
    dur_t = np.zeros((w, gmax, amax))
    dem_t = np.zeros((w, gmax, amax))
    allowed = np.zeros((w, gmax, amax), dtype=bool)
    legal_after = np.zeros((w, gmax), dtype=bool)
    move_ms = np.zeros((w, gmax))
    tau_pair = np.zeros((amax, amax))
    ngroups = np.zeros(w, dtype=np.int64)
    for m, g in enumerate(graphs):
        ng = len(g)
        ngroups[m] = ng
        time_t, dem, legal, move, tp = graph_tables(platform, g)
        tau_pair = tp
        ok = ~np.isnan(time_t)
        if not ok.any(axis=1).all():
            i = int(np.flatnonzero(~ok.any(axis=1))[0])
            raise ValueError(
                f"graph {g.name!r}[{i}] runs on no accelerator of "
                f"platform {platform.name!r}")
        allowed[m, :ng] = ok
        dur_t[m, :ng] = np.nan_to_num(time_t)
        dem_t[m, :ng] = dem
        legal_after[m, :ng] = legal
        move_ms[m, :ng] = move
    its = np.asarray(list(iterations or [1] * w), dtype=np.int64)
    deps = np.asarray([-1 if d is None else int(d)
                       for d in (depends_on or [None] * w)], dtype=np.int64)
    arr = np.asarray(list(arrival_ms or [0.0] * w))
    return SearchTables(
        acc_names=acc_names, w=w, gmax=gmax, amax=amax,
        dur_t=dur_t, dem_t=dem_t, allowed=allowed,
        n_allowed=allowed.sum(axis=-1).astype(np.int64),
        legal_after=legal_after, move_ms=move_ms, tau_pair=tau_pair,
        ngroups=ngroups, iters=its, dep=deps, arrival=arr,
        domshare=domshare, model_of_acc=model_of_acc,
        models=models, surfaces=surfaces,
        max_transitions=int(max_transitions))


def _legal_rows(tables: SearchTables, asg: np.ndarray) -> np.ndarray:
    """Vectorized legality over a (P, w, gmax) batch of index rows."""
    w, gmax = tables.w, tables.gmax
    widx = np.arange(w)[None, :, None]
    gidx = np.arange(gmax)[None, None, :]
    live = gidx < tables.ngroups[None, :, None]
    ok = (tables.allowed[widx, gidx, asg] | ~live).all(axis=(1, 2))
    pair_live = (np.arange(1, gmax)[None, None, :]
                 < tables.ngroups[None, :, None])
    diff = (asg[:, :, 1:] != asg[:, :, :-1]) & pair_live
    ok &= ~(diff & ~tables.legal_after[None, :, :-1]).any(axis=(1, 2))
    ok &= (diff.sum(axis=2) <= tables.max_transitions).all(axis=1)
    return ok


def _scatter_population(tables: SearchTables, row: np.ndarray,
                        pop: int, seed: int) -> np.ndarray:
    """Diversify the initial population: chain 0 keeps ``row`` exactly
    (the never-regress anchor), every other chain takes a seeded random
    walk of legal single-site mutations so islands start in distinct
    basins instead of all climbing out of the same one.  Depends only on
    ``seed`` — chunking, backend, and precision cannot perturb it."""
    asg = np.repeat(row[None].astype(np.int32), pop, axis=0)
    if pop == 1:
        return asg
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5eed]))
    sites = np.array([(m, i) for m in range(tables.w)
                      for i in range(int(tables.ngroups[m]))])
    for _ in range(max(4, 2 * len(sites))):
        pick = sites[rng.integers(0, len(sites), size=pop)]
        wi, gi = pick[:, 0], pick[:, 1]
        k = rng.integers(0, tables.n_allowed[wi, gi])
        acc = (np.cumsum(tables.allowed[wi, gi], axis=1)
               > k[:, None]).argmax(axis=1)
        prop = asg.copy()
        prop[np.arange(pop), wi, gi] = acc.astype(np.int32)
        ok = _legal_rows(tables, prop)
        asg[ok] = prop[ok]
    asg[0] = row
    return asg


def default_init(tables: SearchTables) -> np.ndarray:
    """A legal all-on-one-accelerator starting row: per workload, the
    everywhere-allowed accelerator with the smallest total duration."""
    out = np.zeros((tables.w, tables.gmax), dtype=np.int32)
    for m in range(tables.w):
        ng = int(tables.ngroups[m])
        everywhere = tables.allowed[m, :ng].all(axis=0)
        if not everywhere.any():
            raise ValueError(
                f"workload {m} has no accelerator allowed on every group; "
                f"pass an explicit init_assignment")
        total = np.where(everywhere, tables.dur_t[m, :ng].sum(axis=0),
                         np.inf)
        out[m, :] = int(np.argmin(total))
    return out


# ---------------------------------------------------------------------------
# the compiled search
# ---------------------------------------------------------------------------

def _make_run(w: int, gmax: int, amax: int, kinds: tuple[str, ...],
              obj_kind: str, island: int, backend: str,
              migrate: str = "island", ndev: int = 1,
              axis_name: str | None = None):
    """The (un-jitted) per-shard search program.

    ``migrate="island"`` is the legacy within-island elite fold;
    ``"ring"`` additionally donates each island's elite to the *next*
    island in the global island order at every exchange boundary — the
    cross-device seam travels by ``lax.ppermute`` over ``axis_name`` when
    the program runs as one shard of an ``ndev``-device mesh, and wraps
    locally when ``ndev == 1``.  All migration traffic is pure
    select/gather of already-computed values, so incumbents are
    bit-identical across device counts for a fixed total population.
    """
    from repro.kernels.search import anneal_select

    one = make_event_machine(kinds, 1, record=False)
    rows = jnp.arange(w)[:, None]
    cols = jnp.arange(gmax)[None, :]

    def run(tb, chain_idx, asg0, seed, n_steps, ex_every, t0, t1):
        dt = tb["dur_t"].dtype
        f32 = jnp.float32
        i32 = jnp.int32
        P = asg0.shape[0]
        nisl = P // island
        live = cols < tb["ngroups"][:, None]            # (w, gmax)
        iters_sum = jnp.sum(tb["iters"]).astype(dt)
        cum_live = jnp.cumsum(tb["ngroups"]).astype(i32)
        total_live = cum_live[-1]
        mt = jnp.asarray(tb["max_transitions"], i32)

        def gather(t, asg):
            return jnp.take_along_axis(t, asg[..., None], axis=-1)[..., 0]

        def legal_all(asg):
            alw = gather(tb["allowed"], asg)
            ok = jnp.all(alw | ~live)
            if gmax > 1:
                a0, a1 = asg[:, :-1], asg[:, 1:]
                moved = (a0 != a1) & live[:, 1:]
                ok &= jnp.all(~moved | tb["legal_after"][:, :-1])
                ok &= jnp.all(moved.sum(axis=1) <= mt)
            return ok

        def evaluate(asg):
            dur = gather(tb["dur_t"], asg)
            dem = gather(tb["dem_t"], asg)
            tau = jnp.zeros((w, gmax), dt)
            if gmax > 1:
                a0, a1 = asg[:, :-1], asg[:, 1:]
                moved = (a0 != a1) & live[:, 1:]
                tau = tau.at[:, :-1].set(jnp.where(
                    moved, tb["move_ms"][:, :-1] + tb["tau_pair"][a0, a1],
                    jnp.zeros((), dt)))
            finish, err = one(asg, dur, dem, tau, tb["ngroups"],
                              tb["iters"], tb["dep"], tb["arrival"],
                              tb["domshare"], tb["model_of_acc"], tb["surf"])
            if obj_kind == "latency":
                obj = jnp.max(finish)
            elif obj_kind == "throughput":
                mk = jnp.max(finish)
                obj = jnp.where(mk > 0, -1e3 * iters_sum / mk,
                                -jnp.asarray(jnp.inf, dt))
            else:  # sum_inverse
                obj = -jnp.sum(jnp.where(finish > 0, 1.0 / finish,
                                         jnp.zeros((), dt)))
            return jnp.where(err != 0, jnp.asarray(jnp.inf, dt), obj)

        def mutate(key, asg):
            ks, ka = jax.random.split(key)
            u = jax.random.randint(ks, (), 0, total_live)
            m = jnp.sum((u >= cum_live).astype(i32))
            prev = jnp.where(m > 0, cum_live[jnp.maximum(m - 1, 0)], 0)
            i = u - prev
            na = tb["n_allowed"][m, i]
            k = jax.random.randint(ka, (), 0, jnp.maximum(na, 1))
            hits = jnp.cumsum(tb["allowed"][m, i].astype(i32))
            a = jnp.argmax(hits > k).astype(asg.dtype)
            prop = jnp.where((rows == m) & (cols == i), a, asg)
            return jnp.where(legal_all(prop), prop, asg)

        base = jax.random.PRNGKey(seed)
        chain_keys = jax.vmap(
            lambda i: jax.random.fold_in(base, i))(chain_idx)

        obj0 = jax.vmap(evaluate)(asg0)
        state = dict(step=jnp.zeros((), i32), asg=asg0, obj=obj0,
                     best=asg0, best_obj=obj0)

        def cond(s):
            return s["step"] < n_steps

        def body(s):
            step = s["step"]
            keys = jax.vmap(
                lambda ck: jax.random.fold_in(ck, step))(chain_keys)
            ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
            km, ku = ks[:, 0], ks[:, 1]       # mutation / accept draws
            prop = jax.vmap(mutate)(km, s["asg"])
            prop_obj = jax.vmap(evaluate)(prop)
            u = jax.vmap(
                lambda k: jax.random.uniform(k, (), f32))(ku).astype(dt)
            frac = step.astype(dt) / jnp.maximum(n_steps - 1, 1).astype(dt)
            temp = t0 * (t1 / t0) ** frac
            cur, curo, bst, bsto = anneal_select(
                s["asg"].reshape(P, w * gmax), prop.reshape(P, w * gmax),
                s["best"].reshape(P, w * gmax), s["obj"], prop_obj,
                s["best_obj"], u, temp, backend=backend,
                global_lanes=P * ndev)
            cur = cur.reshape(P, w, gmax)
            bst = bst.reshape(P, w, gmax)
            # elitist island migration: every ex_every steps the island's
            # best incumbent replaces its worst current member.
            do = (step + 1) % ex_every == 0
            obj_i = curo.reshape(nisl, island)
            bo_i = bsto.reshape(nisl, island)
            src = jnp.argmin(bo_i, axis=1)              # first-tie elite
            dst = jnp.argmax(obj_i, axis=1)             # worst current
            bst_i = bst.reshape(nisl, island, w, gmax)
            elite = jnp.take_along_axis(
                bst_i, src[:, None, None, None], axis=1)
            elite_obj = jnp.take_along_axis(bo_i, src[:, None], axis=1)
            repl = (jnp.arange(island)[None, :] == dst[:, None]) & do
            cur_i = jnp.where(repl[..., None, None],
                              elite, cur.reshape(nisl, island, w, gmax))
            obj_i = jnp.where(repl, elite_obj, obj_i)
            if migrate == "ring":
                # cross-island ring: island j's worst post-fold member is
                # replaced by the elite incumbent of island j-1 in the
                # *global* island order.  Only the seam (the last local
                # island's elite) crosses devices — a single ppermute —
                # so the injected values are identical however the global
                # island order is sharded.
                seam, seam_obj = elite[-1:], elite_obj[-1:]
                if axis_name is not None:
                    perm = [(i, (i + 1) % ndev) for i in range(ndev)]
                    seam = jax.lax.ppermute(seam, axis_name, perm)
                    seam_obj = jax.lax.ppermute(seam_obj, axis_name, perm)
                donor = jnp.concatenate([seam, elite[:-1]], axis=0)
                donor_obj = jnp.concatenate([seam_obj, elite_obj[:-1]],
                                            axis=0)
                dst2 = jnp.argmax(obj_i, axis=1)        # worst after fold
                repl2 = (jnp.arange(island)[None, :]
                         == dst2[:, None]) & do
                cur_i = jnp.where(repl2[..., None, None], donor, cur_i)
                obj_i = jnp.where(repl2, donor_obj, obj_i)
            return dict(step=step + 1, asg=cur_i.reshape(P, w, gmax),
                        obj=obj_i.reshape(P), best=bst, best_obj=bsto)

        out = jax.lax.while_loop(cond, body, state)
        return out["best_obj"], out["best"]

    return run


@functools.lru_cache(maxsize=None)
def _compiled_search(w: int, gmax: int, amax: int, kinds: tuple[str, ...],
                     obj_kind: str, island: int, backend: str):
    """One jitted device-resident search per (shape, kinds, objective,
    island, kernel-backend) layout; population size and dtype
    re-specialize through jit as usual."""
    return jax.jit(_make_run(w, gmax, amax, kinds, obj_kind, island,
                             backend))


@functools.lru_cache(maxsize=None)
def _compiled_mesh_search(w: int, gmax: int, amax: int,
                          kinds: tuple[str, ...], obj_kind: str,
                          island: int, backend: str, devices: int,
                          migrate: str, fanout: str):
    """The search fanned out over a 1-D device mesh.

    Returns ``(call_kind, fn)``: ``call_kind`` is ``"flat"`` when ``fn``
    takes the same globally-shaped arguments as the single-device run
    (jit / jit-of-shard_map) and ``"pmap"`` when the caller must reshape
    the sharded arguments to a leading ``devices`` axis.
    """
    if devices == 1:
        # one device needs no collective: the ring seam wraps locally.
        return "flat", jax.jit(_make_run(w, gmax, amax, kinds, obj_kind,
                                         island, backend, migrate=migrate))
    body = _make_run(w, gmax, amax, kinds, obj_kind, island, backend,
                     migrate=migrate, ndev=devices, axis_name="d")
    devs = jax.devices()[:devices]
    if fanout == "shard_map":
        mesh = _Mesh(np.array(devs), ("d",))
        sharded = _PSpec("d")
        repl = _PSpec()
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(repl, sharded, sharded, repl, repl, repl, repl, repl),
            out_specs=(sharded, sharded),
            # while_loop bodies have no replication rule; correctness of
            # the replicated outputs is by construction (pure per-shard).
            check_vma=False)
        return "flat", jax.jit(fn)
    fn = jax.pmap(body, axis_name="d", devices=devs,
                  in_axes=(None, 0, 0, None, None, None, None, None))
    return "pmap", fn


# ---------------------------------------------------------------------------
# host driver
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SearchOutcome:
    """The device search's winner — device-reported, pre-authoritative."""

    assignment: tuple[tuple[str, ...], ...]
    objective: float            # device objective of the incumbent
    chain: int                  # global index of the winning chain
    evaluated: int              # event-machine evaluations performed
    population: int
    steps: int
    seed: int
    precision: str
    backend: str
    devices: int | None = None  # mesh width; None = legacy chunked path
    migrate: str = "island"     # resolved migration topology
    fanout: str | None = None   # resolved mesh fan-out (shard_map/pmap)


def _nearest_multiple(value: int, quantum: int) -> int:
    """The multiple of ``quantum`` nearest to ``value`` (>= quantum)."""
    lo = (value // quantum) * quantum
    hi = lo + quantum
    if lo < quantum:
        return hi
    return lo if (value - lo) <= (hi - value) else hi


def _validate_knobs(population: int, island: int, exchange_every: int,
                    steps: int, chunk: int | None, devices: int | None,
                    migrate: str, fanout: str) -> tuple[int | None, str, str]:
    """Fail fast on inconsistent knob combinations.

    Every rejection names the offending knob and the nearest legal value
    — nothing is silently rounded or truncated.  Returns the resolved
    ``(chunk, migrate, fanout)``.
    """
    if island < 1 or exchange_every < 1 or steps < 0 or population < 1:
        raise ValueError("population/steps/island/exchange_every must be "
                         "positive")
    if island > population:
        raise ValueError(
            f"island ({island}) exceeds population ({population}); "
            f"nearest legal value: island={population}")
    if population % island:
        raise ValueError(
            f"population ({population}) is not a multiple of island "
            f"({island}); nearest legal value: population="
            f"{_nearest_multiple(population, island)}")
    if migrate not in MIGRATIONS:
        raise ValueError(f"unknown migrate {migrate!r}; "
                         f"one of {', '.join(MIGRATIONS)}")
    if fanout not in FANOUTS:
        raise ValueError(f"unknown fanout {fanout!r}; "
                         f"one of {', '.join(FANOUTS)}")
    if devices is not None:
        if devices < 1:
            raise ValueError(f"devices ({devices}) must be >= 1")
        avail = jax.device_count()
        if devices > avail:
            raise ValueError(
                f"devices ({devices}) exceeds the {avail} visible jax "
                f"device(s); nearest legal value: devices={avail} "
                f"(emulate more host devices with "
                f"repro.core.xla_env.apply(devices=N) before jax "
                f"initializes)")
        quantum = island * devices
        if population % quantum:
            raise ValueError(
                f"population ({population}) is not a multiple of "
                f"island*devices ({quantum}); nearest legal value: "
                f"population={_nearest_multiple(population, quantum)}")
    else:
        if fanout != "auto":
            raise ValueError(
                f"fanout ({fanout!r}) requires devices=N (the mesh "
                f"path); nearest legal value: fanout='auto'")
        if migrate == "ring":
            raise ValueError(
                "migrate='ring' requires devices=N: the ring spans the "
                "global island order, which the legacy chunked path "
                "processes in separate device calls; nearest legal "
                "value: migrate='island'")
    if chunk is not None:
        if chunk < 1:
            raise ValueError(f"chunk ({chunk}) must be >= 1")
        if chunk % island:
            raise ValueError(
                f"chunk ({chunk}) must be a multiple of island "
                f"({island}): islands may not straddle device calls; "
                f"nearest legal value: chunk="
                f"{_nearest_multiple(chunk, island)}")
        if chunk > population:
            raise ValueError(
                f"chunk ({chunk}) exceeds population ({population}); "
                f"nearest legal value: chunk={population}")
    mig = migrate if migrate != "auto" else (
        "ring" if devices is not None else "island")
    fo = fanout
    if devices is not None and fo == "auto":
        fo = "shard_map"
    return chunk, mig, fo


def anneal_search(
    tables: SearchTables,
    *,
    objective: str = "latency",
    seed: int = 0,
    population: int = 1024,
    steps: int = 128,
    island: int = DEFAULT_ISLAND,
    exchange_every: int = 16,
    chunk: int | None = None,
    precision: str = "float32",
    backend: str = "auto",
    devices: int | None = None,
    migrate: str = "auto",
    fanout: str = "auto",
    init_assignment: np.ndarray | Sequence[Sequence[str]] | None = None,
    init_objective: float | None = None,
) -> SearchOutcome:
    """Run the device-resident annealing/genetic search over ``tables``.

    ``population`` chains (a multiple of ``island``) run ``steps``
    temperature steps each; ``chunk`` bounds the chains per device call
    and must be island-aligned (default: one full-population call, capped
    at :data:`DEFAULT_CHUNK`).  ``precision="float32"`` ranks in single
    precision (the default — cheap, and the selection order is what
    matters); ``"x64"`` evaluates in float64 inside a scoped
    ``jax.enable_x64``.  ``backend`` selects the selection-kernel dispatch
    (``pallas`` / ``pallas_interpret`` / ``xla`` / ``auto``).

    ``devices=N`` fans the population out over a 1-D mesh of the first N
    visible jax devices (``fanout``: ``shard_map`` by default, ``pmap``
    on request) with ``migrate="ring"`` cross-device elite migration; the
    incumbent is then bit-identical for a fixed ``(seed, population,
    island, exchange_every)`` at *any* device count dividing the island
    count.  ``devices=None`` keeps the legacy sequential-chunk path
    (``migrate="island"``) byte-for-byte.

    The same ``(seed, population, steps, island, exchange_every)`` always
    explores the same chains and returns the bit-identical incumbent
    regardless of ``chunk``, ``fanout`` and selection-kernel backend.
    Inconsistent knob combinations raise ``ValueError`` naming the knob
    and the nearest legal value.
    """
    if objective not in OBJECTIVES:
        raise ValueError(f"unknown objective {objective!r}; "
                         f"one of {', '.join(OBJECTIVES)}")
    if precision not in ("x64", "float32"):
        raise ValueError(f"unknown precision {precision!r} "
                         f"(expected 'x64' or 'float32')")
    chunk, migrate, fanout_r = _validate_knobs(
        population, island, exchange_every, steps, chunk, devices,
        migrate, fanout)
    pop = population
    if chunk is None:
        chunk = max(island, min((DEFAULT_CHUNK // island) * island, pop))

    if init_assignment is None:
        asg_row = default_init(tables)
    elif isinstance(init_assignment, np.ndarray):
        asg_row = np.asarray(init_assignment, dtype=np.int32)
        if asg_row.shape != (tables.w, tables.gmax):
            raise ValueError(
                f"init_assignment shape {asg_row.shape} != "
                f"{(tables.w, tables.gmax)}")
    else:
        asg_row = tables.encode(init_assignment)
    if not tables.legal(asg_row):
        raise ValueError("init_assignment is not a legal schedule "
                         "(allowed accelerators / transition budget)")

    # temperature scale: the initial objective when the caller knows it,
    # else a contention-free serial-latency proxy — only the *scale*
    # matters, the schedule is geometric between t0 and t1.
    if init_objective is not None and np.isfinite(init_objective):
        scale = abs(float(init_objective))
    else:
        scale = float(max(
            float(tables.iters[m]) * tables.dur_t[m, :, :].max(axis=-1).sum()
            for m in range(tables.w)))
    scale = max(scale, 1e-6)
    t0, t1 = 0.1 * scale, 1e-4 * scale

    best_objs = np.empty(pop)
    best_rows = np.empty((pop, tables.w, tables.gmax), dtype=np.int64)

    # the compiled program is looked up (and its closure constants
    # created) OUTSIDE any enable_x64 scope: the lru-cached executable is
    # shared between precision modes, so its captured index constants
    # must not inherit the first caller's x64 setting.
    if devices is None:
        kind, run = "chunked", _compiled_search(
            tables.w, tables.gmax, tables.amax, tables.kinds, objective,
            island, backend)
    else:
        kind, run = _compiled_mesh_search(
            tables.w, tables.gmax, tables.amax, tables.kinds, objective,
            island, backend, devices, migrate, fanout_r)

    tracer = get_tracer()

    def call():
        with tracer.span("anneal.upload", "search"):
            tb = _device_tables(tables)
            asg0_full = jnp.asarray(
                _scatter_population(tables, asg_row, pop, seed))
            args_tail = (seed, jnp.asarray(steps, jnp.int32),
                         jnp.asarray(exchange_every, jnp.int32),
                         jnp.asarray(float(t0)), jnp.asarray(float(t1)))
        if kind == "chunked":
            for ci, lo in enumerate(range(0, pop, chunk)):
                hi = min(lo + chunk, pop)
                # chunk 0 pays any outstanding jit compile for this
                # (shape, objective, backend) — later chunks reuse the
                # executable, so their spans are pure steady state.
                with tracer.span("anneal.chunk", "search", chunk=ci,
                                 lo=lo, hi=hi, includes_compile=(ci == 0)):
                    bo, br = run(tb, jnp.arange(lo, hi, dtype=jnp.int32),
                                 asg0_full[lo:hi], *args_tail)
                    # the pulls block until the device loop has finished
                    with tracer.span("anneal.wait", "search", chunk=ci):
                        best_objs[lo:hi] = np.asarray(bo, dtype=np.float64)
                        best_rows[lo:hi] = np.asarray(br)
            return
        chain_idx = jnp.arange(pop, dtype=jnp.int32)
        with tracer.span("anneal.mesh", "search", fanout=kind,
                         devices=devices):
            if kind == "pmap":
                per = pop // devices
                bo, br = run(tb, chain_idx.reshape(devices, per),
                             asg0_full.reshape(devices, per, tables.w,
                                               tables.gmax), *args_tail)
                bo = bo.reshape(pop)
                br = br.reshape(pop, tables.w, tables.gmax)
            else:
                bo, br = run(tb, chain_idx, asg0_full, *args_tail)
            with tracer.span("anneal.wait", "search"):
                best_objs[:] = np.asarray(bo, dtype=np.float64)
                best_rows[:] = np.asarray(br)

    with tracer.span("anneal_search", "search", population=pop,
                     steps=steps, island=island, seed=seed,
                     backend=backend, devices=devices,
                     objective=objective) as search_sp:
        if precision == "x64":
            with jax.enable_x64(True):
                call()
        else:
            call()

        winner = int(np.argmin(best_objs))   # first min = lowest chain index
        if not np.isfinite(best_objs[winner]):
            raise RuntimeError(
                "device search found no feasible schedule (every chain "
                "error-poisoned); check the contention model coverage")
        search_sp.set(evaluated=pop * (steps + 1), chain=winner,
                      objective_value=float(best_objs[winner]))
    return SearchOutcome(
        assignment=tables.decode(best_rows[winner]),
        objective=float(best_objs[winner]),
        chain=winner,
        evaluated=pop * (steps + 1),
        population=pop,
        steps=steps,
        seed=seed,
        precision=precision,
        backend=backend,
        devices=devices,
        migrate=migrate,
        fanout=fanout_r if devices is not None else None,
    )


def _device_tables(tables: SearchTables) -> dict:
    """The frozen problem as the device-side pytree the search consumes."""
    return {
        "dur_t": jnp.asarray(tables.dur_t),
        "dem_t": jnp.asarray(tables.dem_t),
        "allowed": jnp.asarray(tables.allowed),
        "n_allowed": jnp.asarray(tables.n_allowed.astype(np.int32)),
        "legal_after": jnp.asarray(tables.legal_after),
        "move_ms": jnp.asarray(tables.move_ms),
        "tau_pair": jnp.asarray(tables.tau_pair),
        "ngroups": jnp.asarray(tables.ngroups.astype(np.int32)),
        "iters": jnp.asarray(tables.iters.astype(np.int32)),
        "dep": jnp.asarray(tables.dep.astype(np.int32)),
        "arrival": jnp.asarray(tables.arrival),
        "domshare": jnp.asarray(tables.domshare),
        "model_of_acc": jnp.asarray(tables.model_of_acc.astype(np.int32)),
        "max_transitions": jnp.asarray(tables.max_transitions, jnp.int32),
        "surf": tuple(_surface_params(s) for s in tables.surfaces),
    }


def compile_seconds(
    tables: SearchTables,
    *,
    objective: str = "latency",
    population: int = 1024,
    island: int = DEFAULT_ISLAND,
    backend: str = "auto",
    precision: str = "float32",
    devices: int | None = None,
    migrate: str = "auto",
    fanout: str = "auto",
) -> float:
    """Seconds to trace + lower + XLA-compile one search executable.

    Builds a *fresh* jitted program (bypassing every jit/lru cache) and
    times an explicit AOT ``lower(...).compile()`` for the exact argument
    shapes ``anneal_search`` would use — so repeated calls measure the
    same work and min-of-repeats is meaningful, unlike the legacy
    ``first_call_s - search_s`` single-sample attribution.
    """
    _, mig, fo = _validate_knobs(population, island, 16, 1, None, devices,
                                 migrate, fanout)

    def aot() -> float:
        tb = _device_tables(tables)
        asg0 = jnp.asarray(_scatter_population(
            tables, default_init(tables), population, 0))
        args_tail = (0, jnp.asarray(1, jnp.int32), jnp.asarray(1, jnp.int32),
                     jnp.asarray(1.0), jnp.asarray(1e-3))
        ndev = devices or 1
        if devices is not None and ndev > 1 and fo == "shard_map":
            body = _make_run(tables.w, tables.gmax, tables.amax,
                             tables.kinds, objective, island, backend,
                             migrate=mig, ndev=ndev, axis_name="d")
            mesh = _Mesh(np.array(jax.devices()[:ndev]), ("d",))
            fn = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(_PSpec(), _PSpec("d"), _PSpec("d"), _PSpec(),
                          _PSpec(), _PSpec(), _PSpec(), _PSpec()),
                out_specs=(_PSpec("d"), _PSpec("d")),
                check_vma=False))
        else:
            # pmap has no lower()/compile() AOT path; time the
            # single-shard executable (identical body) as its proxy.
            fn = jax.jit(_make_run(tables.w, tables.gmax, tables.amax,
                                   tables.kinds, objective, island, backend,
                                   migrate=mig))
        chain_idx = jnp.arange(population, dtype=jnp.int32)
        t0 = time.perf_counter()
        fn.lower(tb, chain_idx, asg0, *args_tail).compile()
        return time.perf_counter() - t0

    with get_tracer().span("search.compile", "search",
                           population=population, devices=devices or 1,
                           backend=backend) as sp:
        if precision == "x64":
            with jax.enable_x64(True):
                dt = aot()
        else:
            dt = aot()
        sp.set(compile_s=round(dt, 6))
    # the gauge holds the most recent AOT measurement; bench_search reads
    # it (min-of-repeats over its own calls) instead of re-wall-timing.
    get_registry().gauge(
        "search_compile_s",
        "AOT lower+compile seconds of one search executable").set(dt)
    return dt
