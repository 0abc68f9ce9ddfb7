"""Batched serving engine: continuous batching over decode slots.

A single-model engine: requests enter a queue; free slots admit them via a
single-request prefill whose cache is spliced into the batched cache; every
``step()`` runs one batched decode for all active slots (per-slot lengths),
greedy-samples, and retires finished requests.  This is the vLLM-style
continuous-batching control loop in miniature — slot admission, per-slot
lengths, cache capacity management — runnable on CPU with reduced configs
and lowerable at full scale via the dry-run.

The engine is non-blocking by design: one ``step()`` call performs at most
one batched decode and returns, so an external multiplexer (the multi-tenant
gateway in :mod:`repro.serve.gateway`) can interleave several engines.  An
optional ``admission_gate`` lets that multiplexer impose global policies
(shared memory budget, fairness) on slot admission without changing the
single-engine control flow.

Every request carries an :class:`AdmissionTiming`: when it was
submitted, when its admission started, when its prefill and its cache
splice were dispatched, when its first token reached the host, and the
bytes its splice wrote.  The engine stamps it on the wall clock the
``repro.obs`` tracer uses, tracer or not, and its ``engine.*`` spans
reuse the same stamps.

For a model with MoE layers the prefill and the decode step also return
the rows each held expert computed in each layer; the engine pulls them
to the host with the tokens it samples (no sync of their own) and sums
them into :class:`EngineMetrics`.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Model
from repro.obs import TENANT_SCHEMA, conform, get_tracer


def _now_ms() -> float:
    """The wall clock of a ``repro.obs.Tracer``, in ms."""
    return time.perf_counter() * 1e3


@dataclasses.dataclass
class AdmissionTiming:
    """One request's way from the queue to its first token.

    Stamps are ms on :func:`_now_ms`; ``nan`` until reached.
    ``dispatch_ms`` runs from admission to the first-token sync (the host
    dispatching the prefill and the splice, which can itself block while
    the device is busy); ``wait_ms`` is the sync.
    """

    submitted: float = math.nan
    #: left the queue for a slot
    admitted: float = math.nan
    #: prefill dispatched; the cache splice starts
    prefilled: float = math.nan
    #: splice dispatched; the first-token sync starts
    dispatched: float = math.nan
    #: first token on the host
    first_token: float = math.nan
    #: bytes the splice wrote into the batched cache
    copy_bytes: int = 0

    @property
    def queue_ms(self) -> float:
        return self.admitted - self.submitted

    @property
    def dispatch_ms(self) -> float:
        return self.dispatched - self.admitted

    @property
    def wait_ms(self) -> float:
        return self.first_token - self.dispatched


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int = 16
    eos: int | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    timing: AdmissionTiming = dataclasses.field(
        default_factory=AdmissionTiming)


#: canonical per-tenant telemetry keys shared by every serving layer —
#: ``ServingEngine.metrics()``, the per-tenant rows of
#: ``MultiTenantGateway.metrics()`` and ``repro.serve.fleet`` reports all
#: emit exactly this shape, so a multiplexer consumes one dict format
#: regardless of which layer produced it.  Derived from the registry
#: schema in :mod:`repro.obs.metrics` — the schema is the single source
#: of truth, this tuple is the backward-compatible view of it.
METRIC_KEYS = tuple(TENANT_SCHEMA)


@dataclasses.dataclass
class EngineMetrics:
    """Rolling counters a multiplexer can poll between ``step()`` calls."""

    steps: int = 0
    admitted: int = 0
    #: queue->slot admissions refused by the admission gate.
    deferred: int = 0
    tokens_out: int = 0
    #: wall-clock ms of the most recent decode step (prefills excluded).
    last_step_ms: float = 0.0
    decode_ms_total: float = 0.0
    #: rows the held experts computed, over MoE layers, prefills and steps
    moe_rows_total: int = 0
    #: held experts times MoE layers, summed over prefills and decode steps
    moe_expert_steps: int = 0
    #: of those, the ones given at least one row (each read its weights)
    moe_experts_touched: int = 0
    #: expert assignments left uncomputed; the serving path drops none
    moe_dropped_rows: int = 0
    #: decode steps whose input cache was donated to the step, so the
    #: step updated it in place (read off the old leaves, no host sync)
    cache_inplace_steps: int = 0

    @property
    def mean_step_ms(self) -> float:
        return self.decode_ms_total / self.steps if self.steps else 0.0


class ServingEngine:
    def __init__(self, model: Model, params, max_slots: int = 4,
                 capacity: int = 256,
                 admission_gate: Callable[[Request], bool] | None = None,
                 on_logits: Callable[[str, jax.Array], None] | None = None):
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.capacity = capacity
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * max_slots
        self.lengths = np.zeros((max_slots,), np.int32)
        self.last_tok = np.zeros((max_slots,), np.int32)
        self.caches = model.init_cache(max_slots, capacity)
        self._rid = itertools.count()
        # the cache is donated: the step writes its new rows into the
        # stored stacks in place, and one cache is live, not two
        self._decode = jax.jit(model.decode_step, donate_argnums=1)
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, capacity=capacity))
        self.steps = 0
        self.completed: list[Request] = []
        #: consulted before each queue->slot admission; ``False`` defers the
        #: head request (FIFO is preserved: admission stops for this step).
        self.admission_gate = admission_gate
        #: observer of the device logits the engine samples from, called
        #: with ``("prefill", (1, 1, vocab))`` per admission and
        #: ``("decode", (max_slots, 1, vocab))`` per step, in that order;
        #: it gets the arrays as produced, with no host sync added.
        self.on_logits = on_logits
        self.counters = EngineMetrics()

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new: int = 16, eos: int | None = None
               ) -> Request:
        req = Request(next(self._rid), np.asarray(prompt, np.int32),
                      max_new=max_new, eos=eos)
        req.timing.submitted = _now_ms()
        self.queue.append(req)
        return req

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def has_work(self) -> bool:
        """Anything queued or decoding — i.e. ``step()`` would make progress."""
        return bool(self.queue) or self.active > 0

    def metrics(self) -> dict:
        """Telemetry snapshot in the canonical :data:`METRIC_KEYS` shape.

        Built through :func:`repro.obs.conform` so a missing canonical
        key fails here, at the provider, not in a downstream consumer.
        """
        c = self.counters
        return conform(TENANT_SCHEMA, {
            "steps": c.steps,
            "active": self.active,
            "queue_depth": len(self.queue),
            "admitted": c.admitted,
            "completed": len(self.completed),
            "deferred": c.deferred,
            "tokens_out": c.tokens_out,
            "last_step_ms": c.last_step_ms,
            "mean_step_ms": c.mean_step_ms,
        })

    # ------------------------------------------------------------------
    def _admit(self, tracer):
        for slot in range(self.max_slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            if (self.admission_gate is not None
                    and not self.admission_gate(self.queue[0])):
                self.counters.deferred += 1
                break
            req = self.queue.popleft()
            tm = req.timing
            tm.admitted = _now_ms()
            with tracer.span("engine.admit", "serve", t0_ms=tm.admitted,
                             rid=req.rid, slot=slot,
                             prompt_len=len(req.prompt)) as admit_sp:
                with tracer.span("engine.prefill", "serve",
                                 t0_ms=tm.admitted) as sp:
                    batch = {"token_ids": jnp.asarray(req.prompt)[None]}
                    logits, cache1, stats = self._prefill(self.params, batch)
                    if self.on_logits is not None:
                        self.on_logits("prefill", logits)
                    sp.t1 = tm.prefilled = _now_ms()
                with tracer.span("engine.splice", "serve",
                                 t0_ms=tm.prefilled) as sp:
                    tm.copy_bytes = self._splice(slot, cache1)
                    sp.t1 = tm.dispatched = _now_ms()
                with tracer.span("engine.first_token", "serve",
                                 t0_ms=tm.dispatched) as sp:
                    tok, stats = jax.device_get(
                        (jnp.argmax(logits[0, -1]), stats))
                    sp.t1 = admit_sp.t1 = tm.first_token = _now_ms()
            self._count_moe(stats)
            tok = int(tok)
            req.tokens.append(tok)
            self.slots[slot] = req
            self.lengths[slot] = len(req.prompt)
            self.last_tok[slot] = tok
            self.counters.admitted += 1
            self.counters.tokens_out += 1

    def _count_moe(self, stats: dict):
        """Add a prefill's or a step's MoE statistics (host arrays)."""
        if not stats:
            return
        rows = np.asarray(stats["moe_rows"])          # (MoE layers, held)
        c = self.counters
        c.moe_rows_total += int(rows.sum())
        c.moe_expert_steps += rows.size
        c.moe_experts_touched += int((rows > 0).sum())
        c.moe_dropped_rows += int(stats["moe_dropped"])

    def _splice(self, slot: int, cache1) -> int:
        """Write a single-request cache into ``slot`` of the batched cache;
        returns the bytes written.  Group caches are stacked
        (n_groups, batch, ...), every other part ("lead", "tail") is
        (batch, ...).  The eager ``.at[].set`` is out of place, so every
        replaced leaf is written whole."""
        new = dict(self.caches)
        replaced = []
        for part, big in self.caches.items():
            if big is None:
                continue
            if part == "groups":
                new[part] = jax.tree.map(
                    lambda b, one: b.at[:, slot].set(one[:, 0]),
                    big, cache1[part])
            else:
                new[part] = jax.tree.map(
                    lambda b, one: b.at[slot].set(one[0]), big, cache1[part])
            replaced += jax.tree.leaves(big)
        self.caches = new
        return sum(leaf.nbytes for leaf in replaced)

    def step(self) -> int:
        """Admit + one batched decode step; returns #active slots.

        Non-blocking from the caller's perspective: exactly one batched
        decode dispatch, timed into ``metrics.last_step_ms`` so a
        multiplexer can compare observed step latency against a schedule's
        prediction.
        """
        tracer = get_tracer()
        with tracer.span("engine.step", "serve", step=self.steps):
            return self._step(tracer)

    def _step(self, tracer) -> int:
        self._admit(tracer)
        if self.active == 0:
            return 0
        t0 = _now_ms()
        with tracer.span("engine.decode", "serve", t0_ms=t0) as sp:
            batch = {"token_ids": jnp.asarray(self.last_tok)[:, None],
                     "lengths": jnp.asarray(self.lengths)}
            old = jax.tree.leaves(self.caches)
            logits, self.caches, stats = self._decode(
                self.params, self.caches, batch)
            if all(leaf.is_deleted() for leaf in old):
                self.counters.cache_inplace_steps += 1
            sp.set(cache_inplace_steps=self.counters.cache_inplace_steps)
            if self.on_logits is not None:
                self.on_logits("decode", logits)
            toks, stats = jax.device_get(
                (jnp.argmax(logits[:, 0], axis=-1), stats))
            toks = np.asarray(toks, np.int32)
            sp.t1 = t1 = _now_ms()
        self._count_moe(stats)
        self.counters.last_step_ms = t1 - t0
        self.counters.decode_ms_total += self.counters.last_step_ms
        self.counters.steps += 1
        self.counters.tokens_out += self.active
        self.steps += 1
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            self.lengths[slot] += 1
            tok = int(toks[slot])
            req.tokens.append(tok)
            self.last_tok[slot] = tok
            if (len(req.tokens) >= req.max_new
                    or (req.eos is not None and tok == req.eos)
                    or self.lengths[slot] >= self.capacity - 1):
                req.done = True
                self.completed.append(req)
                self.slots[slot] = None
        return self.active

    def run_until_drained(self, max_steps: int = 10000):
        while (self.queue or self.active) and self.steps < max_steps:
            self.step()
        return self.completed


