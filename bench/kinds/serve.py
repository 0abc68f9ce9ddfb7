"""Serving cells: open-loop arrivals at a fixed rate into the program's
``ServingEngine`` (continuous batching over decode slots), one process,
one host thread.

Set-up builds the engine at the configuration's sizes over weights drawn
from the seed (``reference/lm_ref.make_params``, one jitted call), then
warms every prompt length this run's traffic uses, on every slot.  The
window submits each request when it is due and steps the engine while it
has work; tokens reach the user when the ``step()`` that made them
returns.  After the window closes, requests due in it are served to the
end (at most ``drain_s`` more seconds); the time-to-first-token counts
from when a request was due.

The check: once the program's state is freed, the longest finished
request and a seeded sample of those served beside it (``check_requests``
in all) are run through the plain float32 reference over prompt plus
served tokens; ``served_logit_gap`` is
the widest gap by which a served token's reference logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

import generate
from harness import BenchError, percentile, sub_seed
from reference import lm_ref


@dataclasses.dataclass
class ReqRecord:
    due: float
    prompt: np.ndarray
    max_new: int
    req: object = None
    submit: float = 0.0
    times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    admitted: int
    decode_ms: float
    decoded: list         # context length of each token the decode made
    prompts: list         # prompt lengths admitted in this step


def model_config(cfg: dict):
    """The program's ModelConfig for the sizes the file states."""
    from repro import configs
    base = configs.get(cfg["arch"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        d_head=cfg["hidden_size"] // cfg["num_attention_heads"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        qkv_bias=cfg["use_qkv_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"], dtype=cfg["activation_dtype"],
        kv_cache_dtype=cfg["kv_cache_dtype"])


class Driver:
    def __init__(self, cell, seed: int, seconds: float, tracing, control: bool):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.tracing, self.control = tracing, control
        self.cfg, self.mix = cell.config, cell.traffic
        self.sizes = lm_ref.sizes(self.cfg)
        self.engine = None

    def setup(self):
        import jax
        from repro.models import build
        from repro.serve.engine import ServingEngine
        c = self.cfg
        model = build(model_config(c))
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), model.abstract_params())
        params = lm_ref.make_params(sub_seed(self.seed, "weights"), self.sizes)
        got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
        if got != want:
            raise BenchError("the program's parameter tree differs from the "
                             "one the benchmark draws")
        self.weights_seed = sub_seed(self.seed, "weights")
        eng = ServingEngine(model, params, max_slots=c["engine"]["max_slots"],
                            capacity=c["engine"]["capacity"])
        self.engine = eng
        self.reqs = [ReqRecord(r["due"], r["prompt"], r["max_new"])
                     for r in generate.serve_requests(
                         self.mix, self.seed, self.seconds, vocab=c["vocab_size"],
                         capacity=eng.capacity)]
        # warm-up: every prompt length of this run, on every slot
        rng = np.random.default_rng(sub_seed(self.seed, "warm"))
        lengths = sorted({len(r.prompt) for r in self.reqs})
        while len(lengths) < eng.max_slots:
            lengths = lengths + lengths
        for n in lengths:
            eng.submit(rng.integers(0, c["vocab_size"], size=n, dtype=np.int32),
                       max_new=2)
        eng.run_until_drained()
        eng.completed.clear()

    # ------------------------------------------------------------------
    def window(self):
        eng, tr = self.engine, self.tracing
        c0 = dataclasses.replace(eng.counters)
        pending = list(self.reqs)
        live: list[ReqRecord] = []
        self.steps: list[StepRecord] = []
        drain_s = self.mix.get("drain_s", 60.0)
        clock = time.perf_counter
        t0 = clock()
        tr.start()
        try:
            while pending or live:
                now = clock() - t0
                if now > self.seconds + drain_s:
                    break
                while pending and pending[0].due <= now:
                    r = pending.pop(0)
                    with tr.ann("submit", due_ms=r.due * 1e3):
                        r.req = eng.submit(r.prompt, max_new=r.max_new)
                    r.submit = clock() - t0
                    live.append(r)
                if not eng.has_work:
                    if pending:
                        with tr.ann("generator_wait"):
                            time.sleep(max(0.0, pending[0].due - (clock() - t0)))
                    tr.poll()
                    continue
                admitted0 = eng.counters.admitted
                s0 = clock() - t0
                with tr.ann("engine_step", n=len(self.steps)):
                    eng.step()
                s1 = clock() - t0
                rec = StepRecord(s0, s1, eng.counters.admitted - admitted0,
                                 eng.counters.last_step_ms, [], [])
                for r in live:
                    n = len(r.req.tokens)
                    for k in range(len(r.times) + 1, n + 1):
                        r.times.append(s1)
                        if k == 1:
                            rec.prompts.append(len(r.prompt))
                        else:   # token k was decoded over prompt + k - 1
                            rec.decoded.append(len(r.prompt) + k - 1)
                self.steps.append(rec)
                live = [r for r in live if not r.req.done]
                tr.poll()
        finally:
            tr.stop()
        late = max((r.submit - r.due for r in self.reqs if r.req is not None),
                   default=0.0)
        print(f"generator: latest submission {late * 1e3:.1f} ms after due",
              file=sys.stderr)
        c1 = eng.counters
        self.decode_ms = c1.decode_ms_total - c0.decode_ms_total
        self.decode_steps = c1.steps - c0.steps

    def free(self):
        """Drop the program's device state before the reference runs."""
        import jax
        self.engine = None
        gc.collect()
        jax.clear_caches()

    # ------------------------------------------------------------------
    def end_to_end(self) -> dict:
        inf = float("inf")
        ttft = [r.times[0] - r.due if r.times else inf for r in self.reqs]
        gaps = []
        for r in self.reqs:
            gaps += list(np.diff(r.times))
            if len(r.times) < r.max_new:
                gaps.append(inf)
        toks = sum(sum(t <= self.seconds for t in r.times) for r in self.reqs)
        return {"ttft_p90_ms": 1e3 * percentile(ttft, 90),
                "tbt_p95_ms": 1e3 * percentile(gaps, 95),
                "out_tok_per_s": toks / self.seconds}

    def attempted_failed(self):
        return len(self.reqs), sum(r.req is None or not r.req.done
                                   for r in self.reqs)

    def checks(self) -> dict:
        done = [r for r in self.reqs if r.req is not None and r.req.done]
        if not done:
            raise BenchError("no request finished")
        rng = np.random.default_rng(sub_seed(self.seed, "check"))
        longest = max(done, key=lambda r: len(r.req.tokens))
        lo, hi = longest.times[0], longest.times[-1]
        others = [r for r in done if r is not longest]
        # those that were in the batch beside the longest come first
        beside = [r.times[0] <= hi and r.times[-1] >= lo for r in others]
        order = sorted(rng.permutation(len(others)), key=lambda i: not beside[i])
        sample = [longest] + [others[i] for i in order[:self.mix["check_requests"] - 1]]
        seqs = [(r.prompt, np.asarray(r.req.tokens, np.int32)) for r in sample]
        gaps = lm_ref.served_gaps(self.weights_seed, self.sizes, seqs,
                                  quant="int8", control=self.control)
        widest = float(max(g.max() for g in gaps))
        return {"served_logit_gap": {"value": widest,
                                     "limit": self.cfg["limits"]["served_logit_gap"]}}

    # ------------------------------------------------------------------
    def layer_context(self) -> dict:
        return {"steps": self.steps, "reqs": self.reqs, "cfg": self.cfg,
                "sizes": self.sizes, "decode_ms": self.decode_ms,
                "decode_steps": self.decode_steps}
