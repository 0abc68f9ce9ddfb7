import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Everything below this line may touch jax (device count is locked above).
import argparse        # noqa: E402
import json            # noqa: E402
import pathlib         # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs                      # noqa: E402
from repro.analysis import roofline            # noqa: E402
from repro.configs.base import SHAPES, cell_supported  # noqa: E402
from repro.launch.mesh import make_production_mesh     # noqa: E402
from repro.models import Model, sharding       # noqa: E402
from repro.train import optimizer as opt_lib   # noqa: E402
from repro.train.trainer import TrainState, make_train_step  # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent — sharding
propagates, collectives are legal, per-device memory is bounded — without
real hardware: inputs are ShapeDtypeStructs (no allocation), and the
compiled module yields memory_analysis / cost_analysis / the collective
schedule for the §Roofline analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out artifacts/dryrun
"""

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# cache logical specs (mirror model.init_cache structure)
# ---------------------------------------------------------------------------

def cache_logical(model: Model):
    cfg = model.cfg

    def kv_layer():
        # lane-dense (B, S, Hkv·D): heads are major in the merged last dim
        d = {"data": ("batch", "kv_seq", "kv_heads")}
        if cfg.kv_cache_dtype == "int8":
            d["scale"] = ("batch", "kv_seq", "kv_heads")
        return d

    def one(kind, scanned: bool):
        pre = ("layers",) if scanned else ()
        if kind in ("attn", "local"):
            lay = kv_layer()
            return {"k": {k: pre + v for k, v in lay.items()},
                    "v": {k: pre + v for k, v in lay.items()}}
        if kind == "mla":           # one latent row per position
            return {k: pre + v for k, v in kv_layer().items()}
        if kind == "rglru":
            return {"h": pre + ("batch", "rnn"),
                    "conv": pre + ("batch", None, "rnn")}
        if kind == "rwkv":
            return {"S": pre + ("batch", "heads", None, None),
                    "x_t": pre + ("batch", "embed"),
                    "x_c": pre + ("batch", "embed")}
        raise ValueError(kind)

    kinds = cfg.layer_kinds
    n_lead = cfg.first_dense
    P = len(cfg.block_pattern)
    n_groups = ((len(kinds) - n_lead) // P) if cfg.scan_layers else 0
    groups = (tuple(one(cfg.block_pattern[pos], True) for pos in range(P))
              if n_groups else None)
    tail = tuple(one(kinds[i], False)
                 for i in range(n_lead + n_groups * P, len(kinds)))
    out = {"groups": groups, "tail": tail}
    if n_lead:
        out["lead"] = tuple(one(kinds[i], False) for i in range(n_lead))
    return out


def batch_logical(batch):
    out = {}
    for k, v in batch.items():
        if k in ("token_ids", "labels", "mask"):
            out[k] = ("batch", "seq")
        elif k in ("embeds", "mm_embeds"):
            out[k] = ("batch", "seq", None)
        elif k == "lengths":
            out[k] = ("batch",)
        else:
            out[k] = tuple([None] * v.ndim)
    return out


# ---------------------------------------------------------------------------
# per-cell build + compile
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape: str, mesh, rules_override=None,
               cfg_override=None, cell_override=None, backend="xla"):
    """Returns (fn, args_abstract, in_shardings, meta)."""
    cfg = cfg_override or configs.get(arch)
    cell = cell_override or SHAPES[shape]
    train = cell.kind == "train"
    if not train and cfg.param_dtype != "bfloat16":
        import dataclasses as _dc
        cfg = _dc.replace(cfg, param_dtype="bfloat16")   # serve in bf16
    rules = dict(rules_override or (cfg.rules if train else cfg.serve_rules))
    model = Model(cfg, rules=rules, backend=backend)
    batch = model.input_specs(cell)
    b_sh = sharding.tree_shardings(mesh, rules, batch_logical(batch), batch)
    params = model.abstract_params()
    p_sh = sharding.tree_shardings(mesh, rules, model.specs(), params)

    if train:
        opt = opt_lib.make(cfg.optimizer, cfg.learning_rate)
        opt_state = jax.eval_shape(opt.init, params)
        o_sh = jax.tree.map(
            lambda x: sharding.named_sharding(
                mesh, rules, tuple([None] * x.ndim), x.shape),
            opt_state)
        # better: optimizer state mirrors param shardings where shapes match
        o_sh = _opt_shardings(mesh, rules, model.specs(), params, opt_state)
        state = TrainState(jax.ShapeDtypeStruct((), jnp.int32), params,
                           opt_state)
        state_sh = TrainState(
            sharding.named_sharding(mesh, rules, ()), p_sh, o_sh)
        step = make_train_step(model, opt, cfg.microbatches)
        fn = lambda s, b: step(s, b)
        return fn, (state, batch), (state_sh, b_sh), model

    if cell.kind == "prefill":
        fn = lambda p, b: model.prefill(p, b, capacity=cell.seq_len)
        return fn, (params, batch), (p_sh, b_sh), model

    # decode
    caches = model.cache_specs(cell)
    c_sh = sharding.tree_shardings(mesh, rules, cache_logical(model), caches)
    fn = lambda p, c, b: model.decode_step(p, c, b)
    return fn, (params, caches, batch), (p_sh, c_sh, b_sh), model


def _opt_shardings(mesh, rules, specs, params, opt_state):
    """Optimizer-state shardings: mirror the param's logical axes where the
    state leaf has the same shape; factored (adafactor) leaves drop axes."""
    flat_p, tdef_p = jax.tree.flatten(params)
    flat_s = jax.tree.flatten(specs, is_leaf=sharding._is_logical)[0]
    by_shape = {}
    for p, lg in zip(flat_p, flat_s):
        by_shape.setdefault(p.shape, lg)

    def one(x):
        lg = by_shape.get(x.shape)
        if lg is None:
            # factored moment: match a param by prefix shape
            for shape, plg in by_shape.items():
                if x.shape == shape[:-1]:
                    lg = plg[:-1]
                    break
                if x.shape == shape[:-2] + shape[-1:]:
                    lg = plg[:-2] + plg[-1:]
                    break
        if lg is None:
            lg = tuple([None] * x.ndim)
        return sharding.named_sharding(mesh, rules, lg, x.shape)

    return jax.tree.map(one, opt_state)


def _compile_costs(arch, shape, mesh, cfg, cell, rules_override,
                   backend="stub"):
    """(flops, bytes, coll_operand_bytes) of one compiled variant.

    Probes default to the "stub" mixer backend: temporal-mix ops read/write
    kernel-true HBM shapes with ~zero flops (the Pallas kernels keep score
    tiles in VMEM on the TPU target; the XLA fallback would spill S x bkv
    score tensors and wildly overstate the memory term).  The mixers' flops
    are added back analytically by ``mixer_flops``.
    """
    with mesh:
        fn, args, shardings, _ = build_cell(
            arch, shape, mesh, rules_override, cfg_override=cfg,
            cell_override=cell, backend=backend)
        compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
        cost = compiled.cost_analysis() or {}
        coll = roofline.parse_collectives(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            coll.operand_bytes, coll.op_counts)


def probe_costs(arch: str, shape: str, mesh, rules_override=None):
    """XLA cost_analysis counts a scan body ONCE regardless of trip count,
    so the full-depth compile under-reports flops/bytes/collectives.  We
    measure the layer body on *unrolled* probes (scan_layers=False,
    microbatches=1) and reconstruct the real step cost

        C = opt_base + M * (q + G * r)

    with r (per supergroup) from a depth pair, q (per-microbatch embed/
    logits/loss) from a batch pair, and opt_base (optimizer update, train
    only) as the batch-independent remainder:

        r = C(2P layers, B) - C(P layers, B)
        q + r = C(P layers, 2B) - C(P layers, B)
        opt_base = C(P layers, B) - (q + r)            [0 for serve]

    Unrolled tail layers are checkpointed like scan groups, so remat
    recompute is included.  Fusion differences between the unrolled probes
    and the scanned production program are the residual error.
    """
    import dataclasses as dc
    cfg = configs.get(arch)
    cell = SHAPES[shape]
    P = len(cfg.block_pattern)
    train = cell.kind == "train"
    M_real = cfg.microbatches if train else 1
    G_real = cfg.n_layers / P
    B_mb = max(1, cell.global_batch // M_real)

    def probe(n_layers, batch):
        pcfg = dc.replace(cfg, n_layers=n_layers, microbatches=1,
                          scan_layers=False)
        pcell = dc.replace(cell, global_batch=batch)
        return _compile_costs(arch, shape, mesh, pcfg, pcell, rules_override)

    cA = probe(P, B_mb)
    cB = probe(2 * P, B_mb)
    ops = cB[3]
    if train:
        cC = probe(P, 2 * B_mb)
    out = {}
    for i, name in enumerate(("flops", "bytes", "coll")):
        r = max(cB[i] - cA[i], 0.0)
        if train:
            q_plus_r = max(cC[i] - cA[i], 0.0)
            opt_base = max(cA[i] - q_plus_r, 0.0)
            q = max(q_plus_r - r, 0.0)
        else:
            opt_base = 0.0
            q = max(cA[i] - r, 0.0)
        out[name] = opt_base + M_real * (q + G_real * r)
    out["flops"] += mixer_flops(cfg, cell)
    return out["flops"], out["bytes"], out["coll"], ops


def mixer_flops(cfg, cell) -> float:
    """Analytic global flops of the stubbed temporal-mix kernels, per chip.

    attention: 4 * B * Hq * Sq * kv_len * d_head (QK^T + PV), causal halves
    kv_len, local caps it at the window; rwkv: ~6 H D Dv per token (outer
    product + readout + decay); rglru: ~10 r per token.  Train multiplies by
    4 (fwd + 2x bwd + remat recompute).
    """
    B, S = cell.global_batch, cell.seq_len
    train = cell.kind == "train"
    mult = 4.0 if train else 1.0
    sq = 1 if cell.kind == "decode" else S
    total = 0.0
    H, dh = cfg.n_heads, cfg.d_head
    for kind in cfg.layer_kinds:
        if kind == "attn":
            kv_len = S if cfg.bidirectional else (
                S if cell.kind == "decode" else S / 2)
            total += 4.0 * B * H * sq * kv_len * dh
        elif kind == "local":
            kv_len = min(cfg.local_window, S)
            total += 4.0 * B * H * sq * kv_len * dh
        elif kind == "rwkv":
            d_head_r = cfg.d_model // H
            total += 6.0 * B * sq * H * d_head_r * d_head_r
        elif kind == "rglru":
            total += 10.0 * B * sq * cfg.d_rnn
    # per chip (flops shard over batch x model like the projections)
    return mult * total / 256.0


def run_cell(arch: str, shape: str, multi_pod: bool,
             out_dir: pathlib.Path, rules_override=None,
             tag: str = "") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "tag": tag}
    cfg = configs.get(arch)
    ok, why = cell_supported(cfg, shape)
    if not ok:
        rec["status"] = "skip"
        rec["reason"] = why
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.perf_counter()
    with mesh:
        fn, args, shardings, model = build_cell(arch, shape, mesh,
                                                rules_override)
        lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        print(mem)          # proves it fits (per-device bytes)
        print({k: cost.get(k) for k in ("flops", "bytes accessed")})
        hlo = compiled.as_text()
    coll_sched = roofline.parse_collectives(hlo)
    flops_e, bytes_e, coll_e, probe_ops = probe_costs(
        arch, shape, mesh, rules_override)
    bytes_analytic = roofline.analytic_hbm_bytes(cfg, SHAPES[shape]) \
        / mesh.devices.size
    cost = {"flops": flops_e, "bytes accessed": bytes_analytic,
            "bytes_xla_probe": bytes_e}
    coll = roofline.CollectiveStats(
        {k: v for k, v in sorted(coll_sched.op_counts.items())},
        coll_e, coll_e)

    cell = SHAPES[shape]
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode"
                                  else 1)
    n = cfg.n_active_params() if cfg.moe else cfg.n_params()
    factor = 6 if cell.kind == "train" else 2
    model_flops = factor * n * tokens
    useful_bytes = None
    if cell.kind == "decode":
        # per-chip useful traffic: active params + live kv/state read once
        kv_bytes = _decode_state_bytes(cfg, cell)
        wb = 2 * n  # bf16 weights
        useful_bytes = (wb + kv_bytes) / n_chips
    rl = roofline.analyze(cost, coll, n_chips, model_flops,
                          useful_bytes, cell.kind)

    rec.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            peak_estimate_gb=round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes) / 1e9, 3),
        ),
        cost={k: cost.get(k) for k in ("flops", "bytes accessed",
                                       "utilization")
              if k in cost},
        roofline=roofline.to_dict(rl),
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}_{shape}_{mesh_name}{('_' + tag) if tag else ''}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=1))
    return rec


def _decode_state_bytes(cfg, cell) -> float:
    per_tok = 0
    kv_b = 1 if cfg.kv_cache_dtype == "int8" else 2
    for kind in cfg.layer_kinds:
        if kind == "attn":
            per_tok += 2 * cfg.n_kv_heads * cfg.d_head * kv_b * cell.seq_len
        elif kind == "local":
            per_tok += (2 * cfg.n_kv_heads * cfg.d_head * kv_b
                        * min(cfg.local_window, cell.seq_len))
        elif kind == "rglru":
            per_tok += 4 * cfg.d_rnn * 4
        elif kind == "rwkv":
            H = cfg.n_heads
            per_tok += H * (cfg.d_model // H) ** 2 * 4
    return per_tok * cell.global_batch


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(ARTIFACTS))
    ap.add_argument("--force", action="store_true",
                    help="recompute existing artifacts")
    args = ap.parse_args(argv)

    archs = list(configs.ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    out_dir = pathlib.Path(args.out)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multi" if mp else "single"
                art = out_dir / f"{arch}_{shape}_{mesh_name}.json"
                if art.exists() and not args.force:
                    rec = json.loads(art.read_text())
                    print(f"[cached] {arch} {shape} {mesh_name}: "
                          f"{rec.get('status')}")
                    continue
                label = f"{arch} x {shape} x {mesh_name}"
                try:
                    t0 = time.perf_counter()
                    rec = run_cell(arch, shape, mp, out_dir)
                    dt = time.perf_counter() - t0
                    if rec["status"] == "skip":
                        print(f"[skip] {label}: {rec['reason']}")
                        (out_dir / f"{arch}_{shape}_{mesh_name}.json"
                         ).parent.mkdir(parents=True, exist_ok=True)
                        art.write_text(json.dumps(rec, indent=1))
                    else:
                        r = rec["roofline"]
                        print(f"[ok] {label}: compile={rec['compile_s']}s "
                              f"mem={rec['memory']['peak_estimate_gb']}GB/chip "
                              f"bound={r['bottleneck']} "
                              f"frac={r['roofline_fraction']:.3f} ({dt:.0f}s)")
                except Exception:
                    failures.append(label)
                    print(f"[FAIL] {label}\n{traceback.format_exc()}")
    if failures:
        print("FAILURES:", failures)
        return 1
    print("dry-run complete.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
