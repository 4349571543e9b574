import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
# The two lines above MUST run before any other import (including repro.*): jax locks
# the device count on first backend init. Do not set this flag globally — smoke tests
# and benchmarks must see 1 device.

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis import roofline
from repro.configs import ARCHS, SHAPES, cell_applicable, get_config
from repro.configs.base import RehearsalConfig, RunConfig, TrainConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.utils.compat import cost_analysis, set_mesh


def rehearsal_buffer_cost(built, rcfg) -> dict:
    """Per-DP-worker rehearsal-buffer memory model, tiering- and
    strategy-aware.

    Flat (``tiering='off'``): ``K × slots`` raw rows resident in HBM — exactly
    what the compiled step allocates. Tiered (``'host'``): the hot tier plus the
    raw demotion staging rows stay in HBM, while the cold tier holds
    ``K × cold_slots`` *int8* rows in host memory (per float leaf: 1 byte per
    element, padded to whole 128-lane rows, + a 4-byte row scale —
    ``core.compression.compressed_spec``; int leaves stored raw). The cold
    tier never appears in the compiled HLO (it is host-resident), so it must
    be modeled here rather than read from XLA's memory analysis.

    Strategy aux fields (DER stored logits, grasp_embed embeddings) are part
    of the record spec the builder extends (``built.meta['aux_fields']``), so
    their bytes land in ``raw_row_bytes`` automatically; the ``aux_*`` entries
    break them out so the dense-vs-top-k logit saving (8–16x for big
    vocabularies) is visible in the record.
    """
    if built.meta.get("mode", "off") == "off":
        return {"mode": "off", "hot_hbm_bytes": 0, "cold_host_bytes": 0,
                "total_bytes": 0, "rows_per_bucket": 0}
    from repro.core.compression import LANES, lane_rows

    reps_s = built.args[3]  # [n_dp, r, ...] record structure
    aux_fields = dict(built.meta.get("aux_fields", {}))
    raw_row = cold_row = 0
    for leaf in jax.tree_util.tree_leaves(reps_s):
        shape = leaf.shape[2:]
        n = 1
        for d in shape:
            n *= d
        itemsize = jnp.dtype(leaf.dtype).itemsize
        raw_row += n * itemsize
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            # int8 slab (padded to whole 128-lane rows) + one f32 scale
            cold_row += lane_rows(n) * LANES + 4
        else:
            cold_row += n * itemsize
    aux_row = sum(aux_fields.values())
    k = rcfg.num_buckets
    hot_slots = built.meta["slots_per_bucket"]
    if getattr(rcfg, "tiered", False):
        cold_slots = rcfg.resolved_cold_slots
        stage = rcfg.resolved_demote_stage
        hot = k * hot_slots * raw_row + stage * raw_row
        cold = k * cold_slots * cold_row
        rows = hot_slots + cold_slots
    else:
        cold_slots = stage = 0
        hot = k * hot_slots * raw_row
        cold = 0
        rows = hot_slots
    from repro.buffer.api import resolve_placement

    return {
        "mode": "tiered" if cold_slots else "flat",
        # where the cold bytes land: 'pinned_host' on an accelerator,
        # 'device' on the CPU (tiered.resolve_cold_placement)
        "cold_placement": resolve_placement(rcfg) if cold_slots else None,
        "raw_row_bytes": raw_row,
        "cold_row_bytes": cold_row,
        # strategy aux-field share of every stored row (DER logits: dense
        # vocab rows vs top-k vals+idx pairs; grasp_embed embeddings)
        "strategy": built.meta.get("strategy", "rehearsal"),
        "aux_fields": aux_fields,
        "aux_row_bytes": int(aux_row),
        "aux_hot_bytes": int(aux_row) * k * hot_slots,
        "hot_slots_per_bucket": hot_slots,
        "cold_slots_per_bucket": cold_slots,
        "demote_stage_rows": stage,
        "hot_hbm_bytes": int(hot),
        "cold_host_bytes": int(cold),
        "total_bytes": int(hot + cold),
        "rows_per_bucket": rows,
        # capacity bought per HBM byte vs the flat layout at the same hot size
        "capacity_multiplier": round(rows / max(1, hot_slots), 3),
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    mode: str = "async",
    remat: str = "dots",
    exchange: str = "full",
    capacity: float = 1.25,
    compute_dtype: str = "bfloat16",
    scan_layers: bool = True,  # scan: full-depth compile proof (production HLO)
    out_dir: str = "benchmarks/results/dryrun",
    tag: str = "",
    attn: str = "auto",
    sp: bool = False,
    param_dtype: str = "float32",
    zero1: bool = False,
    kv_dtype: str = "bfloat16",
    tiering: str = "off",
    cold_slots: int = 0,
    strategy: str = "rehearsal",
    der_top_k: int = 0,
) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    if not ok:
        return {"cell": cell_id, "status": "skipped", "reason": reason}

    record = _compile_cell(cfg, arch, shape, multi_pod, mode=mode, remat=remat,
                           exchange=exchange, capacity=capacity,
                           compute_dtype=compute_dtype, scan_layers=scan_layers,
                           attn=attn, sp=sp, param_dtype=param_dtype, zero1=zero1,
                           kv_dtype=kv_dtype, tiering=tiering,
                           cold_slots=cold_slots, strategy=strategy,
                           der_top_k=der_top_k)
    record["cell"] = cell_id
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    return record


def _compile_cell(
    cfg,
    arch: str,
    shape,
    multi_pod: bool,
    *,
    mode: str = "async",
    remat: str = "dots",
    exchange: str = "full",
    capacity: float = 1.25,
    compute_dtype: str = "bfloat16",
    scan_layers: bool = True,
    attn: str = "auto",
    sp: bool = False,
    param_dtype: str = "float32",
    zero1: bool = False,
    kv_dtype: str = "bfloat16",
    tiering: str = "off",
    cold_slots: int = 0,
    strategy: str = "rehearsal",
    der_top_k: int = 0,
) -> dict:
    if capacity != 1.25:
        cfg = dataclasses.replace(cfg, capacity_factor=capacity)
    mesh_name = "multi" if multi_pod else "single"
    # The compiled step always carries the flat (hot/HBM) buffer — the cold
    # tier is host-resident and enters only the analytic cost model below.
    from repro.configs.base import ScenarioConfig, StrategyConfig

    run = RunConfig(
        model=cfg,
        shape=shape,
        train=TrainConfig(remat=remat, compute_dtype=compute_dtype,
                          scan_layers=scan_layers, attn_impl=attn,
                          sequence_parallel=sp, param_dtype=param_dtype,
                          zero1=zero1, kv_dtype=kv_dtype),
        rehearsal=RehearsalConfig(mode=mode),
        strategy=StrategyConfig(top_k=der_top_k),
        scenario=ScenarioConfig(strategy=strategy),
    )
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for s in mesh.shape.values():
        chips *= s

    t0 = time.time()
    with set_mesh(mesh):
        built = build_step(run, mesh, exchange=exchange) if shape.kind == "train" \
            else build_step(run, mesh)
        lowered = built.fn.lower(*built.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = cost_analysis(compiled)
    try:
        mem = compiled.memory_analysis()
    except Exception:  # backend without memory analysis
        mem = None
    hlo = compiled.as_text()

    result = roofline.analyze(
        arch=arch,
        shape=shape.name,
        mesh_name=mesh_name,
        kind=shape.kind,
        chips=chips,
        cost=cost,
        hlo_text=hlo,
        active_params=cfg.active_param_count(),
        tokens_per_step=built.meta["tokens_per_step"],
        memory_stats=mem,
        notes=f"mode={built.meta.get('mode','-')} remat={remat} exchange={exchange}",
    )
    record = dataclasses.asdict(result)
    record.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        total_params=cfg.param_count(),
        meta=built.meta,
    )
    if shape.kind == "train":
        cost_rcfg = dataclasses.replace(
            run.rehearsal, tiering=tiering,
            hot_slots=built.meta.get("slots_per_bucket", 0),
            cold_slots=cold_slots)
        record["rehearsal_buffer"] = rehearsal_buffer_cost(built, cost_rcfg)
        from repro.obs.metrics import estimate_obs_cost

        # what turning run.obs on WOULD add to this cell's step outputs —
        # bytes per step, so obs is a latency question (fig6's 1.03x gate),
        # never a bandwidth one
        record["obs_cost"] = estimate_obs_cost(
            cost_rcfg, has_aux=bool(built.meta.get("aux_fields")),
            policy=getattr(cost_rcfg, "policy", None))
    if mem is not None:
        try:
            record["memory_analysis"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "peak_bytes": int(
                    getattr(mem, "peak_memory_in_bytes", 0)
                    or mem.temp_size_in_bytes + mem.output_size_in_bytes
                ),
            }
        except AttributeError:
            pass

    return record



def _affine_scale(r1: dict, r2: dict, l1: int, l2: int, l_full: int) -> dict:
    """Linear extrapolation of additive cost fields from two shallow compiles.

    Every per-step cost is affine in layer count (const embed/logits/buffer part +
    per-layer part): c(L) = c(l1) + (c(l2)-c(l1))/(l2-l1) * (L-l1). Verified by the
    two-point fit being exact on a third depth (tests/test_dryrun.py).
    """
    def ex(a, b):
        return a + (b - a) * (l_full - l1) / (l2 - l1)

    out = dict(r2)
    for k in ("flops_per_chip", "bytes_per_chip", "collective_bytes_per_chip"):
        out[k] = ex(r1[k], r2[k])
    per = {}
    kinds = set(r1["per_collective"]) | set(r2["per_collective"])
    for kind in kinds:
        d1 = r1["per_collective"].get(kind, {"bytes": 0.0, "count": 0})
        d2 = r2["per_collective"].get(kind, {"bytes": 0.0, "count": 0})
        per[kind] = {"bytes": ex(d1["bytes"], d2["bytes"]),
                     "count": ex(d1["count"], d2["count"])}
    out["per_collective"] = per
    if r1.get("memory_analysis") and r2.get("memory_analysis"):
        out["memory_analysis"] = {
            k: int(ex(r1["memory_analysis"][k], r2["memory_analysis"][k]))
            for k in r1["memory_analysis"]
        }
    # recompute derived terms from the scaled primitives
    out["compute_s"] = out["flops_per_chip"] / roofline.PEAK_FLOPS
    out["memory_s"] = out["bytes_per_chip"] / roofline.HBM_BW
    out["collective_s"] = out["collective_bytes_per_chip"] / roofline.ICI_BW
    terms = {"compute": out["compute_s"], "memory": out["memory_s"],
             "collective": out["collective_s"]}
    out["bottleneck"] = max(terms, key=terms.get)
    glob = max(out["flops_per_chip"] * out["chips"], 1.0)
    out["useful_ratio"] = out["model_flops"] / glob
    ideal_s = (out["model_flops"] / out["chips"]) / roofline.PEAK_FLOPS
    out["roofline_fraction"] = ideal_s / max(max(terms.values()), 1e-12)
    out["depth_fit"] = {"l1": l1, "l2": l2, "l_full": l_full,
                        "compile_s": [r1["compile_s"], r2["compile_s"]]}
    return out


def run_cell_scaled(arch: str, shape_name: str, multi_pod: bool, **kw) -> dict:
    """Accurate roofline numbers via the two-depth unrolled fit (see EXPERIMENTS.md
    §Dry-run for why: XLA cost analysis counts scan bodies once, and full-depth
    unrolled compiles are prohibitively slow on this host)."""
    from repro.models.transformer import unit_period

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    tag = kw.pop("tag", "") or "scaled"
    out_dir = kw.pop("out_dir", "benchmarks/results/dryrun")
    cell_id = f"{arch}__{shape_name}__{mesh_name}__{tag}"
    if not ok:
        return {"cell": cell_id, "status": "skipped", "reason": reason}

    period = unit_period(cfg)
    l_full = cfg.num_layers
    l1, l2 = period, 2 * period
    if l_full <= max(8, l2):  # shallow stacks: one exact full-depth unrolled compile
        rec = run_cell(arch, shape_name, multi_pod, scan_layers=False,
                       out_dir=out_dir, tag=tag, **kw)
        rec["depth_fit"] = {"l1": l_full, "l2": l_full, "l_full": l_full,
                            "compile_s": [rec["compile_s"]]}
    else:
        recs = []
        for l in (l1, l2):
            sub_cfg = dataclasses.replace(cfg, num_layers=l)
            if cfg.num_encoder_layers:
                sub_cfg = dataclasses.replace(sub_cfg, num_encoder_layers=max(
                    1, cfg.num_encoder_layers * l // l_full))
            recs.append(_compile_cell(sub_cfg, arch, shape, multi_pod,
                                      scan_layers=False, **kw))
        rec = _affine_scale(recs[0], recs[1], l1, l2, l_full)
        rec["cell"] = cell_id
        rec["status"] = "ok"
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="async", choices=["async", "sync", "off"],
                    help="rehearsal mode for train cells")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--exchange", default="full", choices=["full", "pod_local", "local"])
    ap.add_argument("--capacity", type=float, default=1.25)
    ap.add_argument("--compute-dtype", default="bfloat16")
    ap.add_argument("--attn", default="auto", choices=["auto", "blocked", "naive"])
    ap.add_argument("--sp", action="store_true", help="Megatron sequence parallelism")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--zero1", action="store_true", help="shard optimizer state over data")
    ap.add_argument("--kv-dtype", default="bfloat16",
                    help="decode-cache storage dtype (bfloat16 | float8_e4m3fn)")
    ap.add_argument("--tiering", default="off", choices=["off", "host"],
                    help="model a host int8 cold tier in the buffer cost model")
    ap.add_argument("--cold-slots", type=int, default=0,
                    help="cold rows/bucket for the tiered cost model (0 -> 3x hot)")
    ap.add_argument("--strategy", default="rehearsal",
                    help="training strategy for train cells (rehearsal | der | "
                         "der_pp | grasp_embed); tap strategies extend the "
                         "record spec with aux fields the cost model accounts")
    ap.add_argument("--der-top-k", type=int, default=0,
                    help="DER stored-logit top-k compression (0 = dense rows)")
    ap.add_argument("--method", default="scan", choices=["scan", "scaled"],
                    help="scan: full-depth compile proof; scaled: two-depth unrolled "
                         "fit for accurate roofline costs")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                tag = args.tag or ("scaled" if args.method == "scaled" else "")
                cell_id = f"{arch}__{shape}__{mesh_name}" + (f"__{tag}" if tag else "")
                path = os.path.join(args.out, cell_id + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"SKIP(existing) {cell_id}", flush=True)
                    continue
                try:
                    runner = run_cell_scaled if args.method == "scaled" else run_cell
                    rec = runner(
                        arch, shape, multi,
                        mode=args.mode, remat=args.remat, exchange=args.exchange,
                        capacity=args.capacity, compute_dtype=args.compute_dtype,
                        attn=args.attn, sp=args.sp, param_dtype=args.param_dtype,
                        zero1=args.zero1, kv_dtype=args.kv_dtype,
                        tiering=args.tiering, cold_slots=args.cold_slots,
                        strategy=args.strategy, der_top_k=args.der_top_k,
                        out_dir=args.out, tag=args.tag,
                    )
                    if rec["status"] == "skipped":
                        print(f"SKIP {cell_id}: {rec['reason']}", flush=True)
                    else:
                        print(
                            f"OK   {cell_id} compile={rec['compile_s']}s "
                            f"flops/chip={rec['flops_per_chip']:.3e} "
                            f"coll/chip={rec['collective_bytes_per_chip']:.3e} "
                            f"bottleneck={rec['bottleneck']} "
                            f"roofline={rec['roofline_fraction']:.3f}",
                            flush=True,
                        )
                except Exception:
                    failures += 1
                    print(f"FAIL {cell_id}", flush=True)
                    traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
