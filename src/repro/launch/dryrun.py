import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production mesh with ShapeDtypeStruct inputs (no
allocation), print memory/cost analysis, and record collective traffic for
the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

The two lines above MUST precede any other import (jax locks the device
count on first init). Run modes:

    python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh multi
    python -m repro.launch.dryrun --all --out results/dryrun   # orchestrator
"""

import argparse
import json
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch.hlo_analysis import (
    MODELED_CHIP,
    analyze_hlo,
    chip_peaks,
    roofline_terms,
)
from repro.launch.mesh import make_production_mesh, mesh_dp_size, mesh_model_size
from repro.models import transformer as tfm
from repro.models.config import SHAPES, ModelConfig
from repro.models.layers import DATA, POD, ShardCtx, dtype_of
from repro.training import optimizer as opt
from repro.training.train_loop import _accumulate_grads

RETRIEVAL_ARCH = "allanpoe-retrieval"  # extra dry-run target: the paper's index


def batch_size_spec(batch: int, mesh) -> P:
    dp = tuple(a for a in (POD, DATA) if a in mesh.axis_names)
    dp_total = mesh_dp_size(mesh)
    if batch % dp_total == 0 and batch >= dp_total:
        return P(dp if len(dp) > 1 else dp[0])
    return P()


def input_specs(cfg: ModelConfig, shape_name: str, mesh) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    shape = SHAPES[shape_name]
    b, l = shape.global_batch, shape.seq_len
    dt = dtype_of(cfg)
    out = {}
    if shape.kind in ("train", "prefill"):
        out["tokens"] = jax.ShapeDtypeStruct((b, l), jnp.int32)
        if cfg.family in ("vlm", "audio"):
            out["frontend"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frontend_tokens, cfg.d_model), dt
            )
    else:  # decode
        out["token"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        out["cache"] = tfm.cache_shape(cfg, b, l)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


def _shardings_for(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_cell_program(cfg: ModelConfig, shape_name: str, mesh):
    """Returns (jitted_fn, arg_structs) ready to .lower(*arg_structs)."""
    shape = SHAPES[shape_name]
    tp = mesh_model_size(mesh)
    dp = mesh_dp_size(mesh)
    ctx = ShardCtx(model_size=tp, fsdp=cfg.fsdp)
    pspecs = tfm.param_specs(cfg, ctx)
    p_shard = _shardings_for(pspecs, mesh)
    param_structs = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    mesh_axes = tuple(mesh.axis_names)
    ins = input_specs(cfg, shape_name, mesh)
    bspec = batch_size_spec(shape.global_batch, mesh)
    b_shard = NamedSharding(mesh, bspec)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        big = cfg.n_params > 100e9
        ocfg = opt.OptConfig(moment_dtype="bfloat16" if big else "float32")
        loss_fn = tfm.make_loss_fn(cfg, mesh_axes)
        opt_structs = jax.eval_shape(lambda p: opt.init_opt_state(p, ocfg), param_structs)
        o_shard = {"m": p_shard, "v": p_shard, "step": repl}

        def step(state, batch):
            loss, grads = _accumulate_grads(loss_fn, state["params"], batch, 1)
            new_p, new_o, metrics = opt.adamw_update(
                grads, state["opt"], state["params"], ocfg
            )
            return {"params": new_p, "opt": new_o}, metrics

        state_structs = {"params": param_structs, "opt": opt_structs}
        state_shard = {"params": p_shard, "opt": o_shard}
        batch_structs = {"tokens": ins["tokens"]}
        batch_shard = {"tokens": b_shard}
        if "frontend" in ins:
            batch_structs["frontend"] = ins["frontend"]
            batch_shard["frontend"] = b_shard
        fn = jax.jit(
            step,
            in_shardings=(state_shard, batch_shard),
            out_shardings=(state_shard, None),
        )
        return fn, (state_structs, batch_structs)

    if shape.kind == "prefill":
        prefill = tfm.make_prefill(cfg, shape.seq_len, mesh_axes)
        cache_specs = tfm.cache_specs(
            cfg, shape.global_batch, shape.seq_len,
            dp_size=dp, model_size=tp, multi_pod=POD in mesh_axes,
        )
        args = [param_structs, ins["tokens"]]
        in_sh = [p_shard, b_shard]
        if "frontend" in ins:
            args.append(ins["frontend"])
            in_sh.append(b_shard)
        fn = jax.jit(
            prefill,
            in_shardings=tuple(in_sh),
            out_shardings=(None, _shardings_for(cache_specs, mesh)),
        )
        return fn, tuple(args)

    # decode
    decode = tfm.make_decode_step(cfg, mesh_axes)
    cache_specs = tfm.cache_specs(
        cfg, shape.global_batch, shape.seq_len,
        dp_size=dp, model_size=tp, multi_pod=POD in mesh_axes,
    )
    c_shard = _shardings_for(cache_specs, mesh)
    fn = jax.jit(
        decode,
        in_shardings=(p_shard, b_shard, c_shard, repl),
        out_shardings=(None, c_shard),
    )
    return fn, (param_structs, ins["token"], ins["cache"], ins["pos"])


def build_retrieval_program(mesh, overrides: dict | None = None):
    """The paper's own workload as a dry-run cell: distributed hybrid search
    over a segment-sharded 1M-doc corpus (shapes from paper Table 1).

    overrides: {"use_kernel": bool, "iters": int, "pool_size": int, ...}."""
    from repro.core.distributed import (
        SegmentedIndex,
        make_distributed_search,
    )
    from repro.core.index import HybridIndex
    from repro.core.search import SearchParams
    from repro.core.usms import FusedVectors, PathWeights, SparseVec

    ov = overrides or {}
    n_total = 1_048_576
    n_seg = mesh_dp_size(mesh)
    n_loc = n_total // n_seg
    d, ps, pf = 1024, 64, 32
    deg, dk, lcap, ed = 32, 8, 4, 4
    n_q = int(ov.get("n_queries", 1024))
    tp = mesh_model_size(mesh)

    f32 = jnp.bfloat16 if ov.get("bf16") else jnp.float32
    i32 = jnp.int32

    def fused(n):
        return FusedVectors(
            dense=jax.ShapeDtypeStruct((n, d), f32),
            learned=SparseVec(
                jax.ShapeDtypeStruct((n, ps), i32), jax.ShapeDtypeStruct((n, ps), f32)
            ),
            lexical=SparseVec(
                jax.ShapeDtypeStruct((n, pf), i32), jax.ShapeDtypeStruct((n, pf), f32)
            ),
        )

    def seg(x):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_seg,) + s.shape, s.dtype), x
        )

    index_structs = HybridIndex(
        corpus=fused(n_loc),
        semantic_edges=jax.ShapeDtypeStruct((n_loc, deg), i32),
        keyword_edges=jax.ShapeDtypeStruct((n_loc, dk), i32),
        logical_edges=jax.ShapeDtypeStruct((n_loc, lcap, 4), i32),
        doc_entities=jax.ShapeDtypeStruct((n_loc, ed), i32),
        entity_to_docs=jax.ShapeDtypeStruct((64, 4), i32),
        entity_adj=jax.ShapeDtypeStruct((64, 64), jnp.bool_),
        entry_points=jax.ShapeDtypeStruct((16,), i32),
        alive=jax.ShapeDtypeStruct((n_loc,), jnp.bool_),
        self_ip=jax.ShapeDtypeStruct((n_loc,), f32),
    )
    seg_structs = SegmentedIndex(index=seg(index_structs), global_ids=jax.ShapeDtypeStruct((n_seg, n_loc), i32))
    q_structs = fused(n_q)
    ov = overrides or {}
    params = SearchParams(
        k=int(ov.get("k", 10)),
        iters=int(ov.get("iters", 48)),
        pool_size=int(ov.get("pool_size", 64)),
        expand=int(ov.get("expand", 1)),
        use_kernel=bool(ov.get("use_kernel", False)),
    )
    run = make_distributed_search(mesh, PathWeights.three_path(), params)
    return run, (seg_structs, q_structs)


def _parse_overrides(spec: str | None) -> dict:
    """--set a=1,b=flash,c=true -> config overrides (perf iterations)."""
    out = {}
    if not spec:
        return out
    for kv in spec.split(","):
        k, v = kv.split("=")
        if v.lower() in ("true", "false"):
            out[k] = v.lower() == "true"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, overrides: str | None = None) -> dict:
    import dataclasses

    mesh = make_production_mesh(multi_pod=multi_pod)
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "overrides": overrides or "",
    }
    t0 = time.time()
    if arch == RETRIEVAL_ARCH:
        fn, args = build_retrieval_program(mesh, _parse_overrides(overrides))
        cfg = None
    else:
        cfg = get_config(arch)
        if overrides:
            cfg = dataclasses.replace(cfg, **_parse_overrides(overrides))
        shape = SHAPES[shape_name]
        if shape_name == "long_500k" and not cfg.supports_long_context:
            record["status"] = "SKIP(full-attn)"
            return record
        fn, args = build_cell_program(cfg, shape_name, mesh)

    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t0, 1)

    try:
        mem = compiled.memory_analysis()
        record["memory"] = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        }
        print("memory_analysis:", record["memory"])
    except Exception as e:  # pragma: no cover
        record["memory"] = {"error": str(e)}

    try:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        record["cost"] = {
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
        }
        print("cost_analysis: flops=%.3e bytes=%.3e" % (
            record["cost"]["flops"], record["cost"]["bytes_accessed"]))
    except Exception as e:  # pragma: no cover
        record["cost"] = {"error": str(e)}

    # loop-aware per-device accounting (scan bodies x trip counts)
    hlo_text = compiled.as_text()
    if os.environ.get("REPRO_SAVE_HLO"):
        import gzip

        path = pathlib.Path(os.environ["REPRO_SAVE_HLO"])
        path.parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(path, "wt") as f:
            f.write(hlo_text)
    hlo = analyze_hlo(hlo_text)
    record["hlo"] = hlo
    print(
        "loop-aware/device: dot_flops=%.3e hbm_bytes=%.3e coll_bytes=%.3e %s"
        % (
            hlo["dot_flops"],
            hlo["hbm_bytes"],
            hlo["collective_bytes"],
            hlo["collective_counts"],
        )
    )

    if cfg is not None:
        shape = SHAPES[shape_name]
        n_tokens = shape.global_batch * (
            shape.seq_len if shape.kind in ("train", "prefill") else 1
        )
        mult = 6 if shape.kind == "train" else 2
        record["model_flops"] = float(mult * cfg.n_active_params * n_tokens)
        record["model_flops_per_device"] = record["model_flops"] / record["n_devices"]
        record["n_params"] = float(cfg.n_params)
        record["n_active_params"] = float(cfg.n_active_params)
        if hlo["dot_flops"] > 0:
            record["useful_flops_ratio"] = (
                record["model_flops_per_device"] / hlo["dot_flops"]
            )

    record["roofline"] = roofline_terms(
        hlo_flops=hlo["dot_flops"],
        hlo_bytes=hlo["hbm_bytes"],
        coll_bytes_per_device=hlo["collective_bytes"],
        n_chips=record["n_devices"],
        peaks=chip_peaks(MODELED_CHIP),
    )
    print("roofline:", {k: (round(v, 4) if isinstance(v, float) else v)
                        for k, v in record["roofline"].items()})
    record["status"] = "OK"
    return record


def orchestrate(out_dir: str, jobs: int, meshes: list[str], archs: list[str], shapes: list[str]):
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    cells = []
    for mesh in meshes:
        for arch in archs:
            if arch == RETRIEVAL_ARCH:
                cells.append((arch, "search_1m", mesh))
                continue
            for shape in shapes:
                cells.append((arch, shape, mesh))
    procs: list[tuple] = []
    results = []

    def drain(block=False):
        for i, (p, cell, path, log) in enumerate(list(procs)):
            if p.poll() is None and not block:
                continue
            p.wait()
            procs.remove((p, cell, path, log))
            if path.exists():
                results.append(json.loads(path.read_text()))
                r = results[-1]
                print(f"[{len(results)}/{len(cells)}] {r['arch']} {r['shape']} "
                      f"{r['mesh']}: {r.get('status')} ({r.get('compile_s', '-')}s)",
                      flush=True)
            else:
                print(f"FAILED: {cell}; see {log}", flush=True)
                results.append({"arch": cell[0], "shape": cell[1],
                                "mesh": cell[2], "status": "COMPILE_FAIL",
                                "log": str(log)})

    for cell in cells:
        arch, shape, mesh = cell
        path = out / f"{arch}__{shape}__{mesh}.json"
        if path.exists():
            results.append(json.loads(path.read_text()))
            continue
        log = out / f"{arch}__{shape}__{mesh}.log"
        cmd = [
            "timeout", "3000",
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape, "--mesh", mesh,
            "--json-out", str(path),
        ]
        env = dict(os.environ)
        env["REPRO_SAVE_HLO"] = str(path.with_suffix(".hlo.gz"))
        with open(log, "w") as lf:
            procs.append((subprocess.Popen(cmd, stdout=lf, stderr=lf, env=env), cell, path, log))
        while len(procs) >= jobs:
            drain()
            time.sleep(2)
    while procs:
        drain()
        time.sleep(2)
    (out / "summary.json").write_text(json.dumps(results, indent=1))
    n_ok = sum(1 for r in results if r.get("status") == "OK")
    n_skip = sum(1 for r in results if str(r.get("status", "")).startswith("SKIP"))
    print(f"\n{n_ok} OK, {n_skip} skipped, {len(results) - n_ok - n_skip} failed "
          f"of {len(results)} cells")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="multi", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--set", dest="overrides", default=None,
                    help="config overrides, e.g. attn_impl=flash,seq_shard=true")
    ap.add_argument("--archs", default=None, help="comma list (with --all)")
    ap.add_argument("--shapes", default=None, help="comma list (with --all)")
    args = ap.parse_args()

    if args.all:
        archs = args.archs.split(",") if args.archs else list_archs() + [RETRIEVAL_ARCH]
        shapes = args.shapes.split(",") if args.shapes else list(SHAPES)
        orchestrate(args.out, args.jobs, ["single", "multi"], archs, shapes)
        return

    record = run_cell(args.arch, args.shape, args.mesh == "multi", args.overrides)
    print(json.dumps({k: v for k, v in record.items() if k != "hlo"}, indent=1))
    if args.json_out:
        pathlib.Path(args.json_out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.json_out).write_text(json.dumps(record, indent=1))
    if record.get("status") not in ("OK",) and not str(record.get("status", "")).startswith("SKIP"):
        sys.exit(1)


if __name__ == "__main__":
    main()
