"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set the host-device override before any other import touches jax —
jax locks the device count on first backend init.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("N2NET_DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro import sharding  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.archs import ASSIGNED_ARCHS  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import shapes as shp  # noqa: E402
from repro.models import decode_step, init_params, prefill  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402
from repro.roofline import analysis, hlo  # noqa: E402
from repro.train.train_step import make_train_step  # noqa: E402


def count_params(cfg, params_sds) -> tuple[float, float]:
    """(total, active) parameter counts; MoE routed experts scale by k/E."""
    total = active = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_sds)[0]:
        ps = "/".join(str(getattr(p, "key", "")) for p in path)
        n = float(np.prod(leaf.shape))
        if re.search(r"(/|_)packed$", ps):
            n *= 32.0  # packed sign words: 32 logical weights per uint32
        if ps.endswith("/alpha"):
            continue   # scales, not weights
        total += n
        if cfg.moe and re.search(r"moe/w_(gate|up|down)", ps):
            active += n * cfg.moe.top_k / cfg.moe.num_experts
        else:
            active += n
    return total, active


def _opt_specs(param_specs_tree, opt_state_sds):
    """Optimizer-state specs mirror param specs; empty placeholders replicate."""
    from jax.sharding import PartitionSpec as P

    def fix(spec, leaf):
        return P() if leaf.ndim <= 1 and leaf.shape in ((), (0,)) else spec

    m = jax.tree.map(fix, param_specs_tree, opt_state_sds.m)
    v = jax.tree.map(fix, param_specs_tree, opt_state_sds.v)
    master = jax.tree.map(fix, param_specs_tree, opt_state_sds.master)
    from repro.optim.adamw import AdamWState

    return AdamWState(P(), m, v, master)


def build_cell(cfg, shape: shp.Shape, mesh):
    """-> (fn, args_sds tuple, in_specs tree, out_specs_or_None, donate)"""
    from jax.sharding import PartitionSpec as P

    params_sds = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    slog: list = []
    pspecs = sharding.param_specs(cfg, params_sds, mesh, log=slog)

    if shape.kind == "train":
        dp = 1
        for a in sharding.dp_axes(mesh):
            dp *= mesh.shape[a]
        mb = shp.microbatches_for(cfg, shape, dp)
        opt = AdamW(
            moment_dtype=jnp.bfloat16 if cfg.opt_half_moments else jnp.float32,
            use_master=cfg.opt_master,
        )
        opt_sds = jax.eval_shape(opt.init, params_sds)
        ospecs = _opt_specs(pspecs, opt_sds)
        batch = shp.train_inputs(cfg, shape)
        bspecs = sharding.batch_specs(cfg, batch, mesh)
        fn = make_train_step(cfg, opt, mesh=mesh, microbatches=mb)
        in_specs = (pspecs, ospecs, bspecs)
        out_specs = (pspecs, ospecs, None)
        return fn, (params_sds, opt_sds, batch), in_specs, out_specs, (0, 1), slog, mb

    if shape.kind == "prefill":
        batch = shp.prefill_inputs(cfg, shape)
        bspecs = sharding.batch_specs(cfg, batch, mesh)

        if cfg.encoder_only:
            from repro.models import forward

            fn = lambda p, b: forward(p, b, cfg, mesh=mesh, remat=False)  # noqa: E731
        else:
            fn = lambda p, b: prefill(p, b, cfg, mesh=mesh)  # noqa: E731
        return fn, (params_sds, batch), (pspecs, bspecs), None, (), slog, 1

    # decode
    token, cache_sds = shp.decode_inputs(cfg, shape)
    cspecs = sharding.cache_specs(cfg, cache_sds, mesh)
    tspec = sharding.batch_specs(cfg, token, mesh)
    fn = lambda p, t, c: decode_step(p, t, c, cfg, mesh=mesh)  # noqa: E731
    in_specs = (pspecs, tspec, cspecs)
    out_specs = (None, cspecs)
    return fn, (params_sds, token, cache_sds), in_specs, out_specs, (2,), slog, 1


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str, out_dir: str,
             overrides: dict | None = None, tag: str = "") -> dict:
    cfg = get_config(arch, **(overrides or {}))
    shape = shp.SHAPES[shape_name]
    ok, reason = shp.runnable(cfg, shape)
    cell_id = f"{arch}{tag}_{shape_name}_{mesh_name}"
    if not ok:
        rec = {"cell": cell_id, "status": "skipped", "reason": reason}
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{cell_id}.json"), "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    t0 = time.time()
    fn, args, in_specs, out_specs, donate, slog, mb = build_cell(cfg, shape, mesh)
    named_in = sharding.to_named(in_specs, mesh)
    kwargs = dict(in_shardings=named_in)
    if out_specs is not None:
        kwargs["out_shardings"] = sharding.to_named(out_specs, mesh)
    if donate:
        kwargs["donate_argnums"] = donate

    with mesh:
        lowered = jax.jit(fn, **kwargs).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    costs = hlo.analyze(text)

    params_sds = args[0]
    n_total, n_active = count_params(cfg, params_sds)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    model_flops = analysis.model_flops_estimate(n_active, tokens, shape.kind)

    per_dev_bytes = float(mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    roof = analysis.build(
        arch=arch + tag,
        shape=shape_name,
        mesh_name=mesh_name,
        chips=int(np.prod(list(mesh.shape.values()))),
        costs=costs,
        model_flops=model_flops,
        per_device_hbm_bytes=per_dev_bytes,
        xla_cost_flops=float(xla_cost.get("flops", 0.0)),
    )

    record = {
        "cell": cell_id,
        "status": "ok",
        "microbatches": mb,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "n_params": n_total,
        "n_active_params": n_active,
        "memory": {
            "argument_bytes_per_dev": int(mem.argument_size_in_bytes),
            "temp_bytes_per_dev": int(mem.temp_size_in_bytes),
            "output_bytes_per_dev": int(mem.output_size_in_bytes),
            "peak_bytes_per_dev": int(getattr(mem, "peak_memory_in_bytes", 0)),
            "fits_16GiB": per_dev_bytes < 16 * 2**30,
        },
        "roofline": roof.row(),
        "collective_counts": costs.collective_counts,
        "sharding_log": slog[:40],
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell_id}.json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record


def main():
    ap = argparse.ArgumentParser(description="N2Net framework multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--quant", default="none", help="bnn quant mode override")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(shp.SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"pod": False, "multipod": True}
    if args.mesh != "both":
        meshes = {args.mesh: meshes[args.mesh]}

    overrides = {}
    tag = ""
    if args.quant != "none":
        from repro.configs.base import QuantConfig

        overrides["quant"] = QuantConfig(mode=args.quant)
        tag = f"+{args.quant}"

    results = []
    for mesh_name, multi in meshes.items():
        mesh = make_production_mesh(multi_pod=multi)
        for arch in archs:
            for shape_name in shapes:
                cell = f"{arch}{tag}_{shape_name}_{mesh_name}"
                path = os.path.join(args.out, cell + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip-existing] {cell}")
                    continue
                try:
                    rec = run_cell(arch, shape_name, mesh, mesh_name, args.out,
                                   overrides, tag)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    rec = {"cell": cell, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    os.makedirs(args.out, exist_ok=True)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                results.append(rec)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (
                        f"compile={rec['compile_s']}s "
                        f"peak={rec['memory']['peak_bytes_per_dev']/2**30:.2f}GiB "
                        f"bottleneck={r['bottleneck']} "
                        f"roofline_frac={r['roofline_fraction']:.3f}"
                    )
                elif status == "skipped":
                    extra = rec["reason"]
                else:
                    extra = rec["error"]
                print(f"[{status}] {cell} {extra}", flush=True)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
