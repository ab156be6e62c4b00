import argparse
import os
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, applicable, get_config
from repro.configs.all_archs import ASSIGNED, PAPER_OWN
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.param import abstract_params, param_axes
from repro.obs.peaks import PEAKS
from repro.parallel import sharding as sh
from repro.training.optimizer import OptConfig, opt_init, opt_state_axes
from repro.training.train_step import make_train_step

# --- TPU v5e target constants (per chip) ---
PEAK_FLOPS = PEAKS["TPU v5 lite"]["bf16_flops"]
HBM_BW = PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
ICI_BW = 50e9            # bytes/s per link (spec-conservative single link)

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "benchmarks", "results",
                           "dryrun")


def _kind(shape_name: str) -> str:
    if shape_name == "long_500k":
        return "long"
    return SHAPES[shape_name].kind


def _abstract_tree(tree, dtype=None):
    def one(x):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype)
    return jax.tree.map(one, tree)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               rule_overrides: Optional[Dict[str, Any]] = None,
               opt_name: str = "adamw", remat: str = "block"):
    """Returns (jitted_fn, example_args, mesh, rules, cfg)."""
    import dataclasses
    cfg = dataclasses.replace(get_config(arch), remat=remat)
    shape = SHAPES[shape_name]
    kind = _kind(shape_name)
    rules = sh.make_rules("train" if kind == "train" else kind,
                          multi_pod=multi_pod, **(rule_overrides or {}))
    mesh = make_production_mesh(multi_pod=multi_pod)

    axes = M.model_param_axes(cfg)
    p_sh = sh.tree_shardings(axes, mesh, rules)
    in_axes_tree = M.input_axes(cfg, shape)
    b_sh = sh.tree_shardings(in_axes_tree, mesh, rules)
    inputs = M.input_specs(cfg, shape)
    repl = NamedSharding(mesh, P())

    if kind == "train":
        p_abs = abstract_params(M.model_specs(cfg), jnp.float32)
        opt_cfg = OptConfig(name=opt_name)
        opt_abs = jax.eval_shape(lambda p: opt_init(opt_cfg, p), p_abs)
        o_axes = opt_state_axes(opt_cfg, axes)
        o_sh = sh.tree_shardings(o_axes, mesh, rules)
        step = make_train_step(cfg, opt_cfg)

        def wrapped(params, opt_state, batch):
            with sh.use_rules(mesh, rules):
                return step(params, opt_state, batch)

        jf = jax.jit(wrapped, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, repl),
                     donate_argnums=(0, 1))
        args = (p_abs, opt_abs, inputs)
    elif kind == "prefill":
        p_abs = abstract_params(M.model_specs(cfg), jnp.bfloat16)
        c_axes = M.cache_axes(cfg)
        c_sh = sh.tree_shardings(c_axes, mesh, rules)
        logits_sh = NamedSharding(mesh, rules.spec(("act_batch",
                                                    "act_vocab")))

        def wrapped(params, batch):
            with sh.use_rules(mesh, rules):
                logits, cache, aux = M.prefill(cfg, params, batch)
            return logits, cache

        jf = jax.jit(wrapped, in_shardings=(p_sh, b_sh),
                     out_shardings=(logits_sh, c_sh))
        args = (p_abs, inputs)
    else:  # decode / long
        p_abs = abstract_params(M.model_specs(cfg), jnp.bfloat16)
        c_axes = M.cache_axes(cfg)
        c_sh = sh.tree_shardings(c_axes, mesh, rules)
        logits_sh = NamedSharding(mesh, rules.spec(("act_batch",
                                                    "act_vocab")))
        tok_sh = b_sh["tokens"]
        len_sh = b_sh["lengths"]

        def wrapped(params, tokens, cache, lengths):
            with sh.use_rules(mesh, rules):
                return M.decode_step(cfg, params, tokens, cache, lengths)

        jf = jax.jit(wrapped,
                     in_shardings=(p_sh, tok_sh, c_sh, len_sh),
                     out_shardings=(logits_sh, c_sh),
                     donate_argnums=(2,))
        args = (p_abs, inputs["tokens"], inputs["cache"], inputs["lengths"])
    return jf, args, mesh, rules, cfg


def model_flops(cfg, shape_name: str) -> float:
    """Analytic MODEL_FLOPS = 6·N·D (train) / 2·N·D (fwd), N = active
    non-embedding params (unembed counted once)."""
    shape = SHAPES[shape_name]
    n_active = cfg.param_count(active_only=True)
    n_active -= cfg.vocab_size * cfg.d_model  # lookup is not a matmul
    if cfg.tie_embeddings:
        n_active += cfg.vocab_size * cfg.d_model  # tied unembed matmul
    kind = _kind(shape_name)
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n_active * tokens


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, tag: str = "",
             rule_overrides=None, remat: str = "block") -> Dict[str, Any]:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
    }
    cfg = get_config(arch)
    ok, why = applicable(cfg, SHAPES[shape_name])
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    t0 = time.time()
    try:
        jf, args, mesh, rules, cfg = build_cell(
            arch, shape_name, multi_pod, rule_overrides, remat=remat)
        lowered = jf.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        n_dev = mesh.size

        ma = compiled.memory_analysis()
        rec["memory_per_device"] = {
            "arguments_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "total_bytes": int(ma.argument_size_in_bytes
                               + ma.temp_size_in_bytes
                               + ma.output_size_in_bytes
                               - ma.alias_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        rec["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "note": "XLA visits while bodies once (no trip multiplication); "
                    "see hlo_walk for trip-corrected numbers",
        }
        hlo_text = compiled.as_text()
        an_raw = hlo_analysis.analyze(hlo_text, n_dev)
        an_nok = hlo_analysis.analyze(hlo_text, n_dev, tpu_dtype_model=True)
        an = hlo_analysis.analyze(hlo_text, n_dev, tpu_dtype_model=True,
                                  kernel_scopes=True)
        rec["hlo_walk_raw_cpu"] = {
            k: v for k, v in an_raw.items() if k != "dot_flops_by_meta"}
        rec["hlo_walk_nokernel"] = {
            k: v for k, v in an_nok.items() if k != "dot_flops_by_meta"}
        rec["hlo_walk"] = {k: v for k, v in an.items()
                          if k != "dot_flops_by_meta"}
        rec["hlo_walk"]["note"] = (
            "TPU dtype model (f32-normalized streams at bf16 width) + "
            "Pallas-kernel VMEM credit for *_kernel_scope regions; "
            "see hlo_walk_nokernel / hlo_walk_raw_cpu for ablations")

        mf = model_flops(cfg, shape_name)
        t_comp = an["flops"] / PEAK_FLOPS
        t_mem = an["bytes"] / HBM_BW
        t_coll = an["collective_wire_bytes"] / ICI_BW
        rec["roofline"] = {
            "chips": n_dev,
            "t_compute_s": t_comp,
            "t_memory_s": t_mem,
            "t_collective_s": t_coll,
            "bound": max(
                [("compute", t_comp), ("memory", t_mem),
                 ("collective", t_coll)], key=lambda kv: kv[1])[0],
            "model_flops_total": mf,
            "hlo_flops_total": an["flops"] * n_dev,
            "useful_flops_ratio": mf / max(an["flops"] * n_dev, 1.0),
            "step_time_bound_s": max(t_comp, t_mem, t_coll),
            "roofline_fraction": t_comp / max(t_comp, t_mem, t_coll, 1e-30),
        }
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 - record and keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = os.path.join(
            out_dir, f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all (arch x shape) cells for the chosen mesh")
    ap.add_argument("--include-paper-own", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = ASSIGNED + (PAPER_OWN if args.include_paper_own else [])
    cells = []
    if args.all:
        for a in archs:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    for arch, shape_name in cells:
        suffix = f"__{args.tag}" if args.tag else ""
        fn = os.path.join(args.out_dir,
                          f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
        if os.path.exists(fn) and not args.force:
            print(f"[skip-cached] {arch} {shape_name} {mesh_name}")
            continue
        rec = run_cell(arch, shape_name, args.multi_pod,
                       out_dir=args.out_dir, tag=args.tag,
                       remat=args.remat)
        r = rec.get("roofline", {})
        print(f"[{rec['status']:7s}] {arch:22s} {shape_name:12s} "
              f"{mesh_name:8s} lower={rec.get('lower_s', '-')}s "
              f"compile={rec.get('compile_s', '-')}s "
              f"bound={r.get('bound', '-')} "
              f"step={r.get('step_time_bound_s', 0):.4f}s "
              f"err={rec.get('error', '')[:120]}",
              flush=True)


if __name__ == "__main__":
    # 512 host devices stand in for the production pod; XLA reads the
    # flag when the backend starts, so it is set before the first device
    # query and only when run as a script
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=512"
                               ).strip()
    main()
