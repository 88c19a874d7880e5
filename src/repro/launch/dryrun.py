import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:

  * 512 host CPU placeholder devices (the XLA_FLAGS and JAX_PLATFORMS
    lines above MUST run before any jax import — device count locks at
    first init; pinned to the CPU, neither this process nor the one-cell
    children it starts with ``--all`` ever takes an accelerator),
  * parameters / optimizer state / caches are jax.ShapeDtypeStruct with
    NamedShardings — a 34B-parameter train state is lowered with ZERO
    allocation,
  * per cell we record compiled.memory_analysis(), cost_analysis(), and
    the collective-bytes sum parsed from the partitioned HLO
    (repro.launch.hlo_analysis) into a JSON for EXPERIMENTS.md.

Usage (one cell per process — compiles are isolated and resumable):
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b \
      --shape train_4k --mesh pod1 --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1
"""
__doc__ = DOC

import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, all_archs, get_config, shape_applicable
from repro.distributed.sharding import make_array_sharding, use_rules
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, make_single_pod_submesh
from repro.models import (abstract_params, cache_specs, param_specs,
                          model as model_lib)
from repro.models.common import abstract, spec_axes
from repro.train import (StepOptions, abstract_train_state, make_decode_step,
                         make_prefill_step, make_train_step)
from repro.train.optim import AdamWConfig


def shaped(shape, dtype, axes):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=make_array_sharding(shape, axes))


def _tree_shaped(spec_tree, dtype):
    """ParamSpec tree -> ShapeDtypeStructs with shardings attached."""
    from repro.models.common import ParamSpec, is_spec_tree_leaf

    def one(s: ParamSpec):
        return jax.ShapeDtypeStruct(
            s.shape, s.dtype or dtype,
            sharding=make_array_sharding(s.shape, s.axes))

    return jax.tree.map(one, spec_tree, is_leaf=is_spec_tree_leaf)


def _abstract_packed(spec_tree, cfg):
    """Abstract param tree with quantize-eligible weights as PackedWeight
    ShapeDtypeStructs (sub-byte payloads in HBM — the deployment layout)."""
    from repro.core.packing import pack_factor
    from repro.kernels.ops import PackedWeight
    from repro.models.common import ParamSpec, is_spec_tree_leaf

    fw = pack_factor(cfg.quant.w_bits)
    rup = lambda x, m: ((x + m - 1) // m) * m

    def one(s: ParamSpec):
        plain = jax.ShapeDtypeStruct(
            s.shape, s.dtype or cfg.dtype,
            sharding=make_array_sharding(s.shape, s.axes))
        if not s.quantize:
            return plain
        core = s.shape[s.stacked:]
        if len(core) != 2:
            return plain
        kp, np_ = rup(core[0], 256), rup(core[1], 128)
        lead = s.shape[:s.stacked]
        pk_shape = lead + (kp // fw, np_)
        sc_shape = lead + (np_,)
        lead_ax = s.axes[:s.stacked]
        return PackedWeight(
            packed=jax.ShapeDtypeStruct(
                pk_shape, jnp.int8, sharding=make_array_sharding(
                    pk_shape, lead_ax + s.axes[s.stacked:])),
            scale=jax.ShapeDtypeStruct(
                sc_shape, jnp.float32, sharding=make_array_sharding(
                    sc_shape, lead_ax + (s.axes[-1],))),
            k=core[0], n=core[1], w_bits=cfg.quant.w_bits)

    return jax.tree.map(one, spec_tree, is_leaf=is_spec_tree_leaf)


def input_specs(arch: str, shape: str, rules: str = "fsdp_sp",
                quant: str = "none", overrides: dict | None = None):
    """ShapeDtypeStruct stand-ins for every input of the lowered step.

    Returns (step_fn, args tuple, donate_argnums).
    """
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    if quant != "none":
        from repro.core.quant import QuantConfig
        w_bits = int(quant[1])
        cfg = cfg.with_(quant=QuantConfig(mode="wo", w_bits=w_bits,
                                          use_kernel=False))
    sp = SHAPES[shape]
    b, s = sp.global_batch, sp.seq_len

    if sp.step == "train":
        if cfg.input_mode == "tokens":
            inputs = shaped((b, s), jnp.int32, ("batch", "seq"))
        else:
            inputs = shaped((b, s, cfg.d_model), cfg.dtype,
                            ("batch", "seq", None))
        batch = {"inputs": inputs,
                 "labels": shaped((b, s), jnp.int32, ("batch", "seq"))}
        specs = param_specs(cfg)
        params_abs = _tree_shaped(specs, cfg.dtype)
        state = abstract_train_state(params_abs)
        # opt-state leaves share the parameter shardings, dtype f32.
        from repro.train.optim import OptState
        f32 = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32,
                                           sharding=x.sharding), t)
        state = state._replace(opt=OptState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            master=f32(params_abs), m=f32(params_abs), v=f32(params_abs)))
        step = make_train_step(cfg, AdamWConfig())
        return cfg, step, (state, batch), (0,)

    if quant != "none":
        params_abs = _abstract_packed(param_specs(cfg), cfg)
    else:
        params_abs = _tree_shaped(param_specs(cfg), cfg.dtype)
    cap = model_lib.cache_capacity(cfg, s)
    cache_abs = _tree_shaped(cache_specs(cfg, b, cap), cfg.dtype)

    if sp.step == "prefill":
        if cfg.input_mode == "tokens":
            inputs = shaped((b, s), jnp.int32, ("batch", "seq"))
        else:
            inputs = shaped((b, s, cfg.d_model), cfg.dtype,
                            ("batch", "seq", None))
        step = make_prefill_step(cfg)
        return cfg, step, (params_abs, inputs, cache_abs), (2,)

    # decode: one new token against a cache filled to s.
    if cfg.input_mode == "tokens":
        tok = shaped((b, 1), jnp.int32, ("batch", None))
    else:
        tok = shaped((b, 1, cfg.d_model), cfg.dtype, ("batch", None, None))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_decode_step(cfg)
    return cfg, step, (params_abs, cache_abs, tok, pos), (1,)


def run_cell(arch: str, shape: str, mesh_name: str, rules: str,
             out_dir: pathlib.Path, tag: str = "baseline",
             quant: str = "none", overrides: dict | None = None) -> dict:
    t0 = time.time()
    mesh = (make_production_mesh(multi_pod=True) if mesh_name == "pod2"
            else make_single_pod_submesh())
    n_chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "rules": rules,
           "tag": tag, "n_chips": int(n_chips), "status": "running"}
    with use_rules(mesh, rules):
        cfg, step, args, donate = input_specs(arch, shape, rules, quant,
                                              overrides)
        rec["params"] = model_lib.param_count(cfg)
        jitted = jax.jit(step, donate_argnums=donate)
        lowered = jitted.lower(*args)
        rec["t_lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["t_compile_s"] = round(time.time() - t1, 1)

        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
        except Exception as e:  # CPU backend may not implement it
            rec["memory_analysis"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            rec["cost_analysis"] = {
                k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes" in k or k in ("utilization",))}
        except Exception as e:
            rec["cost_analysis"] = {"error": str(e)}
        try:
            hlo = compiled.as_text()
            rec["collective_bytes"] = hlo_analysis.collective_bytes(hlo)
            # loop-adjusted flops / HBM traffic (XLA's cost_analysis counts
            # while bodies once; see hlo_analysis.traffic_analysis).
            rec["traffic"] = hlo_analysis.traffic_analysis(hlo)
            rec["hlo_lines"] = hlo.count("\n")
            # persist the partitioned HLO so analyses can be refined
            # offline without recompiling (see --reanalyze).
            import gzip
            out_dir.mkdir(parents=True, exist_ok=True)
            with gzip.open(out_dir / (
                    f"{arch}__{shape}__{mesh_name}__{rules}__{tag}"
                    ".hlo.gz"), "wt") as f:
                f.write(hlo)
        except Exception as e:
            rec["collective_bytes"] = {"error": str(e)}
    rec["status"] = "ok"
    rec["t_total_s"] = round(time.time() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch}__{shape}__{mesh_name}__{rules}__{tag}.json"
    (out_dir / fname).write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--rules", default="fsdp_sp")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--quant", default="none")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override, e.g. --override ssm_chunk=128")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape) via subprocesses")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute traffic/collectives from saved .hlo.gz")
    args = ap.parse_args()
    out = pathlib.Path(args.out)

    if args.reanalyze:
        import gzip
        for jf in sorted(out.glob("*.json")):
            hf = jf.with_suffix("").with_suffix("")  # strip .json
            hf = jf.parent / (jf.name[:-5] + ".hlo.gz")
            if not hf.exists():
                continue
            rec = json.loads(jf.read_text())
            with gzip.open(hf, "rt") as f:
                hlo = f.read()
            rec["collective_bytes"] = hlo_analysis.collective_bytes(hlo)
            rec["traffic"] = hlo_analysis.traffic_analysis(hlo)
            jf.write_text(json.dumps(rec, indent=1))
            print(f"[reanalyzed] {jf.name}")
        return

    if args.all:
        failures = []
        for arch in all_archs():
            cfg = get_config(arch)
            for shape in SHAPES:
                if not shape_applicable(cfg, shape):
                    continue
                fname = out / f"{arch}__{shape}__{args.mesh}__{args.rules}__{args.tag}.json"
                if fname.exists() and json.loads(
                        fname.read_text()).get("status") == "ok":
                    print(f"[skip] {fname.name}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", args.mesh,
                       "--rules", args.rules, "--tag", args.tag,
                       "--out", str(out)]
                print(f"[run ] {arch} x {shape} x {args.mesh}", flush=True)
                r = subprocess.run(cmd)
                if r.returncode:
                    failures.append((arch, shape))
        print("FAILURES:", failures if failures else "none")
        sys.exit(1 if failures else 0)

    try:
        ov = {}
        for item in args.override:
            k, v = item.split("=", 1)
            ov[k] = int(v) if v.lstrip("-").isdigit() else v
        rec = run_cell(args.arch, args.shape, args.mesh, args.rules, out,
                       args.tag, args.quant, ov or None)
        ca = rec.get("cost_analysis", {})
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "t_compile_s")}, indent=1))
        print("flops:", ca.get("flops"), "bytes:",
              ca.get("bytes accessed", ca.get("bytes_accessed")))
        print("collectives:", rec.get("collective_bytes", {}).get("total"))
        print(rec.get("memory_analysis"))
    except Exception:
        traceback.print_exc()
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "rules": args.rules, "tag": args.tag, "status": "error",
               "error": traceback.format_exc()}
        out.mkdir(parents=True, exist_ok=True)
        fname = f"{args.arch}__{args.shape}__{args.mesh}__{args.rules}__{args.tag}.json"
        (out / fname).write_text(json.dumps(rec, indent=1))
        sys.exit(1)


if __name__ == "__main__":
    main()
