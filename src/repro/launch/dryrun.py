import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST be the first lines: jax locks the device count on first init.
# A CPU-only tool: pinned (and its --all children, which inherit this
# environment) to the host, so it never takes an attached accelerator.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the sharding config is coherent end-to-end
(no sharding mismatch, no unsupported collective, memory accounted) and
captures the roofline inputs:

  * compiled.memory_analysis()  -> bytes/device (does it fit HBM?)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes (compute+memory terms)
  * compiled HLO text           -> per-collective bytes (collective term)

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all          # every cell, subprocesses
"""
import argparse
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _shard_tree(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def collective_stats(hlo_text: str) -> dict:
    from repro.core.hlo_comm import extract, summarize
    ops = extract(hlo_text)
    return summarize(ops)


def corrected_totals(hlo_text: str) -> dict:
    """Trip-count-corrected FLOPs/bytes/collectives (scan bodies x trips)."""
    from repro.core.hlo_counter import totals
    t = totals(hlo_text)
    return {"flops": t.flops, "bytes": t.bytes, "bytes_floor": t.bytes_floor,
            "collectives": dict(t.coll)}


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                verbose: bool = True) -> dict:
    from repro.common.pytree import abstract, count_params
    from repro.configs import get_model
    from repro.configs.shapes import ALL_SHAPES, skip_reason
    from repro.launch.mesh import make_production_mesh
    from repro.train.optimizer import init_opt_state, opt_state_specs
    from repro.train.train_step import make_train_step
    from repro.configs.base import TrainConfig

    t0 = time.time()
    shape = ALL_SHAPES[shape_name]
    reason = skip_reason(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = get_model(arch, mesh)
    cfg = model.cfg

    # §Perf A/B knobs: REPRO_OPT=flash,kvquant,gradspec,cap1,tpmoe,chunks4
    opts = set(filter(None, os.environ.get("REPRO_OPT", "").split(",")))
    if opts:
        import dataclasses
        from repro.models.model_api import Model
        repl = {}
        if "flash" in opts:
            repl["flash_attention"] = True
        if "kvquant" in opts:
            repl["kv_quant_int8"] = True
        if "cap1" in opts:
            repl["capacity_factor"] = 1.0
        if "tpmoe" in opts:
            repl["moe_impl"] = "tp"
        if "chunks4" in opts:
            repl["moe_chunks"] = 4
        if "rwkvchunk" in opts:
            repl["rwkv_chunk"] = 32
        if "seqp" in opts:
            repl["seq_parallel"] = True
        if "seqcache" in opts:
            repl["decode_seq_shard"] = True
        if repl:
            model = Model(dataclasses.replace(cfg, **repl), mesh)
            cfg = model.cfg

    p_defs = model.param_defs()
    p_abs = abstract(p_defs)
    p_specs = model.param_specs()
    p_shard = _shard_tree(p_specs, mesh)
    n_params = count_params(p_defs)

    if shape.kind == "train":
        keep_master = jnp.dtype(getattr(cfg, "param_dtype", "float32")) != jnp.float32
        opt_dtype = jnp.dtype(getattr(cfg, "opt_dtype", "float32"))
        opt_abs = jax.eval_shape(
            lambda p: init_opt_state(p, opt_dtype, keep_master), p_abs)
        o_specs = opt_state_specs(p_specs, p_defs, mesh, zero1=True,
                                  keep_master=keep_master)
        o_shard = _shard_tree(o_specs, mesh)
        batch_abs = model.input_specs(shape)
        b_shard = _shard_tree(model.batch_pspecs(shape), mesh)
        # grad-accumulation microbatch sized to keep per-device activation
        # residency bounded (see DESIGN.md §5)
        n_bshard = mesh.devices.size // mesh.shape["model"]
        per_dev = 2 if cfg.d_model >= 5000 else 4
        micro = min(shape.global_batch, per_dev * n_bshard)
        tcfg = TrainConfig(microbatch=micro)
        grad_specs = o_specs["mu"] if "gradspec" in opts else None
        step = make_train_step(model, tcfg, grad_specs=grad_specs)
        fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None))
        args = (p_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        batch_abs = model.input_specs(shape)
        b_shard = _shard_tree(model.batch_pspecs(shape), mesh)
        fn = jax.jit(lambda p, b: model.prefill(p, b),
                     in_shardings=(p_shard, b_shard))
        args = (p_abs, batch_abs)
    else:  # decode
        spec = model.input_specs(shape)
        bspec = model.batch_pspecs(shape)
        cache_abs, tok_abs = spec["cache"], spec["tokens"]
        c_shard = _shard_tree(bspec["cache"], mesh)
        t_shard = _shard_tree(bspec["tokens"], mesh)
        fn = jax.jit(model.decode_step,
                     in_shardings=(p_shard, c_shard, t_shard),
                     out_shardings=(None, c_shard))
        args = (p_abs, cache_abs, tok_abs)

    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    corr = corrected_totals(hlo)
    hlo_dir = os.environ.get("REPRO_HLO_DIR")
    if hlo_dir:  # keep the artifact so metrics can be re-derived w/o recompile
        import gzip
        os.makedirs(hlo_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}"
        with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    del hlo

    mem_d = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
    }
    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.devices.size,
        "n_params": n_params,
        "kind": shape.kind,
        "memory": mem_d,
        "flops_raw": cost.get("flops"),
        "bytes_accessed_raw": cost.get("bytes accessed"),
        "transcendentals": cost.get("transcendentals"),
        "collective_bytes_raw": coll,
        # trip-count-corrected (scan bodies x trips) — use THESE for roofline
        "flops": corr["flops"],
        "bytes_accessed": corr["bytes"],
        "bytes_floor": corr["bytes_floor"],
        "collective_bytes": corr["collectives"],
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }
    if verbose:
        print("memory_analysis:", {k: v for k, v in mem_d.items()})
        print("cost_analysis(raw): flops=%s bytes=%s" % (cost.get("flops"),
                                                         cost.get("bytes accessed")))
        print("corrected: flops=%.3e bytes=%.3e" % (corr["flops"], corr["bytes"]))
        print("collectives:", {k: f"{v:.3e}" for k, v in corr["collectives"].items()})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.all:
        from repro.configs import ARCHS
        from repro.configs.shapes import shapes_for
        os.makedirs("experiments/dryrun", exist_ok=True)
        failures = []
        for arch in ARCHS:
            for shape in shapes_for(arch):
                for mp in (False, True):
                    tag = f"{arch}_{shape.name}_{'mp' if mp else 'sp'}"
                    out = f"experiments/dryrun/{tag}.json"
                    if os.path.exists(out):
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape.name, "--out", out]
                    if mp:
                        cmd.append("--multi-pod")
                    print(">>>", tag, flush=True)
                    r = subprocess.run(cmd, capture_output=True, text=True)
                    if r.returncode != 0:
                        failures.append((tag, r.stderr[-2000:]))
                        print("FAIL", tag, r.stderr[-800:], flush=True)
        print(f"done; {len(failures)} failures")
        sys.exit(1 if failures else 0)

    res = dryrun_cell(args.arch, args.shape, args.multi_pod)
    blob = json.dumps(res, indent=1, default=str)
    print(blob)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob)


if __name__ == "__main__":
    main()
