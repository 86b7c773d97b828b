"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**input_specs).compile()`` must succeed on
the 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh for every assigned
cell.  Each successful compile is archived as a JSON artifact carrying
``memory_analysis()``, ``cost_analysis()`` and the parsed-HLO roofline
inputs (FLOPs / memory bytes / collective bytes with while-loop trip-count
multipliers) — benchmarks/roofline.py renders the table from these.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
      --cell train_4k --mesh single [--variant base] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
"""
import argparse
import dataclasses
import gzip
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis.hlo import analyze_hlo
from repro.configs import (
    ARCH_IDS, SHAPE_CELLS, cell_applicable, cell_by_name, get_config,
)
from repro.distributed import (
    activation_sharding, batch_shardings, cache_shardings, default_rules,
    param_shardings, replicated,
)
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import abstract_params
from repro.models.api import model_specs
from repro.optim import state_specs
from repro.train.step import TrainConfig, make_train_step


def shape_struct(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg, cell):
    """Abstract (ShapeDtypeStruct) inputs for a cell — never allocates."""
    gb, s = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        if cfg.is_encdec:
            return {
                "frames": shape_struct((gb, cfg.encoder_seq, cfg.d_model),
                                       jnp.float32),
                "dec_tokens": shape_struct((gb, s), jnp.int32),
                "dec_labels": shape_struct((gb, s), jnp.int32),
            }
        return {"tokens": shape_struct((gb, s), jnp.int32),
                "labels": shape_struct((gb, s), jnp.int32)}
    if cell.kind == "prefill":
        if cfg.is_encdec:
            return {
                "frames": shape_struct((gb, cfg.encoder_seq, cfg.d_model),
                                       jnp.float32),
                "dec_tokens": shape_struct((gb, s), jnp.int32),
                "dec_labels": shape_struct((gb, s), jnp.int32),
            }
        return {"tokens": shape_struct((gb, s), jnp.int32)}
    # decode
    return {"tokens": shape_struct((gb, 1), jnp.int32)}


def abstract_caches(cfg, batch, max_len):
    """ShapeDtypeStruct tree matching api.init_caches (no allocation)."""
    from repro.models.api import init_caches
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len))


def default_grad_accum(cfg, cell, mesh) -> int:
    """Microbatches per step so the scan-saved residual carries fit HBM.

    The layer scan saves one [B_micro, S, d] carry per layer for the
    backward pass; target <= ~4.5 GiB of carries per chip.
    """
    data_ways = 1
    for ax in ("pod", "data"):
        data_ways *= dict(mesh.shape).get(ax, 1)
    rows_per_dev = max(1, cell.global_batch // data_ways)
    carry_per_row = cfg.n_layers * cell.seq_len * cfg.d_model * 2  # bf16
    target = 4.5e9
    ga = 1
    while (rows_per_dev // ga) > 1 and carry_per_row * (rows_per_dev // ga) > target:
        ga *= 2
    return min(ga, rows_per_dev)


def build_step(cfg, cell, mesh, rules, grad_accum=None):
    """Returns (jitted_fn, arg_specs:list) ready to .lower(*arg_specs)."""
    specs = model_specs(cfg)
    p_abs = abstract_params(specs)
    p_shard = param_shardings(specs, mesh, rules)
    inputs = input_specs(cfg, cell)

    if cell.kind == "train":
        o_specs = state_specs(specs)
        o_abs = abstract_params(o_specs)
        o_shard = param_shardings(o_specs, mesh, rules)
        b_shard = batch_shardings(cfg, mesh, rules, inputs)
        ga = grad_accum or default_grad_accum(cfg, cell, mesh)
        compress = bool(int(os.environ.get("REPRO_COMPRESS_GRADS", "0")))
        step = make_train_step(cfg, TrainConfig(grad_accum=ga,
                                                compress_grads=compress))

        def train_step(params, opt_state, batch):
            with activation_sharding(mesh, rules):
                return step(params, opt_state, batch)

        fn = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, replicated(mesh),
                           replicated(mesh)),
            donate_argnums=(0, 1),
        )
        return fn, (p_abs, o_abs, inputs)

    if cell.kind == "prefill":
        from repro.models.api import prefill_fn
        b_shard = batch_shardings(cfg, mesh, rules, inputs)

        def prefill(params, batch):
            with activation_sharding(mesh, rules):
                if cfg.is_encdec:
                    from repro.models.encdec import encdec_loss
                    # teacher-forced prefill over the full decoder sequence
                    loss, (_, rows) = encdec_loss(
                        cfg, params, batch["frames"], batch["dec_tokens"],
                        batch["dec_labels"])
                    return loss, rows
                return prefill_fn(cfg, params, batch)

        fn = jax.jit(prefill, in_shardings=(p_shard, b_shard),
                     out_shardings=None)
        return fn, (p_abs, inputs)

    # decode
    c_abs = abstract_caches(cfg, cell.global_batch, cell.seq_len)
    c_shard = cache_shardings(cfg, mesh, rules, c_abs)
    b_shard = batch_shardings(cfg, mesh, rules, inputs)
    from repro.train.step import make_serve_step
    step = make_serve_step(cfg)

    def serve_step(params, caches, tokens, pos):
        with activation_sharding(mesh, rules):
            return step(params, caches, tokens, pos)

    fn = jax.jit(
        serve_step,
        in_shardings=(p_shard, c_shard, b_shard["tokens"], replicated(mesh)),
        out_shardings=(b_shard["tokens"], c_shard, replicated(mesh)),
        donate_argnums=(1,),
    )
    pos = shape_struct((), jnp.int32)
    return fn, (p_abs, c_abs, input_specs(cfg, cell)["tokens"], pos)


def run_cell(arch, cell_name, mesh_kind, variant="base",
             out_dir="artifacts/dryrun", save_hlo=True, grad_accum=None,
             cfg_overrides=None):
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    cell = cell_by_name(cell_name)
    ok, why = cell_applicable(cfg, cell)
    result = {
        "arch": arch, "cell": cell_name, "mesh": mesh_kind,
        "variant": variant, "status": None,
    }
    out_path = Path(out_dir)
    out_path.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{cell_name}__{mesh_kind}__{variant}"
    if not ok:
        result.update(status="skipped", reason=why)
        (out_path / f"{tag}.json").write_text(json.dumps(result, indent=1))
        print(f"[dryrun] SKIP {tag}: {why}")
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = default_rules(variant)
    chips = mesh.size
    t0 = time.time()
    try:
        fn, args = build_step(cfg, cell, mesh, rules, grad_accum=grad_accum)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()
        parsed = analyze_hlo(hlo_text)

        result.update(
            status="ok", chips=chips,
            grad_accum=(grad_accum or (default_grad_accum(cfg, cell, mesh)
                                       if cell.kind == "train" else 1)),
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory_analysis={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_estimate_bytes": (mem.argument_size_in_bytes
                                        + mem.output_size_in_bytes
                                        + mem.temp_size_in_bytes
                                        - mem.alias_size_in_bytes),
            },
            cost_analysis={
                "flops_body_once": ca.get("flops", 0.0),
                "bytes_body_once": ca.get("bytes accessed", 0.0),
            },
            parsed={
                "flops": parsed.flops,
                "memory_bytes": parsed.memory_bytes,
                "collective_bytes": parsed.collective_bytes,
                "collective_ops": parsed.collective_ops,
                "while_trip_counts": parsed.while_trip_counts,
                "n_computations": parsed.n_computations,
            },
        )
        if save_hlo:
            with gzip.open(out_path / f"{tag}.hlo.txt.gz", "wt") as f:
                f.write(hlo_text)
        print(f"[dryrun] OK   {tag}: compile={t_compile:.1f}s "
              f"flops/chip={parsed.flops:.3e} "
              f"coll/chip={sum(parsed.collective_bytes.values()):.3e}B "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — archived as a failing cell
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] FAIL {tag}: {type(e).__name__}: {e}")
    (out_path / f"{tag}.json").write_text(json.dumps(result, indent=1))
    return result


def main():
    # the 512 virtual CPU devices of the production meshes; set before the
    # first device query initialises the backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--cell", choices=[c.name for c in SHAPE_CELLS])
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. remat_policy=dots)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("True", "False"):
            v = v == "True"
        overrides[k] = v

    configure_compile_cache()

    if args.all:
        archs = [args.arch] if args.arch else ARCH_IDS
        cells = [args.cell] if args.cell else [c.name for c in SHAPE_CELLS]
        statuses = []
        for arch in archs:
            for cell in cells:
                r = run_cell(arch, cell, args.mesh, args.variant, args.out,
                             save_hlo=not args.no_hlo,
                             grad_accum=args.grad_accum,
                             cfg_overrides=overrides)
                statuses.append(r["status"])
        bad = statuses.count("error")
        print(f"[dryrun] done: {statuses.count('ok')} ok, "
              f"{statuses.count('skipped')} skipped, {bad} failed")
        raise SystemExit(1 if bad else 0)

    if not (args.arch and args.cell):
        ap.error("--arch and --cell required (or --all)")
    r = run_cell(args.arch, args.cell, args.mesh, args.variant, args.out,
                 save_hlo=not args.no_hlo, grad_accum=args.grad_accum,
                 cfg_overrides=overrides)
    raise SystemExit(0 if r["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
