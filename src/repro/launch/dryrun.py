import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production meshes, print memory/cost analysis, and emit roofline rows.

MUST be run as its own process (the two lines above must execute before
any jax import anywhere):

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --json out.json
"""

import argparse
import json
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config, shape_applicable
from repro.launch.mesh import dp_axes_for, make_production_mesh
from repro.models import model as M
from repro.optim.adamw import AdamWConfig
from repro.roofline.analysis import roofline_terms
from repro.sharding import params as sp
from repro.sharding.rules import axis_rules, make_rules
from repro.train.step import init_state, make_train_step

N_PATCH = 256   # vlm stub frontend patch count


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    f32 = jnp.float32
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        if cfg.embed_inputs:           # audio: precomputed frame embeddings
            specs = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                    jnp.bfloat16)}
            if shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
            return specs
        if cfg.family == "vlm":
            specs = {
                "patch_embeds": jax.ShapeDtypeStruct(
                    (B, N_PATCH, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S - N_PATCH), i32),
            }
            if shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((B, S - N_PATCH), i32)
            return specs
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
        return specs
    # decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}


def build_rules(cfg: ModelConfig, shape: ShapeConfig, mesh):
    dp = dp_axes_for(mesh)
    rules = make_rules(mesh, dp_axes=dp)
    rules = rules.resolve_divisibility({
        "batch": shape.global_batch,
        "heads": cfg.n_heads,
        "kv_heads": cfg.n_kv_heads,
        "vocab": cfg.vocab_size,
    })
    if (shape.is_decode and rules.mapping.get("kv_heads") is None
            and shape.seq_len % mesh.shape["model"] == 0):
        # GQA groups can't fill the TP axis → shard the cache sequence
        # instead (flash-decoding split-K combine under GSPMD).
        rules.mapping["kv_seq"] = "model"
    if (shape.kind in ("train", "prefill")
            and cfg.n_heads % mesh.shape["model"] != 0
            and shape.seq_len % mesh.shape["model"] == 0):
        # Heads indivisible by the TP width → attention would replicate
        # and its fp32 scores blow the memory budget (internvl2: 14 heads
        # on TP-16 → 25.9 GB/dev). Shard attention activations over the
        # *sequence* instead (context-parallel scores).
        rules.mapping["seq"] = "model"
    if (shape.kind in ("train", "prefill")
            and not cfg.disable_sp
            and shape.seq_len % mesh.shape["model"] == 0):
        # Megatron sequence parallelism: the residual stream between blocks
        # is sharded over the TP axis (all-gather at qkv/up-proj, reduce-
        # scatter after wo/down-proj) — 16x less activation memory.
        rules.mapping["seq_act"] = "model"
    return rules


def _build_fn(cfg: ModelConfig, shape: ShapeConfig, rules, mesh,
              attn_impl: str, donate: bool, *, unroll: bool, fsdp: bool):
    """jit-wrapped step fn + abstract args for one cell (no allocation).

    Under ``unroll`` (the COST compile, never executed) all inner chunk
    scans are widened to the full sequence: XLA counts while bodies once,
    so any surviving inner scan would undercount FLOPs/collectives by its
    trip count. The scanned (memory) compile keeps production chunk sizes.
    """
    key = jax.random.PRNGKey(0)
    batch = input_specs(cfg, shape)
    batch_sh = sp.to_shardings(sp.batch_specs(batch, rules), rules)
    S = shape.seq_len
    # Cost-compile chunk sizes: as large as XLA buffer limits allow (the
    # remaining Python-level chunk loops are unrolled via unroll_chunks).
    q_chunk = min(S, 8192) if unroll else 1024
    ssd_chunk = min(S, 2048) if unroll else 128
    ce_chunk = S if unroll else 512

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        state_shape = jax.eval_shape(
            lambda k: init_state(k, cfg, opt_cfg), key)
        specs = sp.param_specs(state_shape, rules, fsdp=fsdp)
        state_sh = sp.to_shardings(specs, rules)
        step = make_train_step(cfg, opt_cfg, attn_impl=attn_impl,
                               unroll=unroll, q_chunk=q_chunk,
                               ce_chunk=ce_chunk, ssd_chunk=ssd_chunk)
        fn = jax.jit(step,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,) if donate else ())
        return fn, (state_shape, batch)

    params_shape = jax.eval_shape(lambda k: M.init_params(k, cfg), key)
    params_sh = sp.to_shardings(sp.param_specs(params_shape, rules), rules)

    if shape.kind == "prefill":
        if cfg.is_encoder:
            def fn_(p, b):
                return M.forward(p, cfg, b, attn_impl=attn_impl,
                                 unroll=unroll, q_chunk=q_chunk,
                                 ssd_chunk=ssd_chunk)[0]
        else:
            def fn_(p, b):
                return M.prefill(p, cfg, b, shape.seq_len,
                                 attn_impl=attn_impl, unroll=unroll,
                                 q_chunk=q_chunk, ssd_chunk=ssd_chunk)
        fn = jax.jit(fn_, in_shardings=(params_sh, batch_sh),
                     out_shardings=None)
        return fn, (params_shape, batch)

    # decode
    cache_dt = getattr(jnp, cfg.kv_cache_dtype)
    cache_shape = jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len,
                             dtype=cache_dt))
    cache_sh = sp.to_shardings(sp.cache_specs(cache_shape, rules), rules)

    def fn_(p, t, c, l):
        return M.decode_step(p, cfg, t, c, l, unroll=unroll)
    fn = jax.jit(fn_,
                 in_shardings=(params_sh, batch_sh["tokens"],
                               cache_sh, None),
                 out_shardings=(None, cache_sh),
                 donate_argnums=(2,) if donate else ())
    return fn, (params_shape, batch["tokens"], cache_shape,
                jax.ShapeDtypeStruct((), jnp.int32))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               attn_impl: str = "chunked", donate: bool = True,
               mesh=None, cfg_override=None, unroll: bool = True,
               fsdp: bool = True):
    """Lower + compile one cell. Returns (report_dict, compiled)."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}, None

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = int(np.prod(mesh.devices.shape))
    rules = build_rules(cfg, shape, mesh)
    training = shape.kind == "train"

    with axis_rules(rules):
        fn, args = _build_fn(cfg, shape, rules, mesh, attn_impl, donate,
                             unroll=unroll, fsdp=fsdp)
        with mesh:
            lowered = fn.lower(*args)
            compiled = lowered.compile()
            if unroll:
                # Second, scanned compile for the memory proof: XLA:CPU's
                # buffer liveness over an UNROLLED layer stack pessimizes
                # (every layer's buffers stay live → ~L× overcount), while
                # its cost analysis counts a while-loop body only ONCE
                # (~L× undercount of FLOPs/collectives). So: costs from
                # the unrolled module, memory from the scanned one.
                mem_fn, mem_args = _build_fn(cfg, shape, rules, mesh,
                                             attn_impl, donate,
                                             unroll=False, fsdp=fsdp)
                mem_compiled = mem_fn.lower(*mem_args).compile()
            else:
                mem_compiled = compiled

    mem = mem_compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()

    n_tokens = shape.global_batch * (shape.seq_len if not shape.is_decode
                                     else 1)
    bytes_per_device = getattr(mem, "temp_size_in_bytes", 0) + \
        getattr(mem, "argument_size_in_bytes", 0)
    report = roofline_terms(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        cost_analysis=cost, hlo_text=hlo_text,
        n_params_active=cfg.active_param_count(), n_tokens=n_tokens,
        training=training, bytes_per_device=int(bytes_per_device))
    row = report.row()
    row["flops_per_device"] = float(cost.get("flops", 0.0))
    row["hbm_bytes_per_device"] = float(cost.get("bytes accessed", 0.0))
    row["coll_bytes_per_device"] = int(report.collective_bytes)
    row["mem_analysis"] = str(mem)
    row["warnings"] = rules.warnings
    row["collectives"] = report.collectives
    row["collective_counts"] = report.collective_counts
    return row, compiled


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--attn-impl", default="chunked")
    ap.add_argument("--no-unroll", action="store_true",
                    help="keep lax.scan over layers (faster compile, "
                         "undercounted roofline)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--json", default=None, help="write row(s) as JSON")
    args = ap.parse_args(argv)

    cells = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in ARCH_IDS for s in SHAPES])
    rows = []
    failures = 0
    for arch, shape in cells:
        try:
            row, _ = lower_cell(arch, shape, multi_pod=args.multi_pod,
                                attn_impl=args.attn_impl,
                                unroll=not args.no_unroll,
                                fsdp=not args.no_fsdp)
            rows.append(row)
            if "skipped" in row:
                print(f"[SKIP] {arch} × {shape}: {row['skipped']}")
            else:
                print(f"[OK]   {arch} × {shape} mesh={row['mesh']} "
                      f"dominant={row['dominant']} "
                      f"frac={row['roofline_fraction']:.3f}")
                print(f"       compute {row['t_compute_s']*1e3:.2f}ms "
                      f"memory {row['t_memory_s']*1e3:.2f}ms "
                      f"collective {row['t_collective_s']*1e3:.2f}ms")
                print("       " + row["mem_analysis"])
        except Exception as e:
            failures += 1
            rows.append({"arch": arch, "shape": shape,
                         "error": f"{type(e).__name__}: {e}"})
            print(f"[FAIL] {arch} × {shape}: {type(e).__name__}: {e}")
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1, default=str)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
