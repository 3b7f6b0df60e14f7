"""Distributed training launcher: ``--arch <id>`` selectable configs.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b \\
        --seq 4096 --batch 256 --steps 1000 --mesh 16x16

On a real TPU pod this runs under ``jax.distributed`` (one process per
host); on CPU it runs the same code single-process. ``--smoke`` shrinks
the config for a laptop-scale sanity pass. ALEA host-mode profiling is on
by default (the paper's capped-overhead continuous-profiling deployment).
"""

import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.core import AttributionReport, EnergyProfiler
from repro.launch.cache import enable_compilation_cache
from repro.data.pipeline import SyntheticTokens
from repro.launch.mesh import make_auto_mesh
from repro.optim.adamw import AdamWConfig
from repro.sharding import params as sp
from repro.sharding.rules import axis_rules, make_rules
from repro.train.step import init_state, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def parse_mesh(spec: str | None):
    if not spec:
        return None
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return make_auto_mesh(dims, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None, help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--no-profile", action="store_true")
    ap.add_argument("--compression", action="store_true",
                    help="int8 gradient compression with error feedback")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
        args.steps = min(args.steps, 20)
        args.batch, args.seq = 4, 128
    if cfg.embed_inputs:
        raise SystemExit(f"{args.arch} is encoder-only with a stub frontend;"
                         " use the masked-prediction example instead")

    mesh = parse_mesh(args.mesh)
    rules = make_rules(mesh) if mesh else None
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 5))

    def build():
        state = init_state(jax.random.PRNGKey(0), cfg, opt_cfg,
                           compression=args.compression)
        step = make_train_step(cfg, opt_cfg, compression=args.compression)
        if mesh is None:
            return state, jax.jit(step, donate_argnums=(0,))
        st_sh = sp.to_shardings(sp.param_specs(state, rules, fsdp=True),
                                rules)
        return state, jax.jit(step, in_shardings=(st_sh, None),
                              out_shardings=(st_sh, None),
                              donate_argnums=(0,))

    if rules is not None:
        ctx = axis_rules(rules)
        ctx.__enter__()
    state, step = build()
    n = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n/1e6:.1f}M steps={args.steps}")

    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           global_batch=args.batch)
    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=max(args.steps // 4, 10), log_every=10),
        step, state, data,
        put_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()})
    if trainer.try_resume():
        print(f"resumed at step {trainer.step}")

    if args.no_profile:
        result = trainer.run()
    else:
        prof = EnergyProfiler(period=5e-3)
        with prof.host_session() as sess:
            result = trainer.run()
        print(AttributionReport(sess.estimates()).table(top=10))

    for m in result["metrics"][-5:]:
        print(f"step {m['step']:6d} loss {m['loss']:.4f} "
              f"({m['step_time_s']*1e3:.0f} ms)")
    print(f"stragglers: {result['straggler_events']}")


if __name__ == "__main__":
    main()
