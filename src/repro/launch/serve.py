"""Serving launcher: continuous batching + per-phase energy attribution.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \\
        --requests 8 --new-tokens 16
"""

import argparse

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.core import AttributionReport, EnergyProfiler
from repro.launch.cache import enable_compilation_cache
from repro.models import model as M
from repro.serve.engine import Engine, Request, ServeConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params,
                    ServeConfig(max_batch=args.max_batch,
                                max_len=args.max_len, eos_token=-1))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(4, 16)))
                    .astype(np.int32),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]

    prof = EnergyProfiler(period=2e-3)
    with prof.host_session() as sess:
        done = engine.run_until_drained(reqs)
    print(f"served {len(done)}/{len(reqs)} requests "
          f"({sum(len(r.out_tokens) for r in done)} tokens)")
    print(AttributionReport(sess.estimates()).table(top=8))


if __name__ == "__main__":
    main()
