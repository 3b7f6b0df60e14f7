"""Distribution-layer tests.

Numerical tests needing >1 device run in a subprocess (the device count
must be fixed before jax initializes; tests in THIS process keep 1 CPU
device per the assignment's instruction). The subprocess asserts:

  * pjit'd train step on a (2,2) mesh == single-device step (DP+TP+SP
    + FSDP sharding changes nothing numerically);
  * shard_map MoE (expert-parallel) == local MoE math.

Plus in-process tests for rules/specs and the roofline HLO parser.
"""

import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.roofline.analysis import (model_flops, parse_collective_bytes,
                                     roofline_terms)

_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.dryrun import build_rules
    from repro.launch.mesh import make_auto_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.sharding import params as sp
    from repro.sharding.rules import axis_rules
    from repro.train.step import init_state, make_train_step
    from repro.data.pipeline import SyntheticTokens

    out = {}
    # Dropless capacity: EP truncates per-shard, the local path globally —
    # equality needs no drops on either path (production MoE keeps the
    # standard capacity factor; this is a numerics test).
    cfg = get_config("qwen3-moe-30b-a3b").reduced().replace(
        compute_dtype="float32")
    cfg = cfg.replace(capacity_factor=float(cfg.n_experts / cfg.top_k))
    opt_cfg = AdamWConfig(grad_clip=1e9)
    shape = ShapeConfig("t", 64, 8, "train")
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=64,
                           global_batch=8)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    state = init_state(jax.random.PRNGKey(0), cfg, opt_cfg)
    step = make_train_step(cfg, opt_cfg)

    # single device reference
    s_ref, m_ref = jax.jit(step)(state, batch)
    out["loss_single"] = float(m_ref["loss"])

    # (2, 2) mesh: DP x TP(+EP via shard_map) + FSDP state sharding
    mesh = make_auto_mesh((2, 2), ("data", "model"))
    rules = build_rules(cfg, shape, mesh)
    with axis_rules(rules):
        state2 = init_state(jax.random.PRNGKey(0), cfg, opt_cfg)
        st_sh = sp.to_shardings(
            sp.param_specs(state2, rules, fsdp=True), rules)
        b_sh = sp.to_shardings(sp.batch_specs(batch, rules), rules)
        step2 = make_train_step(cfg, opt_cfg)
        fn = jax.jit(step2, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None))
        with mesh:
            s_dist, m_dist = fn(state2, batch)
    out["loss_dist"] = float(m_dist["loss"])

    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(s_ref["params"]), jax.tree.leaves(s_dist["params"]))]
    out["max_param_diff"] = max(diffs)
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_distributed_train_step_matches_single_device():
    res = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         cwd="/root/repo")
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["loss_single"] == pytest.approx(out["loss_dist"], rel=1e-4)
    assert out["max_param_diff"] < 5e-4, out


# -- roofline HLO parsing ------------------------------------------------------

_FAKE_HLO = """
HloModule test
ENTRY main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ag = f32[16,2048]{1,0} all-gather(%p0), dim=1
  %ar = bf16[1024]{0} all-reduce(%x), to_apply=%sum
  %ar2.start = bf16[1024]{0} all-reduce-start(%x)
  %rs = f32[8,64]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = (f32[4,32]{1,0}, f32[4,32]{1,0}) all-to-all(%a, %b)
  %cp = u32[256]{0} collective-permute(%c), source_target_pairs={{0,1}}
  %add = f32[16,2048]{1,0} add(%ag, %ag)
}
"""


def test_parse_collective_bytes():
    st = parse_collective_bytes(_FAKE_HLO)
    assert st.bytes_by_kind["all-gather"] == 16 * 2048 * 4
    assert st.bytes_by_kind["all-reduce"] == 1024 * 2 * 2   # ar + ar2.start
    assert st.bytes_by_kind["reduce-scatter"] == 8 * 64 * 4
    assert st.bytes_by_kind["all-to-all"] == 2 * 4 * 32 * 4
    assert st.bytes_by_kind["collective-permute"] == 256 * 4
    assert st.count_by_kind["all-reduce"] == 2


def test_parse_ignores_non_collectives():
    st = parse_collective_bytes("%x = f32[10]{0} add(%a, %b)")
    assert st.total_bytes == 0


def test_roofline_terms_math():
    rep = roofline_terms(
        arch="a", shape="s", mesh_name="16x16", chips=256,
        cost_analysis={"flops": 197e12 * 1e-3,          # per-device
                       "bytes accessed": 819e9 * 2e-3},
        hlo_text=_FAKE_HLO, n_params_active=int(1e9), n_tokens=1000,
        training=True)
    assert rep.t_compute == pytest.approx(1e-3)
    assert rep.t_memory == pytest.approx(2e-3)
    assert rep.dominant == "memory"
    assert rep.model_flops_ == pytest.approx(6e12)
    assert 0 < rep.roofline_fraction <= 1.0


def test_model_flops():
    assert model_flops(100, 10, training=True) == 6000
    assert model_flops(100, 10, training=False) == 2000
