"""Ahead-of-time compiles of the main path for one TPU v5e chip.

Each test compiles a kernel or jitted step of the chip's main path at
real widths for one chip of a *described* ``v5e:2x2`` topology, with the
TPU compiler that ships with jaxlib. Nothing runs: a passing compile says
the chip's compiler accepts the program (Mosaic lowering, scoped-VMEM
budget, HBM fit), not what it computes or how fast.

Only one process may load the TPU library at a time, so the topology is
described inside the module-scoped fixture below — never at import, in a
``skipif`` or in ``parametrize`` — and only the worker that runs this
file loads it. All of these compiles stay in this one file for the same
reason. The persistent compilation cache is off around them: an entry
written for a described chip cannot be read back without one.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64
from jax.sharding import SingleDeviceSharding

from repro.core import device_pipeline as dp
from repro.core.power_model import hardware_for
from repro.core.sensors import RaplTraceSensor
from repro.core.timeline import RegionCost, Timeline, synthesize

REGIONS = 1024
CHUNK = dp.DEFAULT_CHUNK
SERVE_BATCH, SERVE_MAX_LEN = 8, 1024    # as chip_smoke.py serves


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            cc.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _timelines(workers: int, *, domains: bool) -> list[Timeline]:
    rng = np.random.default_rng(0)
    costs = [RegionCost(f"bb_{i}", flops=float(rng.uniform(1e11, 1e12)),
                        hbm_bytes=float(rng.uniform(5e8, 5e9)))
             for i in range(REGIONS)]
    return [synthesize(costs, steps=2, seed=s, domains=domains)
            for s in range(workers)]


@pytest.mark.parametrize("num_regions", [128, 8192])
def test_sample_attr_kernel_compiles(one_chip, num_regions):
    from repro.kernels.sample_attr.sample_attr import sample_attr_pallas

    fn = jax.jit(lambda ids, pw: sample_attr_pallas(ids, pw, num_regions))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((CHUNK,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((CHUNK,), jnp.float32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                    r"(fusion|custom-call)\(")
_STAGE = re.compile(r'op_name="[^"]*alea/(clock|lookup|sensor|reduce)\b')


def _stage_by_large_op(hlo: str) -> dict[str, str | None]:
    """Innermost ``alea/<stage>`` of each fusion or custom call of the
    compiled program whose result holds at least a chunk of elements."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and math.prod(int(d) for d in m[3].split(",") if d) >= CHUNK:
            stages = _STAGE.findall(line)
            out[m[1]] = stages[-1] if stages else None
    return out


@pytest.mark.parametrize("domains", [False, True], ids=["D1", "D3"])
def test_fused_region_step_compiles_with_pallas(one_chip, domains):
    """The compiled step runs the Pallas reduction under its name, and
    every large operation carries the stage that it belongs to in its
    ``op_name``, as the device trace reports it."""
    (tl,) = _timelines(1, domains=domains)
    dtl = tl.to_device()
    spec = RaplTraceSensor.make_spec(domains=dtl.domains)
    with enable_x64():
        fn, args = dp.region_pipeline_call(dtl, spec, period=1e-3,
                                           use_pallas=True)
        compiled = fn.lower(*_on(one_chip, args)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert re.search(r"%sample_attr\.\d+ = f32\[8,", hlo)
    stages = _stage_by_large_op(hlo)
    assert stages and all(stages.values()), stages
    assert {"lookup", "sensor", "reduce"} <= set(stages.values())


def test_combo_chunk_step_compiles(one_chip):
    tls = _timelines(16, domains=False)
    dtl = dp.DeviceTimeline.from_timelines(tls)
    spec = RaplTraceSensor.make_spec()
    pack = dp._pack_spec(dtl.num_regions, dtl.num_workers)
    cap = 1 << 14
    with enable_x64():
        step = dp._combo_step_fn(CHUNK, spec, dtl.grid_k, pack)
        table, table_ids, n_rows = dp._build_table(
            dp.CombinationInterner(), cap, dtl.num_workers, pack)
        carry = (jnp.zeros(cap, jnp.int64), jnp.zeros(cap, jnp.float64),
                 jnp.zeros(cap, jnp.float64), jnp.zeros((), jnp.int64),
                 -jnp.ones((), jnp.float64))
        args = (carry, table, table_ids, n_rows, *dtl.arrays(),
                jnp.asarray(dp._ref_times(0, 0, 1e-3, 200e-6, CHUNK)),
                jnp.float64(dtl.t_end))
        step.lower(*_on(one_chip, args)).compile()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention

    q = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = flash_attention.lower(q, q, q, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles(one_chip):
    from repro.kernels.rmsnorm.ops import rmsnorm

    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    compiled = rmsnorm.lower(x, scale, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_fits_one_chip(one_chip):
    """The serving engine's decode step at qwen3-1.7b's published widths,
    at the batch and cache length ``chip_smoke.py`` serves with, fits
    one chip's HBM."""
    from repro.configs.registry import get_config
    from repro.models import model as M
    from repro.serve.engine import _jitted_fns

    cfg = get_config("qwen3-1.7b")
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, SERVE_BATCH,
                                                SERVE_MAX_LEN))
    decode, _ = _jitted_fns(cfg)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = decode.lower(
        _on(one_chip, params), i32(SERVE_BATCH, 1), _on(one_chip, cache),
        i32(SERVE_BATCH),
        jax.ShapeDtypeStruct((SERVE_BATCH,), jnp.bool_, sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < hardware_for("TPU v5 lite").hbm_bytes, mem
