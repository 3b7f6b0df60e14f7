"""Launch-path smoke: lower_cell compiles representative cells on a small
multi-pod mesh in a subprocess (device count must be set pre-jax-init;
this process keeps 1 device). One cell per family × step kind."""

import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import warnings; warnings.filterwarnings("ignore")
    import jax
    from repro.launch.dryrun import lower_cell
    from repro.launch.mesh import make_auto_mesh
    from repro.configs.registry import get_config

    mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
    cells = [("yi-6b", "train_4k"), ("qwen3-moe-30b-a3b", "decode_32k"),
             ("zamba2-1.2b", "long_500k"), ("hubert-xlarge", "prefill_32k"),
             ("xlstm-125m", "decode_32k"), ("hubert-xlarge", "decode_32k")]
    for arch, shape in cells:
        cfg = get_config(arch).reduced()
        row, _ = lower_cell(arch, shape, multi_pod=True, mesh=mesh,
                            cfg_override=cfg)
        status = "SKIP" if "skipped" in row else "OK"
        print(f"CELL {arch} {shape} {status}")
    print("ALLDONE")
""")


@pytest.mark.slow
def test_dryrun_cells_compile_small_mesh():
    res = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=1500,
                         cwd="/root/repo")
    assert res.returncode == 0, res.stderr[-3000:]
    assert "ALLDONE" in res.stdout
    oks = [l for l in res.stdout.splitlines() if l.startswith("CELL")]
    assert len(oks) == 6
    # encoder-only decode must be a documented skip
    assert any("hubert-xlarge decode_32k SKIP" in l for l in oks)
