"""BENCHMARK.json and the files it names: every configuration, traffic
mix, per-layer metric and reference is found by name, and the manifest
keeps the shape the benchmark's contract gives it."""

import json
import re

import pytest

from bench import manifest

ROOT = manifest.ROOT
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir()
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    assert 1 <= MANIFEST["run_seconds"] <= 51


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_finds_everything_by_name(workload):
    cell = manifest.load_cell(workload)
    assert cell.config["name"] == cell.config_name
    assert (manifest.BENCH_DIR / "drivers"
            / f"{cell.config['kind']}.py").is_file()
    assert callable(manifest.generator(cell.traffic["generator"]).generate)
    ref = manifest.reference(cell.config_name)
    assert ref.LIMITS
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(manifest.layer_reader(m["name"]).read)
        assert m["moves"] in names


def test_names_units_and_entry_keys():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", ()):
            assert w in WORKLOADS
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_missing_names_are_errors():
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no-such-cell")
    with pytest.raises(manifest.ManifestError):
        manifest.layer_reader("no_such_metric")


def test_without_a_tpu_the_run_exits_2_and_prints_no_result(tmp_path):
    """From a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths``, on a host whose JAX finds no TPU."""
    import os
    import shutil
    import subprocess
    import sys
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MANIFEST["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    cmd = [sys.executable, *MANIFEST["command"][1:], "--workload",
           WORKLOADS[0], "--seed", str(2**31 + 1), "--seconds", "1"]
    out = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 2, out.stderr
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
