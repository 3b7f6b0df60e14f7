"""``BENCHMARK.json`` and the files it names, found by name.

* configuration ``<c>``: the JSON file its entry names (``file``), and its
  plain reference ``bench/refs/<c>.py``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by the generator
  it names, ``bench/generators/<generator>.py``; a driver per
  configuration ``kind``, ``bench/drivers/<kind>.py``;
* per-layer metric ``<m>``: ``bench/layer_metrics/<m>.py``, whose
  ``read(ctx)`` returns the value or ``None`` when it finds nothing.

Adding a cell, a configuration or a metric adds files and entries; no file
here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class ManifestError(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]   # metrics this cell reports, --trace 0
    per_layer: tuple[dict, ...]    # metrics this cell reports, --trace 1


def load_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing {path}") from None


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (names such as ``attr-call-rapl`` are
    not identifiers, so these files are not importable by name)."""
    if not path.is_file():
        raise ManifestError(f"missing {path}")
    mod_name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH_DIR)))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    manifest = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise ManifestError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    centry = configs[w["config"]]
    config = load_json(root / centry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in manifest["end_to_end"] if _reports(m, name))
    e2e_names = {m["name"] for m in e2e}
    layer = tuple(m for m in manifest["per_layer"]
                  if _reports(m, name) and m["moves"] in e2e_names)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic=traffic,
                end_to_end=e2e, per_layer=layer)


def reference(config_name: str) -> ModuleType:
    return load_module(BENCH_DIR / "refs" / f"{config_name}.py")


def layer_reader(metric_name: str) -> ModuleType:
    return load_module(BENCH_DIR / "layer_metrics" / f"{metric_name}.py")


def generator(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "generators" / f"{name}.py")
