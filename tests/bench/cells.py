"""Cells for the benchmark's CPU tests, built from a configuration and a
traffic mix by name, whether or not ``BENCHMARK.json`` lists the pair."""

from bench import manifest

E2E = ({"name": "samples_per_s", "unit": "samples/s"},
       {"name": "setup_s", "unit": "s"})


def cell(config_name: str, traffic_name: str) -> manifest.Cell:
    config = manifest.load_json(
        manifest.BENCH_DIR / "configs" / f"{config_name}.json")
    traffic = manifest.load_json(
        manifest.BENCH_DIR / "traffic" / f"{traffic_name}.json")
    return manifest.Cell(
        name=f"{config_name}.{traffic_name}", chips=1,
        config_name=config_name, config=config, traffic=traffic,
        end_to_end=E2E, per_layer=())

CONFIG, TRAFFIC = "attr-call-rapl", "region-d3"
WORKLOAD = f"{CONFIG}.{TRAFFIC}"


def tiny(samples: int = 20_000, block: int = 4096,
         checked: int = 2) -> manifest.Cell:
    """The cell at a size a CPU test run holds: a shorter profiled run
    (``samples`` sampling periods) drawn in smaller clock blocks."""
    import copy
    import dataclasses
    c = cell(CONFIG, TRAFFIC)
    cfg, tr = copy.deepcopy(c.config), copy.deepcopy(c.traffic)
    cfg["sampling"]["clock_block"] = block
    tr.update({"samples_per_call": samples, "checked_calls": checked})
    return dataclasses.replace(c, config=cfg, traffic=tr)
