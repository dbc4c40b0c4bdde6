"""BENCHMARK.json -> everything one cell needs, found by name.

    configs/<config>/config.json   the manifest's `file`; adapter.py and
                                   reference.py sit beside it
    traffic/<traffic>.json         parameters one generator reads
    generators/<kind>.py           the traffic file's `generator`
    layer_metrics/<reader>.py      one `read(run)`; a manifest metric named
                                   `<reader>.<tag>` uses the same reader
                                   (one entry per end-to-end metric it moves)
"""
import dataclasses
import importlib.util
import json
import os


class ManifestError(Exception):
    """The manifest or a file it names is missing or inconsistent."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    config_dir: str
    traffic: dict
    bench_dir: str
    end_to_end: list      # manifest entries this cell reports
    per_layer: list


def load(root):
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"no manifest at {path}: {e}") from e


def _read_json(path, what):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"{what}: cannot read {path}: {e}") from e


def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(root, workload):
    """The Cell for `workload`, with its files loaded."""
    manifest = load(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise ManifestError(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"{workload}: no config {w['config']!r}")
    bench_dir = os.path.join(root, manifest["paths"][0])
    config_file = os.path.join(root, configs[w["config"]]["file"])
    e2e = [m for m in manifest["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _applies(m, workload) and m["moves"] in reported]
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=_read_json(config_file, f"config {w['config']}"),
        config_dir=os.path.dirname(config_file),
        traffic=_read_json(
            os.path.join(bench_dir, "traffic", w["traffic"] + ".json"),
            f"traffic {w['traffic']}"),
        bench_dir=bench_dir, end_to_end=e2e, per_layer=per_layer)


def load_py(path, what):
    """Import one file by path (adapters, generators, readers)."""
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file {path}")
    name = "benchmark_dyn_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, "/"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def adapter(cell):
    return load_py(os.path.join(cell.config_dir, "adapter.py"),
                   f"adapter of {cell.config_name}")


def reference(cell):
    return load_py(os.path.join(cell.config_dir, "reference.py"),
                   f"reference of {cell.config_name}")


def generator(cell):
    kind = cell.traffic["generator"]
    return load_py(os.path.join(cell.bench_dir, "generators", kind + ".py"),
                   f"generator {kind}")


def reader(cell, metric_name):
    base = metric_name.split(".", 1)[0]
    return load_py(os.path.join(cell.bench_dir, "layer_metrics", base + ".py"),
                   f"reader of {metric_name}")
