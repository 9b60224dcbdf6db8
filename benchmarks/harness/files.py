"""Where the checkout is, and how the harness finds a file by its name."""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_file(path, name):
    """Import the python file at ``path`` (relative to the checkout)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(bench, name):
    """``(cell, configuration, traffic mix, window module)`` of the cell
    ``name`` of ``BENCHMARK.json``'s workloads."""
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = read_json(entry["file"])
    traffic = read_json("benchmarks", "traffic", cell["traffic"] + ".json")
    window = load_file("benchmarks/windows/%s.py" % traffic["window"],
                       "bench_window")
    return cell, cfg, traffic, window
