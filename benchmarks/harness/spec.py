"""Where the benchmark finds things: every cell, configuration, driver,
reference, counting function and per-layer metric is a file named after
its entry in ``BENCHMARK.json``, so a later PR adds files and entries
and edits nothing that is here.

    workloads[].name    -> traffic/<name>.json   (names its driver)
    configs[].file      -> configs/<name>.json
    traffic "driver"    -> drivers/<driver>.py   (``run(cell, args)``)
    configs[].name      -> references/<name>.py, counts/<name>.py
    per_layer[].name    -> metrics/<name>.py     (``read(ctx)``)
"""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module, by file name."""
    path = os.path.join(ROOT, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"benchmark: no {kind}/{name}.py for '{name}'"
        )
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with everything it names."""

    def __init__(self, workload_name, benchmark=None):
        bench = benchmark or load_benchmark()
        by_name = {w["name"]: w for w in bench["workloads"]}
        if workload_name not in by_name:
            raise SystemExit(
                f"benchmark: unknown workload '{workload_name}'; "
                f"BENCHMARK.json has {sorted(by_name)}"
            )
        self.bench = bench
        self.workload = by_name[workload_name]
        self.name = workload_name
        self.chips = int(self.workload["chips"])
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.workload["config"])
        self.config_name = conf["name"]
        with open(os.path.join(REPO, conf["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", workload_name + ".json")

    def _metrics(self, section):
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]

    @property
    def end_to_end(self):
        return self._metrics("end_to_end")

    @property
    def per_layer(self):
        return self._metrics("per_layer")

    def reference(self):
        return load_module("references", self.config_name)

    def counts(self):
        return load_module("counts", self.config_name)

    def driver(self):
        return load_module("drivers", self.traffic["driver"])


def load_benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)
