"""A later PR adds a configuration, a cell and a per-layer metric as
files and entries only. In a temporary copy of the benchmark, with no
edit to a file that was there, the harness finds each by name."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_new_config_cell_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {
        str(p.relative_to(root)): p.read_bytes()
        for p in (root / "benchmarks").rglob("*") if p.is_file()}
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

    # a new configuration: its file of sizes, its reference and counts
    cfg = json.load(open(root / "benchmarks/configs/chartransformer12.json"))
    cfg["name"] = "chartransformer3"
    cfg["tiny"]["model"]["n_layers"] = 3
    (root / "benchmarks/configs/chartransformer3.json").write_text(
        json.dumps(cfg))
    for kind in ("references", "counts"):
        shutil.copy(root / f"benchmarks/{kind}/chartransformer12.py",
                    root / f"benchmarks/{kind}/chartransformer3.py")
    # a new cell: a traffic file that names an existing driver
    traffic = json.load(
        open(root / "benchmarks/traffic/chartransformer12.fit.json"))
    traffic["tiny"]["batch"] = 2
    (root / "benchmarks/traffic/chartransformer3.fit_b2.json").write_text(
        json.dumps(traffic))
    # a new per-layer metric: a reader of its own
    (root / "benchmarks/metrics/chunks_in_window.py").write_text(
        "def read(ctx):\n"
        "    w = ctx['window']\n"
        "    return w['batches'] // w['chunk']\n")
    bench["configs"].append({
        "name": "chartransformer3", "source": cfg["source"],
        "file": "benchmarks/configs/chartransformer3.json",
        "reduced": ["n_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "chartransformer3.fit_b2", "config": "chartransformer3",
        "traffic": "fit_b2", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "chunks_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "step program",
        "moves": "fit_examples_per_s",
        "workloads": ["chartransformer3.fit_b2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "chartransformer3.fit_b2", "--seed", "4", "--seconds", "0.3",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["metrics"]["chunks_in_window"]["value"] >= 1
    assert "[setup]" in proc.stdout and '"batch": 2' in proc.stdout
    # and the older cell does not report the new cell's metric
    from benchmarks.harness.spec import Cell

    old = Cell("chartransformer12.fit", benchmark=bench)
    assert "chunks_in_window" not in {m["name"] for m in old.per_layer}
    after = {
        str(p.relative_to(root)): p.read_bytes()
        for p in (root / "benchmarks").rglob("*")
        if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items())


def test_only_the_benchmark_is_not_enough():
    """In a directory that holds only BENCHMARK.json and the files
    under ``paths`` the command fails and prints no result: the program
    under test is not there."""
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        shutil.copytree(os.path.join(REPO, "benchmarks"),
                        os.path.join(tmp, "benchmarks"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        proc = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload",
             "resnet50.fit", "--seed", "1", "--seconds", "1", "--trace",
             "0", "--rehearse"],
            cwd=tmp, env=dict(env, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip().splitlines()[-1:] or not \
        proc.stdout.strip().splitlines()[-1].startswith("{")
