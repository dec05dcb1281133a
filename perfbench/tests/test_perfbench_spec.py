"""BENCHMARK.json: every cell resolves to its files by name, a new cell
needs only new files and an entry, and the harness refuses to run
without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import perfbench_tiny as tiny
from harness import spec

BENCH = spec.load_benchmark(tiny.ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.resolve(BENCH, cell, tiny.ROOT)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    e2e = [m["name"] for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.end_to_end:
        assert callable(spec.reader("end_to_end", m["name"]))
    for m in c.per_layer:
        assert callable(spec.reader("metrics", m["name"]))
        assert m["moves"] in e2e


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in BENCH["configs"]:
        cfg = json.loads((tiny.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A copy of the benchmark gains a cell, a mix and a per-layer metric
    by adding files and entries alone; the harness resolves and reads
    them without a change."""
    bench_dir = tmp_path / "perfbench"
    shutil.copytree(tiny.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench_dir / "traffic" / "docqa-short.json").write_text(json.dumps(
        dict(json.loads((bench_dir / "traffic" / "docqa.json").read_text()),
             shared_prefix_tokens=128)))
    (bench_dir / "metrics" / "requests_due.tput.py").write_text(
        "def read(ctx):\n    return float(len(ctx.due_in_window()))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "internlm2-20b-pp8.docqa-short",
                               "config": "internlm2-20b-pp8",
                               "traffic": "docqa-short", "chips": 1,
                               "why": "a test cell"})
    for m in bench["end_to_end"]:
        if m["name"] == "output_tok_s":
            m["workloads"].append("internlm2-20b-pp8.docqa-short")
    bench["per_layer"].append({"name": "requests_due.tput", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": ["internlm2-20b-pp8.docqa-short"]})
    c = spec.resolve(bench, "internlm2-20b-pp8.docqa-short", tiny.ROOT,
                     bench_dir)
    assert c.traffic["shared_prefix_tokens"] == 128
    assert [m["name"] for m in c.end_to_end] == ["output_tok_s", "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["requests_due.tput"]

    class Ctx:
        def due_in_window(self):
            return [1, 2, 3]
    assert spec.reader("metrics", "requests_due.tput", bench_dir)(Ctx()) == 3


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(tiny.BENCH_DIR / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr
