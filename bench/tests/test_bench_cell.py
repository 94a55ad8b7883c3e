"""The harness: it fails without a TPU, it fails without the program, and
a configuration, traffic mix and per-layer metric it has never named run
from their files alone."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

from bench import cell

ARGS = ["--workload", "evalmap-xlstm125m.short", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "bench/cell.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(proc):
    return not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_no_tpu_no_result():
    proc = _run(cell.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0, proc.stderr
    assert _no_result(proc), proc.stdout
    assert "TPU chip" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(cell.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(cell.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert proc.returncode != 0
    assert _no_result(proc), proc.stdout


DUMMY = textwrap.dedent('''
    """A configuration the harness has never named: counts in the driver."""
    import time

    def run(ctx):
        t = ctx.traffic
        t0 = time.monotonic()
        done = sum(1 for _ in range(t["items"]))
        took = max(time.monotonic() - t0, 1e-9)
        return {"end_to_end": {"setup_s": 0.5, "items_per_s": done / took},
                "layers": {"items": done},
                "trace": {"busy_s": 0.1, "window_s": 0.2, "top_ops": [],
                          "idle_gaps": []},
                "attempted": done, "failed": 0,
                "checks": {"items_gap": (abs(done - t["items"]),
                                         ctx.config["exact"])},
                "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                           "memory_peak_bytes": 0}}
''')


def test_new_files_make_a_new_cell(tmp_path):
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "layers"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "dummy-cfg.json").write_text(json.dumps(
        {"name": "dummy-cfg", "exact": 0}))
    (b / "configs" / "dummy-cfg.py").write_text(DUMMY)
    (b / "traffic" / "dummy-cfg.few.json").write_text('{"items": 7}')
    (b / "layers" / "items_done.py").write_text(
        "def read(obs):\n    return obs['items']\n")
    (b / "layers" / "absent_layer.py").write_text(
        "def read(obs):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/cell.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "dummy-cfg", "source": "nowhere",
                     "file": "bench/configs/dummy-cfg.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "dummy-cfg.few", "config": "dummy-cfg",
                       "traffic": "few", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "items_per_s", "unit": "1/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"}],
        "per_layer": [
            {"name": "items_done", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "counting",
             "moves": "items_per_s", "workloads": ["dummy-cfg.few"]},
            {"name": "absent_layer", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "counting",
             "moves": "items_per_s", "workloads": ["dummy-cfg.few"]}]}))
    bench = cell.Bench(str(tmp_path))
    line = cell.run(bench, "dummy-cfg.few", 1, 0.1, False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "items_per_s"}
    assert line["checks"] == {"items_gap": {"value": 0.0, "limit": 0}}
    line = cell.run(bench, "dummy-cfg.few", 1, 0.1, True)
    assert line["metrics"] == {"items_done": {"value": 7.0, "unit": "1"}}
    assert line["device"]["busy_s"] == 0.1
