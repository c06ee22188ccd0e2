"""The harness is driven by data: a configuration, a cell or a metric is
added by adding files; and the chip entry point refuses to run off-chip."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import bench

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 12345


def edit_json(path, **changes):
    with open(path) as fh:
        data = json.load(fh)
    data.update(changes)
    with open(path, "w") as fh:
        json.dump(data, fh)


def _add_cell(root):
    """A new configuration file, cell and per-layer metric reader, added to
    a copy of the benchmark without editing any harness file."""
    cfgs = os.path.join(root, "chipbench", "configs")
    shutil.copy(os.path.join(cfgs, "porcine1_ssd.json"),
                os.path.join(cfgs, "cube_ssd.json"))
    edit_json(os.path.join(cfgs, "cube_ssd.json"), name="cube_ssd",
              volume=[18, 18, 18])
    shutil.copy(os.path.join(root, "chipbench", "limits",
                             "porcine1_ssd.single.json"),
                os.path.join(root, "chipbench", "limits",
                             "cube_ssd.single.json"))
    with open(os.path.join(root, "chipbench", "metrics",
                           "calls_in_window.py"), "w") as fh:
        fh.write("def read(ctx):\n    return ctx.calls\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        b = json.load(fh)
    b["configs"].append({"name": "cube_ssd", "source": "test",
                         "file": "chipbench/configs/cube_ssd.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "cube_ssd.single", "config": "cube_ssd",
                           "traffic": "single", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "register_s":
            m["workloads"].append("cube_ssd.single")
    b["per_layer"].append({"name": "calls_in_window", "unit": "count",
                           "better": "higher", "source": "host_clock",
                           "layer": "entry", "moves": "register_s",
                           "workloads": ["cube_ssd.single"]})
    with open(path, "w") as fh:
        json.dump(b, fh)


def test_an_added_cell_and_metric_are_found_and_run(tiny_root):
    _add_cell(tiny_root)
    cell = bench.find_cell(tiny_root, "cube_ssd.single")
    assert cell.config["volume"] == [18, 18, 18]
    names = [m["name"] for m in bench.cell_metrics(cell, trace=True)]
    assert "calls_in_window" in names and "autotune_s" not in names
    timed = bench.run_cell(tiny_root, "cube_ssd.single", SEED, 0.2, False,
                           require_tpu=False)
    assert timed["correct"] and timed["failed"] == 0
    assert set(timed["metrics"]) == {"setup_s", "register_s"}
    assert timed["metrics"]["register_s"]["unit"] == "s"
    assert list(timed)[-1] == "checks"
    assert set(timed["checks"]) == set(cell.limits)
    traced = bench.run_cell(tiny_root, "cube_ssd.single", SEED, 0.2, True,
                            require_tpu=False)
    # device metrics find no device plane on the CPU and are left out
    assert traced["metrics"] == {"calls_in_window": {
        "value": float(traced["attempted"]), "unit": "count"}}
    assert traced["attempted"] >= 3


def test_batch_cell_reports_pairs_per_second(tiny_root):
    line = bench.run_cell(tiny_root, "porcine1_ssd.batch", 7, 0.2, False,
                          require_tpu=False)
    assert line["correct"]
    assert set(line["metrics"]) == {"setup_s", "pairs_per_s"}
    assert line["attempted"] % 2 == 0 and line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu"


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "porcine1_ssd.single", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_means_no_result():
    out = _run(REPO, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "nothing measured" in out.stderr


def test_the_benchmark_alone_is_no_system(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(tmp_path, "chipbench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "{" not in out.stdout
