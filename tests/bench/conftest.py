"""Fixtures of the chip benchmark's CPU tests: a copy of the benchmark's
data files with every configuration cut to a tiny volume."""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = {"volume": [24, 20, 16], "levels": 2, "iters": 3, "mode": "separable",
        "impl": "jnp", "grad_impl": "jnp", "fused": "off"}


def copy_benchmark(dst):
    """``BENCHMARK.json`` and the benchmark's data files under ``dst``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(REPO, "chipbench", sub),
                        os.path.join(dst, "chipbench", sub))
    return str(dst)


BATCH_CELL = {"name": "porcine1_ssd.batch", "config": "porcine1_ssd",
              "traffic": "batch", "chips": 1, "why": "the batched path"}


def _rewrite(path, edit):
    with open(path) as fh:
        data = json.load(fh)
    edit(data)
    with open(path, "w") as fh:
        json.dump(data, fh)


def _add_batch_cell(bench):
    """``porcine1_ssd.batch``: ``register_batch`` of the batch traffic, with
    ``pairs_per_s``, judged by the single cell's limits."""
    bench["workloads"].append(BATCH_CELL)
    bench["end_to_end"].append(
        {"name": "pairs_per_s", "unit": "pairs/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": [BATCH_CELL["name"]]})


@pytest.fixture
def tiny_root(tmp_path):
    """The benchmark with every configuration at ``TINY`` (jnp BSI forms),
    and a batch cell on the program's ``register_batch`` path."""
    root = copy_benchmark(tmp_path)
    configs = os.path.join(root, "chipbench", "configs")
    for name in os.listdir(configs):
        _rewrite(os.path.join(configs, name), lambda cfg: cfg.update(TINY))
    _rewrite(os.path.join(root, "BENCHMARK.json"), _add_batch_cell)
    limits = os.path.join(root, "chipbench", "limits")
    shutil.copy(os.path.join(limits, "porcine1_ssd.single.json"),
                os.path.join(limits, "porcine1_ssd.batch.json"))
    return root
