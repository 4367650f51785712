"""The chip benchmark's entry point and its data-driven lookup: the result
line carries the contract's keys, a run without a TPU prints no result, and
a cell added as files alone is found by name."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness, model, spec
from chipbench.loadgen import generator_module

import cb_fixtures

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_refuses_a_backend_without_tpu():
    p = _run_py(ROOT, "--workload", "stablelm-1.6b.decode-long", "--seed",
                "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, "--workload", "stablelm-1.6b.decode-long",
                "--seed", "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and "{" not in p.stdout


def test_result_line_holds_the_contract_keys(tmp_path):
    root = cb_fixtures.write_root(tmp_path)
    cell = spec.load_cell(root, "tiny-dense.closed")
    result, run, notes = harness.run_cell(cell, 2 ** 31 + 3, 2.0, False,
                                          time.perf_counter(),
                                          require_tpu=False)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["metrics"]) == {"output_tokens_per_s", "itl_p95_s",
                                      "setup_s"}
    assert all(set(m) == {"value", "unit"}
               for m in result["metrics"].values())
    assert all(set(c) == {"value", "limit"}
               for c in result["checks"].values())
    assert json.loads(json.dumps(result)) == result
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert any(n.startswith("window:") for n in notes)


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    extra = dict(cb_fixtures.TINY_MOE, name="tiny-moe-b")
    root = cb_fixtures.write_root(
        tmp_path, cells=(("tiny-dense.closed", cb_fixtures.TINY_DENSE,
                          "closed", cb_fixtures.CLOSED),
                         ("tiny-moe-b.burst", extra, "open-fast",
                          dict(cb_fixtures.OPEN, rate_per_s=9.0))))
    cell = spec.load_cell(root, "tiny-moe-b.burst")
    assert cell.conf["name"] == "tiny-moe-b"
    assert cell.traffic["rate_per_s"] == 9.0
    assert [m["name"] for m in cell.end_to_end] == [
        "output_tokens_per_s", "itl_p95_s", "setup_s"]
    gen = generator_module(cell.traffic["kind"]).make(
        cell.traffic, 1, 2, 5.0, cell.conf["vocab_size"])
    assert gen.next_arrival() is not None
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_module(m["name"]).value)
    assert model.model_config(cell.conf).moe.n_experts == 4
    with pytest.raises(KeyError):
        spec.load_cell(root, "tiny-moe-b.nothing")


def test_every_metric_and_file_of_the_benchmark_is_there():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_module(m["name"]).value)
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        dep = cell.conf["deployment"]
        gen = generator_module(cell.traffic["kind"]).make(
            cell.traffic, 1, dep["max_slots"], bench["run_seconds"],
            cell.conf["vocab_size"])
        assert dep["max_slots"] >= 1 and gen.max_reach() <= dep["t_max"]
        assert os.path.exists(os.path.join(ROOT, "chipbench", "limits",
                                           f"{w['name']}.json"))
        model.model_config(cell.conf)


def test_a_config_that_the_program_cannot_run_is_refused():
    conf = dict(cb_fixtures.TINY_DENSE, partial_rotary_factor=0.25)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        model.model_config(conf)
    conf = dict(cb_fixtures.TINY_MOE, attention_multiplier=0.015625)
    with pytest.raises(ValueError, match="attention"):
        model.model_config(conf)
