"""CPU rehearsal of ``run.py``: its serve-and-measure path end to end for a
cell of each configuration, at a reduced size in bf16 with the kernels
interpreted; and the command's refusal to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

from chipbench import rehearsal

ROOT = Path(__file__).resolve().parent.parent


def _shape(res, names):
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_chat_cell_end_to_end(capsys):
    res = rehearsal.run("llada-8b-1chip.chat", seed=2 ** 31 + 11)
    _shape(res, {"ttfb_p90_s", "block_gap_p95_s", "setup_s"})
    assert res["correct"] is True
    assert res["attempted"] == 15 and res["failed"] == 0
    out = capsys.readouterr()
    last = out.out.strip().splitlines()[-1]
    assert json.loads(last) == res
    assert "compiles inside the window: 0 " in out.out
    assert res["checks"]["schedule_violations"]["value"] == 0
    assert res["setup"]["run"] == "uncached"
    assert out.err.strip().splitlines()[-1].startswith(
        "check tokens_checked:")


def test_chat_cell_traced(tmp_path):
    res = rehearsal.run("llada-8b-1chip.chat", seed=5, trace=True,
                        trace_dir=tmp_path / "trace")
    assert res["correct"] is True
    # the CPU has no device plane: the device is idle in the whole window
    _shape(res, {"queue_wait_p90_s.chat", "host_ms_per_iter.chat",
                 "device_idle_frac.chat"})
    assert res["metrics"]["device_idle_frac.chat"]["value"] == 1.0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_command_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "llada-8b-1chip.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_command_refuses_in_a_bare_directory(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ has no program:
    the command fails and prints no result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "llada-8b-1chip.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
