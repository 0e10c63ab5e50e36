"""A later change adds a configuration, a traffic mix, a cell and a
per-layer metric as files and BENCHMARK.json entries; the harness runs the
new cell and reports the new metric with no existing file edited."""
import hashlib
import json
import shutil
from pathlib import Path

from chipbench import rehearsal

ROOT = Path(__file__).resolve().parent.parent


def _digest(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_traffic_cell_and_metric_as_files(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root / "chipbench")
    cb = root / "chipbench"
    # a traffic mix whose prompts fill whole blocks
    tr = json.loads((cb / "traffic" / "chat.json").read_text())
    tr["why"] = "chat with prompts of whole blocks"
    tr["prompt_len"] = {"dist": "normal", "mean": 32, "sd": 0, "min": 32,
                        "max": 32}
    (cb / "traffic" / "blocks.json").write_text(json.dumps(tr))
    cell = json.loads((cb / "cells" / "llada-8b-1chip.chat.json")
                      .read_text())
    (cb / "cells" / "mamba2-130m.blocks.json").write_text(json.dumps(cell))
    (cb / "metrics" / "blocks_done.blocks.py").write_text(
        '"""Whole blocks whose values reached the host in the window."""\n'
        "def read(run):\n"
        "    return sum(len(r.blocks) for r in run.reqs)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "mamba2-130m", "source": "https://huggingface.co/"
        "state-spaces/mamba2-130m", "file": "chipbench/configs/"
        "mamba2-130m.json", "reduced": ["vocab_size"], "why": "SSD scan"})
    bench["workloads"].append({
        "name": "mamba2-130m.blocks", "config": "mamba2-130m",
        "traffic": "blocks", "chips": 1, "why": "the SSM family"})
    for m in bench["end_to_end"]:
        if m["name"] == "block_gap_p95_s":
            m["workloads"].append("mamba2-130m.blocks")
    bench["per_layer"].append({
        "name": "blocks_done.blocks", "unit": "blocks", "better": "higher",
        "source": "host_clock", "layer": "engine loop (core/engine.py)",
        "moves": "block_gap_p95_s", "workloads": ["mamba2-130m.blocks"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    added = _digest(cb)
    assert {k: v for k, v in added.items() if k in before} == before

    res = rehearsal.run("mamba2-130m.blocks", seed=3, root=root, ssm=True,
                        trace=True, trace_dir=tmp_path / "trace")
    assert res["correct"] is True
    assert res["metrics"]["blocks_done.blocks"]["value"] > 0
    res0 = rehearsal.run("mamba2-130m.blocks", seed=4, root=root, ssm=True)
    assert set(res0["metrics"]) == {"block_gap_p95_s", "setup_s"}
    assert _digest(cb) == added
