"""A later change adds a configuration, a traffic mix, a cell and a
per-layer metric as files and BENCHMARK.json entries; the harness runs the
new cell and reports the new metric with no existing file edited. A
configuration of an architecture the benchmark has not run brings its
module (``archs/<arch>.py``) as a new file too, and the check replays the
cell with that module's reference."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from chipbench import rehearsal

ROOT = Path(__file__).resolve().parent.parent


def _digest(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _checkout(tmp_path) -> Path:
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_new_config_traffic_cell_and_metric_as_files(tmp_path):
    root = _checkout(tmp_path)
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

    res = rehearsal.run("mamba2-130m.blocks", seed=3, root=root,
                        trace=True, trace_dir=tmp_path / "trace")
    assert res["correct"] is True
    assert res["metrics"]["blocks_done.blocks"]["value"] > 0
    res0 = rehearsal.run("mamba2-130m.blocks", seed=4, root=root)
    assert set(res0["metrics"]) == {"block_gap_p95_s", "setup_s"}
    assert _digest(cb) == added


# LLaDA's block with a bias on each of the q, k and v projections, a field
# (``qkv_bias``) the program serves and no module of the benchmark reads.
# The biases are drawn wide enough that a reference without them misses
# every logit by far more than the rehearsal's limit.
BIAS_MODULE = '''"""LLaDA's block with biases on the q, k and v projections."""
from chipbench.archs import llada
from chipbench.archs.llada import (FIELDS, REHEARSAL, attention_flops,
                                   groups, matmul_flops_per_token)

BIAS_STD = 0.5


def layout(d):
    L, H, K, dh = d["n_layers"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    return dict(llada.layout(d), bq=((L, H, dh), BIAS_STD),
                bk=((L, K, dh), BIAS_STD), bv=((L, K, dh), BIAS_STD))


def qkv(p, h, quant):
    q, k, v = llada.qkv(p, h, quant)
    return q + p["bq"], k + p["bk"], v + p["bv"]


def block_hidden(*args):
    return llada.block_hidden(*args, proj=qkv)
'''
# the control: the same module, its reference without the biases
BIAS_LEFT_OUT = BIAS_MODULE.replace(
    'return q + p["bq"], k + p["bk"], v + p["bv"]', "return q, k, v")


@pytest.mark.parametrize("module, correct", [(BIAS_MODULE, True),
                                             (BIAS_LEFT_OUT, False)],
                         ids=["reference", "control"])
def test_new_architecture_as_files(tmp_path, module, correct):
    assert BIAS_LEFT_OUT != BIAS_MODULE
    root = _checkout(tmp_path)
    cb = root / "chipbench"
    before = _digest(cb)
    (cb / "archs" / "llada_qkv_bias.py").write_text(module)
    conf = json.loads((cb / "configs" / "llada-8b-1chip.json").read_text())
    conf.update(name="llada-qkvb", include_qkv_bias=True,
                arch_module="chipbench/archs/llada_qkv_bias.py")
    conf["program"]["qkv_bias"] = "include_qkv_bias"
    (cb / "configs" / "llada-qkvb.json").write_text(json.dumps(conf))
    shutil.copy(cb / "cells" / "llada-8b-1chip.chat.json",
                cb / "cells" / "llada-qkvb.chat.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "llada-qkvb", "source": "https://huggingface.co/"
        "GSAI-ML/LLaDA-8B-Instruct", "file": "chipbench/configs/"
        "llada-qkvb.json", "reduced": ["n_layers", "include_qkv_bias"],
        "why": "biases on q, k and v"})
    bench["workloads"].append({
        "name": "llada-qkvb.chat", "config": "llada-qkvb",
        "traffic": "chat", "chips": 1, "why": "a block with qkv biases"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("llada-qkvb.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    added = _digest(cb)
    assert {k: v for k, v in added.items() if k in before} == before

    res = rehearsal.run("llada-qkvb.chat", seed=21, root=root)
    gap = res["checks"]["logit_gap_max"]
    assert res["correct"] is correct, gap
    assert (gap["value"] <= gap["limit"]) is correct
    assert res["checks"]["schedule_violations"]["value"] == 0
    assert _digest(cb) == added
