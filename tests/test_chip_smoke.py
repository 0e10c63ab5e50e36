"""CPU rehearsal of ``chip_smoke.py``: its serve-and-compare function at a
reduced size in bf16 with the kernels interpreted, and the refusal of
``main`` to run anywhere but on a TPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402


def test_serve_and_compare_reduced_bf16():
    """The packed path in bf16 — Refresh, Reuse and the fused logit stage,
    kernels on — serves every request and agrees with the padded oracle."""
    cfg = reduced(get_config("llada-8b-1chip"), dtype="bfloat16")
    res = chip_smoke.serve_and_compare(cfg, n_requests=6, hbm_bytes=16 << 30)
    assert chip_smoke.serve_failures(res, oracle=True) == []
    assert res["finished"] == res["submitted"] == 6
    assert res["committed_tokens"] == 6 * chip_smoke.GEN_BLOCKS * \
        chip_smoke.BLOCK
    assert 0 < res["first"]["logit_rel_l2"] <= chip_smoke.logit_tol(
        cfg.n_layers)
    assert res["compiles_warmup"] > 0


def test_serve_failures_names_each_broken_check():
    res = dict(kernels_active=False, packed_refresh_calls=1,
               packed_reuse_calls=0, padded_calls=0, all_finished=True,
               finished=2, submitted=2, conserved=True, outputs_valid=True,
               n_layers=16, first=dict(logits_finite=True, logit_rel_l2=1.0,
                                       fused_conf_max_abs=0.0,
                                       fused_id_gap_max=0.0))
    bad = chip_smoke.serve_failures(res, oracle=True)
    assert len(bad) == 3, bad
    assert any("kernels_active" in b for b in bad)
    assert any("Reuse" in b for b in bad)
    assert any("rel L2" in b for b in bad)


def test_main_refuses_without_tpu(monkeypatch, tmp_path, capsys):
    # keep the helper from pointing this process's cache into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err
