"""The control and the planted faults, on the CPU at the rehearsal size.

The control is the reference in fp8 (e4m3) put in the program's place; the
run's own check (``check.check_run``) must judge it not correct on every
seed while it judges the program correct. The fault tests drive a whole
run with the timed path broken underneath and see ``correct`` come out
false: a token altered where it is produced, a block committed in fewer
steps than the mix's schedule, and a program left to compile inside the
window."""
import numpy as np
import pytest

from chipbench import rehearsal


def test_control_fails_and_program_passes_on_three_seeds():
    from chipbench import check as CK
    from chipbench import harness as H
    from chipbench import spec as SP
    from chipbench import tools
    from chipbench import traffic as TR
    cell = rehearsal.shrink(SP.load_cell("llada-8b-1chip.chat"))
    b = H.build(cell, 31, hbm_bytes=rehearsal.HBM,
                cfg_overrides=cell.arch.REHEARSAL)
    b.times["t_proc0"] = 0.0
    for i, seed in enumerate((31, 32, 33)):
        if i:
            tools.swap_weights(b, seed)
        reqs = TR.generate(cell.traffic, cell.cell, 4.0, seed,
                           b.cfg.vocab_size, b.eng.mask_id)
        run = H.window(b, reqs, 4.0, drain=True)
        run.t_end = float("inf")
        prompts = {r.rid: q.prompt for q, r in zip(reqs, run.reqs)}
        res = CK.check_run(run, prompts, b.params, b.ref_serve(), seed,
                           cell.cell["check"], control="fp8")
        assert len(res["sampled"]) == 4
        program = res["checks"]["logit_gap_max"]["value"]
        control = res["control"]["checks"]["logit_gap_max"]["value"]
        assert res["correct"] is True, (seed, program)
        assert res["control"]["correct"] is False, (seed, control)
        assert program < rehearsal.GAP_LIMIT < control, (seed, program,
                                                         control)


def test_altered_token_fails_the_check(monkeypatch):
    from repro.core import diffusion
    orig = diffusion.commit_tokens

    def altered(block_tokens, ids, conf, n_commit, mask_id):
        out = orig(block_tokens, ids, conf, n_commit, mask_id)
        new = np.nonzero((block_tokens == mask_id) & (out != mask_id))[0]
        if new.size:
            p = new[0]
            out[p] = (out[p] + 1) % mask_id
        return out

    monkeypatch.setattr(diffusion, "commit_tokens", altered)
    res = rehearsal.run("llada-8b-1chip.chat", seed=9)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", ["one_step_per_block", "double_commit"])
def test_schedule_fault_fails_the_check(monkeypatch, fault):
    """The engine commits a whole block at its first step, or twice the
    schedule's positions a step: each token is still the model's best, so
    only the schedule check can see it."""
    from repro.core import diffusion
    orig = diffusion.commit_count

    def faulty(n_masked, steps_remaining):
        if fault == "one_step_per_block":
            return n_masked
        return min(n_masked, 2 * orig(n_masked, steps_remaining))

    monkeypatch.setattr(diffusion, "commit_count", faulty)
    res = rehearsal.run("llada-8b-1chip.chat", seed=10)
    assert res["correct"] is False
    assert res["checks"]["schedule_violations"]["value"] > 0
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] <= gap["limit"]


def test_compile_inside_the_window_fails_the_check(monkeypatch):
    """Without the warm-up of the operations between stages, their first
    shapes compile inside the window, and the run is not correct. (The
    process's caches are cleared first: earlier runs in this process would
    have compiled those shapes already.)"""
    import jax
    from chipbench import harness as H
    jax.clear_caches()
    monkeypatch.setattr(H, "warm_eager_ops", lambda eng, serve: 0)
    res = rehearsal.run("llada-8b-1chip.chat", seed=12)
    assert res["checks"]["compiles_in_window"]["value"] > 0
    assert res["correct"] is False
