"""Whole-iteration token packing: kernels, model, engine, plan, budget.

The padded paths (``serve_refresh`` / ``serve_reuse`` / ``decode_tokens``)
are the correctness oracles throughout — every packed stage must agree on
random ragged batches and the packed engine must never fall back to a
pow2-padded dispatch for any stage (Refresh, Reuse, or the logit stage).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.configs import ARCHS, reduced
from repro.configs.base import ServeConfig
from repro.core.engine import Engine
from repro.core.request import State
from repro.core.scheduler import PhaseMultiplexedScheduler
from repro.kernels import ops, ref
from repro.kernels.flash_varlen import PAD_SEG
from repro.models import backbone as BB
from repro.models import transformer as T

KEY = jax.random.PRNGKey(11)

SERVE = ServeConfig(max_num_batched_tokens=512, max_num_logits=64,
                    block_size=8, steps_per_block=8, max_seq_len=128,
                    max_slots=8, max_refresh_per_iter=2,
                    selection="head", scheduler="phase", logit_mode="chunked",
                    varlen_pack=True, token_bucket=64)

# reduced per-family configs exercised by the packed/padded agreement tests
# (≥2 model families; moe capacity is made non-dropping so padded-batch pad
# rows cannot perturb expert routing of real tokens)
FAMS = {
    "llada-8b": {},
    "phi3.5-moe-42b-a6.6b": {"capacity_factor": 4.0},
    "gemma2-27b": {},
}


def _ragged_stream(lens, max_seq_len, vocab, seed=0, bucket=64):
    """Build padded-batch and packed-stream views of one ragged batch."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    toks = [rng.integers(0, vocab - 1, L).astype(np.int32) for L in lens]
    tok_pad = np.zeros((B, max_seq_len), np.int32)
    valid_pad = np.zeros((B, max_seq_len), bool)
    for j, t in enumerate(toks):
        tok_pad[j, : len(t)] = t
        valid_pad[j, : len(t)] = True
    t_real = int(sum(lens))
    tp = -(-t_real // bucket) * bucket
    flat = np.zeros(tp, np.int32)
    pos = np.zeros(tp, np.int32)
    seg = np.full(tp, PAD_SEG, np.int32)
    val = np.zeros(tp, bool)
    cu = np.full(B, max(0, tp - 1), np.int32)
    sl = np.zeros(B, np.int32)
    off = 0
    for j, t in enumerate(toks):
        L = len(t)
        flat[off: off + L] = t
        pos[off: off + L] = np.arange(L)
        seg[off: off + L] = j
        val[off: off + L] = True
        cu[j] = off
        sl[j] = L
        off += L
    return tok_pad, valid_pad, flat, pos, seg, val, cu, sl


# ---------------------------------------------------------------------------
# kernel: ragged flash attention vs the full-mask oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("softcap,window,is_local", [
    (0.0, 0, False), (25.0, 0, False), (0.0, 8, True)])
def test_flash_varlen_matches_ref(softcap, window, is_local):
    rng = np.random.default_rng(3)
    lens = rng.integers(5, 40, size=4)
    t_real = int(lens.sum())
    tp = -(-t_real // 64) * 64
    seg = np.full(tp, PAD_SEG, np.int32)
    pos = np.zeros(tp, np.int32)
    valid = np.zeros(tp, bool)
    off = 0
    for i, L in enumerate(lens):
        seg[off: off + L] = i
        pos[off: off + L] = np.arange(L)
        valid[off: off + L] = True
        off += L
    H, K, dh = 4, 2, 16
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (tp, H, dh))
    k = jax.random.normal(kk, (tp, K, dh))
    v = jax.random.normal(kv, (tp, K, dh))
    out = ops.flash_varlen_attention(
        q, k, v, seg_ids=jnp.asarray(seg), positions=jnp.asarray(pos),
        kv_valid=jnp.asarray(valid), softcap=softcap, window=window,
        is_local=is_local, q_tile=16, kv_tile=32)
    out_r = ref.varlen_attention(
        q, k, v, jnp.asarray(seg), jnp.asarray(pos), jnp.asarray(valid),
        softcap=softcap, window=window, is_local=is_local)
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(out_r)[valid], atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999), q_tile=st.sampled_from([8, 16, 64]),
       kv_tile=st.sampled_from([16, 32, 64]))
def test_flash_varlen_tile_invariance(seed, q_tile, kv_tile):
    """Online accumulation + tile-skip must be invariant to tiling."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 30, size=int(rng.integers(1, 5)))
    t_real = int(lens.sum())
    tp = -(-t_real // 64) * 64
    seg = np.full(tp, PAD_SEG, np.int32)
    pos = np.zeros(tp, np.int32)
    valid = np.zeros(tp, bool)
    off = 0
    for i, L in enumerate(lens):
        seg[off: off + L] = i
        pos[off: off + L] = np.arange(L)
        valid[off: off + L] = True
        off += L
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (tp, 4, 8))
    k = jax.random.normal(kk, (tp, 2, 8))
    v = jax.random.normal(kv, (tp, 2, 8))
    kw = dict(seg_ids=jnp.asarray(seg), positions=jnp.asarray(pos),
              kv_valid=jnp.asarray(valid))
    a = ops.flash_varlen_attention(q, k, v, q_tile=q_tile, kv_tile=kv_tile,
                                   **kw)
    b = ops.flash_varlen_attention(q, k, v, q_tile=64, kv_tile=64, **kw)
    np.testing.assert_allclose(np.asarray(a)[valid], np.asarray(b)[valid],
                               atol=1e-5)


# ---------------------------------------------------------------------------
# model: packed vs padded serve_refresh agreement (the oracle contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", list(FAMS))
@pytest.mark.parametrize("use_kernel", [False, True])
def test_packed_refresh_matches_padded(arch, use_kernel):
    cfg = reduced(ARCHS[arch], **FAMS[arch])
    params = BB.init_params(cfg, KEY)
    # the padded oracle always runs the chunked-jnp path; the packed side
    # optionally dispatches the Pallas varlen kernel (kernel-vs-jnp check)
    ctx = T.ServeContext(block_size=8, retain=24, q_chunk=32, max_seq_len=96)
    ctx_pk = dataclasses.replace(ctx, use_flash_refresh=use_kernel)
    rng = np.random.default_rng(7)
    for trial in range(2):
        lens = [int(x) for x in rng.integers(12, 96, size=3)]
        bstarts = np.array([max(0, L - 8 - int(rng.integers(0, max(1, L - 8))))
                            for L in lens], np.int32)
        bstarts = (bstarts // 8) * 8
        tok_pad, valid_pad, flat, pos, seg, val, cu, sl = _ragged_stream(
            lens, 96, cfg.vocab_size, seed=trial)
        out_pad = BB.serve_refresh(
            params, cfg, jnp.asarray(tok_pad), jnp.asarray(bstarts), ctx,
            token_valid=jnp.asarray(valid_pad))
        out_pk = BB.serve_refresh_packed(
            params, cfg, jnp.asarray(flat), jnp.asarray(pos),
            jnp.asarray(seg), jnp.asarray(val), jnp.asarray(cu),
            jnp.asarray(sl), jnp.asarray(bstarts), ctx_pk)
        np.testing.assert_allclose(
            np.asarray(out_pk.block_hidden, np.float32),
            np.asarray(out_pad.block_hidden, np.float32), atol=1e-4)
        # retained caches must agree too (pre-pool masking keeps selection
        # independent of batch composition; rare fp-tie flips aside, the
        # overwhelming majority of retained positions must match)
        pos_eq = (np.asarray(out_pk.cache.pos)
                  == np.asarray(out_pad.cache.pos)).mean()
        assert pos_eq > 0.99, pos_eq


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 500), n=st.integers(1, 4))
def test_packed_refresh_property_random_ragged(seed, n):
    """Property form: any ragged batch, any block offsets, dense family."""
    cfg = reduced(ARCHS["llada-8b"])
    params = BB.init_params(cfg, jax.random.PRNGKey(1))
    ctx = T.ServeContext(block_size=8, retain=16, q_chunk=32, max_seq_len=64)
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.integers(9, 64, size=n)]
    bstarts = np.array([int(rng.integers(0, L - 8)) for L in lens], np.int32)
    tok_pad, valid_pad, flat, pos, seg, val, cu, sl = _ragged_stream(
        lens, 64, cfg.vocab_size, seed=seed, bucket=32)
    out_pad = BB.serve_refresh(
        params, cfg, jnp.asarray(tok_pad), jnp.asarray(bstarts), ctx,
        token_valid=jnp.asarray(valid_pad))
    out_pk = BB.serve_refresh_packed(
        params, cfg, jnp.asarray(flat), jnp.asarray(pos), jnp.asarray(seg),
        jnp.asarray(val), jnp.asarray(cu), jnp.asarray(sl),
        jnp.asarray(bstarts), ctx)
    np.testing.assert_allclose(
        np.asarray(out_pk.block_hidden, np.float32),
        np.asarray(out_pad.block_hidden, np.float32), atol=1e-4)


def test_varlen_score_chunking_invariance():
    """The jnp score fallback must chunk ANY stream length (sentinel-padded
    to whole chunks) without changing scores."""
    from repro.models.sparse_select import head_scores_varlen
    R, Sb, H, K, dh, T = 2, 4, 4, 2, 8, 40   # 40 % 16 != 0
    ks = jax.random.split(KEY, 2)
    q = jax.random.normal(ks[0], (R, Sb, H, dh))
    kf = jax.random.normal(ks[1], (T, K, dh))
    seg = np.repeat(np.arange(R, dtype=np.int32), [24, 16])
    a = head_scores_varlen(q, kf, jnp.asarray(seg), kernel_size=3,
                           s_chunk=16)
    b = head_scores_varlen(q, kf, jnp.asarray(seg), kernel_size=3,
                           s_chunk=4096)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_token_bucket_round_never_beats_pow2_oracle():
    """The packed bucket may never exceed the pow2 oracle bucket, even for
    non-pow2 token buckets (the CI waste gate's invariant)."""
    from repro.core.budgeting import pow2_bucket, token_bucket_round
    for bucket in (1, 3, 8, 24, 32, 100, 128):
        for n in range(1, 300):
            r = token_bucket_round(n, bucket)
            assert n <= r <= pow2_bucket(n), (n, bucket, r)


def test_selection_ignores_foreign_neighbours():
    """A request's retained KV set must not depend on what it is packed
    with: rows past seq_len in the per-request gather view belong to the
    NEXT request, and the score max-pool must not leak their relevance into
    valid boundary tokens (scores are masked to -inf pre-pool)."""
    from repro.models.sparse_select import select_and_pack
    B, Sb, K, G, S, dh = 1, 4, 2, 2, 24, 8
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Sb, K * G, dh))
    kf = jax.random.normal(ks[1], (B, S, K, dh))
    vf = jax.random.normal(ks[2], (B, S, K, dh))
    valid = jnp.zeros((B, S), bool).at[:, :16].set(True)   # tokens ≥16 foreign
    excl = ~valid
    kw = dict(retain=8, kernel_size=3, mode="head", exclude=excl,
              token_valid=valid)
    p1 = select_and_pack(q, kf, vf, **kw)
    # replace the foreign tail with adversarially-huge keys: selection of the
    # valid region must be bit-identical
    kf2 = kf.at[:, 16:].set(100.0 * jnp.abs(kf[:, 16:]) + 50.0)
    p2 = select_and_pack(q, kf2, vf, **kw)
    assert np.array_equal(np.asarray(p1.pos), np.asarray(p2.pos))
    assert np.array_equal(np.asarray(p1.valid), np.asarray(p2.valid))


def test_windowed_stream_attention_matches_plain():
    """The windowed jnp fallback (KV window = q_chunk + 2L) must be exact:
    build a stream long enough that windows genuinely truncate."""
    cfg = reduced(ARCHS["llada-8b"])
    rng = np.random.default_rng(9)
    S_max, c = 24, 16
    lens, total = [], 0
    while total < 200:
        L = int(rng.integers(6, S_max + 1))
        lens.append(L)
        total += L
    tp = -(-total // c) * c
    seg = np.full(tp, PAD_SEG, np.int32)
    pos = np.zeros(tp, np.int32)
    val = np.zeros(tp, bool)
    off = 0
    for i, L in enumerate(lens):
        seg[off: off + L] = i
        pos[off: off + L] = np.arange(L)
        val[off: off + L] = True
        off += L
    H, K, dh = 4, 2, 16
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (1, tp, H, dh))
    k = jax.random.normal(kk, (1, tp, K, dh))
    v = jax.random.normal(kv, (1, tp, K, dh))
    serve = T.ServeContext(block_size=8, retain=8, q_chunk=c,
                           max_seq_len=S_max)
    assert c + 2 * S_max < tp   # windows actually truncate
    win = T._attend_packed_stream(
        q, k, v, jnp.asarray(pos)[None], jnp.asarray(seg)[None],
        jnp.asarray(val)[None], cfg, jnp.asarray(False), serve)
    ref_out = ref.varlen_attention(
        q[0], k[0], v[0], jnp.asarray(seg), jnp.asarray(pos),
        jnp.asarray(val))
    np.testing.assert_allclose(np.asarray(win)[0][val],
                               np.asarray(ref_out)[val], atol=2e-5)


# modality-frontend (vlm/audio) packed-vs-padded agreement lives in
# tests/test_frontend_packing.py — no family rejects the packed path anymore.


# ---------------------------------------------------------------------------
# SSM/hybrid: segment-reset varlen scan vs the padded oracle
# ---------------------------------------------------------------------------

SCAN_FAMS = ("mamba2-130m", "zamba2-7b")


@pytest.mark.parametrize("arch", SCAN_FAMS)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_packed_refresh_matches_padded_scan_families(arch, use_kernel):
    """serve_refresh_packed for SSM/hybrid: block hidden AND the captured
    serving cache (recurrent state + conv history + hybrid packed KV
    positions) must reproduce the padded oracle on ragged batches."""
    cfg = reduced(ARCHS[arch])
    params = BB.init_params(cfg, KEY)
    ctx = T.ServeContext(block_size=8, retain=24, q_chunk=32, max_seq_len=96)
    ctx_pk = dataclasses.replace(ctx, use_flash_kernel=use_kernel)
    rng = np.random.default_rng(17)
    for trial in range(2):
        lens = [int(x) for x in rng.integers(12, 96, size=3)]
        bstarts = np.array([((L - 8) // 8) * 8 for L in lens], np.int32)
        tok_pad, valid_pad, flat, pos, seg, val, cu, sl = _ragged_stream(
            lens, 96, cfg.vocab_size, seed=trial)
        out_pad = BB.serve_refresh(
            params, cfg, jnp.asarray(tok_pad), jnp.asarray(bstarts), ctx,
            token_valid=jnp.asarray(valid_pad))
        out_pk = BB.serve_refresh_packed(
            params, cfg, jnp.asarray(flat), jnp.asarray(pos),
            jnp.asarray(seg), jnp.asarray(val), jnp.asarray(cu),
            jnp.asarray(sl), jnp.asarray(bstarts), ctx_pk)
        np.testing.assert_allclose(
            np.asarray(out_pk.block_hidden, np.float32),
            np.asarray(out_pad.block_hidden, np.float32), atol=1e-4)
        c_pk, c_pad = out_pk.cache, out_pad.cache
        st_pk = c_pk.state if arch == "mamba2-130m" else c_pk.ssm_state
        st_pad = c_pad.state if arch == "mamba2-130m" else c_pad.ssm_state
        np.testing.assert_allclose(np.asarray(st_pk), np.asarray(st_pad),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(c_pk.conv, np.float32),
            np.asarray(c_pad.conv, np.float32), atol=1e-5)
        if arch == "zamba2-7b":
            pos_eq = (np.asarray(c_pk.kv.pos)
                      == np.asarray(c_pad.kv.pos)).mean()
            assert pos_eq > 0.99, pos_eq


@pytest.mark.parametrize("arch", SCAN_FAMS)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_packed_reuse_matches_padded_scan_families(arch, use_kernel):
    """serve_reuse_packed for SSM/hybrid must reproduce the padded Reuse
    oracle on the same refreshed caches (hybrid exercises the causal flat
    cross-attention dispatch under use_kernel)."""
    cfg = reduced(ARCHS[arch])
    params = BB.init_params(cfg, KEY)
    ctx = T.ServeContext(block_size=8, retain=24, q_chunk=32, max_seq_len=96)
    ctx_pk = dataclasses.replace(ctx, use_flash_kernel=use_kernel)
    rng = np.random.default_rng(23)
    lens = [int(x) for x in rng.integers(16, 96, size=3)]
    bstarts = np.array([((L - 8) // 8) * 8 for L in lens], np.int32)
    cache, btok, bpos = _refresh_cache(cfg, params, ctx, lens, bstarts)
    h_pad = BB.serve_reuse(params, cfg, jnp.asarray(btok),
                           jnp.asarray(bpos), cache, ctx)
    h_pk = BB.serve_reuse_packed(
        params, cfg, jnp.asarray(btok.reshape(-1)),
        jnp.asarray(bpos.reshape(-1)), cache, ctx_pk)
    np.testing.assert_allclose(
        np.asarray(h_pk, np.float32).reshape(len(lens), 8, -1),
        np.asarray(h_pad, np.float32), atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 500), n=st.integers(1, 4))
def test_varlen_ssd_scan_segment_reset_property(seed, n):
    """cu_seqlens segment-reset property: the packed scan over a stream of n
    concatenated requests equals n independent per-request scans — outputs
    AND captured states at arbitrary rows (vs the sequential recurrence)."""
    from repro.models.ssm import varlen_ssd_scan
    H, P, N = 3, 4, 5
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.integers(3, 20, size=n)]
    T_real = sum(lens)
    tp = -(-T_real // 16) * 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    xh = jax.random.normal(ks[0], (tp, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (tp, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (tp, N))
    Cm = jax.random.normal(ks[4], (tp, N))
    reset = np.zeros(tp, bool)
    cu, off = [], 0
    for L in lens:
        reset[off] = True
        cu.append(off)
        off += L
    reset[off:] = True                       # bucket padding self-resets
    cap_off = [int(rng.integers(0, L)) for L in lens]
    cap_rows = np.array([c + o for c, o in zip(cu, cap_off)], np.int32)
    y, st = varlen_ssd_scan(xh, dt, A, Bm, Cm, jnp.asarray(reset),
                            jnp.asarray(cap_rows))
    # oracle: independent sequential recurrence per request
    for j, (c, L) in enumerate(zip(cu, lens)):
        state = np.zeros((H, P, N), np.float32)
        for t in range(c, c + L):
            a = np.exp(np.asarray(dt[t]) * np.asarray(A))
            state = state * a[:, None, None] + np.einsum(
                "h,n,hp->hpn", np.asarray(dt[t]), np.asarray(Bm[t]),
                np.asarray(xh[t]))
            yt = np.einsum("n,hpn->hp", np.asarray(Cm[t]), state)
            np.testing.assert_allclose(np.asarray(y[t]), yt, atol=2e-4)
            if t == cap_rows[j]:
                np.testing.assert_allclose(np.asarray(st[j]), state,
                                           atol=2e-4)


def test_ssm_segment_scan_kernel_matches_fallback():
    """The Pallas segment-scan kernel against the associative-scan fallback,
    invariant to the chunk tiling (the in-kernel capture accumulation must
    not depend on which chunk owns a capture row)."""
    from repro.kernels import ops
    from repro.models.ssm import varlen_ssd_scan
    H, P, N, tp = 4, 4, 6, 96
    rng = np.random.default_rng(2)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    xh = jax.random.normal(ks[0], (tp, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (tp, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (tp, N))
    Cm = jax.random.normal(ks[4], (tp, N))
    reset = np.zeros(tp, bool)
    for off in (0, 17, 40, 77):
        reset[off] = True
    cap_rows = np.array([-1, 16, 39, 55, 95], np.int32)
    y_ref, st_ref = varlen_ssd_scan(xh, dt, A, Bm, Cm, jnp.asarray(reset),
                                    jnp.asarray(cap_rows))
    for chunk in (8, 16, 32, 48, 96):
        y, st = ops.ssm_segment_scan(xh, dt, A, Bm, Cm, jnp.asarray(reset),
                                     jnp.asarray(cap_rows), chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref, np.float32),
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                                   atol=2e-4)
        assert not np.asarray(st[0]).any()   # -1 capture row = zero state


# ---------------------------------------------------------------------------
# engine: the packed fast path never issues a padded refresh
# ---------------------------------------------------------------------------

def _serve_engine(serve, n=5, seed=0, arch="llada-8b", forbid_padded=False):
    cfg = reduced(ARCHS[arch])
    eng = Engine(cfg, serve, seed=seed)
    if forbid_padded:
        def _boom(chunk):
            raise AssertionError("padded [B, max_seq_len] refresh on the "
                                 "packed path")
        eng._run_refresh = _boom
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(8, 40))),
                       gen_len=16, arrival=0.0, rid=i)
            for i in range(n)]
    stats = eng.run()
    return eng, reqs, stats


def test_engine_packed_no_padded_refresh_call():
    eng, reqs, stats = _serve_engine(SERVE, forbid_padded=True)
    assert all(r.state == State.FINISHED for r in reqs)
    assert all((r.output_tokens() != eng.mask_id).all() for r in reqs)
    assert stats.padded_refresh_calls == 0
    assert stats.packed_refresh_calls > 0
    # executed tokens within one token-bucket of Σ total_len per dispatch
    assert stats.refresh_tokens_exec >= stats.refresh_tokens_real
    assert stats.refresh_tokens_exec < stats.refresh_tokens_real + \
        SERVE.token_bucket * stats.packed_refresh_calls


def test_engine_packed_padded_same_totals():
    _, r_pk, s_pk = _serve_engine(SERVE, seed=3)
    _, r_pd, s_pd = _serve_engine(
        dataclasses.replace(SERVE, varlen_pack=False), seed=3)
    assert s_pk.committed_tokens == s_pd.committed_tokens
    assert all(r.state == State.FINISHED for r in r_pk + r_pd)
    # the padded oracle pays strictly more executed tokens on ragged work
    assert s_pk.refresh_tokens_exec < s_pd.refresh_tokens_exec
    assert s_pk.refresh_tokens_real == s_pd.refresh_tokens_real


def test_engine_packed_flash_kernel_path():
    serve = dataclasses.replace(SERVE, use_flash_kernel=True)
    _, reqs, stats = _serve_engine(serve, n=3, forbid_padded=True)
    assert all(r.state == State.FINISHED for r in reqs)
    assert stats.packed_refresh_calls > 0


@pytest.mark.parametrize("arch", SCAN_FAMS)
def test_engine_scan_families_run_packed(arch):
    """Acceptance: under varlen_pack an SSM and a hybrid config serve
    Refresh AND Reuse with zero pow2-padded dispatches."""
    cfg = reduced(ARCHS[arch])
    eng = Engine(cfg, SERVE, seed=0)

    def _boom(*a, **k):
        raise AssertionError("pow2-padded dispatch on the packed path")

    eng._run_refresh = _boom
    eng._run_reuse = _boom
    eng._decode_fn = _boom
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(8, 40))),
                       gen_len=16, arrival=0.0, rid=i) for i in range(3)]
    stats = eng.run()
    assert all(r.state == State.FINISHED for r in reqs)
    assert all((r.output_tokens() != eng.mask_id).all() for r in reqs)
    assert stats.packed_refresh_calls > 0 and stats.padded_refresh_calls == 0
    assert stats.packed_reuse_calls > 0 and stats.padded_reuse_calls == 0


@pytest.mark.parametrize("arch", SCAN_FAMS)
def test_engine_scan_families_packed_padded_same_totals(arch):
    _, r_pk, s_pk = _serve_engine(SERVE, n=4, seed=3, arch=arch)
    _, r_pd, s_pd = _serve_engine(
        dataclasses.replace(SERVE, varlen_pack=False), n=4, seed=3, arch=arch)
    assert s_pk.committed_tokens == s_pd.committed_tokens
    assert all(r.state == State.FINISHED for r in r_pk + r_pd)
    assert s_pk.refresh_tokens_real == s_pd.refresh_tokens_real
    # the packed scan pays (at most) one token bucket over the real count;
    # the padded oracle pays the pow2 rectangle
    assert s_pk.refresh_tokens_exec < s_pd.refresh_tokens_exec
    assert s_pk.refresh_waste <= s_pd.refresh_waste
    assert s_pk.reuse_waste <= s_pd.reuse_waste


def test_engine_fused_refresh_single_dispatch():
    """The packed engine launches ONE fused refresh dispatch per iteration
    even when the refresh set spans several max_refresh_per_iter chunks.
    The request-level scheduler admits oversized refresh sets (the phase
    scheduler caps them at refresh_slots), so it is what exercises a
    multi-chunk layout."""
    serve = dataclasses.replace(SERVE, scheduler="request")
    eng, reqs, stats = _serve_engine(serve, n=6, seed=5, forbid_padded=True)
    assert all(r.state == State.FINISHED for r in reqs)
    n_refresh_iters = sum(1 for it in stats.iter_log if it["n_refresh"] > 0)
    assert stats.packed_refresh_calls == n_refresh_iters
    assert any(it["n_refresh"] > serve.max_refresh_per_iter
               for it in stats.iter_log), \
        "workload never exceeded one chunk — fusion untested"


def test_engine_zero_refresh_cap_serves_to_completion():
    """Acceptance: max_refresh_per_iter=0 (documented 0-means-unlimited)
    must serve to completion instead of deferring every Refresh forever."""
    serve0 = dataclasses.replace(SERVE, max_refresh_per_iter=0)
    eng, reqs, stats = _serve_engine(serve0, n=5, forbid_padded=True)
    assert all(r.state == State.FINISHED for r in reqs)
    assert stats.packed_refresh_calls > 0


# ---------------------------------------------------------------------------
# plan: packed layout + query-token invariant
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 10), budget=st.integers(64, 512),
       seed=st.integers(0, 99))
def test_packed_plan_layout_and_invariant(n, budget, seed):
    from repro.core.request import Request
    cfg = dataclasses.replace(SERVE, max_num_batched_tokens=budget)
    sched = PhaseMultiplexedScheduler(cfg)
    rng = np.random.default_rng(seed)
    for i in range(n):
        plen = int(rng.integers(4, 48))
        if plen + 16 + 8 > cfg.max_seq_len or plen + 16 > budget:
            plen = 8
        sched.submit(Request(rid=i, prompt=np.zeros(plen, np.int32),
                             gen_len=16, arrival=0.0, cfg=cfg, mask_id=255))
    for _ in range(3):
        plan = sched.plan(now=1e9)
        cu = plan.refresh_cu_seqlens()
        assert cu[0] == 0 and cu[-1] == plan.refresh_total_tokens
        assert np.all(np.diff(cu) > 0) or len(plan.refresh) == 0
        assert list(np.diff(cu)) == plan.refresh_token_counts
        # query-token invariant holds for the packed layout too
        assert plan.refresh_total_tokens <= plan.query_tokens <= budget
        for r in plan.refresh + plan.reuse:
            blk = r.block_tokens().copy()
            blk[:] = 1
            r.advance(blk, now=0.0)
            if r.state == State.FINISHED:
                sched.finish(r)


# ---------------------------------------------------------------------------
# budgeting: packed activation accounting buys KV slots
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Reuse phase: packed stream vs the padded oracle (whole-iteration packing)
# ---------------------------------------------------------------------------

def _refresh_cache(cfg, params, ctx, lens, bstarts, seed=0):
    rng = np.random.default_rng(seed)
    R = len(lens)
    S = ctx.max_seq_len
    toks = np.zeros((R, S), np.int32)
    valid = np.zeros((R, S), bool)
    for j, L in enumerate(lens):
        toks[j, :L] = rng.integers(0, cfg.vocab_size - 1, L)
        valid[j, :L] = True
    out = BB.serve_refresh(params, cfg, jnp.asarray(toks),
                           jnp.asarray(bstarts), ctx,
                           token_valid=jnp.asarray(valid))
    btok = np.stack([toks[j, bstarts[j]: bstarts[j] + ctx.block_size]
                     for j in range(R)])
    bpos = np.stack([np.arange(b, b + ctx.block_size)
                     for b in bstarts]).astype(np.int32)
    return out.cache, btok, bpos


@pytest.mark.parametrize("arch", list(FAMS))
@pytest.mark.parametrize("use_kernel", [False, True])
def test_packed_reuse_matches_padded(arch, use_kernel):
    """serve_reuse_packed must reproduce the padded Reuse oracle on the same
    gathered caches — jnp fallback bit-comparable, cross kernel to fp
    tolerance (gemma2 exercises softcap + alternating local windows)."""
    cfg = reduced(ARCHS[arch], **FAMS[arch])
    params = BB.init_params(cfg, KEY)
    ctx = T.ServeContext(block_size=8, retain=24, q_chunk=32, max_seq_len=96)
    ctx_pk = dataclasses.replace(ctx, use_flash_kernel=use_kernel)
    rng = np.random.default_rng(13)
    for trial in range(2):
        lens = [int(x) for x in rng.integers(16, 96, size=3)]
        bstarts = np.array([((L - 8) // 8) * 8 for L in lens], np.int32)
        cache, btok, bpos = _refresh_cache(cfg, params, ctx, lens, bstarts,
                                           seed=trial)
        h_pad = BB.serve_reuse(params, cfg, jnp.asarray(btok),
                               jnp.asarray(bpos), cache, ctx)
        h_pk = BB.serve_reuse_packed(
            params, cfg, jnp.asarray(btok.reshape(-1)),
            jnp.asarray(bpos.reshape(-1)), cache, ctx_pk)
        np.testing.assert_allclose(
            np.asarray(h_pk, np.float32).reshape(len(lens), 8, -1),
            np.asarray(h_pad, np.float32), atol=2e-4)


def test_cross_kernel_matches_masked_reference():
    """The cross-attention varlen kernel (packed queries vs per-segment KV,
    per-head KV positions/validity) against a full-mask jnp reference."""
    rng = np.random.default_rng(5)
    R, Sb, Cr = 4, 8, 16
    H, K, dh = 4, 2, 16
    G = H // K
    Tq, Tkv = R * Sb, R * (Cr + Sb)
    q_seg = np.repeat(np.arange(R, dtype=np.int32), Sb)
    kv_seg = np.repeat(np.arange(R, dtype=np.int32), Cr + Sb)
    # engine-coherent geometry: each request's block queries are contiguous
    # positions, its cache positions precede the block, and the live-block
    # KV tail mirrors the query positions (so no query row is ever fully
    # masked, even under a sliding window — the engine invariant)
    bstarts = rng.integers(0, 48, R).astype(np.int32)
    q_pos = np.concatenate([b + np.arange(Sb, dtype=np.int32)
                            for b in bstarts])
    kv_pos = np.zeros((K, Tkv), np.int32)
    kv_valid = rng.random((K, Tkv)) > 0.25
    kv_valid = kv_valid.reshape(K, R, Cr + Sb)
    kv_pos = kv_pos.reshape(K, R, Cr + Sb)
    for j, b in enumerate(bstarts):
        kv_pos[:, j, :Cr] = rng.integers(0, max(1, b) + Sb, (K, Cr))
        kv_pos[:, j, Cr:] = b + np.arange(Sb)
    kv_valid[:, :, Cr:] = True
    kv_pos = kv_pos.reshape(K, Tkv)
    kv_valid = kv_valid.reshape(K, Tkv)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (Tq, H, dh))
    k = jax.random.normal(ks[1], (K, Tkv, dh))
    v = jax.random.normal(ks[2], (K, Tkv, dh))
    for softcap, window, is_local in [(0.0, 0, False), (20.0, 8, True)]:
        out = ops.flash_varlen_cross_attention(
            q, k, v, q_seg=jnp.asarray(q_seg), q_pos=jnp.asarray(q_pos),
            kv_seg=jnp.asarray(kv_seg), kv_pos=jnp.asarray(kv_pos),
            kv_valid=jnp.asarray(kv_valid), window=window,
            is_local=is_local, softcap=softcap, q_tile=8, kv_tile=16)
        # reference: per-head full [Tq, Tkv] masked softmax
        qg = np.asarray(q).reshape(Tq, K, G, dh)
        z = np.einsum("tkgd,ksd->kgts", qg, np.asarray(k)) * dh ** -0.5
        if softcap:
            z = softcap * np.tanh(z / softcap)
        ok = (q_seg[:, None] == kv_seg[None, :])[None] & kv_valid[:, None, :]
        if window:
            dist = np.abs(q_pos[None, :, None] - kv_pos[:, None, :])
            ok = ok & np.where(is_local, dist <= window, True)
        z = np.where(ok[:, None], z, -1e30)
        p = jax.nn.softmax(jnp.asarray(z), axis=-1)
        ref_out = np.einsum("kgts,ksd->tkgd", np.asarray(p), np.asarray(v))
        np.testing.assert_allclose(
            np.asarray(out), ref_out.reshape(Tq, H, dh), atol=2e-5)


# (G, softcap, window, is_local, Cr, kv_tile): GQA, softcap, a sliding
# window on a local and on a global layer, a retained axis that is no
# multiple of 128, and one cut into three tiles
POOL_CASES = {
    "g1": (1, 0.0, 0, False, 24, 1024),
    "g4-softcap": (4, 30.0, 0, False, 40, 1024),
    "window-local": (1, 0.0, 8, True, 24, 1024),
    "window-global-g4": (4, 0.0, 8, False, 24, 1024),
    "tiled": (1, 0.0, 0, False, 96, 32),
}


@pytest.mark.parametrize("case", list(POOL_CASES))
def test_pool_kernel_matches_reference(case):
    """The slot-table kernel reads each request's retained K/V in place
    from a pool and must equal a float32 cross-attention over [retained ;
    live block]: ragged R with padding rows on the scratch slot (their
    output is zero), a slot table out of order that repeats a slot (a
    shared prefix), per-head valid masks that leave a head two rows."""
    G, softcap, window, is_local, Cr, kv_tile = POOL_CASES[case]
    seed = list(POOL_CASES).index(case)
    rng = np.random.default_rng(seed)
    Lyr, S, K, dh, Sb = 3, 6, 2, 16, 8
    H, R, n_live, scratch, layer = K * G, 5, 3, S - 1, 1
    rows = np.array([3, 0, 3, scratch, scratch], np.int32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool_k = np.asarray(jax.random.normal(ks[0], (Lyr, S, K, Cr, dh)))
    pool_v = np.asarray(jax.random.normal(ks[1], (Lyr, S, K, Cr, dh)))
    q = np.asarray(jax.random.normal(ks[2], (R * Sb, H, dh)))
    kb = np.asarray(jax.random.normal(ks[3], (R * Sb, K, dh)))
    vb = np.asarray(jax.random.normal(ks[4], (R * Sb, K, dh)))
    pos = rng.integers(0, 64, (Lyr, S, K, Cr)).astype(np.int32)
    valid = rng.random((Lyr, S, K, Cr)) > 0.3
    valid[:, :, 1, 2:] = False
    valid[:, scratch] = False
    bstarts = rng.integers(0, 56, R)
    q_pos = (bstarts[:, None] + np.arange(Sb)).reshape(-1).astype(np.int32)
    kv_pos = ops.retained_positions(jnp.asarray(pos), jnp.asarray(valid),
                                    jnp.asarray(rows), kv_tile=kv_tile)
    assert kv_pos.shape[3] == (3 if case == "tiled" else 1)
    out = np.asarray(ops.flash_varlen_pool_attention(
        jnp.asarray(q), jnp.asarray(kb), jnp.asarray(vb),
        jnp.asarray(pool_k), jnp.asarray(pool_v), kv_pos,
        rows=jnp.asarray(rows), n_live=jnp.asarray([n_live], jnp.int32),
        layer=jnp.int32(layer), q_pos=jnp.asarray(q_pos), window=window,
        is_local=is_local, softcap=softcap))
    for r in range(R):
        got = out[r * Sb:(r + 1) * Sb]
        if r >= n_live:
            assert not got.any()
            continue
        blk = slice(r * Sb, (r + 1) * Sb)
        k_all = np.concatenate([pool_k[layer, rows[r]],
                                kb[blk].transpose(1, 0, 2)], axis=1)
        v_all = np.concatenate([pool_v[layer, rows[r]],
                                vb[blk].transpose(1, 0, 2)], axis=1)
        kp = np.concatenate([pos[layer, rows[r]],
                             np.broadcast_to(q_pos[blk], (K, Sb))], axis=1)
        ok = np.concatenate([valid[layer, rows[r]], np.ones((K, Sb), bool)],
                            axis=1)[:, None, :]            # [K, 1, T]
        if window:
            dist = np.abs(q_pos[blk][None, :, None] - kp[:, None, :])
            ok = ok & np.where(is_local, dist <= window, True)
        z = np.einsum("tkgd,ksd->kgts", q[blk].reshape(Sb, K, G, dh),
                      k_all) * dh ** -0.5
        if softcap:
            z = softcap * np.tanh(z / softcap)
        z = np.where(ok[:, None], z, -np.inf)
        p = np.exp(z - z.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("kgts,ksd->tkgd", p, v_all).reshape(Sb, H, dh)
        np.testing.assert_allclose(got, want, atol=2e-5)


# ---------------------------------------------------------------------------
# logit stage: packed decode vs the padded oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llada-8b", "gemma2-27b"])
@pytest.mark.parametrize("mode", ["chunked", "fused", "monolithic"])
def test_packed_decode_matches_padded(arch, mode):
    """decode_tokens_packed over a token-bucketed stream with a validity
    mask: exact ids and confidence-to-tolerance agreement with the oracle on
    the real rows, zeros on the padding rows (gemma2 = tied embeddings +
    final softcap)."""
    from repro.models import lm_head as LM
    cfg = reduced(ARCHS[arch])
    params = BB.init_params(cfg, KEY)
    rng = np.random.default_rng(4)
    for trial in range(3):
        N = int(rng.integers(3, 80))
        Nx = N + int(rng.integers(0, 40))
        h = jax.random.normal(jax.random.PRNGKey(trial), (Nx, cfg.d_model),
                              jnp.dtype(cfg.dtype))
        valid = jnp.arange(Nx) < N
        ids_p, conf_p = LM.decode_tokens_packed(
            params["embed"], cfg, h, valid, max_num_logits=16, mode=mode,
            vocab_tile=64)
        ids_o, conf_o = LM.decode_tokens(
            params["embed"], cfg, h[:N], max_num_logits=16, mode=mode,
            vocab_tile=64)
        assert np.array_equal(np.asarray(ids_p[:N]), np.asarray(ids_o))
        np.testing.assert_allclose(np.asarray(conf_p[:N]),
                                   np.asarray(conf_o), atol=2e-5)
        assert not np.asarray(ids_p[N:]).any()
        assert not np.asarray(conf_p[N:]).any()


# ---------------------------------------------------------------------------
# engine: the whole-iteration packed pipeline
# ---------------------------------------------------------------------------

def test_engine_packed_no_padded_reuse_or_decode():
    """Under varlen_pack no stage may fall back to a pow2 dispatch."""
    cfg = reduced(ARCHS["llada-8b"])
    eng = Engine(cfg, SERVE, seed=0)

    def _boom(*a, **k):
        raise AssertionError("pow2-padded dispatch on the packed path")

    eng._run_refresh = _boom
    eng._run_reuse = _boom
    eng._decode_fn = _boom
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(8, 40))),
                       gen_len=16, arrival=0.0, rid=i) for i in range(5)]
    stats = eng.run()
    assert all(r.state == State.FINISHED for r in reqs)
    assert stats.packed_reuse_calls > 0 and stats.padded_reuse_calls == 0
    assert stats.logit_tokens_real > 0


def test_engine_whole_iteration_packed_accounting():
    """Acceptance: one full modeled-clock serve run reports per-iteration
    ``reuse_tokens_exec == R·block_size`` rounded only to the token bucket
    (exact below one bucket — never pow2) and ``logit_tokens_exec`` below
    the pow2 row bucket whenever the plan is ragged."""
    from repro.core.budgeting import pow2_bucket
    serve = dataclasses.replace(SERVE, token_bucket=32)
    cfg = reduced(ARCHS["llada-8b"])
    eng = Engine(cfg, serve, seed=7, clock="modeled")
    rng = np.random.default_rng(7)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(8, 60))),
                       gen_len=16, arrival=0.0, rid=i) for i in range(7)]
    stats = eng.run()
    assert all(r.state == State.FINISHED for r in reqs)
    Sb = serve.block_size
    rb = serve.token_bucket // Sb
    saw_ragged_logit = False
    for it in stats.iter_log:
        n = it["n_reuse"]
        if n:
            rp = n if n <= rb else -(-n // rb) * rb
            assert it["reuse_tokens_exec"] == rp * Sb, it
        nr = it["logit_tokens_real"]
        if nr:
            tb = serve.token_bucket
            expect = nr if nr <= tb else -(-nr // tb) * tb
            assert it["logit_tokens_exec"] == expect, it
            assert expect <= pow2_bucket(nr, lo=Sb), it
            if expect < pow2_bucket(nr, lo=Sb):
                # ragged plan: packed exec beats the pow2 row bucket
                saw_ragged_logit = True
    assert saw_ragged_logit
    assert stats.reuse_tokens_exec >= stats.reuse_tokens_real
    assert stats.logit_tokens_exec >= stats.logit_tokens_real


def test_engine_packed_waste_never_worse_than_padded():
    _, r_pk, s_pk = _serve_engine(SERVE, n=6, seed=11)
    _, r_pd, s_pd = _serve_engine(
        dataclasses.replace(SERVE, varlen_pack=False), n=6, seed=11)
    assert s_pk.committed_tokens == s_pd.committed_tokens
    assert s_pk.refresh_waste <= s_pd.refresh_waste
    assert s_pk.reuse_waste <= s_pd.reuse_waste
    assert s_pk.logit_waste <= s_pd.logit_waste
    assert s_pk.reuse_tokens_real == s_pd.reuse_tokens_real
    assert s_pk.logit_tokens_real == s_pd.logit_tokens_real


# ---------------------------------------------------------------------------
# plan: whole-iteration packed layout partitions the stream exactly
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 12), budget=st.integers(64, 512),
       cap=st.integers(1, 4), seed=st.integers(0, 99))
def test_whole_iteration_layout_partitions_stream(n, budget, cap, seed):
    """Property: for random plans, every stage's cu_seqlens partition its
    stream with no overlap and no gap, refresh chunks tile the plan-level
    stream, reuse segments are exactly block_size, and logit_tokens counts
    one block per scheduled request."""
    from repro.core.request import Request
    cfg = dataclasses.replace(SERVE, max_num_batched_tokens=budget)
    sched = PhaseMultiplexedScheduler(cfg)
    rng = np.random.default_rng(seed)
    for i in range(n):
        plen = int(rng.integers(4, 48))
        if plen + 16 + 8 > cfg.max_seq_len or plen + 16 > budget:
            plen = 8
        sched.submit(Request(rid=i, prompt=np.zeros(plen, np.int32),
                             gen_len=16, arrival=0.0, cfg=cfg, mask_id=255))
    for _ in range(4):
        plan = sched.plan(now=1e9)
        layout = plan.packed_layout(cap)
        # refresh chunks tile the plan-level stream
        off = 0
        plan_cu = plan.refresh_cu_seqlens()
        covered = []
        for seg in layout.refresh_chunks:
            cu = seg.cu_seqlens
            assert cu[0] == 0
            assert np.all(np.diff(cu) > 0)
            assert seg.token_counts == [r.total_len for r in seg.requests]
            for j in range(len(seg.requests)):
                covered.append((off + int(cu[j]), off + int(cu[j + 1])))
            off += seg.total_tokens
        assert off == plan.refresh_total_tokens == plan_cu[-1]
        # segments are contiguous, non-overlapping, gap-free
        for (a0, a1), (b0, b1) in zip(covered, covered[1:]):
            assert a1 == b0 and a0 < a1
        if layout.reuse:
            cu = layout.reuse.cu_seqlens
            assert list(np.diff(cu)) == [cfg.block_size] * len(plan.reuse)
        assert layout.logit_tokens == \
            (len(plan.refresh) + len(plan.reuse)) * cfg.block_size
        for r in plan.refresh + plan.reuse:
            blk = r.block_tokens().copy()
            blk[:] = 1
            r.advance(blk, now=0.0)
            if r.state == State.FINISHED:
                sched.finish(r)


def test_budgeting_packed_tokens_buy_slots():
    from repro.configs import get_config
    from repro.core.budgeting import max_exec_tokens, plan_memory
    cfg = get_config("llada-8b")
    base = ServeConfig(max_num_batched_tokens=4000, max_num_logits=2048,
                       max_seq_len=2048, max_slots=256, max_refresh_per_iter=4,
                       logit_mode="chunked")
    packed = dataclasses.replace(base, varlen_pack=True)
    assert max_exec_tokens(packed, cfg) < max_exec_tokens(base, cfg)
    # every family is billed by packed tokens now: the scan families
    # (segment-reset varlen scan) AND the modality-frontend archs
    # (frontend-prefix segments) — no padded reservation survives under
    # varlen_pack
    from repro.configs import get_config as _gc
    ssm_cfg = _gc("mamba2-130m")
    assert max_exec_tokens(packed, ssm_cfg) < max_exec_tokens(base, ssm_cfg)
    vlm_cfg = _gc("internvl2-76b")
    assert max_exec_tokens(packed, vlm_cfg) < max_exec_tokens(base, vlm_cfg)
    p_pad = plan_memory(cfg, base, 24 << 30)
    p_pk = plan_memory(cfg, packed, 24 << 30)
    assert p_pk.activation_bytes < p_pad.activation_bytes
    assert p_pk.max_slots >= p_pad.max_slots
    assert p_pk.kv_pool_bytes > p_pad.kv_pool_bytes


def test_budgeting_bills_reuse_and_logit_by_packed_tokens():
    """plan_memory's per-stage accounting mirrors the engine's real
    execution: Reuse and the logit stage are billed token-bucketed under
    varlen_pack, pow2-bucketed otherwise."""
    from repro.configs import get_config
    from repro.core.budgeting import (logit_exec_tokens, pow2_bucket,
                                      reuse_exec_tokens)
    cfg = get_config("llada-8b")
    base = ServeConfig(max_num_batched_tokens=4000, max_num_logits=2048,
                       max_seq_len=2048, max_slots=48,
                       logit_mode="monolithic")
    packed = dataclasses.replace(base, varlen_pack=True)
    # reuse: pow2(min(slots, budget // Sb)) vs token-bucket multiples
    # (48 slots: pow2 pays 64 blocks, the packed stream exactly 48)
    assert reuse_exec_tokens(base, cfg) == \
        pow2_bucket(base.max_slots) * base.block_size
    assert reuse_exec_tokens(packed, cfg) < reuse_exec_tokens(base, cfg)
    assert reuse_exec_tokens(packed, cfg) % packed.token_bucket == 0
    # every family packs its Reuse stream now — SSM and the frontend archs
    # included (the Reuse stream is text-only for vlm/audio too)
    ssm = get_config("mamba2-130m")
    assert reuse_exec_tokens(packed, ssm) < reuse_exec_tokens(base, ssm)
    vlm = get_config("internvl2-76b")
    assert reuse_exec_tokens(packed, vlm) < reuse_exec_tokens(base, vlm)
    # logit stage: ragged N → token-bucket rounding beats the pow2 bucket
    # (and the logit head packs for every family, SSM included)
    n = 2500
    assert logit_exec_tokens(base, n) == pow2_bucket(n, lo=base.block_size)
    assert logit_exec_tokens(packed, n) < logit_exec_tokens(base, n)
    from repro.core.budgeting import logit_activation_bytes
    assert logit_activation_bytes(cfg, packed, n) < \
        logit_activation_bytes(cfg, base, n)