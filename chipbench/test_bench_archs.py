"""What the benchmark reads stays put when the code of an architecture
moves: each configuration's weights, its reference's gaps on one fixed
trajectory (and the fp8 control's) and its work counts, at the module's
rehearsal sizes, against values recorded before that code moved into
``archs/``. And a configuration's ``program`` may set any ``ModelConfig``
field: the program's sizes then equal the file's, as ``harness.build``
requires."""
import copy
import hashlib

import jax
import numpy as np
import pytest

from chipbench import config as C
from chipbench import spec as SP
from chipbench import weights as W
from chipbench import work
from chipbench.reference import Reference, Trajectory

SEED = 1234567
SERVE = dict(block_size=32, steps_per_block=8, refresh_interval=4,
             retain=16, kernel_size=3)

# Recorded at commit 1adfc34, where the architectures' code still lived in
# weights.py, reference.py and work.py (same sizes, seed and trajectory).
# The gaps are compared to 1e-6: XLA:CPU sums a float32 matmul in an order
# that follows its thread count, and on one core the recorded gaps moved
# by up to 3.6e-7; leaving out one leaf or one step moves them by far more.
PARENT = {
    "llada-8b-1chip": dict(
        params="beb3479ec7cb3b76b287a5b70bb7ea51"
               "a910a4c85729f13b00bb5cf10bebb136",
        flops={
            "refresh.rehearsal": 322322432,
            "refresh.full": 2150541295616,
            "reuse.rehearsal": 57671680,
            "reuse.full": 257295384576,
        },
        gaps=[
            0.94559747, 0.853678048, 1.05021083, 0.960416496, 0.772336602,
            1.10335028, 0.77341342, 0.897148728, 0.964028597, 0.779127955,
            0.80884701, 0.952832997, 0.711718023, 1.27526319, 0.815077722,
            0.797894955, 0.777962863, 0.933999836, 0.720229149, 0.461623847,
            0.774713874, 1.09887969, 0.873690665, 1.18248737, 0.4946118,
            0.99623841, 0.990579903, 1.1456151, 0.776180506, 0.609983325,
            0.568573654, 0.796298385, 0.591287971, 0.75637579, 0.940257728,
            0.677589893, 0.653840542, 1.0448246, 0.713803172, 0.464250118,
            0.901854813, 1.04053247, 0.788981378, 1.03874087, 1.12400854,
            0.707527459, 1.20201612, 0.759268045, 0.952986658, 0.973407805,
            0.903403044, 0.640021324, 1.01558208, 0.783796668, 0.6550892,
            1.23227859, 1.03436553, 0.813410342, 0.763900936, 0.668483555,
            1.02952051, 0.517106533, 0.544521272, 0.661275208
        ],
        gaps_fp8=[
            0, 0, 0.00119739771, 0.00264543295, 0, 0.0260214806, 0.025824368,
            0.0264710784, 0.0120462179, 0.0114719272, 0.0143818855,
            0.0121223927, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0.0230474472, 0,
            0.0126286149, 0.00894707441, 0.00602734089, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0.00805926323, 0.0058376193, 0.0118398666, 0.00836086273, 0, 0, 0,
            0
        ],
    ),
    "mamba2-130m": dict(
        params="66f62ba730217a0fee7af622be6fdd79"
               "368e0d2af1b5d5cc13e6866a0d93ae2c",
        flops={
            "refresh.rehearsal": 34226176,
            "refresh.full": 56528732160,
            "reuse.rehearsal": 4587520,
            "reuse.full": 8237481984,
        },
        gaps=[
            0.302939773, 0.601612806, 0.604996085, 0.491366982, 0.433510929,
            0.391878814, 0.545941055, 0.238415569, 0.463204145, 0.28802979,
            0.294385493, 0.588091671, 0.387839764, 0.48409459, 0.521749616,
            0.57997191, 0.419620752, 0.924934447, 0.628131628, 0.667286873,
            0.281437516, 0.429915279, 0.301861405, 0.404999435, 0.956471384,
            0.703438759, 0.400370419, 0.268966258, 0.251952171, 0.481092036,
            0.704378486, 0.418200672, 0.715150237, 0.573840559, 0.174119592,
            0.340169847, 0.426915318, 0.371191561, 0.668270886, 0.685547233,
            0.433175385, 0.557303011, 0.3240031, 0.36447522, 0.645739794,
            0.665713608, 0.587126017, 0.588350713, 0.509267569, 0.555094481,
            0.398246229, 0.342320323, 0.612156868, 0.76221621, 0.491487682,
            0.0876193047, 0.526502609, 0.545124412, 0.192197442, 0.12117663,
            0.787644506, 0.510727048, 0.51933068, 0.302445769
        ],
        gaps_fp8=[
            0, 0, 0, 0, 0.0105598867, 0, 0.00612485409, 0, 0, 0, 0, 0, 0,
            0.011451304, 0, 0, 0, 0, 0, 0, 0, 0.00220602751, 0, 0, 0, 0,
            0.00142985582, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0.0216543078, 0.000486671925, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0.00109151006
        ],
    ),
}


def _conf(name):
    conf = SP.load_json(SP.ROOT / "chipbench" / "configs" / f"{name}.json")
    return conf, SP.load_module(SP.ROOT / conf["arch_module"])


def _rehearsal_dims(name):
    conf, arch = _conf(name)
    cfg = C.model_config(conf, arch, **arch.REHEARSAL)
    return C.dims_of(cfg, conf, arch)


def _trajectory(mask, seed=5):
    """A prompt of 20 ids and two blocks of 32, four positions committed
    a step over eight steps, every id drawn from the seed."""
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, mask, 20).astype(np.int32)
    steps = []
    for _ in range(2):
        blk = np.full(32, mask, np.int32)
        order = rng.permutation(32)
        bs = []
        for s in range(8):
            pos = np.sort(order[4 * s:4 * s + 4])
            ids = rng.integers(0, mask, 4).astype(np.int32)
            bs.append((blk.copy(), pos, ids))
            blk[pos] = ids
        steps.append(bs)
    return Trajectory(0, prompt, 64, steps)


def _digest(tree):
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint8).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT))
def test_weights_match_the_recorded_digest(name):
    d = _rehearsal_dims(name)
    assert _digest(W.make_params(d, SEED)) == PARENT[name]["params"]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_reference_gaps_match_the_recorded_values(name):
    d = _rehearsal_dims(name)
    mask = d["vocab_size"] - 1
    ref = Reference(d, W.make_params(d, SEED), dict(SERVE, mask_id=mask))
    gaps, fp8 = ref.gaps(_trajectory(mask), (None, "fp8"))
    np.testing.assert_allclose(gaps, PARENT[name]["gaps"], rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(fp8, PARENT[name]["gaps_fp8"], rtol=0,
                               atol=1e-6)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_step_flops_match_the_recorded_values(name):
    conf, arch = _conf(name)
    sizes = {"rehearsal": _rehearsal_dims(name), "full": C.dims(conf, arch)}
    got = {f"{ph}.{k}": work.step_flops(d, ph, 300, 32, 64)
           for ph in ("refresh", "reuse") for k, d in sizes.items()}
    assert got == PARENT[name]["flops"]


@pytest.mark.parametrize("fields", [
    {"n_experts": 8, "experts_per_token": 2},
    {"qkv_bias": True},
], ids=["experts", "qkv_bias"])
def test_dims_of_carries_every_field_the_program_sets(fields):
    """Fields outside every module's ``FIELDS`` reach both sides, so the
    check in ``harness.build`` compares them and passes."""
    conf, arch = _conf("llada-8b-1chip")
    conf = copy.deepcopy(conf)
    conf["program"].update(fields)
    d = C.dims(conf, arch)
    assert {k: d[k] for k in fields} == fields
    cfg = C.model_config(conf, arch)
    assert C.dims_of(cfg, conf, arch) == d
    assert {k: getattr(cfg, k) for k in fields} == fields
