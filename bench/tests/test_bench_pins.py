"""What the two proven cells read, pinned to the values the harness gave
before configuration files could describe their own tables: the ring's
bytes at full size, the reference's readings at smoke size and the counts
of one step's work at full size."""
import hashlib

import numpy as np
import pytest

from bench import feed, harness
from bench.tests.helpers import smoke_spec, spec_of

RINGS = {
    ("rm1.nockpt", 7):
        "3f5313ee0371d33c8a17fb11f7adeb47c9b2cba5149c67e5c16a5bc03f815f8c",
    ("rm1.nockpt", 2**33 + 5):
        "6bd37a261d39c0d31513ed06b750bb9ebcf986aa7f8f90cb153cc33b6f410e18",
    ("rm3.nockpt", 7):
        "a5d502e1d39edf7db3ffd5ae8eb0efcda514fcc863fcb7fcc5aaadfd42f09377",
    ("rm3.nockpt", 2**33 + 5):
        "de4fb999614a101a8a2ea9d44bd2b0374a12c05b29aff0a093232249b7c0b58b",
}

# seed 7, ring batch 0: unique rows, (FLOPs, bytes) of the step, FLOPs a
# sample
WORK = {"rm1.nockpt": (165093, (26254046924.0, 362287380), 101717888.0),
        "rm3.nockpt": (50784, (65336922508.0, 851980948), 253229056.0)}

# smoke size, seed 11: losses, first gradient norms, changes after three
# steps
READINGS = {
    "rm1.nockpt": {
        "losses": [2.2667253017425537, 7.931484222412109, 4.624285697937012],
        "grad_norms": {
            "bottom.0.b": 0.0012861653231084347,
            "bottom.0.w": 0.004553326405584812,
            "bottom.1.b": 0.0026464187540113926,
            "bottom.1.w": 0.009406588040292263,
            "top.0.b": 0.0011505699949339032, "top.0.w": 0.9532647132873535,
            "top.1.b": 0.0025883102789521217, "top.1.w": 0.3019278049468994,
            "table": 12.879535675048828},
        "change_norms": {
            "bottom.0.b": 0.016399899497628212,
            "bottom.0.w": 0.0595964752137661,
            "bottom.1.b": 0.010723568499088287,
            "bottom.1.w": 0.08705957978963852, "table": 1.3483707904815674,
            "top.0.b": 0.008293038234114647, "top.0.w": 0.16416317224502563,
            "top.1.b": 0.0007567598368041217,
            "top.1.w": 0.009735649451613426}},
    "rm3.nockpt": {
        "losses": [0.6185747385025024, 0.7828084230422974,
                   0.7535067796707153],
        "grad_norms": {
            "bottom.0.b": 0.0037299066316336393,
            "bottom.0.w": 0.009638020768761635,
            "bottom.1.b": 0.008792686276137829,
            "bottom.1.w": 0.024323366582393646,
            "top.0.b": 0.013681215234100819, "top.0.w": 0.945809543132782,
            "top.1.b": 0.018534300848841667, "top.1.w": 0.3227039575576782,
            "table": 1.0987768173217773},
        "change_norms": {
            "bottom.0.b": 0.016079522669315338,
            "bottom.0.w": 0.054684828966856,
            "bottom.1.b": 0.010033313184976578,
            "bottom.1.w": 0.08553820103406906, "table": 0.11520014703273773,
            "top.0.b": 0.00979661475867033, "top.0.w": 0.17044943571090698,
            "top.1.b": 0.0005558428820222616,
            "top.1.w": 0.010491118766367435}},
}


def ring_sha256(ring: list[dict]) -> str:
    h = hashlib.sha256()
    for batch in ring:
        for k in sorted(batch):
            a = np.ascontiguousarray(batch[k])
            h.update(f"{k}:{a.dtype.str}:{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell,seed", sorted(RINGS), ids=str)
def test_ring_bytes_are_pinned(cell, seed):
    spec = spec_of(cell)
    ring = feed.make_ring(spec.config["sizes"], spec.traffic, seed)
    assert ring_sha256(ring) == RINGS[cell, seed]


@pytest.mark.parametrize("cell", sorted(WORK))
def test_step_work_is_pinned(cell):
    import jax  # noqa: F401  numpy learns bfloat16 from it
    spec = spec_of(cell)
    sizes = spec.config["sizes"]
    unique, work, per_sample = WORK[cell]
    ring = feed.make_ring(sizes, spec.traffic, 7)
    assert feed.unique_rows(sizes, ring[0]) == unique
    assert spec.counts.step_work(sizes, unique) == work
    assert spec.counts.model_flops_per_sample(sizes) == per_sample


@pytest.mark.parametrize("cell", sorted(READINGS))
def test_reference_readings_are_pinned(cell):
    spec = smoke_spec(cell)
    ring = feed.make_ring(spec.config["sizes"], spec.traffic, 11)
    got = harness.reference_readings(spec, 11, ring)
    want = READINGS[cell]
    assert set(got) == set(want)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    for part in ("grad_norms", "change_norms"):
        assert set(got[part]) == set(want[part])
        for leaf, v in want[part].items():
            assert got[part][leaf] == pytest.approx(v, rel=1e-5), leaf
