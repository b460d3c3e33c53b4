"""The gather-free tower AES vs the table core, and the rule that picks.

The circuit is derived from GF((2^4)^2) algebra at import (and the
module asserts its full S-box truth table then); these tests pin the
assembled cipher, the nd wrapper the CTR/GCM call sites use, `get_core`'s
one input (the platform) and the `set_core` seam end-to-end through
`srtp_protect`.
"""

import contextlib
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from libjitsi_tpu.kernels import aes
from libjitsi_tpu.kernels.aes_bitsliced import (
    aes_encrypt_bitsliced_tower, aes_encrypt_bitsliced_tower_nd)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def unpinned_core(monkeypatch):
    """`get_core()` as a fresh process sees it: no `set_core` before."""
    monkeypatch.setattr(aes, "_CORE_NAME", None)


@pytest.mark.parametrize("backend,core", [("cpu", "table"),
                                          ("tpu", "bitsliced_tower")])
def test_get_core_by_platform(unpinned_core, monkeypatch, backend, core):
    import jax

    if backend == "cpu":
        assert jax.default_backend() == "cpu"       # conftest's platform
    else:
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert aes.get_core() == core


def test_get_core_unmoved_by_the_old_environment_switch():
    """`LIBJITSI_TPU_AES_CORE` was read at import: a process that
    imports `kernels.aes` afresh under it still answers by platform."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               LIBJITSI_TPU_AES_CORE="bitsliced")
    res = subprocess.run(
        [sys.executable, "-c",
         "from libjitsi_tpu.kernels import aes; print(aes.get_core())"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert res.returncode == 0, res.stderr[-500:]
    assert res.stdout.split() == ["table"]


@pytest.mark.parametrize("name", ["bitsliced", "pallas_bitsliced"])
def test_set_core_refuses_a_deleted_name(unpinned_core, name):
    with pytest.raises(ValueError, match="aes core must be one of"):
        aes.set_core(name)
    assert aes._CORE_NAME is None


@pytest.mark.slow          # set_core clears jax caches -> recompiles
def test_set_core_switches_srtp_protect_bit_identically(unpinned_core):
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    mk, ms = bytes(range(16)), bytes(range(40, 54))

    def protect():
        t = SrtpStreamTable(capacity=2)
        t.add_stream(0, mk, ms)
        b = rtp_header.build([b"core-check-%d" % i for i in range(4)],
                             [50 + i for i in range(4)], [0] * 4,
                             [0xC0DE] * 4, [96] * 4, stream=[0] * 4)
        return [t.protect_rtp(b).to_bytes(i) for i in range(4)]

    assert aes.get_core() == "table"
    want = protect()
    try:
        aes.set_core("bitsliced_tower")   # what an accelerator runs
        assert protect() == want
        aes.set_core("bitsliced32")       # kept, unselected (PR 29)
        assert protect() == want
    finally:
        aes.set_core("table")             # drop the tower's programs


@pytest.mark.slow   # two fresh packed-circuit compiles (~1-2 min cold)
def test_bitsliced32_packed_words_bit_exact():
    """The packed-word provider (32 blocks per uint32 word, per-block
    keys packed the same way) must match the table core bit for bit,
    including the non-multiple-of-32 pad path and AES-256."""
    rng = np.random.default_rng(9)
    from libjitsi_tpu.kernels.aes_bitsliced import aes_encrypt_bitsliced32

    for n, kl in ((33, 16), (64, 32)):
        rks = aes.expand_keys_batch(
            rng.integers(0, 256, (n, kl), dtype=np.uint8))
        blocks = rng.integers(0, 256, (n, 16), dtype=np.uint8)
        want = np.asarray(aes.aes_encrypt_table(rks, blocks))
        got = np.asarray(aes_encrypt_bitsliced32(rks, blocks))
        assert np.array_equal(got, want), (n, kl)


@contextlib.contextmanager
def _time_limit(seconds: int):
    """Fail the case, not the run: the alarm is delivered as soon as the
    compile it may be waiting in returns to Python."""
    def over(_sig, _frm):
        raise TimeoutError(f"over {seconds} s")
    was = signal.signal(signal.SIGALRM, over)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, was)


# FIPS-197 appendix C.1
_FIPS_KEY = bytes(range(16))
_FIPS_PT = bytes.fromhex("00112233445566778899aabbccddeeff")
_FIPS_CT = bytes.fromhex("69c4e0d86a7b0430d8cdb78070b4c55a")


@pytest.mark.parametrize("n,key_lens,nd_wrapper,limit_s", [
    (8, (16,), False, 90),     # tier 1: one AES-128 compile, ~7 s cold
    pytest.param(48, (16, 32), True, 900, marks=pytest.mark.slow),
])
def test_bitsliced_tower_sbox_and_provider_bit_exact(n, key_lens,
                                                     nd_wrapper, limit_s):
    """The composite-field (GF((2^4)^2)) provider must match the table
    core bit for bit (the tower parameters and basis-change matrices are
    derived+asserted at import; this pins the full cipher), and both
    the FIPS-197 vector in row 0."""
    rng = np.random.default_rng(5)
    with _time_limit(limit_s):
        for kl in key_lens:
            keys = rng.integers(0, 256, (n, kl), dtype=np.uint8)
            blocks = rng.integers(0, 256, (n, 16), dtype=np.uint8)
            if kl == 16:
                keys[0] = np.frombuffer(_FIPS_KEY, dtype=np.uint8)
                blocks[0] = np.frombuffer(_FIPS_PT, dtype=np.uint8)
            rks = aes.expand_keys_batch(keys)
            want = np.asarray(aes.aes_encrypt_table(rks, blocks))
            got = np.asarray(aes_encrypt_bitsliced_tower(rks, blocks))
            assert np.array_equal(got, want), (n, kl)
            if kl == 16:
                assert got[0].tobytes() == _FIPS_CT
        if nd_wrapper:
            # BROADCAST keys — the exact shape the CTR/GCM call sites
            # feed the accelerator's dispatch
            rks = aes.expand_keys_batch(
                rng.integers(0, 256, (6, 16), dtype=np.uint8))
            blocks = rng.integers(0, 256, (6, 3, 16), dtype=np.uint8)
            rk_b = np.broadcast_to(rks[:, None], (6, 3, 11, 16))
            want = np.asarray(aes.aes_encrypt_table(
                rks[:, None].repeat(3, 1).reshape(-1, 11, 16),
                blocks.reshape(-1, 16))).reshape(6, 3, 16)
            got = np.asarray(aes_encrypt_bitsliced_tower_nd(rk_b, blocks))
            assert np.array_equal(got, want)


def test_sbox_circuits_match_table_fast():
    """Both S-box circuits (addition chain, composite field) over all
    256 inputs, in plain numpy (no jit, no full-cipher compile): the
    module's own import-time check, which raises where a circuit and
    the table differ."""
    from libjitsi_tpu.kernels.aes_bitsliced import _self_check

    _self_check()
