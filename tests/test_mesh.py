"""Sharded paths on the virtual 8-device CPU mesh (conftest forces cpu).

Validates that stream-sharded SRTP and the psum mixer produce outputs
byte-identical to the single-device kernels — the multi-chip design's
correctness contract (SURVEY §2.7).
"""

import numpy as np
import jax
import pytest

from libjitsi_tpu.conference.mixer import mix_minus
from libjitsi_tpu.mesh import (
    make_media_mesh,
    sharded_mix_minus,
    sharded_srtp_protect,
)
from libjitsi_tpu.transform.srtp import kernel
from libjitsi_tpu.kernels.aes import expand_key
from libjitsi_tpu.kernels.sha1 import hmac_precompute


def _protect_args(batch, width, rng):
    rk = np.stack([
        expand_key(rng.integers(0, 256, 16, dtype=np.uint8).tobytes())
        for _ in range(batch)])
    mid = np.stack([
        hmac_precompute(rng.integers(0, 256, 20, dtype=np.uint8).tobytes())
        for _ in range(batch)])
    data = rng.integers(0, 256, (batch, width), dtype=np.uint8)
    length = np.full(batch, width - 16, dtype=np.int32)
    payload_off = np.full(batch, 12, dtype=np.int32)
    iv = rng.integers(0, 256, (batch, 16), dtype=np.uint8)
    roc = np.zeros(batch, dtype=np.uint32)
    return data, length, payload_off, rk, iv, mid, roc


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_media_mesh(jax.devices()[:8])


@pytest.mark.slow
def test_sharded_protect_matches_single(mesh):
    rng = np.random.default_rng(5)
    args = _protect_args(32, 128, rng)
    want_d, want_l = kernel.srtp_protect(*args, tag_len=10, encrypt=True)
    got_d, got_l = sharded_srtp_protect(mesh, tag_len=10)(*args)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))


def test_sharded_mix_matches_single(mesh):
    rng = np.random.default_rng(6)
    pcm = rng.integers(-5000, 5000, (32, 160)).astype(np.int16)
    active = rng.random(32) < 0.8
    want_out, want_lvl = mix_minus(pcm, active)
    got_out, got_lvl = sharded_mix_minus(mesh)(pcm, active)
    np.testing.assert_array_equal(np.asarray(got_out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(got_lvl), np.asarray(want_lvl))


@pytest.mark.slow   # ~75s: the full dryrun incl. pipelined/F8/GCM parity
# + async-overlap steps; the mesh parity tests below cover the core tier
def test_dryrun_multichip():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_entry_compiles():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    d, l = out
    assert d.shape == args[0].shape
    assert np.all(np.asarray(l) == args[1] + 10)


def test_multihost_2d_mesh_mixer():
    """(dcn, streams) mesh: conference psum over ICI then DCN."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from libjitsi_tpu.mesh import make_multihost_mesh, sharded_mix_minus_2d

    mesh = make_multihost_mesh(2, jax.devices()[:8])  # 2 "hosts" x 4 chips
    assert mesh.shape == {"dcn": 2, "streams": 4}
    rng = np.random.default_rng(9)
    pcm = rng.integers(-3000, 3000, (32, 64)).astype(np.int16)
    active = np.ones(32, dtype=bool)
    out, lvl = sharded_mix_minus_2d(mesh)(pcm, active)
    want, want_lvl = mix_minus(pcm, active)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(lvl), np.asarray(want_lvl))


def test_sharded_bridge_mix_matches_host(mesh):
    from libjitsi_tpu.mesh import sharded_bridge_mix

    rng = np.random.default_rng(12)
    C, N, F = 16, 6, 96          # C divisible by the 8-device mesh
    pcm = rng.integers(-9000, 9000, (C, N, F)).astype(np.int16)
    active = rng.random((C, N)) < 0.8
    out, lvl = sharded_bridge_mix(mesh)(pcm, active)
    from libjitsi_tpu.conference import mix_minus_many

    want, want_lvl = mix_minus_many(pcm, active)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(lvl), np.asarray(want_lvl))


def test_sharded_gcm_fanout_matches_single_device():
    """Receiver legs sharded over the mesh seal identically to the
    single-device grouped kernel (zero collectives — leg-parallel)."""
    import jax

    from libjitsi_tpu.kernels.gcm import gcm_protect_fanout
    from libjitsi_tpu.mesh import make_media_mesh, sharded_gcm_fanout

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    rng = np.random.default_rng(17)
    G, Pk, W = 16, 4, 128                # 2 legs per device
    rks = rng.integers(0, 256, (G, 11, 16), dtype=np.uint8)
    gms = rng.integers(0, 2, (G, 128, 128), dtype=np.int8)
    data = rng.integers(0, 256, (Pk, W), dtype=np.uint8)
    length = np.full(Pk, 100, np.int32)
    iv = rng.integers(0, 256, (G, Pk, 12), dtype=np.uint8)

    mesh = make_media_mesh(jax.devices()[:8])
    out_s, len_s = sharded_gcm_fanout(mesh)(data, length, rks, gms, iv)
    out_1, len_1 = gcm_protect_fanout(data, length, rks, gms, iv,
                                      aad_const=12)
    assert np.array_equal(np.asarray(out_s), np.asarray(out_1))
    assert np.array_equal(np.asarray(len_s), np.asarray(len_1))
