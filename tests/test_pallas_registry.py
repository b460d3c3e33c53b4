"""Pallas kernel twins + provider registry (reference pattern: crypto.Aes
benchmarks AES providers at startup and installs the fastest)."""

import numpy as np
import pytest

from libjitsi_tpu.conference.mixer import AudioMixer, _mix_jit
from libjitsi_tpu.kernels import registry
from libjitsi_tpu.kernels.pallas_ops import mix_minus_pallas


def _rand_frame(n=32, f=960, seed=0):
    rng = np.random.default_rng(seed)
    pcm = rng.integers(-20000, 20000, (n, f)).astype(np.int16)
    active = rng.random(n) < 0.8
    active[1] = False
    pcm[2] = 0                      # silent-but-active row
    return pcm, active


@pytest.mark.parametrize("n,f", [
    (32, 960),      # one block: the whole frame
    (600, 960),     # two row tiles, the second padded
    (1536, 160),    # three full tiles of a narrow frame
])
def test_pallas_mixer_bit_identical_to_xla(n, f):
    pcm, active = _rand_frame(n, f)
    out_x, lvl_x = _mix_jit(pcm, active)
    out_p, lvl_p = mix_minus_pallas(pcm, active, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))
    np.testing.assert_array_equal(np.asarray(lvl_x), np.asarray(lvl_p))
    assert np.asarray(lvl_p)[1] == 127      # inactive -> silence level
    assert np.asarray(lvl_p)[2] == 127      # silent   -> silence level


def test_registry_selects_and_pins_a_provider():
    assert sorted(registry.providers("mix_minus")) == ["pallas", "xla"]
    registry.force("mix_minus", None)
    mixer = AudioMixer(capacity=16, frame_samples=960)
    for sid in range(4):
        mixer.add_participant(sid)
        mixer.push(sid, np.full(960, 100 * (sid + 1), np.int16))
    out, lvl = mixer.mix()
    total = sum(100 * (s + 1) for s in range(4))
    for sid in range(4):
        assert out[sid, 0] == total - 100 * (sid + 1)
    rep = registry.report()["mix_minus"]
    assert rep["choices"], "first call must have pinned a provider"
    assert all(len(t) == 2 for t in rep["timings_ms"].values()), \
        "both providers must have been timed"


def test_registry_force_each_provider_same_result():
    pcm, active = _rand_frame(seed=7)
    results = {}
    for prov in registry.providers("mix_minus"):
        registry.force("mix_minus", prov)
        try:
            out, lvl = registry.call("mix_minus", pcm, active)
            results[prov] = (np.asarray(out), np.asarray(lvl))
        finally:
            registry.force("mix_minus", None)
    a, b = results["xla"], results["pallas"]
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_registry_force_unknown_provider_rejected():
    with pytest.raises(KeyError):
        registry.force("mix_minus", "cuda")


def test_warmup_pins_before_first_tick_and_errors_are_recorded():
    registry.force("mix_minus", None)
    mixer = AudioMixer(capacity=8, frame_samples=960)   # warms in __init__
    sig_choices = registry.report()["mix_minus"]["choices"]
    assert any("(8, 960)" in k for k in sig_choices), sig_choices
    # a broken provider is excluded WITH a recorded reason, not silently
    def boom(pcm, active):
        raise RuntimeError("mosaic lowering failed")
    registry.register("mix_minus_err", "xla", _mix_jit)
    registry.register("mix_minus_err", "broken", boom)
    pcm, active = _rand_frame(n=8)
    out, lvl = registry.call("mix_minus_err", pcm, active)
    rep = registry.report()["mix_minus_err"]
    errs = list(rep["errors"].values())
    assert errs and "mosaic lowering failed" in str(errs[0])


def test_config_key_overrides_selection():
    import libjitsi_tpu
    libjitsi_tpu.init()
    cfg = libjitsi_tpu.configuration_service()
    registry.force("mix_minus", None)
    cfg.set("kernels.provider.mix_minus", "pallas")
    try:
        pcm, active = _rand_frame(n=8, seed=3)
        out, lvl = registry.call("mix_minus", pcm, active)
        # config forced pallas: no benchmarking entry for this signature
        out_x, lvl_x = _mix_jit(pcm, active)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_x))
    finally:
        cfg.set("kernels.provider.mix_minus", None)
