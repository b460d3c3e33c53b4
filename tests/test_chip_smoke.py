"""chip_smoke.py's contract, as far as a machine without the chip can
show it: no CPU mode, a wrong oracle row or a failing provider ends the
run with an exception (a non-zero exit), and the compile-cache rule.
The failures are induced by monkeypatching — the script has no option
that weakens a check."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from libjitsi_tpu.kernels import registry
from libjitsi_tpu.utils import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # never rebuild the native library under the test suite: other
    # workers have it mapped
    monkeypatch.setattr(mod, "build_native", lambda: None)
    return mod


def _no_phase(*_a, **_k):
    raise AssertionError("a phase ran")


def test_default_run_refuses_the_cpu_before_phase_a(smoke, monkeypatch):
    for name in ("phase_a", "phase_b", "phase_c", "phase_mesh"):
        monkeypatch.setattr(smoke, name, _no_phase)
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "not 'tpu'" in str(exc.value.code)


def test_rehearsal_says_ok_false(smoke, monkeypatch, capsys):
    for name in ("phase_a", "phase_b", "phase_c"):
        monkeypatch.setattr(smoke, name, lambda *a, **k: None)
    assert smoke.main(["--rows", "16"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == ('{"ok": false, "device": {"platform": "cpu", '
                    '"kind": "cpu", "count": %d}}' % len(jax.devices()))


def test_oracle_agrees_then_a_wrong_row_ends_the_run(smoke, monkeypatch):
    from libjitsi_tpu.transform.srtp import SrtpProfile

    # the real oracle agrees with the device path (else the failure
    # below would prove nothing) ...
    smoke._crypto_roundtrip("cm", SrtpProfile.AES_CM_128_HMAC_SHA1_80,
                            smoke.protect_oracle, rows=16, seed=5)
    # ... and one wrong byte in one oracle row is an exception out of
    # main, i.e. a non-zero exit
    good = smoke.protect_oracle

    def off_by_one(mk, ms, pkt, index, tag_len=10):
        out = bytearray(good(mk, ms, pkt, index, tag_len))
        if pkt[11] == 7:                    # ssrc 0x100007: row 7
            out[20] ^= 1
        return bytes(out)

    monkeypatch.setattr(smoke, "protect_oracle", off_by_one)
    monkeypatch.setattr(smoke, "phase_b", _no_phase)
    with pytest.raises(AssertionError, match="oracle at row 7"):
        smoke.main(["--rows", "16"])


def test_a_raising_provider_ends_the_run(smoke, monkeypatch):
    def boom(pcm, active):
        raise RuntimeError("mosaic lowering failed")

    op = registry._OPS["mix_minus"]
    monkeypatch.setattr(smoke, "phase_a", lambda *a, **k: None)
    # interpret-mode Pallas AES is minutes of CPU compile; the provider
    # race inside the product's AudioMixer is what this test is about
    monkeypatch.setattr(smoke, "_check_pallas_twins",
                        lambda *a, **k: None)
    monkeypatch.setattr(smoke, "phase_c", _no_phase)
    registry.force("mix_minus", None)
    registry.register("mix_minus", "boom", boom)
    try:
        with pytest.raises(AssertionError, match="provider errors"):
            smoke.main(["--rows", "16"])
    finally:
        del op.providers["boom"]
        op.errors.clear()
        op.choice.clear()
        op.timings.clear()


def test_select_reraises_off_the_cpu(monkeypatch):
    """On the chip a provider's failure stops the program; the CPU
    tier records it and carries on (test_pallas_registry covers that
    side)."""
    def boom(x):
        raise RuntimeError("refused")

    registry.register("smoke_probe", "fine", lambda x: x)
    registry.register("smoke_probe", "boom", boom)
    try:
        monkeypatch.setattr(registry.jax, "default_backend",
                            lambda: "tpu")
        with pytest.raises(RuntimeError, match="refused"):
            registry.call("smoke_probe", np.zeros(4, np.int32))
        errs = registry.report()["smoke_probe"]["errors"]
        assert "refused" in str(errs)
    finally:
        del registry._OPS["smoke_probe"]


def test_compile_cache_rule(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set nothing is set in code (JAX
    read the variable itself at import); unset, the directory is the
    fixed `<checkout>/.jax_cache`."""
    was = jax.config.jax_compilation_cache_dir
    try:
        # as at interpreter start with the variable exported
        env_dir = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert not os.path.exists(env_dir)      # JAX makes it, not us

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(_ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_concurrently_runs_all_and_raises_first():
    ran = []

    def ok(i):
        return lambda: ran.append(i)

    def bad():
        raise ValueError("thunk failed")

    compile_cache.compile_concurrently([])
    compile_cache.compile_concurrently([ok(0)])
    compile_cache.compile_concurrently([ok(1), ok(2), ok(3)])
    assert sorted(ran) == [0, 1, 2, 3]
    with pytest.raises(ValueError, match="thunk failed"):
        compile_cache.compile_concurrently([ok(4), bad, ok(5)])
    assert {4, 5} <= set(ran)                   # the others finished


def test_assert_spread_reads_the_buffers(smoke):
    """`--chips 4`'s placement check: a row-sharded table passes and
    reports each device's bytes; state that sits on one device, or a
    table with no device arrays, fails."""
    from libjitsi_tpu.mesh import ShardedSrtpTable, make_media_mesh

    rows, n_dev = 64, 4
    rng = np.random.default_rng(3)
    tab = ShardedSrtpTable(rows, make_media_mesh(jax.devices()[:n_dev]))
    tab.add_streams(np.arange(rows),
                    rng.integers(0, 256, (rows, 16), dtype=np.uint8),
                    rng.integers(0, 256, (rows, 14), dtype=np.uint8))
    arrays = tab._sharded_device("rtp")
    held = smoke._assert_spread("table", arrays, n_dev)
    assert sorted(held) == [d.id for d in jax.devices()[:n_dev]]
    assert len(set(held.values())) == 1 and min(held.values()) > 0
    assert sum(held.values()) == sum(a.nbytes for a in arrays)
    with pytest.raises(AssertionError, match="1 device"):
        smoke._assert_spread("one", [jax.device_put(np.zeros((rows, 4)))],
                             n_dev)
    with pytest.raises(AssertionError, match="no device arrays"):
        smoke._assert_spread("empty", (), n_dev)
