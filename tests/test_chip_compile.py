"""Ask the v5e compiler, nothing attached.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described, not present (`topologies.get_topology_desc`).
These tests compile the served path's programs at served shapes — what
a 10,240-packet tick of 172-byte audio lands in after `bucket_by_size`
— so a kernel the chip would refuse (a block off the tiling, too much
VMEM, a program over HBM) fails here, at no chip time.  Nothing runs:
a compile that passes is not a chip run (`chip_smoke.py` is).

All of it lives in this one file, and the topology is described inside
a module-scoped fixture: only one process may hold libtpu, the suite
runs under several xdist workers, and each worker imports every test
file — so nothing here may touch the topology at import, `parametrize`
or `skipif` time.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from libjitsi_tpu.core.packet import (CLASS_HEADROOM, LENGTH_CLASSES,
                                      _round_rows)
from libjitsi_tpu.kernels import aes as aes_mod

CAP = 10_240                                   # installed streams
ROWS = _round_rows(CAP)                        # 12,288: 3 x top class
WIDTH = LENGTH_CLASSES[0] + CLASS_HEADROOM     # 224: the audio class


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip — the next run
    would warn and compile again.  Off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tower_core():
    """The core `get_core()` picks off the CPU; on this CPU it picks
    `table`.  Read at trace time, so set around each lowering and
    restored."""
    was = aes_mod._CORE_NAME
    aes_mod.set_core("bitsliced_tower")
    yield
    aes_mod._CORE_NAME = was
    jax.clear_caches()


def _on(sharding):
    """shape, dtype -> the abstract argument placed by `sharding`."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _cm_args(one_chip, rows=ROWS, width=WIDTH):
    s = _on(one_chip)
    return (s((CAP, 11, 16), jnp.uint8), s((CAP, 2, 5), jnp.uint32),
            s((rows,), jnp.int32), s((rows, width), jnp.uint8),
            s((rows,), jnp.int32), s((rows,), jnp.int32),
            s((rows, 16), jnp.uint8), s((rows,), jnp.uint32))


def _fits(compiled, limit=16 << 30):
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < limit, f"{total} bytes on a 16 GB chip"


# Tier 1 keeps one CM program at the full 12,288 rows (the donated
# ingest twin, which only an accelerator runs) and the other direction
# at the 4,096 class; the remaining twins are `slow` — each big compile
# is a minute of several cores beside the loopback tests.
@pytest.mark.parametrize("fn_name,rows", [
    ("_protect_rtp_dev", 4096),
    ("_unprotect_rtp_dev_donated", ROWS),
    pytest.param("_protect_rtp_dev", ROWS, marks=pytest.mark.slow),
    pytest.param("_unprotect_rtp_dev", 4096, marks=pytest.mark.slow),
])
def test_srtp_cm_served_shape(one_chip, no_persistent_cache, tower_core,
                              fn_name, rows):
    from libjitsi_tpu.transform.srtp import context as ctx
    _fits(getattr(ctx, fn_name).lower(
        *_cm_args(one_chip, rows=rows), tag_len=10, encrypt=True,
        off_const=12).compile())


# The two CM calls the served tick makes, on one packed plane each
# (core/staging.py) at the top warmed row class; the donated plane comes
# back in the same shape, so the compiler may write over it.  The
# fan-out has ONE form a shape (its payload offset is a word of the
# plane); its full-MTU width is `slow`.
@pytest.mark.parametrize("module,fn_name,width,static", [
    ("libjitsi_tpu.sfu.translator", "_fanout_protect", WIDTH, {}),
    ("libjitsi_tpu.transform.srtp.context",
     "_unprotect_rtp_packed_donated", WIDTH, {"off_const": 12}),
    pytest.param("libjitsi_tpu.sfu.translator", "_fanout_protect", 1536,
                 {}, marks=pytest.mark.slow),
])
def test_packed_cm_served_shape(one_chip, no_persistent_cache, tower_core,
                                module, fn_name, width, static):
    import importlib

    from libjitsi_tpu.core import staging

    s = _on(one_chip)
    c = getattr(importlib.import_module(module), fn_name).lower(
        s((CAP, 11, 16), jnp.uint8), s((CAP, 2, 5), jnp.uint32),
        s((4096, width + staging.TAIL), jnp.uint8), tag_len=10,
        encrypt=True, **static).compile()
    _fits(c)
    assert "input_output_alias" in c.as_text()    # the donation took


def test_gcm_grouped_protect_served_shape(one_chip, no_persistent_cache,
                                          tower_core):
    from libjitsi_tpu.transform.srtp import context as ctx

    s = _on(one_chip)
    rows, g, p = 4096, 1024, 4        # 4 packets per stream, pow2 grid
    _fits(ctx._protect_gcm_grouped_dev.lower(
        s((CAP, 11, 16), jnp.uint8), s((CAP, 128, 128), jnp.int8),
        s((rows,), jnp.int32), s((rows, WIDTH), jnp.uint8),
        s((rows,), jnp.int32), s((rows,), jnp.int32),
        s((rows, 12), jnp.uint8), s((g, p), jnp.int32),
        s((g,), jnp.int32), s((rows,), jnp.int32),
        aad_const=12).compile())


# The two GCM programs the served tick makes (per-row GHASH, the form
# `context._gcm_form_grid` picks inside the row classes), each on one
# packed plane `[rows, WIDTH + TAIL]` (core/staging.py): the fan-out at
# the top warmed row class (one form: the AAD length is a word of the
# plane), the unprotect at the class 584 uplink packets pad to.  Each
# donates its plane.
@pytest.mark.parametrize("module,fn_name,rows,static", [
    ("libjitsi_tpu.sfu.translator", "_fanout_protect_gcm", 4096, {}),
    ("libjitsi_tpu.transform.srtp.context", "_unprotect_gcm_dev_donated",
     1024, {"aad_const": 12}),
])
def test_gcm_per_row_served_shape(one_chip, no_persistent_cache,
                                  tower_core, module, fn_name, rows,
                                  static):
    import importlib

    from libjitsi_tpu.core import staging

    s = _on(one_chip)
    c = getattr(importlib.import_module(module), fn_name).lower(
        s((CAP, 11, 16), jnp.uint8), s((CAP, 128, 128), jnp.int8),
        s((rows, WIDTH + staging.TAIL), jnp.uint8), **static).compile()
    _fits(c)
    assert "input_output_alias" in c.as_text()    # the donation took


# The fan-out's own 512-row class (`core/packet.py:FANOUT_ROW_CLASSES`;
# the ladder warms it beside the 1,024-row rung): the same two
# functions at `[512, WIDTH + TAIL]`, under the names the benchmark's
# readers look for.
@pytest.mark.parametrize("suite", ["cm", "gcm"])
def test_fanout_512_row_class_served_shape(one_chip, no_persistent_cache,
                                           tower_core, suite):
    from libjitsi_tpu.core import staging
    from libjitsi_tpu.core.packet import FANOUT_ROW_CLASSES
    from libjitsi_tpu.sfu import translator

    assert 512 in FANOUT_ROW_CLASSES
    s = _on(one_chip)
    plane = s((512, WIDTH + staging.TAIL), jnp.uint8)
    if suite == "cm":
        lowered = translator._fanout_protect.lower(
            s((CAP, 11, 16), jnp.uint8), s((CAP, 2, 5), jnp.uint32),
            plane, tag_len=10, encrypt=True)
    else:
        lowered = translator._fanout_protect_gcm.lower(
            s((CAP, 11, 16), jnp.uint8), s((CAP, 128, 128), jnp.int8),
            plane)
    c = lowered.compile()
    _fits(c)
    text = c.as_text()
    assert "input_output_alias" in text           # the donation took
    assert ("jit__fanout_protect_gcm" if suite == "gcm"
            else "jit__fanout_protect") in text


@pytest.mark.slow
def test_keystream_fill_chunk(one_chip, no_persistent_cache, tower_core):
    from libjitsi_tpu.transform.srtp import keystream as ks

    s = _on(one_chip)
    slots = 128 * 64 + 1              # default pool x window + scratch
    n = ks.FILL_CHUNK
    _fits(ks._fill_dev.lower(
        s((slots, 256), jnp.uint8), s((slots, 16), jnp.uint8),
        s((n, 11, 16), jnp.uint8), s((n, 12), jnp.uint8),
        s((n,), jnp.int32), nblocks=16).compile())


@pytest.mark.parametrize("n,f", [
    (256, 960), (8, 160),           # one block
    (4096, 960), (CAP, 960),        # a grid of row tiles: one whole-array
])                                  # VMEM block is refused from 4096 rows
def test_mixer_xla_and_pallas(one_chip, no_persistent_cache, n, f):
    from libjitsi_tpu.conference.mixer import _mix_jit
    from libjitsi_tpu.kernels.pallas_ops import mix_minus_pallas
    pcm = _on(one_chip)((n, f), jnp.int16)
    act = _on(one_chip)((n,), jnp.bool_)
    _mix_jit.lower(pcm, act).compile()
    c = mix_minus_pallas.lower(pcm, act, interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()    # Mosaic took the kernel


def test_affinity_tick_four_chips(topo, no_persistent_cache, tower_core):
    """The whole mesh tick on the 2x2 host: every array row-sharded
    over four chips, and no collective in the compiled program."""
    from libjitsi_tpu.mesh.placement import affinity_tick
    from libjitsi_tpu.mesh.sharded import AXIS

    mesh = Mesh(np.asarray(topo.devices), (AXIS,))
    rows, frame, n_conf = 4096, 960, 4096 // 4 // 8

    def s(shape, dtype):           # rows over the mesh, rest whole
        spec = P(AXIS, *([None] * (len(shape) - 1)))
        return _on(NamedSharding(mesh, spec))(shape, dtype)

    def dense():
        return (s((rows, WIDTH), jnp.uint8), s((rows,), jnp.int32),
                s((rows,), jnp.int32), s((rows, 11, 16), jnp.uint8),
                s((rows, 16), jnp.uint8), s((rows, 2, 5), jnp.uint32),
                s((rows,), jnp.uint32))

    args = dense() + (s((rows, frame), jnp.int16), s((rows,), jnp.bool_),
                      s((rows,), jnp.int32)) + dense()
    c = affinity_tick(mesh, n_conf, 10).lower(*args).compile()
    text = c.as_text()
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute"):
        assert coll not in text, f"{coll} in the shard-local tick"
    for sh in jax.tree_util.tree_leaves(c.output_shardings):
        assert len(sh.device_set) == 4
    _fits(c)


# The two programs a tick of the bridge on a device mesh launches
# (`SfuBridge(mesh=4)`: mesh/table.py, mesh/translator.py), over the
# 2x2 host with the 40,960-row tables of `audio-sfu-cm-40k-mesh4`
# row-sharded 10,240 a chip.  Tier 1 asks at the 256-lane class; the
# fan-out at the top class, 4,096 lanes a chip, is `slow`.
@pytest.mark.parametrize("what,lanes", [
    ("unprotect", 256), ("fanout", 256),
    pytest.param("fanout", 4096, marks=pytest.mark.slow),
])
def test_mesh_served_programs_four_chips(topo, no_persistent_cache,
                                         tower_core, what, lanes):
    from libjitsi_tpu.core import staging
    from libjitsi_tpu.mesh import ShardedRtpTranslator, ShardedSrtpTable
    from libjitsi_tpu.mesh.sharded import AXIS

    mesh = Mesh(np.asarray(topo.devices), (AXIS,))
    cap = 40960

    def s(shape, dtype):           # leading axis over the mesh
        spec = P(AXIS, *([None] * (len(shape) - 1)))
        return _on(NamedSharding(mesh, spec))(shape, dtype)

    fn = (ShardedSrtpTable(cap, mesh)._shard_fn("unprotect", 10, True, 12)
          if what == "unprotect"
          else ShardedRtpTranslator(cap, mesh)._fanout_fn())
    assert fn.__name__ == {"unprotect": "mesh_unprotect_rtp",
                           "fanout": "mesh_fanout_protect"}[what]
    # one packed lane plane (core/staging.py) beside the tables
    c = fn.lower(
        s((cap, 11, 16), jnp.uint8), s((cap, 2, 5), jnp.uint32),
        s((4, lanes, WIDTH + staging.TAIL), jnp.uint8)).compile()
    text = c.as_text()
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute"):
        assert coll not in text, f"{coll} in a shard-local program"
    for sh in jax.tree_util.tree_leaves(c.output_shardings):
        assert len(sh.device_set) == 4
    _fits(c)
