"""The device seam's host spans (core/staging.py `dispatch`, `put`,
`put_each`; the unprotect's `unprotect_block` / `unprotect_d2h`): the
put is spanned once, where it happens, with what crossed; only inside
a `dispatch`, only on its thread."""

import threading

import numpy as np
import pytest

from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.utils.metrics import MetricsRegistry
from libjitsi_tpu.utils.tracing import PipelineTracer
from test_srtp import make_table, rtp_pkt


@pytest.fixture
def tracer():
    return PipelineTracer(MetricsRegistry(), annotate=False)


def _plane(rows=16, width=224):
    return staging.alloc(rows, width)


def test_put_outside_a_dispatch_opens_no_span(tracer):
    dev = staging.put(_plane())
    assert dev.shape == (16, 256)
    assert tracer.take_ledger() == {} and tracer.last_counts == {}


@pytest.mark.parametrize("seam", ["unprotect", "fanout"])
def test_put_inside_a_dispatch_books_the_seams_put(tracer, seam):
    plane = _plane()
    with staging.dispatch(tracer, seam) as sp:
        dev = staging.put(plane)
        sp.note(h2d_arrays=1, h2d_bytes=plane.nbytes)
    assert np.array_equal(np.asarray(dev), plane)
    led = tracer.take_ledger()
    assert set(led) == {seam + "_dispatch", seam + "_put"}
    assert tracer.last_counts[seam + "_put"] == \
        tracer.last_counts[seam + "_dispatch"] == {
            "h2d_arrays": 1, "h2d_bytes": 16 * 256}
    # the put is the dispatch's child: its time comes off the parent's
    self_led = tracer.last_self_ledger
    assert self_led[seam + "_put"] == led[seam + "_put"]
    assert self_led[seam + "_dispatch"] == pytest.approx(
        led[seam + "_dispatch"] - led[seam + "_put"])


@pytest.mark.parametrize("seam", ["unprotect", "fanout"])
def test_put_each_books_one_span_with_the_count_of_its_arrays(tracer,
                                                              seam):
    arrays = (np.arange(8, dtype=np.int32), _plane(8),
              np.zeros((8, 12), np.uint8))
    with staging.dispatch(tracer, seam):
        dev, n, nbytes = staging.put_each(arrays)
    assert (n, nbytes) == (3, 32 + 8 * 256 + 96) and len(dev) == 3
    tracer.take_ledger()
    assert tracer.last_counts == {
        seam + "_put": {"h2d_arrays": 3, "h2d_bytes": nbytes}}
    # ONE span: the stage's ring saw one entry
    ring = tracer.metrics.timing(f"stage_{seam}_put")
    assert ring.count == 1


def test_put_on_another_thread_opens_no_span_and_leaves_the_tree(tracer):
    """A warm-up in the compile pool calls the same seams while the
    tick thread may be inside a dispatch: its puts book nothing and the
    tracer's open span stays the tick thread's."""
    seen = []

    def other():
        staging.put(_plane())
        seen.append(tracer._open.stage)

    with staging.dispatch(tracer, "fanout"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen == ["fanout_dispatch"]
    assert set(tracer.take_ledger()) == {"fanout_dispatch"}
    assert tracer._open is None


def test_dispatch_restores_what_was_open_before_it(tracer):
    with staging.dispatch(tracer, "unprotect"):
        with staging.dispatch(tracer, "fanout"):
            staging.put(_plane())
        staging.put(_plane())
    staging.put(_plane())                   # outside both: no span
    tracer.take_ledger()
    assert {k: v["h2d_arrays"] for k, v in tracer.last_counts.items()} \
        == {"fanout_put": 1, "unprotect_put": 1}


def test_dispatch_without_a_tracer_is_the_null_span():
    with staging.dispatch(None, "fanout") as sp:
        sp.note(h2d_arrays=1)
        dev = staging.put(_plane(4))
    assert dev.shape == (4, 256)


def test_put_onto_a_mesh_is_one_span_a_block_a_chip(tracer):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[:4]
    if len(devs) < 4:
        pytest.skip("needs four devices")
    mesh = Mesh(np.array(devs), ("x",))
    lanes = np.arange(4 * 8 * 256, dtype=np.uint8).reshape(4, 8, 256)
    with staging.dispatch(tracer, "fanout"):
        dev = staging.put(lanes, NamedSharding(mesh, P("x", None, None)))
    assert len(dev.addressable_shards) == 4
    assert dev.addressable_shards[0].data.shape == (1, 8, 256)
    assert np.array_equal(np.asarray(dev), lanes)
    tracer.take_ledger()
    assert tracer.last_counts == {
        "fanout_put": {"h2d_arrays": 1, "h2d_bytes": lanes.nbytes}}


def _cm_wire(tx, seq0, n=12):
    pkts = [rtp_pkt(seq0 + i // 4, ssrc=0x1000 + i % 4,
                    payload=bytes([i]) * 40) for i in range(n)]
    return tx.protect_rtp(PacketBatch.from_payloads(
        pkts, stream=[i % 4 for i in range(n)]))


def test_cm_unprotect_books_the_four_phases_with_its_counts(tracer):
    """`unprotect_wait` is a container: dispatch (holding the put),
    block and copy back tile it, the put books the call's `h2d_*`, the
    copy back its `d2h_*`."""
    t, tx = make_table(n=4), make_table(n=4)
    t.tracer = tracer
    t.unprotect_rtp(_cm_wire(tx, 10))         # warms the program
    tracer.take_ledger()
    _, ok = t.unprotect_rtp(_cm_wire(tx, 20))
    assert ok.all()
    led = tracer.take_ledger()
    self_led, c = tracer.last_self_ledger, tracer.last_counts
    assert set(led) == {"unprotect_host", "unprotect_wait",
                        "unprotect_dispatch", "unprotect_put",
                        "unprotect_block", "unprotect_d2h"}
    plane = 16 * (192 + 32 + 32)
    assert c["unprotect_put"] == {"h2d_arrays": 1, "h2d_bytes": plane}
    assert c["unprotect_d2h"] == {"d2h_arrays": 1, "d2h_bytes": plane}
    assert c["unprotect_wait"] == {
        "rows": 12, "rows_padded": 16, "h2d_arrays": 1,
        "h2d_bytes": plane, "d2h_arrays": 1, "d2h_bytes": plane}
    inner = (led["unprotect_dispatch"] + led["unprotect_block"]
             + led["unprotect_d2h"])
    assert self_led["unprotect_wait"] == pytest.approx(
        led["unprotect_wait"] - inner)
    assert 0.0 <= self_led["unprotect_wait"] < 0.2 * led["unprotect_wait"]
    assert led["unprotect_put"] < led["unprotect_dispatch"]


def test_unprotect_waits_before_it_copies_back(tracer, monkeypatch):
    """The unprotect blocks on the launch inside `unprotect_block` and
    only then fetches, inside `unprotect_d2h`."""
    order = []
    block, fetch = staging.Launch.block_until_ready, staging.Launch.fetch

    def seen(name, fn):
        def wrapped(self):
            order.append((name, tracer._open.stage))
            return fn(self)
        return wrapped

    monkeypatch.setattr(staging.Launch, "block_until_ready",
                        seen("block", block))
    monkeypatch.setattr(staging.Launch, "fetch", seen("fetch", fetch))
    t, tx = make_table(n=4), make_table(n=4)
    t.tracer = tracer
    _, ok = t.unprotect_rtp(_cm_wire(tx, 10))
    assert ok.all()
    assert order == [("block", "unprotect_block"),
                     ("fetch", "unprotect_d2h")]


def test_async_unprotect_opens_no_seam_span(tracer):
    """`unprotect_rtp_async` dispatches outside any `dispatch`: the
    same put helper, no span (a served tick takes the direct path)."""
    t, tx = make_table(n=4), make_table(n=4)
    t.tracer = tracer
    pend = t.unprotect_rtp_async(_cm_wire(tx, 10))
    _, ok = pend.result()
    assert ok.all()
    assert not any(k.endswith("_put") or k.endswith("_dispatch")
                   for k in tracer.take_ledger())


def test_copy_back_async_asks_for_device_outputs_only():
    """`Launch.copy_back_async` starts the copy of every device array
    among the outputs and passes over what is none (a mesh seam's
    deferred scatter, a host length); `fetch` then gives the same
    values."""
    import jax

    class Deferred:
        def __array__(self, dtype=None, copy=None):
            return np.arange(3)

    dev = jax.device_put(np.arange(8, dtype=np.uint8))
    launch = staging.Launch((dev, Deferred(), np.int32(5)))
    assert launch.copy_back_async() is launch
    a, b, c = launch.block_until_ready().fetch()
    assert a.tolist() == list(range(8)) and b.tolist() == [0, 1, 2]
    assert int(c) == 5 and launch.d2h_arrays == 3
