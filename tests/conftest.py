"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; per the build contract the
sharded paths are validated on a virtual CPU mesh
(`--xla_force_host_platform_device_count=8`).

The tests never touch an accelerator: `JAX_PLATFORMS=cpu` is set here
and again through `jax.config.update`, which still takes effect where
something imported jax before conftest ran, because no backend has been
initialized yet.  The program is proved on the chip by `chip_smoke.py`,
and `tests/test_chip_compile.py` asks the chip's compiler without one.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache (same one bench.py/__graft_entry__ use):
# the suite is dominated by CPU XLA compiles; caching them on disk makes
# re-runs start warm.
from libjitsi_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


import pytest  # noqa: E402


@pytest.fixture
def warmed_launch_guard(monkeypatch):
    """A context manager round calls that must run warmed and staged by
    hand: inside it an implicit host-to-device transfer raises (a NumPy
    array handed to a jitted call, `jnp.asarray(..., dtype=other)`;
    `jax.device_put` may), and on leaving it no eager
    `convert_element_type` program was started and nothing compiled."""
    import contextlib

    import jax
    from jax import lax

    from libjitsi_tpu.utils.compile_cache import compile_stats

    @contextlib.contextmanager
    def guard():
        converts = []
        impl = lax.convert_element_type_p.impl
        monkeypatch.setattr(
            lax.convert_element_type_p, "impl",
            lambda *a, **k: (converts.append(k), impl(*a, **k))[1])
        compiles0 = compile_stats().compile_events
        with jax.transfer_guard_host_to_device("disallow"):
            yield
        monkeypatch.setattr(lax.convert_element_type_p, "impl", impl)
        assert converts == []
        assert compile_stats().compile_events == compiles0

    return guard


class EgressTap:
    """Sits on one engine's `send_batch_async`: records every burst as
    it is handed over (`handed`: (destination port, datagram) in row
    order, which is the order one `sendmmsg` sends them in) and, while
    `synchronous` is set, sends it with the synchronous `send_batch`
    instead of the egress worker, behind the same hand-over / reap
    interface: the fan-out as it was before the worker, to compare
    with."""

    def __init__(self, engine):
        self.engine = engine
        self.synchronous = False
        self.handed = []
        self.reaped = []            # every completion `reap` returned
        self._async, self._reap = engine.send_batch_async, engine.reap
        self._done = []
        engine.send_batch_async, engine.reap = self._send, self._reap_all

    def _send(self, batch, dst_ip, dst_port):
        import time

        import numpy as np

        from libjitsi_tpu.io.udp import SendDone, SendJob

        n = batch.batch_size
        ports = np.broadcast_to(np.asarray(dst_port), (n,))
        self.handed += [(int(ports[i]), batch.to_bytes(i))
                        for i in range(n)]
        if not self.synchronous:
            return self._async(batch, dst_ip, dst_port)
        t0 = time.perf_counter()
        sent = self.engine.send_batch(batch, dst_ip, dst_port)
        job = SendJob(-1 - len(self.reaped) - len(self._done), n, False)
        self._done.append(SendDone(job.id, sent, t0, time.perf_counter()))
        return job

    def _reap_all(self):
        done, self._done = self._reap() + self._done, []
        self.reaped += done
        return done

    def undo(self):
        del self.engine.send_batch_async, self.engine.reap


@pytest.fixture(scope="session")
def egress_tap():
    """`egress_tap(bridge) -> EgressTap` on the bridge's engine."""
    return lambda bridge: EgressTap(bridge.loop.engine)


@pytest.fixture
def sfu_with_traffic():
    """`(sfu, sup, send)`: an SfuBridge of three keyed endpoints behind
    a supervisor; `send()` puts one protected packet of each endpoint
    on the bridge's socket (`send.csrcs[k]`: endpoint k's CSRC list,
    empty to begin with), `send.until_forwarded()` sends and ticks
    back to back until a tick is the STEADY one: it collected and
    handed over the fan-out the tick before it dispatched, and
    dispatched its own (addresses latch on an endpoint's first packet,
    so the first round forwards to nobody).  That tick's ledgers hold
    every leaf; its own fan-out is still in flight and the burst it
    handed over not reaped (`sfu.flush_egress()` settles both)."""
    import time

    import libjitsi_tpu
    from libjitsi_tpu.io import UdpEngine
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.service.sfu_bridge import SfuBridge
    from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                                 SupervisorConfig)
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    sup = BridgeSupervisor(sfu, SupervisorConfig(deadline_ms=60_000.0),
                           metrics=sfu.loop.metrics)
    eps = []
    for k in range(3):
        ssrc = 0x100 + 7 * k
        rx = (bytes([ssrc & 0xFF]) * 16, bytes([(ssrc + 1) & 0xFF]) * 14)
        tx = (bytes([(ssrc + 2) & 0xFF]) * 16,
              bytes([(ssrc + 3) & 0xFF]) * 14)
        sfu.add_endpoint(ssrc, rx, tx)
        tab = SrtpStreamTable(capacity=1)
        tab.add_stream(0, *rx)
        eps.append((ssrc, tab, UdpEngine(port=0, max_batch=64)))
    seq = [500]

    def send():
        for (ssrc, tab, eng), csrcs in zip(eps, send.csrcs):
            b = rtp_header.build([b"m-%08x" % ssrc], [seq[0]], [0],
                                 [ssrc], [96], csrcs=[csrcs], stream=[0])
            eng.send_batch(tab.protect_rtp(b), "127.0.0.1", sfu.port)
        seq[0] += 1

    def until_forwarded(ticks=50):
        for _ in range(ticks):
            send()
            time.sleep(0.01)
            sup.tick(now=50.0)
            if {"egress", "fanout_dispatch"} <= set(sup.last_ledger):
                return
        raise AssertionError("the bridge never forwarded")

    send.csrcs = [[] for _ in eps]
    send.until_forwarded = until_forwarded
    yield sfu, sup, send
    sup.close()
    sfu.close()
    for _ssrc, _tab, eng in eps:
        eng.close()
