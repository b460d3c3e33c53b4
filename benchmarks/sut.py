"""The system under test, assembled the way a user assembles it:

    libjitsi_tpu.init() -> <bridge class>(capacity, profile, port=0)
    -> BridgeSupervisor -> StreamLifecycleManager.enable_placement
    -> request_join for every endpoint, ticked until all are live

Everything that shapes it comes from the configuration file (profile,
capacity, conference sizes, bridge class, mesh device count, lifecycle
and supervisor settings); nothing here knows a configuration's name.
This is the only benchmark module that imports `libjitsi_tpu`.
"""

from __future__ import annotations

import os
import socket
import time

import numpy as np

from loadgen import SSRC_BASE, endpoint_keys, key_pair


class System:
    """Bridge + supervisor + lifecycle, with the handles the harness
    reads: `sup.tick()`, `sup.last_tick_s`, `sup.last_ledger`,
    `loop.rx_packets`, `loop.ticks`, `loop.perf.h2d_bytes`."""

    def __init__(self, config: dict, say):
        import jax

        import libjitsi_tpu
        from libjitsi_tpu.service import lifecycle as lifecycle_mod
        from libjitsi_tpu.service import supervisor as supervisor_mod
        from libjitsi_tpu.transform.srtp import SrtpProfile
        from libjitsi_tpu.utils.compile_cache import compile_stats

        self.config = config
        self.say = say
        self.stats = compile_stats()
        libjitsi_tpu.stop()
        libjitsi_tpu.init()
        cfg = libjitsi_tpu.configuration_service()
        profile = SrtpProfile[config["profile"]]
        if profile.policy.window_size != config["replay_window"]:
            raise SystemExit(
                f"configuration states replay window "
                f"{config['replay_window']}, the program's profile has "
                f"{profile.policy.window_size}")
        kwargs = {"port": 0, "capacity": int(config["capacity"]),
                  "profile": profile}
        if config.get("recv_window_ms") is not None:
            kwargs["recv_window_ms"] = int(config["recv_window_ms"])
        n_mesh = int(config.get("mesh_devices") or 0)
        if n_mesh > 1:
            from libjitsi_tpu.mesh import make_media_mesh

            kwargs["mesh"] = make_media_mesh(jax.devices()[:n_mesh])
        if config["bridge_class"] == "SfuBridge":
            from libjitsi_tpu.service.sfu_bridge import SfuBridge as cls
        elif config["bridge_class"] == "ConferenceBridge":
            from libjitsi_tpu.service.bridge import ConferenceBridge as cls
        else:
            raise SystemExit(f"bridge_class {config['bridge_class']!r}")
        self.bridge = cls(cfg, **kwargs)
        self.loop = self.bridge.loop
        if config.get("ingest_max_batch"):
            # the engine's live batching knob (io/udp.py `max_batch`)
            for eng in self.loop.rings:
                eng.max_batch = int(config["ingest_max_batch"])
        reg = self.loop.metrics
        self.default_deadline_ms = \
            supervisor_mod.SupervisorConfig().deadline_ms
        self.sup = supervisor_mod.BridgeSupervisor(
            self.bridge,
            supervisor_mod.SupervisorConfig(**config["supervisor"]),
            metrics=reg)
        self.lc = lifecycle_mod.StreamLifecycleManager(
            self.bridge, supervisor=self.sup,
            config=lifecycle_mod.LifecycleConfig(**config["lifecycle"]),
            metrics=reg)
        self.lc.enable_placement(max(1, n_mesh))
        self.port = self.bridge.port
        self.fanout = int(config["conference_sizes"][0]) - 1

    def admit_all(self, seed: int) -> dict:
        """Every endpoint through `request_join`, a wave per tick,
        ticked until all are live (the normal path; the lifecycle's
        warm ladder is the program's and is paid as it is)."""
        from oracle import SUITES

        config, lc, sup = self.config, self.lc, self.sup
        rows = int(config["capacity"])
        cs = int(config["conference_sizes"][0])
        keys = endpoint_keys(seed, rows, SUITES[config["profile"]][2])
        t0 = time.perf_counter()
        queued = 0
        while lc.admits < rows:
            for i in range(queued, min(rows, queued + lc.cfg.install_batch)):
                ok, why = lc.request_join(
                    SSRC_BASE + i, key_pair(keys[i, 0]),
                    key_pair(keys[i, 1]), conference=i // cs)
                if not ok:
                    raise SystemExit(
                        f"admission refused endpoint {i}: {why}")
                queued += 1
            sup.tick(now=time.time())
            if sup.ticks > 4 * rows // lc.cfg.install_batch + 64:
                raise SystemExit(f"only {lc.admits}/{rows} live after "
                                 f"{sup.ticks} ticks")
        live = len(self.bridge._ssrc_of) - len(self.bridge._staged)
        if live != rows:
            raise SystemExit(f"{live} live rows, wanted {rows}")
        out = {"admit_s": time.perf_counter() - t0,
               "admit_ticks": sup.ticks,
               "compile_events": self.stats.compile_events,
               "compile_seconds": self.stats.compile_seconds,
               "cache_hits": self.stats.hits,
               "cache_misses": self.stats.misses}
        self.say(f"admission: {rows} endpoints live after {sup.ticks} "
                 f"ticks, {out['admit_s']:.1f}s; compile events "
                 f"{out['compile_events']} ({out['compile_seconds']:.0f}s "
                 f"thread-summed), cache hits {out['cache_hits']} "
                 f"misses {out['cache_misses']}")
        return out

    def counters(self) -> dict:
        loop, sup, lc = self.loop, self.sup, self.lc
        h = sup.health()
        return {
            "rx_packets": loop.rx_packets, "tx_packets": loop.tx_packets,
            "loop_ticks": loop.ticks, "forwarded": self.bridge.forwarded,
            "h2d_bytes": loop.perf.h2d_bytes,
            "d2h_bytes": loop.perf.d2h_bytes,
            "compile_events": self.stats.compile_events,
            "datapath_recompiles": lc.datapath_recompiles,
            "shed": len(h["shed"]), "quarantined": len(h["quarantined"]),
            "level": int(h["level"]),
            "quarantine_total": int(sup.quarantine_total),
            "refused": int(sum(lc.admit_rejected.values())),
            "unknown_ssrc": loop.unknown_ssrc_dropped,
            "inbound_dropped": loop.inbound_dropped_total,
        }

    def member_sids(self, rows) -> np.ndarray:
        """The bridge's stream ids of the endpoints `rows` (= ssrc -
        SSRC_BASE): what its address table is indexed by."""
        sid_of = {ssrc: sid for sid, ssrc in self.bridge._ssrc_of.items()}
        return np.array([sid_of[SSRC_BASE + int(r)] for r in rows],
                        dtype=np.int64)

    def unlatched(self, sids) -> int:
        """How many of these streams the bridge knows no address for
        (it learns a leg's address from that leg's own packets): what
        it forwards to them goes nowhere."""
        return int((self.loop.addr_port[sids] == 0).sum())

    def forget_addresses(self) -> None:
        """Between chained windows: every leg unlatched, as in a new
        process."""
        self.loop.addr_ip[:] = 0
        self.loop.addr_port[:] = 0

    def break_fanout(self, share: float = 0.02):
        """Fault `bridge-bitflip`: the timed path broken underneath —
        one payload bit flipped in a share of the fan-out rows where
        the bridge produces them.  `correct` has to come out false."""
        tr = self.bridge.translator
        inner = tr.translate
        rng = np.random.default_rng(1)

        def translate(batch, index):
            wire, recv = inner(batch, index)
            n = wire.batch_size
            if n:
                from libjitsi_tpu.core.packet import PacketBatch

                rows = np.nonzero(rng.random(n) < share)[0]
                data = np.array(wire.data)
                data[rows, 20] ^= 0x04
                wire = PacketBatch(data, wire.length, wire.stream)
            return wire, recv

        tr.translate = translate
        return lambda: setattr(tr, "translate", inner)

    def socket_rcvbuf(self) -> int:
        """What the kernel granted the bridge's socket (a log line: the
        depth of the queue an overloaded bridge sheds from)."""
        with socket.socket(fileno=os.dup(self.loop.engine._fd)) as s:
            return s.getsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF)

    def close(self) -> None:
        self.bridge.close()
