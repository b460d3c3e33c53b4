"""Device memory stats (SURVEY §5 aux subsystems).

The reference has no built-in tracer beyond level-guarded logging — users
attach JVM profilers, and `PacketLoggingService` gives pcap-level data-path
tracing (we have the pcap tap in `io/pcap.py`).  Here a trace is
`jax.profiler.trace(dir)`, into which `utils.tracing.PipelineTracer`
writes the host's stage spans; what is left in this module is
`device_memory()`: current live-buffer stats per device, the analog of
eyeballing a JVM heap profiler for leaks.
"""

from __future__ import annotations

from typing import Optional

import jax


def device_memory(device: Optional[object] = None) -> dict:
    """Live-buffer stats for one device (default: first)."""
    dev = device or jax.devices()[0]
    try:
        stats = dev.memory_stats() or {}
    except (AttributeError, NotImplementedError):
        stats = {}
    return {
        "device": str(dev),
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "num_allocs": stats.get("num_allocs"),
    }
