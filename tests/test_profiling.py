"""Device memory stats (SURVEY §5 aux); the tracer's spans and their
profiler annotations are tested in test_observability.py."""

from libjitsi_tpu.utils import profiling


def test_device_memory_stats_shape():
    info = profiling.device_memory()
    assert "device" in info and "bytes_in_use" in info
