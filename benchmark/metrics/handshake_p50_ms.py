"""handshake_p50_ms: the median of the same latencies as
handshake_p99_ms."""

import numpy as np


def read(run: dict) -> float | None:
    lat = run.get("handshake_latency_s")
    return float(np.percentile(lat, 50) * 1e3) if lat else None
