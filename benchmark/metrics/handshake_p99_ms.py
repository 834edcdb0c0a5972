"""handshake_p99_ms: the 99th percentile, over every genuine handshake due
in the window, of the time from its scheduled send to a usable session on
the client; one that never completed counts as the give-up time."""

import numpy as np


def read(run: dict) -> float | None:
    lat = run.get("handshake_latency_s")
    return float(np.percentile(lat, 99) * 1e3) if lat else None
