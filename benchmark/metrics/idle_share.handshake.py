"""idle_share (device layer): the share of the profiled sub-window in which
no operation ran on the device, 1 - busy_s / window_s, in percent, from the
profiler trace."""


def read(run: dict) -> float | None:
    p = run.get("profile")
    if not p:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
