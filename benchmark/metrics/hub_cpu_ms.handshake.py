"""hub_cpu_ms.handshake (protocol layer): the hub process's CPU seconds
(time.process_time, every thread) over the measured window, per genuine
handshake completed in it, in ms."""


def read(run: dict) -> float | None:
    done = run.get("handshakes_done_in_window")
    if not done or not run.get("cpu_s"):
        return None
    return run["cpu_s"] * 1e3 / done
