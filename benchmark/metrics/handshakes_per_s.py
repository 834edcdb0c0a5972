"""handshakes_per_s: genuine handshakes completed inside the measured
window, over its length."""


def read(run: dict) -> float | None:
    done = run.get("handshakes_done_in_window")
    return done / run["seconds"] if done is not None else None
