"""setup_s: process start to the first timed request (the window's open),
on the host clock: loading, compiling, warming, the traffic's own set-up
and the warm-up segment."""


def read(run: dict) -> float | None:
    return run["setup_s"]
