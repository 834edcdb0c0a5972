"""flush_rows.fused (batch queue layer): operations per flush of the hub's
fused encaps_verify_sign queue over the measured window (the rows that
carry work in each 1024-row program)."""


def read(run: dict) -> float | None:
    q = run["queues"].get("fused.encaps_verify_sign")
    if not q or not q["flushes"]:
        return None
    return q["ops"] / q["flushes"]
