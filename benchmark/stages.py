"""Trials of a cell on one warm hub, read through the program's own spans.

    python3 benchmark/stages.py --workload <cell> --seconds <s> \\
        --seeds <a,b,...> [--trace 1]

Not the benchmark's command: like ``readings.py``, one hub serves a trial
per seed and pays its set-up once.  A listener on the program's tracer
(``lib/spans.py``) keeps the spans that finish in each trial's measured
window.  One JSON line per trial gives the cell's metrics (end-to-end and
per-layer, whatever the trace flag), the five numbers of the device
flushes' stages (``stages``; None from a program without them), the
fused flushes' accounting (``flush_account``), the clients' lateness
and, traced, every device-idle gap of 5 ms or more with the worker stage
that overlaps it (``stage_gaps``).  The device's peak memory comes last.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.lib import check, harness, spans, spec, trace  # noqa: E402
from benchmark.lib.hub import lateness  # noqa: E402


def _reduce_with_stage_gaps(reduce_file):
    """``trace.reduce_file``, adding the gaps' stages to its reduction."""

    def reduce(path):
        from jax.profiler import ProfileData

        out = reduce_file(path)
        data = ProfileData.from_file(str(path))
        out["stage_gaps"] = spans.stage_gaps(data.planes)
        return out

    return reduce


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.prepare_environment()
    cell = spec.cell(args.workload)
    devices = harness.tpu_devices(cell.chips)
    if devices is None:
        return 1
    from quantum_resistant_p2p_tpu import native

    native.load()
    trace.reduce_file = _reduce_with_stage_gaps(trace.reduce_file)
    recorder = spans.Recorder().install()
    seeds = [int(s) for s in args.seeds.split(",")]

    def emit(run, seed: int) -> None:
        view = harness.summary(run)
        view["spans"] = recorder.take()
        checks = check.evaluate(run, cell.config, seed, seeds[0])
        profile = run.profile or {}
        print(json.dumps({
            "seed": seed, "traced": bool(args.trace),
            "correct": all(c.ok for c in checks),
            "attempted": view["attempted"],
            "failed": view["late"] + run.fallback_ops,
            "metrics": {m.name: spec.reader(m.name)(view)
                        for m in cell.end_to_end + cell.per_layer},
            "stages": {name: read(view)
                       for name, read in spans.READERS.items()},
            "flush_account": spans.flush_account(view),
            "spans": len(view["spans"]), "lateness": lateness(run),
            "stage_gaps": profile.get("stage_gaps"),
            "idle_gaps": profile.get("idle_gaps")}), flush=True)

    def plan(runs):
        if runs:
            emit(runs[-1], seeds[len(runs) - 1])
        if len(runs) < len(seeds):
            return seeds[len(runs)], cell.traffic, None
        return None

    out = asyncio.run(harness.run_cell(cell, plan, args.seconds,
                                       bool(args.trace), T_START, devices))
    print(json.dumps({"memory_peak_bytes": out["memory_peak_bytes"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
