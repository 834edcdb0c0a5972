"""Readings that share one warm hub: many seeds or rates in one process.

    python3 benchmark/readings.py --workload <cell> --seconds <s> \\
        --seeds <a,b,...> [--rates <r1,r2,...>] \\
        [--plant <fault> --plant-seeds <x,y,...>] [--trace 1] [--dump <dir>]

Not the benchmark's command (each of its runs is a process of its own and
pays its own set-up).  This is how the numbers behind the cell files and
the check's limits are read on the chip, in this order:

* the knee sweep (``--rates``, ascending): one trial per rate on the first
  seed (the others are the sound runs'), up to the first rate past the
  knee.  The knee is the highest rate
  whose trial was correct, had no request later than the protocol's
  timeout, kept ``handshake_p99_ms`` at or under 2,000 ms (the product's
  handshake SLO) and completed at least 90 % of the offered rate in the
  window (a backlog that does not grow).  A line ``{"knee", "rate"}``
  gives it and 4/5 of it;
* sound runs: one trial per seed, at 4/5 of the knee after a sweep, else
  at the cell's rate;
* the control or a planted fault (``--plant``, one of ``lib/faults.py``),
  planted for good after the sound runs, one trial per ``--plant-seeds``.

One JSON line per trial, and the device's peak memory last.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.lib import check, faults, harness, hub as hub_mod, spec  # noqa: E402

#: the product's handshake SLO (app/messaging.py HANDSHAKE_SLO_THRESHOLD_S)
KNEE_P99_MS = 2000.0
#: a sweep trial keeps up when it completes this share of the offered rate
KNEE_DONE_SHARE = 0.9


def keeps_up(line: dict) -> bool:
    """Whether a sweep trial's rate is at or under the knee."""
    return (line["correct"] and line["late"] == 0
            and (line["knee"]["handshake_p99_ms"] or 1e9) <= KNEE_P99_MS
            and (line["knee"]["handshakes_per_s"] or 0.0)
            >= KNEE_DONE_SHARE * line["rate_per_s"])


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--plant", choices=sorted(faults.PLANTS))
    ap.add_argument("--plant-seeds", default="")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", help="write each trial's summary (what the "
                    "metric readers read) as JSON into this directory")
    args = ap.parse_args(argv)
    harness.prepare_environment()
    cell = spec.cell(args.workload)
    devices = harness.tpu_devices(cell.chips)
    if devices is None:
        return 1
    from quantum_resistant_p2p_tpu import native

    native.load()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    plant_seeds = [int(s) for s in args.plant_seeds.split(",") if s]
    plant = faults.PLANTS[args.plant] if args.plant else None
    trials, lines = [], []
    state = {"phase": "sweep" if rates else "sound", "rate":
             cell.traffic["rate_per_s"], "hub_seed": seeds[0]}

    def emit(run, seed, traffic, phase) -> dict:
        view = harness.summary(run)
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            (Path(args.dump) / f"summary_{cell.name}_{seed}_{phase}_"
             f"{traffic['rate_per_s']}.json").write_text(json.dumps({
                 "workload": cell.name, "seed": seed,
                 "rate_per_s": traffic["rate_per_s"], "summary": view}))
        checks = check.evaluate(run, cell.config, seed, state["hub_seed"])
        line = {
            "phase": phase, "seed": seed, "rate_per_s": traffic["rate_per_s"],
            "plant": args.plant if phase == "plant" else None,
            "attempted": view["attempted"], "late": view["late"],
            "fallback_ops": run.fallback_ops,
            "correct": all(c.ok for c in checks),
            "checks": {c.name: c.value for c in checks},
            "metrics": {m.name: spec.reader(m.name)(view)
                        for m in cell.end_to_end + cell.per_layer},
            "knee": {name: spec.reader(name)(view) for name in (
                "handshake_p99_ms", "handshakes_per_s")},
            "lateness": hub_mod.lateness(run), "queues": run.queues,
            "profile": {k: v for k, v in (run.profile or {}).items()}}
        print(json.dumps(line), flush=True)
        return line

    def plan(runs):
        if runs:
            seed, traffic, phase = trials[-1]
            lines.append(emit(runs[-1], seed, traffic, phase))
        if state["phase"] == "sweep":
            done = [ln for ln in lines if ln["phase"] == "sweep"]
            if len(done) < len(rates) and all(keeps_up(ln) for ln in done):
                trials.append((seeds[0], dict(cell.traffic,
                                              rate_per_s=rates[len(done)]),
                               "sweep"))
                return trials[-1][0], trials[-1][1], None
            ok = [ln["rate_per_s"] for ln in done if keeps_up(ln)]
            if not ok:
                print(json.dumps({"knee": None}), flush=True)
                return None
            state.update(phase="sound", rate=round(0.8 * max(ok), 2))
            print(json.dumps({"knee": max(ok), "rate": state["rate"]}),
                  flush=True)
        traffic = dict(cell.traffic, rate_per_s=state["rate"])
        sound = seeds[1:] if rates else seeds
        n_sound = sum(1 for t in trials if t[2] == "sound")
        if n_sound < len(sound):
            trials.append((sound[n_sound], traffic, "sound"))
            return trials[-1][0], traffic, None
        n_plant = sum(1 for t in trials if t[2] == "plant")
        if plant is not None and n_plant < len(plant_seeds):
            trials.append((plant_seeds[n_plant], traffic, "plant"))
            return trials[-1][0], traffic, plant
        return None

    out = asyncio.run(harness.run_cell(cell, plan, args.seconds,
                                       bool(args.trace), T_START, devices))
    print(json.dumps({"memory_peak_bytes": out["memory_peak_bytes"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
