"""One run of one cell, from the command line to the result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device`` and, traced, ``breakdown``,
then ``checks``: every number compared beside its limit.  The same
numbers are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 1.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import time

from . import check, hub as hub_mod, spec
from .compiles import LEDGER
from .hub import log

#: client processes per cell, unless the traffic file says otherwise
CLIENT_PROCS = 6


def summary(run: hub_mod.Run) -> dict:
    """The run as the metric readers see it: what every kind has, and what
    its kind adds (``summary`` of ``benchmark/kinds/<kind>.py``)."""
    out = {"kind": run.kind, "seconds": run.seconds, "setup_s": run.setup_s,
           "attempted": sum(1 for e in run.events
                            if e.in_window and not e.forged),
           "cpu_s": run.cpu_s, "queues": run.queues, "profile": run.profile}
    out.update(spec.kind(run.kind).summary(run, hub_mod.GIVE_UP_S,
                                           hub_mod.HANDSHAKE_TIMEOUT_S))
    return out


def _device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


async def run_cell(cell: spec.Cell, plan, seconds: float, traced: bool,
                   t_start: float, devices) -> dict:
    """Everything a run does after the device check, up to the hub's stop.

    One hub serves trial after trial, with client processes of its own for
    each: ``plan(runs)`` gives the next ``(seed, traffic, plant)`` from the
    runs so far, or None to stop (a benchmark run is one trial; the
    readings of many seeds or rates share one warm hub).  ``plant(hub)``,
    when given, breaks the hub before that trial, for good (the control and
    the fault tests).  The hub's identity comes from the first trial's
    seed.  The device's peak memory is read after the last window, before
    the stop."""
    trial = plan([])
    hub_seed = trial[0]
    hub = hub_mod.Hub(cell.config, hub_seed)
    port = await hub.start()
    runs, clients, planted = [], None, set()
    try:
        while trial is not None:
            seed, traffic, plant = trial
            clients = await hub_mod.Clients.start(
                int(traffic.get("client_procs", CLIENT_PROCS)), {
                    "seed": seed, "seconds": seconds, "traffic": traffic,
                    "suite": cell.config["suite"], "port": port,
                    "give_up_s": hub_mod.GIVE_UP_S})
            if not runs:
                hub.build()
                steps = await asyncio.get_running_loop().run_in_executor(
                    None, hub_mod.warm, hub.engine)
                log(f"hub ready after {time.monotonic() - t_start:.1f} s: "
                    + json.dumps({k: round(v, 2) for k, v in steps.items()})
                    + " compiles " + json.dumps(LEDGER.summary()))
            if plant is not None and plant not in planted:
                plant(hub)
                planted.add(plant)
            runs.append(await hub_mod.drive(
                hub, clients, traffic, seed, seconds, traced,
                None if len(runs) else t_start))
            log(f"trial {len(runs) - 1}: compiles in the window: "
                f"{len(LEDGER.in_window)} {LEDGER.in_window[:5]}; queues "
                + json.dumps(hub_mod.queue_latency(hub.engine)))
            await clients.stop()
            clients = None
            trial = plan(runs)
        peak = hub_mod.memory_peak_bytes(devices)
    finally:
        if clients is not None:
            await clients.stop()
        await hub.stop()
    return {"runs": runs, "hub_seed": hub_seed,
            "memory_peak_bytes": peak}


def _result(cell: spec.Cell, run: hub_mod.Run, seed: int, traced: bool,
            device: dict, checks: list[check.Check]) -> dict:
    view = summary(run)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.reader(m.name)(view)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    out = {"correct": all(c.ok for c in checks),
           "attempted": view["attempted"],
           "failed": view["late"] + run.fallback_ops,
           "metrics": metrics, "device": device}
    if traced:
        p = run.profile
        device.update(busy_s=p["busy_s"], window_s=p["window_s"])
        out["breakdown"] = {"device_ops": p["device_ops"],
                            "idle_gaps": p["idle_gaps"]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def report(cell: spec.Cell, run: hub_mod.Run, seed: int, traced: bool,
           device: dict, hub_seed: int | None = None) -> dict:
    """Check the run against the reference, print the result, return it."""
    t = time.monotonic()
    checks = check.evaluate(run, cell.config, seed,
                            seed if hub_seed is None else hub_seed)
    log(f"reference check: {time.monotonic() - t:.1f} s, "
        f"{len(run.sample)} sampled requests")
    late = hub_mod.lateness(run)
    log("generator lateness: " + json.dumps(late))
    out = _result(cell, run, seed, traced, device, checks)
    print(json.dumps(out), flush=True)
    for c in checks:
        print(f"check {c.name} = {c.value} (limit {c.limit})",
              file=sys.stderr, flush=True)
    return out


def parse(argv: list[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_environment() -> None:
    """Before JAX is imported: every cache the run keeps at a fixed path
    inside the checkout (JAX's compile cache and the native core's build),
    and no TPU log directory under /tmp."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    os.environ["QRP_NATIVE_CACHE"] = str(spec.ROOT / ".bench_cache" / "native")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    logging.basicConfig(level=logging.ERROR)


def tpu_devices(chips: int):
    """The chips the cell asks for, or None (with the reason on stderr)."""
    import jax

    from quantum_resistant_p2p_tpu.utils.compile_cache import (
        enable_compile_cache)

    enable_compile_cache()
    LEDGER.install()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind})")
        return None
    return devices[:chips]


def main(argv: list[str], t_start: float) -> int:
    args = parse(argv)
    prepare_environment()
    cell = spec.cell(args.workload)
    devices = tpu_devices(cell.chips)
    if devices is None:
        return 1
    from quantum_resistant_p2p_tpu import native

    native.load()  # built once here, before the client processes load it
    state = asyncio.run(run_cell(
        cell, lambda runs: None if runs else (args.seed, cell.traffic, None),
        args.seconds, bool(args.trace), t_start, devices))
    device = _device_info(devices)
    device["memory_peak_bytes"] = state["memory_peak_bytes"]
    report(cell, state["runs"][0], args.seed, bool(args.trace), device)
    return 0
