"""Headline benchmark: batched ML-KEM-768 encapsulation throughput.

Prints ONE JSON line with the required keys {"metric", "value", "unit",
"vs_baseline"} plus dispatch-size labels (an ADVICE round-3 item): the
headline "value" is measured at the scaling-plateau dispatch size
(``dispatch_rows``, 2x the provider cap) and the shipped provider
configuration's figure rides along as ``value_at_provider_dispatch`` /
``provider_dispatch_rows``.  The metric name embeds the headline dispatch
size (so it reads ``mlkem768_encaps_batch4096_dispatch2048``; rounds 1-3
recorded the same quantity as ``mlkem768_encaps_batch4096``).

``--slo`` switches to the latency SLO probe: 32 sequential warm handshakes
through the tpu+batch stack (tools/swarm_bench.py at concurrency 1), with
single-handshake warm p50/p99 and MEASURED dispatch trips per handshake in
the emitted JSON — so BENCH_* rounds track the latency frontier (dispatch
count, docs/dispatch_budget.md) alongside the encaps/s headline.  The SLO
baseline is round 4's warm p50 (bench_results/
slo_single_handshake_r4.json, pre-fusion, taken on an earlier platform and
not comparable with a chip run): ``vs_baseline`` > 1 means faster.

Baseline: BASELINE.md / BASELINE.json north star — >= 50,000 ML-KEM-768
encaps/sec on one v5e chip (the reference's serial liboqs path measures
~4 full handshakes/sec end-to-end), so vs_baseline is value / 50_000.

Methodology (see utils/benchmarking.py): every timed region ends in
``block_until_ready``.  Fresh random inputs, first call excluded (compile),
best-of-3 trials of 3 back-to-back dispatches.

The full BASELINE.json config suite (keygen/decaps, FrodoKEM, ML-DSA,
SPHINCS+, swarm) lives in tools/full_bench.py.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

BATCH = 4096
BASELINE_OPS_PER_S = 50_000.0
#: round-4 single-handshake warm p50 (pre-fusion; ~9-11 serial trips/hs)
SLO_BASELINE_P50_S = 1.5412
SLO_PEERS = 32


#: the --slo run FAILS (non-zero exit) when less than this fraction of the
#: warm window's ops rode the device path — the round-3 "silent CPU swarm"
#: regression (breaker open, fleet quietly degraded) is a tooling error now
SLO_MIN_DEVICE_SERVED = 0.9


def _write_slo_report(mode: str, slo: dict | None) -> None:
    """The SLO engines' burn/budget evaluation for the bench run (CI
    uploads it next to the trace and metrics artifacts;
    ``if-no-files-found: ignore`` covers runs without one).  The path is
    per-mode — ``slo_report.json`` for the ``--slo`` probe,
    ``storm_slo_report.json`` for ``--storm`` — so a session running both
    benches leaves BOTH evaluations on disk instead of the last writer
    silently replacing the other's under the slo-probe's name."""
    from pathlib import Path

    if slo is None:
        return
    name = "slo_report.json" if mode == "slo" else f"{mode}_slo_report.json"
    Path("bench_results").mkdir(exist_ok=True)
    Path(f"bench_results/{name}").write_text(
        json.dumps({"mode": mode, "slo": slo}, indent=2) + "\n")


def _write_cost_snapshot(mode: str, cost: dict | None) -> None:
    """The device-cost ledger snapshot for the bench run (obs/cost.py):
    padding waste, compile counts/seconds, opcache hit rates — written as
    ``bench_results/{mode}_cost_snapshot.json`` next to the storm
    artifacts and uploaded by ci.yml (``if-no-files-found: ignore``)."""
    from pathlib import Path

    if cost is None:
        return
    Path("bench_results").mkdir(exist_ok=True)
    Path(f"bench_results/{mode}_cost_snapshot.json").write_text(
        json.dumps({"mode": mode, "cost": cost}, indent=2,
                   sort_keys=True) + "\n")


def slo_main(out_path: str | None = None, peers: int = SLO_PEERS,
             warmup: int = 4) -> int:
    """Single-handshake SLO probe as a first-class bench output.

    Exit status gates CI: non-zero when any handshake failed OR when the
    warm run was < ``SLO_MIN_DEVICE_SERVED`` device-served (i.e. the "TPU"
    pipeline was actually the cpu fallback).
    """
    import asyncio
    import sys

    from tools.swarm_bench import run_swarm, write_obs_artifacts

    stats = asyncio.run(
        run_swarm(peers, backend="tpu", use_batching=True, max_batch=4096,
                  max_wait_ms=2.0, concurrency=1, warmup=warmup,
                  prewarm=True, slo=True)
    )
    # obs/ artifacts ride along with the SLO JSON (bench_results/): the
    # trace-event file renders the measured handshakes as flame graphs
    # (the 4-trips budget, visible), the MERGED multi-node trace puts the
    # hub and the peers on separate process lanes under the propagated
    # trace ids, and the metrics snapshot captures the queue/breaker state
    # the p50/p99 numbers were measured under
    write_obs_artifacts(stats, "bench_results", stem="slo")
    _write_slo_report("slo", stats.get("slo"))
    p50 = stats.get("p50_handshake_s")
    fraction = stats.get("device_served_fraction")
    out = {
        "metric": f"single_handshake_warm_p50_seq{peers}",
        "value": p50,
        "unit": "s",
        # latency SLO: >1 means faster than the round-4 (pre-fusion) probe
        "vs_baseline": round(SLO_BASELINE_P50_S / p50, 3) if p50 else None,
        "p99_handshake_s": stats.get("p99_handshake_s"),
        "trips_per_handshake": stats.get("trips_per_handshake"),
        "initiator_trips_p50": stats.get("initiator_trips_p50"),
        "initiator_trips_max": stats.get("initiator_trips_max"),
        "device_served_pct": stats.get("device_served_pct"),
        "device_served_fraction": fraction,
        "min_device_served_fraction": SLO_MIN_DEVICE_SERVED,
        "failures": stats.get("failures"),
        "detail": stats,
    }
    line = json.dumps(out)
    print(line)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")
    if stats.get("failures"):
        print(f"SLO FAIL: {stats['failures']} handshake failure(s)",
              file=sys.stderr)
        return 1
    if fraction is not None and fraction < SLO_MIN_DEVICE_SERVED:
        print(
            f"SLO FAIL: warm run only {fraction:.1%} device-served "
            f"(< {SLO_MIN_DEVICE_SERVED:.0%}): the device path is degraded "
            "(breaker state "
            f"{stats.get('breaker_state')!r}) — the 'TPU' numbers above "
            "measure the cpu fallback", file=sys.stderr)
        return 1
    return 0


#: storm ratchet configuration: the seeded trace the CI gate replays.
#: Moderate-load shape (bounded concurrency, paced arrival): the gateway
#: keeps up, device-served stays ~1.0, and run-to-run variance is small
#: enough for a meaningful tuned-vs-static comparison on this class of
#: host (full-saturation storms measured ±20-40% session noise).
STORM_SESSIONS = 1000
STORM_ARRIVAL_RATE = 150.0
STORM_CONCURRENCY = 128
STORM_SEED = 11
STORM_REPS = 2  # interleaved (static, tuned) pairs; metrics compared on means


def storm_main(out_path: str | None = None, sessions: int = STORM_SESSIONS,
               reps: int = STORM_REPS) -> int:
    """Gateway storm ratchet (docs/gateway.md): replay one seeded
    sustained-traffic trace under the STATIC flush policy and under the
    autotuner, write ``bench_results/storm_r0N.json``, and gate on:

    * zero failed handshakes and >= 0.9 device-served in every run;
    * the autotuner beating the static configuration on handshakes/s OR
      p99 (means over ``reps`` interleaved pairs — single-run comparisons
      flap with host noise);
    * the checked-in budget (``bench_results/storm_budget.json``), whose
      thresholds carry headroom for this host class's session variance.
    """
    import asyncio
    import statistics
    import sys
    from pathlib import Path

    from tools.swarm_bench import run_storm, write_obs_artifacts

    params = dict(
        sessions=sessions, arrival_rate=STORM_ARRIVAL_RATE,
        concurrency=STORM_CONCURRENCY, msgs_per_session=2, rekey_every=2,
        churn_fraction=0.1, seed=STORM_SEED,
    )
    runs: dict[bool, list[dict]] = {False: [], True: []}
    for _ in range(reps):
        for tuned in (False, True):  # interleaved: host drift hits both
            runs[tuned].append(
                asyncio.run(run_storm(autotune=tuned, **params)))

    def agg(tuned: bool, key: str) -> float:
        return round(statistics.mean(r[key] for r in runs[tuned]), 4)

    failures = sum(r["failures"] for rs in runs.values() for r in rs)
    min_served = min(r["device_served_fraction"] or 0.0
                     for rs in runs.values() for r in rs)
    tuned_hs, static_hs = agg(True, "handshakes_per_s"), agg(False, "handshakes_per_s")
    tuned_p99, static_p99 = agg(True, "p99_handshake_s"), agg(False, "p99_handshake_s")
    beats = tuned_hs >= static_hs or tuned_p99 <= static_p99

    budget_path = Path("bench_results/storm_budget.json")
    budget = (json.loads(budget_path.read_text()) if budget_path.exists()
              else None)
    out = {
        "metric": f"storm_{sessions}_sessions_handshakes_per_s",
        "value": tuned_hs,
        "unit": "handshakes/s",
        "vs_baseline": (round(tuned_hs / budget["min_handshakes_per_s"], 3)
                        if budget else None),
        "sessions": sessions,
        "reps_per_config": reps,
        "failures": failures,
        "min_device_served_fraction": min_served,
        "tuned": {"handshakes_per_s": tuned_hs, "p99_handshake_s": tuned_p99,
                  "p99_rekey_s": agg(True, "p99_rekey_s"),
                  "runs": runs[True]},
        "static": {"handshakes_per_s": static_hs,
                   "p99_handshake_s": static_p99,
                   "p99_rekey_s": agg(False, "p99_rekey_s"),
                   "runs": runs[False]},
        "autotuner_beats_static": beats,
        "budget": budget,
        "ok": True,
    }
    # obs artifacts for the LAST (tuned) storm window: merged multi-node
    # trace + metrics snapshot, plus the SLO engines' burn report and the
    # device-cost ledger snapshot (padding waste / compiles / opcache)
    write_obs_artifacts(out, "bench_results", stem="storm")
    _write_slo_report("storm", runs[True][-1].get("slo"))
    _write_cost_snapshot("storm", runs[True][-1].get("cost"))
    rc = 0
    if failures:
        print(f"STORM FAIL: {failures} handshake failure(s)", file=sys.stderr)
        rc = 1
    if min_served < SLO_MIN_DEVICE_SERVED:
        print(f"STORM FAIL: a run was only {min_served:.1%} device-served "
              f"(< {SLO_MIN_DEVICE_SERVED:.0%})", file=sys.stderr)
        rc = 1
    if not beats:
        print(f"STORM FAIL: autotuner beat static on neither handshakes/s "
              f"({tuned_hs} vs {static_hs}) nor p99 ({tuned_p99}s vs "
              f"{static_p99}s)", file=sys.stderr)
        rc = 1
    if budget is not None:
        if tuned_hs < budget["min_handshakes_per_s"]:
            print(f"STORM FAIL: {tuned_hs} handshakes/s under the budget "
                  f"floor {budget['min_handshakes_per_s']}", file=sys.stderr)
            rc = 1
        if tuned_p99 > budget["max_p99_handshake_s"]:
            print(f"STORM FAIL: p99 {tuned_p99}s over the budget cap "
                  f"{budget['max_p99_handshake_s']}s", file=sys.stderr)
            rc = 1
    out["ok"] = rc == 0
    line = json.dumps(out)
    print(line)
    Path("bench_results").mkdir(exist_ok=True)
    n = 1
    while Path(f"bench_results/storm_r{n:02d}.json").exists():
        n += 1
    Path(f"bench_results/storm_r{n:02d}.json").write_text(line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    return rc


#: bulk-mix storm ratchet configuration (docs/gateway.md "Bulk-heavy
#: storms"): a bulk-heavy seeded trace (15 messages/session, 8 KiB
#: payloads) replayed twice — once on the scalar ChaCha20-Poly1305 path,
#: once through the batched device AEAD — and gated on the speedup.
#: 8 KiB payloads make the AEAD the dominant per-message cost (the shape
#: the data plane exists for — small-payload storms measure the Python
#: protocol loop, which both paths share); concurrency is a power of two
#: so every coalesced flush lands on a prewarmed pow2 batch bucket.
BULK_SESSIONS = 48
BULK_MSGS_PER_SESSION = 15
BULK_PAYLOAD_BYTES = 8192
BULK_CONCURRENCY = 64
BULK_ARRIVAL_RATE = 30.0
#: the tentpole's ratchet: batched bulk messages/s must beat the scalar
#: path by at least this factor, with zero failures and a p99 bound
MIN_BULK_SPEEDUP = 5.0
MAX_BULK_P99_MSG_S = 1.0


def bulk_storm_main(out_path: str | None = None,
                    sessions: int = BULK_SESSIONS,
                    msgs_per_session: int = BULK_MSGS_PER_SESSION) -> int:
    """Bulk-heavy storm ratchet (the data-plane gate): replay one seeded
    bulk-mix trace on the SCALAR ChaCha20-Poly1305 path and through the
    BATCHED device AEAD + binary wire, write
    ``bench_results/bulk_storm_r0N.json``, and gate on:

    * zero failed handshakes/sends in both runs;
    * batched bulk messages/s >= ``MIN_BULK_SPEEDUP`` x the scalar path;
    * batched p99 per-message latency <= ``MAX_BULK_P99_MSG_S``;
    * the batched run >= ``SLO_MIN_DEVICE_SERVED`` device-served (a
      quietly-degraded data plane must not pass on fallback numbers).

    Small session counts (tools/ci_smoke.sh) run in smoke mode: gates on
    failures only — sub-noise-floor ratio comparisons and the committed
    artifact are full-size-run territory.
    """
    import asyncio
    import sys
    from pathlib import Path

    from tools.swarm_bench import run_storm

    smoke = sessions < BULK_SESSIONS
    params = dict(
        sessions=sessions, arrival_rate=BULK_ARRIVAL_RATE,
        concurrency=BULK_CONCURRENCY, msgs_per_session=msgs_per_session,
        payload_bytes=BULK_PAYLOAD_BYTES, seed=STORM_SEED,
    )
    # untimed warm pass: compiles the batched AEAD's live (batch, length)
    # buckets so the measured window starts device-served (the in-process
    # jit cache persists across run_storm calls)
    asyncio.run(run_storm(aead_mode="chacha",
                          **{**params, "sessions": min(24, sessions)}))
    batched = asyncio.run(run_storm(aead_mode="chacha", **params))
    scalar = asyncio.run(run_storm(aead_mode="chacha-scalar", **params))

    speedup = (round(batched["msgs_per_s"] / scalar["msgs_per_s"], 2)
               if scalar["msgs_per_s"] else None)
    out = {
        "metric": (f"bulk_storm_{sessions}x{msgs_per_session}"
                   f"x{BULK_PAYLOAD_BYTES}B_msgs_per_s"),
        "value": batched["msgs_per_s"],
        "unit": "msgs/s",
        "vs_baseline": speedup,  # the scalar path IS the baseline
        "min_speedup": MIN_BULK_SPEEDUP,
        "max_p99_msg_s": MAX_BULK_P99_MSG_S,
        "speedup": speedup,
        "batched": batched,
        "scalar": scalar,
        "ok": True,
    }
    rc = 0
    failures = batched["failures"] + scalar["failures"]
    if failures:
        print(f"BULK STORM FAIL: {failures} failure(s)", file=sys.stderr)
        rc = 1
    if not smoke:
        if speedup is None or speedup < MIN_BULK_SPEEDUP:
            print(f"BULK STORM FAIL: batched path only {speedup}x the "
                  f"scalar baseline (< {MIN_BULK_SPEEDUP}x): "
                  f"{batched['msgs_per_s']} vs {scalar['msgs_per_s']} msgs/s",
                  file=sys.stderr)
            rc = 1
        if (batched["p99_msg_s"] or 0) > MAX_BULK_P99_MSG_S:
            print(f"BULK STORM FAIL: batched p99 message latency "
                  f"{batched['p99_msg_s']}s over the {MAX_BULK_P99_MSG_S}s "
                  "bound", file=sys.stderr)
            rc = 1
        served = batched["device_served_fraction"] or 0.0
        if served < SLO_MIN_DEVICE_SERVED:
            print(f"BULK STORM FAIL: batched run only {served:.1%} "
                  f"device-served (< {SLO_MIN_DEVICE_SERVED:.0%}) — the "
                  "'batched' numbers measure the scalar fallback",
                  file=sys.stderr)
            rc = 1
    out["ok"] = rc == 0
    line = json.dumps(out)
    print(line)
    if not smoke:
        Path("bench_results").mkdir(exist_ok=True)
        n = 1
        while Path(f"bench_results/bulk_storm_r{n:02d}.json").exists():
            n += 1
        Path(f"bench_results/bulk_storm_r{n:02d}.json").write_text(line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    return rc


#: fleet chaos ratchet configuration (docs/fleet.md): the seeded
#: gateway-death storm the CI gate replays.  gw1 is SIGKILLed on its 8th
#: fleet health tick (~2 s in, mid-ramp at the paced arrival rate), so a
#: slice of live and in-flight sessions really does lose its gateway.
FLEET_GATEWAYS = 3
FLEET_KILL_GATEWAY = "gw1"
FLEET_KILL_TICK = 8


def fleet_storm_main(out_path: str | None = None,
                     sessions: int = STORM_SESSIONS,
                     gateways: int = FLEET_GATEWAYS,
                     spawn: str = "process") -> int:
    """Fleet chaos-storm ratchet (docs/fleet.md): replay ONE seeded
    sustained-traffic trace through ``gateways`` gateway PROCESSES behind
    the consistent-hash router, SIGKILL ``gw1`` mid-storm via the fault
    plan's process scope, write ``bench_results/fleet_storm_r0N.json``,
    and gate on the chaos number:

    * **zero lost established sessions** — every session that completed a
      handshake finished its workload (re-routed to the ring successor
      and re-keyed where needed);
    * **zero plaintext sends** (structural: the engine refuses to send
      without a shared key);
    * fleet ``device_served_fraction`` >= ``SLO_MIN_DEVICE_SERVED``
      across every gateway process plus the client plane;
    * the kill actually fired (the seeded ``injected`` log is non-empty)
      and the handshake-failure burst stayed BOUNDED — no larger than one
      concurrency window of attempts.

    ``--fleet 1`` runs the same harness with a single gateway and no kill
    (there is no successor to hand off to) — the within-noise comparison
    point against the single-process ``storm_r0N.json`` gate.
    """
    import asyncio
    import sys
    from pathlib import Path

    from quantum_resistant_p2p_tpu.fleet.storm import (
        default_kill_rules, run_fleet_storm, write_fleet_artifacts)
    from tools.swarm_bench import write_obs_artifacts

    # smoke mode (tools/ci_smoke.sh): a small session count finishes well
    # before the ratchet's ~2 s kill point, so tighten the heartbeat and
    # kill tick to keep the death genuinely MID-storm — and skip the
    # committed-artifact writes, which record official full-size runs only
    smoke = sessions < 500
    hb_interval = 0.1 if smoke else 0.25
    kill_tick = 4 if smoke else FLEET_KILL_TICK
    rules = (default_kill_rules(FLEET_KILL_GATEWAY, kill_tick)
             if gateways > 1 else None)
    # only the full-size CHAOS config owns the committed per-node reports
    # (the files ci.yml uploads): smoke runs and the --fleet 1 parity run
    # must not overwrite them — None -> run_fleet_storm uses a tempdir
    chaos_run = rules is not None
    report_dir = (Path("bench_results/fleet_reports")
                  if chaos_run and not smoke else None)
    # live telemetry rides every fleet ratchet run: one scrapeable
    # endpoint per gateway (announced via hello/heartbeat) and a mid-storm
    # qrtop --snapshot against them — the committed
    # fleet_storm_cost_snapshot.json is produced by the SAME scrape path
    # a human's dashboard uses (tools/qrtop.py)
    from tools.qrtop import snapshot_endpoints

    out = asyncio.run(run_fleet_storm(
        sessions, gateways=gateways, seed=STORM_SEED,
        arrival_rate=STORM_ARRIVAL_RATE, concurrency=STORM_CONCURRENCY,
        msgs_per_session=2, spawn=spawn, fault_rules=rules,
        hb_interval=hb_interval, report_dir=report_dir,
        telemetry=True, scrape_cb=snapshot_endpoints,
    ))
    served = out["device_served_fraction"] or 0.0
    burst_budget = STORM_CONCURRENCY
    out.update({
        "metric": f"fleet_storm_{sessions}x{gateways}_lost_established",
        "value": out["lost_established_sessions"],
        "unit": "sessions",
        "vs_baseline": None,
        "burst_budget": burst_budget,
    })
    rc = 0
    if out["lost_established_sessions"]:
        print(f"FLEET STORM FAIL: {out['lost_established_sessions']} "
              "established session(s) lost", file=sys.stderr)
        rc = 1
    if out["plaintext_sends"]:
        print(f"FLEET STORM FAIL: {out['plaintext_sends']} plaintext "
              "send(s)", file=sys.stderr)
        rc = 1
    if served < SLO_MIN_DEVICE_SERVED:
        print(f"FLEET STORM FAIL: fleet only {served:.1%} device-served "
              f"(< {SLO_MIN_DEVICE_SERVED:.0%})", file=sys.stderr)
        rc = 1
    if rules is not None and not out.get("chaos", {}).get("injected"):
        print("FLEET STORM FAIL: the seeded gateway kill never fired",
              file=sys.stderr)
        rc = 1
    if out["handshake_failures"] > burst_budget:
        print(f"FLEET STORM FAIL: handshake-failure burst "
              f"{out['handshake_failures']} exceeds one concurrency window "
              f"({burst_budget})", file=sys.stderr)
        rc = 1
    out["ok"] = rc == 0
    line = json.dumps(out)
    print(line)
    if not smoke:
        if chaos_run:
            # the shared artifact names (traces, merged fleet SLO) record
            # the flagship chaos run, never the parity comparison point
            write_obs_artifacts(out, "bench_results", stem="fleet_storm")
            write_fleet_artifacts(out, "bench_results")
            _write_cost_snapshot("fleet_storm", {
                "snapshot": out.get("cost_snapshot"),
                "fleet_totals": out.get("fleet_cost"),
                "telemetry": out.get("telemetry"),
            })
        Path("bench_results").mkdir(exist_ok=True)
        n = 1
        while Path(f"bench_results/fleet_storm_r{n:02d}.json").exists():
            n += 1
        Path(f"bench_results/fleet_storm_r{n:02d}.json").write_text(line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    return rc


#: resume-mix storm ratchet configuration (docs/protocol.md "Session
#: resumption"): every session drops its TCP connection mid-workload and
#: re-establishes — with a held ticket that is a 1-RTT resume.  The gates
#: pin the three claims the resumption machinery makes: reconnects
#: actually resume (rate), resumes are CHEAP (p50 under the full
#: handshake's), and they cost ~0 device-seconds (the sequential probe).
RESUME_SESSIONS = 400
RESUME_MSGS_PER_SESSION = 4
RESUME_CONCURRENCY = 128
RESUME_ARRIVAL_RATE = 150.0
MIN_RESUME_RATE = 0.9


def resume_storm_main(out_path: str | None = None,
                      sessions: int = RESUME_SESSIONS) -> int:
    """Resume-mix storm ratchet: one seeded trace where every session
    reconnects mid-workload via its resumption ticket.  Writes
    ``bench_results/resume_storm_r0N.json`` and gates on:

    * zero failures (every reconnect ends established — fallback included);
    * ticket-resume rate >= ``MIN_RESUME_RATE`` (reconnects actually skip
      the KEM + 3 signatures);
    * resume p50 <= full-handshake p50 (the abbreviated exchange is the
      cheap path it claims to be);
    * the sequential cost probe's device trips stay ~0 (no device dispatch
      rides a resume — at most a straggler flush from the storm tail).
    """
    import asyncio
    import sys
    from pathlib import Path

    from tools.swarm_bench import run_storm

    smoke = sessions < 48
    out = asyncio.run(run_storm(
        sessions, seed=STORM_SEED, arrival_rate=RESUME_ARRIVAL_RATE,
        concurrency=RESUME_CONCURRENCY,
        msgs_per_session=RESUME_MSGS_PER_SESSION, resume_mix=True,
    ))
    rate = out.get("ticket_resume_rate") or 0.0
    probe = out.get("resume_cost_probe") or {}
    out.update({
        "metric": f"resume_storm_{sessions}_sessions_resume_rate",
        "value": rate,
        "unit": "fraction",
        "vs_baseline": None,
    })
    rc = 0
    if out["failures"]:
        print(f"RESUME STORM FAIL: {out['failures']} failed session(s)",
              file=sys.stderr)
        rc = 1
    if rate < MIN_RESUME_RATE:
        print(f"RESUME STORM FAIL: ticket-resume rate {rate:.1%} < "
              f"{MIN_RESUME_RATE:.0%}", file=sys.stderr)
        rc = 1
    p50_resume = out.get("p50_resume_s")
    p50_full = out.get("p50_handshake_s")
    if (p50_resume is not None and p50_full is not None
            and p50_resume > p50_full):
        print(f"RESUME STORM FAIL: resume p50 {p50_resume}s slower than "
              f"the full handshake's {p50_full}s", file=sys.stderr)
        rc = 1
    if probe and probe.get("resumes") and (
            probe.get("device_trips", 0) > probe["resumes"] // 2):
        print(f"RESUME STORM FAIL: {probe['device_trips']} device trips "
              f"across {probe['resumes']} pure resumes — resumes are "
              "supposed to cost ~0 device dispatches", file=sys.stderr)
        rc = 1
    out["ok"] = rc == 0
    line = json.dumps(out)
    print(line)
    if not smoke:
        Path("bench_results").mkdir(exist_ok=True)
        n = 1
        while Path(f"bench_results/resume_storm_r{n:02d}.json").exists():
            n += 1
        Path(f"bench_results/resume_storm_r{n:02d}.json").write_text(
            line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    return rc


#: fleet rolling-restart ratchet configuration (docs/robustness.md
#: "Rolling restarts"): the full fleet-storm trace with a mid-storm
#: rolling SIGTERM restart of EVERY gateway plus one SIGKILL — the
#: planned-maintenance and the crash case in one run.  gw2 is killed
#: late enough that the roll is already in flight.
ROLL_DELAY_S = 2.0
ROLL_KILL_GATEWAY = "gw2"
ROLL_KILL_TICK = 16
MIN_POST_ROLL_RESUME_RATE = 0.9


def fleet_roll_main(out_path: str | None = None,
                    sessions: int = STORM_SESSIONS,
                    gateways: int = FLEET_GATEWAYS,
                    spawn: str = "process") -> int:
    """Fleet rolling-restart chaos ratchet: replay the seeded fleet trace
    while ``GatewayFleet.rolling_restart()`` drains + respawns every
    gateway mid-storm and the fault plan SIGKILLs one.  Writes
    ``bench_results/fleet_roll_r0N.json`` and gates on:

    * **zero lost established sessions** and **zero plaintext sends** —
      the fleet-storm invariants hold through a full rolling restart;
    * >= ``MIN_POST_ROLL_RESUME_RATE`` of post-restart reconnects resumed
      VIA TICKET (not full handshake) — the reconnect wave after a
      restart is the cheap path, which is the whole point of ISSUE 15;
    * the rolling restart itself completed (every gateway re-registered).
    """
    import asyncio
    import sys
    from pathlib import Path

    from quantum_resistant_p2p_tpu.fleet.storm import (default_kill_rules,
                                                       run_fleet_storm)
    from tools.swarm_bench import write_obs_artifacts

    smoke = sessions < 500
    hb_interval = 0.1 if smoke else 0.25
    # smoke runs pace arrivals slowly enough that sessions are genuinely
    # IN FLIGHT when the roll begins (a burst of tiny sessions finishes
    # before any gateway drains and proves nothing)
    roll_delay = 0.8 if smoke else ROLL_DELAY_S
    arrival = min(STORM_ARRIVAL_RATE, sessions / 3.0) if smoke \
        else STORM_ARRIVAL_RATE
    # the SIGKILL rides only the full-size chaos run with >= 3 gateways
    # (a 2-gateway smoke losing one to a kill AND one to a drain has no
    # capacity left to hand off to)
    rules = (default_kill_rules(ROLL_KILL_GATEWAY, ROLL_KILL_TICK)
             if not smoke and gateways > 2 else None)
    out = asyncio.run(run_fleet_storm(
        sessions, gateways=gateways, seed=STORM_SEED,
        arrival_rate=arrival, concurrency=STORM_CONCURRENCY,
        msgs_per_session=8, spawn=spawn, fault_rules=rules,
        hb_interval=hb_interval, roll=True, roll_delay_s=roll_delay,
        session_attempts=8, msg_interval_s=0.1 if smoke else 0.05,
    ))
    out.update({
        "metric": f"fleet_roll_{sessions}x{gateways}_lost_established",
        "value": out["lost_established_sessions"],
        "unit": "sessions",
        "vs_baseline": None,
    })
    rc = 0
    if out["lost_established_sessions"]:
        print(f"FLEET ROLL FAIL: {out['lost_established_sessions']} "
              "established session(s) lost", file=sys.stderr)
        rc = 1
    if out["plaintext_sends"]:
        print(f"FLEET ROLL FAIL: {out['plaintext_sends']} plaintext "
              "send(s)", file=sys.stderr)
        rc = 1
    if not (out.get("roll") or {}).get("ok"):
        print("FLEET ROLL FAIL: the rolling restart did not complete "
              "(a gateway never re-registered)", file=sys.stderr)
        rc = 1
    post = (out.get("post_roll_resumed") or 0) + (out.get("post_roll_full")
                                                  or 0)
    rate = out.get("post_roll_resume_rate")
    if smoke:
        # smoke gate: at least ONE displaced session must have resumed
        # via ticket (tiny smokes produce a handful of reconnects)
        if not out.get("resumed_reconnects"):
            print("FLEET ROLL FAIL: no ticket resume observed across the "
                  "rolling restart", file=sys.stderr)
            rc = 1
    elif post and (rate or 0.0) < MIN_POST_ROLL_RESUME_RATE:
        print(f"FLEET ROLL FAIL: post-restart ticket-resume rate "
              f"{rate:.1%} < {MIN_POST_ROLL_RESUME_RATE:.0%} "
              f"({out['post_roll_resumed']}/{post})", file=sys.stderr)
        rc = 1
    if rules is not None and not out.get("chaos", {}).get("injected"):
        print("FLEET ROLL FAIL: the seeded mid-roll gateway kill never "
              "fired", file=sys.stderr)
        rc = 1
    out["ok"] = rc == 0
    line = json.dumps(out)
    print(line)
    if not smoke:
        # fleet_roll_* obs artifacts only: the shared fleet_slo_report.json
        # name stays owned by the flagship kill-storm run
        write_obs_artifacts(out, "bench_results", stem="fleet_roll")
        Path("bench_results").mkdir(exist_ok=True)
        n = 1
        while Path(f"bench_results/fleet_roll_r{n:02d}.json").exists():
            n += 1
        Path(f"bench_results/fleet_roll_r{n:02d}.json").write_text(
            line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    return rc


ROUTER_ROLL_ROUTERS = 2
ROUTER_KILL_TICK = 8
MIN_POST_FAILOVER_RESUME_RATE = 0.9


def router_roll_main(out_path: str | None = None,
                     sessions: int = STORM_SESSIONS,
                     gateways: int = FLEET_GATEWAYS,
                     routers: int = ROUTER_ROLL_ROUTERS,
                     spawn: str = "process") -> int:
    """Router-roll chaos ratchet (``--storm --fleet N --router-roll``):
    the control plane is ``routers`` replicated router processes behind a
    leader lease (fleet/router.py), and the chaos targets THEM — the
    seeded fault plan SIGKILLs the leader replica mid-storm, then a
    rolling restart cycles every router while the sessions run.  Writes
    ``bench_results/router_roll_r0N.json`` and gates on:

    * **zero lost established sessions** and **zero plaintext sends** —
      router death moves routing + STEK authority, never the data plane;
    * >= ``MIN_POST_FAILOVER_RESUME_RATE`` of post-failover reconnects
      resumed VIA TICKET — tickets minted under the dead leader's STEK
      still redeem after the lease moves (the replicated dual-key
      window, docs/fleet.md "HA control plane");
    * the seeded leader kill fired and the rolling restart completed.
    """
    import asyncio
    import sys
    from pathlib import Path

    from quantum_resistant_p2p_tpu.fleet.storm import (
        default_router_kill_rules, run_router_storm)
    from tools.swarm_bench import write_obs_artifacts

    smoke = sessions < 500
    hb_interval = 0.1 if smoke else 0.25
    roll_delay = 1.2 if smoke else ROLL_DELAY_S
    arrival = min(STORM_ARRIVAL_RATE, sessions / 3.0) if smoke \
        else STORM_ARRIVAL_RATE
    # rt0 (rank 0) claims first by construction, so the kill rule names
    # the replica that IS the leader when the storm opens
    rules = default_router_kill_rules("rt0", ROUTER_KILL_TICK)
    out = asyncio.run(run_router_storm(
        sessions, gateways=gateways, routers=routers, seed=STORM_SEED,
        arrival_rate=arrival, concurrency=STORM_CONCURRENCY,
        msgs_per_session=8, spawn=spawn, fault_rules=rules,
        hb_interval=hb_interval, roll=True, roll_delay_s=roll_delay,
        session_attempts=8, msg_interval_s=0.1 if smoke else 0.05,
        lease_ttl_s=0.8 if smoke else 1.0,
    ))
    out.update({
        "metric": (f"router_roll_{sessions}x{gateways}gw{routers}rt"
                   "_lost_established"),
        "value": out["lost_established_sessions"],
        "unit": "sessions",
        "vs_baseline": None,
    })
    rc = 0
    if out["lost_established_sessions"]:
        print(f"ROUTER ROLL FAIL: {out['lost_established_sessions']} "
              "established session(s) lost", file=sys.stderr)
        rc = 1
    if out["plaintext_sends"]:
        print(f"ROUTER ROLL FAIL: {out['plaintext_sends']} plaintext "
              "send(s)", file=sys.stderr)
        rc = 1
    if not out.get("chaos", {}).get("injected"):
        print("ROUTER ROLL FAIL: the seeded leader SIGKILL never fired",
              file=sys.stderr)
        rc = 1
    if not (out.get("roll") or {}).get("ok"):
        print("ROUTER ROLL FAIL: the router rolling restart did not "
              "complete (a replica never came back)", file=sys.stderr)
        rc = 1
    post = (out.get("post_failover_resumed") or 0) + (
        out.get("post_failover_full") or 0)
    rate = out.get("post_failover_resume_rate")
    if smoke:
        # smoke gate: at least one reconnect AFTER the failover must have
        # redeemed a ticket minted before it
        if not out.get("post_failover_resumed"):
            print("ROUTER ROLL FAIL: no post-failover ticket resume "
                  "observed", file=sys.stderr)
            rc = 1
    elif not post:
        print("ROUTER ROLL FAIL: no reconnects landed after the "
              "failover — the storm proves nothing", file=sys.stderr)
        rc = 1
    elif (rate or 0.0) < MIN_POST_FAILOVER_RESUME_RATE:
        print(f"ROUTER ROLL FAIL: post-failover ticket-resume rate "
              f"{rate:.1%} < {MIN_POST_FAILOVER_RESUME_RATE:.0%} "
              f"({out['post_failover_resumed']}/{post})", file=sys.stderr)
        rc = 1
    out["ok"] = rc == 0
    line = json.dumps(out)
    print(line)
    if not smoke:
        write_obs_artifacts(out, "bench_results", stem="router_roll")
        Path("bench_results").mkdir(exist_ok=True)
        n = 1
        while Path(f"bench_results/router_roll_r{n:02d}.json").exists():
            n += 1
        Path(f"bench_results/router_roll_r{n:02d}.json").write_text(
            line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    return rc


def multichip_main(out_path: str | None, shards: str, hs_peers: int,
                   emulate: int) -> int:
    """1→N-chip scaling probe (tools/swarm_bench.run_multichip): batch-4096
    ML-KEM-768 encaps/s on a batch-sharded mesh plus warm handshakes/s
    through the placement scheduler, at each shard count.  Writes the
    scaling-curve JSON (a REAL ``MULTICHIP_r0N.json`` — earlier rounds
    only recorded reachability) to ``--out`` and, for the CI artifact, to
    ``bench_results/multichip_scaling.json``.

    Exit status: non-zero when any shard count's handshake window had
    failures (reachability-only environments still exit 0 with the
    encaps-only curve).
    """
    import sys

    from tools.swarm_bench import run_multichip

    counts = tuple(int(c) for c in shards.split(",") if c)
    out = run_multichip(shard_counts=counts, hs_peers=hs_peers,
                        emulate=emulate)
    line = json.dumps(out)
    print(line)
    from pathlib import Path

    Path("bench_results").mkdir(exist_ok=True)
    Path("bench_results/multichip_scaling.json").write_text(line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    failures = sum(e.get("failures") or 0 for e in out["shards"].values())
    if failures:
        print(f"MULTICHIP FAIL: {failures} handshake failure(s) across the "
              "scaling sweep", file=sys.stderr)
        return 1
    return 0


#: default dispatch rows for the --raw-ops --family frodo probe: a full
#: lane tile x2 (the kernel's (8, 128) layout) — big enough to amortise
#: a dispatch's fixed round trip, small enough for CPU-twin smoke runs
FRODO_RAW_BATCH = 256
#: the frodo raw-ops probe FAILS when less than this fraction of its ops
#: rode the device path (same bar as --slo): a silently-degraded kernel
#: path must not report fallback numbers as device numbers
FRODO_MIN_DEVICE_SERVED = SLO_MIN_DEVICE_SERVED


def frodo_raw_ops_main(out_path: str | None = None,
                       batch: int = FRODO_RAW_BATCH,
                       name: str = "FrodoKEM-640-SHAKE") -> int:
    """Raw-ops probe for the FrodoKEM device path (``--raw-ops --family
    frodo``): keygen / cold encaps / warm (operand-cached) encaps / decaps
    per second at ``batch`` rows, same forced-readback methodology as the
    ML-KEM headline (device-resident operands, 1-element readback fence).

    The run is gated the way the SLO probe is: the pinned pyref KAT must
    pass through the device path FIRST (provider/health.py), and the cost
    ledger's ``device_served_fraction`` over the run must stay >=
    ``FRODO_MIN_DEVICE_SERVED`` — a minimal image whose kernel path
    regressed to fallback exits non-zero instead of shipping wrong numbers.
    """
    import sys
    from pathlib import Path

    import jax

    from quantum_resistant_p2p_tpu.kem import frodo
    from quantum_resistant_p2p_tpu.obs.cost import CostLedger
    from quantum_resistant_p2p_tpu.provider import health
    from quantum_resistant_p2p_tpu.provider.kem_providers import (
        FrodoKEMKeyExchange)
    from quantum_resistant_p2p_tpu.utils.benchmarking import sync, timeit
    from quantum_resistant_p2p_tpu.utils.compile_cache import (
        enable_compile_cache)

    enable_compile_cache()
    level = {"FrodoKEM-640-SHAKE": 1, "FrodoKEM-976-SHAKE": 3,
             "FrodoKEM-1344-SHAKE": 5}[name]
    kem = FrodoKEMKeyExchange(security_level=level, backend="tpu",
                              use_aes=False)
    p = kem.params
    ledger = CostLedger()
    kem.opcache.attach_cost(ledger, "frodo_pk")
    ops_done = 0
    ledger.set_handshakes_fn(lambda: max(ops_done, 1))

    verdict = health._check_frodo_kat(kem)
    short = name.replace("FrodoKEM-", "frodo").replace("-SHAKE", "shake")
    out: dict = {
        "metric": f"{short}_encaps_warm_batch{batch}",
        "unit": "encaps/s",
        "vs_baseline": None,  # no committed frodo baseline before this round
        "platform": jax.devices()[0].platform,
        "batch": batch,
        "kat_ok": bool(verdict.ok),
        "kat_detail": verdict.detail,
        "min_device_served_fraction": FRODO_MIN_DEVICE_SERVED,
    }
    rc = 0
    if not verdict.ok:
        # every op this run WOULD have done is a bypass: the device path
        # is not trustworthy, so nothing below is worth timing
        ledger.bypass_items("frodo.encaps", "kat_failed", batch)
        out.update({"value": None, "device_served_fraction": 0.0})
        rc = 1
    else:
        rng = np.random.default_rng(640)

        def dev(shape):
            a = jax.device_put(
                rng.integers(0, 256, size=shape, dtype=np.uint8))
            sync(a)
            return a

        kg, _, dec = frodo.get(p.name)
        enc_cold, enc_pre = frodo.get_pre(p.name)
        s, se, z = (dev((batch, p.len_sec)) for _ in range(3))
        mu = dev((batch, p.len_sec))
        pk, sk = kg(s, se, z)
        sync((pk, sk))
        keygen_s = timeit(lambda: kg(s, se, z))
        # single-key batch (the handshake shape): cold fills the per-key
        # operand cache in one dispatch, warm reuses the device-resident
        # expanded A matrix — the provider's opcache fast path
        pk0 = jax.device_put(np.asarray(pk)[0])
        sync(pk0)
        cold_s = timeit(lambda: enc_cold(pk0, mu))
        pre, ct, ss = enc_cold(pk0, mu)
        sync((ct, ss))
        warm_s = timeit(lambda: enc_pre(pre, mu))
        skb = jax.device_put(np.broadcast_to(np.asarray(sk)[0],
                                             (batch, p.sk_len)))
        sync(skb)
        decaps_s = timeit(lambda: dec(skb, ct))
        for op, secs in (("keygen", keygen_s), ("encaps_cold", cold_s),
                         ("encaps_warm", warm_s), ("decaps", decaps_s)):
            # full rows, full bucket: raw ops pad nothing — the padding
            # waste the ledger reports is genuinely the dispatch shape's
            ledger.flush_occupancy(f"frodo.{op}", "bulk", batch, batch)
            ledger.device_time(f"frodo.{op}", secs)
            ops_done += batch
        # provider surface: one cold + one warm single-key batch so the
        # opcache accounting (hit rate, device-served story) reflects the
        # path handshakes actually take
        pks = np.broadcast_to(np.asarray(pk)[0], (batch, p.pk_len)).copy()
        for _ in range(2):
            kem.encapsulate_batch(pks)
            ledger.flush_occupancy("frodo.encaps_provider", "bulk", batch,
                                   batch)
            ops_done += batch
        served = ledger.device_served_fraction()
        totals = ledger.totals()
        out.update({
            "value": round(batch / warm_s, 1),
            "keygen_per_s": round(batch / keygen_s, 1),
            "encaps_cold_per_s": round(batch / cold_s, 1),
            "encaps_warm_per_s": round(batch / warm_s, 1),
            "decaps_per_s": round(batch / decaps_s, 1),
            "warm_vs_cold": round(cold_s / warm_s, 2),
            "device_served_fraction": served,
            "device_seconds_per_1k_ops":
                ledger.device_seconds_per_1k_handshakes(),
            "padding_waste_fraction": ledger.padding_waste_fraction(),
            "opcache": kem.opcache.stats(),
            "cost": totals,
        })
        if (served or 0.0) < FRODO_MIN_DEVICE_SERVED:
            print(f"RAW-OPS FAIL: frodo run only {(served or 0.0):.1%} "
                  f"device-served (< {FRODO_MIN_DEVICE_SERVED:.0%})",
                  file=sys.stderr)
            rc = 1
    if not out["kat_ok"]:
        print(f"RAW-OPS FAIL: frodo device KAT failed: {verdict.detail}",
              file=sys.stderr)
    line = json.dumps(out)
    print(line)
    Path("bench_results").mkdir(exist_ok=True)
    Path("bench_results/frodo_raw_ops.json").write_text(line + "\n")
    if out_path:
        Path(out_path).write_text(line + "\n")
    return rc


def main() -> None:
    from quantum_resistant_p2p_tpu.kem import mlkem
    from quantum_resistant_p2p_tpu.utils.benchmarking import sync, timeit
    from quantum_resistant_p2p_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # The 4096 batch runs as back-to-back dispatches at TWO dispatch sizes,
    # both emitted (an ADVICE round-3 item: the headline must carry its
    # dispatch size, since the two differ ~6%):
    #   * 2048 rows — two full grid steps of the fused Pallas SampleNTT
    #     kernel (the top of an earlier platform's scaling plateau; not
    #     measured on this chip) — this is the headline "value";
    #   * 1024 rows — MAX_DEVICE_BATCH, what the shipped provider actually
    #     dispatches (kept lower for queue latency) — emitted as
    #     "value_at_provider_dispatch".
    # Raw-ops methodology: operands stay device-resident between dispatches;
    # the provider's per-slice host work and host<->device copies are
    # excluded here and measured by the swarm benchmark instead.
    import jax

    kg, enc, _ = mlkem.get("ML-KEM-768")
    rng = np.random.default_rng(0)

    def measure(step: int) -> float:
        assert BATCH % step == 0, "ops_per_s assumes reps * step == BATCH"
        reps = BATCH // step
        d = rng.integers(0, 256, size=(step, 32), dtype=np.uint8)
        z = rng.integers(0, 256, size=(step, 32), dtype=np.uint8)
        m = rng.integers(0, 256, size=(step, 32), dtype=np.uint8)
        ek, _ = kg(d, z)
        sync(ek)
        # Device-resident operands per the raw-ops methodology above (ek
        # already lives on device as kg's output; without this, every
        # dispatch re-sends m from the host).
        m = jax.device_put(m)
        sync(m)

        def run():
            out = None
            for _ in range(reps):
                out = enc(ek, m)
            return out

        return BATCH / timeit(run)

    provider_step = mlkem.MAX_DEVICE_BATCH
    plateau_step = 2 * mlkem.MAX_DEVICE_BATCH
    at_provider = measure(provider_step)
    at_plateau = measure(plateau_step)
    print(
        json.dumps(
            {
                "metric": f"mlkem768_encaps_batch4096_dispatch{plateau_step}",
                "value": round(at_plateau, 1),
                "unit": "encaps/s",
                "vs_baseline": round(at_plateau / BASELINE_OPS_PER_S, 3),
                "dispatch_rows": plateau_step,
                "value_at_provider_dispatch": round(at_provider, 1),
                "provider_dispatch_rows": provider_step,
                "vs_baseline_at_provider_dispatch": round(
                    at_provider / BASELINE_OPS_PER_S, 3
                ),
            }
        )
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slo", action="store_true",
                    help="latency SLO probe (sequential warm handshakes + "
                         "trips/handshake) instead of the throughput headline")
    ap.add_argument("--multichip", action="store_true",
                    help="1->N-chip scaling sweep (encaps/s on a sharded "
                         "mesh + handshakes/s through the placement "
                         "scheduler) instead of the single-chip headline")
    ap.add_argument("--storm", action="store_true",
                    help="gateway storm ratchet: one seeded 1000-session "
                         "sustained-traffic trace, static flush policy vs "
                         "the autotuner, gated on the checked-in budget "
                         "(docs/gateway.md)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="with --storm: run the FLEET chaos ratchet instead "
                         "— this many gateway processes behind the "
                         "consistent-hash router, one seeded mid-storm "
                         "gateway kill, gated on zero lost established "
                         "sessions (docs/fleet.md)")
    ap.add_argument("--spawn", default="process",
                    choices=("process", "task"),
                    help="fleet gateway isolation (--storm --fleet): real "
                         "subprocesses or in-process asyncio tasks")
    ap.add_argument("--resume-mix", action="store_true",
                    help="with --storm: run the session-RESUMPTION ratchet "
                         "instead — every session reconnects mid-workload "
                         "via its ticket, gated on resume rate / latency / "
                         "~0 device cost (docs/protocol.md)")
    ap.add_argument("--roll", action="store_true",
                    help="with --storm --fleet: run the ROLLING-RESTART "
                         "chaos ratchet instead — every gateway drained "
                         "and respawned mid-storm (+ one SIGKILL), gated "
                         "on 0 lost sessions and a >=90%% post-restart "
                         "ticket-resume rate (docs/robustness.md)")
    ap.add_argument("--router-roll", action="store_true",
                    help="with --storm --fleet: run the ROUTER-roll chaos "
                         "ratchet — N replicated routers behind a leader "
                         "lease, seeded mid-storm SIGKILL of the leader "
                         "plus a rolling restart of every router, gated "
                         "on 0 lost sessions and a >=90%% post-failover "
                         "ticket-resume rate (docs/fleet.md)")
    ap.add_argument("--routers", type=int, default=ROUTER_ROLL_ROUTERS,
                    help="router replica count for --router-roll")
    ap.add_argument("--bulk-mix", action="store_true",
                    help="with --storm: run the BULK-heavy data-plane "
                         "ratchet instead — one seeded bulk-mix trace on "
                         "the scalar ChaCha20-Poly1305 path vs the batched "
                         "device AEAD, gated on >=5x messages/s and a p99 "
                         "message-latency bound (docs/gateway.md)")
    ap.add_argument("--sessions", type=int, default=STORM_SESSIONS,
                    help="concurrent sessions in the storm ratchet")
    ap.add_argument("--reps", type=int, default=STORM_REPS,
                    help="interleaved (static, tuned) pairs in the storm "
                         "ratchet")
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this path "
                         "(slo/multichip modes)")
    ap.add_argument("--peers", type=int, default=SLO_PEERS,
                    help="handshakes in the slo probe")
    ap.add_argument("--warmup", type=int, default=4,
                    help="untimed warmup handshakes in the slo probe")
    ap.add_argument("--shards", default="1,2,4,8",
                    help="comma-separated shard counts for --multichip")
    ap.add_argument("--hs-peers", type=int, default=32,
                    help="warm handshakes per shard count in --multichip "
                         "(0 skips the handshake half of the sweep)")
    ap.add_argument("--emulate", type=int, default=0,
                    help="force an N-device virtual CPU platform for "
                         "--multichip (single-accelerator hosts)")
    ap.add_argument("--raw-ops", action="store_true",
                    help="raw per-op device throughput for one KEM family "
                         "(see --family) instead of the handshake modes: "
                         "keygen / cold + warm (operand-cached) encaps / "
                         "decaps per second with forced readback, gated on "
                         "the device KAT and >=90%% device-served")
    ap.add_argument("--family", default="mlkem",
                    choices=("mlkem", "frodo"),
                    help="KEM family for --raw-ops: mlkem routes to the "
                         "headline benchmark, frodo runs the FrodoKEM "
                         "device-path probe")
    ap.add_argument("--batch", type=int, default=FRODO_RAW_BATCH,
                    help="dispatch rows for --raw-ops --family frodo")
    ap.add_argument("--full-snapshots", action="store_true",
                    help="write RAW per-registry metrics snapshots "
                         "(~MBs for a storm) instead of the compact "
                         "committed digests")
    args = ap.parse_args()
    from tools.swarm_bench import set_full_snapshots
    set_full_snapshots(args.full_snapshots)
    if args.raw_ops and args.family == "frodo":
        raise SystemExit(frodo_raw_ops_main(args.out, args.batch))
    if args.slo:
        raise SystemExit(slo_main(args.out, args.peers, args.warmup))
    if args.storm and args.fleet and args.router_roll:
        raise SystemExit(router_roll_main(args.out, args.sessions,
                                          args.fleet, args.routers,
                                          args.spawn))
    if args.storm and args.fleet and args.roll:
        raise SystemExit(fleet_roll_main(args.out, args.sessions,
                                         args.fleet, args.spawn))
    if args.storm and args.fleet:
        raise SystemExit(fleet_storm_main(args.out, args.sessions,
                                          args.fleet, args.spawn))
    if args.storm and args.resume_mix:
        sessions = (args.sessions if args.sessions != STORM_SESSIONS
                    else RESUME_SESSIONS)
        raise SystemExit(resume_storm_main(args.out, sessions))
    if args.storm and args.bulk_mix:
        sessions = (args.sessions if args.sessions != STORM_SESSIONS
                    else BULK_SESSIONS)
        raise SystemExit(bulk_storm_main(args.out, sessions))
    if args.storm:
        raise SystemExit(storm_main(args.out, args.sessions, args.reps))
    if args.multichip:
        raise SystemExit(multichip_main(args.out, args.shards, args.hs_peers,
                                        args.emulate))
    main()
