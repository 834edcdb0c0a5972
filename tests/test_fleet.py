"""Gateway-pod fleet (fleet/) — the handoff-edge acceptance suite.

Covers ISSUE 11's test satellite:

* consistent-hash ring determinism + stability (adding/removing one
  gateway moves ONLY its arc),
* the shared two-level placement policy (``provider.scheduler.select_slot``)
  picking among :class:`GatewayMember` slots exactly as it picks among
  local shards,
* fleet admission shed at the router with the typed ``__busy__`` reply,
* ring-walk routing past a breaker-open (dead) gateway to its successor,
  with client-side ``exclude`` honored,
* gateway death mid-handshake: the initiator's in-flight handshake fails
  FAST with a typed reason (never burning the protocol timeout) so the
  fleet retry loop can re-route promptly — and nothing plaintext moves,
* the healed gateway's half-open re-entry: partition -> missed heartbeats
  -> fleet breaker opens -> arc drains to the successor -> probe succeeds
  -> arc snaps back (live task-mode fleet over real localhost TCP),
* seeded kill-chaos determinism: the same plan seed yields the same
  ``injected`` log, byte for byte,
* ``storm_env`` restoring the module-global protocol timeout even when
  the storm raises,
* per-node SLO report merging (``obs.slo.merge_reports`` +
  ``tools/slo_merge.py``): fleet totals, worst-node attribution.

Everything runs on minimal images: stdlib toy crypto (fleet/stormlib.py),
injectable clocks for the breaker timelines, in-process (``spawn="task"``)
gateways for the live-fleet cases — same control protocol, real TCP.
"""

import asyncio
import json
import time

import pytest

from quantum_resistant_p2p_tpu.app import messaging as messaging_mod
from quantum_resistant_p2p_tpu.faults import FaultPlan, FaultRule
from quantum_resistant_p2p_tpu.fleet import control as fleet_control
from quantum_resistant_p2p_tpu.fleet.manager import (FleetBusy, GatewayFleet,
                                                     GatewayMember)
from quantum_resistant_p2p_tpu.fleet.ring import HashRing
from quantum_resistant_p2p_tpu.fleet.stormlib import storm_env
from quantum_resistant_p2p_tpu.obs.slo import merge_reports
from quantum_resistant_p2p_tpu.provider.scheduler import select_slot


@pytest.fixture
def run():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.run_until_complete(loop.shutdown_asyncgens())
    loop.close()


@pytest.fixture(autouse=True)
def fast_timeout(monkeypatch):
    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 5.0)
    monkeypatch.setattr(messaging_mod, "KE_RETRY_BACKOFF_S", 0.05)


KEYS = [f"peer{i:04d}" for i in range(400)]


# -- consistent-hash ring -----------------------------------------------------


def test_ring_deterministic_across_instances():
    """Same (seed, membership) -> byte-identical assignment, regardless of
    insertion order: the router and any offline tool agree without
    coordination."""
    a = HashRing(["gw0", "gw1", "gw2"], seed=7)
    b = HashRing(["gw2", "gw0", "gw1"], seed=7)
    assert [a.assign(k) for k in KEYS] == [b.assign(k) for k in KEYS]
    c = HashRing(["gw0", "gw1", "gw2"], seed=8)
    assert [a.assign(k) for k in KEYS] != [c.assign(k) for k in KEYS]


def test_ring_add_moves_only_the_new_members_arc():
    ring = HashRing(["gw0", "gw1", "gw2"], seed=0)
    before = {k: ring.assign(k) for k in KEYS}
    ring.add("gw3")
    moved = {k for k in KEYS if ring.assign(k) != before[k]}
    assert moved  # the new member takes a real share
    assert all(ring.assign(k) == "gw3" for k in moved)


def test_ring_remove_moves_only_the_dead_members_arc():
    ring = HashRing(["gw0", "gw1", "gw2"], seed=0)
    before = {k: ring.assign(k) for k in KEYS}
    ring.remove("gw1")
    for k in KEYS:
        if before[k] != "gw1":
            assert ring.assign(k) == before[k]
        else:
            assert ring.assign(k) in ("gw0", "gw2")


def test_ring_successors_start_at_owner_and_cover_members():
    ring = HashRing(["gw0", "gw1", "gw2"], seed=0)
    for k in KEYS[:32]:
        order = list(ring.successors(k))
        assert order[0] == ring.assign(k)
        assert sorted(order) == ["gw0", "gw1", "gw2"]


# -- the shared two-level placement policy ------------------------------------


def _member(gid, index, clock):
    return GatewayMember(gid, index, cooloff_s=1.0, cooloff_max_s=8.0,
                         clock=clock)


def test_select_slot_places_among_gateway_members():
    """GatewayMember satisfies the same slot protocol as a local Shard:
    least-loaded closed member wins, ties break on index."""
    now = [100.0]
    members = [_member(f"gw{i}", i, lambda: now[0]) for i in range(3)]
    members[0].inflight = 5
    members[1].inflight = 2
    members[2].inflight = 2
    assert select_slot(members) is members[1]


def test_select_slot_prefers_probe_ready_member_then_degrades():
    now = [100.0]
    members = [_member(f"gw{i}", i, lambda: now[0]) for i in range(3)]
    members[1].breaker.record_failure("device")  # open, cooloff 1s
    assert select_slot(members) is members[0]  # closed beats open
    now[0] += 2.0  # past cool-off: the dead member is probe-eligible
    assert select_slot(members) is members[1]
    # quarantined members are never placed while an alternative exists
    members[1].breaker.record_failure("probe")  # re-open (not quarantine)
    members[0].breaker.quarantine("test")
    assert select_slot(members) is members[2]


# -- router-side routing and admission (offline: no processes) ----------------


def _offline_fleet(n=3, per_gateway_max_peers=0, clock=None):
    fleet = GatewayFleet(n, spawn="task",
                         per_gateway_max_peers=per_gateway_max_peers,
                         clock=clock or time.monotonic)
    for m in fleet.members.values():  # pretend every gateway registered
        m.host, m.port = "127.0.0.1", 40000 + m.index
    return fleet


def test_fleet_admission_shed_is_typed_busy():
    """Over-budget route queries shed AT THE ROUTER: FleetBusy in-process,
    the typed ``__busy__`` frame on the wire — the same shape a gateway's
    own connection budget uses."""
    fleet = _offline_fleet(2, per_gateway_max_peers=2)  # fleet budget 4
    for i in range(4):
        assert fleet.route(f"peer{i}") is not None
    with pytest.raises(FleetBusy):
        fleet.route("peer4")
    reply = fleet._route_reply({"peer_id": "peer5"})
    assert reply == {"type": fleet_control.BUSY, "scope": "fleet"}
    assert fleet.route_sheds == 2
    # a finished session releases its slot and routing resumes
    fleet.session_done(fleet.ring.assign("peer0"))
    assert fleet.route("peer6") is not None


def test_fleet_budget_excludes_open_members():
    """A dead gateway's capacity is not capacity: the fleet budget is the
    sum over CLOSED members only."""
    now = [100.0]
    fleet = _offline_fleet(3, per_gateway_max_peers=5, clock=lambda: now[0])
    assert fleet.fleet_budget() == 15
    fleet.members["gw1"].breaker.record_failure("device")
    assert fleet.fleet_budget() == 10


def test_all_dead_budget_sheds_instead_of_admitting_unbounded():
    """Zero healthy capacity is budget 0, NOT 'unconfigured': with every
    breaker open a configured fleet sheds route queries with the typed
    busy frame rather than piling unlimited sessions onto degraded
    members (None, not 0, is the no-budget sentinel)."""
    now = [100.0]
    fleet = _offline_fleet(3, per_gateway_max_peers=5, clock=lambda: now[0])
    for m in fleet.members.values():
        m.breaker.record_failure("device")
    assert fleet.fleet_budget() == 0
    with pytest.raises(FleetBusy):
        fleet.route("peer0")
    assert _offline_fleet(3).fleet_budget() is None  # unconfigured


def test_probe_heal_refreshes_liveness_no_instant_redeath(run):
    """A successful half-open canary IS fresh liveness evidence: the next
    health tick must not re-declare the just-healed member dead off its
    stale pre-outage heartbeat timestamp (the heal-flap edge)."""
    now = [100.0]
    fleet = _offline_fleet(2, clock=lambda: now[0])
    gw1 = fleet.members["gw1"]
    gw1.last_hb = now[0]
    now[0] += fleet.hb_miss_limit * fleet.hb_interval + 1.0  # outage
    fleet._health_tick()
    assert gw1.breaker.state == "open"
    now[0] += gw1.breaker.cooloff_s + 0.1  # cool-off over: probe-eligible
    assert gw1.breaker.acquire_dispatch() == "probe"

    async def wire_probe_ok(member, n):
        return None

    fleet._probe_call = wire_probe_ok  # canary round-trip succeeds
    run(fleet._probe_gateway(gw1, 1))
    assert gw1.breaker.state == "closed"
    # the very next tick, BEFORE any post-outage heartbeat lands, must not
    # flap the breaker back open off the stale timestamp
    fleet._health_tick()
    assert gw1.breaker.state == "closed"


def test_route_hands_open_members_arc_to_ring_successor():
    now = [100.0]
    fleet = _offline_fleet(3, clock=lambda: now[0])
    owner_key = next(k for k in KEYS if fleet.ring.assign(k) == "gw1")
    successor = list(fleet.ring.successors(owner_key))[1]
    assert fleet.route(owner_key).gateway_id == "gw1"
    fleet.members["gw1"].breaker.record_failure("device")  # gw1 is dead
    assert fleet.route(owner_key).gateway_id == successor
    assert fleet.handoffs == 1
    # client-side exclude is honored even while the breaker is closed
    # (the router may be one heartbeat behind the client's observation)
    key2 = next(k for k in KEYS if fleet.ring.assign(k) == "gw0")
    assert fleet.route(key2, exclude=("gw0",)).gateway_id != "gw0"


# -- seeded process-scope chaos ----------------------------------------------


def test_process_chaos_log_is_deterministic_from_seed():
    """Same seed + same health-tick event stream -> the same ``injected``
    log, byte for byte (the fleet storm's reproducibility claim)."""

    def drive(seed):
        plan = FaultPlan(seed, [
            FaultRule("process", "kill_gateway", match={"gateway": "gw1"},
                      nth=3),
            FaultRule("process", "pause_gateway", match={"gateway": "gw0"},
                      nth=2, delay_s=0.5),
        ])
        with plan.activate():
            from quantum_resistant_p2p_tpu.faults import plan as plan_mod

            for _tick in range(4):  # the health loop: sorted order, 1 poll
                for gid in ("gw0", "gw1", "gw2"):  # per gateway per tick
                    plan_mod.process_control(gid)
        return json.dumps(plan.injected, sort_keys=True)

    log = drive(11)
    assert log == drive(11)
    assert json.loads(log) == [
        {"scope": "process", "action": "pause_gateway", "n": 2,
         "gateway": "gw0", "delay_s": 0.5},
        {"scope": "process", "action": "kill_gateway", "n": 3,
         "gateway": "gw1"},
    ]
    assert drive(12) == log  # seed only feeds RNG-bearing actions


def test_process_control_is_noop_without_plan():
    from quantum_resistant_p2p_tpu.faults import plan as plan_mod

    assert plan_mod.process_control("gw0") == []


# -- storm_env ----------------------------------------------------------------


def test_storm_env_restores_timeout_even_on_raise():
    before = messaging_mod.KEY_EXCHANGE_TIMEOUT
    with pytest.raises(RuntimeError):
        with storm_env(99.0):
            assert messaging_mod.KEY_EXCHANGE_TIMEOUT == 99.0
            raise RuntimeError("storm blew up")
    assert messaging_mod.KEY_EXCHANGE_TIMEOUT == before


# -- per-node SLO report merging ---------------------------------------------


def _node_report(node, good, bad, burn_fast, alerting=False):
    return {
        "node": node,
        "slo": {"specs": [{
            "name": "handshake_p99", "objective": 0.99,
            "good_total": good, "bad_total": bad,
            "burn_fast": burn_fast, "alerting": alerting,
        }]},
    }


def test_merge_reports_fleet_totals_and_worst_node():
    merged = merge_reports([
        _node_report("gw0", 98.0, 2.0, 0.5),
        _node_report("gw1", 40.0, 10.0, 20.0, alerting=True),
        _node_report("gw2", 100.0, 0.0, 0.0),
    ])
    slo = merged["slos"]["handshake_p99"]
    assert slo["good_total"] == 238.0 and slo["bad_total"] == 12.0
    assert slo["fleet_error_rate"] == round(12.0 / 250.0, 6)
    assert slo["fleet_burn"] == round((12.0 / 250.0) / 0.01, 4)
    assert slo["worst_node"] == "gw1"
    assert merged["worst_node"] == "gw1"
    assert merged["alerting"] == ["gw1"]


def test_slo_merge_cli_merges_a_report_dir(tmp_path, capsys):
    from tools import slo_merge

    for i in range(2):
        (tmp_path / f"gw{i}_slo_report.json").write_text(
            json.dumps(_node_report(f"gw{i}", 10.0 * (i + 1), float(i), 0.1)))
    out = tmp_path / "fleet.json"
    assert slo_merge.main([str(tmp_path), "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["nodes"] == ["gw0", "gw1"]
    assert doc["slos"]["handshake_p99"]["good_total"] == 30.0
    assert "2 node report(s)" in capsys.readouterr().out


# -- live fleet: death, handoff, half-open heal (task mode, real TCP) ---------


FAST = dict(hb_interval=0.05, cooloff_s=0.25, cooloff_max_s=2.0,
            register_timeout=30.0)


def test_gateway_death_mid_handshake_fails_fast_typed(run):
    """The messaging-layer half of the handoff contract: when the gateway
    drops mid-handshake, the initiator's in-flight exchange fails NOW with
    a typed reason — never burning KEY_EXCHANGE_TIMEOUT — so the fleet
    retry loop can walk to the ring successor promptly.  Nothing plaintext
    is ever sent (no shared key exists)."""

    async def scenario():
        fleet = GatewayFleet(2, spawn="task", **FAST)
        await fleet.start()
        try:
            from quantum_resistant_p2p_tpu.fleet.stormlib import (
                StormAEAD, register_storm_providers)
            from quantum_resistant_p2p_tpu.net.p2p_node import P2PNode
            from quantum_resistant_p2p_tpu.provider import (get_kem,
                                                            get_signature)

            register_storm_providers()
            node = P2PNode(node_id="client", host="127.0.0.1", port=0)
            sm = messaging_mod.SecureMessaging(
                node, kem=get_kem("STORM-KEM", "cpu"),
                symmetric=StormAEAD(),
                signature=get_signature("STORM-SIG", "cpu"), auto_heal=False)
            victim = fleet.members["gw0"]
            assert await node.connect_to_peer(
                "127.0.0.1", victim.port) == "gw0"
            # pin the race: the gateway's ke_response is dropped by the
            # seeded plan, so the initiator is PROVABLY mid-handshake
            # (waiting on a response that can never arrive) when the
            # gateway dies
            plan = FaultPlan(0, [FaultRule(
                "net.send", "drop", match={"msg_type": "ke_response"},
                nth=1)])
            with plan.activate():
                task = asyncio.ensure_future(
                    sm.initiate_key_exchange("gw0"))
                await asyncio.sleep(0.15)
                fleet.kill("gw0")
                t0 = time.monotonic()
                ok = await task
                waited = time.monotonic() - t0
            assert plan.injected  # the drop really happened
            assert ok is False
            # typed fast-fail, not a protocol-timeout burn
            assert waited < messaging_mod.KEY_EXCHANGE_TIMEOUT / 2
            assert "gw0" not in sm.shared_keys
            assert await sm.send_message("gw0", b"secret") is None
            await node.stop()
        finally:
            await fleet.stop()

    run(scenario())


def test_partitioned_gateway_heals_via_half_open_probe(run):
    """The false-dead case end to end on a LIVE task-mode fleet: a control
    partition makes gw1 miss heartbeats -> its fleet breaker opens and the
    ring arc drains to gw0 -> the partition lifts -> the half-open canary
    probe succeeds -> the breaker closes and gw1's arc snaps back (ring
    membership never changed)."""

    async def scenario():
        fleet = GatewayFleet(2, spawn="task", **FAST)
        events = []
        fleet.on_event(lambda ev, gid: events.append((ev, gid)))
        await fleet.start()
        try:
            owned = next(k for k in KEYS if fleet.ring.assign(k) == "gw1")
            assert fleet.route(owned).gateway_id == "gw1"
            fleet.partition("gw1", 0.6)
            for _ in range(100):  # detection: hb_miss_limit * hb_interval
                if fleet.members["gw1"].breaker.state != "closed":
                    break
                await asyncio.sleep(0.05)
            assert fleet.members["gw1"].breaker.state == "open"
            assert ("gateway_dead", "gw1") in events
            assert fleet.route(owned).gateway_id == "gw0"  # arc drained
            for _ in range(200):  # partition lifts; probe closes it
                if fleet.members["gw1"].breaker.state == "closed":
                    break
                await asyncio.sleep(0.05)
            assert fleet.members["gw1"].breaker.state == "closed"
            assert ("gateway_healed", "gw1") in events
            assert fleet.route(owned).gateway_id == "gw1"  # arc snapped back
        finally:
            await fleet.stop()

    run(scenario())


def test_fleet_storm_survives_seeded_gateway_kill(run):
    """The chaos acceptance shape in miniature (the CI ratchet runs it at
    1000 sessions via ``bench.py --storm --fleet 3``): a seeded mid-storm
    gateway kill, every established session finishes (ring-successor
    handoff + re-key), 0 plaintext sends, and the injected log replays
    byte-for-byte from the seed."""
    from quantum_resistant_p2p_tpu.fleet.storm import (default_kill_rules,
                                                       run_fleet_storm)

    # msg_interval_s paces sessions so they are ALIVE at the kill tick —
    # on a fast host an unpaced 10-session storm finishes before tick 2
    # and the kill (the thing under test) never fires
    out = run(run_fleet_storm(
        sessions=10, gateways=3, spawn="task", concurrency=10,
        msgs_per_session=4, hb_interval=0.05, ke_timeout=30.0,
        msg_interval_s=0.1, session_attempts=8,
        fault_rules=default_kill_rules("gw1", tick=2), seed=5))
    assert out["completed_sessions"] == 10
    assert out["lost_established_sessions"] == 0
    assert out["plaintext_sends"] == 0
    assert out["chaos"]["injected_log"] == [
        {"scope": "process", "action": "kill_gateway", "n": 2,
         "gateway": "gw1"}]
    assert out["fleet"]["members"][1]["killed"] is True


# -- graceful drain / rolling restart / STEK distribution (ISSUE 15) ----------


def test_draining_member_excluded_from_routing():
    fleet = _offline_fleet(3)
    fleet.members["gw0"].draining = True
    for peer in (f"p{i}" for i in range(24)):
        m = fleet.route(peer)
        assert m is not None and m.gateway_id != "gw0"
        fleet.session_done(m.gateway_id)
    # budget counts only non-draining capacity
    fleet.per_gateway_max_peers = 4
    assert fleet.fleet_budget() == 8


def test_drain_gateway_is_a_valid_chaos_action():
    FaultRule("process", "drain_gateway", match={"gateway": "gw0"})
    with pytest.raises(ValueError):
        FaultRule("process", "nonsense")
    # the ticket scope exists with exactly its three typed actions
    for action in ("corrupt", "expire", "replay"):
        FaultRule("ticket", action)
    with pytest.raises(ValueError):
        FaultRule("ticket", "drop")


def test_reset_for_respawn_forgets_the_dead_incarnation():
    m = GatewayMember("gw0", 0, clock=time.monotonic)
    m.host, m.port, m.pid = "127.0.0.1", 40000, 123
    m.last_hb = 1.0
    m.breaker.record_failure("device")
    m.inflight = 7
    m.reset_for_respawn()
    assert not m.registered and m.pid is None and m.last_hb is None
    assert m.breaker.state == "closed"  # a planned restart is not failure
    assert m.inflight == 0 and m.restarts == 1


def test_stek_pushed_on_registration_and_rotation(run):
    """Every gateway's ticket ring is the ROUTER's ring (pushed at hello),
    and a rotation re-pushes the new window — the property that makes a
    ticket minted by gw0 resume on gw1, and on a respawned gw0."""
    async def main():
        fleet = GatewayFleet(2, spawn="task", hb_interval=0.05)
        try:
            await fleet.start()
            blob = fleet.ticket_keys.seal_ticket(
                {"v": 1, "holder": "x", "secret": "00" * 32, "nonce": "n"})
            epoch0 = fleet.ticket_keys.current_epoch
            epoch1 = await fleet.rotate_stek()
            assert epoch1 != epoch0
            # dual-key window: the pre-rotation blob still opens
            meta, _secret = fleet.ticket_keys.open_ticket(blob)
            assert meta["holder"] == "x"
            assert fleet.stats()["stek_epoch"] == epoch1
        finally:
            await fleet.stop()

    run(main())


def test_rolling_restart_respawns_and_reregisters(run):
    async def main():
        fleet = GatewayFleet(2, spawn="task", hb_interval=0.05)
        try:
            await fleet.start()
            rep = await fleet.rolling_restart(drain_timeout=10.0)
            assert rep["ok"] is True
            assert [r["gateway"] for r in rep["restarted"]] == ["gw0", "gw1"]
            assert all(r["graceful_exit"] and r["registered"]
                       for r in rep["restarted"])
            assert all(m.registered and not m.draining
                       for m in fleet.members.values())
            assert all(m.restarts == 1 for m in fleet.members.values())
        finally:
            await fleet.stop()

    run(main())


def test_roll_storm_sessions_survive_and_resume(run):
    """The rolling-restart acceptance shape in miniature (the CI ratchet
    runs it at 1000 sessions via ``bench.py --storm --fleet 3 --roll``):
    every gateway drained + respawned mid-storm, 0 lost established
    sessions, 0 plaintext, and displaced sessions resume VIA TICKET on
    wherever the ring re-routes them."""
    from quantum_resistant_p2p_tpu.fleet.storm import run_fleet_storm

    out = run(run_fleet_storm(
        sessions=24, gateways=2, spawn="task", concurrency=8,
        msgs_per_session=6, arrival_rate=20.0, hb_interval=0.05,
        ke_timeout=30.0, seed=5, roll=True, roll_delay_s=0.5,
        drain_timeout=10.0, session_attempts=8, msg_interval_s=0.05))
    assert out["completed_sessions"] == 24
    assert out["lost_established_sessions"] == 0
    assert out["plaintext_sends"] == 0
    assert out["roll"] and out["roll"]["ok"]
    assert out["resumed_reconnects"] >= 1
    assert out["full_handshake_reconnects"] == 0
    assert out["post_roll_resume_rate"] in (None, 1.0)


# -- one chip per process -----------------------------------------------------


def test_process_spawn_refused_while_this_process_holds_a_chip(monkeypatch):
    """A gateway subprocess on the real providers cannot reach a chip its
    parent holds; the fleet refuses to spawn one instead of letting it fail
    or hang.  Stdlib-provider gateways never touch the device."""
    import jax

    from quantum_resistant_p2p_tpu.fleet import gateway

    jax.devices()  # the suite's CPU backend: nothing is held
    gateway.refuse_if_chip_held("device")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="spawn='task'"):
        gateway.refuse_if_chip_held("device")
    gateway.refuse_if_chip_held("stdlib")


def test_process_fleet_serves_on_a_cpu_host_without_jax_platforms(
        run, monkeypatch):
    """A process fleet on a host with no accelerator and JAX_PLATFORMS
    unset comes up: nothing on the spawn path insists on a chip."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    async def scenario():
        fleet = GatewayFleet(1, spawn="process", **FAST)
        await fleet.start()
        try:
            member = fleet.members["gw0"]
            assert member.registered and member.proc.returncode is None
        finally:
            await fleet.stop()

    run(scenario())
