"""The check against faults planted under a run.

These drive the whole of a run but the look for a chip: the hub on the
CPU backend (the native core, small flushes), real client processes over
loopback TCP, a short window, and the reference check.  A sound run is
correct; the control (the hub accepting signatures without verifying
them) and every planted fault make ``correct`` false.
"""

import asyncio
import dataclasses
import json
import os
import subprocess
import sys
import time
import types

import jax
import pytest

from benchmark.lib import check, faults, harness, hub, spec

SECONDS = 2.0


@pytest.fixture(autouse=True)
def _short_give_up(monkeypatch):
    """A lost request is given up after 4 s, not a minute."""
    monkeypatch.setattr(hub, "GIVE_UP_S", 4.0)


def _cell(name: str, **traffic) -> spec.Cell:
    cell = spec.cell(name)
    config = json.loads(json.dumps(cell.config))
    config["hub"].update(backend="cpu", batch_floor=1, max_batch=64)
    base = {"rate_per_s": 25, "warmup_s": 0.5, "client_procs": 2,
            "check_sample": 8, "forged_share": 0.1, "linger_s": 0.3}
    return dataclasses.replace(cell, config=config,
                               traffic=dict(cell.traffic, **base, **traffic))


def _checks(cell: spec.Cell, seed: int, plant=None) -> dict:
    state = asyncio.run(harness.run_cell(
        cell, lambda runs: None if runs else (seed, cell.traffic, plant),
        SECONDS, False, time.monotonic(), jax.devices()[:1]))
    run = state["runs"][0]
    return {c.name: c.value for c in check.evaluate(run, cell.config, seed,
                                                    seed)}


CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    got = _checks(_cell(name), 2**32 + 77)
    assert not any(got.values()), got


PLANTED = [
    ("d1-handshake-open", "accept_every_signature",
     ("forged_accepted", "init_verdict_mismatch")),
    ("d1-handshake-open", "alter_secret", ("key_disagree", "kem_key_mismatch")),
    ("d1-handshake-open", "stale_secret", ("key_disagree",)),
    ("d1-handshake-open", "alter_plaintext", ("plaintext_mismatch",)),
    ("d1-handshake-open", "drop_half", ("sessions_lost",)),
]


@pytest.mark.parametrize("name,plant,caught", PLANTED,
                         ids=[f"{n}-{p}" for n, p, _ in PLANTED])
def test_planted_fault_is_not_correct(name, plant, caught):
    got = _checks(_cell(name), 31337, faults.PLANTS[plant])
    assert all(got[c] > 0 for c in caught), got


def test_no_tpu_no_result():
    """Without a TPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "d1-handshake-open", "--seed", "5", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "TPU" in out.stderr


def test_warm_refuses_a_hub_without_its_queues():
    """On the TPU backend, a queue the responder drives that the engine
    does not have stops the warm-up, rather than leaving its compile to the
    window."""
    engine = types.SimpleNamespace(backend="tpu", _bfused=None,
                                   _bsig=types.SimpleNamespace(_verify=None))
    with pytest.raises(RuntimeError, match="encaps_verify_sign"):
        hub.warm(engine)
