"""What decides ``correct``: the run's answers against the plain reference.

Every number here counts answers that are wrong; each has the limit 0 (an
exact comparison), and a run is correct when none exceeds it.

All genuine requests due in the window are checked for what is cheap to
check; a sample drawn from the seed is re-checked by the reference of the
configuration's suite family (``benchmark/suites/<family>.py``).  What a
request's answers are, and so what is compared, is its kind's
(``benchmark/kinds/<kind>.py``, its ``checks``); common to every kind are
the CPU fallback (no op may be served by it) and forged messages (none may
be delivered).
"""

from __future__ import annotations

from dataclasses import dataclass

from .. import reference
from . import schedule, spec


@dataclass(frozen=True)
class Check:
    name: str
    value: int
    limit: int = 0

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def first(rec: dict | None, key: str):
    """The first operand the client recorded under ``key``, or None."""
    vals = (rec or {}).get(key) or []
    return vals[0] if vals else None


class Ref:
    """The reference's view of one run's identities."""

    def __init__(self, config: dict, seed: int, hub_seed: int) -> None:
        self.suite = config["suite"]
        self.family = spec.suite(config)
        self.seed = seed
        self.hub_pk = self.family.ref_public_key(
            self.suite, schedule.hub_identity_seed(hub_seed))
        self._pks: dict[int, bytes] = {}

    def client_pk(self, session: int) -> bytes:
        if session not in self._pks:
            self._pks[session] = self.family.ref_public_key(
                self.suite, schedule.identity_seed(self.seed, session))
        return self._pks[session]

    def verify(self, pk: bytes, signed_hex: str, sig_hex: str) -> bool:
        return self.family.ref_verify(self.suite, pk, bytes.fromhex(signed_hex),
                                      bytes.fromhex(sig_hex))

    def session_key(self, peer: str, dk_hex: str, ct_hex: str) -> bytes:
        """The message key of the session whose initiator holds ``dk`` and
        received ``ct`` from the hub."""
        secret = self.family.ref_decaps(self.suite, bytes.fromhex(dk_hex),
                                        bytes.fromhex(ct_hex))
        return reference.message_key(secret, "hub", peer, self.suite["aead"])


def message_checks(run, seed: int, due: list, counts: dict) -> None:
    """Every message of ``due`` delivered to the hub's listener, from its
    sender, with the plaintext sent."""
    for e in due:
        got = run.delivered.get(e.index)
        if got is None:
            counts["messages_lost"] += 1
            continue
        peer, content, _ = got
        if (peer != schedule.node_id(seed, e.session)
                or content != schedule.payload(seed, e.index, e.size)):
            counts["plaintext_mismatch"] += 1


def evaluate(run, config: dict, seed: int, hub_seed: int) -> list[Check]:
    """The checks of ``run`` (traffic from ``seed``, hub identity from
    ``hub_seed``)."""
    counts = dict.fromkeys((
        "messages_lost", "plaintext_mismatch", "forged_accepted",
        "msg_verdict_mismatch", "unchecked_sample"), 0)
    counts["forged_accepted"] = sum(1 for e in run.events if e.in_window
                                    and e.forged and e.index in run.delivered)
    spec.kind(run.kind).checks(run, Ref(config, seed, hub_seed), seed, counts)
    counts["fallback_ops"] = run.fallback_ops
    return [Check(k, int(v)) for k, v in sorted(counts.items())]
