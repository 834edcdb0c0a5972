"""The one traffic generator: a traffic file's parameters and a seed in, the
run's events out.

Every seed gets the same multiset of inter-arrival gaps and forged share,
drawn at fixed quantiles, in another order: the seed changes which request
comes when, never how much work a run holds.  What a request carries
beyond its due time (its size, its session) is drawn by the traffic's kind
(``benchmark/kinds/<kind>.py``, its ``draw``).  The hub and every client
process build the same schedule from the same ``(traffic, seed,
seconds)``, so nothing but the seed crosses a pipe.
"""

from __future__ import annotations

import hashlib
import zlib
from dataclasses import dataclass

import numpy as np

from . import spec


@dataclass(frozen=True)
class Event:
    """One request.  ``due`` is seconds after the schedule's origin."""

    index: int
    due: float
    in_window: bool
    forged: bool
    session: int
    size: int


def rng(seed: int, purpose: str, segment: int) -> np.random.Generator:
    """The generator's stream for one purpose in one segment of a run."""
    return np.random.default_rng([int(seed) & (2**64 - 1),
                                  zlib.crc32(purpose.encode()), segment])


def quantile_gaps(rate: float, duration: float,
                  gen: np.random.Generator) -> np.ndarray:
    """Poisson inter-arrival gaps at ``rate`` over ``duration`` seconds: the
    exponential's quantiles at ``(k + 0.5) / n`` for ``n = rate * duration``
    arrivals, shuffled.  Their sum, and so the load, is the same for every
    seed."""
    n = max(1, int(round(rate * duration)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gen.shuffle(gaps)
    return gaps


def _segment(traffic: dict, seed: int, segment: int, t0: float,
             duration: float, first_index: int, in_window: bool
             ) -> list[Event]:
    gaps = quantile_gaps(float(traffic["rate_per_s"]), duration,
                         rng(seed, "gaps", segment))
    n = len(gaps)
    dues = t0 + np.cumsum(gaps) - gaps[0] * 0.5
    n_forged = int(round(float(traffic.get("forged_share", 0.0)) * n))
    forged = np.zeros(n, bool)
    if n_forged:
        forged[rng(seed, "forged", segment).choice(n, n_forged,
                                                   replace=False)] = True
    sizes, sessions = spec.kind(traffic["kind"]).draw(
        traffic, seed, segment, n, first_index)
    return [Event(first_index + i, float(dues[i]), in_window, bool(forged[i]),
                  int(sessions[i]), int(sizes[i])) for i in range(n)]


def build(traffic: dict, seed: int, seconds: float) -> list[Event]:
    """The run's events, warm-up segment first, then the measured window,
    which starts at ``traffic["warmup_s"]`` after the origin."""
    warm = float(traffic["warmup_s"])
    events = _segment(traffic, seed, 0, 0.0, warm, 0, False)
    events += _segment(traffic, seed, 1, warm, float(seconds), len(events),
                       True)
    return events


def check_sample(traffic: dict, seed: int, events: list[Event]) -> set[int]:
    """Indices of the window's events that the reference re-checks: up to
    ``check_sample`` genuine ones and every forged one up to a quarter of
    that, drawn from the seed."""
    k = int(traffic["check_sample"])
    gen = rng(seed, "sample", 0)
    genuine = [e.index for e in events if e.in_window and not e.forged]
    forged = [e.index for e in events if e.in_window and e.forged]
    pick = list(gen.choice(genuine, min(k, len(genuine)), replace=False))
    pick += list(gen.choice(forged, min(max(1, k // 4), len(forged)),
                            replace=False)) if forged else []
    return {int(i) for i in pick}


def identity_seed(seed: int, session: int) -> bytes:
    """The 32-byte key seed of client identity ``session``."""
    return hashlib.sha256(b"qrp2p-bench/identity/%d/%d" % (seed, session)
                          ).digest()


def hub_identity_seed(seed: int) -> bytes:
    return hashlib.sha256(b"qrp2p-bench/hub/%d" % seed).digest()


def payload(seed: int, index: int, size: int) -> bytes:
    """The plaintext of message ``index``: its index, then bytes drawn from
    the seed, ``size`` bytes in all."""
    head = b"%d:" % index
    body = hashlib.shake_256(b"qrp2p-bench/payload/%d/%d" % (seed, index)
                             ).digest(max(0, size - len(head)))
    return (head + body)[:max(size, len(head))]


def node_id(seed: int, session: int) -> str:
    return "c%08x-%d" % (seed & 0xFFFFFFFF, session)
