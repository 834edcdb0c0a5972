"""Ways to break the hub under a run, to show that the check sees them.

``accept_every_signature`` is the control: it breaks the configuration's
authenticity guarantee, the hub accepting a ke_init or a message without
its signature holding.  The others plant the faults a served answer can
have: an answer altered where it is produced (the session secret, the
delivered plaintext), state that does not move (one secret reused for
every session), and half of the work left out (every other ke_init
dropped).  Each takes the ``Hub`` and patches its engine in place.
"""

from __future__ import annotations


def accept_every_signature(hub) -> None:
    eng = hub.engine

    async def verify(*args, **kwargs):
        return True

    eng._verify = verify
    fused = eng._bfused
    if fused is not None:
        evs = fused.encaps_verify_sign

        async def accept(*args, **kwargs):
            _, ct, secret, sig = await evs(*args, **kwargs)
            return True, ct, secret, sig

        fused.encaps_verify_sign = accept


def _wrap_secret(hub, change) -> None:
    eng = hub.engine
    respond = eng._respond_established

    async def wrapped(peer_id, secret, resp, sig):
        return await respond(peer_id, change(bytes(secret)), resp, sig)

    eng._respond_established = wrapped


def alter_secret(hub) -> None:
    _wrap_secret(hub, lambda s: bytes([s[0] ^ 1]) + s[1:])


def stale_secret(hub) -> None:
    first: list[bytes] = []

    def change(s: bytes) -> bytes:
        if not first:
            first.append(s)
        return first[0]

    _wrap_secret(hub, change)


def alter_plaintext(hub) -> None:
    notify = hub.engine._notify

    def wrapped(peer_id, message):
        if not message.is_system and message.content:
            c = bytes(message.content)
            message.content = c[:-1] + bytes([c[-1] ^ 1])
        notify(peer_id, message)

    hub.engine._notify = wrapped


def drop_half(hub) -> None:
    eng = hub.engine
    handle = eng._handle_ke_init
    seen = [0]

    async def wrapped(peer_id, msg):
        seen[0] += 1
        if seen[0] % 2:
            return await handle(peer_id, msg)

    eng.node._msg_handlers["ke_init"] = [wrapped]


PLANTS = {f.__name__: f for f in (accept_every_signature, alter_secret,
                                  stale_secret, alter_plaintext, drop_half)}
