"""Device-health gate: validate the accelerated path per environment.

Hardware-accelerator PQC evaluations (PQC-HA, arXiv:2308.06621) stress that
the correctness of an accelerated implementation must be RE-VALIDATED in
every environment before it is trusted — a new device kind, XLA release, or
JAX version can silently change numerics (the HQC f32-FFT cyclic product is
the documented in-repo example, kem/hqc.py).  This module runs fast
on-device self-checks at provider startup:

* **HQC** — the FFT-vs-Toeplitz cyclic-product exactness probe
  (``kem.hqc._fft_selfcheck``, the same check ``chip_smoke.py`` runs);
  an unvalidated environment routes HQC to the exact
  Toeplitz-MXU path and logs why.
* **ML-KEM** — a pinned known-answer vector: deterministic
  ``keygen(d, z)`` / ``encaps(ek, m)`` digests computed from the pure-Python
  FIPS 203 reference (pyref/mlkem_ref.py), checked against the device path.
* **every other family** — a deterministic roundtrip on the device provider
  plus CROSS-IMPLEMENTATION agreement with its cpu twin (device-encapsulated
  secrets must decapsulate identically on the independent cpu backend;
  device signatures must verify on the cpu backend and a tampered signature
  must not).

Each probe runs at its facade's bucket floor (``rows``), so it exercises the
very programs the queue's flushes run and compiles nothing of its own; a
cold start with a large floor would otherwise compile every program twice.

Verdicts are keyed by an environment fingerprint (device kind, platform,
jax/jaxlib versions) and cached on disk (the native-build cache dir), so the
cost is once per environment, not per process.  Only POSITIVE verdicts are
trusted from the cache: a failed probe re-runs at next startup
(self-healing) instead of pinning the slow path forever.

On failure the gate acts, loudly: HQC is re-routed to the Toeplitz path, and
a batched facade whose device provider fails is QUARANTINED — its shared
breaker pins the cpu fallback for the process lifetime, because a device
that computes wrong answers cannot be probed back to health by a latency
canary.  ``QRP2P_HEALTH_GATE=0`` skips the gate entirely (trust the device).
"""

from __future__ import annotations

import dataclasses
import hashlib
import hmac
import json
import logging
import os
import pathlib
from typing import Any

from ..native import wipe

logger = logging.getLogger(__name__)

#: bump to invalidate cached verdicts when the probe suite changes
_PROBE_VERSION = 1

#: pinned ML-KEM-768 KAT (seeds -> digests), computed from pyref/mlkem_ref
#: (ML_KEM.KeyGen_internal / Encaps_internal with d=00..1f, z=20..3f,
#: m=40..5f); the device path must reproduce these byte-for-byte
_MLKEM768_KAT = {
    "d": bytes(range(32)),
    "z": bytes(range(32, 64)),
    "m": bytes(range(64, 96)),
    "ek_sha256": "0b7934c83125c788995e2ba6bd761e33046b3e40571be53e023309a29f398cc9",
    "ct_sha256": "dbf4e9aa48b078ad46ec1c9c47bda8c2d2fec9d0e7a21bd48d2238a2abedb856",
    "ss_hex": "9cddd089ffe70e3996e76f7c8d06746df34d07e8657bc0fcf2bb0e1c3084aea1",
}

#: pinned FrodoKEM-640-SHAKE KAT, computed from pyref/frodo_ref (keygen
#: seeds s=00..0f, seedSE=10..1f, z=20..2f; encaps mu=30..3f); the Pallas
#: matmul + inline-SHAKE device path must reproduce these byte-for-byte
_FRODO640SHAKE_KAT = {
    "s": bytes(range(16)),
    "seed_se": bytes(range(16, 32)),
    "z": bytes(range(32, 48)),
    "mu": bytes(range(48, 64)),
    "pk_sha256": "e1933f44de4f6410af9155c4baa3b7454c6e93ec7701971daee3c7d2be3e03f3",
    "ct_sha256": "eefd2976cb8656e208526b33babf14eccd8f9a123db06e6032a30c449c1fc211",
    "ss_hex": "c2cb61ee5b4f5f6679259f09fc6b253b",
}


@dataclasses.dataclass
class HealthVerdict:
    family: str
    ok: bool
    detail: str
    cached: bool = False
    #: False = never write this verdict to the disk cache (e.g. the HQC gate
    #: manages its own marker with its own re-probe policy)
    cacheable: bool = True

    def as_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def env_fingerprint() -> str:
    """(device kind, platform, jax version, jaxlib version) — the axes along
    which accelerated numerics can silently change."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", dev.platform)
    return (
        f"jax={jax.__version__}|jaxlib={jaxlib.__version__}"
        f"|platform={dev.platform}|dev={kind}|probe={_PROBE_VERSION}"
    )


def _cache_dir() -> pathlib.Path:
    override = os.environ.get("QRP2P_HEALTH_CACHE")
    if override:
        return pathlib.Path(override)
    from ..native import _CACHE_DIR

    return pathlib.Path(_CACHE_DIR)


def _marker(family: str, fingerprint: str) -> pathlib.Path:
    digest = hashlib.sha256(f"{family}|{fingerprint}".encode()).hexdigest()[:16]
    return _cache_dir() / f"health_{digest}.json"


def _read_cached(family: str, fingerprint: str) -> HealthVerdict | None:
    """Positive cached verdict for (family, environment), else None."""
    try:
        rec = json.loads(_marker(family, fingerprint).read_text())
        if (isinstance(rec, dict) and rec.get("key") == fingerprint
                and rec.get("family") == family and rec.get("ok")):
            return HealthVerdict(family, True, rec.get("detail", "cached"),
                                 cached=True)
    except (OSError, ValueError, KeyError):
        pass
    return None


def _write_cached(family: str, fingerprint: str, verdict: HealthVerdict) -> None:
    if not verdict.ok or not verdict.cacheable:
        return  # negative verdicts re-probe every startup (self-healing)
    try:
        d = _cache_dir()
        d.mkdir(parents=True, exist_ok=True)
        _marker(family, fingerprint).write_text(json.dumps(
            {"family": family, "key": fingerprint, "ok": True,
             "detail": verdict.detail}
        ))
    except OSError:
        pass


# -- family probes ------------------------------------------------------------


def _check_hqc(algo) -> HealthVerdict:
    """FFT-vs-Toeplitz cyclic-product exactness on-device (the check
    ``chip_smoke.py`` runs).  An unvalidated
    environment is HEALED, not quarantined: ``kem.hqc`` re-routes every HQC
    op to the exact Toeplitz-MXU product for this process and logs why —
    so the verdict is ok either way, with the routing in the detail.

    Never cached here: kem.hqc keeps its own per-environment marker with
    the matching policy (positives cached, failures re-probed per process).
    """
    from ..kem import hqc

    hqc._maybe_gate_fft()  # runs (or recalls) the probe; forces Toeplitz on failure
    if hqc._FORCED_IMPL is not None:
        detail = (f"fft self-check failed; HQC re-routed to the exact "
                  f"{hqc._FORCED_IMPL} cyclic product for this process")
        logger.warning("device health %s: %s", algo.name, detail)
    else:
        detail = f"cyclic product impl {hqc._cyclic_impl()!r} validated on-device"
    return HealthVerdict(algo.name, True, detail, cacheable=False)


def _rows(data: bytes, rows: int):
    """One PUBLIC byte string as ``rows`` identical uint8 rows (a probe's
    batch); secret rows are built inline, where their wipe is."""
    import numpy as np

    return np.repeat(np.frombuffer(data, np.uint8)[None], rows, axis=0)


def _check_mlkem_kat(algo, rows: int = 1) -> HealthVerdict:
    """Pinned FIPS 203 vector through the device (jax) path, in a batch of
    ``rows`` identical rows."""
    import numpy as np

    from ..kem import mlkem

    kat = _MLKEM768_KAT
    kg, enc, dec = mlkem.get("ML-KEM-768")
    d, z, m = (_rows(kat[k], rows) for k in ("d", "z", "m"))
    ek, dk = kg(d, z)
    ek_b = bytes(np.asarray(ek[0], np.uint8))
    if hashlib.sha256(ek_b).hexdigest() != kat["ek_sha256"]:
        return HealthVerdict(algo.name, False, "keygen KAT mismatch (ek)")
    ss, ct = enc(ek, m)
    ct_b = bytes(np.asarray(ct[0], np.uint8))
    ss_b = bytes(np.asarray(ss[0], np.uint8))
    if hashlib.sha256(ct_b).hexdigest() != kat["ct_sha256"]:
        return HealthVerdict(algo.name, False, "encaps KAT mismatch (ct)")
    if ss_b.hex() != kat["ss_hex"]:
        return HealthVerdict(algo.name, False, "encaps KAT mismatch (ss)")
    ss2 = dec(dk, ct)
    if bytes(np.asarray(ss2[0], np.uint8)) != ss_b:
        return HealthVerdict(algo.name, False, "decaps KAT mismatch")
    return HealthVerdict(algo.name, True, "FIPS 203 KAT ok (keygen/encaps/decaps)")


def _check_frodo_kat(algo) -> HealthVerdict:
    """Pinned FrodoKEM-640-SHAKE vector through the device (jax) path, batch-1.

    The SHAKE parameter sets share the Pallas matmul + inline-SHAKE kernels
    (kem/frodo_pallas.py), so one pinned set certifies the whole family's
    tile math on this environment; the AES sets still go through the
    generic roundtrip probe.
    """
    import numpy as np

    from ..kem import frodo

    kat = _FRODO640SHAKE_KAT
    kg, enc, dec = frodo.get("FrodoKEM-640-SHAKE")
    s = np.frombuffer(kat["s"], np.uint8)[None]
    se = np.frombuffer(kat["seed_se"], np.uint8)[None]
    z = np.frombuffer(kat["z"], np.uint8)[None]
    mu = np.frombuffer(kat["mu"], np.uint8)[None]
    pk, sk = kg(s, se, z)
    pk_b = bytes(np.asarray(pk[0], np.uint8))
    if hashlib.sha256(pk_b).hexdigest() != kat["pk_sha256"]:
        return HealthVerdict(algo.name, False, "keygen KAT mismatch (pk)")
    ct, ss = enc(pk, mu)
    ct_b = bytes(np.asarray(ct[0], np.uint8))
    ss_b = bytes(np.asarray(ss[0], np.uint8))
    if hashlib.sha256(ct_b).hexdigest() != kat["ct_sha256"]:
        return HealthVerdict(algo.name, False, "encaps KAT mismatch (ct)")
    if ss_b.hex() != kat["ss_hex"]:
        return HealthVerdict(algo.name, False, "encaps KAT mismatch (ss)")
    ss2 = dec(sk, ct)
    if bytes(np.asarray(ss2[0], np.uint8)) != ss_b:
        return HealthVerdict(algo.name, False, "decaps KAT mismatch")
    return HealthVerdict(algo.name, True,
                         "FrodoKEM KAT ok (keygen/encaps/decaps, pyref-pinned)")


def _check_kem_roundtrip(algo, cpu_twin, rows: int = 1) -> HealthVerdict:
    """Device roundtrip + cross-implementation agreement with the cpu twin,
    on row 0 of a ``rows``-row batch."""
    pks, sks = algo.generate_keypair_batch(rows)
    cts, sss = algo.encapsulate_batch(pks)
    got = algo.decapsulate_batch(sks, cts)
    sk, ct, ss = (bytes(x[0]) for x in (sks, cts, sss))
    try:
        if not hmac.compare_digest(bytes(got[0]), ss):
            return HealthVerdict(algo.name, False,
                                 "device decaps != device encaps")
        if cpu_twin is not None and not hmac.compare_digest(
                cpu_twin.decapsulate(sk, ct), ss):
            return HealthVerdict(
                algo.name, False,
                "cpu reference decaps disagrees with device encaps",
            )
        agree = " + cpu agreement" if cpu_twin is not None else ""
        return HealthVerdict(algo.name, True, f"device roundtrip ok{agree}")
    finally:
        wipe(sks, sss, got, sk, ss)  # probe-only key material


def _check_sig_roundtrip(algo, cpu_twin, rows: int = 1) -> HealthVerdict:
    """Device sign/verify + cross-implementation verify + tamper rejection,
    in batches of ``rows`` rows under one key."""
    import numpy as np

    msg = b"qrp2p device-health probe"
    pk, sk = algo.generate_keypair()
    pks = _rows(pk, rows)
    sks = np.repeat(np.frombuffer(sk, np.uint8)[None], rows, axis=0)
    try:
        sig = bytes(algo.sign_batch(sks, [msg] * rows)[0])
        if not algo.verify_batch(pks, [msg] * rows, [sig] * rows)[0]:
            return HealthVerdict(algo.name, False,
                                 "device verify rejects device sign")
        if cpu_twin is not None and not cpu_twin.verify(pk, msg, sig):
            return HealthVerdict(
                algo.name, False,
                "cpu reference verify rejects device signature",
            )
        bad = bytes([sig[0] ^ 0xFF]) + sig[1:]
        if algo.verify_batch(pks, [msg] * rows, [bad] * rows)[0]:
            return HealthVerdict(algo.name, False,
                                 "device verify accepts tampered sig")
        agree = " + cpu agreement" if cpu_twin is not None else ""
        return HealthVerdict(algo.name, True, f"device sign/verify ok{agree}")
    finally:
        wipe(sk, sks)  # probe-only key material


def _check_fused(facade, rows: int = 1) -> HealthVerdict:
    """Validate the composite fused-handshake path (provider/batched.py
    ``BatchedFused``): the fused programs are a SEPARATE device code path
    from the per-op families (device-side hex render into transcript
    templates + fused sign), so both can pass while these kernels are
    broken.  Probe: one ``keygen_sign`` of ``rows`` rows at the facade's
    LIVE offsets; the rendered-template signature must verify on the cpu twin
    and the generated KEM keypair must roundtrip through the cpu twin —
    covering the shared render/sign machinery the other two composite ops
    reuse."""
    import numpy as np

    fused = facade.fused
    name = f"fused:{fused.name}"
    cpu_kem, cpu_sig = facade.fallback_kem, facade.fallback_sig
    if cpu_kem is None or cpu_sig is None:
        return HealthVerdict(name, True, "no cpu twins armed; skipped")
    sig_pk, sig_sk = cpu_sig.generate_keypair()
    sig_sks = np.repeat(np.frombuffer(sig_sk, np.uint8)[None], rows, axis=0)
    ss = ksks = b""
    try:
        tmpl_len = min(fused.init_template_len,
                       facade.pk_off + 2 * fused.kem.public_key_len + 2)
        tmpl = b"{" + b"0" * (tmpl_len - 2) + b"}"
        pks, ksks, sigs = fused.keygen_sign_batch(
            sig_sks, [tmpl] * rows, facade.pk_off
        )
        pk, ksk = (bytes(np.asarray(pks[0], np.uint8)),
                   bytes(np.asarray(ksks[0], np.uint8)))
        rendered = (tmpl[: facade.pk_off] + pk.hex().encode()
                    + tmpl[facade.pk_off + 2 * len(pk):])
        if not cpu_sig.verify(sig_pk, rendered, sigs[0]):
            return HealthVerdict(
                name, False,
                "cpu reference rejects the fused keygen_sign signature "
                "(device-side render/sign numerics)",
            )
        ct, ss = cpu_kem.encapsulate(pk)
        if not hmac.compare_digest(cpu_kem.decapsulate(ksk, ct), ss):
            return HealthVerdict(
                name, False, "fused keygen keypair fails the cpu KEM roundtrip",
            )
        return HealthVerdict(name, True,
                             "fused keygen_sign render/sign/keypair ok vs cpu")
    finally:
        wipe(sig_sk, sig_sks, ksks, ss)  # probe-only key material


#: pinned RFC 8439 §2.8.2 AEAD vector: the device seal must reproduce the
#: spec ciphertext+tag byte-for-byte before the batched data plane is
#: trusted with live traffic
_CHACHA_KAT = {
    "key": bytes(range(0x80, 0xA0)),
    "nonce": bytes([0x07, 0, 0, 0]) + bytes(range(0x40, 0x48)),
    "aad": bytes.fromhex("50515253c0c1c2c3c4c5c6c7"),
    "pt": (b"Ladies and Gentlemen of the class of '99: If I could offer "
           b"you only one tip for the future, sunscreen would be it."),
    "ct_tag_sha256":
        "4e54427e462f3beb69677d39865c5da8d57f603a85f7bf71368dce8ec9b9933c",
}


def _check_aead(facade) -> HealthVerdict:
    """Validate a batched AEAD facade's device path: the pinned RFC 8439
    §2.8.2 vector through the device seal, tamper rejection on open, and
    cross-implementation agreement with the scalar twin (device-sealed
    frames must open on the independent scalar path and vice versa)."""
    import numpy as np

    name = f"aead:{facade.name}"
    kat = _CHACHA_KAT
    dev, scalar = facade.device, facade.scalar
    keys = np.frombuffer(kat["key"], np.uint8)[None]
    nonces = np.frombuffer(kat["nonce"], np.uint8)[None]
    sealed = dev.seal_batch(keys, nonces, [kat["pt"]], [kat["aad"]])[0]
    if hashlib.sha256(sealed).hexdigest() != kat["ct_tag_sha256"]:
        return HealthVerdict(name, False, "RFC 8439 §2.8.2 KAT mismatch")
    got = dev.open_batch(keys, nonces, [sealed], [kat["aad"]])[0]
    if not isinstance(got, bytes) or got != kat["pt"]:
        return HealthVerdict(name, False, "device open rejects device seal")
    bad = bytes([sealed[0] ^ 0xFF]) + sealed[1:]
    if not isinstance(dev.open_batch(keys, nonces, [bad],
                                     [kat["aad"]])[0], ValueError):
        return HealthVerdict(name, False,
                             "device open accepts tampered ciphertext")
    if scalar is not None:
        if scalar.open_(kat["key"], kat["nonce"], sealed,
                        kat["aad"]) != kat["pt"]:
            return HealthVerdict(
                name, False, "scalar twin rejects device seal")
    agree = " + scalar agreement" if scalar is not None else ""
    return HealthVerdict(name, True, f"RFC 8439 KAT + tamper-reject ok{agree}")


def _probe(algo, cpu_twin, rows: int = 1) -> HealthVerdict:
    name = getattr(algo, "name", type(algo).__name__)
    if name.startswith("HQC"):
        return _check_hqc(algo)
    from .base import KeyExchangeAlgorithm, SignatureAlgorithm

    if name == "ML-KEM-768":
        # the pinned vector covers keygen/encaps/decaps end to end; the
        # generic roundtrip would add nothing
        return _check_mlkem_kat(algo, rows)
    if name.startswith("FrodoKEM") and name.endswith("SHAKE"):
        # certifies the shared Pallas matmul + inline-SHAKE kernel family
        return _check_frodo_kat(algo)
    if isinstance(algo, KeyExchangeAlgorithm):
        return _check_kem_roundtrip(algo, cpu_twin, rows)
    if isinstance(algo, SignatureAlgorithm):
        return _check_sig_roundtrip(algo, cpu_twin, rows)
    return HealthVerdict(name, True, "no probe registered; skipped")


# -- public API ---------------------------------------------------------------


def gate_enabled() -> bool:
    return os.environ.get("QRP2P_HEALTH_GATE", "1") != "0"


def ensure_validated(algo, cpu_twin=None, rows: int = 1) -> HealthVerdict:
    """Run (or recall) the health probe for one provider's family.

    Positive verdicts are cached on disk keyed by the environment
    fingerprint; negatives are returned but never cached.  Probe crashes
    count as failures — an accelerator that cannot run the probe cannot be
    trusted with live traffic either.
    """
    family = getattr(algo, "name", type(algo).__name__)
    if getattr(algo, "backend", "cpu") != "tpu":
        return HealthVerdict(family, True, "cpu backend; no device to gate")
    fingerprint = env_fingerprint()
    cached = _read_cached(family, fingerprint)
    if cached is not None:
        return cached
    try:
        verdict = _probe(algo, cpu_twin, rows)
    except Exception as e:
        logger.exception("device-health probe for %s crashed", family)
        verdict = HealthVerdict(family, False, f"probe crashed: {e!r}")
    _write_cached(family, fingerprint, verdict)
    return verdict


def gate_facades(*facades) -> list[HealthVerdict]:
    """Validate each batched facade's device provider at startup; quarantine
    the shared breaker on failure (only when a cpu fallback is armed — with
    no fallback there is nothing safer to route to, so only log).

    Accepts ``provider.batched.BatchedKEM`` / ``BatchedSignature`` /
    ``BatchedFused`` facades (None entries are skipped) and returns the
    verdicts.
    """
    out: list[HealthVerdict] = []
    if not gate_enabled():
        return out
    for facade in facades:
        if facade is None:
            continue
        rows = getattr(facade, "bucket_floor", 1)
        if hasattr(facade, "fused"):
            verdict = _ensure_fused_validated(facade, rows)
        elif hasattr(facade, "device"):  # BatchedAEAD (data plane)
            verdict = _ensure_aead_validated(facade)
        else:
            verdict = ensure_validated(facade.algo,
                                       getattr(facade, "fallback", None), rows)
        out.append(verdict)
        from ..obs import flight as _flight

        if verdict.ok:
            _flight.record("health_ok", family=verdict.family,
                           detail=verdict.detail, cached=verdict.cached)
            logger.info("device health %s: ok (%s)%s", verdict.family,
                        verdict.detail, " [cached]" if verdict.cached else "")
            continue
        logger.error(
            "device health %s: FAILED (%s) in environment %s",
            verdict.family, verdict.detail, env_fingerprint(),
        )
        # the quarantine below emits the breaker_quarantined trigger; this
        # event records the verdict itself (also for the no-fallback case)
        _flight.record("health_failed", family=verdict.family,
                       detail=verdict.detail, env=env_fingerprint())
        have_fb = (getattr(facade, "fallback", None) is not None
                   or getattr(facade, "fallback_kem", None) is not None)
        if have_fb:
            why = (f"{verdict.family} failed the device-health gate: "
                   f"{verdict.detail}")
            sched = getattr(facade, "scheduler", None)
            if sched is not None:
                # the verdict is about the device PROGRAMS, which every
                # shard runs: quarantine the whole placement axis, not
                # just the shard-0 compat breaker
                sched.quarantine_all(why)
            else:
                facade.breaker.quarantine(why)
    return out


def _ensure_aead_validated(facade) -> HealthVerdict:
    """Cached wrapper around :func:`_check_aead` (same verdict policy as
    ensure_validated: positives cached per environment, failures
    re-probed)."""
    family = f"aead:{facade.name}"
    fingerprint = env_fingerprint()
    cached = _read_cached(family, fingerprint)
    if cached is not None:
        return cached
    try:
        verdict = _check_aead(facade)
    except Exception as e:
        logger.exception("device-health probe for %s crashed", family)
        verdict = HealthVerdict(family, False, f"probe crashed: {e!r}")
    verdict.family = family
    _write_cached(family, fingerprint, verdict)
    return verdict


def _ensure_fused_validated(facade, rows: int = 1) -> HealthVerdict:
    """Cached wrapper around :func:`_check_fused` (same verdict policy as
    ensure_validated; the cache key carries the live transcript offsets —
    jit keys on them, so a different protocol layout re-probes)."""
    family = f"fused:{facade.fused.name}@{facade.pk_off}"
    fingerprint = env_fingerprint()
    cached = _read_cached(family, fingerprint)
    if cached is not None:
        return cached
    try:
        verdict = _check_fused(facade, rows)
    except Exception as e:
        logger.exception("device-health probe for %s crashed", family)
        verdict = HealthVerdict(family, False, f"probe crashed: {e!r}")
    verdict.family = family
    _write_cached(family, fingerprint, verdict)
    return verdict
