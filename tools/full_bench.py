"""Full BASELINE.json benchmark suite (all 5 configs) on the real device.

Configs (BASELINE.json):
  1. ML-KEM-768 single keygen+encaps+decaps — scalar CPU path (native C++,
     the role liboqs plays for the reference's crypto_algorithms_tester.py).
  2. ML-KEM-512/768/1024 batch=4096 keygen/encaps/decaps on the TPU backend,
     plus a batch-scaling curve for ML-KEM-768 encaps (256 -> 16384).
  3. FrodoKEM-640-AES batch=1024 on TPU (dense-LWE MXU matmul showcase).
  4. ML-DSA-65 batch=8192 sign + verify; SPHINCS+-SHA2-128s and 128f verify.
  5. 1000-peer swarm: real TCP handshakes through the batching queue
     (tools/swarm_bench.py).

Every timed region uses utils.benchmarking.timeit (back-to-back dispatches
ending in ``block_until_ready``).  Results append incrementally to --out as JSON so a partial run
still leaves numbers behind.  An audit section records XLA cost analysis
(flops / bytes accessed) for the headline program so the numbers can be
checked against a roofline, and a sanity check proves ciphertexts depend on
the message input (nothing constant-folded).

Input residency: large operands (public keys, secret keys, ciphertexts) are
``jax.device_put`` BEFORE timing, so configs 2-4 measure device compute
throughput — the same methodology as liboqs's in-memory speed tests, and
what "ops/sec/chip" means; config 5 (swarm) times the complete production
pipeline including every host<->device hop.

Usage: python -m tools.full_bench [--configs 1 2 3 4 5] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BASELINE_ENCAPS_PER_S = 50_000.0  # north-star target (BASELINE.md)
REFERENCE_HANDSHAKE_S = 0.25     # reference's measured ML-KEM+ML-DSA handshake

RNG = np.random.default_rng(20260730)


def _u8(shape) -> np.ndarray:
    return RNG.integers(0, 256, size=shape, dtype=np.uint8)


def jnp_tile(arr, reps: int):
    """Tile a device array along axis 0 (stays on device)."""
    import jax.numpy as jnp

    return jnp.tile(jnp.asarray(arr), (reps,) + (1,) * (arr.ndim - 1))


def _result(out: dict, section: str, payload: dict, path: Path) -> None:
    # per-section stamp: the resume-merge (main) can combine runs from
    # different days, so provenance lives with each section, not the file
    out.setdefault(section, {}).update(payload)
    out[section]["measured_at"] = time.strftime("%Y%m%d_%H%M%S")
    path.write_text(json.dumps(out, indent=2))
    print(f"[{section}] {json.dumps(payload)}", flush=True)


# -- config 1: scalar CPU path ------------------------------------------------

def bench_config1(out: dict, path: Path) -> None:
    from quantum_resistant_p2p_tpu.provider import get_kem, get_signature

    kem = get_kem("ML-KEM-768", "cpu")
    res = {"impl": kem.description}
    pk, sk = kem.generate_keypair()
    ct, ss = kem.encapsulate(pk)

    def rate(fn, n=200) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return n / (time.perf_counter() - t0)

    res["keygen_per_s"] = round(rate(kem.generate_keypair), 1)
    res["encaps_per_s"] = round(rate(lambda: kem.encapsulate(pk)), 1)
    res["decaps_per_s"] = round(rate(lambda: kem.decapsulate(sk, ct)), 1)

    sig = get_signature("ML-DSA-65", "cpu")
    spk, ssk = sig.generate_keypair()
    s = sig.sign(ssk, b"bench")
    res["mldsa65_sign_per_s"] = round(rate(lambda: sig.sign(ssk, b"bench"), 100), 1)
    res["mldsa65_verify_per_s"] = round(rate(lambda: sig.verify(spk, b"bench", s), 100), 1)
    _result(out, "config1_scalar_cpu", res, path)


# -- config 2: batched ML-KEM on TPU -----------------------------------------

def bench_config2(out: dict, path: Path) -> None:
    import jax

    from quantum_resistant_p2p_tpu.kem import mlkem
    from quantum_resistant_p2p_tpu.utils.benchmarking import sync, timeit

    batch = 4096
    for name in ("ML-KEM-512", "ML-KEM-768", "ML-KEM-1024"):
        kg, enc, dec = mlkem.get(name)
        # device-resident operands per the module docstring (ek/dk/ct are
        # device outputs already; the seeds/messages are device_put so no
        # timed call re-sends them from the host)
        d, z, m = (jax.device_put(_u8((batch, 32))) for _ in range(3))
        ek, dk = kg(d, z)
        sync((ek, dk))
        key, ct = enc(ek, m)
        sync((key, ct))
        res = {
            "batch": batch,
            "keygen_per_s": round(batch / timeit(kg, d, z), 1),
            "encaps_per_s": round(batch / timeit(enc, ek, m), 1),
            "decaps_per_s": round(batch / timeit(dec, dk, ct), 1),
        }
        if name == "ML-KEM-768":
            res["vs_baseline_encaps"] = round(res["encaps_per_s"] / BASELINE_ENCAPS_PER_S, 3)
            # audit: XLA cost analysis of the compiled encaps program
            try:
                lowered = jax.jit(lambda e, mm: mlkem.get(name)[1](e, mm)).lower(
                    np.asarray(ek), m
                )
                ca = lowered.compile().cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0]
                res["xla_cost_analysis"] = {
                    k: ca[k] for k in ("flops", "bytes accessed") if k in ca
                }
            except Exception as e:  # cost analysis is best-effort per backend
                res["xla_cost_analysis"] = f"unavailable: {e}"
            # sanity: ciphertext depends on m (nothing folded to a constant)
            m2 = np.asarray(m).copy()
            m2[0, 0] ^= 1
            _, ct2 = enc(ek, m2)
            res["ct_depends_on_m"] = bool(
                (np.asarray(ct)[0] != np.asarray(ct2)[0]).any()
                and (np.asarray(ct)[1] == np.asarray(ct2)[1]).all()
            )
        _result(out, f"config2_{name}", res, path)

    # batch-scaling curve for the headline op
    kg, enc, _ = mlkem.get("ML-KEM-768")
    curve = {}
    for b in (256, 512, 1024, 2048, 4096, 8192, 16384):
        d, z, m = (jax.device_put(_u8((b, 32))) for _ in range(3))
        ek, _dk = kg(d, z)
        sync(ek)
        curve[str(b)] = round(b / timeit(enc, ek, m), 1)
    _result(out, "config2_scaling_mlkem768_encaps", curve, path)


# -- config 3: FrodoKEM on TPU ------------------------------------------------

def bench_config3(out: dict, path: Path) -> None:
    from quantum_resistant_p2p_tpu.kem import frodo
    from quantum_resistant_p2p_tpu.pyref import frodo_ref
    from quantum_resistant_p2p_tpu.utils.benchmarking import sync, timeit

    p = frodo_ref.FRODO640AES
    batch = 1024
    # Single dispatches >= 1024 reproducibly crash this environment's TPU
    # worker (kem/frodo.py MAX_DEVICE_BATCH); the 1024 batch runs as
    # back-to-back sliced dispatches, exactly as the provider does.
    step = frodo.MAX_DEVICE_BATCH
    reps = batch // step
    kg, enc, dec = frodo.get(p.name)
    s1, s2, s3 = _u8((step, p.len_sec)), _u8((step, p.len_sec)), _u8((step, p.len_sec))
    pk, sk = kg(s1, s2, s3)
    sync((pk, sk))
    mu = _u8((step, p.len_sec))
    ct, ss = enc(pk, mu)
    sync((ct, ss))

    def n_of(fn, *a):
        def run():
            o = None
            for _ in range(reps):
                o = fn(*a)
            return o

        return run

    _result(
        out,
        "config3_frodo640aes",
        {
            "batch": batch,
            "dispatch_slice": step,
            "keygen_per_s": round(batch / timeit(n_of(kg, s1, s2, s3)), 1),
            "encaps_per_s": round(batch / timeit(n_of(enc, pk, mu)), 1),
            "decaps_per_s": round(batch / timeit(n_of(dec, sk, ct)), 1),
        },
        path,
    )


# -- config 4: signatures on TPU ---------------------------------------------

def bench_config4(out: dict, path: Path) -> None:
    from quantum_resistant_p2p_tpu.sig import mldsa, sphincs
    from quantum_resistant_p2p_tpu.pyref import slhdsa_ref
    from quantum_resistant_p2p_tpu.utils.benchmarking import sync, timeit

    batch = 8192
    kg, sign_mu, verify_mu = mldsa.get("ML-DSA-65")
    xi = _u8((batch, 32))
    pk, sk = kg(xi)
    sync((pk, sk))
    mus, rnds = _u8((batch, 64)), _u8((batch, 32))
    sigs, done = sign_mu(sk, mus, rnds)
    sync((sigs, done))
    assert bool(np.asarray(done).all())
    _result(
        out,
        "config4_mldsa65",
        {
            "batch": batch,
            "keygen_per_s": round(batch / timeit(kg, xi), 1),
            "sign_per_s": round(batch / timeit(sign_mu, sk, mus, rnds), 1),
            "verify_per_s": round(batch / timeit(verify_mu, pk, mus, sigs), 1),
        },
        path,
    )

    # config 4 names 128s VERIFY; sign batches are kept small for the 's'
    # sets (FORS holds k * 2^a leaves in HBM during signing).
    for name, vbatch, sbatch in (
        ("SPHINCS+-SHA2-128s-simple", 2048, 128),
        ("SPHINCS+-SHA2-128f-simple", 2048, 1024),
    ):
        p = slhdsa_ref.PARAMS[name]
        skg, ssign, sverify = sphincs.get(name)
        n = p.n
        sk_seed, sk_prf, pk_seed = _u8((sbatch, n)), _u8((sbatch, n)), _u8((sbatch, n))
        spk, ssk = skg(sk_seed, sk_prf, pk_seed)
        sync((spk, ssk))
        r, digest = _u8((sbatch, n)), _u8((sbatch, p.m))
        sigs = ssign(ssk, r, digest)
        sync(sigs)
        reps = vbatch // sbatch
        vpk = jnp_tile(spk, reps)
        vdig = jnp_tile(digest, reps)
        vsigs = jnp_tile(sigs, reps)
        ok = sverify(vpk, vdig, vsigs)
        assert bool(np.asarray(ok).all())
        _result(
            out,
            f"config4_{name}",
            {
                "verify_batch": vbatch,
                "verify_per_s": round(vbatch / timeit(sverify, vpk, vdig, vsigs), 1),
                "sign_batch": sbatch,
                "sign_per_s": round(sbatch / timeit(ssign, ssk, r, digest), 1),
            },
            path,
        )


# -- config 5: swarm ----------------------------------------------------------

def bench_config5(out: dict, path: Path, peers: int) -> None:
    import asyncio

    from tools.swarm_bench import run_swarm

    stats = asyncio.run(
        run_swarm(peers, backend="tpu", use_batching=True, max_batch=4096,
                  max_wait_ms=3.0, concurrency=256, warmup=32)
    )
    _result(out, "config5_swarm", stats, path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="*", type=int, default=[1, 2, 3, 4, 5])
    ap.add_argument("--peers", type=int, default=1000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    stamp = time.strftime("%Y%m%d_%H%M%S")
    path = Path(args.out or f"bench_results/full_bench_{stamp}.json")
    path.parent.mkdir(parents=True, exist_ok=True)
    # resume-friendly: merge into an existing results file (re-run a single
    # crashed config without losing the rest)
    out: dict = {}
    if path.exists():
        try:
            out = json.loads(path.read_text())
        except json.JSONDecodeError:
            out = {}
    out["stamp"] = stamp
    try:
        import jax

        from quantum_resistant_p2p_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        out["platform"] = jax.default_backend()
        out["devices"] = [str(d) for d in jax.devices()]
    except Exception:
        pass
    path.write_text(json.dumps(out, indent=2))

    dispatch = {1: bench_config1, 2: bench_config2, 3: bench_config3,
                4: bench_config4,
                5: lambda o, p: bench_config5(o, p, args.peers)}
    unknown = [c for c in args.configs if c not in dispatch]
    if unknown:
        ap.error(f"unknown configs {unknown}; valid: 1-5")
    for cfg in args.configs:
        t0 = time.time()
        dispatch[cfg](out, path)
        print(f"config {cfg} done in {time.time() - t0:.1f}s", flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
