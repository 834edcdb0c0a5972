"""The served path's Pallas kernels compile for a described v5e.

Nothing here runs on a chip: each test lowers a routed function at a real
width for one device of a described ``v5e:2x2`` topology, compiles it with
the TPU compiler (installed with jax), and checks that the program holds a
Mosaic kernel (``tpu_custom_call``).  Interpret-mode tests cannot see a
slice that is not tile-aligned or a kernel that wants too much VMEM; this
can, at no chip time.  ``chip_smoke.py`` runs the same kernels on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

LANES = 1024  # one full grid step of the 1024-lane sponge tile


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # or the compiler logs under /tmp
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out of the cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture
def pallas_routing(monkeypatch):
    """The routed functions ask ``jax.default_backend()``, which is the CPU
    here; steer them onto the chip's branch for the trace."""
    from quantum_resistant_p2p_tpu.core import keccak

    monkeypatch.setattr(keccak, "_use_pallas", lambda: True)


def _spec(one_chip, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernels():
    """(name, fn, [(shape, dtype)...]) for every launcher of the served
    path, at the widths the served buckets and MAX_DEVICE_BATCH reach."""
    from quantum_resistant_p2p_tpu.core import keccak, sha256, sha512
    from quantum_resistant_p2p_tpu.core.chacha_pallas import aead_core
    from quantum_resistant_p2p_tpu.kem import frodo, mlkem
    from quantum_resistant_p2p_tpu.pyref.frodo_ref import NBAR
    from quantum_resistant_p2p_tpu.pyref.frodo_ref import PARAMS as FRODO
    from quantum_resistant_p2p_tpu.sig import mldsa

    u8, u32, i32 = np.uint8, np.uint32, np.int32
    two = np.arange(2, dtype=np.uint8)
    p, p5 = FRODO["FrodoKEM-640-SHAKE"], FRODO["FrodoKEM-1344-SHAKE"]
    frodo_rows = 512  # kem/frodo.py MAX_DEVICE_BATCH
    return {
        "keccak_sponge": (lambda m: keccak.shake256(m, 272),
                          [((LANES, 64), u8)]),
        "mlkem_sample_ntt": (mlkem.sample_ntt, [((LANES, 34), u8)]),
        "mlkem_cbd_ntt": (lambda s: mlkem._prf_cbd_ntt(s, two, 2),
                          [((LANES // 2, 32), u8)]),
        "mldsa_rej_ntt": (mldsa.rej_ntt_poly, [((LANES, 34), u8)]),
        "mldsa_rej_bounded": (lambda s: mldsa.rej_bounded_poly(2, s),
                              [((LANES, 66), u8)]),
        "mldsa_ntt": (lambda f: (mldsa.ntt(f), mldsa.ntt_inv(f)),
                      [((LANES, 256), i32)]),
        "chacha20_blocks": (
            lambda k, n, d, ln, a, al: aead_core(k, n, d, ln, a, al,
                                                 seal=True, use_pallas=True),
            [((256, 32), u8), ((256, 12), u8), ((256, 1024), u8),
             ((256,), i32), ((256, 16), u8), ((256,), i32)]),
        "frodo_a_times_s": (lambda sa, s: frodo._a_times_s(p, sa, s),
                            [((frodo_rows, 16), u8),
                             ((frodo_rows, p.n, NBAR), i32)]),
        "frodo_s_times_a": (lambda sa, sp: frodo._s_times_a(p, sp, sa),
                            [((frodo_rows, 16), u8),
                             ((frodo_rows, NBAR, p.n), i32)]),
        "frodo1344_s_times_a": (lambda sa, sp: frodo._s_times_a(p5, sp, sa),
                                [((frodo_rows, 16), u8),
                                 ((frodo_rows, NBAR, p5.n), i32)]),
        "sha256_compress": (sha256.compress,
                            [((LANES, 8), u32), ((LANES, 64), u8)]),
        "sha512_compress": (lambda h, lo, b: sha512.compress((h, lo), b),
                            [((LANES, 8), u32), ((LANES, 8), u32),
                             ((LANES, 128), u8)]),
    }


@pytest.mark.parametrize("name", [
    "keccak_sponge", "mlkem_sample_ntt", "mlkem_cbd_ntt", "mldsa_rej_ntt",
    "mldsa_rej_bounded", "mldsa_ntt", "chacha20_blocks", "frodo_a_times_s",
    "frodo_s_times_a", "frodo1344_s_times_a", "sha256_compress", "sha512_compress",
])
def test_kernel_compiles_for_v5e(name, one_chip, pallas_routing):
    import jax

    fn, shapes = _kernels()[name]
    args = [_spec(one_chip, shape, dtype) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the program compiled for v5e"
