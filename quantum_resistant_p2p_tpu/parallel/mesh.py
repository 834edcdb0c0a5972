"""Device mesh + sharded batched handshake step.

TPU-native design
-----------------
Handshakes are embarrassingly parallel, so the right decomposition is pure
data sharding: a 1-D mesh over all chips with the batch dimension of every
operand sharded across the ``"batch"`` axis.  XLA then runs each chip's shard
of keygen/encaps/decaps locally with zero cross-chip traffic on the hot path;
the only collective is a `psum` reducing per-shard success counts — a few
bytes over ICI per flush.

This replaces nothing in the reference (it had no device mesh; its
"distributed backend" is asyncio TCP, networking/p2p_node.py:277-397, which we
keep host-side unchanged): the mesh exists purely inside the crypto provider,
below the plugin boundary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kem import mlkem
from ..pyref.mlkem_ref import PARAMS

BATCH_AXIS = "batch"


def make_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, have {len(devs)} "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
                f"JAX_PLATFORMS=cpu before importing jax to emulate a mesh)"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (BATCH_AXIS,))


def shard_devices(n: int | None = None) -> list[jax.Device]:
    """The first ``n`` devices of the placement axis (default: all).

    The latency-path twin of :func:`make_mesh`: where the mesh shards ONE
    big batch across chips (shard_map), the placement axis
    (provider/scheduler.py) pins each small queue flush WHOLE onto one of
    these devices.  Raises like make_mesh when fewer devices exist."""
    devs = jax.devices()
    if n is not None:
        if len(devs) < n:
            raise RuntimeError(
                f"need {n} devices, have {len(devs)} "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
                f"JAX_PLATFORMS=cpu before importing jax to emulate)"
            )
        devs = devs[:n]
    return list(devs)


def shard_batch(mesh: Mesh, *arrays: jax.Array):
    """Place arrays with their leading (batch) dim sharded across the mesh."""
    sharding = NamedSharding(mesh, P(BATCH_AXIS))
    out = tuple(jax.device_put(a, sharding) for a in arrays)
    return out[0] if len(out) == 1 else out


def handshake_step(p, d, z, m):
    """One full KEM handshake over a batch: keygen -> encaps -> decaps.

    Returns (ek, ct, key_initiator, key_responder, n_ok) where n_ok is the
    global count of shared-secret agreements (a cross-chip psum when the batch
    is sharded).  This is the framework's "training step" analog: the complete
    per-handshake device computation of reference app/messaging.py:546-1134's
    five hot FFI calls, batched.
    """
    ek, dk = mlkem.keygen(p, d, z)
    key_e, ct = mlkem.encaps(p, ek, m)
    key_d = mlkem.decaps(p, dk, ct)
    n_ok = jnp.sum(jnp.all(key_e == key_d, axis=-1).astype(jnp.int32))
    return ek, ct, key_e, key_d, n_ok


@functools.cache
def make_sharded_handshake(mesh: Mesh, param_name: str = "ML-KEM-768"):
    """Jit the full handshake step over the batch-sharded mesh: each chip
    runs its shard (``shard_map``: the Pallas kernels inside cannot be
    partitioned by the compiler), and ``n_ok`` is psum-reduced."""
    p = PARAMS[param_name]

    def local(d, z, m):
        ek, ct, key_e, key_d, n_ok = handshake_step(p, d, z, m)
        return ek, ct, key_e, key_d, jax.lax.psum(n_ok, BATCH_AXIS)

    data = P(BATCH_AXIS)
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(data,) * 3,
        out_specs=(data,) * 4 + (P(),), check_vma=False))
