"""Activation-sharding hook for the residual stream.

The launch layer installs a NamedSharding for the residual stream
(B, S, d) — e.g. P(None, "model", None): Megatron-style sequence sharding
across the TP group between blocks. Model scan bodies call
``shard_residual`` on the carry; under the FL worker vmap the leading W dim
is batched out (unconstrained), so the same model code works on CPU (hook
unset => no-op) and on the production mesh.

Why: without this, GSPMD may keep the remat checkpoint stack
(L, B, S, d) fully replicated across the model axis — 48-96 GiB/device for
the 34B config. Sequence-sharding the carry makes the saved activations
1/TP of that, at the cost of an all-gather per layer on recompute.

The same hook records the mesh the launch layer partitions the program
over: ``partitioned()`` tells model code that the partitioner will split
it across devices, where a Mosaic kernel (which the partitioner cannot
split) must give way to its jnp reference.
"""
from __future__ import annotations

import contextlib

import jax

_RESIDUAL_SHARDING = None
_MESH = None


@contextlib.contextmanager
def activation_sharding(sharding, mesh=None):
    """sharding: NamedSharding for per-worker (B, S, d) activations, or
    None; mesh: the mesh the program is partitioned over."""
    global _RESIDUAL_SHARDING, _MESH
    prev = _RESIDUAL_SHARDING, _MESH
    _RESIDUAL_SHARDING = sharding
    _MESH = mesh
    try:
        yield
    finally:
        _RESIDUAL_SHARDING, _MESH = prev


def partitioned() -> bool:
    """Whether the program being traced is split over more than one device
    by the partitioner (the mesh installed by ``activation_sharding``)."""
    return _MESH is not None and _MESH.size > 1


def shard_residual(x):
    if _RESIDUAL_SHARDING is None:
        return x
    return jax.lax.with_sharding_constraint(x, _RESIDUAL_SHARDING)


def gather_weight(w):
    """Under sequence-sharded activations the partitioner must all-gather
    model-sharded weights at each use; constraining the weight itself to
    replicated makes that gather happen on the bf16 parameter (344 MiB for
    the 34B MLP) instead of on an f32-converted copy (688 MiB) fused into
    the matmul."""
    if _RESIDUAL_SHARDING is None:
        return w
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(_RESIDUAL_SHARDING.mesh, P(*([None] * w.ndim)))
    return jax.lax.with_sharding_constraint(w, rep)


def replicate_kv(k, v):
    """When sequence-sharded activations are active, pin projected K/V to
    replicated — one bf16 all-gather per layer instead of per-KV-chunk
    f32 gathers inside the flash scan."""
    if _RESIDUAL_SHARDING is None:
        return k, v
    mesh = _RESIDUAL_SHARDING.mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P(*([None] * k.ndim)))
    return (jax.lax.with_sharding_constraint(k, rep),
            jax.lax.with_sharding_constraint(v, rep))
