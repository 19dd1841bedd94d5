"""Activation-sharding policy context.

Model code is mesh-agnostic; the launcher installs a policy that pins
activation shardings at layer boundaries (without it, GSPMD can resolve
the FSDP weight-sharding/batch-sharding conflict on the `data` axis by
all-gathering *activations* to the global batch — observed in the first
mamba2 dry-run, 16x memory blow-up; see EXPERIMENTS §Dry-run notes).

Kinds:
  "btd"   — (B, S, D) residual-stream activations: batch over (pod, data)
  "btv"   — logits
  "cache" — decode caches (handled by explicit in_shardings instead)
"""
from __future__ import annotations

import contextlib

_POLICY = None


@contextlib.contextmanager
def activation_policy(policy):
    global _POLICY
    old = _POLICY
    _POLICY = policy
    try:
        yield
    finally:
        _POLICY = old


def constrain(x, kind: str):
    return _POLICY(x, kind) if _POLICY is not None else x


def moe_scatter(slot, xk, n_rows: int):
    """Dispatch scatter: per-batch-row  zeros(n_rows, D).at[slot_b].add(xk_b).

    Under a mesh policy this runs inside shard_map over the batch axes —
    a *batched* scatter is unpartitionable for GSPMD (it all-gathers the
    (B, S*K, D) operand and all-reduces its gradients: 20+ TB/step
    observed on dbrx train, §Perf cell A iter 5); inside shard_map the
    scatter is shard-local with zero collectives and a local-gather
    gradient."""
    import jax
    import jax.numpy as jnp

    D = xk.shape[-1]

    def scatter_rows(slot_s, xk_s):
        def one(slot_b, xk_b):
            return jnp.zeros((n_rows, D), dtype=xk.dtype).at[slot_b].add(xk_b)

        return jax.vmap(one)(slot_s, xk_s)

    pol = _POLICY
    mesh = getattr(pol, "mesh", None) if pol is not None else None
    if mesh is None:
        return scatter_rows(slot, xk)
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    ba = pol.batch_axes
    size = pol.batch_size
    if slot.shape[0] % size != 0:
        return scatter_rows(slot, xk)
    return shard_map(
        scatter_rows,
        mesh=mesh,
        in_specs=(P(ba, None), P(ba, None, None)),
        out_specs=P(ba, None, None),
    )(slot, xk)


def moe_gather(eout, slot):
    """Combine gather: per-batch-row eout_b[slot_b] — shard_map'd for the
    same reason as moe_scatter (the batched gather's BACKWARD is a batched
    scatter, which GSPMD replicates)."""
    import jax
    import jax.numpy as jnp

    def gather_rows(eout_s, slot_s):
        return jnp.take_along_axis(eout_s, slot_s[..., None], axis=1)

    pol = _POLICY
    mesh = getattr(pol, "mesh", None) if pol is not None else None
    if mesh is None:
        return gather_rows(eout, slot)
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    ba = pol.batch_axes
    if slot.shape[0] % pol.batch_size != 0:
        return gather_rows(eout, slot)
    return shard_map(
        gather_rows,
        mesh=mesh,
        in_specs=(P(ba, None, None), P(ba, None)),
        out_specs=P(ba, None, None),
    )(eout, slot)


def make_crypto_policy(mesh, plan):
    """Activation policy for the crypto serving engine: pins the
    polymul *stage boundaries* to the ``partition.polymul_specs``
    layout — ``"segments"``/``"limbs"`` batch-sharded over ``data``,
    ``"residues"`` channel-sharded over ``model`` — so GSPMD cannot
    resolve the batched dispatch by all-gathering residue tensors (the
    crypto twin of the LM policy below; the heavy cascade itself runs
    under an explicit ``shard_map`` in
    :mod:`repro.serve.crypto_engine`).

    ``plan`` is anything with ``.t`` (an ``api.Plan`` or its params).
    Constraints apply only when the named dim divides the mesh axes;
    everything else passes through untouched.
    """
    import jax
    from jax.sharding import NamedSharding

    from repro.sharding import partition

    specs = partition.polymul_specs(mesh, plan)
    ba = partition.batch_axes(mesh)
    size = 1
    for a in ba:
        size *= mesh.shape[a]

    def policy(x, kind):
        spec = specs.get(kind)
        if spec is None or x.ndim != 3:
            return x
        batch_dim = 1 if kind == "residues" else 0
        if x.shape[batch_dim] % size != 0:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    policy.mesh = mesh
    policy.batch_axes = ba
    policy.batch_size = size
    return policy


def make_mesh_policy(mesh, *, strategy: str = "baseline"):
    """Activation policies (the §Perf levers):

    baseline — batch over (pod, data); everything else to GSPMD.
    seqpar   — additionally shard the SEQUENCE dim of (B, S, D) activations
               over `model` (context parallelism): splits the O(S^2)
               attention score tensors 16-way, turning softmax cross-shard
               reductions into (B, H, Sq)-sized collectives instead of
               S^2 resharding.  Prefill/long-context lever.
    dp_only  — small models: batch over ALL mesh axes (pure DP; the 16-way
               TP of a <1B model is pure collective overhead).  Used with
               replicated param specs (see dryrun --strategy dp_only).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import partition

    ba = partition.batch_axes(mesh)
    if strategy == "dp_only":
        ba = tuple(mesh.axis_names)
    size = 1
    for a in ba:
        size *= mesh.shape[a]
    model_n = mesh.shape["model"]

    def policy(x, kind):
        if kind in ("btd", "btv") and x.ndim >= 2 and x.shape[0] % size == 0:
            dims = [ba] + [None] * (x.ndim - 1)
            if (
                strategy == "seqpar"
                and x.ndim >= 3
                and x.shape[1] > 1
                and x.shape[1] % model_n == 0
            ):
                dims[1] = "model"
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*dims))
            )
        if kind == "moe_buf" and x.ndim == 3 and x.shape[0] % size == 0:
            # (B, E*C+1, D) row-local scatter result: strictly batch-sharded
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(ba, None, None))
            )
        if kind == "moe_w" and x.ndim == 3 and x.shape[0] % model_n == 0:
            # experts stay model-sharded; FSDP dims gathered for compute
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("model", None, None))
            )
        if (
            kind == "moe_tokens"
            and x.ndim == 4
            and x.shape[0] % size == 0
            and x.shape[1] % model_n == 0
        ):
            # (B, E, C, D) dispatch buffer: batch rows data-parallel,
            # experts model-local (the canonical MoE all-to-all boundary)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(ba, "model", None, None))
            )
        return x

    policy.mesh = mesh
    policy.batch_axes = ba
    policy.batch_size = size
    return policy
