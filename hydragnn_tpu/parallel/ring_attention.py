"""Ring self-attention: exact attention over a node axis sharded across a
mesh axis — sequence/context parallelism for graphs too large for one chip.

The reference has no long-context machinery (its GPS attention is dense
per-graph on one device, hydragnn/globalAtt/gps.py:125-141, and molecular
graphs are small). This module goes beyond parity: for *giant* graphs —
periodic supercells, mesoscale assemblies — whose node set must be sharded
over devices, global attention still needs every (query, key) pair. Ring
attention computes it exactly:

- every device holds its local query/key/value block ([n_local, ...]);
- K/V blocks rotate around the mesh axis via ``ppermute`` (ICI
  neighbor-to-neighbor traffic, no all-gather memory spike);
- softmax is accumulated *online* (flash-attention style running max /
  denominator), so the full [N, N] score matrix never materializes.

After ``n_devices`` rotations each query block has attended to every key
block; results are exact (up to float reassociation) vs dense softmax
attention — asserted by tests/test_ring_attention.py on the virtual
8-device CPU mesh.

Use inside ``shard_map`` over the mesh axis that shards nodes, e.g.::

    out = shard_map(
        lambda q, k, v, m: ring_self_attention(q, k, v, m, axis_name="data"),
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data")),
        out_specs=P("data"),
    )(q, k, v, mask)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _block_attend(q, k, v, kmask, m, denom, acc, scale, use_flash=False):
    """One online-softmax accumulation step against a K/V block.

    q: [n_q, H, dh]; k/v: [n_k, H, dh]; kmask: [n_k] bool;
    m/denom: [n_q, H]; acc: [n_q, H, dh].

    ``use_flash``: compute the block's (max, denom, acc) partial with the
    segment-masked flash kernel's inner loop
    (ops/pallas_flash_attention.py ``flash_block_summary`` — the local
    score block stays in VMEM) and merge it here in plain jnp; the dense
    einsum below is the identical math and the off-TPU route.
    """
    if use_flash:
        from ..ops.pallas_flash_attention import flash_block_summary

        m_b, l_b, acc_b = flash_block_summary(
            q, k, v, kmask, interpret=jax.default_backend() != "tpu",
        )
        new_m = jnp.maximum(m, m_b)
        corr = jnp.exp(m - new_m)
        corr_b = jnp.exp(m_b - new_m)
        denom = denom * corr + l_b * corr_b
        acc = acc * corr[..., None] + acc_b * corr_b[..., None]
        return new_m.astype(m.dtype), denom, acc
    # [n_q, H, n_k]
    logits = jnp.einsum("qhd,khd->qhk", q, k) * scale
    neg = jnp.finfo(logits.dtype).min
    logits = jnp.where(kmask[None, None, :], logits, neg)
    blk_max = jnp.max(logits, axis=-1)  # [n_q, H]
    new_m = jnp.maximum(m, blk_max)
    # correction of previously accumulated terms; exp(neg - new_m) underflows
    # to 0 for fully-masked blocks, keeping denom/acc unchanged
    corr = jnp.exp(m - new_m)
    p = jnp.exp(logits - new_m[..., None])  # [n_q, H, n_k]
    p = jnp.where(kmask[None, None, :], p, 0.0)
    denom = denom * corr + jnp.sum(p, axis=-1)
    acc = acc * corr[..., None] + jnp.einsum("qhk,khd->qhd", p, v)
    return new_m, denom, acc


def ring_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    key_mask: Optional[jnp.ndarray],
    axis_name: str,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Exact multi-head self-attention with the key/value blocks ring-rotated
    around ``axis_name``. Must run inside ``shard_map``/``pmap`` over that
    axis.

    Shapes (per device): q/k/v ``[n_local, H, dh]``; ``key_mask``
    ``[n_local]`` bool marking real (non-padding) keys, or None.
    Returns ``[n_local, H, dh]`` — each local query attended over the
    GLOBAL key set. ``use_flash`` routes each per-chip block-attend through
    the Pallas flash inner loop when the route is enabled
    (ops/pallas_flash_attention.py ``_flash_route_enabled``); the math is
    identical, the local score block just never leaves VMEM.
    """
    from ..ops.pallas_flash_attention import _flash_route_enabled

    use_flash = use_flash and _flash_route_enabled()
    n_dev = jax.lax.psum(1, axis_name)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    if key_mask is None:
        key_mask = jnp.ones(k.shape[:1], bool)

    # initial carries derived from q so shard_map types them as varying
    # along the mesh axis (a bare constant would be axis-invariant and
    # mismatch the scan carry after the first ppermute step)
    m = jnp.full_like(q[..., 0], jnp.finfo(q.dtype).min)  # [n_q, H]
    denom = jnp.zeros_like(q[..., 0])
    acc = jnp.zeros_like(q)

    # neighbor ring: device i receives from i+1 (send left) every step, so
    # after s steps it holds block (i + s) mod n_dev
    perm = [(s, (s - 1) % n_dev) for s in range(n_dev)]

    def step(carry, _):
        k_blk, v_blk, kmask, m, denom, acc = carry
        m, denom, acc = _block_attend(
            q, k_blk, v_blk, kmask, m, denom, acc, scale, use_flash
        )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        kmask = jax.lax.ppermute(kmask, axis_name, perm)
        return (k_blk, v_blk, kmask, m, denom, acc), None

    # n_dev - 1 attend+rotate steps, then the final block without the
    # rotation: the last ppermute would only complete the ring back to the
    # start, a full K+V shard of wasted ICI traffic per call
    if n_dev > 1:
        (k, v, key_mask, m, denom, acc), _ = jax.lax.scan(
            step, (k, v, key_mask, m, denom, acc), None, length=n_dev - 1
        )
    m, denom, acc = _block_attend(
        q, k, v, key_mask, m, denom, acc, scale, use_flash
    )
    return acc / jnp.maximum(denom, 1e-30)[..., None]


def sharded_global_attention(mesh, axis_name: str = "data",
                             use_flash: bool = False):
    """A jitted callable computing exact global self-attention over arrays
    whose leading (node) axis is sharded on ``axis_name`` of ``mesh``:
    (q, k, v, key_mask) -> out, all ``[N_global, H, dh]`` sharded the same
    way. The convenience wrapper around ``ring_self_attention`` for the
    giant-graph regime (docs/MULTIHOST.md)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fn = shard_map(
        lambda q, k, v, mask: ring_self_attention(
            q, k, v, mask, axis_name, use_flash=use_flash
        ),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        # pallas_call has no replication rule (same reason the GPS module's
        # shard_map disables the check, models/gps.py)
        check_vma=False,
    )
    return jax.jit(fn)
