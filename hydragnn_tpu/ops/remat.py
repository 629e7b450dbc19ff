"""Tuned rematerialization policies (``Training.remat_policy``).

Through PR 10 every remat decision was a scattered bare ``jax.checkpoint``:
the fused-edge kernel call (models/layers.py ``_FusedEdgeDense``), the GPS
flash-attention call (models/gps.py), and — when
``Training.conv_checkpointing`` is on — the whole loss function
(train/loop.py, parallel/dp.py, parallel/branch.py). Bare checkpoint is the
maximal policy: recompute EVERYTHING inside the wrapped region during the
backward. That is the right default for the kernel call sites (their whole
point is keeping [E, C] tangent residuals out of the forward), but it is a
blunt instrument for the whole-loss wrap: recomputing the Pallas kernels
themselves in the backward re-pays their launch + redundant-revisit cost
when saving just their (node-sized, already-HBM-resident) outputs would do.

``Training.remat_policy`` names the policy once and applies it everywhere a
remat wrap happens:

- ``full`` (default — today's per-call behavior): bare ``jax.checkpoint``,
  recompute everything;
- ``dots``: ``jax.checkpoint_policies.checkpoint_dots`` — save matmul
  outputs, recompute the elementwise chains between them;
- ``names``: ``jax.checkpoint_policies.save_only_these_names`` over the
  kernel outputs tagged below — the Pallas kernels run ONCE (forward),
  their node-sized outputs are saved, and everything else inside the wrap
  is recomputed. The tuned point for kernel-heavy message paths;
- ``none``: kernel call sites are left unwrapped (save everything); the
  whole-loss ``conv_checkpointing`` wrap degrades to ``full`` (asking for
  conv checkpointing and no-remat at once is a contradiction — the
  checkpoint must exist for the flag to mean anything).

The policy is surfaced in the compile plane's report next to the flops/MFU
accounting (train/compile_plane.py) so a banked bench cell always records
which recompute schedule its FLOP count was measured under — remat changes
XLA's counted FLOPs, and an A/B across policies is meaningless without it.
"""

from __future__ import annotations

import jax

REMAT_POLICIES = ("none", "dots", "names", "full")

# checkpoint_name tags planted on the Pallas kernel outputs at their call
# sites — the save set of the ``names`` policy. One tuple so the policy and
# the tags can never drift apart.
KERNEL_OUTPUT_NAMES = (
    "fused_edge_sum",      # models/layers.py _FusedEdgeDense
    "multi_agg_moments",   # models/pna.py pna_aggregate (multi-agg route)
    "flash_attention_out", # models/gps.py flash attention
)

# what the causal flash launch's backward reads of its forward
# (ops/pallas_flash_attention.py ``_causal_vjp_fwd``): the save set of a
# decoder layer's remat (models/decoder.py ``remat_in_training``), so that the
# forward kernel runs once a step. Not among ``KERNEL_OUTPUT_NAMES``: the
# ``names`` policy and the steps compiled under it stay as they are
CAUSAL_FLASH_RESIDUAL_NAMES = ("flash_causal_out", "flash_causal_lse")

# what a sparse-attention layer keeps of its indexer across the same remat
# (ops/pallas_dsa_indexer.py): the selection with its threshold, tie bound and
# log-sum-exp, and the indexer loss's gradient, whole after the forward pass
DSA_RESIDUAL_NAMES = ("dsa_select", "dsa_index_grads")


def tag(x, name: str):
    """Tag a kernel output (array or pytree) for ``save_only_these_names``.
    A no-op unless the surrounding ``jax.checkpoint`` runs a policy that
    saves ``name``, so call sites tag unconditionally."""
    from jax.ad_checkpoint import checkpoint_name

    return jax.tree_util.tree_map(lambda v: checkpoint_name(v, name), x)


def _policy_of(policy: str):
    if policy not in REMAT_POLICIES:
        raise ValueError(
            f"remat_policy {policy!r} must be one of {REMAT_POLICIES}"
        )
    if policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if policy == "names":
        return jax.checkpoint_policies.save_only_these_names(
            *KERNEL_OUTPUT_NAMES
        )
    return None  # none / full: no policy object


def kernel_remat(fn, policy: str = "full"):
    """Remat wrap for a Pallas-kernel call site. ``none`` leaves the call
    unwrapped (store residuals); every other policy checkpoints with the
    corresponding save rule."""
    if policy == "none":
        return fn
    pol = _policy_of(policy)
    return jax.checkpoint(fn, policy=pol) if pol is not None else jax.checkpoint(fn)


def loss_remat(fn, policy: str = "full"):
    """Remat wrap for the whole-loss ``conv_checkpointing`` sites. ``none``
    and ``full`` keep today's bare checkpoint (the flag asked for a
    checkpoint; ``none`` only relaxes the kernel call sites)."""
    pol = _policy_of(policy)
    return jax.checkpoint(fn, policy=pol) if pol is not None else jax.checkpoint(fn)
