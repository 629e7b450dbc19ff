"""Driver of the cells whose traffic file says ``"kind": "train_tokens"``:
``drive_train.py``'s set-up, window and comparison, unchanged, with ONE piece
replaced: how the reference's three AdamW steps are computed, so that they fit.

``compare.reference_readings`` jits a step that takes parameters and optimizer
state, returns new ones AND the gradients, and donates nothing: for P float32
parameters it holds 4 P in, 4 P out and P of gradients at once, 36 bytes a
parameter, and the whole batch's float32 activations beside them. The decoder
configuration has 601.6M parameters: 21.7 GB on a 16 GB chip before any
activation (read on the chip, PR 29: `Attempting to reserve 7.96G ... 55.69M
free`; compiled here for a v5e with donation alone: 18.1 GB). Here:

- the step's documents are taken a few at a time (at most ``MICRO_TOKENS``
  tokens): documents do not see each other and the loss is a mean over tokens,
  so the gradient is the token-weighted sum of the groups' gradients, exactly;
  each group's float32 activations are a quarter of the batch's;
- the buffers the configuration carries beside its parameters (the router's
  balancing bias, `batch_stats` in the program) follow their own rule from step
  to step (``reference/<mpnn_type>.py balance``), from the whole step's loads;
- the accumulated gradient, the parameters and the optimizer state are donated
  to the programs that update them; the gradient's leaf norms are returned in
  its place; the start parameters are drawn again from the seed at the end
  rather than kept;
- the rate of step k follows the traffic's ``Optimizer.warmup_steps`` (k/N of
  the rate over the first N steps, as ``hydragnn_tpu/train/optimizer.py``
  ramps it): ``compare.reference_readings`` knows one constant rate.

Everything else (the loss of ``reference/<mpnn_type>.py``, ``reference/
common.py``'s AdamW, weights and batching, the norms, ``compare.compare``) is
the harness's own.
"""

from __future__ import annotations

import functools
import importlib
import json
from typing import Dict, List
from unittest import mock

import compare
import drive_train

# a group of documents of one reference pass; the longest document is 8,192
MICRO_TOKENS = 8704


@functools.lru_cache(maxsize=None)
def _reference_programs(mpnn_type: str, arch_json: str, mode: str):
    import jax
    import jax.numpy as jnp
    from reference import common as rc

    ref = importlib.import_module(f"reference.{mpnn_type.lower()}")
    arch = json.loads(arch_json)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def accumulate(p, acc, batch, weight, buffers):
        (loss, loads), grads = jax.value_and_grad(
            lambda q: ref.loss_and_loads(q, batch, arch, mode, buffers), has_aux=True)(p)
        return jax.tree_util.tree_map(lambda a, g: a + weight * g, acc, grads), loss * weight, loads

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(p, opt, acc, lr):
        norms = jax.tree_util.tree_map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), acc)
        new, opt = rc.adamw_update(p, acc, opt, lr)
        return new, opt, norms

    return accumulate, apply


def token_groups(records: List[dict], limit: int) -> List[List[dict]]:
    """Consecutive documents, at most ``limit`` tokens a group."""
    groups, cur, n = [], [], 0
    for r in records:
        k = r["x"].shape[0]
        if cur and n + k > limit:
            groups.append(cur)
            cur, n = [], 0
        cur.append(r)
        n += k
    return groups + ([cur] if cur else [])


def reference_readings(mpnn_type: str, arch: dict, input_dim: int, seed: int,
                       step_records: List[List[List[dict]]], lr: float, mode: str = "f32",
                       drop_half: bool = False, warmup_steps: int = 0) -> Dict[str, Dict[str, float]]:
    """``compare.reference_readings``'s three AdamW steps and readings, one
    chip's (``step_records[step][0]``), a group of documents at a time; step
    k of the first ``warmup_steps`` at k / warmup_steps of ``lr``."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc

    ref = importlib.import_module(f"reference.{mpnn_type.lower()}")
    spec = ref.weight_spec(arch, input_dim)
    start = rc.make_weights(spec, seed)
    # the buffers (the router's balancing bias) follow their own rule from step to step
    p, buffers = start["params"], start["batch_stats"]
    del start
    steps = [shards[0] for shards in step_records]
    if drop_half:
        steps = [recs[: max(len(recs) // 2, 1)] for recs in steps]
    grouped = [token_groups(recs, MICRO_TOKENS) for recs in steps]
    every = [g for groups in grouped for g in groups]
    n_pad = rc.pad_to(max(sum(r["x"].shape[0] for r in g) for g in every) + 1, 128)
    e_pad = rc.pad_to(max(sum(r["senders"].shape[0] for r in g) for g in every), 128)
    g_pad = max(len(g) for g in every) + 1
    accumulate, apply = _reference_programs(mpnn_type, json.dumps(arch, sort_keys=True), mode)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, p)
    opt = {"mu": zeros(), "nu": zeros(), "t": jnp.zeros((), jnp.float32)}
    pairs = lambda g: sum(max(r["x"].shape[0] - 1, 0) for r in g)  # (token, next token) pairs
    losses, g1 = [], None
    for i, groups in enumerate(grouped):
        total, acc, loss, loads = max(sum(pairs(g) for g in groups), 1), zeros(), 0.0, 0.0
        for g in groups:
            batch = {k: jnp.asarray(v) for k, v in rc.batch_records(g, n_pad, e_pad, g_pad).items()}
            acc, part, group_loads = accumulate(p, acc, batch, pairs(g) / total, buffers)
            loss, loads = loss + float(part), loads + group_loads
        buffers = ref.balance(buffers, loads, arch)
        ramp = min((i + 1) / warmup_steps, 1.0) if warmup_steps > 0 else 1.0
        p, opt, norms = apply(p, opt, acc, jnp.float32(lr * ramp))
        losses.append(loss)
        if i == 0:
            g1 = compare.flat_norms(jax.device_get(norms))
    del opt
    _, delta_norms = compare.leaf_norms_fn()
    return {"loss": losses, "grad": g1,
            "dparam": compare.flat_norms(delta_norms(p, rc.make_weights(spec, seed)["params"]))}


def warmup_of(traffic: dict) -> int:
    return int(traffic.get("training_overrides", {}).get("Optimizer", {}).get("warmup_steps", 0) or 0)


def drive(ctx, *args, **kwargs):
    """``drive_train.drive`` with the reference's step replaced."""
    readings = functools.partial(reference_readings, warmup_steps=warmup_of(ctx["traffic"]))
    with mock.patch.object(compare, "reference_readings", readings):
        return drive_train.drive(ctx, *args, **kwargs)
