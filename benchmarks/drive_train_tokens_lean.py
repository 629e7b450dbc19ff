"""Driver of the cells whose traffic file says ``"kind": "train_tokens_lean"``:
``drive_train_tokens.py``'s run with two pieces of its own.

1. **The reference keeps AdamW's moments on the host between its updates.**
   ``drive_train_tokens.reference_readings`` holds parameters, accumulated
   gradient, ``mu`` and ``nu`` on the device while a group of documents is
   differentiated: 16 B a parameter beside the group's gradient and float32
   activations. For 680.4M parameters that is 10.9 GB + 2.7 GB + the
   activations of 8,704 tokens through 32 heads of 192 / 128: compiled here for
   a v5e, the accumulating program alone peaks at 12.9 GB (arguments 5.4, its
   gradient and activations 7.5), 18.4 GB with the moments beside it, on a
   chip of 16 GiB. The moments are read by the update only: they wait on the
   host (two copies of 5.4 GB a step) and the programs, groups of documents,
   ramp and readings are ``drive_train_tokens.py``'s own.

2. **A traced run reads device seconds by the program's scopes.** The trace's
   events carry HLO instruction names; which ``jax.named_scope`` an instruction
   was traced under is in the compiled step's metadata. Before the window the
   step is compiled once more (from the compile cache) for its text, and the
   reduction gains ``scope_s``: seconds of the span's device ops whose
   ``op_name`` holds each ``hg_`` scope of ``SCOPES`` (a ``while`` is left to
   its body's ops), per chip. A program without a scope reads nothing there.
"""

from __future__ import annotations

import functools
import importlib
import json
import re
import sys
from typing import Dict, List
from unittest import mock

import compare
import drive_train
import drive_train_tokens as tokens
import tracing

# the scopes whose device seconds a traced run reports (``ctx["trace"]["scope_s"]``)
SCOPES = ("hg_mla_proj", "hg_router", "hg_moe_dispatch", "hg_moe_combine", "hg_shared_expert", "hg_mtp",
          "hg_token_loss", "hg_optimizer")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WHILE = re.compile(r"\bwhile\(")


def reference_readings(mpnn_type: str, arch: dict, input_dim: int, seed: int,
                       step_records: List[List[List[dict]]], lr: float, mode: str = "f32",
                       drop_half: bool = False, warmup_steps: int = 0) -> Dict[str, Dict[str, float]]:
    """``drive_train_tokens.reference_readings`` with the moments on the host
    while a step's groups are differentiated."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc

    ref = importlib.import_module(f"reference.{mpnn_type.lower()}")
    spec = ref.weight_spec(arch, input_dim)
    start = rc.make_weights(spec, seed)
    p, buffers = start["params"], start["batch_stats"]
    del start
    steps = [shards[0] for shards in step_records]
    if drop_half:
        steps = [recs[: max(len(recs) // 2, 1)] for recs in steps]
    grouped = [tokens.token_groups(recs, tokens.MICRO_TOKENS) for recs in steps]
    every = [g for groups in grouped for g in groups]
    n_pad = rc.pad_to(max(sum(r["x"].shape[0] for r in g) for g in every) + 1, 128)
    e_pad = rc.pad_to(max(sum(r["senders"].shape[0] for r in g) for g in every), 128)
    g_pad = max(len(g) for g in every) + 1
    accumulate, apply = tokens._reference_programs(mpnn_type, json.dumps(arch, sort_keys=True), mode)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, p)
    pairs = lambda g: sum(max(r["x"].shape[0] - 1, 0) for r in g)  # (token, next token) pairs
    losses, g1, moments, t = [], None, None, jnp.zeros((), jnp.float32)
    for i, groups in enumerate(grouped):
        total, acc, loss, loads = max(sum(pairs(g) for g in groups), 1), zeros(), 0.0, 0.0
        for g in groups:
            batch = {k: jnp.asarray(v) for k, v in rc.batch_records(g, n_pad, e_pad, g_pad).items()}
            acc, part, group_loads = accumulate(p, acc, batch, pairs(g) / total, buffers)
            loss, loads = loss + float(part), loads + group_loads
        buffers = ref.balance(buffers, loads, arch)
        ramp = min((i + 1) / warmup_steps, 1.0) if warmup_steps > 0 else 1.0
        mu, nu = (zeros(), zeros()) if moments is None else jax.device_put(moments)
        p, opt, norms = apply(p, {"mu": mu, "nu": nu, "t": t}, acc, jnp.float32(lr * ramp))
        del mu, nu, acc
        t = opt["t"]
        moments = jax.device_get((opt["mu"], opt["nu"])) if i + 1 < len(grouped) else None
        del opt
        losses.append(loss)
        if i == 0:
            g1 = compare.flat_norms(jax.device_get(norms))
    _, delta_norms = compare.leaf_norms_fn()
    return {"loss": losses, "grad": g1,
            "dparam": compare.flat_norms(delta_norms(p, rc.make_weights(spec, seed)["params"]))}


def op_names_of(hlo_text: str) -> Dict[str, str]:
    """`%name type[shape]` -> op_name metadata of a compiled module's
    instructions, a ``while`` left out (its body's instructions are there)."""
    out = {}
    for line in hlo_text.splitlines():
        m, n = _INSTR.match(line), _OP_NAME.search(line)
        if m and n and not _WHILE.search(line.split(", metadata=")[0]):
            out.setdefault(f"{m.group(1)} {m.group(2)}", n.group(1))
    return out


def scope_seconds(rows, names: Dict[str, str], chips: int) -> Dict[str, float]:
    planes = sorted({r[0] for r in rows if r[0].startswith(tracing.DEVICE_PREFIX)})[:chips]
    out = {s: 0.0 for s in SCOPES}
    for plane, line, text, _, dur in rows:
        if plane not in planes or line != tracing.OPS_LINE:
            continue
        m = _INSTR.match(text)
        op_name = names.get(f"{m.group(1)} {m.group(2)}", "") if m else ""
        for s in SCOPES:
            if s in op_name:
                out[s] += dur / 1e9 / max(len(planes), 1)
    return out


class _StepText:
    """Wraps the compiled step: keeps the abstract arguments of its first call
    and, asked once before the window, the op names of the program compiled
    for them."""

    def __init__(self, step):
        self.step, self.args, self.names = step, None, {}

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, state, batch, rng):
        import jax

        if self.args is None:
            def abstract(x):
                aval = jax.api_util.shaped_abstractify(x)
                return jax.ShapeDtypeStruct(aval.shape, aval.dtype, weak_type=aval.weak_type)

            self.args = jax.tree_util.tree_map(abstract, (state, batch, rng))
        return self.step(state, batch, rng)

    def read_names(self) -> None:
        if self.args is not None and hasattr(self.step, "lower"):
            self.names = op_names_of(self.step.lower(*self.args).compile().as_text())


def _scoped(fn):
    """``drive_train.drive`` with the step's op names read after the first
    steps and ``scope_s`` added to the span's reduction."""
    logs: List[_StepText] = []
    setup, first_steps, reduce_events = drive_train.setup, drive_train.first_steps, tracing.reduce_events

    def logged_setup(*a, **k):
        env = setup(*a, **k)
        env.raw_step = _StepText(env.raw_step)
        logs.append(env.raw_step)
        return env

    def named_first_steps(*a, **k):
        out = first_steps(*a, **k)
        for log in logs:
            log.read_names()
        return out

    def scoped_reduce(rows, step_prefix, chips=1, *a, **k):
        reduced = reduce_events(rows, step_prefix, chips, *a, **k)
        names = {key: v for log in logs for key, v in log.names.items()}
        if reduced is not None and names:
            reduced["scope_s"] = scope_seconds(rows, names, chips)
            print("scope_s " + json.dumps({**reduced["scope_s"], "busy_s": reduced["busy_s"]}), file=sys.stderr)
        return reduced

    @functools.wraps(fn)
    def run(*a, **k):
        with mock.patch.object(drive_train, "setup", logged_setup), \
                mock.patch.object(drive_train, "first_steps", named_first_steps), \
                mock.patch.object(tracing, "reduce_events", scoped_reduce):
            return fn(*a, **k)

    return run


def drive(ctx, seed, seconds, trace, *args, **kwargs):
    """``drive_train.drive`` with the reference's step replaced and, traced,
    the scopes' seconds read."""
    readings = functools.partial(reference_readings, warmup_steps=tokens.warmup_of(ctx["traffic"]))
    run = _scoped(drive_train.drive) if trace else drive_train.drive
    with mock.patch.object(compare, "reference_readings", readings):
        return run(ctx, seed, seconds, trace, *args, **kwargs)
