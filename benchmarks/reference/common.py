"""Plain pieces shared by the references: dense layer with a precision switch,
masked batch norm, MLPs, the masked L1 loss, AdamW, own batching and own
weights. ``jax.numpy`` only; nothing of ``hydragnn_tpu`` is imported.

Precision modes of ``dense`` (every matrix product of a reference goes
through it):

- ``f32``  : float32 operands, ``precision=HIGHEST``: the reference proper.
- ``bf16`` : operands and result rounded to bfloat16 (the control below
  float32).
- ``fp8``  : operands scaled per tensor to float8_e4m3fn's range and rounded to
  it, product accumulated in f32, result rounded to bfloat16 (the control
  below bfloat16).

A parameter tree is a nested dict whose names follow the flax tree of the
program (``graph_convs_0/edge_lin2/kernel`` ...): that is a naming convention
only; the values are drawn here from the seed.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, mode: str):
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":  # per-tensor scale to the format's range, as fp8 is used
        scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x


def dense(x, kernel, bias=None, mode: str = "f32"):
    y = jnp.dot(_round(x, mode), _round(kernel, mode), precision=HIGHEST)
    if bias is not None:
        y = y + _round(bias, mode)
    if mode in ("bf16", "fp8"):
        y = y.astype(jnp.bfloat16).astype(jnp.float32)
    return y


def act_round(x, mode: str):
    """Round an activation as a low-precision step would keep it."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if mode != "f32" else x


def leaky(x, slope: float):
    return jnp.where(x >= 0, x, slope * x)


def mlp(p: Dict, x, n_layers: int, act, final_activation: bool, mode: str):
    for i in range(n_layers):
        d = p[f"Dense_{i}"]
        x = dense(x, d["kernel"], d.get("bias"), mode)
        if i < n_layers - 1 or final_activation:
            x = act(x)
    return x


def batch_norm_train(x, node_w, scale, bias, eps: float = 1e-5):
    """Batch statistics over real rows only (``node_w`` is 1.0 on real rows)."""
    m = node_w[:, None]
    n = jnp.maximum(jnp.sum(m), 1.0)
    mean = jnp.sum(x * m, axis=0) / n
    var = jnp.sum(((x - mean) ** 2) * m, axis=0) / n
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def segment_sum(values, ids, n: int):
    return jnp.zeros((n,) + values.shape[1:], values.dtype).at[ids].add(values)


def masked_mae(pred, target, row_w):
    m = row_w[:, None]
    denom = jnp.maximum(jnp.sum(m) * pred.shape[-1], 1.0)
    return jnp.sum(jnp.abs(pred - target) * m) / denom


def edge_geometry(pos, senders, receivers):
    vec = pos[senders] - pos[receivers]
    length = jnp.sqrt(jnp.maximum(jnp.sum(vec * vec, axis=-1, keepdims=True), 1e-12))
    return vec, length


# ---------------------------------------------------------------- AdamW

ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}


def adamw_update(params, grads, opt, lr: float):
    b1, b2, eps, wd = (ADAMW[k] for k in ("b1", "b2", "eps", "weight_decay"))
    t = opt["t"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"], grads)
    c1, c2 = 1 - b1**t, 1 - b2**t
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
        params, mu, nu,
    )
    return new, {"mu": mu, "nu": nu, "t": t}


# ---------------------------------------------------------------- batching

def pad_to(n: int, multiple: int) -> int:
    return ((int(n) + multiple - 1) // multiple) * multiple


def batch_records(records: Sequence[Dict[str, np.ndarray]], n_pad: int, e_pad: int,
                  g_pad: int) -> Dict[str, np.ndarray]:
    """The reference's own batch of raw records: graphs concatenated, one
    dummy node (index ``n_real``) takes every padding edge, float masks."""
    n = sum(r["x"].shape[0] for r in records)
    e = sum(r["senders"].shape[0] for r in records)
    g = len(records)
    if not (n < n_pad and e <= e_pad and g < g_pad):
        raise ValueError(f"batch ({n}, {e}, {g}) does not fit its padding ({n_pad}, {e_pad}, {g_pad})")
    fx = records[0]["x"].shape[1]
    out = {
        "x": np.zeros((n_pad, fx), np.float32), "pos": np.zeros((n_pad, 3), np.float32),
        "forces": np.zeros((n_pad, 3), np.float32),
        "node_graph": np.full((n_pad,), g_pad - 1, np.int32),
        "node_w": np.zeros((n_pad,), np.float32),
        "senders": np.full((e_pad,), n, np.int32), "receivers": np.full((e_pad,), n, np.int32),
        "edge_w": np.zeros((e_pad,), np.float32),
        "energy": np.zeros((g_pad, 1), np.float32), "graph_w": np.zeros((g_pad,), np.float32),
    }
    no = eo = 0
    for gi, r in enumerate(records):
        k, m = r["x"].shape[0], r["senders"].shape[0]
        out["x"][no:no + k] = r["x"]
        out["pos"][no:no + k] = r["pos"]
        out["forces"][no:no + k] = r["forces"]
        out["node_graph"][no:no + k] = gi
        out["node_w"][no:no + k] = 1.0
        out["senders"][eo:eo + m] = r["senders"] + no
        out["receivers"][eo:eo + m] = r["receivers"] + no
        out["edge_w"][eo:eo + m] = 1.0
        out["energy"][gi] = r["energy"]
        out["graph_w"][gi] = 1.0
        no += k
        eo += m
    return out


# ---------------------------------------------------------------- weights

def _leaf(key, shape, kind: str):
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    fan_in, fan_out = shape[-2], shape[-1]
    if kind == "lecun":
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)
    if kind == "mirror":  # columns in (w, -w) pairs, LeCun scale
        half = (fan_out + 1) // 2
        w = jax.random.normal(key, shape[:-1] + (half,), jnp.float32) / np.sqrt(fan_in)
        return jnp.concatenate([w, -w[..., : fan_out - half]], axis=-1)
    if kind == "gate":  # variance_scaling(0.001, fan_avg, uniform)
        lim = np.sqrt(3.0 * 0.001 / ((fan_in + fan_out) / 2.0))
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)
    raise ValueError(kind)


def seed_key(seed: int):
    """A key from any whole number up to 64 bits."""
    s = int(seed)
    return jax.random.wrap_key_data(
        jnp.asarray([(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], jnp.uint32)
    )


def make_weights(spec: List[tuple], seed: int) -> Dict:
    """All leaves of ``spec`` ((path, shape, kind) rows) in one jitted call."""
    @jax.jit
    def build(key):
        flat = {}
        for i, (path, shape, kind) in enumerate(spec):
            flat[path] = _leaf(jax.random.fold_in(key, i), tuple(shape), kind)
        return flat

    flat = build(seed_key(seed))
    tree: Dict = {}
    for path, _, _ in spec:
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = flat[path]
    return tree


def dense_spec(prefix: tuple, fan_in: int, fan_out: int, bias: bool = True,
               kind: str = "lecun", bank: bool = False) -> List[tuple]:
    lead = (1,) if bank else ()
    rows = [(prefix + ("kernel",), lead + (fan_in, fan_out), kind)]
    if bias:
        rows.append((prefix + ("bias",), lead + (fan_out,), "zeros"))
    return rows


def mlp_spec(prefix: tuple, fan_in: int, features: Sequence[int], mirror: bool = False,
             final_activation: bool = False, bank: bool = False) -> List[tuple]:
    rows = []
    for i, f in enumerate(features):
        last = i == len(features) - 1
        kind = "mirror" if mirror and (not last or final_activation) else "lecun"
        rows += dense_spec(prefix + (f"Dense_{i}",), fan_in, f, kind=kind, bank=bank)
        fan_in = f
    return rows


def unbank(p: Dict) -> Dict:
    """Drop the leading branch axis (one branch) of a decoder bank."""
    return jax.tree_util.tree_map(lambda a: a[0], p)
