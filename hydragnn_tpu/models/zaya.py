"""ZAYA stack: a decoder language model on the graph plumbing.

Token = node, document = graph, packed sequence = packed batch. The batcher
lays graphs out contiguously along the flat node axis, so ``node_graph`` is a
packed sequence's segment ids, the node's index within its graph is its
position, and "previous node" is ``t - 1`` if it is in the same graph, else
zero: documents never see each other, through the convolutions, the value
shift, the attention or the router state.

A layer is a CCA sublayer (compressed convolutional attention,
arXiv:2510.04476: latent q/k projections, two causal convolutions along the
node axis, value shift by one node, q-k mean, normalised q/k with a
temperature, partial RoPE, causal grouped-query attention through
ops/pallas_flash_attention.py ``flash_causal_attention``) and a top-1 expert
sublayer behind an MLP router with a state carried from layer to layer
(arXiv:2511.17127), joined to the residual stream by learned scale-and-shift
vectors. The expert sublayer is told which experts it holds
(``Architecture.experts_held``): it routes over all ``num_experts``, computes
the tokens whose expert lives here (a grouped product over the rows sorted by
expert, ops/pallas_grouped_matmul.py; no token is dropped) and adds nothing
for the others. On one chip the layer runs without its exchange.

Node ids ride in ``batch.z`` (int32; ``mp_cast`` leaves it alone). The
router path is float32 (top-1 is discrete): the model states which leaves the
mixed-precision cast leaves alone, ``ZayaModel.float32_leaves``. The model returns the final normalised
hidden state; the tied head and the next-node cross-entropy are
train/loss.py ``token_loss`` (the ``[T, V]`` logits never exist whole).
Every layer is rematerialised in training: one saved residual stream a layer
and, of its flash launch, ``o`` and a row's ``lse`` (``decoder.remat_in_training``).
What this stack shares with models/joyai.py (norm, RoPE, embedding, the
routes to the two kernels, the balancing rule, initial scales) is
models/decoder.py.

The plain reference of these equations, item by item, is
benchmarks/reference/zaya.py.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..data.graph import GraphBatch
from ..utils import tracer as tr
# what the decoder stacks share lives in models/decoder.py; the names stay
# importable from here
from .decoder import (  # noqa: F401
    INIT as _INIT, ROUTER_BIAS_GAIN, balanced_bias, batch_aux, causal_attention, causal_pairs,
    dense as _dense, embed_tokens, expert_loads, expert_products, flash_blocks, flash_steps, graphs_overflow, held_slot,
    layer_params, pick, poison, remat_in_training, rms_norm, rope)

ARCH_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "cca_time0",
    "cca_time1", "partial_rotary_factor", "rope_theta", "num_experts",
    "experts_held", "moe_intermediate_size", "router_hidden_size",
    "vocab_size", "rms_norm_eps", "loss_chunk_rows",
)

@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """The ``ZAYA`` keys of ``Architecture`` (docs/CONFIG.md), named as the
    published ``config.json`` names them."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    experts_held: Tuple[int, ...]
    moe_intermediate_size: int
    router_hidden_size: int
    vocab_size: int
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5.0e6
    rms_norm_eps: float = 1.0e-5
    loss_chunk_rows: int = 4096

    @staticmethod
    def from_arch(arch: Dict) -> "ZayaConfig":
        missing = [k for k in ARCH_KEYS if k not in arch or arch[k] is None]
        if missing:
            raise ValueError(f"mpnn_type ZAYA needs Architecture keys {missing}")
        z = ZayaConfig(
            num_attention_heads=int(arch["num_attention_heads"]),
            num_key_value_heads=int(arch["num_key_value_heads"]),
            head_dim=int(arch["head_dim"]),
            num_experts=int(arch["num_experts"]),
            experts_held=tuple(int(e) for e in arch["experts_held"]),
            moe_intermediate_size=int(arch["moe_intermediate_size"]),
            router_hidden_size=int(arch["router_hidden_size"]),
            vocab_size=int(arch["vocab_size"]),
            cca_time0=int(arch["cca_time0"]),
            cca_time1=int(arch["cca_time1"]),
            partial_rotary_factor=float(arch["partial_rotary_factor"]),
            rope_theta=float(arch["rope_theta"]),
            rms_norm_eps=float(arch["rms_norm_eps"]),
            loss_chunk_rows=int(arch["loss_chunk_rows"]),
        )
        if z.num_attention_heads % z.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if z.num_key_value_heads * z.head_dim % 2 or z.head_dim % 2:
            raise ValueError("head_dim and the key-value latent must be even")
        held = z.experts_held
        if not held or sorted(set(held)) != list(held) or held[0] < 0 or held[-1] >= z.num_experts:
            raise ValueError(
                f"experts_held {list(held)} must be ascending, distinct ids below num_experts {z.num_experts}")
        rot = int(z.head_dim * z.partial_rotary_factor)
        if rot % 2:
            raise ValueError("head_dim * partial_rotary_factor must be even")
        return z

    @property
    def latent_q(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def latent_k(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads


def shift_in_graph(a, pos, j: int):
    """``a[t - j]`` where node ``t - j`` is in ``t``'s graph, else zero."""
    if j == 0:
        return a
    keep = (pos >= j).reshape((-1,) + (1,) * (a.ndim - 1))
    return jnp.where(keep, jnp.roll(a, j, axis=0), jnp.zeros((), a.dtype))


def cca_sublayer(p: Dict, u, aux, z: ZayaConfig, max_nodes: int):
    """The CCA sublayer on the normalised stream ``u [T, D]`` -> ``[T, D]``
    (before the residual add). ``p`` holds the layer's ``cca_*`` leaves."""
    pos, t, dt = aux["pos"], u.shape[0], u.dtype
    hq, hk, d = z.num_attention_heads, z.num_key_value_heads, z.head_dim
    lq, lk, grp = z.latent_q, z.latent_k, z.group
    q_lat, k_lat = _dense(u, p["cca_q"]), _dense(u, p["cca_k"])
    v = jnp.concatenate(
        [_dense(u, p["cca_v1"]), shift_in_graph(_dense(u, p["cca_v2"]), pos, 1)], axis=-1
    ).reshape(t, hk, d)
    with tr.scope(tr.HG_CCA_CONV):
        c = lq + lk
        zc = jnp.concatenate([q_lat, k_lat], axis=-1)
        w1, w2 = p["cca_conv1"].astype(dt), p["cca_conv2"].astype(dt)
        c1 = p["cca_conv1_bias"].astype(dt) + sum(
            w1[j] * shift_in_graph(zc, pos, j) for j in range(z.cca_time0))
        c1h = c1.reshape(t, hq + hk, d)
        c2 = p["cca_conv2_bias"].astype(dt) + sum(
            jnp.einsum("thc,hcd->thd", shift_in_graph(c1h, pos, j), w2[j])
            for j in range(z.cca_time1)).reshape(t, c)
        qh, kh = q_lat.reshape(t, hk, grp, d), k_lat.reshape(t, hk, d)
        q = c2[:, :lq].reshape(t, hk, grp, d) + 0.5 * (qh + kh[:, :, None, :])
        k = c2[:, lq:].reshape(t, hk, d) + 0.5 * (jnp.mean(qh, axis=2) + kh)

        def unit(a):  # sqrt(d) a / |a| per head, in float32
            af = a.astype(jnp.float32)
            return af * jax.lax.rsqrt(jnp.sum(af * af, axis=-1, keepdims=True) + 1e-12) * (d ** 0.5)

        tau = p["cca_temperature"].astype(jnp.float32)
        q = unit(q).reshape(t, hq, d).astype(dt)
        k = (unit(k) * tau[None, :, None]).astype(dt)
        rot = int(d * z.partial_rotary_factor)
        q, k = rope(q, pos, rot, z.rope_theta), rope(k, pos, rot, z.rope_theta)
    o = causal_attention(q, k, v, aux, max_nodes).reshape(t, lq)
    return _dense(o, p["cca_o"])


def route(p: Dict, beta, u, s_prev, z: ZayaConfig, first: bool):
    """The router, in float32: -> (choice [T] over ALL experts, gate [T],
    state [T, R] handed to the next layer)."""
    f32 = lambda name: p[name].astype(jnp.float32)
    hp = lambda a, w: jnp.dot(a, w, precision="highest")
    s = hp(u.astype(jnp.float32), f32("router_down")) + f32("router_down_bias")
    if not first:
        s = s + f32("router_eda") * s_prev
    hdn = rms_norm(s, f32("router_norm"), z.rms_norm_eps)
    hdn = jax.nn.gelu(hp(hdn, f32("router_fc1")) + f32("router_fc1_bias"), approximate=False)
    hdn = jax.nn.gelu(hp(hdn, f32("router_fc2")) + f32("router_fc2_bias"), approximate=False)
    probs = jax.nn.softmax(hp(hdn, f32("router_out")), axis=-1)
    # the balancing bias is a buffer: it moves the choice, takes no gradient
    choice = jnp.argmax(probs + jax.lax.stop_gradient(beta.astype(jnp.float32)), axis=-1)
    return choice, pick(probs, choice[:, None])[:, 0], s


def expert_sublayer(p: Dict, beta, u, s_prev, node_mask, z: ZayaConfig, first: bool,
                    choice=None):
    """The expert sublayer on the normalised stream ``u [T, D]``: route over
    all experts, compute the tokens whose expert is in ``z.experts_held``
    (``p["experts_*"]`` hold those, in that order), nothing for the others.
    -> (y [T, D] before the residual add, router state, the held experts'
    loads [held], every expert's load [num_experts]). ``choice`` overrides the
    router's (tests)."""
    from ..ops.pallas_grouped_matmul import aligned_layout, normalize_tiles, permute_rows

    t, d_model = u.shape
    with tr.scope(tr.HG_ROUTER):
        routed, gate, s = route(p, beta, u, s_prev, z, first)
        choice = routed if choice is None else choice
        held = len(z.experts_held)
        slot = jnp.where(node_mask, held_slot(choice, z.experts_held), held)
        kernel = jax.default_backend() == "tpu"
        # each expert's rows start at a multiple of the kernel's row tile
        block_m = normalize_tiles(t, d_model, z.moe_intermediate_size, dtype=u.dtype)[0]
        layout = aligned_layout(slot, held, block_m)
    with tr.scope(tr.HG_MOE):
        rows = permute_rows(u, layout["src"], layout["dest"])
        out_rows = expert_products(rows, p["experts_gate"], p["experts_up"], p["experts_down"],
                                   layout, block_m, kernel)
        y = permute_rows(out_rows, layout["dest"], layout["src"]) * gate[:, None].astype(u.dtype)
    every = expert_loads(choice[:, None], node_mask, z.num_experts)
    return y, s, layout["counts"], every


def layer_param_shapes(hidden: int, z: ZayaConfig, first: bool) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init kind) of one layer's parameter leaves (the kinds:
    ``models/decoder.py INIT``; ``small`` for the two projections that write
    into the residual stream)."""
    d, lq, lk = hidden, z.latent_q, z.latent_k
    r, e, f, held = z.router_hidden_size, z.num_experts, z.moe_intermediate_size, len(z.experts_held)
    c, heads = lq + lk, z.num_attention_heads + z.num_key_value_heads
    shapes = {
        "attn_norm": ((d,), "ones"), "moe_norm": ((d,), "ones"),
        "cca_q": ((d, lq), "lecun"), "cca_k": ((d, lk), "lecun"),
        "cca_v1": ((d, lk // 2), "lecun"), "cca_v2": ((d, lk // 2), "lecun"),
        "cca_conv1": ((z.cca_time0, c), "lecun"), "cca_conv1_bias": ((c,), "zeros"),
        "cca_conv2": ((z.cca_time1, heads, z.head_dim, z.head_dim), "lecun"), "cca_conv2_bias": ((c,), "zeros"),
        "cca_temperature": ((z.num_key_value_heads,), "ones"), "cca_o": ((lq, d), "small"),
        "router_down": ((d, r), "lecun"), "router_down_bias": ((r,), "zeros"), "router_norm": ((r,), "ones"),
        "router_fc1": ((r, r), "lecun"), "router_fc1_bias": ((r,), "zeros"),
        "router_fc2": ((r, r), "lecun"), "router_fc2_bias": ((r,), "zeros"), "router_out": ((r, e), "lecun"),
        "experts_gate": ((held, d, f), "lecun"), "experts_up": ((held, d, f), "lecun"),
        "experts_down": ((held, f, d), "small"),
    }
    for sub in ("attn", "moe"):
        shapes.update({f"{sub}_res_scale": ((d,), "ones"), f"{sub}_res_bias": ((d,), "zeros"),
                       f"{sub}_out_scale": ((d,), "ones"), f"{sub}_out_bias": ((d,), "zeros")})
    if not first:
        shapes["router_eda"] = ((r,), "ones")
    return shapes


def residual_add(p: Dict, sub: str, x, y):
    """x <- (a_r x + b_r) + (a_y y + b_y), four learned vectors a sublayer."""
    v = lambda name: p[f"{sub}_{name}"].astype(x.dtype)
    return (v("res_scale") * x + v("res_bias")) + (v("out_scale") * y.astype(x.dtype) + v("out_bias"))


class ZayaLayer(nn.Module):
    """One hybrid layer: CCA sublayer, then the expert sublayer."""

    hidden: int
    z: ZayaConfig
    first: bool
    max_nodes: int

    @nn.compact
    def __call__(self, x, s_prev, aux, beta):
        z = self.z
        p = layer_params(self, layer_param_shapes(self.hidden, z, self.first))
        u = rms_norm(x, p["attn_norm"], z.rms_norm_eps)
        x = residual_add(p, "attn", x, cca_sublayer(p, u, aux, z, self.max_nodes))
        u = rms_norm(x, p["moe_norm"], z.rms_norm_eps)
        y, s, counts, every = expert_sublayer(p, beta, u, s_prev, aux["node_mask"], z, self.first)
        counts = counts.astype(jnp.float32)
        return (residual_add(p, "moe", x, y), s,
                jnp.stack([jnp.sum(counts), jnp.max(counts), jnp.mean(counts)]), every)


class ZayaModel(nn.Module):
    """Embedding, the layers, the final norm. ``__call__`` returns the final
    normalised hidden state ``[N, hidden]`` under the head's name, and the
    step's routing counters under ``tr.COUNTER_PREFIX`` names."""

    cfg: "ModelConfig"  # noqa: F821 - models/base.py

    @staticmethod
    def float32_leaves(name: str) -> bool:
        """The router's leaves: ``train/loop.py mp_keep`` asks, and the
        mixed-precision cast leaves them float32."""
        return name.startswith("router_")

    @nn.compact
    def __call__(self, batch: GraphBatch, train: bool = False):
        cfg, z = self.cfg, self.cfg.zaya
        d_model = cfg.hidden_dim
        if batch.z is None:
            raise ValueError("mpnn_type ZAYA reads node ids from batch.z (int32)")
        # stored as the head reads it, [hidden, vocabulary]: the lookup takes
        # rows of the transpose
        x, _ = embed_tokens(self.param("embedding", _INIT["lecun"], (d_model, z.vocab_size)),
                            batch.z, z.vocab_size)
        aux = batch_aux(batch)
        layer_cls = remat_in_training(ZayaLayer, train)
        s = jnp.zeros((x.shape[0], z.router_hidden_size), jnp.float32)
        counters = jnp.zeros((3,), jnp.float32)
        for i in range(cfg.num_conv_layers):
            # the balancing bias: a buffer (no gradient, no optimizer state),
            # moved once a training step by the loads it produced
            beta = self.variable("batch_stats", f"router_bias_{i}",
                                 lambda: jnp.zeros((z.num_experts,), jnp.float32))
            x, s, c, loads = layer_cls(d_model, z, i == 0, cfg.max_nodes_per_graph,
                                       name=f"layers_{i}")(x, s, aux, beta.value)
            if train and not self.is_initializing():
                beta.value = balanced_bias(beta.value, loads)
            counters = counters + c
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones, (d_model,)), z.rms_norm_eps)
        real = jnp.sum(batch.node_mask.astype(jnp.float32))
        x = poison(x, graphs_overflow(batch, cfg.max_nodes_per_graph))
        return {
            cfg.output_names[0]: x,
            tr.CT_TOKENS: real * cfg.num_conv_layers,
            tr.CT_TOKENS_ROUTED_HERE: counters[0],
            tr.CT_EXPERT_LOAD_MAX: counters[1],
            tr.CT_EXPERT_LOAD_MEAN: counters[2],
            tr.CT_CAUSAL_PAIRS: causal_pairs(batch),
            **flash_steps(batch, cfg.max_nodes_per_graph, z.head_dim, z.head_dim, x.dtype),
            **flash_blocks(cfg.num_conv_layers, train),
        }
