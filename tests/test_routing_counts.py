"""Routing's bookkeeping is built by counting: the group-aligned layout
(ops/pallas_grouped_matmul.py ``aligned_layout``), a token's gates
(models/decoder.py ``pick``), the experts' loads (``expert_loads``) and each
row's gate (``permute_rows`` through the layout's inverse map). Each is held
bit for bit, values and gradients, to the sort and scatter spelling it
replaces; the compiled expert sublayers hold no sort and no scatter of the
routing's assignments."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.models import decoder as dc
from hydragnn_tpu.models import zaya as zm
from hydragnn_tpu.ops import pallas_grouped_matmul as gm
from hydragnn_tpu.ops.remat import CAUSAL_FLASH_RESIDUAL_NAMES


def sort_layout(slot, groups, block_m, rows=0):
    """The layout as it was built before: a stable sort of the assignments by
    group, then scatters of each assignment's row and of each row's
    assignment. The oracle."""
    t = slot.shape[0]
    r = gm.aligned_rows(t, groups, block_m)
    if rows:
        r = min(r, gm._round_up(rows, block_m))
    slot = slot.astype(jnp.int32)
    counts = jnp.zeros((groups + 1,), jnp.int32).at[slot].add(1)[:groups]
    tiles = jnp.maximum((counts + block_m - 1) // block_m, 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * block_m
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    sorted_slot = slot[order]
    group_first = jnp.cumsum(counts) - counts
    held = sorted_slot < groups
    safe = jnp.minimum(sorted_slot, groups - 1)
    rank = jnp.arange(t, dtype=jnp.int32) - group_first[safe]
    dest_sorted = row_start[safe] + rank
    fits = dest_sorted < r
    overrun = jnp.sum((held & ~fits).astype(jnp.int32))
    dest_sorted = jnp.where(held & fits, dest_sorted, r)
    dest = jnp.zeros((t,), jnp.int32).at[order].set(dest_sorted)
    src = jnp.full((r,), t, jnp.int32).at[dest_sorted].set(order, mode="drop")
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(r // block_m, dtype=jnp.int32), side="right"),
        groups - 1).astype(jnp.int32)
    return {"dest": dest, "src": src, "tile_group": tile_group,
            "n_tiles": jnp.minimum(tile_end[-1], r // block_m).astype(jnp.int32),
            "counts": counts, "overrun": overrun}


def _assert_same(got, want):
    assert sorted(got) == sorted(want)
    for key in want:
        g, w = np.asarray(got[key]), np.asarray(want[key])
        assert g.dtype == w.dtype and g.shape == w.shape, (key, g.dtype, w.dtype, g.shape, w.shape)
        assert np.array_equal(g, w), (key, g, w)


def _slots(kind, groups, a, rng):
    if kind == "mixed":
        return rng.integers(0, groups + 1, size=a)
    if kind == "none_held":
        return np.full(a, groups)
    if kind == "all_held":
        return rng.integers(0, groups, size=a)
    if kind == "one_expert":
        return np.full(a, groups - 1)
    # skewed: some groups empty, one crowded
    return rng.choice(groups + 1, size=a, p=rng.dirichlet(np.full(groups + 1, 0.3)))


# (what the case holds, slot kind, groups, assignments, block_m, row budget)
LAYOUT_CASES = {
    "mixed": ("mixed", 5, 300, 32, 0),
    "mixed_long": ("mixed", 8, 4099, 64, 0),
    "budget_overrun": ("one_expert", 3, 200, 16, 48),
    "budget_overrun_skewed": ("skewed", 6, 999, 32, 256),
    "budget_tight_mixed": ("mixed", 4, 640, 32, 224),
    "empty_groups": ("skewed", 8, 150, 16, 0),
    "none_held": ("none_held", 4, 77, 16, 0),
    "none_held_budget": ("none_held", 4, 77, 16, 32),
    "all_held": ("all_held", 4, 513, 32, 0),
    "one_group": ("all_held", 1, 100, 16, 0),
    "one_group_overrun": ("all_held", 1, 100, 16, 64),
    "below_block_m": ("mixed", 3, 5, 16, 0),
    "single_assignment": ("all_held", 2, 1, 16, 0),
}


@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def pytest_counting_layout_equals_the_sort_layout_bit_for_bit(case):
    kind, groups, a, block_m, rows = LAYOUT_CASES[case]
    rng = np.random.default_rng(sorted(LAYOUT_CASES).index(case))
    slot = jnp.asarray(_slots(kind, groups, a, rng).astype(np.int32))
    want = sort_layout(slot, groups, block_m, rows)
    got = jax.jit(lambda s: gm.aligned_layout(s, groups, block_m, rows))(slot)
    _assert_same(got, want)
    if case.endswith("overrun"):
        assert int(got["overrun"]) > 0


@pytest.mark.parametrize("seed", range(6))
def pytest_counting_layout_equals_the_sort_layout_on_random_routings(seed):
    rng = np.random.default_rng(100 + seed)
    groups = int(rng.integers(1, 17))
    a = int(rng.integers(1, 3000))
    block_m = int(rng.choice([16, 32, 64, 128]))
    rows = int(rng.choice([0, block_m, a // 3 + block_m * groups]))
    slot = jnp.asarray(_slots(str(rng.choice(["mixed", "skewed", "all_held"])), groups, a, rng).astype(np.int32))
    _assert_same(gm.aligned_layout(slot, groups, block_m, rows), sort_layout(slot, groups, block_m, rows))


def _choice(t, k, experts, rng):
    return jnp.asarray(np.stack([rng.permutation(experts)[:k] for _ in range(t)]).astype(np.int32))


@pytest.mark.parametrize("rows_budget", [0, 64, 96])
def pytest_topk_layout_tokens_equal_the_sort_layouts(rows_budget):
    """``token`` (each row's token) and ``tokens_here`` follow from the layout
    as before."""
    t, k, experts, held, block_m = 40, 3, 12, (1, 4, 5, 9), 16
    rng = np.random.default_rng(rows_budget)
    choice = _choice(t, k, experts, rng)
    node_mask = jnp.asarray(np.arange(t) < t - 3)
    got = dc.topk_layout(choice, node_mask, held, experts, block_m, rows_budget)
    slot = jnp.where(node_mask[:, None], _held_table(held, experts)[choice], len(held))
    want = sort_layout(slot.reshape(-1), len(held), block_m, rows_budget)
    want["token"] = jnp.where(want["src"] < t * k, want["src"] // k, t)
    want["tokens_here"] = jnp.sum(jnp.any(slot < len(held), axis=1).astype(jnp.int32))
    _assert_same(got, want)


def _held_table(experts_held, num_experts):
    """expert id -> its place among the experts held, or ``len(experts_held)``:
    the lookup table the slots were read from before."""
    table = np.full((num_experts,), len(experts_held), np.int32)
    table[list(experts_held)] = np.arange(len(experts_held))
    return jnp.asarray(table)


@pytest.mark.parametrize("held", [(2, 5, 6), (0, 1, 2, 3), (7,)], ids=["scattered", "leading", "one"])
def pytest_held_slot_equals_the_table_lookup(held):
    choice = _choice(50, 4, 8, np.random.default_rng(len(held)))
    got = jax.jit(dc.held_slot, static_argnums=1)(choice, held)
    want = _held_table(held, 8)[choice]
    assert got.dtype == want.dtype and np.array_equal(np.asarray(got), np.asarray(want))


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("t,k,experts", [(64, 8, 128), (33, 4, 16), (50, 1, 16)],
                         ids=["top8_of_128", "top4_of_16", "top1_of_16"])
def pytest_gate_pick_equals_take_along_axis_in_values_and_gradients(t, k, experts):
    rng = np.random.default_rng(k)
    s = jnp.asarray(rng.normal(size=(t, experts)), jnp.float32)
    choice = _choice(t, k, experts, rng)
    probe = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
    take = lambda s_: jnp.take_along_axis(s_, choice, axis=-1)
    assert np.array_equal(_bits(jax.jit(dc.pick)(s, choice)), _bits(take(s)))
    grad = lambda f: jax.jit(jax.grad(lambda s_: jnp.sum(jax.nn.sigmoid(f(s_)) * probe)))(s)
    assert np.array_equal(_bits(grad(lambda s_: dc.pick(s_, choice))), _bits(grad(take)))


@pytest.mark.parametrize("k,experts", [(8, 256), (1, 16)], ids=["top8_of_256", "top1_of_16"])
def pytest_expert_loads_equal_the_scatter_add(k, experts):
    t = 300
    rng = np.random.default_rng(experts)
    choice = _choice(t, k, experts, rng)
    mask = jnp.asarray(rng.random(t) < 0.9)
    want = jnp.zeros((experts,), jnp.float32).at[choice.reshape(-1)].add(jnp.repeat(mask.astype(jnp.float32), k))
    assert np.array_equal(_bits(jax.jit(dc.expert_loads, static_argnums=2)(choice, mask, experts)), _bits(want))


@pytest.mark.parametrize("rows_budget", [0, 48])
def pytest_gate_row_through_the_inverse_map_equals_indexing(rows_budget):
    """A row's gate through ``permute_rows`` (its cotangent a gather through
    ``dest``) against ``concat(gate, 0)[src]`` (a scatter-add), in values and
    gradients."""
    t, k, experts, held, block_m = 30, 4, 10, (0, 3, 7), 16
    rng = np.random.default_rng(7 + rows_budget)
    choice = _choice(t, k, experts, rng)
    lay = dc.topk_layout(choice, jnp.ones((t,), bool), held, experts, block_m, rows_budget)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=lay["src"].shape), jnp.float32)
    mapped = lambda g: gm.permute_rows(g.reshape(-1, 1), lay["src"], lay["dest"])[:, 0]
    indexed = lambda g: jnp.concatenate([g.reshape(-1), jnp.zeros((1,), g.dtype)])[lay["src"]]
    assert np.array_equal(_bits(mapped(gate)), _bits(indexed(gate)))
    grad = lambda f: jax.jit(jax.grad(lambda g: jnp.sum(f(g) * probe)))(gate)
    assert np.array_equal(_bits(grad(mapped)), _bits(grad(indexed)))


# ---------------------------------------------------------------------------
# the expert sublayers, whole: bit-identical to the sort and scatter spelling,
# and with none of it in the compiled program
# ---------------------------------------------------------------------------

T, D = 96, 32
TOPK = dc.ExpertSpec(num_experts=16, top_k=4, experts_held=(0, 5, 6, 11), width=24, shared=1, scale=2.5,
                     row_capacity=1.5)
ZAYA = zm.ZayaConfig(num_attention_heads=2, num_key_value_heads=1, head_dim=8, num_experts=8,
                     experts_held=(1, 2, 3, 6), moe_intermediate_size=24, router_hidden_size=8, vocab_size=11)


def _params(shapes, rng):
    return {name: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2] if len(shape) > 1 else 1.0), jnp.float32)
            for name, (shape, _) in shapes.items()}


def _sublayer(stack):
    """(differentiable function of (params, u), its params, the rest) at a
    small shape; the rows of the top-k stack get a budget."""
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    mask = jnp.asarray(np.arange(T) < T - 5)
    if stack == "topk":
        p = _params(dc.expert_param_shapes(D, TOPK), rng)
        beta = jnp.asarray(rng.normal(size=(16,)) * 0.01, jnp.float32)

        def f(p_, u_):
            y, counts, every, stats = dc.expert_sublayer(p_, beta, u_, mask, TOPK)
            return y, (counts, every, stats)
    else:
        p = {k: v for k, v in _params(zm.layer_param_shapes(D, ZAYA, False), rng).items()
             if k.startswith(("router_", "experts_"))}
        s_prev = jnp.asarray(rng.normal(size=(T, 8)), jnp.float32)
        beta = jnp.zeros((8,), jnp.float32)

        def f(p_, u_):
            y, s, counts, every = zm.expert_sublayer(p_, beta, u_, s_prev, mask, ZAYA, False)
            return y + jnp.sum(s) * 0.0, (counts, every)
    return f, p, u


def _remat_loss(f):
    layer = jax.checkpoint(lambda p_, u_: f(p_, u_)[0],
                           policy=jax.checkpoint_policies.save_only_these_names(*CAUSAL_FLASH_RESIDUAL_NAMES))
    return lambda p_, u_: jnp.sum(layer(p_, u_) * jnp.cos(u_))


@pytest.mark.parametrize("stack", ["topk", "zaya"])
def pytest_expert_sublayer_is_bit_identical_to_the_sort_and_scatter_spelling(stack, monkeypatch):
    f, p, u = _sublayer(stack)
    loss = _remat_loss(f)
    new = jax.jit(f)(p, u), jax.jit(jax.grad(loss, (0, 1)))(p, u)
    monkeypatch.setattr(gm, "aligned_layout", sort_layout)
    monkeypatch.setattr(gm, "permute_rows", _indexing_permute)
    monkeypatch.setattr(dc, "pick", lambda s, c: jnp.take_along_axis(s, c, axis=-1))
    monkeypatch.setattr(zm, "pick", lambda s, c: jnp.take_along_axis(s, c, axis=-1))
    scatter_loads = lambda c, w, e: jnp.zeros((e,), jnp.float32).at[c.reshape(-1)].add(
        jnp.repeat(w.astype(jnp.float32), c.shape[1]))
    monkeypatch.setattr(dc, "expert_loads", scatter_loads)
    monkeypatch.setattr(zm, "expert_loads", scatter_loads)
    old = jax.jit(f)(p, u), jax.jit(jax.grad(loss, (0, 1)))(p, u)
    leaves_new, leaves_old = jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old)
    assert len(leaves_new) == len(leaves_old)
    for a, b in zip(leaves_new, leaves_old):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b) if a.dtype.kind in "iub" else np.array_equal(_bits(a), _bits(b))


def _indexing_permute(x, index, inverse):
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0)[index]


@pytest.mark.parametrize("stack", ["topk", "zaya"])
def pytest_compiled_expert_sublayer_holds_no_sort_and_no_scatter_of_the_assignments(stack):
    """Forward and gradient under the layer's remat, compiled: no sort at
    all, and the only scatters left are the row-wide float32 ``[T + 1, D]``
    sums of the top-k dispatch's backward and combine's forward."""
    f, p, u = _sublayer(stack)
    for fn in (jax.jit(f), jax.jit(jax.grad(_remat_loss(f), (0, 1)))):
        text = fn.lower(p, u).compile().as_text()
        assert not re.findall(r" sort\(", text)
        scatters = {re.sub(r"\{.*", "", s) for s in re.findall(r"= (\S+) scatter\(", text)}
        assert scatters <= {f"f32[{T + 1},{D}]"}, scatters
        if stack == "zaya":
            assert not scatters
