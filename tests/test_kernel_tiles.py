"""A kernel's tile sizes are constants of its module (``ops/pallas_*.py``):
the defaults of the public entry point, clamped by that module's
``normalize_tiles`` before they become ``custom_jvp`` / ``custom_vjp``
non-differentiable arguments.

(a) at the shapes the benchmark's cells and ``chip_smoke.py`` launch them
    with, the entry points given no tiles run the tiles (and the grid) the
    ledger's rates were measured with, written here as numbers;
(b) a request past the clamp and the clamped request are one program and one
    jit cache entry;
(c) no ``Training.autotune*`` key is left in the configuration.

Launches are traced at the real size from shapes alone (no array is made, and
nothing is compiled or run); (b) runs tiny shapes in interpret mode.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops import pallas_flash_attention as pfa
from hydragnn_tpu.ops import pallas_fused_edge as pfe
from hydragnn_tpu.ops import pallas_grouped_matmul as pgm
from hydragnn_tpu.ops import pallas_multi_agg as pma
from hydragnn_tpu.ops import pallas_segment as ps

# the EGNN-866 cells' packed batch: edges, nodes, in-degree bound
E, N, DEG = 196608, 12136, 36
# the ZAYA cell: node slots, query / key-value heads of 128, longest document;
# experts held, model width = expert width
T, HQ, HK, D, LONGEST, HELD, WIDTH = 32768, 8, 2, 128, 8192, 8, 2048
# chip_smoke.py's long-graph flash case: 1100 nodes and 9 of padding, 8 x 32
GPS_N, GPS_NMAX, GPS_H, GPS_D = 1109, 1100, 8, 32

ROW_TILES = ("block_rows", "block_edges", "block_cols")
QK_TILES = ("block_q", "block_k")
MNK_TILES = ("block_m", "block_n", "block_k")


def shaped(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def sorted_segment(c):
    def launch(dtype):
        ops = (shaped((E, c), dtype), shaped((E,), "int32"))
        return (ROW_TILES, lambda *t: ps.normalize_tiles(c, *t),
                lambda **t: jax.make_jaxpr(
                    lambda m, i: ps.sorted_segment_sum(m, i, N, DEG, **t))(*ops))
    return launch


def fused_edge(c):
    def launch(dtype):
        ops = (shaped((N, c), dtype), shaped((E, c), dtype), shaped((c, c), dtype),
               shaped((c,), dtype), shaped((E,), "int32"))
        return (ROW_TILES, lambda *t: pfe.normalize_tiles(c, c, dtype, *t),
                lambda **t: jax.make_jaxpr(
                    lambda *o: pfe.fused_edge_message_sum(*o, N, DEG, **t))(*ops))
    return launch


def multi_agg(c, recv, gate):
    def launch(dtype):
        edge = shaped((E, c), dtype)
        ops = (shaped((N, c), dtype) if recv else None, edge,
               edge if gate else None, shaped((E,), "int32"))
        return (ROW_TILES, lambda *t: pma.normalize_tiles(c, dtype, recv, gate, *t),
                lambda **t: jax.make_jaxpr(
                    lambda *o: pma.fused_multi_agg(*o, N, DEG, **t))(*ops))
    return launch


def flash_self(dtype):
    qkv = shaped((GPS_N, GPS_H, GPS_D), dtype)
    ops = (qkv, qkv, qkv, shaped((GPS_N,), "int32"), shaped((GPS_N,), "bool"))
    return (QK_TILES, pfa.normalize_tiles,
            lambda **t: jax.make_jaxpr(
                lambda *o: pfa.flash_self_attention(*o, 2, GPS_NMAX, **t))(*ops))


def flash_causal(dtype):
    kv = shaped((T, HK, D), dtype)
    ops = (shaped((T, HQ, D), dtype), kv, kv, shaped((T,), "int32"), shaped((T,), "bool"))
    return (QK_TILES,
            lambda *t: pfa.normalize_tiles(*(t or (pfa.CAUSAL_BLOCK_Q, pfa.CAUSAL_BLOCK_K))),
            lambda **t: jax.make_jaxpr(
                lambda *o: pfa.flash_causal_attention(*o, LONGEST, **t))(*ops))


def grouped(dtype):
    # models/zaya.py expert_sublayer: the row tile from the tokens, then the
    # group-aligned buffer's rows
    block_m = pgm.normalize_tiles(T, WIDTH, WIDTH, dtype=dtype)[0]
    rows = pgm.aligned_rows(T, HELD, block_m)
    ops = (shaped((rows, WIDTH), dtype), shaped((HELD, WIDTH, WIDTH), dtype),
           shaped((rows // block_m,), "int32"), shaped((), "int32"))
    return (MNK_TILES, lambda *t: pgm.normalize_tiles(rows, WIDTH, WIDTH, *t, dtype=dtype),
            lambda **t: jax.make_jaxpr(lambda *o: pgm.grouped_matmul(*o, **t))(*ops))


# (launch, dtype, the tiles it runs given none, the grid of its first launch):
# what ``tune.runtime.tile_plan`` returned at the parent of PR 31, with no
# table, for these shapes, and the grids of the steps the ledger's lines up to
# PR 30 were measured with
CASES = [
    ("sorted_segment_sum c=866", sorted_segment(866), "bfloat16", (128, 512, 512), (2, 95, 10)),
    ("sorted_segment_sum c=866", sorted_segment(866), "float32", (128, 512, 512), (2, 95, 10)),
    ("sorted_segment_sum c=3", sorted_segment(3), "bfloat16", (128, 512, 128), (1, 95, 10)),
    ("sorted_segment_sum c=3", sorted_segment(3), "float32", (128, 512, 128), (1, 95, 10)),
    ("fused_edge_message_sum c=866", fused_edge(866), "bfloat16", (128, 512, 896), (1, 95, 10)),
    ("fused_edge_message_sum c=866", fused_edge(866), "float32", (128, 128, 896), (1, 95, 37)),
    ("fused_multi_agg c=866 node_recv", multi_agg(866, True, False), "bfloat16", (128, 512, 128), (7, 95, 10)),
    ("fused_multi_agg c=866 node_recv", multi_agg(866, True, False), "float32", (128, 512, 128), (7, 95, 10)),
    ("fused_multi_agg c=256 node_recv gate", multi_agg(256, True, True), "bfloat16", (128, 512, 128), (2, 95, 10)),
    ("fused_multi_agg c=256 node_recv gate", multi_agg(256, True, True), "float32", (128, 512, 128), (2, 95, 10)),
    ("fused_multi_agg c=866 alone", multi_agg(866, False, False), "bfloat16", (128, 512, 128), (7, 95, 10)),
    ("flash_self_attention", flash_self, "bfloat16", (128, 128), (8, 9, 9)),
    ("flash_self_attention", flash_self, "float32", (128, 128), (8, 9, 9)),
    # since PR 34 the window's loop runs inside the kernel: no third grid axis
    # (it was 18), a key/value head's arrays resident as one block each
    ("flash_causal_attention", flash_causal, "bfloat16", (512, 512), (8, 64)),
    ("flash_causal_attention", flash_causal, "float32", (512, 512), (8, 64)),
    ("grouped_matmul", grouped, "bfloat16", (512, 1024, 512), (72, 2, 4)),
    ("grouped_matmul", grouped, "float32", (512, 512, 512), (72, 4, 4)),
]


def first_grid(closed):
    """The grid of the first ``pallas_call`` of a traced launch."""
    found = re.search(r"GridMapping\(grid=\(([\d, ]+)\)", str(closed))
    assert found, "the launch traced no pallas_call"
    return tuple(int(g) for g in found.group(1).split(",") if g.strip())


def first_blocks(closed):
    """The block shapes of the first ``pallas_call``'s operands and outputs."""
    mappings = str(closed).split("GridMapping(", 1)[1].split("input_output_aliases", 1)[0]
    return [tuple(int(b) for b in re.findall(r"block_size=(\d+)", m))
            for m in mappings.split("BlockMapping(")[1:]]


@pytest.mark.parametrize("name,launch,dtype,tiles,grid", CASES,
                         ids=[f"{c[0]} {c[2]}".replace(" ", "-") for c in CASES])
def pytest_entry_point_given_no_tiles_runs_the_measured_ones(name, launch, dtype, tiles, grid):
    names, clamp, trace = launch(dtype)
    assert clamp() == tiles
    # the numbers above are what runs, not a request that is clamped to it
    assert clamp(*tiles) == tiles
    given_none = trace()
    assert str(given_none) == str(trace(**dict(zip(names, tiles))))
    assert first_grid(given_none) == grid
    if name == "flash_causal_attention":
        # graph-id column, the key ids as [k_blocks, 1, block_k], the query
        # block, the head's keys and values whole, the output and lse blocks
        held = (1, 512, 128)
        assert first_blocks(given_none) == [
            (512, 1), (T // 512, 1, 512), held, (1, T, D), (1, T, D), held, held]


# ---------------------------------------------------------------------------
# (b) the clamp comes before the non-differentiable arguments
# ---------------------------------------------------------------------------

def _ids(e=64, n=16):
    return jnp.asarray(np.minimum(np.arange(e) // 4, n - 1).astype(np.int32))


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), jnp.float32)


def _attention_ops(n=48, hq=2, hk=2, d=16):
    return (_rand((n, hq, d)), _rand((n, hk, d), 1), _rand((n, hk, d), 2),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool))


def _grouped_ops():
    slot = jnp.asarray(np.arange(40) % 3, jnp.int32)
    lay = pgm.aligned_layout(slot, 3, 16)
    rows = pgm.aligned_rows(40, 3, 16)
    return _rand((rows, 48)), _rand((3, 48, 40), 1), lay["tile_group"], lay["n_tiles"]


# (module, its custom_jvp / custom_vjp function, that function's static
# arguments, a call of the public entry point, a request past the clamp, the
# clamped request)
CLAMPED = {
    "pallas_segment": (
        ps, "_sorted_segment_sum", (2, 3, 4, 5, 6, 7),
        lambda **t: ps.sorted_segment_sum(_rand((64, 16)), _ids(), 16, 8, interpret=True, **t),
        dict(block_rows=16, block_edges=32, block_cols=512),
        dict(block_rows=16, block_edges=32, block_cols=128)),
    "pallas_fused_edge": (
        pfe, "_fused_edge_message_sum", (5, 6, 7, 8, 9, 10),
        lambda **t: pfe.fused_edge_message_sum(
            _rand((16, 8)), _rand((64, 8), 1), _rand((8, 24), 2), _rand((24,), 3), _ids(), 16, 8,
            interpret=True, **t),
        dict(block_rows=16, block_edges=32, block_cols=512),
        dict(block_rows=16, block_edges=32, block_cols=128)),
    "pallas_multi_agg": (
        pma, "_fused_multi_agg", (4, 5, 6, 7, 8, 9),
        lambda **t: pma.fused_multi_agg(
            _rand((16, 8)), _rand((64, 8), 1), None, _ids(), 16, 8, interpret=True, **t),
        dict(block_rows=16, block_edges=32, block_cols=256),
        dict(block_rows=16, block_edges=32, block_cols=128)),
    "pallas_flash_attention self": (
        pfa, "_flash_self_attention", (5, 6, 7, 8, 9),
        lambda **t: pfa.flash_self_attention(*_attention_ops(), 1, 48, interpret=True, **t),
        dict(block_q=40, block_k=200), dict(block_q=32, block_k=128)),
    "pallas_flash_attention block_summary": (
        pfa, "_flash_block_summary", (4, 5, 6),
        lambda **t: pfa.flash_block_summary(
            *_attention_ops()[:3], jnp.ones((48,), bool), interpret=True, **t),
        dict(block_q=40, block_k=200), dict(block_q=32, block_k=128)),
    "pallas_flash_attention causal": (
        pfa, "_flash_causal_attention", (5, 6, 7, 8),
        lambda **t: pfa.flash_causal_attention(*_attention_ops(hq=4), 48, interpret=True, **t),
        dict(block_q=40, block_k=200), dict(block_q=32, block_k=128)),
    "pallas_grouped_matmul": (
        pgm, "_grouped_matmul", (4, 5, 6, 7),
        lambda **t: pgm.grouped_matmul(*_grouped_ops(), block_m=16, interpret=True, **t),
        dict(block_n=1024, block_k=512), dict(block_n=128, block_k=128)),
}


@pytest.mark.parametrize("kernel", list(CLAMPED), ids=[k.replace(" ", "-") for k in CLAMPED])
def pytest_request_past_the_clamp_is_the_clamped_program(monkeypatch, kernel):
    module, inner, static, call, past, clamped = CLAMPED[kernel]
    assert str(jax.make_jaxpr(lambda: call(**past))()) == str(jax.make_jaxpr(lambda: call(**clamped))())
    # the function that carries the tiles as non-differentiable arguments,
    # under a jit that holds them static: both requests are one entry
    jitted = jax.jit(getattr(module, inner), static_argnums=static)
    monkeypatch.setattr(module, inner, jitted)
    a, b = call(**past), call(**clamped)
    assert jitted._cache_size() == 1
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def pytest_other_tiles_change_the_schedule_not_the_sums():
    msg, ids = _rand((64, 24), 7), _ids()
    assert ps.normalize_tiles(24, 64, 256, 256) != ps.normalize_tiles(24)
    default = ps.sorted_segment_sum(msg, ids, 16, 8, interpret=True)
    other = ps.sorted_segment_sum(msg, ids, 16, 8, 64, 256, 256, interpret=True)
    assert np.array_equal(np.asarray(default), np.asarray(other))


# ---------------------------------------------------------------------------
# (c) the configuration has no autotune key
# ---------------------------------------------------------------------------

def _completion_config(**training):
    from hydragnn_tpu.data import (
        VariablesOfInterest,
        deterministic_graph_dataset,
        extract_variables,
        split_dataset,
    )

    voi = VariablesOfInterest([0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1])
    ready = [extract_variables(g, voi) for g in deterministic_graph_dataset(8, seed=97)]
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "hidden_dim": 8, "num_conv_layers": 2,
                "output_heads": {"graph": {
                    "num_sharedlayers": 1, "dim_sharedlayers": 8,
                    "num_headlayers": 2, "dim_headlayers": [8, 8]}},
                "task_weights": [1.0],
            },
            "Training": {"num_epoch": 1, "batch_size": 4,
                         "Optimizer": {"learning_rate": 0.01}, **training},
            "Variables_of_interest": {
                "input_node_features": [0], "output_names": ["sum_x_x2_x3"],
                "output_index": [0], "type": ["graph"]},
        },
        "Dataset": {"node_features": {"dim": [1, 1, 1]}, "graph_features": {"dim": [1]}},
    }
    return (config, *split_dataset(ready, 0.7, seed=0))


@pytest.mark.parametrize("key,old_value", [
    ("autotune", "sweep"), ("autotune_budget", -1), ("autotune_cache_dir", "/nonexistent/table")])
def pytest_autotune_keys_are_unknown_keys(key, old_value):
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.config.lint import lint_config

    training = update_config(*_completion_config())["NeuralNetwork"]["Training"]
    assert key not in training
    # a saved configuration that still carries the key: read as any unknown
    # key is (kept, never validated), and named by the lint
    carried = {"autotune": "sweep", key: old_value}
    done = update_config(*_completion_config(**carried))
    assert done["NeuralNetwork"]["Training"][key] == old_value
    status = {f.path: f.status for f in lint_config(done)}
    assert status[f"NeuralNetwork.Training.{key}"] == "unknown"
