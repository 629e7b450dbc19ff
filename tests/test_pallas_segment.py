"""Pallas sorted-segment-sum kernel vs the XLA scatter reference
(interpret mode on CPU; the kernel itself targets TPU — ops/pallas_segment.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.pallas_segment import sorted_segment_sum


def _sorted_capped_receivers(rng, e, n, max_degree):
    recv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    while np.unique(recv, return_counts=True)[1].max() > max_degree:
        recv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    return recv


@pytest.mark.parametrize(
    "e,n,c,max_degree",
    [(300, 50, 7, 16), (1000, 128, 64, 20), (37, 400, 3, 4), (512, 64, 130, 16)],
)
def pytest_matches_xla_segment_sum(e, n, c, max_degree):
    rng = np.random.default_rng(e + n)
    recv = _sorted_capped_receivers(rng, e, n, max_degree)
    msg = jnp.asarray(rng.normal(size=(e, c)).astype(np.float32))
    ref = jax.ops.segment_sum(msg, jnp.asarray(recv), num_segments=n)
    out = sorted_segment_sum(
        msg, jnp.asarray(recv), n, max_degree, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                rtol=2e-5, atol=2e-5)


def pytest_gradient_is_gather():
    rng = np.random.default_rng(3)
    recv = _sorted_capped_receivers(rng, 200, 40, 12)
    msg = jnp.asarray(rng.normal(size=(200, 5)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(40, 5)).astype(np.float32))

    def loss(m):
        return jnp.sum(
            w * sorted_segment_sum(m, jnp.asarray(recv), 40, 12, interpret=True)
        )

    g = jax.grad(loss)(msg)
    np.testing.assert_allclose(np.asarray(g), np.asarray(w)[recv], atol=1e-6)


def pytest_grad_of_grad_composes():
    """Force-style second order (the r5 custom_vjp raised
    NotImplementedError here — examples/md17 on the chip): energy built
    through the kernel, forces = -dE/dpos via an inner grad, outer grad
    of the force loss. The custom-JVP tangent rule is plain jnp, so this
    composes to any order; values must match the dense XLA route."""
    rng = np.random.default_rng(17)
    n, e = 24, 100
    recv = _sorted_capped_receivers(rng, e, n, 10)
    send = rng.integers(0, n, e).astype(np.int32)
    pos = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    proj = jnp.asarray(rng.normal(size=(3, 6)).astype(np.float32))

    def energy(pos, agg):
        msg = (pos[send] - pos[recv]) @ proj
        return jnp.sum(agg(msg) ** 2)

    def force_loss(pos, agg):
        f = -jax.grad(energy, argnums=0)(pos, agg)
        return jnp.sum(f ** 2) + energy(pos, agg)

    agg_p = lambda m: sorted_segment_sum(m, jnp.asarray(recv), n, 10,
                                         interpret=True)
    agg_d = lambda m: jax.ops.segment_sum(m, jnp.asarray(recv),
                                          num_segments=n)
    gp = jax.grad(force_loss)(pos, agg_p)
    gd = jax.grad(force_loss)(pos, agg_d)
    scale = max(float(jnp.abs(gd).max()), 1.0)
    np.testing.assert_allclose(np.asarray(gp) / scale,
                               np.asarray(gd) / scale, rtol=1e-5, atol=1e-5)


def pytest_empty_and_trailing_segments():
    """Segments with no edges (incl. a trailing run) come out zero."""
    recv = jnp.asarray(np.array([2, 2, 5], np.int32))
    msg = jnp.asarray(np.ones((3, 4), np.float32))
    out = np.asarray(
        sorted_segment_sum(msg, recv, 64, 8, interpret=True)
    )
    expect = np.zeros((64, 4), np.float32)
    expect[2] = 2.0
    expect[5] = 1.0
    np.testing.assert_allclose(out, expect)


def pytest_batching_sort_edges_gives_sorted_receivers():
    """sort_edges=True yields a globally sorted batched receivers array —
    the kernel's precondition, end to end through the real batching path."""
    from hydragnn_tpu.data import deterministic_graph_dataset
    from hydragnn_tpu.data.graph import SpecLadder, batch_graphs

    graphs = deterministic_graph_dataset(8, seed=4)
    spec = SpecLadder.for_dataset(graphs, 8).specs[-1]
    b = batch_graphs(graphs, spec, sort_edges=True)
    recv = np.asarray(b.receivers)
    assert np.all(np.diff(recv) >= 0)
    # aggregation is order-invariant: same segment sums as unsorted batching
    b0 = batch_graphs(graphs, spec)
    msg = np.asarray(b0.x)[np.asarray(b0.senders)]
    ref = jax.ops.segment_sum(jnp.asarray(msg), b0.receivers,
                               num_segments=spec.n_nodes)
    msg_s = np.asarray(b.x)[np.asarray(b.senders)]
    out = jax.ops.segment_sum(jnp.asarray(msg_s), b.receivers,
                               num_segments=spec.n_nodes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    # edge_attr permutes with the edges when present
    import dataclasses
    g = dataclasses.replace(
        graphs[0],
        edge_attr=np.arange(graphs[0].num_edges, dtype=np.float32)[:, None],
    )
    from hydragnn_tpu.data.graph import sort_edges_by_receiver
    gs = sort_edges_by_receiver(g)
    # per-edge identity preserved: attr still matches its (s, r) pair
    m0 = {(int(s), int(r)): float(a) for s, r, a in
          zip(g.senders, g.receivers, g.edge_attr[:, 0])}
    for s, r, a in zip(gs.senders, gs.receivers, gs.edge_attr[:, 0]):
        assert m0[(int(s), int(r))] == float(a)


def pytest_graphloader_sort_edges_plumbed():
    """GraphLoader(sort_edges=True) emits batches with globally sorted
    receivers — the end-to-end production path to the kernel."""
    from hydragnn_tpu.data import GraphLoader, deterministic_graph_dataset

    graphs = deterministic_graph_dataset(20, seed=6)
    for num_shards in (1, 4):
        loader = GraphLoader(graphs, 8, sort_edges=True, shuffle=False,
                             num_shards=num_shards)
        for b in loader:
            recv = np.asarray(b.receivers)
            if recv.ndim == 1:
                assert np.all(np.diff(recv) >= 0)
            else:
                for shard in recv:
                    assert np.all(np.diff(shard) >= 0)


def pytest_bf16_messages_stream_without_upcast():
    """bf16 messages keep their dtype through the kernel (mixed-precision
    path); accumulation is still f32 so results match the f32 reference to
    bf16 quantization tolerance."""
    rng = np.random.default_rng(11)
    recv = _sorted_capped_receivers(rng, 400, 64, 16)
    msg32 = rng.normal(size=(400, 32)).astype(np.float32)
    msg16 = jnp.asarray(msg32).astype(jnp.bfloat16)
    out = sorted_segment_sum(msg16, jnp.asarray(recv), 64, 16, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = jax.ops.segment_sum(
        jnp.asarray(msg16).astype(jnp.float32), jnp.asarray(recv),
        num_segments=64,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=2e-2, atol=2e-2
    )


# ---------------------------------------------------------------------------
# ops/segment.py gather(sorted_ids=True): a row gather over receiver-sorted
# ids whose TRANSPOSE is the kernel above (HYDRAGNN_PALLAS_SEGMENT=1 routes
# it off the TPU, in interpret mode)
# ---------------------------------------------------------------------------


def _padded_ids(kind, rng, n, max_degree):
    """Receiver-sorted ids of ``n`` rows, the last the dummy node:
    ``capped`` every row within the bound; ``empty_trailing`` edge-less rows
    inside and a trailing run of them; ``dummy`` a padded batch's layout,
    the last row holding several edge windows' worth of padding edges."""
    if kind == "empty_trailing":
        return np.array([2, 2, 5, 5, 5, 9], np.int32)
    deg = rng.integers(0, max_degree + 1, n - 1)
    deg[3] = 0
    ids = np.repeat(np.arange(n - 1), deg)
    if kind == "dummy":
        ids = np.concatenate([ids, np.full(1500, n - 1)])
    return ids.astype(np.int32)


def _gather_case(kind, dtype, seed=0, n=40, c=6, max_degree=8):
    rng = np.random.default_rng(seed)
    ids = _padded_ids(kind, rng, n, max_degree)
    x = jnp.asarray(rng.normal(size=(n, c)), dtype)
    ct = rng.normal(size=(ids.shape[0], c))
    # a padded batch's own rule: a padding edge's cotangent is exactly zero
    ct[ids == n - 1] = 0.0
    return jnp.asarray(ids), x, jnp.asarray(ct, dtype), max_degree


_GATHER_DTYPES = [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)]


@pytest.mark.parametrize("dtype,tol", _GATHER_DTYPES)
@pytest.mark.parametrize("kind", ["capped", "empty_trailing", "dummy"])
def pytest_sorted_gather_forward_and_vjp(monkeypatch, kind, dtype, tol):
    """Forward bit for bit ``x[ids]``; the VJP is the scatter-add's (float32
    accumulation against XLA's in the operand dtype, so bf16 to the kernel's
    tolerance), with edge-less, trailing and over-cap dummy-node rows."""
    from hydragnn_tpu.ops.segment import gather

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    ids, x, ct, deg = _gather_case(kind, dtype)
    out, vjp = jax.vjp(lambda v: gather(v, ids, True, deg), x)
    assert out.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(x[ids], np.float32))
    (got,) = vjp(ct)
    assert got.dtype == x.dtype and got.shape == x.shape
    ref = jax.ops.segment_sum(
        ct.astype(jnp.float32), ids, num_segments=x.shape[0])
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref), rtol=tol, atol=tol)
    # the dummy row too: any partial sum of zero cotangents is zero
    assert not np.asarray(got, np.float32)[-1].any()


def pytest_sorted_gather_nonzero_padding_cotangents_stay_in_the_dummy_row(
        monkeypatch):
    """The contract's edge: were padding edges to carry a cotangent, only
    the dummy node's row would be unspecified; every real row stays exact."""
    from hydragnn_tpu.ops.segment import gather

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    ids, x, _, deg = _gather_case("dummy", jnp.float32)
    ct = jnp.asarray(
        np.random.default_rng(1).normal(size=(ids.shape[0], x.shape[1])),
        jnp.float32)
    (got,) = jax.vjp(lambda v: gather(v, ids, True, deg), x)[1](ct)
    ref = jax.ops.segment_sum(ct, ids, num_segments=x.shape[0])
    np.testing.assert_allclose(
        np.asarray(got)[:-1], np.asarray(ref)[:-1], rtol=1e-6, atol=1e-6)


def _gather_loss(take, ids, w):
    """A loss with curvature through the gathered rows, masked the way a
    conv stack masks its padding edges."""
    real = (ids < ids.max())[:, None]

    def loss(x):
        return jnp.sum(jnp.where(real, jnp.sin(take(x)) * w, 0.0))

    return loss


@pytest.mark.parametrize(
    "transform",
    ["jvp", "grad_of_grad", "checkpoint_jit", "jit_grad"],
)
def pytest_sorted_gather_composes(monkeypatch, transform):
    """``jax.jvp`` (the fused-edge tangent rule's use), grad-of-grad
    (energy-force training), ``jax.checkpoint`` under ``jit`` (the kernels'
    remat) and a jitted grad: each equals the plain gather's."""
    from hydragnn_tpu.ops.segment import gather

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    ids, x, w, deg = _gather_case("dummy", jnp.float32, seed=2)
    t = jnp.asarray(np.random.default_rng(3).normal(size=x.shape), jnp.float32)
    routed = _gather_loss(lambda v: gather(v, ids, True, deg), ids, w)
    plain = _gather_loss(lambda v: v[ids], ids, w)

    def apply(loss):
        if transform == "jvp":
            return jax.jvp(loss, (x,), (t,))
        if transform == "grad_of_grad":
            return jax.grad(lambda v: jnp.sum(jax.grad(loss)(v) ** 2))(x)
        if transform == "checkpoint_jit":
            return jax.jit(jax.grad(jax.checkpoint(loss)))(x)
        return jax.jit(jax.grad(loss))(x)

    for got, ref in zip(jax.tree_util.tree_leaves(apply(routed)),
                        jax.tree_util.tree_leaves(apply(plain))):
        scale = max(float(jnp.abs(ref).max()), 1.0)
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(ref) / scale,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "case,route_env,sorted_ids,max_degree,ndim",
    [
        ("unsorted", "1", False, 8, 2),
        ("no_bound", "1", True, None, 2),
        ("zero_bound", "1", True, 0, 2),
        ("one_dimensional", "1", True, 8, 1),
        ("route_off", "0", True, 8, 2),
        ("cpu_default", None, True, 8, 2),
    ],
)
def pytest_sorted_gather_stays_a_bare_gather(
        monkeypatch, case, route_env, sorted_ids, max_degree, ndim):
    """Outside the predicate ``segment_sum`` routes on, the gather and its
    gradient are the jaxprs ``x[ids]`` gives: no linear call, no kernel."""
    import re

    from hydragnn_tpu.ops.segment import gather

    if route_env is None:
        monkeypatch.delenv("HYDRAGNN_PALLAS_SEGMENT", raising=False)
    else:
        monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", route_env)
    ids, x, _, _ = _gather_case("capped", jnp.float32)
    if ndim == 1:
        x = x[:, 0]
    strip = lambda fn: re.sub(
        r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(jax.value_and_grad(
            lambda v: jnp.sum(fn(v) ** 2)))(x)))
    routed = strip(lambda v: gather(v, ids, sorted_ids, max_degree))
    assert routed == strip(lambda v: v[ids]), case
    assert "linear_call" not in routed and "pallas_call" not in routed


def pytest_sorted_gather_transpose_is_the_named_kernel(monkeypatch):
    """With the route on, the gradient's jaxpr holds the gather as a linear
    call and its transpose as the sorted-segment kernel, and the lowered
    program names the transposed call's scope around the kernel's own."""
    from hydragnn_tpu.ops.segment import gather
    from hydragnn_tpu.utils import tracer as tr

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    ids, x, _, deg = _gather_case("capped", jnp.float32)
    grad = jax.grad(lambda v: jnp.sum(gather(v, ids, True, deg) ** 2))
    text = str(jax.make_jaxpr(grad)(x))
    assert text.count("= linear_call[") == 2 and "pallas_call" in text
    assert "scatter-add" not in text and "scatter_add" not in text
    lowered = jax.jit(grad).lower(x).as_text(debug_info=True)
    assert (f"{tr.HG_GATHER_TRANSPOSE}/{tr.HG_SORTED_SEGMENT}" in lowered
            ), "the transposed call's scope is missing from the program"


# ---------------------------------------------------------------------------
# the edge-sized row gathers of a step (scope ``hg_row_gather``): ``gather``
# forward and the sorted-segment tangent, whose transpose is ``dout[ids]``.
# A gather copies rows, so both are held bit for bit to ``x[ids]`` /
# ``jax.ops.segment_sum`` at the widths a step has: 3 (coordinates), 128
# (one lane block) and 866 (the EGNN-866 cells' features)
# ---------------------------------------------------------------------------


def _bits(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


def _row_gather_case(dtype, c):
    """``_gather_case``'s padded-batch layout (edge-less rows inside, a
    padding run to the dummy node with zero cotangents) at width ``c``."""
    return _gather_case("dummy", dtype, seed=c, n=24, c=c, max_degree=6)


_ROW_WIDTHS = [3, 128, 866]
_ROW_DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("c", _ROW_WIDTHS)
@pytest.mark.parametrize("dtype", _ROW_DTYPES)
@pytest.mark.parametrize("route", ["0", "1"])
def pytest_row_gather_equals_indexing_bit_for_bit(monkeypatch, route, dtype, c):
    """``gather`` on either route: the rows are ``x[ids]`` to the bit, its
    tangent too; off the Pallas route the gradient is the scatter-add's to
    the bit (on it, the kernel's: ``pytest_sorted_gather_forward_and_vjp``)."""
    from hydragnn_tpu.ops.segment import gather

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", route)
    ids, x, ct, deg = _row_gather_case(dtype, c)
    take = lambda v: gather(v, ids, True, deg)
    out, t_out = jax.jvp(take, (x,), (2 * x,))
    assert out.dtype == x.dtype and out.shape == (ids.shape[0], c)
    np.testing.assert_array_equal(_bits(out), _bits(x[ids]))
    np.testing.assert_array_equal(_bits(t_out), _bits((2 * x)[ids]))
    if route == "0":
        (got,) = jax.vjp(take, x)[1](ct)
        (ref,) = jax.vjp(lambda v: v[ids], x)[1](ct)
        np.testing.assert_array_equal(_bits(got), _bits(ref))


@pytest.mark.parametrize("c", _ROW_WIDTHS)
@pytest.mark.parametrize("dtype", _ROW_DTYPES)
def pytest_sorted_segment_tangent_equals_segment_sum_bit_for_bit(dtype, c):
    """The kernel's tangent rule is the plain sum and its transpose the
    plain ``dout[ids]`` gather, dummy row and edge-less rows included (the
    PRIMAL of the over-cap dummy row is the kernel's and unspecified)."""
    ids, x, ct, deg = _row_gather_case(dtype, c)
    n = x.shape[0]
    total = lambda m: sorted_segment_sum(m, ids, n, deg, interpret=True)
    _, t_out = jax.jvp(total, (ct,), (ct,))
    ref = jax.ops.segment_sum(ct, ids, num_segments=n)
    assert t_out.dtype == ct.dtype
    np.testing.assert_array_equal(_bits(t_out), _bits(ref))
    (got,) = jax.vjp(total, ct)[1](x)
    np.testing.assert_array_equal(_bits(got), _bits(x[ids]))


def pytest_row_gathers_carry_their_scope(monkeypatch):
    """Every spelling of an edge-sized feature gather names the scope, on
    both routes, and the sorted-segment tangent hands it to its transpose."""
    import re

    from hydragnn_tpu.ops.segment import gather
    from hydragnn_tpu.utils import tracer as tr

    ids, x, ct, deg = _row_gather_case(jnp.float32, 3)
    n = x.shape[0]
    for route in ("0", "1"):
        monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", route)
        text = jax.jit(lambda v: gather(v, ids, True, deg)).lower(x).as_text(
            debug_info=True)
        assert f"{tr.HG_ROW_GATHER}/gather" in text, route
    grad = jax.grad(lambda m: jnp.sum(
        sorted_segment_sum(m, ids, n, deg, interpret=True) ** 2))
    text = jax.jit(grad).lower(ct).as_text(debug_info=True)
    assert re.search(
        rf"transpose\(jvp\({tr.HG_SORTED_SEGMENT}{tr.TANGENT}\)\)/"
        rf"{tr.HG_ROW_GATHER}/gather", text
    ), "the transposed tangent sum's gather does not carry the scope"
