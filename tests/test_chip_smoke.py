"""The chip check's CPU side: ``chip_smoke.py`` must refuse a backend that is
not a TPU, and config completion must ask the backend that initialised — not
the environment — before switching the Pallas routes on."""

import importlib.util
import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import hydragnn_tpu.config.config as config_mod

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke


def pytest_chip_smoke_refuses_a_cpu_backend():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0, proc.stdout[-500:]
    assert "backend 'cpu'" in proc.stderr, proc.stderr[-500:]
    # no result line, and nothing was built before the refusal
    assert '"ok"' not in proc.stdout and proc.stdout.strip() == "", proc.stdout


def pytest_chip_smoke_result_line_holds_the_contract_keys_only():
    # the driver rejects a last line with any key beside these (PR 21 was
    # refused once for carrying the legs and timings there)
    smoke = _load_smoke()
    stamp = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = smoke.result_line({**stamp, "extra": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": stamp}
    src = inspect.getsource(smoke.main)
    assert src.rstrip().endswith(
        "print(result_line(device), flush=True)\n    return 0"), src[-200:]


def pytest_jit_target_follows_the_initialised_backend(monkeypatch):
    # the environment may say anything: JAX is on the CPU in this suite
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    is_tpu, source = config_mod._jit_target_inference()
    assert is_tpu is False and "'cpu'" in source, (is_tpu, source)
    # no platform-name branch and no libtpu probe: the function does not
    # read the environment at all
    src = inspect.getsource(config_mod._jit_target_inference)
    assert not any(t in src for t in ("os.environ", "getenv", "find_spec"))


def pytest_chip_smoke_cell_shape_case_rehearsed():
    """``chip_smoke.kernel_cases`` yields the cell-shape bf16 fused-edge case
    FIRST (forward by max norm, tangent by L2); at a tiny size in interpret
    mode it holds the tolerances it is held to on the chip."""
    smoke = _load_smoke()
    assert smoke.CELL_SHAPE == {"n_nodes": 12136, "edges": 196608,
                                "max_degree": 36, "mean_degree": 15.6}
    tiny = {"n_nodes": 90, "edges": 700, "max_degree": 9, "mean_degree": 5.0}
    name, dt, check = next(iter(smoke.kernel_cases(
        channels=(24,), n_nodes=70, max_degree=8, interpret=True,
        cell_shape=tiny)))
    assert name.startswith("fused_edge cell") and dt == "bfloat16", name
    (fwd, fwd_err), (tan, tan_err, tan_tol) = check()
    assert fwd.startswith("forward") and fwd_err <= smoke.TOL[dt], fwd_err
    assert tan.startswith("tangent L2") and tan_tol == smoke.TOL_TANGENT_L2
    assert tan_err <= tan_tol, tan_err
    ids = smoke._cell_ids(np.random.default_rng(0), **smoke.CELL_SHAPE)
    assert ids.shape == (196608,) and (np.diff(ids) >= 0).all()
    assert np.bincount(ids[ids < 12135]).max() <= 36


def pytest_chip_smoke_gather_transpose_case_rehearsed(monkeypatch):
    """The cell-shape case of the receiver gather's transpose comes SECOND
    (after the fused-edge call whose tangent rule uses it); at a tiny size,
    with the route forced on in interpret mode, the kernel's VJP holds the
    bf16 tolerance with the dummy node's row compared, and the scatter-add
    it replaces is read beside it."""
    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    smoke = _load_smoke()
    tiny = {"n_nodes": 90, "edges": 700, "max_degree": 9, "mean_degree": 5.0}
    cases = iter(smoke.kernel_cases(
        channels=(24,), n_nodes=70, max_degree=8, interpret=True,
        cell_shape=tiny))
    next(cases)
    name, dt, check = next(cases)
    assert name.startswith("gather_transpose cell") and dt == "bfloat16", name
    (vjp, vjp_err), (scatter, scatter_err, scatter_tol) = check()
    assert vjp.startswith("vjp ") and vjp.endswith(" ms"), vjp
    assert vjp_err <= smoke.TOL[dt], vjp_err
    assert "scatter-add" in scatter and scatter_err <= scatter_tol == 1.0
    # with the route off the case refuses to time a plain gather as the kernel
    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "0")
    with pytest.raises(AssertionError, match="kernel route"):
        check()


@pytest.mark.parametrize("route", ["0", "1"])
def pytest_chip_smoke_row_gather_case_rehearsed(monkeypatch, route):
    """The cell-shape case of a layer's pair of row gathers comes THIRD; at
    a tiny size, on either route, the ordered spelling's rows equal the plain
    one's to the bit and both wall times are printed."""
    import re

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", route)
    smoke = _load_smoke()
    tiny = {"n_nodes": 90, "edges": 700, "max_degree": 9, "mean_degree": 5.0}
    cases = iter(smoke.kernel_cases(
        channels=(24,), n_nodes=70, max_degree=8, interpret=True,
        cell_shape=tiny))
    next(cases), next(cases)
    name, dt, check = next(cases)
    assert name.startswith("row_gather cell") and dt == "bfloat16", name
    ((label, differing, tol),) = check()
    assert re.match(r"ordered \d+\.\d\d ms, plain \d+\.\d\d ms, ", label), label
    assert differing == 0.0 and tol == 0.0


def pytest_chip_smoke_force_gradient_gap_rehearsed(monkeypatch):
    """The second-order leg's comparison at a tiny width, the kernel routes
    forced on in interpret mode (on the chip config completion turns them
    on): the energy-force gradient through the transposed gathers equals
    the plain gather's, and the plain side keeps only the fused rule's
    closing linear calls."""
    import hydragnn_tpu.config as config_pkg

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    complete = config_pkg.update_config

    def routed(config, *datasets):
        config["NeuralNetwork"]["Architecture"].update(
            use_sorted_aggregation=True, use_fused_edge_kernel=True)
        return complete(config, *datasets)

    monkeypatch.setattr(config_pkg, "update_config", routed)
    smoke = _load_smoke()
    assert smoke.egnn_force_gradient_gap(hidden=16) <= 5e-3



@pytest.mark.parametrize("leg,shapes,tags", [
    # the ZAYA cell's shapes: one head width, one slot a token
    ("decoder_kernel_leg", dict(tokens=512, heads=4, kv_heads=2, head_dim=32, longest=160, groups=4, width=64),
     ("flash_causal float32 fwd_ms", "grouped_expert float32 groups=4 64->64 fwd+bwd_ms")),
    # the JOYAI cell's: 192-wide queries and keys beside 128-wide values, top-k rows within a budget
    ("joyai_kernel_leg", dict(tokens=512, heads=2, kv_heads=2, longest=160, groups=4, width=64, width_out=48,
                              experts=32, topk=4),
     ("flash_causal float32 2x192/128 fwd+bwd_ms", "grouped_expert float32 groups=4 64->48 top-4 fwd+bwd_ms",
      "routing top-4 of 32 held=4 layout_ms", "routing top-4 of 32 held=4 router fwd+bwd_ms")),
    # the Trinity cell's: the sliding launches beside the full ones on the same grouped-query operands
    ("trinity_kernel_leg", dict(tokens=512, heads=4, kv_heads=2, head_dim=32, longest=300, window=40, groups=4,
                                width=64, width_out=48, experts=32, topk=4),
     ("flash_causal float32 fwd+bwd_ms", "flash_window(40) float32 fwd+bwd_ms",
      "grouped_expert float32 groups=4 64->48 top-4 fwd+bwd_ms", "routing top-4 of 32 held=4 router fwd_ms")),
])
def pytest_chip_smoke_decoder_kernel_legs_rehearsed(leg, shapes, tags):
    """Both decoder kernel legs at a tiny size in interpret mode: every check
    a leg makes on the chip (forward, dq / dk / dv, dx / dw against blocked
    jnp references) holds its float32 tolerance, and the JOYAI leg's defaults
    are the cell's shapes."""
    import inspect

    smoke = _load_smoke()
    got = getattr(smoke, leg)(interpret=True, dtypes=("float32",), block=128, **shapes)
    for tag in tags:
        assert tag in got["launch_ms"], (tag, list(got["launch_ms"]))
    src = inspect.getsource(smoke.joyai_kernel_leg)
    for shape in ("tokens=16384", "heads=32", "head_dim=192", "value_dim=128", "groups=16", "width_out=768",
                  "topk=8", "experts=256"):
        assert shape in src, shape
    src = inspect.getsource(smoke.trinity_kernel_leg)
    for shape in ("tokens=16384", "heads=32", "kv_heads=4", "head_dim=128", "window=2048", "groups=8",
                  "width_out=1024", "topk=8", "experts=128"):
        assert shape in src, shape


def pytest_chip_smoke_dsa_times_rehearsed():
    """The sparse attention's leg at a tiny size in interpret mode: the
    selection holds ``min(n_t, topk)`` keys a row, the three launches run and
    are timed under their names; its defaults are the Keye-VL-2.0 cell's
    shapes."""
    import inspect

    smoke = _load_smoke()
    got = smoke.dsa_times(tokens=1024, heads=4, kv_heads=2, head_dim=32, index_heads=4, index_dim=16, topk=64,
                          sizes=(600, 200, 100), interpret=True, dtype="float32")
    for name in ("hg_dsa_indexer_ms", "hg_dsa_indexer_bwd_ms", "hg_flash_sparse fwd_ms", "hg_flash_sparse fwd+bwd_ms"):
        assert f"dsa 1024 tokens top-64 {name}" in got["launch_ms"], name
    src = inspect.getsource(smoke.dsa_times)
    for shape in ("tokens=32768", "heads=32", "kv_heads=4", "head_dim=128", "index_heads=16", "index_dim=64",
                  "topk=2048"):
        assert shape in src, shape



def pytest_chip_smoke_head_times_rehearsed():
    """The token head's leg at a tiny size: both rules run, agree and are
    timed, one pass and two; its defaults are the ZAYA and JOYAI cells'
    shapes."""
    import inspect

    smoke = _load_smoke()
    got = smoke.head_times(cells=(("a", 100, 97, 1), ("b", 64, 50, 2)), width=32, chunk=16, dtype="float32")
    for tag in ("head a [100, 32] x [32, 97] chunk 16 x 1", "head b [64, 32] x [32, 50] chunk 16 x 2"):
        for rule in ("checkpointed", "grad_in_forward"):
            assert f"{tag} {rule}_ms" in got["head_ms"] and f"{tag} {rule}_temp_bytes" in got["head_ms"]
    src = inspect.getsource(smoke.head_times)
    for shape in ('"zaya", 32768, 32784, 1', '"joyai", 16384, 16160, 2', "width=2048", "chunk=4096"):
        assert shape in src, shape
