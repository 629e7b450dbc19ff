"""graftlint fixture tests: every checker proven to FIRE on a tiny
known-bad snippet (right finding kind + fix hint), the waiver grammar
proven to waive, and the real tree proven clean — tier-1, no JAX import
anywhere in the analysis path (docs/ANALYSIS.md).

The marquee regression here is the PR 3 weak_type incident: reintroducing
the int32 cast on the step counter into the REAL train/loop.py source
must re-trigger the trace_hazard checker (the review-time analog of
tests/test_compile_plane.py's runtime sentinel assertion).
"""

import json
import os
import textwrap

import pytest

from hydragnn_tpu import analysis
from hydragnn_tpu.analysis import Repo, run_checkers
from hydragnn_tpu.analysis.__main__ import main as cli_main

REAL_ROOT = analysis.default_root()


# ---------------------------------------------------------------------------
# fixture scaffolding: a miniature repo in tmp
# ---------------------------------------------------------------------------

def mini_repo(tmp_path, files):
    """Build a tiny repo tree ({relpath: source}) and return its Repo."""
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return Repo(str(tmp_path))


def findings_of(repo, checker_id, include_waived=True):
    out = [f for f in run_checkers(repo, only={checker_id}) if f.checker == checker_id]
    return out if include_waived else [f for f in out if not f.waived]


# a docs/CONFIG.md stub with one documented flag row (table grammar)
DOCS_STUB = """
    # config

    ## Environment flags (the `HYDRAGNN_*` channel)

    | Flag | Parse | Default | Read by | Meaning |
    |---|---|---|---|---|
    | `HYDRAGNN_DOCUMENTED` | string | — | m.py | a documented flag |
"""


# ---------------------------------------------------------------------------
# env_census
# ---------------------------------------------------------------------------

def pytest_env_census_direct_read_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/m.py": """
            import os
            v = os.getenv("HYDRAGNN_DOCUMENTED")
            w = os.environ.get("HYDRAGNN_DOCUMENTED")
            x = os.environ["HYDRAGNN_DOCUMENTED"]
        """,
        "docs/CONFIG.md": DOCS_STUB,
    })
    got = findings_of(repo, "env_census")
    assert len(got) == 3, got
    assert all("bypasses the shared parse boundary" in f.message for f in got)
    assert all("utils/envflags.py" in f.hint for f in got)
    assert {f.line for f in got} == {3, 4, 5}


def pytest_env_census_undocumented_flag_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/utils/envflags.py": "def env_int(n, d):\n    return d\n",
        "hydragnn_tpu/m.py": """
            from .utils import envflags
            v = envflags.env_int("HYDRAGNN_SECRET_KNOB", 4)
        """,
        "docs/CONFIG.md": DOCS_STUB,
    })
    got = findings_of(repo, "env_census")
    assert len(got) == 2, got  # undocumented read + stale documented row
    missing = [f for f in got if "HYDRAGNN_SECRET_KNOB" in f.message]
    assert missing and "no docs/CONFIG.md env-table row" in missing[0].message
    assert "--env-table" in missing[0].hint
    stale = [f for f in got if "HYDRAGNN_DOCUMENTED" in f.message]
    assert stale and "no code in the tree mentions" in stale[0].message


def pytest_env_census_clean_when_routed_and_documented(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/utils/envflags.py": "def env_str(n, d=None):\n    return d\n",
        "hydragnn_tpu/m.py": """
            from .utils import envflags
            v = envflags.env_str("HYDRAGNN_DOCUMENTED")
        """,
        "docs/CONFIG.md": DOCS_STUB,
    })
    assert findings_of(repo, "env_census") == []


def pytest_env_table_preserves_meaning_and_reports_parse(tmp_path):
    from hydragnn_tpu.analysis.env_census import render_env_table

    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/m.py": """
            from .utils import envflags
            v = envflags.env_str("HYDRAGNN_DOCUMENTED")
        """,
        "docs/CONFIG.md": DOCS_STUB,
    })
    table = render_env_table(repo)
    row = [l for l in table.splitlines() if "HYDRAGNN_DOCUMENTED" in l][0]
    assert "a documented flag" in row      # meaning preserved from docs
    assert "string" in row                 # parse type from the helper call
    assert "m.py" in row                   # owning module from the census


# ---------------------------------------------------------------------------
# config_keys
# ---------------------------------------------------------------------------

CONFIG_LINT_STUB = """
    _OPAQUE = {"Dataset.path"}
    _HANDLED = {
        "Dataset.name",
        "NeuralNetwork.Training.batch_size",
        "NeuralNetwork.Training.ghost_key",
    }
    _TOPLEVEL_SECTIONS = ("Verbosity", "Dataset", "NeuralNetwork")
    _LEGACY = {}
    _NOT_APPLICABLE = {}
"""

CONFIG_DOCS_STUB = """
    ## Dataset

    | Key | Meaning |
    |---|---|
    | `name` | dataset id |
    | `undeclared_key` | documented but unknown to lint |

    ## NeuralNetwork.Training

    | Key | Meaning |
    |---|---|
    | `batch_size` (default `32`) | loop basics |
"""


def pytest_config_keys_bidirectional_drift_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/config/lint.py": CONFIG_LINT_STUB,
        "docs/CONFIG.md": CONFIG_DOCS_STUB,
    })
    got = findings_of(repo, "config_keys")
    msgs = "\n".join(f.message for f in got)
    # handled-but-undocumented (ghost_key) AND documented-but-unknown
    assert "ghost_key" in msgs and "HANDLED by config lint but has no" in msgs
    assert "undeclared_key" in msgs and "unknown to config/lint.py" in msgs
    # the default `32` inside the parenthesized qualifier is NOT a key
    assert "32" not in msgs


def pytest_config_keys_undeclared_toplevel_section_read_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/config/lint.py": CONFIG_LINT_STUB,
        "hydragnn_tpu/m.py": 'def f(config):\n    return config.get("Mystery")\n',
    })
    got = findings_of(repo, "config_keys")
    assert len(got) == 1
    assert "'Mystery'" in got[0].message
    assert "_TOPLEVEL_SECTIONS" in got[0].message


# ---------------------------------------------------------------------------
# obs_contract
# ---------------------------------------------------------------------------

EVENTS_STUB = """
    from typing import Dict
    EV_A = "alpha"
    EV_B = "beta"
    EVENT_KINDS = (EV_A, EV_B)
    SEVERITIES = ("info", "warn", "error", "fatal")
    DEFAULT_SEVERITY: Dict[str, str] = {EV_A: "warn"}
"""


def pytest_obs_contract_unranked_kind_and_undeclared_emit_fire(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/obs/events.py": EVENTS_STUB,
        "hydragnn_tpu/m.py": """
            from .obs.events import emit
            emit("gamma", step=3)
        """,
    })
    got = findings_of(repo, "obs_contract")
    msgs = "\n".join(f.message for f in got)
    assert "EV_B has no DEFAULT_SEVERITY" in msgs
    assert "undeclared event kind 'gamma'" in msgs
    hints = "\n".join(f.hint for f in got)
    assert "obs/events.py" in hints


def pytest_obs_contract_undocumented_series_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/m.py": """
            def f(registry):
                registry.counter("hydragnn_phantom_total", "desc")
        """,
        "docs/OBSERVABILITY.md": "# obs\n\n`hydragnn_real_total` is documented.\n",
    })
    got = findings_of(repo, "obs_contract")
    assert len(got) == 1
    assert "hydragnn_phantom_total" in got[0].message
    assert "docs/OBSERVABILITY.md" in got[0].hint


def pytest_obs_contract_brace_expanded_docs_cover_series(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/m.py": """
            def f(registry):
                registry.gauge("hydragnn_fleet_min", "d")
                registry.counter("hydragnn_events_total", "d")
        """,
        "docs/OBSERVABILITY.md":
            "`hydragnn_fleet_{min,mean,max}` and `hydragnn_events_total{kind=...}`\n",
    })
    assert findings_of(repo, "obs_contract") == []


# ---------------------------------------------------------------------------
# trace_hazard — including the PR 3 weak_type regression
# ---------------------------------------------------------------------------

def pytest_trace_hazard_host_syncs_fire(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/train/loop.py": """
            import numpy as np
            def make_train_step(model, tx):
                def train_step(state, batch, rng):
                    loss = compute(state, batch).item()
                    arr = np.asarray(batch.x)
                    n = int(state.step)
                    return state, loss
                return train_step
        """,
    })
    got = findings_of(repo, "trace_hazard")
    msgs = "\n".join(f.message for f in got)
    assert ".item() inside step builder" in msgs
    assert "np.asarray" in msgs
    assert "int() on a TrainState counter" in msgs
    assert len(got) == 3


def pytest_trace_hazard_refires_on_reintroduced_pr3_weak_type_cast(tmp_path):
    """The acceptance drill: splice the PR 3 cast back into the REAL
    train/loop.py source and the checker must re-detect it."""
    real = open(os.path.join(REAL_ROOT, "hydragnn_tpu/train/loop.py")).read()
    assert "step=state.step + 1," in real  # the weakly-typed counter bump
    poisoned = real.replace(
        "step=state.step + 1,", "step=jnp.int32(state.step + 1),", 1
    )
    repo = mini_repo(tmp_path, {"hydragnn_tpu/train/loop.py": "PLACEHOLDER"})
    (tmp_path / "hydragnn_tpu/train/loop.py").write_text(poisoned)
    got = findings_of(repo, "trace_hazard")
    assert len(got) == 1, got
    assert "weak type" in got[0].message
    assert "PR 3" in got[0].message
    assert "docs/PERFORMANCE.md" in got[0].hint
    # and the unpoisoned real file is clean (the gate's steady state)
    repo2 = mini_repo(tmp_path / "clean", {"hydragnn_tpu/train/loop.py": "X"})
    (tmp_path / "clean/hydragnn_tpu/train/loop.py").write_text(real)
    assert findings_of(repo2, "trace_hazard") == []


def pytest_trace_hazard_astype_cast_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/parallel/dp.py": """
            def make_parallel_train_step(model):
                def step(state, batch, rng):
                    return state.replace(step=state.step.astype("int32"))
                return step
        """,
    })
    got = findings_of(repo, "trace_hazard")
    assert len(got) == 1 and "dtype cast on a TrainState counter" in got[0].message


# ---------------------------------------------------------------------------
# threads
# ---------------------------------------------------------------------------

def pytest_threads_fixture_fires_all_three_rules(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/m.py": """
            import threading
            def f(q, t):
                th = threading.Thread(target=f)
                th.join()
                item = q.get()
                return th, item
        """,
    })
    got = findings_of(repo, "threads")
    msgs = "\n".join(f.message for f in got)
    assert "without daemon=True" in msgs
    assert ".join() with no timeout" in msgs
    assert "bare queue .get()" in msgs
    assert len(got) == 3


def pytest_threads_waiver_with_reason_waives_and_without_reason_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/m.py": """
            def f(q, p):
                a = q.get()  # graftlint: disable=threads -- idle loop of a daemon worker
                b = p.get()  # graftlint: disable=threads
                return a, b
        """,
    })
    got = run_checkers(repo, only={"threads"})
    thread_findings = [f for f in got if f.checker == "threads"]
    assert [f.waived for f in sorted(thread_findings, key=lambda f: f.line)] == [True, False]
    waived = [f for f in thread_findings if f.waived][0]
    assert waived.waive_reason == "idle loop of a daemon worker"
    # the reasonless pragma is its own finding
    assert any(f.checker == "waiver" and "no reason" in f.message for f in got)


# ---------------------------------------------------------------------------
# atomic_write
# ---------------------------------------------------------------------------

def pytest_atomic_write_fires_on_in_place_write_and_passes_on_replace(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/train/checkpoint.py": """
            import os
            def bad_save(path, data):
                with open(path, "wb") as f:
                    f.write(data)
            def good_save(path, data):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(data)
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            def manifest_append(path, line):
                with open(path, "a") as f:
                    f.write(line)
        """,
    })
    got = findings_of(repo, "atomic_write")
    assert len(got) == 1, got
    assert "bad_save" in got[0].message and "torn file" in got[0].message
    assert "_fsync_replace" in got[0].hint


def pytest_atomic_write_module_level_write_fires(tmp_path):
    # a top-level in-place open is flagged even when some FUNCTION in the
    # module publishes atomically (the replace there does not excuse it)
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/data/lappe.py": """
            import os
            fh = open("cache_index.json", "w")
            def good(path, data):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
        """,
    })
    got = findings_of(repo, "atomic_write")
    assert len(got) == 1, got
    assert "module scope" in got[0].message and got[0].line == 3


def pytest_env_census_stale_row_not_kept_alive_by_linter_prose(tmp_path):
    # a flag named ONLY in the analysis plane's / envflags' own docstrings
    # is dead: the docs row for it must still be flagged stale
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/analysis/some_checker.py":
            '"""mentions HYDRAGNN_DOCUMENTED in prose."""\n',
        "hydragnn_tpu/utils/envflags.py":
            '"""catalogs HYDRAGNN_DOCUMENTED too."""\n\ndef env_str(n, d=None):\n    return d\n',
        "docs/CONFIG.md": DOCS_STUB,
    })
    got = findings_of(repo, "env_census")
    assert len(got) == 1, got
    assert "no code in the tree mentions" in got[0].message


def pytest_atomic_write_ignores_unscoped_modules(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/postprocess/plots.py":
            'def save(p, d):\n    with open(p, "w") as f:\n        f.write(d)\n',
    })
    assert findings_of(repo, "atomic_write") == []


# ---------------------------------------------------------------------------
# error_codes
# ---------------------------------------------------------------------------

def pytest_error_codes_duplicate_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/serve/errors.py": """
            class AError(RuntimeError):
                code = "shed"
            class BError(RuntimeError):
                code = "shed"
        """,
    })
    got = findings_of(repo, "error_codes")
    assert len(got) == 1
    assert "'shed' on BError is already claimed by AError" in got[0].message


# ---------------------------------------------------------------------------
# fault_coverage
# ---------------------------------------------------------------------------

FAULTINJECT_STUB = """
    def configure(**kwargs):
        keymap = {
            "covered": "HYDRAGNN_FAULT_COVERED",
            "orphan": "HYDRAGNN_FAULT_ORPHAN",
        }
        return keymap
"""


def pytest_fault_coverage_unarmed_point_fires(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/utils/faultinject.py": FAULTINJECT_STUB,
        "tests/test_x.py": 'ENV = {"HYDRAGNN_FAULT_COVERED": "1"}\n',
    })
    got = findings_of(repo, "fault_coverage")
    assert len(got) == 1
    assert "HYDRAGNN_FAULT_ORPHAN" in got[0].message
    assert "nothing drills it" in got[0].message
    assert "delete the point" in got[0].hint


def pytest_fault_coverage_configure_key_counts_as_evidence(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/utils/faultinject.py": FAULTINJECT_STUB,
        "tests/test_x.py":
            'fi.configure(covered="1")\nfi.configure(orphan="2")\n',
    })
    assert findings_of(repo, "fault_coverage") == []


# ---------------------------------------------------------------------------
# the gate: clean tree, red mutation, CLI/baseline plumbing
# ---------------------------------------------------------------------------

def pytest_real_tree_is_clean_with_empty_baseline():
    """The committed repo carries zero unwaived findings — the invariant
    ci.sh's baseline-free gate enforces. Every waiver carries a reason."""
    findings = analysis.analyze(REAL_ROOT)
    active = [f for f in findings if not f.waived]
    assert active == [], "\n".join(f.render() for f in active)
    for f in findings:
        assert f.waive_reason, f.render()


def pytest_cli_exit_codes_and_json_shape(tmp_path):
    rc = cli_main(["--json", "--root", REAL_ROOT])
    assert rc == 0
    # mutation smoke: an undocumented direct env read turns the gate red
    repo_files = {
        "hydragnn_tpu/m.py":
            'import os\nv = os.getenv("HYDRAGNN_UNDOCUMENTED_KNOB")\n',
        "docs/CONFIG.md": DOCS_STUB,
    }
    mini_repo(tmp_path, repo_files)
    assert cli_main(["--json", "--root", str(tmp_path)]) == 1
    assert cli_main(["--only", "no_such_checker", "--root", str(tmp_path)]) == 2


def pytest_baseline_roundtrip_is_local_only_suppression(tmp_path, capsys):
    mini_repo(tmp_path, {
        "hydragnn_tpu/m.py": 'import os\nv = os.getenv("HYDRAGNN_X_KNOB")\n',
    })
    base = tmp_path / "base.json"
    assert cli_main(["--write-baseline", str(base), "--root", str(tmp_path)]) == 0
    assert json.loads(base.read_text())  # non-empty keys recorded
    # with the baseline: green; without (the CI mode): red
    assert cli_main(["--baseline", str(base), "--root", str(tmp_path)]) == 0
    assert cli_main(["--root", str(tmp_path)]) == 1
    capsys.readouterr()


def pytest_checker_catalog_lists_all_nine():
    ids = {c.id for c in analysis.checkers()}
    assert ids == {
        "env_census", "config_keys", "obs_contract", "trace_hazard",
        "threads", "atomic_write", "error_codes", "fault_coverage",
        "sharding_rules",
    }
    for c in analysis.checkers():
        assert c.rationale, c.id  # every checker cites its incident


def pytest_sharding_rules_fires_outside_parallel_and_exempts_engine(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/models/m.py": """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            def place(x, mesh):
                y = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))
                return shard_map(lambda z: z, mesh=mesh)(y)
        """,
        "hydragnn_tpu/parallel/engine.py": """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            def place(x, mesh):
                return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))
        """,
    })
    got = findings_of(repo, "sharding_rules")
    assert len(got) == 3, got  # wsc + NamedSharding ctor + shard_map call
    assert all(f.path == "hydragnn_tpu/models/m.py" for f in got)
    assert all("outside parallel/" in f.message for f in got)
    assert any("parallel/rules.py" in f.hint for f in got)


def pytest_sharding_rules_waiver_with_reason_waives(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/models/m.py": """
            def attn(q, mesh):
                # graftlint: disable=sharding_rules -- collective lives with the attention math
                return shard_map(lambda z: z, mesh=mesh)(q)
        """,
    })
    got = findings_of(repo, "sharding_rules")
    assert len(got) == 1 and got[0].waived, got
    assert findings_of(repo, "sharding_rules", include_waived=False) == []


def pytest_doctor_static_findings_record_is_clean_and_bounded():
    from hydragnn_tpu.obs.doctor import static_findings_record

    rec = static_findings_record(REAL_ROOT)
    assert rec.get("error") is None, rec
    assert rec["clean"] is True
    assert rec["active"] == 0
    assert rec["v"] == analysis.ANALYSIS_SCHEMA_VERSION


def pytest_analysis_package_never_imports_jax():
    import sys

    loaded = [m for m in sys.modules if m.startswith("hydragnn_tpu.analysis")]
    assert loaded, "analysis must be loaded by this test module"
    # jax may have been imported by OTHER test modules in the same run;
    # assert the analysis modules themselves hold no jax reference
    for m in loaded:
        mod = sys.modules[m]
        assert not hasattr(mod, "jax"), m


def pytest_parse_failure_is_a_loud_finding(tmp_path):
    repo = mini_repo(tmp_path, {
        "hydragnn_tpu/broken.py": "def f(:\n    pass\n",
    })
    got = [f for f in run_checkers(repo) if f.checker == "parse"]
    assert len(got) == 1 and "does not parse" in got[0].message
