"""Tests for the ``repro.analysis`` static-analysis gate.

Covers the three passes (golden fixture findings for the linter, lattice
+ agreement proofs for the kernel checker, accept/reject behavior for
the plan verifier), the baseline contract, the CLI exit codes, and the
acceptance criterion: ``QueryRegistry.register`` rejects a hand-built
timing-violating decomposition with ``PlanInvariantError`` on both the
REF and PALLAS_INTERPRET backends, leaving the service untouched.
"""

import json
import os

import pytest

from repro.analysis import (
    ERROR, WARNING, PlanInvariantError, load_baseline, verify_plan)
from repro.analysis import kernel_check as KC
from repro.analysis.ast_lint import lint_tree
from repro.analysis.cli import main as cli_main
from repro.analysis.plan_check import check_plan, verify_corpus
from repro.core.decompose import TCSubquery
from repro.core.join import JoinBackend
from repro.core.plan import compile_plan
from repro.core.query import example_paper_query
from repro.runtime.service import ContinuousSearchService

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "analysis_fixtures")
SRC_REPRO = os.path.join(REPO_ROOT, "src", "repro")
BASELINE = os.path.join(REPO_ROOT, "analysis_baseline.json")


# --------------------------------------------------------------------- #
# ast_lint: golden fixture findings
# --------------------------------------------------------------------- #
def test_lint_fixture_golden_findings():
    findings, stats = lint_tree(FIXTURES)
    got = {(f.rule, f.symbol.rsplit(".", 1)[-1]) for f in findings}
    assert got == {
        ("TRC101", "bad_cast"),
        ("TRC102", "bad_numpy"),
        ("TRC103", "bad_sync"),
        ("TRC104", "bad_branch"),
        ("TRC105", "tick"),
        ("TRC106", "serve"),
        ("TRC107", "bad_obs_emit"),
    }
    sev = {f.rule: f.severity for f in findings}
    assert sev["TRC101"] == sev["TRC104"] == sev["TRC107"] == ERROR
    assert sev["TRC105"] == sev["TRC106"] == WARNING
    # the inline-suppressed cast and every ok_* pattern stay silent
    assert not any("suppressed" in f.symbol or "ok_" in f.symbol
                   or "host_helper" in f.symbol or "clean" in f.symbol
                   or "donating" in f.symbol for f in findings)
    assert stats["n_traced_functions"] >= 6
    # the census sees both the traced (bad) and host (ok) emission sites
    assert stats["n_obs_sites"] >= 3


def test_lint_obs_sites_census_and_clean_tree():
    """The real tree: every repro.obs emission site is host-side (zero
    TRC107 findings), and the census proves the linter actually sees
    the instrumented serve loop (service/session/ingest/benches)."""
    findings, stats = lint_tree(SRC_REPRO)
    assert not [f for f in findings if f.rule == "TRC107"]
    assert stats["n_obs_sites"] >= 10


def test_lint_recognizes_aliased_shard_map_roots(tmp_path):
    """An aliased import ``shard_map as _shard_map``: functions handed
    to the alias must still become traced roots (TRC-checked) and be
    counted in the ``n_shard_map_roots`` census."""
    (tmp_path / "m.py").write_text(
        "from jax import shard_map as _shard_map\n"
        "def serve(mesh):\n"
        "    def body(x):\n"
        "        return int(x) + 1\n"
        "    return _shard_map(body, mesh=mesh, in_specs=(None,),\n"
        "                      out_specs=None)\n")
    findings, stats = lint_tree(str(tmp_path))
    assert stats["n_shard_map_roots"] == 1
    assert any(f.rule == "TRC101" and "body" in f.symbol
               for f in findings)


def test_mesh_tick_builder_is_trc_covered():
    """The mesh subsystem's shard_map-wrapped tick builder is inside the
    linted tree's traced-root census — the TRC rules see it."""
    _, stats = lint_tree(SRC_REPRO)
    assert stats["n_shard_map_roots"] >= 1


def test_lint_tree_clean_at_error_severity():
    """Satellite contract: the real tree has zero error findings and
    every warning is covered by the shipped baseline."""
    findings, _ = lint_tree(SRC_REPRO)
    assert [f.format() for f in findings if f.severity == ERROR] == []
    baseline = load_baseline(BASELINE)
    not_covered = [f.format() for f in findings
                   if f.severity == WARNING and not baseline.suppresses(f)]
    assert not_covered == []


# --------------------------------------------------------------------- #
# kernel_check
# --------------------------------------------------------------------- #
def test_kernel_contracts_prove_clean():
    findings, stats = KC.check_kernels(fast=True)
    assert [f.format() for f in findings] == []
    assert stats["n_pallas_sites"] == 4


def test_bounds_checker_catches_non_divisible_blockspec():
    # 96 rows tiled at 64: the second block covers [64, 128) > 96
    bad = KC._bounds_ok((2,), [("x", (96,), (64,), lambda i: (i,))])
    assert bad and bad[0][0] == "x"
    # and a correct tiling proves clean
    assert KC._bounds_ok((2,), [("x", (128,), (64,), lambda i: (i,))]) == []


@pytest.mark.parametrize("array_shape,block_shape", [
    ((2048,), (256,)),          # 1-D int32 (tile,) block: layout refused
    ((64, 2048), (1, 256)),     # per-slot (1, tile) block of a [S, C] array
])
def test_block_rule_rejects_shapes_the_compiler_refused(array_shape,
                                                        block_shape):
    """KC102 encodes the TPU compiler's block rule: the shapes the v5e
    compiler refused for the compat_join kernels are findings, and the
    packed layout the kernels use now proves clean."""
    f = KC.block_shape_finding("x", array_shape, block_shape)
    assert f is not None and f.rule == "KC102" and f.severity == ERROR
    assert KC.block_shape_finding("x", (2048,), (2048,)) is None
    assert KC.block_shape_finding("x", (64, 6, 16384), (None, 6, 256)) is None
    assert KC.block_shape_finding("x", (64, 16384, 1024),
                                  (None, 256, 256), sublane=32) is None


def test_unmodeled_pallas_call_flagged(tmp_path):
    kdir = tmp_path / "kernels" / "newk"
    kdir.mkdir(parents=True)
    (kdir / "kernel.py").write_text(
        "from jax.experimental import pallas as pl\n"
        "def mystery_kernel(x):\n"
        "    return pl.pallas_call(lambda i, o: None, grid=(1,))(x)\n")
    findings, stats = KC.check_kernels(
        kernels_root=str(tmp_path / "kernels"), fast=True)
    assert stats["n_pallas_sites"] == 1
    assert any(f.rule == "KC100" and f.severity == WARNING
               and f.symbol == "mystery_kernel" for f in findings)


def test_smem_cursor_proof_requires_the_clamp(monkeypatch):
    """The KC104 proof is conditional on the emit clamp being present in
    the kernel source; if the clamp expression disappears, the pass must
    fail loudly instead of vacuously passing."""
    monkeypatch.setattr(KC, "_CLAMP_EXPR", "jnp.some_other_clamp(")
    findings = KC.check_smem_cursor(fast=True)
    assert any(f.rule == "KC104" and f.severity == ERROR for f in findings)


# --------------------------------------------------------------------- #
# plan_check + registry wiring (acceptance criterion)
# --------------------------------------------------------------------- #
def _timing_violating_plan(caps):
    """A hand-built decomposition whose first 'timing sequence' pairs
    two adjacent edges that ≺ does NOT order (violates Definition 10)."""
    q = example_paper_query()
    bad = next((x, y) for x in range(q.n_edges) for y in range(q.n_edges)
               if x != y and q.edges_adjacent(x, y)
               and not q.precedes(x, y))
    rest = [e for e in range(q.n_edges) if e not in bad]
    dec = [TCSubquery(frozenset(bad), tuple(bad))] + \
        [TCSubquery(frozenset({e}), (e,)) for e in rest]
    return q, compile_plan(q, 25, decomposition=dec, **caps)


@pytest.mark.parametrize(
    "backend", [JoinBackend.REF, JoinBackend.PALLAS_INTERPRET])
def test_register_rejects_timing_violating_plan(backend):
    caps = dict(level_capacity=256, l0_capacity=256, max_new=64)
    q, plan = _timing_violating_plan(caps)
    svc = ContinuousSearchService(slots_per_group=2, backend=backend,
                                  **caps)
    with pytest.raises(PlanInvariantError) as exc:
        svc.register(q, 25, plan=plan)
    assert any(f.rule == "PC102" for f in exc.value.findings)
    # fail-fast BEFORE any state mutation: nothing half-registered
    assert len(svc.registry) == 0
    assert svc.registry.next_qid == 0


def test_adopt_rejects_corrupted_manifest_decomposition():
    from repro.core.registry import QueryRegistry
    q = example_paper_query()
    reg = QueryRegistry()
    bad = next((x, y) for x in range(q.n_edges) for y in range(q.n_edges)
               if x != y and q.edges_adjacent(x, y)
               and not q.precedes(x, y))
    rest = [(e,) for e in range(q.n_edges) if e not in bad]
    with pytest.raises(PlanInvariantError):
        reg.adopt(7, q, 25, decomposition=[tuple(bad)] + rest)
    assert 7 not in reg


def test_verify_plan_accepts_planner_output_and_custom_singletons():
    from repro.core.query import QueryGraph
    q = example_paper_query()
    verify_plan(compile_plan(q, 25))
    # the all-singletons custom decomposition used by the restore tests
    tri = QueryGraph(3, (0, 1, 2), ((0, 1), (1, 2), (2, 0)), (0, 0, 0),
                     frozenset({(0, 1), (1, 2), (0, 2)}))
    custom = [TCSubquery(frozenset({e}), (e,)) for e in range(3)]
    verify_plan(compile_plan(tri, 25, decomposition=custom))


def test_check_plan_flags_each_broken_invariant():
    q = example_paper_query()
    plan = compile_plan(q, 25)
    # PC101: drop an edge from the cover
    import copy
    p = copy.deepcopy(plan)
    p.subqueries = p.subqueries[1:]
    assert any(f.rule == "PC101" for f in check_plan(p))
    # PC107: corrupt a label table
    p = copy.deepcopy(plan)
    p.edge_src_label = p.edge_src_label + 1
    assert any(f.rule == "PC107" for f in check_plan(p))
    # PC108: non-positive window
    p = copy.deepcopy(plan)
    p.window = 0
    assert any(f.rule == "PC108" for f in check_plan(p))
    # PC106: orphan edge_site entry
    p = copy.deepcopy(plan)
    p.edge_site[99] = (0, 0)
    assert any(f.rule == "PC106" for f in check_plan(p))


def test_corpus_sweep_is_error_free():
    findings, stats = verify_corpus()
    assert stats["n_plans_verified"] >= 10
    assert [f.format() for f in findings if f.severity == ERROR] == []


# --------------------------------------------------------------------- #
# baseline contract
# --------------------------------------------------------------------- #
def test_shipped_baseline_loads_and_has_no_error_entries():
    baseline = load_baseline(BASELINE)
    assert baseline.entries          # the known warnings are listed
    # load_baseline would have raised on error-severity suppressions;
    # double-check the raw file anyway
    doc = json.load(open(BASELINE))
    assert all(e.get("severity") != ERROR for e in doc["suppressions"])


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"pass": "lint", "rule": "TRC105", "path": "x.py", "symbol": "f",
         "justification": "   "}]}))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(p))


def test_baseline_rejects_error_severity_suppression(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"pass": "lint", "rule": "TRC101", "path": "x.py", "symbol": "f",
         "severity": "error", "justification": "because"}]}))
    with pytest.raises(ValueError, match="errors must be fixed"):
        load_baseline(str(p))


def test_missing_baseline_is_empty(tmp_path):
    b = load_baseline(str(tmp_path / "nope.json"))
    assert b.entries == {}


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #
def test_cli_green_on_tree_and_writes_report(tmp_path, capsys):
    out = tmp_path / "report.json"
    rc = cli_main(["--fast", "--json", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["schema"] == "repro_analysis/v1"
    assert doc["findings_by_severity"]["error"] == 0
    assert doc["findings_by_severity"]["warning"] == 0
    assert doc["stats"]["n_pallas_sites"] == 4
    assert doc["stats"]["n_plans_verified"] >= 10
    assert len(doc["suppressed"]) >= 4
    assert "repro.analysis:" in capsys.readouterr().out


def test_cli_fails_on_error_findings(capsys):
    rc = cli_main(["--root", FIXTURES, "--pass", "lint"])
    assert rc == 1
    assert "TRC101" in capsys.readouterr().out


def test_cli_error_on_findings_promotes_warnings(tmp_path, capsys):
    # with an empty baseline the tree's warnings become failures under
    # --error-on-findings, but not without it
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"suppressions": []}))
    argv = ["--pass", "lint", "--baseline", str(empty)]
    assert cli_main(argv) == 0
    assert cli_main(argv + ["--error-on-findings"]) == 1
    capsys.readouterr()
