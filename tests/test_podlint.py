"""podlint self-tests: every rule fires on its known-bad fixture and
stays silent on the repaired form (including the historical PR 5 lock
pattern and a PR 2-style bf16 carry), the suppression / config /
exit-code contracts hold, and the repo tree itself scans clean.

Pure AST work — no jax import, no device."""
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))  # tools/ is not on the src PYTHONPATH

from tools.podlint import REGISTRY, lint_paths, lint_source
from tools.podlint.cli import main as podlint_main
from tools.podlint.config import Config, ConfigError, load_config

TESTDATA = REPO / "tools" / "podlint" / "testdata"
ALL_CODES = ("PL001", "PL002", "PL003", "PL004", "PL005", "PL006",
             "PL007", "PL008")


def _cfg(**kw):
    kw.setdefault("exclude", [])
    kw.setdefault("traced_functions", [])
    kw.setdefault("untraced_functions", [])
    kw.setdefault("rules", {})
    return Config(**kw)


def _lint_file(path, select=None, cfg=None):
    source = pathlib.Path(path).read_text()
    rel = str(pathlib.Path(path).relative_to(REPO))
    return lint_source(source, rel, cfg or _cfg(),
                       select=set(select) if select else None)


# ------------------------------------------------------------ rule catalog
def test_registry_has_the_eight_rules():
    assert set(REGISTRY) == set(ALL_CODES)
    for code, cls in REGISTRY.items():
        assert cls.code == code and cls.summary


@pytest.mark.parametrize("code", ALL_CODES)
def test_rule_fires_on_bad_fixture_and_not_on_repaired(code):
    n = code[-1]
    bad, _ = _lint_file(TESTDATA / f"pl00{n}_bad.py", select=[code])
    good, _ = _lint_file(TESTDATA / f"pl00{n}_good.py", select=[code])
    assert bad, f"{code} must fire on its known-bad fixture"
    assert all(f.code == code for f in bad)
    assert all(f.line > 0 and f.col > 0 for f in bad)
    assert not good, f"{code} fired on the repaired form: {good}"


def test_pl002_catches_the_pr5_router_lock_pattern():
    """The historical deadlock: a blocking buffer.put under the router
    lock (fixed in ingest.PodRouter.put by moving the enqueue out)."""
    findings, _ = _lint_file(TESTDATA / "pl002_bad.py", select=["PL002"])
    put_hits = [f for f in findings if "put(...)" in f.message]
    assert put_hits, "the blocking put under self._lock must be flagged"
    assert "self._lock" in put_hits[0].message


def test_pl001_catches_the_pr2_bf16_carry_shape():
    """An implicit-f32 scan carry next to a traced gains call — the
    PR 2 bug class (ThreeSieves.run_batched's carry crashed on bf16)."""
    findings, _ = _lint_file(TESTDATA / "pl001_bad.py", select=["PL001"])
    assert any("zeros" in f.message for f in findings)  # the carry
    assert any("full" in f.message for f in findings)  # the weights


def test_pl003_flags_direct_and_named_donation():
    findings, _ = _lint_file(TESTDATA / "pl003_bad.py", select=["PL003"])
    assert len(findings) == 2
    assert {"advance" in f.message or "jit" in f.message
            for f in findings} == {True}


def test_pl006_flags_both_counter_and_span_but_not_at_set():
    """Metric .inc AND span entry fire in the bad fixture; the jnp
    ``x.at[i].set(v)`` idiom must never trip the rule (the reason gauge
    ``set`` is excluded from the default record_methods)."""
    findings, _ = _lint_file(TESTDATA / "pl006_bad.py", select=["PL006"])
    assert any(".inc(...)" in f.message or "inc(...)" in f.message
               for f in findings)
    assert any("span" in f.message for f in findings)
    src = ("import jax\nimport jax.numpy as jnp\n"
           "def step(state, i, v):\n"
           "    return state.at[i].set(v)\n"
           "stepped = jax.jit(step)\n")
    quiet, _ = lint_source(src, "x.py", _cfg(), select={"PL006"})
    assert not quiet


def test_pl006_flags_stage_entry():
    findings, _ = _lint_file(TESTDATA / "pl006_bad.py", select=["PL006"])
    assert any("obs.stage(...)" in f.message for f in findings)


# --------------------------------------------- interprocedural (PL007/PL008)
def test_pl008_catches_the_pr5_pattern_cross_module():
    """The PR 5 deadlock split over two files: the router holds its
    lock and calls a helper whose *callee in the other module* blocks.
    PL002's lexical walk cannot see it; PL008 must — with a witness
    chain reaching into the buffer module."""
    pair = [str(TESTDATA.relative_to(REPO) / f)
            for f in ("pl008_xmod_router.py", "pl008_xmod_buffer.py")]
    r8 = lint_paths(pair, root=str(REPO), select=["PL008"])
    assert len(r8.findings) == 1
    f = r8.findings[0]
    assert f.path.endswith("pl008_xmod_router.py")
    assert "MiniBuffer.feed" in f.message  # resolved cross-module
    assert "pl008_xmod_buffer.py" in f.message  # chain cites the primitive
    r2 = lint_paths(pair, root=str(REPO), select=["PL002"])
    assert not r2.findings, "the lexical rule must NOT own this defect"


def test_pl008_closes_the_nested_def_blind_spot():
    """A blocking join inside a closure invoked under the lock: PL002
    skips nested defs by design; PL008 resolves the bare-name call."""
    bad, _ = _lint_file(TESTDATA / "pl008_nested_bad.py", select=["PL008"])
    good, _ = _lint_file(TESTDATA / "pl008_nested_good.py", select=["PL008"])
    assert len(bad) == 1 and "handoff" in bad[0].message
    assert not good
    lex, _ = _lint_file(TESTDATA / "pl008_nested_bad.py", select=["PL002"])
    assert not lex  # the blind spot, pinned


def test_pl008_flags_wait_with_extra_lock_held():
    bad, _ = _lint_file(TESTDATA / "pl008_bad.py", select=["PL008"])
    assert any("releases only its own lock" in f.message for f in bad)


def test_lock_graph_artifact_has_the_router_edge_and_no_cycles():
    """The acceptance gate: the repo's acquired-before graph contains
    the real PodRouter -> TaggedBuffer ordering and is cycle-free."""
    result = lint_paths(["src"], config_path=str(REPO / "podlint.toml"),
                        root=str(REPO), want_lock_graph=True)
    assert not result.errors
    g = result.lock_graph
    pairs = {(e["src"], e["dst"]) for e in g["edges"]}
    assert ("PodRouter._lock", "TaggedBuffer._lock") in pairs
    assert g["cycles"] == []
    assert "TaggedBuffer._lock" in g["locks"]
    assert "jaxbridge._install_lock" in g["locks"]
    dot = result.lock_graph_dot
    assert dot.startswith("digraph lockorder")
    assert '"PodRouter._lock" -> "TaggedBuffer._lock"' in dot


def test_traced_marks_propagate_across_modules(tmp_path):
    """A helper imported from another module and called from a jitted
    entry is traced there too — PL004 fires on its host sync."""
    (tmp_path / "entry.py").write_text(
        "import jax\n"
        "from helper import summarize\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return summarize(x)\n")
    (tmp_path / "helper.py").write_text(
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def summarize(x):\n"
        "    return np.asarray(x).sum()\n")
    r = lint_paths(["entry.py", "helper.py"], root=str(tmp_path),
                   select=["PL004"])
    assert len(r.findings) == 1
    assert r.findings[0].path == "helper.py"
    assert "np.asarray" in r.findings[0].message


def test_untraced_functions_glob_stops_propagation(tmp_path):
    cfg_file = tmp_path / "podlint.toml"
    cfg_file.write_text('[podlint]\nuntraced_functions = ["summarize"]\n')
    (tmp_path / "entry.py").write_text(
        "import jax\n"
        "from helper import summarize\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return summarize(x)\n")
    (tmp_path / "helper.py").write_text(
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def summarize(x):\n"
        "    return np.asarray(x).sum()\n")
    r = lint_paths(["entry.py", "helper.py"], root=str(tmp_path),
                   select=["PL004"], config_path=str(cfg_file))
    assert not r.findings


def test_pl003_sees_donation_through_a_factory_function():
    """`advance = self._advance_fn()` where the factory (inferred
    repo-wide) returns a donating jit program: the later read of the
    donated name is still flagged."""
    src = ("import jax\n"
           "def _advance_for(f):\n"
           "    return jax.jit(f, donate_argnums=(0,))\n"
           "class Pod:\n"
           "    def step(self, f, state):\n"
           "        advance = _advance_for(f)\n"
           "        out = advance(state)\n"
           "        return state.sum(), out\n")
    findings, _ = lint_source(src, "x.py", _cfg(), select={"PL003"})
    assert len(findings) == 1
    assert "use-after-donate: `state`" in findings[0].message


# ------------------------------------------------------------- suppressions
def test_ignore_comment_suppresses_only_named_rule():
    src = ("import jax.numpy as jnp\n"
           "a = jnp.zeros((3,))  # podlint: ignore[PL001] -- test buffer\n"
           "b = jnp.zeros((3,))  # podlint: ignore[PL002] -- wrong code\n"
           "c = jnp.zeros((3,))\n")
    findings, suppressed = lint_source(src, "x.py", _cfg())
    assert [f.line for f in findings] == [3, 4]
    assert suppressed == 1


def test_bare_ignore_suppresses_all_rules_on_the_line():
    src = ("import jax.numpy as jnp\n"
           "a = jnp.zeros((3,))  # podlint: ignore\n")
    findings, suppressed = lint_source(src, "x.py", _cfg())
    assert not findings and suppressed == 1


def test_skip_file_pragma_exempts_the_whole_module():
    src = ("# podlint: skip-file -- generated\n"
           "import jax.numpy as jnp\n"
           "a = jnp.zeros((3,))\n")
    findings, suppressed = lint_source(src, "x.py", _cfg())
    assert not findings and suppressed == 0


def test_parse_error_is_a_finding_not_a_crash():
    findings, _ = lint_source("def broken(:\n", "x.py", _cfg())
    assert [f.code for f in findings] == ["PL000"]


# ------------------------------------------------------------------- config
def test_rule_include_scopes_rule_to_matching_paths():
    cfg = _cfg(rules={"PL001": {"include": ["src/**"]}})
    src = "import jax.numpy as jnp\na = jnp.zeros((3,))\n"
    hit, _ = lint_source(src, "src/repro/x.py", cfg, select={"PL001"})
    miss, _ = lint_source(src, "tests/test_x.py", cfg, select={"PL001"})
    assert hit and not miss


def test_unknown_rule_code_in_config_is_a_config_error(tmp_path):
    bad = tmp_path / "podlint.toml"
    bad.write_text("[rule.PL999]\n")
    with pytest.raises(ConfigError, match="PL999"):
        load_config(str(bad), REGISTRY.keys())


def test_traced_functions_glob_seeds_pl004(tmp_path):
    src = ("import numpy as np\n"
           "import jax.numpy as jnp\n"
           "class A:\n"
           "    def ingest_routed(self, state):\n"
           "        return np.asarray(state)\n")
    quiet, _ = lint_source(src, "x.py", _cfg(), select={"PL004"})
    cfg = _cfg(traced_functions=["ingest_routed"])
    loud, _ = lint_source(src, "x.py", cfg, select={"PL004"})
    assert not quiet and len(loud) == 1


# ---------------------------------------------------------- exit-code / CLI
def test_exit_codes_clean_findings_error(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("import jax.numpy as jnp\n"
                     "a = jnp.zeros((3,), jnp.float32)\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax.numpy as jnp\na = jnp.zeros((3,))\n")
    assert podlint_main([clean.name, "--root", str(tmp_path)]) == 0
    assert podlint_main([dirty.name, "--root", str(tmp_path)]) == 1
    assert podlint_main(["no/such/dir", "--root", str(tmp_path)]) == 2
    assert podlint_main([clean.name, "--root", str(tmp_path),
                         "--select", "PL999"]) == 2
    capsys.readouterr()


def test_report_file_mirrors_stdout(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax.numpy as jnp\na = jnp.zeros((3,))\n")
    report = tmp_path / "report.txt"
    rc = podlint_main([dirty.name, "--root", str(tmp_path),
                       "--report", str(report)])
    out = capsys.readouterr().out
    assert rc == 1
    assert report.read_text().strip() == out.strip()
    assert "PL001" in out and "dirty.py:2:" in out


def test_sarif_output_is_valid_and_locates_findings(tmp_path, capsys):
    import json
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax.numpy as jnp\na = jnp.zeros((3,))\n")
    rc = podlint_main([dirty.name, "--root", str(tmp_path),
                       "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "podlint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert set(ALL_CODES) <= rule_ids and "PL000" in rule_ids
    res = run["results"][0]
    assert res["ruleId"] == "PL001"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "dirty.py"
    assert loc["region"]["startLine"] == 2


def test_changed_only_reports_only_the_diff(tmp_path, capsys):
    """--changed-only narrows reporting to git-changed files, but the
    whole scan set is still parsed (interprocedural facts stay sound)."""
    git = lambda *a: subprocess.run(
        ["git", *a], cwd=tmp_path, capture_output=True, text=True,
        timeout=60, check=True)
    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    committed = tmp_path / "old.py"
    committed.write_text("import jax.numpy as jnp\na = jnp.zeros((3,))\n")
    git("add", "old.py")
    git("commit", "-qm", "seed")
    fresh = tmp_path / "new.py"
    fresh.write_text("import jax.numpy as jnp\nb = jnp.zeros((4,))\n")
    rc = podlint_main(["old.py", "new.py", "--root", str(tmp_path),
                       "--changed-only"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "new.py:2:" in out and "old.py:2:" not in out
    assert "across 2 files" in out  # both parsed, one reported


def test_module_entrypoint_runs():
    """`python -m tools.podlint` is what Make/CI invoke — keep it alive."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.podlint", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0
    for code in ALL_CODES:
        assert code in proc.stdout


# ----------------------------------------------------------- the tree scan
def test_repo_tree_scans_clean():
    """The `make analyze` gate, as a test: src+tests+benchmarks carry no
    unsuppressed findings under the repo's podlint.toml."""
    result = lint_paths(["src", "tests", "benchmarks"],
                        config_path=str(REPO / "podlint.toml"),
                        root=str(REPO))
    assert not result.errors
    assert result.files > 50
    assert not result.findings, "\n".join(
        f.render() for f in result.findings)
