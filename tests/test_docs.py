"""The front-door docs stay honest: tools.check_docs finds real rot
and the repo's own docs pass it (the same check CI's ``docs`` job runs
via ``make docs-check``)."""
from pathlib import Path

from tools.check_docs import _design_sections, _make_targets, check

ROOT = Path(__file__).resolve().parents[1]


def test_repo_docs_are_clean():
    assert check(ROOT) == []


def test_checker_catches_rot(tmp_path):
    (tmp_path / "Makefile").write_text(
        "verify:\n\tpytest\nlint ruff:\n\ttrue\nVAR := x\n")
    (tmp_path / "DESIGN.md").write_text("## §1 Overview\n## §2 Details\n")
    (tmp_path / "README.md").write_text(
        "[design](DESIGN.md) [gone](nope.md)\n"
        "run `make verify`, `make lint` and `make bench-nope`\n"
        "see DESIGN.md §2 and DESIGN.md §9\n"
        "[web](https://example.com) is out of scope\n")
    problems = check(tmp_path)
    assert any("broken link -> nope.md" in p for p in problems)
    assert any("unknown make target -> bench-nope" in p for p in problems)
    assert any("§9 does not exist" in p for p in problems)
    # real targets / links / sections produce no findings
    assert not any("verify" in p or "lint" in p for p in problems)
    assert not any("DESIGN.md §2" in p for p in problems)
    assert len(problems) == 3


def test_makefile_parser_sees_phony_and_rules():
    targets = _make_targets(ROOT)
    for t in ("verify", "lint", "analyze", "docs-check", "bench",
              "verify-lockdep"):
        assert t in targets
    assert "PYTHONPATH" not in targets  # := assignment is not a rule


def test_design_sections_match_the_doc():
    sections = _design_sections(ROOT)
    assert set(range(1, 16)) <= sections  # §1..§15 all present
