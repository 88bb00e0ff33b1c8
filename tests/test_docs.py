"""Docs cannot rot: operator-reference regression tests + link check.

* Every field of the typed :class:`repro.serving.api.AuditReport` (the
  schema behind ``engine.audit()``, §14) must be documented in
  ``docs/OPERATIONS.md`` (the counter tables), and every ``serve.py``
  flag must appear there too — adding a counter or flag without
  documenting it fails CI. Diffing the dataclass needs no live engine
  run: the field list IS the audit surface.
* Every relative markdown link in the repo's ``*.md`` files must resolve
  to a real file, and a ``#fragment`` must match a heading anchor in the
  target (GitHub slugification).
"""
import re
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
OPERATIONS = REPO / "docs" / "OPERATIONS.md"


# ---------------------------------------------------------------------------
# audit-doc regression: AuditReport schema vs docs/OPERATIONS.md
# ---------------------------------------------------------------------------

def _documented_keys(text):
    """Keys documented as `code` spans (counter tables use `key` cells)."""
    return set(re.findall(r"`([A-Za-z_][A-Za-z0-9_]*)`", text))


def test_every_audit_field_documented():
    from repro.serving.api import AuditReport
    text = OPERATIONS.read_text()
    # split composite cells like `a` / `b` too — the regex already
    # captures each span separately
    documented = _documented_keys(text)
    missing = sorted(set(AuditReport.field_names()) - documented)
    assert not missing, (
        f"AuditReport fields missing from docs/OPERATIONS.md: {missing} — "
        f"document each new counter with the invariant it witnesses")


def test_every_request_result_field_documented():
    from dataclasses import fields

    from repro.serving.api import RequestResult
    documented = _documented_keys(OPERATIONS.read_text())
    missing = sorted({f.name for f in fields(RequestResult)} - documented)
    assert not missing, (
        f"RequestResult fields missing from docs/OPERATIONS.md: {missing}")


def test_every_span_and_timeline_stamp_documented():
    """The span vocabulary (names the benchmark reads and operators see
    in a trace) and the timeline stamps."""
    from repro.core import spans
    text = OPERATIONS.read_text()
    names = spans.SPANS + spans.TIMELINE_STAMPS + (
        "prompt_chunk_steps", "prompt_tail_steps")
    missing = [n for n in names if f"`{n}`" not in text]
    assert not missing, (
        f"spans/stamps missing from docs/OPERATIONS.md: {missing}")


def test_audit_report_matches_live_audit():
    """The typed schema and a live ``engine.audit()`` dict agree exactly:
    same keys (``as_dict`` is the back-compat surface), no drift."""
    import numpy as np
    from repro.configs import get_reduced
    from repro.core.engine import EngineConfig, KVRMEngine
    from repro.core.scheduler import Request
    from repro.models import registry
    from repro.serving.api import AuditReport
    cfg = get_reduced("qwen2.5-32b")
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    eng = KVRMEngine(cfg, params, EngineConfig(
        mode="paged_merge", batch=2, max_seq=32, block_tokens=8))
    eng.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                       gen_len=4))
    eng.run(max_steps=64)
    rep = eng.audit_report()
    assert isinstance(rep, AuditReport)
    assert list(eng.audit()) == list(AuditReport.field_names())
    assert eng.audit() == rep.as_dict()


def test_every_serve_flag_documented():
    from repro.launch.serve import build_arg_parser
    text = OPERATIONS.read_text()
    flags = [opt for a in build_arg_parser()._actions
             for opt in a.option_strings if opt.startswith("--")]
    assert flags, "serve parser exposes no flags?"
    missing = sorted(f for f in flags if f != "--help" and f not in text)
    assert not missing, (
        f"serve.py flags missing from docs/OPERATIONS.md: {missing}")


# ---------------------------------------------------------------------------
# benchmark hygiene: every bench engine goes through the serving factory
# ---------------------------------------------------------------------------

def test_no_benchmark_constructs_engine_directly():
    """Benchmarks must build engines via ``serving.build`` (through
    ``benchmarks.common.engine``), never hand-roll ``KVRMEngine(...)`` /
    ``EngineConfig(...)`` — the factory is where params caching, lane
    wiring and flag defaults live (§14), and a hand-rolled engine
    silently diverges from what ``serve.py`` actually runs. common.py may
    IMPORT the class for type annotations; nothing may instantiate it."""
    errors = []
    for py in sorted((REPO / "benchmarks").glob("*.py")):
        text = py.read_text()
        for pat in (r"\bKVRMEngine\s*\(", r"\bEngineConfig\s*\("):
            for m in re.finditer(pat, text):
                line = text.count("\n", 0, m.start()) + 1
                errors.append(f"{py.relative_to(REPO)}:{line}: "
                              f"direct {m.group(0).rstrip('(').strip()}() "
                              f"construction — use benchmarks.common.engine")
        if py.name != "common.py" and "core.engine" in text:
            errors.append(f"{py.relative_to(REPO)}: imports repro.core."
                          f"engine — route through benchmarks.common")
    assert not errors, "\n".join(errors)


# ---------------------------------------------------------------------------
# markdown link check: relative links resolve, fragments match headings
# ---------------------------------------------------------------------------

_LINK = re.compile(r"(?<!!)\[[^\]]*\]\(([^)\s]+)\)")
_HEADING = re.compile(r"^#{1,6}\s+(.*?)\s*#*\s*$", re.M)
_CODE_FENCE = re.compile(r"```.*?```", re.S)


def _slugify(heading: str) -> str:
    """GitHub-style anchor: lowercase, drop punctuation, spaces -> '-'."""
    h = heading.strip().lower()
    h = re.sub(r"[^\w\- ]", "", h, flags=re.UNICODE)
    return h.replace(" ", "-")


def _anchors(md: Path) -> set:
    text = _CODE_FENCE.sub("", md.read_text())
    return {_slugify(m) for m in _HEADING.findall(text)}


def _md_files():
    skip = {".git", "__pycache__", ".pytest_cache", ".hypothesis"}
    return [p for p in REPO.rglob("*.md")
            if not (set(p.relative_to(REPO).parts[:-1]) & skip)]


def test_markdown_relative_links_resolve():
    errors = []
    for md in _md_files():
        text = _CODE_FENCE.sub("", md.read_text())
        for target in _LINK.findall(text):
            if re.match(r"^[a-z][a-z0-9+.-]*:", target):   # URL scheme
                continue
            path_part, _, frag = target.partition("#")
            dest = md if not path_part else (md.parent / path_part).resolve()
            if path_part and not dest.exists():
                errors.append(f"{md.relative_to(REPO)}: broken link "
                              f"-> {target}")
                continue
            if frag and dest.suffix == ".md" and dest.exists():
                if frag.lower() not in _anchors(dest):
                    errors.append(f"{md.relative_to(REPO)}: bad anchor "
                                  f"-> {target}")
    assert not errors, "\n".join(errors)


def test_link_checker_catches_breakage(tmp_path):
    """The checker itself must flag a broken link (fail-closed sanity)."""
    bad = "[x](does-not-exist-9f3.md) and [y](OPERATIONS.md#no-such-anchor)"
    text = _CODE_FENCE.sub("", bad)
    found = _LINK.findall(text)
    assert found == ["does-not-exist-9f3.md", "OPERATIONS.md#no-such-anchor"]
    assert "no-such-anchor" not in _anchors(OPERATIONS)
