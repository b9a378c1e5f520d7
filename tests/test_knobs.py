"""utils/knobs.py — the central OTPU_* env-knob registry.

The completeness test is the teeth: every ``OTPU_`` literal anywhere in
the source tree must be declared in the registry (or be one of the two
documented stdout markers), so a new knob cannot ship undocumented the
way the first ten did."""

import os
import re

import pytest

from orange3_spark_tpu.utils import knobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TOKEN = re.compile(r"OTPU_[A-Z0-9_]*[A-Z0-9]")


def _source_files():
    roots = [os.path.join(REPO, "orange3_spark_tpu"),
             os.path.join(REPO, "tools")]
    files = [os.path.join(REPO, "bench.py"),
             os.path.join(REPO, "bench_suite.py")]
    for root in roots:
        for dirpath, _dirs, names in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            files.extend(os.path.join(dirpath, n) for n in names
                         if n.endswith(".py"))
    return files


def test_every_otpu_literal_is_registered():
    """Grep the source tree: any OTPU_ token not in the registry fails.
    A token that is a strict PREFIX of >= 2 registered knobs is a family
    mention in prose (e.g. 'OTPU_RETRY_*' docstrings) and passes."""
    registered = set(knobs.KNOBS)
    unknown: dict[str, list] = {}
    for path in _source_files():
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for tok in set(_TOKEN.findall(text)):
            if tok in registered or tok in knobs.NON_KNOB_MARKERS:
                continue
            family = [k for k in registered if k.startswith(tok + "_")]
            if len(family) >= 2:
                continue
            unknown.setdefault(tok, []).append(os.path.relpath(path, REPO))
    assert not unknown, (
        f"OTPU_ literals missing from utils/knobs.py KNOBS: {unknown} — "
        "declare them (name/type/default/subsystem/doc) in the registry")


def test_registry_entries_are_complete():
    for k in knobs.KNOBS.values():
        assert k.type in ("flag", "str", "int", "float", "marker"), k
        assert k.subsystem and k.doc and len(k.doc) > 10, k


def test_typed_getters_defaults_and_overrides(monkeypatch):
    monkeypatch.delenv("OTPU_RETRY_ATTEMPTS", raising=False)
    assert knobs.get_int("OTPU_RETRY_ATTEMPTS") == 4
    monkeypatch.setenv("OTPU_RETRY_ATTEMPTS", "7")
    assert knobs.get_int("OTPU_RETRY_ATTEMPTS") == 7
    # malformed values fall back to the declared default, never raise
    monkeypatch.setenv("OTPU_RETRY_ATTEMPTS", "lots")
    assert knobs.get_int("OTPU_RETRY_ATTEMPTS") == 4
    monkeypatch.setenv("OTPU_MB_DEADLINE_S", "nope")
    assert knobs.get_float("OTPU_MB_DEADLINE_S") == 30.0
    monkeypatch.delenv("OTPU_OBS", raising=False)
    assert knobs.get_bool("OTPU_OBS") is True
    monkeypatch.setenv("OTPU_OBS", "0")
    assert knobs.get_bool("OTPU_OBS") is False
    monkeypatch.setenv("OTPU_OBS", "1")
    assert knobs.get_bool("OTPU_OBS") is True
    monkeypatch.delenv("OTPU_BENCH_DIR", raising=False)
    assert knobs.get_str("OTPU_BENCH_DIR") == ""
    # unregistered names are a programming error, loudly
    with pytest.raises(KeyError):
        knobs.get_raw("OTPU_NOT_A_KNOB")


def test_resolution_goes_through_registry(monkeypatch):
    """The migrated call sites resolve via knobs (malformed -> default
    instead of the old ValueError/def-default drift)."""
    from orange3_spark_tpu.resilience.retry import RetryPolicy
    from orange3_spark_tpu.resilience.watchdog import dispatch_budget_s

    monkeypatch.setenv("OTPU_DISPATCH_BUDGET_S", "not-a-number")
    assert dispatch_budget_s() == 0.0
    monkeypatch.setenv("OTPU_DISPATCH_BUDGET_S", "1.5")
    assert dispatch_budget_s() == 1.5
    monkeypatch.setenv("OTPU_RETRY_BASE_S", "0.125")
    assert RetryPolicy.from_env().base_delay_s == 0.125


def test_knob_table_render_and_doc_pinned():
    md = knobs.knob_table_md()
    lines = md.strip().splitlines()
    assert lines[0].startswith("| knob |")
    assert len(lines) == 2 + len(knobs.KNOBS)
    for k in knobs.KNOBS:
        assert f"`{k}`" in md
    doc = os.path.join(REPO, "docs", "observability.md")
    with open(doc, encoding="utf-8") as f:
        text = f.read()
    begin, end = "<!-- KNOBS:BEGIN -->", "<!-- KNOBS:END -->"
    assert begin in text and end in text, "knob table markers missing"
    embedded = text.split(begin)[1].split(end)[0].strip()
    assert embedded == md.strip(), (
        "docs/observability.md knob table is stale — regenerate it with "
        "python -c 'from orange3_spark_tpu.utils.knobs import "
        "knob_table_md; print(knob_table_md())'")
