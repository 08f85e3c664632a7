"""Knob-doc completeness lint: every typed env var ships documented.

``docs/env.md`` is the one-table reference for every ``AUTODIST_*``
variable; this tier-1 lint pins it against the typed source of truth
(``const.ENV``) in BOTH directions, so a new knob cannot ship
undocumented and a deleted knob cannot linger in the docs (several
PR 5/6 knobs were at risk of drifting before the table existed).
"""
import functools
import os
import re

import pytest

from autodist_tpu import const

_DOCS_ENV = os.path.join(os.path.dirname(__file__), os.pardir,
                         "docs", "env.md")


def _documented_vars():
    with open(_DOCS_ENV) as f:
        text = f.read()
    # Table rows document knobs as `AUTODIST_X` in the first column.
    return set(re.findall(r"`(AUTODIST_[A-Z0-9_]+)`", text))


def test_every_env_knob_documented():
    documented = _documented_vars()
    missing = sorted(e.var_name for e in const.ENV
                     if e.var_name not in documented)
    assert not missing, (
        f"env knobs missing from docs/env.md: {missing} — add a table row "
        f"(tier-1 lint, tests/test_docs_env.py)")
    # The module-level working-dir override is documented too.
    assert "AUTODIST_WORKING_DIR" in documented


def test_no_stale_documented_knobs():
    known = {e.var_name for e in const.ENV} | {"AUTODIST_WORKING_DIR"}
    stale = sorted(_documented_vars() - known)
    assert not stale, (
        f"docs/env.md documents knobs const.py no longer defines: {stale}")


#: Knobs that left ``const.ENV``; a later removal is one more entry.
_REMOVED = (
    "AUTODIST_OVERLAP", "AUTODIST_ZERO1_AG_SCOPE",              # PR 43
    "AUTODIST_ANOMALY_ZSCORE", "AUTODIST_GUARD_MAX_STRIKES",
    "AUTODIST_LOADER_POOL", "AUTODIST_LOADER_RING",
    "AUTODIST_METRICS_WINDOW", "AUTODIST_PROFILE_TOPK",
    "AUTODIST_SELFHEAL_HORIZON", "AUTODIST_RETUNE_MARGIN_PCT",
    "AUTODIST_SERVE_MAX_WAIT_MS", "AUTODIST_TUNER_PROBE",
    "AUTODIST_PREFETCH_DEPTH",
)
_ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
_SHIPPED = ("autodist_tpu", "docs", "examples", "scripts", "README.md")


@functools.lru_cache(maxsize=None)
def _shipped_text():
    """``{relative path: text}`` of every shipped file, read once."""
    paths = []
    for top in _SHIPPED:
        path = os.path.join(_ROOT, top)
        if os.path.isfile(path):
            paths.append(path)
        for folder, _, names in os.walk(path):
            paths.extend(os.path.join(folder, name) for name in names
                         if not name.endswith((".pyc", ".so")))
    text = {}
    for path in paths:
        with open(path, errors="ignore") as f:
            text[os.path.relpath(path, _ROOT)] = f.read()
    return text


@pytest.mark.parametrize("name", _REMOVED)
def test_a_removed_knob_is_named_nowhere(name):
    """A removed knob is no member of ``const.ENV`` and no shipped file
    (package, docs, examples, scripts, README) still names it."""
    assert name not in const.ENV.__members__
    pattern = re.compile(rf"\b{name}\b")
    named_in = sorted(path for path, text in _shipped_text().items()
                      if pattern.search(text))
    assert not named_in, f"{name} is still named in {named_in}"
