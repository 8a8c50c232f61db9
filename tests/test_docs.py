"""Docs stay true to the code.

docs/configuration.md claims to list every ``REPRO_*`` environment
variable and every FederatedConfig / PrivacyConfig field — so these
tests grep the source tree and the dataclasses and fail on any knob the
page forgot. Link checks keep README/docs cross-references resolvable.
"""
import dataclasses
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCS = REPO / "docs"
CONFIG_MD = (DOCS / "configuration.md").read_text()

# Whole names only: IS_REPRO_FALLBACK is a module attribute, not a variable.
_ENV_RE = re.compile(r"(?<![A-Z0-9_])REPRO_[A-Z][A-Z0-9_]*[A-Z0-9]")


def _source_env_vars():
    found = set()
    for py in (REPO / "src").rglob("*.py"):
        found.update(_ENV_RE.findall(py.read_text()))
    # drop pure prefixes that only ever appear as startswith() filters
    return {v for v in found if not any(w != v and w.startswith(v) for w in found)}


def test_every_env_var_documented():
    documented = set(_ENV_RE.findall(CONFIG_MD))
    missing = _source_env_vars() - documented
    assert not missing, (
        f"env vars used in src/ but absent from docs/configuration.md: "
        f"{sorted(missing)}"
    )


def test_every_config_field_documented():
    from repro.federated.trainer import FederatedConfig
    from repro.privacy.config import PrivacyConfig

    for cls in (FederatedConfig, PrivacyConfig):
        for f in dataclasses.fields(cls):
            assert f"`{f.name}`" in CONFIG_MD, (
                f"{cls.__name__}.{f.name} missing from docs/configuration.md"
            )


def test_readme_links_the_docs():
    readme = (REPO / "README.md").read_text()
    for page in ("threat_model.md", "architecture.md", "configuration.md"):
        assert (DOCS / page).exists(), f"docs/{page} missing"
        assert f"docs/{page}" in readme, f"README does not link docs/{page}"


_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)#\s]+)(?:#[^)\s]*)?\)")


@pytest.mark.parametrize(
    "md",
    [REPO / "README.md", *sorted(DOCS.glob("*.md"))],
    ids=lambda p: p.name,
)
def test_relative_links_resolve(md):
    dead = []
    for target in _LINK_RE.findall(md.read_text()):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        if not (md.parent / target).exists():
            dead.append(target)
    assert not dead, f"dead relative links in {md.name}: {dead}"
