"""Docs can't rot: docstring, snippet-exec, and link-integrity gates.

Three regression surfaces, all cheap enough for tier-1 (CI also runs
them in the dedicated `docs` job):

* every public module under src/repro/ must carry a non-trivial
  docstring — docs/architecture.md points readers at module docstrings
  as the authoritative per-box reference, so an empty one is a doc bug;
* every ```python fenced block in docs/*.md is extracted and exec'd
  from the repo root (append ``noexec`` to the info string for
  illustrative snippets that need external state, e.g. a multi-host
  pod);
* every markdown link in docs/*.md and README.md resolves: repo-local
  paths must exist, intra-repo #anchors must match a real heading
  (http(s) links are recorded but NOT fetched — no network in CI).
"""
import importlib
import os
import pkgutil
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs")
DOC_FILES = sorted(
    os.path.join(DOCS, f) for f in os.listdir(DOCS) if f.endswith(".md"))
LINKED_FILES = DOC_FILES + [os.path.join(REPO, "README.md")]

MIN_DOCSTRING = 40     # chars: one real sentence, not a placeholder


def _public_modules() -> list[str]:
    import repro
    names = ["repro"]
    for m in pkgutil.walk_packages(repro.__path__, "repro."):
        if not any(part.startswith("_") for part in m.name.split(".")[1:]):
            names.append(m.name)
    return names


@pytest.mark.parametrize("name", _public_modules())
def test_public_module_has_nontrivial_docstring(name):
    """docs/architecture.md delegates per-module detail to docstrings;
    this keeps that promise honest."""
    if name == "repro.launch.dryrun":
        # importing dryrun pins XLA_FLAGS=...device_count=512 (see its
        # module NOTE) — read the docstring from source instead
        import ast
        path = os.path.join(REPO, "src", *name.split(".")) + ".py"
        with open(path) as f:
            doc = ast.get_docstring(ast.parse(f.read()))
    else:
        doc = importlib.import_module(name).__doc__
    assert doc and len(doc.strip()) >= MIN_DOCSTRING, (
        f"{name} has no (or a trivial) module docstring — document the "
        f"module or it falls out of the architecture guide")


# --- doc snippets -----------------------------------------------------------


def _python_snippets():
    """(doc, index, code) for every executable ```python block."""
    out = []
    fence = re.compile(r"^```(\S+)([^\n]*)\n(.*?)^```\s*$",
                       re.MULTILINE | re.DOTALL)
    for path in DOC_FILES:
        with open(path) as f:
            text = f.read()
        n = 0
        for m in fence.finditer(text):
            lang, info, code = m.group(1), m.group(2), m.group(3)
            if lang != "python":
                continue
            n += 1
            if "noexec" in info:
                continue
            out.append((os.path.basename(path), n, code))
    return out


SNIPPETS = _python_snippets()


def test_docs_contain_executable_snippets():
    """The extractor really found code (an empty list would make the
    exec test below pass vacuously)."""
    assert len(SNIPPETS) >= 4
    assert {doc for doc, _, _ in SNIPPETS} >= {
        "architecture.md", "sweep-backends.md",
        "reproducing-paper-figures.md", "serving.md",
        "campaigns.md"}


@pytest.mark.parametrize("doc,idx,code",
                         SNIPPETS,
                         ids=[f"{d}#{i}" for d, i, _ in SNIPPETS])
def test_doc_snippet_executes(doc, idx, code, monkeypatch):
    """Doctest-style: every ```python block in docs/ must run as-is from
    the repo root (mark genuinely non-runnable examples ``noexec``)."""
    monkeypatch.chdir(REPO)
    namespace = {"__name__": f"docsnippet_{doc}_{idx}"}
    exec(compile(code, f"{doc}#snippet{idx}", "exec"), namespace)


# --- links ------------------------------------------------------------------


def _github_anchor(heading: str) -> str:
    """GitHub's heading -> anchor slug (enough of it for our docs)."""
    h = heading.strip().lower()
    h = re.sub(r"[`*_]", "", h)
    h = re.sub(r"[^\w\- ]", "", h)
    return h.replace(" ", "-")


def _anchors_of(path: str) -> set:
    anchors = set()
    in_fence = False
    with open(path) as f:
        for line in f:
            if line.lstrip().startswith("```"):
                in_fence = not in_fence
            elif not in_fence and line.startswith("#"):
                anchors.add(_github_anchor(line.lstrip("#")))
    return anchors


def test_markdown_links_resolve():
    """Internal anchors + repo-relative paths only; no network."""
    link_re = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
    errors = []
    for path in LINKED_FILES:
        base = os.path.dirname(path)
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            text = f.read()
        # fenced code often contains [x](y)-looking noise — strip it
        text = re.sub(r"```.*?```", "", text, flags=re.DOTALL)
        for target in link_re.findall(text):
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            dest, _, anchor = target.partition("#")
            dest_path = os.path.normpath(os.path.join(base, dest)) \
                if dest else path
            if not os.path.exists(dest_path):
                errors.append(f"{rel}: broken path {target!r}")
                continue
            if anchor and dest_path.endswith(".md"):
                if anchor not in _anchors_of(dest_path):
                    errors.append(f"{rel}: missing anchor {target!r}")
    assert not errors, "\n".join(errors)


def test_readme_links_the_docs_tree():
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    for doc in ("docs/architecture.md", "docs/sweep-backends.md",
                "docs/reproducing-paper-figures.md", "docs/serving.md",
                "docs/campaigns.md"):
        assert doc in readme, f"README does not link {doc}"
