"""The documents name only what exists.

One case per document: every repository path it writes in backticks
(ending in ``.py``, ``.json``, ``.yaml``, ``.yml``, ``.md`` or ``/``),
every ``make <target>`` and every ``SDBKP_*`` environment variable it
names must exist in the tree, in the ``Makefile``, or be read by the
package. Flags and metric names are held by ``make analyze``
(``tools/analysis``); this holds the rest, so a deleted file, target or
knob cannot live on in the prose that tells an operator what to run.
"""

import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "spicedb_kubeapi_proxy_tpu")

DOCUMENTS = ["README.md", "docs/operations.md", "docs/architecture.md",
             "docs/development.md", "docs/embedding.md", "PARITY.md",
             "BASELINE.md"]

PATH_SUFFIXES = (".py", ".json", ".yaml", ".yml", ".md", "/")

# named in the documents and rightly not in the tree: what running the
# tools leaves behind, an operator's own files in a command line, and the
# reference implementation's paths (PARITY.md and BASELINE.md map them to
# this repository's)
NOT_IN_THE_TREE = {
    ".jax_compile_cache/", ".chip_smoke/", "chiprun_out/",
    "shard-map.json", "target-map.json",
    "pkg/spicedb/", "pkg/inmemory/", "pkg/failpoints/", "magefiles/",
    "benchmarks/", "e2e/rules.yaml",
}

_SPAN = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_MAKE = re.compile(r"\bmake ([a-z][a-z0-9-]*)")
_ENV = re.compile(r"\bSDBKP_[A-Z_]+\b")
_TOKEN = re.compile(r"^[A-Za-z0-9_.\-/]+$")


@functools.lru_cache(maxsize=None)
def _tracked_files() -> set:
    """Every file and directory of the tree, relative to the root, minus
    what ``.gitignore`` names (caches, chip outputs, scratch copies).
    Walked once for the seven cases, like the two sets below."""
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = {ln.strip().rstrip("/") for ln in f
                   if ln.strip() and not ln.startswith("#")}
    out = set()
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in ignored and d != ".git"]
        rel = os.path.relpath(base, ROOT)
        rel = "" if rel == "." else rel + "/"
        out.update(rel + d + "/" for d in dirs)
        out.update(rel + f for f in files)
    return out


def _resolves(token: str, doc_dir: str, tree: set) -> bool:
    """A path as the documents write them: from the root, from the
    package, from the document's own directory, or (a bare file name or
    a path's tail) anywhere below the root."""
    for prefix in ("", "spicedb_kubeapi_proxy_tpu/", doc_dir):
        if os.path.normpath(prefix + token) + ("/" if token.endswith("/")
                                               else "") in tree:
            return True
    return any(p == token or p.endswith("/" + token) for p in tree)


def _named_paths(text: str):
    for span in _SPAN.findall(_FENCE.sub("", text)):
        for token in span.split():
            token = token.split("::")[0]
            token = re.sub(r":[0-9,\-]+$", "", token)
            if not token.endswith(PATH_SUFFIXES) or not _TOKEN.match(token):
                continue  # a template, a glob, a URL or a sentence
            if token.startswith(("/", "~", "-")) or token in ("/", "./"):
                continue  # an absolute path is not a repository path
            yield token


@functools.lru_cache(maxsize=None)
def _make_targets() -> set:
    with open(os.path.join(ROOT, "Makefile")) as f:
        text = f.read()
    phony = re.search(r"^\.PHONY:(.*)$", text, re.M).group(1).split()
    return set(phony) | set(re.findall(r"^([a-z][a-z0-9-]*):", text, re.M))


@functools.lru_cache(maxsize=None)
def _env_read_by_the_package() -> set:
    found = set()
    for base, _dirs, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith((".py", ".cpp")):
                with open(os.path.join(base, name), errors="replace") as f:
                    found.update(_ENV.findall(f.read()))
    return found


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    doc_dir = os.path.dirname(document)
    doc_dir = doc_dir + "/" if doc_dir else ""
    tree = _tracked_files()
    stale = sorted({t for t in _named_paths(text)
                    if t not in NOT_IN_THE_TREE
                    and not _resolves(t, doc_dir, tree)})
    assert not stale, f"{document} names paths that do not exist: {stale}"
    code = " ".join(_SPAN.findall(text) + _FENCE.findall(text))
    gone = sorted(set(_MAKE.findall(code)) - _make_targets())
    assert not gone, f"{document} names make targets that do not exist: {gone}"
    unread = sorted(set(_ENV.findall(text)) - _env_read_by_the_package())
    assert not unread, \
        f"{document} names variables the package does not read: {unread}"
