"""Documentation path ratchet (the test_env_knobs pattern applied to
file names): every backticked token in the documents that looks like a
path of this repo (``*.py``, ``*.cc``, ``*.json``, ``*.md``, with or
without ``:line``) names a file that exists, so a document cannot keep
sending its reader to a file that went."""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ("README.md", "docs/ARCHITECTURE.md", "PARITY.md", "BASELINE.md",
        "benchmarks/README.md", ".claude/skills/verify/SKILL.md")

#: where a document's path may start
LOOKUP = ("", "riak_ensemble_tpu", "benchmarks", "tests")

PATH_RE = re.compile(
    r"`([A-Za-z0-9_./*-]+\.(?:py|cc|json|md))(?::[0-9][0-9,:–-]*)?`")

#: names that are not files of this checkout
ALLOWED = (
    "doc/*.md",         # the Erlang reference's own documentation
    "flight_*.json",    # a flight recorder's dumps, made at run time
    ".bench_out/*",     # a benchmark run's scratch directory
    # benchmarks/README.md names this traffic file without its
    # directory (benchmarks/traffic/): the next `benchmark` PR's
    "ycsb-a-r400.json",
)


def _exists(token: str) -> bool:
    if any(fnmatch.fnmatch(token, pat) for pat in ALLOWED):
        return True
    return any(glob.glob(os.path.join(REPO, root, token))
               for root in LOOKUP)


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        named = set(PATH_RE.findall(fh.read()))
    assert named, f"{doc}: the scan found no path at all"
    gone = sorted(t for t in named if not _exists(t))
    assert not gone, (
        f"{doc} names file(s) that do not exist: {gone}; fix the "
        "sentence, or allow-list a name that is not this checkout's")
