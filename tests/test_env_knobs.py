"""Env-knob documentation tripwire.

Every ``RETPU_*`` environment variable the source tree reads must
appear in README.md's "Tuning knobs" table, and every knob the table
documents must still exist in code — so a new knob can't ship
undocumented and a removed one can't haunt the docs.  (Four knobs
shipped undocumented before this table existed; this test is the
ratchet.)
"""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: source roots scanned for knob reads (tests excluded: a test may
#: reference hypothetical knobs in strings)
SOURCE_ROOTS = ("riak_ensemble_tpu", "chip_smoke.py",
                "__graft_entry__.py")

KNOB_RE = re.compile(r"RETPU_[A-Z0-9_]+")


def _source_files():
    for root in SOURCE_ROOTS:
        path = os.path.join(REPO, root)
        if os.path.isfile(path):
            yield path
            continue
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__"]
            for f in filenames:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def _knobs_in_code():
    knobs = set()
    for path in _source_files():
        with open(path, encoding="utf-8") as fh:
            knobs.update(KNOB_RE.findall(fh.read()))
    return knobs


def _knobs_in_readme_table():
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        readme = fh.read()
    # table rows look like: | `RETPU_FOO` | default | effect |
    return set(re.findall(r"^\|\s*`(RETPU_[A-Z0-9_]+)`",
                          readme, re.MULTILINE))


def test_every_code_knob_is_documented():
    code = _knobs_in_code()
    documented = _knobs_in_readme_table()
    assert code, "knob scan found nothing — SOURCE_ROOTS broken?"
    missing = code - documented
    assert not missing, (
        f"undocumented RETPU_* knob(s) {sorted(missing)}: add a row "
        "to README.md's 'Tuning knobs (environment)' table")


def test_every_documented_knob_exists_in_code():
    stale = _knobs_in_readme_table() - _knobs_in_code()
    assert not stale, (
        f"README documents removed knob(s) {sorted(stale)}: drop the "
        "row or restore the knob")
