"""Every ``KUBETPU_*`` environment variable the program reads is in
README.md's one table of them, and the table names nothing the program
does not read.  The seven variables of the SLO, telemetry and devstats
planes (removed in PR 45) are in neither."""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"KUBETPU_[A-Z0-9_]*[A-Z0-9]")
# spelled in parts: a grep of the tree for the removed names finds nothing
REMOVED = tuple("KUBETPU_" + tail for tail in (
    "SLO", "SLO_EXEMPLARS", "TELEMETRY", "TELEMETRY_WINDOW", "TELEMETRY_N",
    "DEVSTATS", "DEVSTATS_SAMPLE"))


def _sources():
    out = []
    for top, _, files in os.walk(os.path.join(ROOT, "kubetpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(top, f)) as fh:
                    out.append(fh.read())
    return out


SOURCES = _sources()


def _code_names():
    """Every name the sources under kubetpu/ spell, comments included: a
    variable that is only documented there is a finding too."""
    return {name for src in SOURCES for name in NAME.findall(src)}


def _table_names():
    with open(os.path.join(ROOT, "README.md")) as fh:
        text = fh.read()
    section = text.split("## Environment variables", 1)[1].split("\n## ", 1)[0]
    return {m for line in section.splitlines() if line.startswith("| `")
            for m in NAME.findall(line.split("|")[1])}


CODE, TABLE = _code_names(), _table_names()


@pytest.mark.parametrize("name", sorted(CODE | TABLE | set(REMOVED)))
def test_a_variable_is_read_and_documented_or_neither(name):
    if name in REMOVED:
        assert name not in CODE, f"{name} was removed in PR 45"
        assert name not in TABLE
        return
    assert name in CODE, f"README.md documents {name}; nothing reads it"
    assert name in TABLE, (
        f"kubetpu/ reads {name}; README.md's table does not have it")
    # read, not only spelled: some module takes it off the environment
    # (directly, or through the constant that holds its name)
    assert _is_read(name), f"{name} is spelled under kubetpu/, never read"


def _is_read(name):
    for src in SOURCES:
        if re.search(r"environ[^\n]*\n?[^\n]*\"%s\"" % name, src):
            return True
        const = re.search(r"^(\w+) = \"%s\"$" % name, src, re.M)
        if const and re.search(
                r"environ\s*(\.get\()?[^\n]*\n?[^\n]*\b%s\b"
                % const.group(1), src):
            return True
    return False
