"""Documentation/registry sync — tier 1.

The PWT code registry (analysis/diagnostics.py CODES + FAMILIES) is the
contract CI and users match on, and three things must not drift from
it: the family overviews in ARCHITECTURE.md and README.md, the
`--list-codes` surface, and the golden matrix's coverage.  Every code
must either appear in tests/golden/analysis_matrix.json (a bait in
tests/test_analysis.py build_lintful_graph triggers it) or sit in the
explicit exemption list below with the reason it cannot appear there —
and an exemption goes stale the moment the matrix does cover the code.
"""

import ast
import json
import re
from pathlib import Path

import pytest

from pathway_tpu.analysis.diagnostics import CODES, FAMILIES

ROOT = Path(__file__).resolve().parent.parent

# codes that cannot be produced by the static golden matrix, each with
# the place that does exercise it
GOLDEN_EXEMPT = {
    # runtime parity verifiers: emitted after an engine BUILDS (or runs)
    # and the plan disagrees with reality — the golden matrix never
    # builds an engine; negative tests force each one
    "PWT399": "verify_against_plan drift (test_perf_smoke parity tests)",
    "PWT599": "verify_fusion drift (PATHWAY_FUSION_FORCE_SKIP tests)",
    "PWT699": "verify_capacity drift (test_memtrack)",
    # environment-dependent lints the matrix's pinned env doesn't arm
    "PWT304": "flatten vector gate disabled (test_analysis unit tests)",
    "PWT604": "headroom warn band sits between PWT603's trigger and "
              "clean — covered by capacity unit tests (test_analysis)",
    "PWT702": "needs a declared SLO target below the batch window "
              "(test_serving / test_analysis unit tests)",
    "PWT801": "needs PATHWAY_SERVE_TENANT_RATE armed with qtrace off "
              "(test_costledger)",
    "PWT1001": "pass gates on provenance.ACTIVE, which the matrix's "
               "pinned env never arms (test_provenance unit tests)",
    "PWT1099": "needs PATHWAY_PROVENANCE_REQUIRE=1 on top of an armed "
               "tracker (test_provenance unit tests)",
}


def _golden_codes() -> set:
    payload = json.loads(
        (ROOT / "tests" / "golden" / "analysis_matrix.json").read_text()
    )
    return {f["code"] for f in payload["findings"]}


def test_every_family_documented_in_architecture_and_readme():
    arch = (ROOT / "ARCHITECTURE.md").read_text()
    readme = (ROOT / "README.md").read_text()
    for prefix, (family, owner) in sorted(FAMILIES.items()):
        tag = f"{prefix}xx"
        assert tag in arch, (
            f"{tag} ({family}, {owner}) missing from ARCHITECTURE.md"
        )
        assert tag in readme, (
            f"{tag} ({family}, {owner}) missing from README.md"
        )


def test_every_code_belongs_to_a_registered_family():
    prefixes = tuple(FAMILIES)
    for code in CODES:
        assert code.startswith(prefixes), (
            f"{code} has no family entry in FAMILIES"
        )


def test_every_code_in_golden_matrix_or_exemption_list():
    covered = _golden_codes()
    missing = sorted(set(CODES) - covered - set(GOLDEN_EXEMPT))
    assert not missing, (
        f"codes neither exercised by the golden matrix nor exempted: "
        f"{missing} — add a bait to build_lintful_graph (and regen via "
        f"python -m tests.regen_golden) or an exemption with a reason"
    )


def test_exemption_list_carries_no_stale_or_unknown_entries():
    covered = _golden_codes()
    stale = sorted(set(GOLDEN_EXEMPT) & covered)
    assert not stale, (
        f"exempted codes now covered by the golden matrix — prune "
        f"them: {stale}"
    )
    unknown = sorted(set(GOLDEN_EXEMPT) - set(CODES))
    assert not unknown, f"exemptions for unregistered codes: {unknown}"


def test_list_codes_surface_matches_registry():
    from pathway_tpu.analysis.tool import list_codes

    payload = json.loads(list_codes(as_json=True))
    listed = {entry["code"] for entry in payload["codes"]}
    assert listed == set(CODES)
    assert set(payload["families"]) == set(FAMILIES)


# -- the table of options ----------------------------------------------------
#
# internals/config.py OPTIONS is the configuration space; nothing else
# under pathway_tpu/ reads a PATHWAY_* variable, and README.md's options
# table is the same rows.

_NAME = re.compile(r"PATHWAY_[A-Z0-9_]+")
# a log sentinel of io/airbyte.py, not a variable
_NOT_OPTIONS = {"PATHWAY_AIRBYTE_SYNC_DONE"}


def _program_sources():
    for path in sorted((ROOT / "pathway_tpu").rglob("*.py")):
        yield path.relative_to(ROOT).as_posix(), path.read_text()


def _readme_option_rows() -> dict:
    rows = {}
    for line in (ROOT / "README.md").read_text().splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) == 5 and re.fullmatch(r"`PATHWAY_[A-Z0-9_]+`", cells[0]):
            rows[cells[0].strip("`")] = cells[1:4]
    return rows


def test_every_option_named_in_the_program_is_a_row_of_the_table():
    from pathway_tpu.internals.config import OPTIONS

    named = {}
    for rel, text in _program_sources():
        for name in _NAME.findall(text):
            named.setdefault(name, rel)
    stray = {
        n: rel for n, rel in named.items()
        if n not in OPTIONS and n not in _NOT_OPTIONS
    }
    assert not stray, f"PATHWAY_* names that are no row of OPTIONS: {stray}"

    readme = _readme_option_rows()
    assert set(readme) == set(OPTIONS), (
        f"README.md options table: missing {sorted(set(OPTIONS) - set(readme))}, "
        f"unknown {sorted(set(readme) - set(OPTIONS))}"
    )
    for name, (kind, default, read, role) in OPTIONS.items():
        shown = (
            "unset" if default is None else '""' if default == ""
            else str(int(default)) if kind is bool else str(default)
        )
        assert readme[name] == [f"`{shown}`", kind.__name__, f"{role}, {read}"], (
            f"README.md row of {name} reads {readme[name]}"
        )


def test_every_row_of_the_table_is_read_somewhere():
    from pathway_tpu.internals.config import OPTIONS

    read = set()
    for _rel, text in _program_sources():
        read.update(re.findall(r"\benv\(\s*\"(PATHWAY_[A-Z0-9_]+)\"", text))
    assert read == set(OPTIONS), (
        f"rows nothing reads: {sorted(set(OPTIONS) - read)}; "
        f"reads of no row: {sorted(read - set(OPTIONS))}"
    )
    for name, (kind, default, read_at, role) in OPTIONS.items():
        assert kind in (bool, int, float, str), name
        assert default is None or isinstance(default, kind), name
        assert read_at in ("import", "call"), name
        assert role in ("deployment", "gate", "recovery", "test-lever"), name


def test_only_config_reads_the_environment_for_an_option():
    """No module but internals/config.py parses an environment value or
    looks a PATHWAY_* name up (cli.py and supervisor.py only write them
    into the environment of a child they start)."""
    offenders = []
    for rel, text in _program_sources():
        if rel == "pathway_tpu/internals/config.py":
            continue
        for node in ast.walk(ast.parse(text)):
            if isinstance(node, ast.FunctionDef) and re.fullmatch(
                r"_?env_(bool|int|float|str)", node.name
            ):
                offenders.append(f"{rel}:{node.lineno} defines {node.name}")
            lookup = None
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("get", "getenv", "pop", "setdefault")
                and node.args
            ):
                lookup = node.args[0]
            elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load
            ):
                lookup = node.slice
            if (
                isinstance(lookup, ast.Constant)
                and isinstance(lookup.value, str)
                and lookup.value.startswith("PATHWAY_")
            ):
                offenders.append(f"{rel}:{node.lineno} reads {lookup.value}")
    assert not offenders, offenders


@pytest.mark.parametrize(
    "name, raw, value",
    [
        ("PATHWAY_HEALTH", None, True),  # a gate is on unless told "0"
        ("PATHWAY_HEALTH", "0", False),
        ("PATHWAY_HEALTH", "", True),  # empty reads as unset
        ("PATHWAY_SANITIZE", "1", True),
        ("PATHWAY_SANITIZE", "maybe", False),  # no parse: the default
        ("PATHWAY_EXCHANGE_WRITERS", None, None),  # unset means "decide"
        ("PATHWAY_EXCHANGE_WRITERS", "0", False),
        ("PATHWAY_INGEST_CHUNK", "512", 512),
        ("PATHWAY_INGEST_CHUNK", "many", 0),
        ("PATHWAY_PACK_TOKEN_BUDGET", None, None),
        ("PATHWAY_SERVE_BATCH_WINDOW_MS", "0.5", 0.5),
        ("PATHWAY_SLO_P99_MS", "fast", None),
        ("PATHWAY_TRACE", "0", "0"),  # a string comes back as it is
        ("PATHWAY_PERSISTENT_STORAGE", None, "./Cache"),
    ],
)
def test_env_reads_one_option_by_its_row(monkeypatch, name, raw, value):
    from pathway_tpu.internals import config

    if raw is None:
        monkeypatch.delenv(name, raising=False)
    else:
        monkeypatch.setenv(name, raw)
    got = config.env(name)
    assert got == value and type(got) is type(value)


def test_env_refuses_a_name_that_is_no_row():
    from pathway_tpu.internals import config

    with pytest.raises(KeyError):
        config.env("PATHWAY_PIPELINE_QUEUE")
