"""blades-lint (tools/lint): the tier-1 static-analysis gate.

Three layers:

1. **Fixture coverage** — every pass has a known-bad / known-good pair
   under ``tests/lint_fixtures/`` (deliberately-seeded violations of
   each invariant: donation reuse, key reuse, env-read-in-jit, host
   sync, unfrozen static config, unregistered metric key, unmarked mesh
   test, stale artifact stamp), pragma-suppression behavior, and the
   ``--changed`` file filter.
2. **CLI contract** — ``python -m tools.lint --json`` emits the
   machine-readable findings the sweep/bench harnesses consume.
3. **CI enforcement** — every pass over THIS repo's full tree must
   report zero unsuppressed error findings (the test that makes lint
   regressions tier-1 failures), inside the lint wall-time budget.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from tools.lint import ERROR, run_passes  # noqa: E402
from tools.lint.cli import main as lint_main  # noqa: E402
from tools.lint import core  # noqa: E402
from tools.lint.core import changed_files, collect_files  # noqa: E402
from tools.lint.passes import ALL_PASSES  # noqa: E402
from tools.lint.passes.artifacts import (  # noqa: E402
    ArtifactStampsPass,
    recompute_stamps,
)
from tools.lint.passes.donation import DonationPass  # noqa: E402
from tools.lint.passes.host_sync import HostSyncPass  # noqa: E402
from tools.lint.passes.pass_discipline import PassDisciplinePass  # noqa: E402
from tools.lint.passes.prng import PrngPass  # noqa: E402
from tools.lint.passes.purity import PurityPass  # noqa: E402
from tools.lint.passes.schema_drift import SchemaDriftPass  # noqa: E402
from tools.lint.passes.slow_markers import audit_path  # noqa: E402
from tools.lint.passes.static_args import StaticArgsPass  # noqa: E402
from tools.lint.passes.topology_discipline import (  # noqa: E402
    TopologyDisciplinePass,
)
from tools.lint.passes.trace_discipline import TraceDisciplinePass  # noqa: E402
from tools.lint.core import LintContext  # noqa: E402

FIX = "tests/lint_fixtures"


def run_fixture(passes, *names):
    """Run `passes` over the named fixture files only."""
    only = [REPO / FIX / n for n in names]
    return run_passes(REPO, passes, only=only)


def errors_of(findings, pass_name=None):
    return [f for f in findings if f.severity == ERROR
            and (pass_name is None or f.pass_name == pass_name)]


# ---------------------------------------------------------------------------
# per-pass fixture pairs (seeded violations must be caught; clean twins
# must stay clean)
# ---------------------------------------------------------------------------


def test_donation_fixtures():
    bad = errors_of(run_fixture([DonationPass()], "donation_bad.py"),
                    "use-after-donate")
    msgs = "\n".join(f.message for f in bad)
    assert "'state' is read after being donated" in msgs
    assert "'s0' is read after being donated" in msgs  # the loop form
    assert "'state' is read after being donated to step()" in msgs
    assert len(bad) >= 3
    assert run_fixture([DonationPass()], "donation_good.py") == []


def test_prng_fixtures():
    bad = errors_of(run_fixture([PrngPass()], "prng_bad.py"), "prng-reuse")
    msgs = "\n".join(f.message for f in bad)
    assert "key 'key' already consumed" in msgs
    assert "loop-invariant key 'key'" in msgs
    assert sum("dropout" not in m for m in [f.message for f in bad]) >= 2
    assert len(bad) == 3  # double draw, loop invariant, dropout reuse
    assert run_fixture([PrngPass()], "prng_good.py") == []


def test_purity_fixtures():
    bad = errors_of(run_fixture([PurityPass()], "purity_bad.py"),
                    "jit-purity")
    msgs = "\n".join(f.message for f in bad)
    assert "`os.environ.get` read inside `env_in_jit`" in msgs
    assert "`print()` call inside `helper`" in msgs  # via _jit reachability
    assert "`global` statement" in msgs  # via jax.jit(mutating_body)
    assert run_fixture([PurityPass()], "purity_good.py") == []


def test_host_sync_fixtures():
    hs = HostSyncPass(modules=[f"{FIX}/hostsync_bad.py"])
    bad = errors_of(run_fixture([hs], "hostsync_bad.py"), "host-sync")
    msgs = "\n".join(f.message for f in bad)
    assert "float() on an array expression" in msgs
    assert "np.asarray()" in msgs
    assert ".item()" in msgs
    assert "jax.device_get()" in msgs
    assert ".block_until_ready()" in msgs
    assert len(bad) == 5
    hs_good = HostSyncPass(modules=[f"{FIX}/hostsync_good.py"])
    assert run_fixture([hs_good], "hostsync_good.py") == []


def test_staging_discipline_fixtures():
    """ISSUE 15: the host-sync pass covers the out-of-core staging hot
    path (blades_tpu/state/ rides DEVICE_SIDE) — a blocking fetch
    anywhere but the pragma'd prefetcher boundary is a finding."""
    from tools.lint.passes.host_sync import DEVICE_SIDE

    assert "blades_tpu/state/store.py" in DEVICE_SIDE
    assert "blades_tpu/state/prefetch.py" in DEVICE_SIDE
    hs = HostSyncPass(modules=[f"{FIX}/stagingdiscipline_bad.py"])
    bad = errors_of(run_fixture([hs], "stagingdiscipline_bad.py"),
                    "host-sync")
    msgs = "\n".join(f.message for f in bad)
    assert "float() on an array expression" in msgs
    assert "np.asarray()" in msgs
    assert "jax.device_get()" in msgs
    assert ".item()" in msgs
    assert ".block_until_ready()" in msgs
    assert len(bad) == 5
    hs_good = HostSyncPass(modules=[f"{FIX}/stagingdiscipline_good.py"])
    assert run_fixture([hs_good], "stagingdiscipline_good.py") == []


def test_datastore_discipline_fixtures():
    """ISSUE 20: the host-sync pass covers the out-of-core data plane
    (blades_tpu/data/store.py + stream.py ride DEVICE_SIDE) — cohort
    gathers are host IO by construction and the streaming evaluator's
    only sanctioned sync is the pragma'd four-scalar per-chunk fetch;
    any other blocking fetch is a finding."""
    from tools.lint.passes.host_sync import DEVICE_SIDE
    from tools.lint.passes.purity import TRACED_MODULES

    assert "blades_tpu/data/store.py" in DEVICE_SIDE
    assert "blades_tpu/data/stream.py" in DEVICE_SIDE
    # ...and both in jit-purity's whole-module set: the chunked eval
    # program traces, and the shard writer's file IO is pragma'd.
    assert "blades_tpu/data/store.py" in TRACED_MODULES
    assert "blades_tpu/data/stream.py" in TRACED_MODULES
    hs = HostSyncPass(modules=[f"{FIX}/datastorediscipline_bad.py"])
    bad = errors_of(run_fixture([hs], "datastorediscipline_bad.py"),
                    "host-sync")
    msgs = "\n".join(f.message for f in bad)
    assert "float() on an array expression" in msgs
    assert "np.asarray()" in msgs
    assert "jax.device_get()" in msgs
    assert ".item()" in msgs
    assert ".block_until_ready()" in msgs
    assert len(bad) == 5
    hs_good = HostSyncPass(modules=[f"{FIX}/datastorediscipline_good.py"])
    assert run_fixture([hs_good], "datastorediscipline_good.py") == []


def test_ledger_discipline_fixtures():
    """ISSUE 16: the host-sync pass covers the client ledger's
    per-round update path (blades_tpu/obs/ledger.py rides DEVICE_SIDE)
    — observe() must consume already-fetched host rows; any device
    fetch outside the pragma'd coercion boundary is a finding."""
    from tools.lint.passes.host_sync import DEVICE_SIDE
    from tools.lint.passes.purity import TRACED_MODULES

    assert "blades_tpu/obs/ledger.py" in DEVICE_SIDE
    # ...but NOT in jit-purity's whole-module set: the ledger is host
    # code by construction and its checkpoint I/O is legitimate.
    assert "blades_tpu/obs/ledger.py" not in TRACED_MODULES
    hs = HostSyncPass(modules=[f"{FIX}/ledgerdiscipline_bad.py"])
    bad = errors_of(run_fixture([hs], "ledgerdiscipline_bad.py"),
                    "host-sync")
    msgs = "\n".join(f.message for f in bad)
    assert "np.asarray()" in msgs
    assert "jax.device_get()" in msgs
    assert "float() on an array expression" in msgs
    assert "int() on an array expression" in msgs
    assert ".block_until_ready()" in msgs
    assert len(bad) == 5
    hs_good = HostSyncPass(modules=[f"{FIX}/ledgerdiscipline_good.py"])
    assert run_fixture([hs_good], "ledgerdiscipline_good.py") == []


def test_control_discipline_fixtures():
    """ISSUE 17: the host-sync pass covers the control plane's decision
    path (blades_tpu/control/ rides DEVICE_SIDE) — policy decisions must
    be pure over already-fetched sensor rows, so a device fetch mid-
    decision is a finding, and a wall-clock cooldown (actions no longer
    pure in (round, tick) ⇒ the journal stops re-deriving) is the
    trace-discipline half of the same contract."""
    from tools.lint.passes.host_sync import DEVICE_SIDE

    assert "blades_tpu/control/policy.py" in DEVICE_SIDE
    assert "blades_tpu/control/controller.py" in DEVICE_SIDE
    hs = HostSyncPass(modules=[f"{FIX}/controldiscipline_bad.py"])
    bad = errors_of(run_fixture([hs], "controldiscipline_bad.py"),
                    "host-sync")
    msgs = "\n".join(f.message for f in bad)
    assert "np.asarray()" in msgs
    assert "float() on an array expression" in msgs
    assert "jax.device_get()" in msgs
    assert len(bad) == 3
    tp = TraceDisciplinePass(prefixes=[f"{FIX}/controldiscipline_bad.py"])
    clocks = errors_of(run_fixture([tp], "controldiscipline_bad.py"),
                       "trace-discipline")
    cmsgs = "\n".join(f.message for f in clocks)
    assert "time.time()" in cmsgs
    assert "time.perf_counter()" in cmsgs
    assert len(clocks) == 2
    # Clean twin: host-row reads + round-indexed cooldowns are silent
    # under BOTH passes.
    hs_good = HostSyncPass(modules=[f"{FIX}/controldiscipline_good.py"])
    assert run_fixture([hs_good], "controldiscipline_good.py") == []
    tp_good = TraceDisciplinePass(
        prefixes=[f"{FIX}/controldiscipline_good.py"])
    assert run_fixture([tp_good], "controldiscipline_good.py") == []


def test_static_args_fixtures():
    sa = StaticArgsPass(prefixes=[f"{FIX}/static_bad.py"])
    bad = errors_of(run_fixture([sa], "static_bad.py"), "static-config")
    msgs = "\n".join(f.message for f in bad)
    assert "UnfrozenConfig is not frozen=True" in msgs
    assert "IdentityHashConfig sets eq=False" in msgs
    assert "UnhashableFieldsConfig.schedule" in msgs
    assert "UnhashableFieldsConfig.table" in msgs  # dict inside Optional
    assert "defaults to a mutable list()" in msgs
    sa_good = StaticArgsPass(prefixes=[f"{FIX}/static_good.py"])
    assert run_fixture([sa_good], "static_good.py") == []


def test_schema_drift_fixtures():
    sd = SchemaDriftPass(schema_module=f"{FIX}/schema_mod.py",
                         stamp_modules=[f"{FIX}/schema_stamp_bad.py"])
    findings = run_fixture([sd], "schema_mod.py", "schema_stamp_bad.py")
    bad = errors_of(findings, "schema-drift")
    assert len(bad) == 1 and "mystery_key" in bad[0].message
    warns = [f for f in findings if f.severity != ERROR]
    assert len(warns) == 1 and "never_stamped" in warns[0].message
    # The clean twin: every stamp registered; only the warning remains.
    sd_good = SchemaDriftPass(schema_module=f"{FIX}/schema_mod.py",
                              stamp_modules=[f"{FIX}/schema_stamp_good.py"])
    findings = run_fixture([sd_good], "schema_mod.py",
                           "schema_stamp_good.py")
    assert errors_of(findings) == []
    assert any("never_stamped" in f.message for f in findings)


def test_pass_discipline_fixtures():
    bad = errors_of(run_fixture([PassDisciplinePass()],
                                "passdiscipline_bad.py"),
                    "streamed-pass-discipline")
    msgs = "\n".join(f.message for f in bad)
    assert "row_sq_norms()" in msgs
    assert "gram()" in msgs
    assert "wrs()" in msgs           # aliased import resolves
    assert "sg.sign_counts()" in msgs  # module-attribute access
    # Wire-domain decode discipline: the raw decode-to-f32 primitive is
    # flagged through both the bare import and a codec-module alias.
    assert "dequantize()" in msgs
    assert "cc.dequantize()" in msgs
    assert len(bad) == 6
    # Clean twin: planner requests, layout.py's SAME-NAMED shard helper
    # (a different module), and the sanctioned wire path (decode_deferred
    # + aggregate_wire) produce nothing.
    assert run_fixture([PassDisciplinePass()],
                       "passdiscipline_good.py") == []


def test_topology_discipline_fixtures():
    """ISSUE 19 fixture pair: a file that builds topology neighbor
    tables and spells a raw cross-device collective is an UNCOUNTED
    neighborhood exchange (gossip_ici_bytes stops reconciling); the
    host-side-graph-math twin stays silent."""
    bad = errors_of(run_fixture([TopologyDisciplinePass()],
                                "topologydiscipline_bad.py"),
                    "topology-discipline")
    msgs = "\n".join(f.message for f in bad)
    assert "lax.all_gather()" in msgs
    assert "jax.lax.psum()" in msgs
    assert "jax.lax.ppermute()" in msgs
    assert len(bad) == 3
    assert run_fixture([TopologyDisciplinePass()],
                       "topologydiscipline_good.py") == []


def test_topology_discipline_repo_tree_clean():
    """The real tree is clean: gossip.py's counted gathers are exempt by
    construction (the one sanctioned module), and collective-using files
    that never build tables (parallel/hier.py) must not false-positive."""
    findings = errors_of(run_passes(REPO, [TopologyDisciplinePass()]),
                         "topology-discipline")
    assert findings == [], "\n".join(f.render() for f in findings)


def test_trace_discipline_fixtures():
    tp = TraceDisciplinePass(prefixes=[f"{FIX}/tracediscipline_bad.py"])
    bad = errors_of(run_fixture([tp], "tracediscipline_bad.py"),
                    "trace-discipline")
    msgs = "\n".join(f.message for f in bad)
    assert "time.time()" in msgs
    assert "perf_counter()" in msgs          # from-import form
    assert "mono()" in msgs                  # aliased from-import
    assert "time.perf_counter_ns()" in msgs  # _ns variant
    assert len(bad) == 5
    # Clean twin: spans, obs.trace.now(), time.sleep, an injectable
    # clock REFERENCE, and a pragma'd wall-clock stamp all stay silent.
    tg = TraceDisciplinePass(prefixes=[f"{FIX}/tracediscipline_good.py"])
    assert run_fixture([tg], "tracediscipline_good.py") == []


def test_arrival_purity_fixtures():
    """ISSUE 14 fixture pair: arrival realizations must be pure in
    (seed, tick) — a wall-clock-derived tick (or a raw-clock ingest
    measurement) in an arrival process is exactly the trace-discipline
    violation class, and the virtual-tick/\\ ``obs.trace.now()`` twin
    stays silent."""
    ap = TraceDisciplinePass(prefixes=[f"{FIX}/arrivalpurity_bad.py"])
    bad = errors_of(run_fixture([ap], "arrivalpurity_bad.py"),
                    "trace-discipline")
    msgs = "\n".join(f.message for f in bad)
    assert "time.time()" in msgs             # wall-clock tick derivation
    assert "mono()" in msgs                  # aliased from-import form
    assert "time.perf_counter()" in msgs     # raw ingest-rate measurement
    assert len(bad) == 4
    # Clean twin: the virtual tick counter and the sanctioned
    # obs.trace.now() ingest measurement produce zero findings.
    ag = TraceDisciplinePass(prefixes=[f"{FIX}/arrivalpurity_good.py"])
    assert run_fixture([ag], "arrivalpurity_good.py") == []


def test_trace_discipline_allows_timer_modules():
    """The span layer itself (and its shims) are the sanctioned homes of
    raw clock reads — the default-configured pass must skip them while
    still scanning the rest of blades_tpu/."""
    findings = errors_of(run_passes(REPO, [TraceDisciplinePass()]),
                         "trace-discipline")
    assert findings == [], "\n".join(f.render() for f in findings)


def test_slow_markers_fixture(tmp_path):
    bad = tmp_path / "probe.py"
    bad.write_text(
        "import pytest\n"
        "from blades_tpu.parallel import make_mesh\n\n"
        "@pytest.fixture\n"
        "def setup():\n"
        "    return make_mesh()\n\n"
        "def test_uses_fixture(setup):\n"
        "    pass\n\n"
        "@pytest.mark.slow\n"
        "def test_marked():\n"
        "    make_mesh()\n"
    )
    findings = audit_path(bad)
    assert len(findings) == 1
    assert "test_uses_fixture" in findings[0].message
    assert "fixture 'setup'" in findings[0].message


def test_artifact_stamps_fixture(tmp_path):
    # A miniature repo: the reference-grid constants + one stale artifact.
    curves = tmp_path / "blades_tpu" / "benchmarks"
    curves.mkdir(parents=True)
    (curves / "accuracy_curves.py").write_text(
        'REFERENCE_AGGREGATORS = ["Mean", "Median"]\n'
        "REFERENCE_MALICIOUS_FRACS = [0.0, 0.5]\n")
    art = tmp_path / "artifacts" / "smoke"
    art.mkdir(parents=True)
    rows = [{"aggregator": "Mean", "num_malicious": 0}]
    (art / "curves.json").write_text(json.dumps(
        {"num_clients": 10, "complete": True, "rows": rows}))
    findings = list(ArtifactStampsPass().run(LintContext(tmp_path, [])))
    assert len(findings) == 1 and "stale complete: True" in findings[0].message
    # Re-stamped under reference-grid semantics the artifact is accepted.
    data = json.loads((art / "curves.json").read_text())
    data.update(recompute_stamps(data, ["Mean", "Median"], [0.0, 0.5]))
    assert data["complete"] is False
    assert data["reference_cells_missing"] == ["Mean@5", "Median@0",
                                               "Median@5"]
    (art / "curves.json").write_text(json.dumps(data))
    assert list(ArtifactStampsPass().run(LintContext(tmp_path, []))) == []


def test_restamp_curves_cli(tmp_path):
    """The fixer round-trips: --check flags, a rewrite silences."""
    stale = tmp_path / "curves.json"
    stale.write_text(json.dumps({
        "num_clients": 60, "complete": True,
        "rows": [{"aggregator": "Mean", "num_malicious": 0}]}))
    cmd = [sys.executable, str(REPO / "tools" / "restamp_curves.py")]
    r = subprocess.run(cmd + ["--check", str(stale)],
                       capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 1 and "True -> False" in r.stdout
    r = subprocess.run(cmd + [str(stale)],
                       capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 0, r.stderr
    data = json.loads(stale.read_text())
    assert data["complete"] is False and data["reference_cells_missing"]
    r = subprocess.run(cmd + ["--check", str(stale)],
                       capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 0  # stamps now current


# ---------------------------------------------------------------------------
# pragma allowlist
# ---------------------------------------------------------------------------


def test_pragma_suppresses_with_reason():
    hs = HostSyncPass(modules=[f"{FIX}/pragma_suppressed.py"])
    findings = run_fixture([hs], "pragma_suppressed.py")
    # Both violations suppressed (named pass + `all`), pragmas carry
    # reasons, so nothing at all is reported.
    assert findings == []


def test_pragma_requires_reason_and_real_pass_name():
    hs = HostSyncPass(modules=[f"{FIX}/pragma_bad.py"])
    findings = run_fixture([hs], "pragma_bad.py")
    pragma = [f for f in findings if f.pass_name == "pragma"]
    assert any("without a justification" in f.message for f in pragma)
    assert any("unknown pass(es) ['host-sink']" in f.message
               for f in pragma)
    # The bare-but-parsed pragma still suppresses its line; the typo'd
    # one suppresses nothing, so its host-sync violation survives.
    hs_findings = errors_of(findings, "host-sync")
    assert len(hs_findings) == 1 and hs_findings[0].line == 10


def test_pragma_in_string_is_not_live(tmp_path):
    # A pragma spelled inside a docstring/string (e.g. a module
    # documenting the grammar) must register nothing — neither a
    # suppression nor a pragma-audit finding.
    f = tmp_path / "docstrings.py"
    f.write_text(
        '"""Grammar doc:\n'
        "``# blades-lint: disable-file=host-sync — example``\n"
        '"""\n'
        'S = "# blades-lint: disable=all — in a string"\n'
        "x = 1  # blades-lint: disable=host-sync — a REAL comment pragma\n"
    )
    sf = core.SourceFile(f, tmp_path)
    assert len(sf.pragmas) == 1 and sf.pragmas[0].line == 5
    assert not sf.disabled("host-sync", 2)


# ---------------------------------------------------------------------------
# --changed filtering + CLI
# ---------------------------------------------------------------------------


def test_changed_file_filtering(tmp_path):
    def git(*args):
        subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                        *args], cwd=tmp_path, check=True,
                       capture_output=True)

    git("init", "-q")
    committed = tmp_path / "committed.py"
    committed.write_text("import jax\n\ndef f(key):\n"
                         "    a = jax.random.normal(key, ())\n"
                         "    return a + jax.random.normal(key, ())\n")
    git("add", "committed.py")
    git("commit", "-qm", "seed")
    fresh = tmp_path / "fresh.py"
    fresh.write_text(committed.read_text())
    changed = changed_files(tmp_path)
    assert changed == [fresh]
    # Only the changed file is linted: committed.py's identical
    # violation stays invisible to a --changed run.
    findings = run_passes(tmp_path, [PrngPass()], only=changed)
    assert {f.path for f in findings} == {"fresh.py"}
    assert errors_of(findings, "prng-reuse")


def test_cli_json_machine_readable():
    r = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--json",
         f"{FIX}/prng_bad.py", f"{FIX}/donation_bad.py"],
        capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 1
    payload = json.loads(r.stdout)
    assert payload["summary"]["errors"] >= 4
    by_pass = {f["pass_name"] for f in payload["findings"]}
    assert {"prng-reuse", "use-after-donate"} <= by_pass
    sample = payload["findings"][0]
    assert {"pass_name", "path", "line", "message", "fix_hint",
            "severity"} <= set(sample)


def test_cli_lists_all_passes():
    r = subprocess.run([sys.executable, "-m", "tools.lint",
                        "--list-passes"],
                       capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 0
    names = [line.split()[0] for line in r.stdout.splitlines() if line]
    assert len(names) >= 7  # ISSUE 8: at least 6 passes + the folded audit
    for expected in ("use-after-donate", "prng-reuse", "jit-purity",
                     "host-sync", "static-config", "schema-drift",
                     "streamed-pass-discipline", "trace-discipline",
                     "slow-markers", "artifact-stamps"):
        assert expected in names


# ---------------------------------------------------------------------------
# CI enforcement: the real tree is clean, inside the wall-time budget
# ---------------------------------------------------------------------------


def test_repo_tree_is_clean():
    """Every pass over blades_tpu/, tests/ and tools/: zero
    unsuppressed ERROR findings — new violations land as tier-1
    failures with file:line + fix-hint."""
    t0 = time.perf_counter()
    findings = run_passes(REPO, ALL_PASSES)
    elapsed = time.perf_counter() - t0
    bad = errors_of(findings)
    assert not bad, "\n" + "\n".join(f.render() for f in bad)
    # Warnings must stay actionable, not accumulate as noise: the
    # dynamically-stamped schema keys are pragma'd, so a clean tree
    # reports NO warnings either.
    assert findings == [], "\n".join(f.render() for f in findings)
    # ISSUE 8 budget: the full-tree lint stays well under 60 s so it
    # rides tier-1 without denting the 870 s cap.
    assert elapsed < 60.0, f"lint took {elapsed:.1f}s"


def test_fixture_dir_is_excluded_from_tree_scan():
    files = {f.rel for f in collect_files(REPO)}
    assert not any("lint_fixtures" in rel for rel in files)
    assert "blades_tpu/core/round.py" in files
    assert "tools/lint/core.py" in files


@pytest.mark.parametrize("seeded", [
    "donation_bad.py", "prng_bad.py", "purity_bad.py", "hostsync_bad.py",
    "static_bad.py", "schema_stamp_bad.py", "passdiscipline_bad.py",
    "tracediscipline_bad.py"])
def test_every_seeded_violation_class_is_caught(seeded):
    """ISSUE 8 acceptance (+ ISSUE 9's pass discipline, ISSUE 12's
    trace discipline): donation reuse, key reuse, env-read-in-jit, host
    sync, unfrozen static config, unregistered metric key,
    raw-traversal-outside-planner, raw-clock-outside-trace-layer — each
    deliberately-seeded class is caught by its pass."""
    passes = [
        DonationPass(), PrngPass(), PurityPass(),
        HostSyncPass(modules=[f"{FIX}/hostsync_bad.py"]),
        StaticArgsPass(prefixes=[f"{FIX}/static_bad.py"]),
        SchemaDriftPass(schema_module=f"{FIX}/schema_mod.py",
                        stamp_modules=[f"{FIX}/schema_stamp_bad.py"]),
        PassDisciplinePass(),
        TraceDisciplinePass(prefixes=[f"{FIX}/tracediscipline_bad.py"]),
    ]
    extra = (["schema_mod.py"] if seeded == "schema_stamp_bad.py" else [])
    findings = run_fixture(passes, seeded, *extra)
    assert errors_of(findings), f"no pass caught {seeded}"
