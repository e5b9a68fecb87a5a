"""ccsa invariant-linter tests: framework mechanics (suppressions,
baseline, CLI), per-rule true-positive + suppressed fixtures, and the
repo self-check (the tree must lint clean with an empty baseline —
ISSUE 9's acceptance bar)."""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from cruise_control_tpu.lint import (  # noqa: E402
    FileContext, all_rules, load_baseline, run_lint, write_baseline,
)
from cruise_control_tpu.lint.core import (  # noqa: E402
    DEFAULT_PATHS, Finding, fingerprint,
)

FIXTURES = ROOT / "tests" / "fixtures" / "ccsa"


def ctx_for(path: pathlib.Path, rel: str | None = None) -> FileContext:
    """FileContext with an optionally SPOOFED repo-relative path, so
    path-scoped rules (CCSA001 pump modules, CCSA004 deterministic
    modules) can be exercised from fixture files."""
    return FileContext(path, rel or path.name, path.read_text())


def findings_of(rule_id: str, ctx: FileContext) -> tuple[list, list]:
    """(active, suppressed) findings of one rule on one context."""
    rule = all_rules()[rule_id]
    active, suppressed = [], []
    for f in rule.check_file(ctx):
        reason = ctx.suppression_for(f.line, f.rule)
        (suppressed if reason else active).append(f)
    return active, suppressed


# ---------------------------------------------------------------------------
# Per-rule fixtures: ≥1 true positive and ≥1 suppressed case each.

def test_ccsa001_pump_host_sync_fixture():
    ctx = ctx_for(FIXTURES / "bad_host_sync.py",
                  "cruise_control_tpu/analyzer/chain.py")
    active, suppressed = findings_of("CCSA001", ctx)
    assert len(active) == 2           # float(applied) + np.asarray(ring)
    assert len(suppressed) == 1       # the annotated int(rounds)
    assert all("pump region" in f.message for f in active)


def test_ccsa001_outside_pump_modules_is_silent():
    ctx = ctx_for(FIXTURES / "bad_host_sync.py")  # fixture's own path
    active, suppressed = findings_of("CCSA001", ctx)
    assert not active and not suppressed


def test_ccsa002_donation_fixture():
    ctx = ctx_for(FIXTURES / "bad_donation.py")
    active, suppressed = findings_of("CCSA002", ctx)
    # decorator-form `rest` + the vmap-call-form `rest` (the megabatch
    # kernel shape: donation resolved THROUGH jax.vmap to the batched
    # body's parameters).
    assert len(active) == 2
    assert all("rest" in f.message for f in active)
    assert len(suppressed) == 1       # the scratch-buffer donation


def test_ccsa001_megabatch_pump_fixture():
    """Round-14 scoping: the fleet megabatch module is a pump file, its
    pump + enqueue closures are regions, suppressions still apply."""
    ctx = ctx_for(FIXTURES / "bad_megabatch_pump.py",
                  "cruise_control_tpu/fleet/megabatch.py")
    active, suppressed = findings_of("CCSA001", ctx)
    # np.asarray(rounds) + int(active.sum()) in the pump, float(budget)
    # in the module-level enqueue region.
    assert len(active) == 3
    assert len(suppressed) == 1
    # Outside the pump modules the same file is silent.
    plain = ctx_for(FIXTURES / "bad_megabatch_pump.py")
    a2, s2 = findings_of("CCSA001", plain)
    assert not a2 and not s2


def test_ccsa002_repo_donation_sites_resolve():
    """The real donated kernels (decorator form in analyzer/chain —
    including the round-14 batched megabatch twins — and the jit-call
    form wrapping shard_map bodies in parallel/chain_sharded) must
    verify CLEAN — donation exactly {assignment, leader_slot}, and the
    bounded pass's own carry ``resume``."""
    for rel in ("cruise_control_tpu/analyzer/chain.py",
                "cruise_control_tpu/analyzer/direct.py",
                "cruise_control_tpu/parallel/chain_sharded.py",
                "cruise_control_tpu/fleet/megabatch.py"):
        ctx = ctx_for(ROOT / rel, rel)
        active, suppressed = findings_of("CCSA002", ctx)
        assert not active, [f.message for f in active]
        assert not suppressed


def test_ccsa001_direct_kernel_fixture():
    """Round-17 scoping: analyzer/direct.py is a pump file — its donated
    transport kernels are regions (structural donate_argnums detection),
    host syncs inside them fire, suppressions apply, and the file is
    silent under a non-pump path."""
    ctx = ctx_for(FIXTURES / "bad_direct.py",
                  "cruise_control_tpu/analyzer/direct.py")
    active, suppressed = findings_of("CCSA001", ctx)
    assert len(active) == 2           # float(plan) + plan.tolist()
    assert len(suppressed) == 1       # the annotated int(plan)
    plain = ctx_for(FIXTURES / "bad_direct.py")
    a2, s2 = findings_of("CCSA001", plain)
    assert not a2 and not s2


def test_ccsa002_direct_fixture():
    """Decorator form (round 17) AND the round-21 mesh traced-driver
    form: donation through ``jax.jit(shard_map(body, ...))`` resolves
    the argnums to the body's same-position parameters, so donating the
    topology `rest` fires in both shapes and the strip_mutable pair
    stays clean."""
    ctx = ctx_for(FIXTURES / "bad_direct.py")
    active, _suppressed = findings_of("CCSA002", ctx)
    assert len(active) == 2
    assert all("rest" in f.message for f in active)


def test_ccsa004_direct_rounding_fixture():
    """Round-21 scoping: analyzer/direct.py is a deterministic module —
    the rounding PRNG must be crc32-seeded derivation only, so a global
    `random` draw fires under the spoofed path, the documented
    suppression holds, the crc32 helper stays clean, and the fixture is
    silent under its own (non-deterministic-module) path."""
    spoofed = ctx_for(FIXTURES / "bad_direct.py",
                      "cruise_control_tpu/analyzer/direct.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    assert len(active) == 1           # random.random() in rounding_seed_bad
    assert "random.random" in active[0].message
    assert len(suppressed) == 1       # the annotated random.uniform
    plain = ctx_for(FIXTURES / "bad_direct.py")
    a2, s2 = findings_of("CCSA004", plain)
    assert not a2 and not s2


def test_ccsa004_real_direct_module_contract():
    """The real kernel module carries the replan determinism contract:
    no active CCSA004 findings, and exactly the two documented
    flight-telemetry clock suppressions in the host driver."""
    rel = "cruise_control_tpu/analyzer/direct.py"
    ctx = ctx_for(ROOT / rel, rel)
    active, suppressed = findings_of("CCSA004", ctx)
    assert not active, [f.message for f in active]
    assert len(suppressed) == 2


def test_ccsa001_real_direct_module_clean():
    """The real direct.py must lint clean: its donated kernels are pure
    traced code, and the synchronous readback lives in run_direct_pass
    (a plain host driver, not a region)."""
    rel = "cruise_control_tpu/analyzer/direct.py"
    ctx = ctx_for(ROOT / rel, rel)
    active, suppressed = findings_of("CCSA001", ctx)
    assert not active, [f.message for f in active]
    assert not suppressed


def test_ccsa003_trace_mutation_fixture():
    ctx = ctx_for(FIXTURES / "bad_trace_mutation.py")
    active, suppressed = findings_of("CCSA003", ctx)
    assert len(active) == 2           # while_loop append + scan subscript
    assert len(suppressed) == 1
    assert all("trace time" in f.message for f in active)


def test_ccsa004_determinism_fixture():
    spoofed = ctx_for(FIXTURES / "bad_determinism.py",
                      "cruise_control_tpu/testing/simulator.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    # hash(topic) + time.time(); the injected-clock default and __hash__
    # stay clean; hash(parts) is suppressed.
    assert len(active) == 2
    assert len(suppressed) == 1
    kinds = {f.message.split("`")[1] for f in active}
    assert kinds == {"hash()", "time.time"} or len(kinds) == 2


def test_ccsa004_covers_futures_modules():
    """The round-15 futures engine sits under the same byte-identical
    determinism contract as the twin: wall-clock and global-random
    calls are findings under the futures paths, the injected-clock
    reference and the documented observability suppression stay legal —
    and the REAL modules verify clean."""
    spoofed = ctx_for(FIXTURES / "bad_futures_generator.py",
                      "cruise_control_tpu/futures/generator.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    assert len(active) == 2           # time.time() + random.random()
    assert len(suppressed) == 1       # the documented perf_counter probe
    for rel in ("cruise_control_tpu/futures/generator.py",
                "cruise_control_tpu/futures/evaluator.py"):
        ctx = ctx_for(ROOT / rel, rel)
        real_active, _sup = findings_of("CCSA004", ctx)
        assert not real_active, [f.message for f in real_active]


def test_ccsa_covers_heal_ledger_module():
    """The round-16 heal ledger is a deterministic module (CCSA004: its
    phase stamps come from the injectable clock seam) whose chain ring
    must mutate under the lock (CCSA007) — the fixture exercises both
    under the spoofed ledger path, and the REAL module verifies clean."""
    spoofed = ctx_for(FIXTURES / "bad_heal_ledger.py",
                      "cruise_control_tpu/utils/heal_ledger.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    assert len(active) == 1           # inline time.time()
    assert len(suppressed) == 1       # documented perf_counter probe
    assert "time.time" in active[0].message
    lock_active, lock_suppressed = findings_of("CCSA007", spoofed)
    assert len(lock_active) == 1      # unlocked _CHAINS.append
    assert len(lock_suppressed) == 1  # documented single-writer append
    assert "_CHAINS" in lock_active[0].message
    rel = "cruise_control_tpu/utils/heal_ledger.py"
    real = ctx_for(ROOT / rel, rel)
    for rule in ("CCSA004", "CCSA007"):
        real_active, _sup = findings_of(rule, real)
        assert not real_active, [f.message for f in real_active]


def test_ccsa_covers_warmstart_module():
    """The round-18 warmstart module is a deterministic module (CCSA004:
    seed validity/fallback are pure functions of model state; the
    prewarm manager's duration rides the injectable monotonic seam) and
    its module-level prewarm-manager registry must mutate under
    _REGISTRY_LOCK (CCSA007) — fixture true-positive + suppressed pairs
    under the spoofed path, and the REAL module verifies clean."""
    spoofed = ctx_for(FIXTURES / "bad_warmstart.py",
                      "cruise_control_tpu/warmstart.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    assert len(active) == 1           # inline time.monotonic()
    assert len(suppressed) == 1       # documented perf_counter sweep
    assert "time.monotonic" in active[0].message
    lock_active, lock_suppressed = findings_of("CCSA007", spoofed)
    assert len(lock_active) == 1      # unlocked _MANAGERS write
    assert len(lock_suppressed) == 1  # documented single-writer write
    assert "_MANAGERS" in lock_active[0].message
    rel = "cruise_control_tpu/warmstart.py"
    real = ctx_for(ROOT / rel, rel)
    for rule in ("CCSA004", "CCSA007"):
        real_active, _sup = findings_of(rule, real)
        assert not real_active, [f.message for f in real_active]


def test_ccsa_covers_forecast_modules():
    """The round-19 forecast subsystem feeds SOLVER INPUTS and anomaly
    decisions, so it sits under CCSA004's deterministic contract: wall
    clock and global randomness are findings under the forecast paths,
    the injected-clock reference and the documented observability
    suppression stay legal — and the REAL modules verify clean."""
    spoofed = ctx_for(FIXTURES / "bad_forecast.py",
                      "cruise_control_tpu/forecast/forecaster.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    assert len(active) == 2           # time.time() + random.random()
    assert len(suppressed) == 1       # the documented perf_counter probe
    assert any("time.time" in f.message for f in active)
    assert any("random.random" in f.message for f in active)
    for rel in ("cruise_control_tpu/forecast/forecaster.py",
                "cruise_control_tpu/forecast/engine.py",
                "cruise_control_tpu/detector/predictive.py"):
        ctx = ctx_for(ROOT / rel, rel)
        real_active, _sup = findings_of("CCSA004", ctx)
        assert not real_active, [f.message for f in real_active]


def test_ccsa_covers_serving_modules():
    """The round-20 serving front door sits under CCSA004's deterministic
    contract: the loadgen schedule is a pure function of the seed (its
    digest is pinned in bench_baseline.json) and the engine/cache/
    admission layers time themselves through injected ``monotonic``
    seams only — wall clock and global randomness are findings under the
    serving paths, the injected-seam reference and the documented
    observability suppression stay legal, and the REAL modules verify
    clean."""
    spoofed = ctx_for(FIXTURES / "bad_serving_loadgen.py",
                      "cruise_control_tpu/serving/loadgen.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    assert len(active) == 2           # time.time() + random.random()
    assert len(suppressed) == 1       # the documented perf_counter probe
    assert any("time.time" in f.message for f in active)
    assert any("random.random" in f.message for f in active)
    for rel in ("cruise_control_tpu/serving/tasks.py",
                "cruise_control_tpu/serving/cache.py",
                "cruise_control_tpu/serving/admission.py",
                "cruise_control_tpu/serving/loadgen.py"):
        ctx = ctx_for(ROOT / rel, rel)
        real_active, _sup = findings_of("CCSA004", ctx)
        assert not real_active, [f.message for f in real_active]


def test_ccsa_covers_redteam_modules():
    """The round-22 red-team miner sits under CCSA004's deterministic
    contract: the whole search — sampling, mutation, tie-breaks,
    frontier order — is crc32-derived from the sweep seed (the committed
    frontier JSON is byte-identical per seed) and the wall budget rides
    the caller-injected ``clock`` callable only. Wall clock and global
    randomness are findings under the redteam paths, the injected-clock
    reference and the documented observability suppression stay legal,
    and the REAL modules verify clean."""
    spoofed = ctx_for(FIXTURES / "bad_redteam.py",
                      "cruise_control_tpu/redteam/miner.py")
    active, suppressed = findings_of("CCSA004", spoofed)
    assert len(active) == 2           # time.time() + random.random()
    assert len(suppressed) == 1       # the documented perf_counter probe
    assert any("time.time" in f.message for f in active)
    assert any("random.random" in f.message for f in active)
    for rel in ("cruise_control_tpu/redteam/miner.py",
                "cruise_control_tpu/redteam/frontier.py",
                "cruise_control_tpu/redteam/blindspot.py"):
        ctx = ctx_for(ROOT / rel, rel)
        real_active, _sup = findings_of("CCSA004", ctx)
        assert not real_active, [f.message for f in real_active]


def test_ccsa004_hash_ban_is_repo_wide_but_clock_is_not():
    plain = ctx_for(FIXTURES / "bad_determinism.py")
    active, suppressed = findings_of("CCSA004", plain)
    assert len(active) == 1           # hash() still flagged
    assert "hash()" in active[0].message
    assert len(suppressed) == 1


def test_ccsa005_undeclared_key_fixture():
    ctx = ctx_for(FIXTURES / "bad_config_key.py")
    active, suppressed = findings_of("CCSA005", ctx)
    assert {f.message.split("`")[1] for f in active} \
        == {"totally.unknown.key", "another.unknown.key"}
    assert len(suppressed) == 1


def test_ccsa007_lock_discipline_fixture():
    ctx = ctx_for(FIXTURES / "bad_lock.py")
    active, suppressed = findings_of("CCSA007", ctx)
    assert len(active) == 2           # put() and drop()
    assert len(suppressed) == 1       # mark()
    assert all("_CACHE" in f.message for f in active)


def test_ccsa006_sensor_drift_detected(tmp_path):
    """A registered-but-undocumented sensor fails CCSA006 in a synthetic
    mini-repo (the real tree's docs are verified by the self-check)."""
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "gen_docs.py").write_text(
        (ROOT / "tools" / "gen_docs.py").read_text())
    pkg = tmp_path / "cruise_control_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'SENSORS.count("fixture_only_sensor")\n')
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "SENSORS.md").write_text("# Sensors\n")
    rule = all_rules()["CCSA006"]
    findings = rule.check_tree(tmp_path, [])
    assert any("fixture_only_sensor" in f.message for f in findings)


def test_ccsa005_doc_staleness_detected(tmp_path):
    """A CONFIGURATION.md that does not match the live registry fails
    the CCSA005 tree check."""
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "gen_docs.py").write_text(
        (ROOT / "tools" / "gen_docs.py").read_text())
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "CONFIGURATION.md").write_text("# stale\n")
    rule = all_rules()["CCSA005"]
    findings = rule.check_tree(tmp_path, [])
    assert findings and "stale" in findings[0].message


# ---------------------------------------------------------------------------
# Framework mechanics.

def test_suppression_requires_reason(tmp_path):
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent("""\
        def unstable(topic):
            return hash(topic)  # ccsa: ok[CCSA004]
    """))
    result = run_lint([f], root=tmp_path, rules=["CCSA004"])
    assert result.failed
    assert any(x.rule == "CCSA000" and "no reason" in x.message
               for x in result.errors)


def test_suppression_comment_block_above(tmp_path):
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent("""\
        def unstable(topic):
            # ccsa: ok[CCSA004] memo key that never leaves
            # this process (wrapped reason line)
            return hash(topic)
    """))
    result = run_lint([f], root=tmp_path, rules=["CCSA004"])
    assert not result.failed
    assert len(result.suppressed) == 1
    assert "memo key" in result.suppressed[0].reason


def test_multi_rule_suppression(tmp_path):
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent("""\
        _REG: dict = {}


        def put(topic):
            # ccsa: ok[CCSA004,CCSA007] fixture: one comment, two rules
            _REG[hash(topic)] = topic
    """))
    result = run_lint([f], root=tmp_path, rules=["CCSA004", "CCSA007"])
    assert not result.failed
    assert len(result.suppressed) == 2


def test_nested_rebinding_does_not_shadow_outer_scope(tmp_path):
    """A nested closure rebinding a module container's name must not
    hide the OUTER function's unlocked mutation (CCSA007), and a nested
    def rebinding a free name must not hide a lax-body mutation
    (CCSA003) — Python scoping: inner bindings don't leak out."""
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent("""\
        import jax

        _CACHE: dict = {}


        def outer(k, v):
            def helper():
                _CACHE = {}
                return _CACHE
            _CACHE[k] = v
            return helper


        def loop(x):
            log = []

            def body(c):
                def rebind():
                    log = []
                    return log
                log.append(c)
                return c + 1, rebind

            def cond(c):
                return c < 3

            return jax.lax.while_loop(cond, body, x), log
    """))
    result = run_lint([f], root=tmp_path, rules=["CCSA007", "CCSA003"])
    assert {(x.rule, "log.append" in x.message or "_CACHE" in x.message)
            for x in result.new} == {("CCSA007", True), ("CCSA003", True)}


def test_nested_region_violation_reported_once():
    """A host-sync inside an `enqueue` closure nested in
    `run_bounded_pass` is one violation, not two (nested regions are
    walked in their own right only)."""
    src = textwrap.dedent("""\
        def run_bounded_pass(st, cap):
            def enqueue(st, budget):
                return int(budget_future)
            return enqueue(st, cap)
    """)
    import cruise_control_tpu.lint.core as core
    ctx = core.FileContext(pathlib.Path("x.py"),
                           "cruise_control_tpu/analyzer/chain.py", src)
    findings = all_rules()["CCSA001"].check_file(ctx)
    assert len(findings) == 1
    assert "enqueue" in findings[0].message


def test_nonexistent_path_fails_the_gate(tmp_path):
    """A typo'd path must not pass vacuously with 0 files scanned."""
    result = run_lint([tmp_path / "no_such_dir"], root=tmp_path,
                      rules=["CCSA004"])
    assert result.failed
    assert any("matched no Python files" in x.message
               for x in result.errors)
    proc = _run_cli("no/such/path.py")
    assert proc.returncode == 1


def test_scoped_write_baseline_keeps_out_of_scope_fingerprints(tmp_path):
    """--write-baseline with explicit paths unions the prior baseline:
    out-of-scope acceptances survive a scoped rewrite."""
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("def f(t):\n    return hash(t)\n")
    b.write_text("def g(t):\n    return hash(t + 'x')\n")
    base = tmp_path / "base.json"
    proc = _run_cli(str(a), str(b), "--rules", "CCSA004",
                    "--root", str(tmp_path),
                    "--baseline", str(base), "--write-baseline")
    assert proc.returncode == 0, proc.stderr
    full = load_baseline(base)
    assert len(full) == 2
    proc = _run_cli(str(a), "--rules", "CCSA004", "--root", str(tmp_path),
                    "--baseline", str(base), "--write-baseline")
    assert proc.returncode == 0, proc.stderr
    assert load_baseline(base) == full      # b.py's acceptance survived


def test_broken_pipe_preserves_failing_verdict():
    """`ccsa | head -c 1` on a failing tree must still exit non-zero."""
    proc = subprocess.run(
        f"{sys.executable} -m tools.ccsa tests/fixtures/ccsa "
        "--rules CCSA007 | head -c 1; exit ${PIPESTATUS[0]}",
        shell=True, executable="/bin/bash", cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stderr


def test_ccsa007_sees_through_module_level_blocks(tmp_path):
    """Functions (and container declarations) nested under module-level
    if/try blocks are scanned — tree.body-only walking would fail open
    on e.g. the `try: shard_map = ...` pattern in parallel/mesh.py."""
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent("""\
        _CACHE: dict = {}

        if True:
            try:
                _AUX: list = []
            except ImportError:
                pass

            def put(k, v):
                _CACHE[k] = v

            def aux(v):
                _AUX.append(v)
    """))
    result = run_lint([f], root=tmp_path, rules=["CCSA007"])
    assert {m.message.split("`")[1] for m in result.new} \
        == {"_CACHE", "_AUX"}


def test_ccsa007_lock_does_not_cover_nested_closure(tmp_path):
    """A closure DEFINED inside `with lock:` executes later, unlocked —
    the guard must not carry into the nested scope."""
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent("""\
        import threading

        _CACHE: dict = {}
        _LOCK = threading.Lock()


        def outer():
            with _LOCK:
                _CACHE["init"] = 1          # genuinely guarded

                def cb(k, v):
                    _CACHE[k] = v           # runs after release
            return cb
    """))
    result = run_lint([f], root=tmp_path, rules=["CCSA007"])
    assert len(result.new) == 1
    assert result.new[0].line == 12


def test_rules_filter_tolerates_spaces():
    proc = _run_cli("tests/fixtures/ccsa/bad_lock.py",
                    "--rules", "CCSA004, CCSA007", "--format", "json")
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert {f["rule"] for f in payload["findings"]} == {"CCSA007"}


def test_suppression_marker_in_string_is_inert(tmp_path):
    """A `# ccsa: ok[...]` inside a string literal or docstring is data,
    not a comment: it must neither suppress a finding on its line nor
    appear in the suppression registry."""
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent('''\
        def unstable(t):
            """Docs may QUOTE the syntax: # ccsa: ok[CCSA004] example."""
            return hash(t + " # ccsa: ok[CCSA004] smuggled reason")
    '''))
    result = run_lint([f], root=tmp_path, rules=["CCSA004"])
    assert result.failed and len(result.new) == 1
    assert not result.suppressed
    ctx = ctx_for(f, "frag.py")
    assert not ctx.suppressions


def test_stacked_single_rule_suppressions(tmp_path):
    """Two adjacent single-rule markers above one line both apply — a
    non-matching marker must not end the upward walk."""
    f = tmp_path / "frag.py"
    f.write_text(textwrap.dedent("""\
        _REG: dict = {}


        def put(topic):
            # ccsa: ok[CCSA004] reason for the hash
            # ccsa: ok[CCSA007] reason for the unlocked write
            _REG[hash(topic)] = topic
    """))
    result = run_lint([f], root=tmp_path, rules=["CCSA004", "CCSA007"])
    assert not result.failed, [x.message for x in result.new]
    assert len(result.suppressed) == 2


def test_write_baseline_keeps_prior_acceptances(tmp_path):
    """--write-baseline must union still-present baselined findings with
    the new ones — rewriting can never un-accept a prior acceptance."""
    f = tmp_path / "frag.py"
    f.write_text("def a(t):\n    return hash(t)\n")
    base = tmp_path / "base.json"
    proc = _run_cli(str(f), "--rules", "CCSA004", "--root", str(tmp_path),
                    "--baseline", str(base), "--write-baseline")
    assert proc.returncode == 0, proc.stderr
    first = load_baseline(base)
    assert len(first) == 1
    # A second finding appears; rewriting keeps the first fingerprint.
    f.write_text("def a(t):\n    return hash(t)\n"
                 "def b(t):\n    return hash(t + 'x')\n")
    proc = _run_cli(str(f), "--rules", "CCSA004", "--root", str(tmp_path),
                    "--baseline", str(base), "--write-baseline")
    assert proc.returncode == 0, proc.stderr
    assert first <= load_baseline(base)
    proc = _run_cli(str(f), "--rules", "CCSA004", "--root", str(tmp_path),
                    "--baseline", str(base))
    assert proc.returncode == 0, proc.stdout


def test_baseline_accepts_then_clears(tmp_path):
    f = tmp_path / "frag.py"
    f.write_text("def unstable(topic):\n    return hash(topic)\n")
    result = run_lint([f], root=tmp_path, rules=["CCSA004"])
    assert result.failed and len(result.new) == 1
    ctx = ctx_for(f, "frag.py")
    finding = result.new[0]
    baseline_path = tmp_path / "base.json"
    write_baseline(baseline_path,
                   [fingerprint(finding, ctx.line_text(finding.line))])
    result2 = run_lint([f], root=tmp_path, rules=["CCSA004"],
                       baseline=load_baseline(baseline_path))
    assert not result2.failed
    assert len(result2.baselined) == 1


def test_fingerprint_survives_line_moves():
    f = Finding("CCSA004", "a.py", 10, "m")
    moved = Finding("CCSA004", "a.py", 99, "m")
    assert fingerprint(f, "return hash(x)") \
        == fingerprint(moved, "  return   hash(x)")


def test_unknown_rule_filter_fails():
    result = run_lint([FIXTURES / "bad_lock.py"], root=ROOT,
                      rules=["CCSA999"])
    assert result.failed
    assert any("unknown rule" in f.message for f in result.errors)


def test_syntax_error_is_meta_finding(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def nope(:\n")
    result = run_lint([f], root=tmp_path, rules=["CCSA007"])
    assert result.failed
    assert any(x.rule == "CCSA000" and "syntax error" in x.message
               for x in result.errors)


# ---------------------------------------------------------------------------
# CLI + gate behavior.

def _run_cli(*args: str):
    return subprocess.run(
        [sys.executable, "-m", "tools.ccsa", *args],
        cwd=ROOT, capture_output=True, text=True, timeout=300)


def test_cli_red_on_seeded_violations():
    """The CI red-gate contract: linting the fixture corpus with the
    path-independent rules MUST exit non-zero."""
    proc = _run_cli("tests/fixtures/ccsa",
                    "--rules", "CCSA002,CCSA003,CCSA004,CCSA007",
                    "--format", "json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    flagged = {f["rule"] for f in payload["findings"]}
    assert {"CCSA002", "CCSA003", "CCSA004", "CCSA007"} <= flagged


def test_cli_self_check_repo_tree_is_clean():
    """`python -m tools.ccsa` on the default tree exits 0 with the
    committed (EMPTY) baseline — the acceptance criterion."""
    proc = _run_cli("--format", "json")
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    payload = json.loads(proc.stdout)
    assert not payload["failed"]
    assert payload["files_scanned"] > 100
    # Bias check: the committed baseline is empty — nothing grandfathered.
    assert not any(f["baselined"] for f in payload["findings"])
    assert load_baseline(ROOT / ".ccsa-baseline.json") == set()


def test_cli_list_rules_names_all_seven():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    listed = {line.split()[0] for line in proc.stdout.splitlines() if line}
    assert {"CCSA001", "CCSA002", "CCSA003", "CCSA004", "CCSA005",
            "CCSA006", "CCSA007"} <= listed


def test_cli_list_suppressions_reports_tolerances():
    proc = _run_cli("--list-suppressions")
    assert proc.returncode == 0
    # The PR 5 persistent-controller tolerance is machine-readable now.
    assert "optimizer.py" in proc.stdout
    assert "CCSA007" in proc.stdout


def test_default_scan_skips_fixture_corpus():
    result = run_lint(DEFAULT_PATHS, root=ROOT,
                      rules=["CCSA004", "CCSA007"])
    assert not any(f.path.startswith("tests/fixtures/ccsa")
                   for f in result.new + result.suppressed)


@pytest.mark.parametrize("rel", [
    "cruise_control_tpu/testing/simulator.py",
    "cruise_control_tpu/testing/chaos.py",
    "cruise_control_tpu/utils/flight_recorder.py",
    "cruise_control_tpu/forecast/forecaster.py",
    "cruise_control_tpu/forecast/engine.py",
    "cruise_control_tpu/detector/predictive.py",
])
def test_deterministic_modules_lint_clean(rel):
    """The twin/chaos/flight-recorder modules carry no ACTIVE wall-clock
    or hash findings — every remaining site is an annotated tolerance."""
    ctx = ctx_for(ROOT / rel, rel)
    active, _suppressed = findings_of("CCSA004", ctx)
    assert not active, [f"{f.line}: {f.message}" for f in active]
