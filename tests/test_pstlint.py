"""pstlint: the analyzer's own test suite.

Three rings:

1. Fixture ring — every check fires on its known-bad snippet and stays
   quiet on its known-good one (tests/fixtures/pstlint/).
2. Live-tree ring — the real tree is lint-clean, every suppression
   carries a reason, and the acceptance mutations (delete a bucket
   family from precompile.py's enumeration / add an unregistered jit
   site) flip the recompile-risk check to failing.
3. CLI ring — exit codes and the JSON report format.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "pstlint"

sys.path.insert(0, str(REPO))

from production_stack_tpu.analysis.pstlint import run_checks  # noqa: E402

pytestmark = pytest.mark.fast


def lint(path: pathlib.Path, check: str = None, unused: bool = False):
    checks = [check] if check else None
    findings = run_checks(
        [str(path)], checks=checks, root=path, report_unused=unused
    )
    return [f for f in findings if not f.suppressed]


def lint_with_root(path: pathlib.Path, root: pathlib.Path, check: str):
    findings = run_checks([str(path)], checks=[check], root=root)
    return [f for f in findings if not f.suppressed]


# ---------------------------------------------------------------------------
# 1. Fixture ring
# ---------------------------------------------------------------------------


class TestAsyncBlocking:
    def test_fires_on_bad(self):
        active = lint(FIXTURES / "async_blocking", "async-blocking")
        msgs = [f.message for f in active]
        assert len(active) >= 6, msgs
        assert all(f.path.endswith("bad.py") for f in active)
        joined = "\n".join(msgs)
        for needle in ("time.sleep", "requests", "urllib", "subprocess",
                       "open()"):
            assert needle in joined

    def test_clean_on_good(self):
        active = lint(FIXTURES / "async_blocking", "async-blocking")
        assert not [f for f in active if f.path.endswith("good.py")]

    def test_sync_sleep_rule_scoped_to_loop_packages(self):
        active = lint(FIXTURES / "async_blocking", "async-blocking")
        sync_hits = [f for f in active if f.line == 20]  # sync_helper()
        assert len(sync_hits) == 1


class TestHopContract:
    def test_fires_on_bad(self):
        active = lint(FIXTURES / "hop_contract", "hop-contract")
        assert all(f.path.endswith("bad.py") for f in active)
        hops = [f for f in active if "outbound" in f.message]
        errors = [f for f in active if "error response" in f.message]
        assert len(hops) == 2
        assert len(errors) == 1

    def test_clean_on_good(self):
        active = lint(FIXTURES / "hop_contract", "hop-contract")
        assert not [f for f in active if f.path.endswith("good.py")]


class TestRecompileRisk:
    def test_clean_on_good(self):
        assert lint(FIXTURES / "recompile_risk" / "good",
                    "recompile-risk") == []

    def test_missing_family_fires(self):
        active = lint(FIXTURES / "recompile_risk" / "bad_missing_family",
                      "recompile-risk")
        assert any("'prefill'" in f.message for f in active), \
            [f.message for f in active]

    def test_unregistered_jit_and_key_fire(self):
        active = lint(FIXTURES / "recompile_risk" / "bad_unregistered_jit",
                      "recompile-risk")
        assert any("jit-family" in f.message for f in active)
        assert any("shape key" in f.message for f in active)


class TestMetricRegistry:
    def test_clean_on_good(self):
        assert lint(FIXTURES / "metric_registry" / "good",
                    "metric-registry") == []

    def test_bad_fires_all_three_ways(self):
        active = lint(FIXTURES / "metric_registry" / "bad",
                      "metric-registry")
        joined = "\n".join(f.message for f in active)
        assert "pst_fixture_undeclared" in joined  # code -> registry
        assert "pst_fixture_ghost" in joined       # registry -> code
        assert "constructed as a counter but declared as a gauge" in joined


class TestLockDiscipline:
    def test_fires_on_bad(self):
        active = lint(FIXTURES / "lock_discipline", "lock-discipline")
        assert all(f.path.endswith("bad.py") for f in active)
        joined = "\n".join(f.message for f in active)
        assert "outside 'with self._lock'" in joined
        assert "second writer surface" in joined
        # two unlocked table writes + rogue_writer + a foreign __init__
        # clearing another object's state + a module-level write
        assert len(active) == 5

    def test_clean_on_good(self):
        active = lint(FIXTURES / "lock_discipline", "lock-discipline")
        assert not [f for f in active if f.path.endswith("good.py")]

    def test_backend_discipline_fires_on_undeclared_mutable_state(self):
        active = lint(FIXTURES / "lock_discipline_backend", "lock-discipline")
        assert all(f.path.endswith("bad.py") for f in active)
        joined = "\n".join(f.message for f in active)
        # The three undeclared containers, each named in a finding.
        for attr in ("'table'", "'items'", "'pending'"):
            assert attr in joined, joined
        assert len(active) == 3, [f.message for f in active]
        assert "StateBackend" in joined

    def test_backend_discipline_accepts_all_owner_kinds(self):
        active = lint(FIXTURES / "lock_discipline_backend", "lock-discipline")
        # good.py declares lock:, task: and the new backend: kind — all
        # accepted, and backend-owned state gets no same-file mutation
        # checking (the backend owns the merge semantics).
        assert not [f for f in active if f.path.endswith("good.py")]

    def test_backend_discipline_scoped_to_routing_state_surfaces(self):
        # The same undeclared-state pattern OUTSIDE the scope (plain
        # lock_discipline fixture dir, no router/resilience path) is quiet:
        # the backend rule must not tax unrelated code.
        active = lint(FIXTURES / "lock_discipline", "lock-discipline")
        assert not [f for f in active if "declares no writer" in f.message]


class TestTaskLifecycle:
    def test_fires_on_bad(self):
        active = lint(FIXTURES / "task_lifecycle", "task-lifecycle")
        assert all(f.path.endswith("bad.py") for f in active)
        joined = "\n".join(f.message for f in active)
        assert "fire-and-forget" in joined
        assert "never consumed again" in joined
        assert "no cancellation path" in joined
        assert "never stored" in joined
        # unannotated attr store + bare + unread local + no-cancel + mismatch
        assert len(active) == 5, [f.message for f in active]

    def test_clean_on_good(self):
        active = lint(FIXTURES / "task_lifecycle", "task-lifecycle")
        assert not [f for f in active if f.path.endswith("good.py")]

    def test_good_suppression_carries_reason(self):
        findings = run_checks(
            [str(FIXTURES / "task_lifecycle" / "good.py")],
            checks=["task-lifecycle"],
            root=FIXTURES / "task_lifecycle",
        )
        suppressed = [f for f in findings if f.suppressed]
        assert len(suppressed) == 1
        assert "reasons" in (suppressed[0].reason or "")


class TestLockOrder:
    def test_fires_on_bad(self):
        active = lint(FIXTURES / "lock_order", "lock-order")
        assert all(f.path.endswith("bad.py") for f in active)
        joined = "\n".join(f.message for f in active)
        assert "asyncio lock" in joined          # await under async lock
        assert "SYNC lock" in joined             # await under threading lock
        assert "lock-acquisition-order cycle" in joined
        assert "lock_a" in joined and "lock_b" in joined
        assert len(active) == 3, [f.message for f in active]

    def test_clean_on_good(self):
        active = lint(FIXTURES / "lock_order", "lock-order")
        assert not [f for f in active if f.path.endswith("good.py")]

    def test_await_in_context_expr_runs_before_acquisition(self, tmp_path):
        """An await inside the with-item's own context expression executes
        BEFORE the lock is acquired — it must not be flagged (review
        finding on the first implementation)."""
        mod = tmp_path / "m.py"
        mod.write_text(
            "import asyncio\n"
            "async def budget():\n"
            "    return 1\n"
            "class T:\n"
            "    def __init__(self):\n"
            "        # pstlint: owned-by=lock:_lock\n"
            "        self.rows = {}\n"
            "        self._lock = asyncio.Lock()\n"
            "    async def m(self):\n"
            "        async with self._lock.acquire_timeout(await budget()):\n"
            "            self.rows[1] = 1\n"
        )
        active = lint(tmp_path, "lock-order")
        assert active == [], [f.message for f in active]


class TestSimpleYaml:
    """The stdlib YAML-subset reader config-contract trusts for
    helm/values.yaml: cross-validated against PyYAML on the real file,
    and loud outside its subset."""

    def test_matches_pyyaml_on_real_values_yaml(self):
        import yaml

        from production_stack_tpu.analysis import simpleyaml

        text = (REPO / "helm" / "values.yaml").read_text()
        assert simpleyaml.parse(text) == yaml.safe_load(text)

    def test_scalars_and_flow(self):
        from production_stack_tpu.analysis import simpleyaml

        doc = simpleyaml.parse(
            "a: 1\n"
            "b: 2.5\n"
            "c: true\n"
            "d: null\n"
            "e: \"quoted: colon\"\n"
            "f: {x: 1, y: \"z\"}\n"
            "g: []\n"
            "lst:\n"
            "  - name: one\n"
            "    v: 1\n"
            "  - name: two\n"
        )
        assert doc == {
            "a": 1, "b": 2.5, "c": True, "d": None, "e": "quoted: colon",
            "f": {"x": 1, "y": "z"}, "g": [],
            "lst": [{"name": "one", "v": 1}, {"name": "two"}],
        }

    def test_yaml11_booleans_fail_loudly(self):
        from production_stack_tpu.analysis import simpleyaml

        with pytest.raises(simpleyaml.SimpleYamlError):
            simpleyaml.parse("tracing: on\n")
        with pytest.raises(simpleyaml.SimpleYamlError):
            simpleyaml.parse("flag: Yes\n")
        # Quoted forms stay plain strings.
        assert simpleyaml.parse('k: "on"\n') == {"k": "on"}

    def test_unsupported_syntax_fails_loudly(self):
        from production_stack_tpu.analysis import simpleyaml

        with pytest.raises(simpleyaml.SimpleYamlError):
            simpleyaml.parse("a: {unbalanced: 1\n")
        with pytest.raises(simpleyaml.SimpleYamlError):
            simpleyaml.parse("\ta: 1\n")


class TestAppScope:
    def test_fires_on_bad(self):
        active = lint(FIXTURES / "app_scope", "app-scope")
        assert all(f.path.endswith("bad.py") for f in active)
        joined = "\n".join(f.message for f in active)
        for name in ("'_cache'", "'pending_requests'", "'_seen'"):
            assert name in joined, joined
        assert "'global _discovery'" in joined
        assert len(active) == 4, [f.message for f in active]

    def test_clean_on_good_and_scoped_to_router(self):
        # good.py (ContextVar + UPPER constants) is clean, and the same
        # mutable-module-state pattern OUTSIDE router/ (other/mod.py) is
        # deliberately not taxed.
        active = lint(FIXTURES / "app_scope", "app-scope")
        assert not [f for f in active if not f.path.endswith("bad.py")]


class TestConfigContract:
    def test_clean_on_good(self):
        assert lint(FIXTURES / "config_contract" / "good",
                    "config-contract") == []

    def test_bad_fires_every_direction(self):
        active = lint(FIXTURES / "config_contract" / "bad",
                      "config-contract")
        joined = "\n".join(f.message for f in active)
        assert "'--surprise' has no ConfigSpec" in joined  # parser -> registry
        assert "'--ghost' names a flag" in joined          # registry -> parser
        assert "default drift for --rate" in joined        # parser vs values
        assert "absent from helm/values.schema.json" in joined
        assert "cli-only spec '--verbose' IS emitted" in joined
        assert "routerSpec.orphanKnob" in joined           # values -> registry
        assert "routerSpec.ghostOnly" in joined            # schema -> registry
        assert "--mode is not documented" in joined        # docs row
        assert len(active) == 8, [f.message for f in active]

    def test_autoscale_contract_fires_both_directions(self, tmp_path):
        """The TPURuntime spec.autoscale.* knobs are contract-checked
        against their four surfaces (CRD schema, reconciler reads,
        sample CR, docs). Mutating the registry (one ghost knob added,
        one real knob dropped) must fire every direction against the
        REAL repo anchors."""
        analysis = tmp_path / "analysis"
        analysis.mkdir()
        src = (
            REPO / "production_stack_tpu/analysis/config_registry.py"
        ).read_text()
        src += (
            "\nAUTOSCALE_KEYS = tuple(\n"
            "    s for s in AUTOSCALE_KEYS if s.key != 'scaleToZero'\n"
            ") + (AutoscaleKeySpec('ghostKnob'),)\n"
        )
        (analysis / "config_registry.py").write_text(src)
        router = tmp_path / "router"
        router.mkdir()
        (router / "parser.py").write_text(
            (REPO / "production_stack_tpu/router/parser.py").read_text()
        )
        active = lint_with_root(tmp_path, REPO, "config-contract")
        msgs = "\n".join(f.message for f in active)
        assert "AutoscaleKeySpec 'ghostKnob' is absent from" in msgs
        assert "'ghostKnob' is never read by" in msgs
        assert "'ghostKnob' is missing from the sample CR" in msgs
        assert "'ghostKnob' is not documented in" in msgs
        assert "CRD autoscale key 'scaleToZero' has no AutoscaleKeySpec" \
            in msgs
        assert "reads spec.autoscale.scaleToZero but no AutoscaleKeySpec" \
            in msgs
        assert len(active) == 6, [f.message for f in active]


class TestSuppressionMachinery:
    def test_reasonless_disable_is_flagged_and_inert(self):
        findings = run_checks(
            [str(FIXTURES / "suppressions")],
            root=FIXTURES / "suppressions",
        )
        active = [f for f in findings if not f.suppressed]
        checks = {f.check for f in active}
        assert "bad-suppression" in checks
        # The reasonless disable must NOT silence the finding it targeted.
        assert "async-blocking" in checks

    def test_unused_suppression_is_flagged(self):
        findings = run_checks(
            [str(FIXTURES / "suppressions")],
            root=FIXTURES / "suppressions",
        )
        unused = [f for f in findings if f.check == "unused-suppression"]
        assert len(unused) == 1
        assert "hop-contract" in unused[0].message


# ---------------------------------------------------------------------------
# 2. Live-tree ring
# ---------------------------------------------------------------------------

LIVE_PATHS = [str(REPO / "production_stack_tpu"), str(REPO / "scripts")]


class TestLiveTree:
    def test_tree_is_lint_clean(self):
        findings = run_checks(LIVE_PATHS, root=REPO)
        active = [f for f in findings if not f.suppressed]
        assert active == [], "\n" + "\n".join(f.format() for f in active)

    def test_every_suppression_carries_a_reason(self):
        findings = run_checks(LIVE_PATHS, root=REPO)
        # bad-suppression findings are unsuppressible; clean tree == all
        # reasons present. Belt and braces: recheck the parsed model.
        from production_stack_tpu.analysis import load_project

        project = load_project(LIVE_PATHS, root=REPO)
        for src in project.files:
            assert not src.bad_directives, (src.rel, src.bad_directives)
            for sup in src.suppressions:
                assert sup.reason.strip(), (src.rel, sup.line)
        suppressed = [f for f in findings if f.suppressed]
        assert suppressed, "expected the documented suppressions to exist"

    def test_known_suppressions_present(self):
        """The issue-mandated suppression: runner.py's device poll."""
        findings = run_checks(LIVE_PATHS, root=REPO)
        polls = [
            f for f in findings
            if f.suppressed and f.check == "async-blocking"
            and f.path.endswith("engine/runner.py")
        ]
        assert len(polls) == 1
        assert "step thread" in polls[0].reason

    @pytest.mark.parametrize(
        "family", ["decode", "decode_burst", "prefill", "spec_verify", "encode"]
    )
    def test_deleting_bucket_family_fails_lint(self, family, tmp_path):
        """Acceptance: deleting any bucket family from precompile.py's
        enumeration makes recompile-risk fail."""
        engine = tmp_path / "engine"
        engine.mkdir()
        pre = (REPO / "production_stack_tpu/engine/precompile.py").read_text()
        assert '"%s"' % family in pre
        pre = pre.replace('"%s"' % family, '"%s_disabled"' % family)
        (engine / "precompile.py").write_text(pre)
        shutil.copy(
            REPO / "production_stack_tpu/engine/runner.py",
            engine / "runner.py",
        )
        active = lint(tmp_path, "recompile-risk")
        assert any(
            "'%s'" % family in f.message for f in active
        ), "deleting %s must fail lint: %s" % (
            family, [f.message for f in active],
        )

    def test_adding_unregistered_jit_site_fails_lint(self, tmp_path):
        engine = tmp_path / "engine"
        engine.mkdir()
        shutil.copy(
            REPO / "production_stack_tpu/engine/precompile.py",
            engine / "precompile.py",
        )
        runner = (REPO / "production_stack_tpu/engine/runner.py").read_text()
        runner += "\n\n_ROGUE_JIT = jax.jit(lambda x: x)\n"
        (engine / "runner.py").write_text(runner)
        active = lint(tmp_path, "recompile-risk")
        assert any("jit-family" in f.message for f in active)

    # -- PR 11 acceptance mutations: each new check flips to failing on a
    #    mutated copy of the live tree -----------------------------------

    def test_deleting_task_owner_annotation_fails_lint(self, tmp_path):
        stats = tmp_path / "router" / "stats"
        stats.mkdir(parents=True)
        src = (
            REPO / "production_stack_tpu/router/stats/engine_stats.py"
        ).read_text()
        assert "# pstlint: task-owner=_task" in src
        src = src.replace("# pstlint: task-owner=_task", "# (annotation gone)")
        (stats / "engine_stats.py").write_text(src)
        active = lint(tmp_path, "task-lifecycle")
        assert any("fire-and-forget" in f.message for f in active), \
            [f.message for f in active]

    def test_await_under_annotated_lock_fails_lint(self, tmp_path):
        routing = tmp_path / "router" / "routing"
        routing.mkdir(parents=True)
        src = (
            REPO / "production_stack_tpu/router/routing/hashtrie.py"
        ).read_text()
        needle = (
            "        async with node.lock:\n"
            "            node.endpoints.add(endpoint)"
        )
        assert needle in src
        src = src.replace(needle, (
            "        async with node.lock:\n"
            "            await asyncio.sleep(0)\n"
            "            node.endpoints.add(endpoint)"
        ))
        (routing / "hashtrie.py").write_text(src)
        active = lint(tmp_path, "lock-order")
        assert any(
            "await while holding annotated asyncio lock" in f.message
            for f in active
        ), [f.message for f in active]

    def test_new_module_level_mutable_in_router_fails_lint(self, tmp_path):
        router = tmp_path / "router"
        router.mkdir()
        (router / "rogue.py").write_text(
            "_registry = {}\n"
            "_service = None\n"
            "def initialize_service(s):\n"
            "    global _service\n"
            "    _service = s\n"
        )
        active = lint(tmp_path, "app-scope")
        msgs = "\n".join(f.message for f in active)
        assert "'_registry'" in msgs
        assert "'global _service'" in msgs

    def test_changed_parser_default_without_values_twin_fails_lint(
        self, tmp_path
    ):
        """Acceptance: one parser default changed without its values.yaml
        twin produces a config-contract default-drift finding (checked
        against the REAL helm/docs/registry anchors at the repo root)."""
        router = tmp_path / "router"
        router.mkdir()
        src = (REPO / "production_stack_tpu/router/parser.py").read_text()
        needle = '"--admission-queue-size", type=int, default=128'
        assert needle in src
        src = src.replace(
            needle, '"--admission-queue-size", type=int, default=256'
        )
        (router / "parser.py").write_text(src)
        active = lint_with_root(tmp_path, REPO, "config-contract")
        assert any(
            "default drift for --admission-queue-size" in f.message
            for f in active
        ), [f.message for f in active]

    def test_live_config_contract_classifies_all_flags(self):
        """Acceptance: bidirectional parity over the FULL router flag
        surface — every parser flag classified by the registry, every
        spec backed by a parser flag, helm-scoped knobs verified against
        values/schema/template/docs (a clean run IS the proof; this test
        additionally pins the 1:1 count so a vacuous pass cannot hide)."""
        from production_stack_tpu.analysis import load_project
        from production_stack_tpu.analysis.checks.config_contract import (
            parser_flags,
        )
        from production_stack_tpu.analysis.config_registry import (
            CLI_ONLY, HELM, ROUTER_FLAGS, TEMPLATE,
        )

        project = load_project(
            [str(REPO / "production_stack_tpu" / "router" / "parser.py")],
            root=REPO,
        )
        flags = parser_flags(project.files[0])
        spec_flags = {s.flag for s in ROUTER_FLAGS}
        assert set(flags) == spec_flags
        assert len(ROUTER_FLAGS) == len(flags)
        for spec in ROUTER_FLAGS:
            assert spec.scope in (HELM, TEMPLATE, CLI_ONLY)
            if spec.scope == CLI_ONLY:
                assert spec.note, "cli-only spec %s needs a reason" % spec.flag
            if spec.scope == HELM:
                assert spec.helm, "helm spec %s needs a values path" % spec.flag
        active = lint_with_root(
            REPO / "production_stack_tpu", REPO, "config-contract"
        )
        assert active == [], [f.message for f in active]

    def test_subset_lint_resolves_cross_file_anchors(self, tmp_path):
        """Linting a subtree must not report the registry/lattice as
        missing — anchors resolve from the repo root (reviewer finding:
        changed-files-only lint workflows)."""
        active = lint_with_root(
            REPO / "production_stack_tpu" / "router", REPO, "metric-registry"
        )
        assert active == [], [f.message for f in active]
        active = lint_with_root(
            REPO / "production_stack_tpu" / "engine" / "runner.py",
            REPO, "recompile-risk",
        )
        assert active == [], [f.message for f in active]

    def test_single_file_lint_honors_anchor_suppressions(self):
        """Linting one engine file must not surface findings that the
        resolved anchor (runner.py) suppresses in its own text — and must
        not emit unused-suppression noise for files nobody asked about."""
        findings = run_checks(
            [str(REPO / "production_stack_tpu/engine/cross_encoder.py")],
            root=REPO,
        )
        active = [f for f in findings if not f.suppressed]
        assert active == [], "\n".join(f.format() for f in active)

    def test_lambda_bodies_are_not_async_context(self, tmp_path):
        """The executor-offload idiom (a lambda passed to
        run_in_executor) must not fire async-blocking."""
        mod = tmp_path / "router" / "m.py"
        mod.parent.mkdir()
        mod.write_text(
            "import asyncio\n"
            "async def f(path):\n"
            "    loop = asyncio.get_running_loop()\n"
            "    return await loop.run_in_executor(\n"
            "        None, lambda: open(path).read()\n"
            "    )\n"
        )
        active = lint(tmp_path, "async-blocking")
        assert active == [], [f.message for f in active]

    def test_real_lattice_families_complete(self):
        """The real enumeration registers exactly the six families (the
        sixth since PR 53: the verify-and-draft step of an engine that
        serves its model's own draft)."""
        from production_stack_tpu.analysis.checks.recompile_risk import (
            lattice_families,
        )
        from production_stack_tpu.analysis import load_project

        project = load_project(
            [str(REPO / "production_stack_tpu" / "engine")], root=REPO
        )
        pre = project.find("engine/precompile.py")[0]
        families, _ = lattice_families(pre)
        assert families == {
            "decode", "decode_burst", "prefill", "spec_verify", "encode",
            "mtp_verify",
        }


# ---------------------------------------------------------------------------
# 3. CLI ring
# ---------------------------------------------------------------------------


def run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "production_stack_tpu.analysis.pstlint",
         *args],
        capture_output=True, text=True, cwd=REPO,
    )


class TestCLI:
    def test_clean_tree_exits_zero(self):
        proc = run_cli("production_stack_tpu/", "scripts/")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 finding(s)" in proc.stdout

    def test_bad_fixture_exits_one_with_json(self):
        proc = run_cli(
            "--format", "json", "--no-unused",
            "--root", str(FIXTURES / "lock_discipline"),
            str(FIXTURES / "lock_discipline"),
        )
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert report["summary"]["active"] >= 3
        checks = {f["check"] for f in report["findings"]}
        assert "lock-discipline" in checks

    def test_list_checks(self):
        proc = run_cli("--list-checks")
        assert proc.returncode == 0
        for check in ("async-blocking", "recompile-risk", "hop-contract",
                      "metric-registry", "lock-discipline",
                      "task-lifecycle", "lock-order", "app-scope",
                      "config-contract"):
            assert check in proc.stdout

    def test_unknown_check_usage_error(self):
        proc = run_cli("--checks", "nope", "production_stack_tpu/")
        assert proc.returncode == 2

    def test_check_metric_docs_shim(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "check_metric_docs.py")],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "registry" in proc.stdout

    def test_nonexistent_path_is_a_loud_error(self):
        proc = run_cli("production_stack_tp/")  # typo'd directory
        assert proc.returncode == 2
        assert "do not exist" in proc.stderr


# ---------------------------------------------------------------------------
# 4. Report schema stability (JSON + SARIF are consumed contracts)
# ---------------------------------------------------------------------------


class TestReportSchemas:
    """CI uploads these reports (SARIF annotates PR diffs); their shape is
    a contract. A key rename must fail HERE, not in the CI annotations."""

    def _bad_fixture_args(self, fmt):
        return (
            "--format", fmt, "--no-unused",
            "--root", str(FIXTURES / "lock_discipline"),
            str(FIXTURES / "lock_discipline"),
        )

    def test_json_schema_stable(self):
        proc = run_cli(*self._bad_fixture_args("json"))
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert set(report) == {"findings", "summary"}
        assert set(report["summary"]) == {"active", "suppressed"}
        assert report["findings"], "bad fixture must produce findings"
        for finding in report["findings"]:
            assert set(finding) == {
                "check", "path", "line", "col", "message", "suppressed",
                "reason",
            }

    def test_sarif_schema_stable(self):
        proc = run_cli(*self._bad_fixture_args("sarif"))
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert report["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in report["$schema"]
        assert len(report["runs"]) == 1
        run = report["runs"][0]
        driver = run["tool"]["driver"]
        assert driver["name"] == "pstlint"
        rule_ids = {r["id"] for r in driver["rules"]}
        # Every registered check advertises a rule, firing or not.
        assert {
            "async-blocking", "recompile-risk", "hop-contract",
            "metric-registry", "lock-discipline", "task-lifecycle",
            "lock-order", "app-scope", "config-contract",
        } <= rule_ids
        assert run["results"], "bad fixture must produce results"
        for result in run["results"]:
            assert result["ruleId"] in rule_ids
            assert result["level"] in ("error", "note")
            assert result["message"]["text"]
            loc = result["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"]
            assert loc["region"]["startLine"] >= 1
            assert loc["region"]["startColumn"] >= 1

    def test_sarif_marks_suppressions(self):
        proc = run_cli(
            "--format", "sarif", "--no-unused",
            "--root", str(REPO),
            str(REPO / "production_stack_tpu" / "engine" / "runner.py"),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        suppressed = [
            r for r in report["runs"][0]["results"] if "suppressions" in r
        ]
        assert suppressed, "runner.py's documented suppression must appear"
        for result in suppressed:
            assert result["level"] == "note"
            assert result["suppressions"][0]["kind"] == "inSource"
            assert result["suppressions"][0]["justification"]
