"""radoslint analyzer tests: positive+negative fixtures per rule,
suppression comments, baseline round-trip + ratchet, the lint_tool and
module entry points, changed-only mode, the runtime sanitizer — and
the tier-1 gate: the full suite over ceph_tpu/ must produce zero
non-baselined findings."""
import asyncio
import json
import os
import subprocess
import sys

import pytest

from ceph_tpu.tools import lint_tool
from ceph_tpu.tools.radoslint import cli, core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "radoslint_fixtures")

ALL_RULES = {"detached-task", "blocking-in-coroutine", "await-under-lock",
             "cancellation-swallow", "loop-affinity",
             "registry-consistency", "decl-use",
             "report-export-consistency",
             "view-escape", "view-across-await",
             "lock-order-cycle", "await-in-gate"}


def lint(path, rules):
    return core.run_lint([os.path.join(FIXTURES, path)], root=FIXTURES,
                         rules=rules)


# -- one known-positive and one known-negative fixture per rule -------------

@pytest.mark.parametrize("rule,pos,expected,neg", [
    ("detached-task", "detached_task_pos.py", 2, "detached_task_neg.py"),
    ("blocking-in-coroutine", "blocking_pos.py", 4, "blocking_neg.py"),
    ("await-under-lock", "await_under_lock_pos.py", 1,
     "await_under_lock_neg.py"),
    ("cancellation-swallow", "cancellation_swallow_pos.py", 2,
     "cancellation_swallow_neg.py"),
    ("loop-affinity", "loop_affinity_pos.py", 2, "loop_affinity_neg.py"),
    ("decl-use", "decl_use_bad.py", 5, "decl_use_good.py"),
    ("decl-use", "decl_use_faultinject_bad.py", 2,
     "decl_use_faultinject_good.py"),
    ("decl-use", "decl_use_offload_bad.py", 2,
     "decl_use_offload_good.py"),
    ("decl-use", "decl_use_clients_bad.py", 2,
     "decl_use_clients_good.py"),
    ("decl-use", "decl_use_pipeline_bad.py", 2,
     "decl_use_pipeline_good.py"),
    ("decl-use", "decl_use_qos_bad.py", 2,
     "decl_use_qos_good.py"),
    ("decl-use", "decl_use_scrub_bad.py", 2,
     "decl_use_scrub_good.py"),
    ("decl-use", "decl_use_flight_bad.py", 2,
     "decl_use_flight_good.py"),
    ("decl-use", "decl_use_tracer_bad.py", 2,
     "decl_use_tracer_good.py"),
    ("report-export-consistency", "report_export_bad.py", 1,
     "report_export_good.py"),
    ("view-escape", "view_escape_pos.py", 5, "view_escape_neg.py"),
    ("view-across-await", "view_across_await_pos.py", 2,
     "view_across_await_neg.py"),
    ("lock-order-cycle", "lock_order_cycle_pos.py", 2,
     "lock_order_cycle_neg.py"),
    ("await-in-gate", "await_in_gate_pos.py", 3,
     "await_in_gate_neg.py"),
])
def test_rule_fixtures(rule, pos, expected, neg):
    findings = lint(pos, rules=[rule])
    assert len(findings) == expected, \
        f"{pos}: {[f.render() for f in findings]}"
    assert all(f.rule == rule for f in findings)
    assert lint(neg, rules=[rule]) == []


def test_registry_consistency_fixtures():
    findings = lint("registry_bad", rules=["registry-consistency"])
    msgs = [f.message for f in findings]
    assert sum("collides with MPing" in m for m in msgs) == 1
    assert sum("never passed to register_message" in m for m in msgs) == 1
    assert sum("bound to MMislabeled" in m for m in msgs) == 1
    assert sum("frame tag AUTH=1 collides" in m for m in msgs) == 1
    assert sum("dead wire protocol" in m for m in msgs) == 4
    assert len(findings) == 8
    assert lint("registry_good", rules=["registry-consistency"]) == []


def test_rule_ids_match_registered_set():
    from ceph_tpu.tools.radoslint import (checkers, lockorder,  # noqa: F401
                                          project)
    assert set(core.RULES) == ALL_RULES
    kinds = {r.id: r.kind for r in core.RULES.values()}
    assert kinds["registry-consistency"] == "project"
    assert kinds["decl-use"] == "project"
    assert kinds["report-export-consistency"] == "project"
    assert kinds["lock-order-cycle"] == "project"
    assert kinds["await-in-gate"] == "file"


# -- suppression comments ----------------------------------------------------

def test_suppression_comments(tmp_path):
    src = ("import asyncio\n"
           "async def f():\n"
           "    asyncio.create_task(f())  # radoslint: disable=detached-task\n"
           "    # radoslint: disable-next=detached-task\n"
           "    asyncio.create_task(f())\n"
           "    asyncio.create_task(\n"
           "        f())  # radoslint: disable=detached-task\n"
           "    asyncio.create_task(f())\n")
    p = tmp_path / "s.py"
    p.write_text(src)
    findings = core.run_lint([str(p)], root=str(tmp_path),
                             rules=["detached-task"])
    # same-line, next-line, and multi-line-statement suppressions all
    # hold; only the unsuppressed spawn on the last line survives
    assert [f.line for f in findings] == [8]

    p2 = tmp_path / "s2.py"
    p2.write_text("# radoslint: disable-file=all\n" + src)
    assert core.run_lint([str(p2)], root=str(tmp_path),
                         rules=["detached-task"]) == []


# -- baseline round-trip and ratchet -----------------------------------------

def test_baseline_roundtrip(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import asyncio\n"
                   "async def f():\n"
                   "    asyncio.create_task(f())\n")
    base = tmp_path / "base.json"
    argv = [str(bad), "--root", str(tmp_path), "--baseline", str(base)]
    assert cli.main(argv) == 1                      # finding, no baseline
    assert cli.main(argv + ["--write-baseline"]) == 0
    assert cli.main(argv) == 0                      # grandfathered: clean
    # a NEW finding is not covered by the baseline
    bad.write_text(bad.read_text() +
                   "async def g():\n"
                   "    asyncio.ensure_future(f())\n")
    assert cli.main(argv) == 1
    # fixing everything: clean run reports the stale entry (ratchet cue)
    bad.write_text("x = 1\n")
    capsys.readouterr()
    assert cli.main(argv) == 0
    assert "stale" in capsys.readouterr().out


def test_write_baseline_refuses_restricted_runs(tmp_path, capsys):
    """--write-baseline from a --rules/--changed-only run would clobber
    the full baseline with a partial finding set: refused."""
    bad = tmp_path / "bad.py"
    bad.write_text("import asyncio\n"
                   "async def f():\n"
                   "    asyncio.create_task(f())\n")
    base = tmp_path / "base.json"
    argv = [str(bad), "--root", str(tmp_path), "--baseline", str(base)]
    assert cli.main(argv + ["--write-baseline",
                            "--rules", "detached-task"]) == 2
    assert cli.main(argv + ["--write-baseline", "--changed-only"]) == 2
    assert not base.exists()


def test_cli_json_output(tmp_path, capsys):
    rc = cli.main([os.path.join(FIXTURES, "detached_task_pos.py"),
                   "--root", FIXTURES, "--json",
                   "--baseline", str(tmp_path / "none.json")])
    assert rc == 1
    data = json.loads(capsys.readouterr().out)
    assert len(data["findings"]) == 2
    assert all(f["rule"] == "detached-task" for f in data["findings"])
    assert set(data["findings"][0]) == {"path", "line", "rule", "message"}


def test_parse_error_becomes_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def oops(:\n")
    findings = core.run_lint([str(p)], root=str(tmp_path))
    assert [f.rule for f in findings] == ["parse-error"]


# -- findings cache ----------------------------------------------------------

def test_cache_warm_run_identical_and_parse_free(tmp_path):
    """A warm full run must (a) reproduce the cold run's findings
    byte for byte — including suppressions and parse errors — and
    (b) parse NOTHING (PARSE_COUNT is the instrument)."""
    (tmp_path / "bad.py").write_text(
        "import asyncio\n"
        "async def f():\n"
        "    asyncio.create_task(f())\n"
        "    asyncio.ensure_future(f())  "
        "# radoslint: disable=detached-task\n")
    (tmp_path / "broken.py").write_text("def oops(:\n")
    cold = core.run_lint([str(tmp_path)], root=str(tmp_path))
    assert {f.rule for f in cold} == {"detached-task", "parse-error"}
    assert os.path.exists(os.path.join(str(tmp_path), core.CACHE_NAME))
    before = core.PARSE_COUNT
    warm = core.run_lint([str(tmp_path)], root=str(tmp_path))
    assert core.PARSE_COUNT == before, "warm run re-parsed the tree"
    assert [f.key for f in warm] == [f.key for f in cold]
    # an uncached run agrees too (the cache changes cost, not truth)
    nocache = core.run_lint([str(tmp_path)], root=str(tmp_path),
                            use_cache=False)
    assert [f.key for f in nocache] == [f.key for f in cold]
    # editing a file invalidates exactly its entries: the new finding
    # appears, the fixed one disappears
    (tmp_path / "bad.py").write_text(
        "import asyncio\n"
        "async def g():\n"
        "    asyncio.ensure_future(g())\n")
    third = core.run_lint([str(tmp_path)], root=str(tmp_path))
    assert any(f.rule == "detached-task" and f.line == 3
               for f in third)
    assert all(f.path != "bad.py" or f.line == 3 for f in third)


# -- lint_tool (ec_tool-style operator surface) ------------------------------

def test_lint_tool_rules_and_explain(capsys):
    assert lint_tool.main(["rules"]) == 0
    out = capsys.readouterr().out
    for rid in ALL_RULES:
        assert rid in out
    assert lint_tool.main(["explain", "await-under-lock"]) == 0
    assert "lockdep" in capsys.readouterr().out
    assert lint_tool.main(["explain", "no-such-rule"]) == 2


def test_lint_tool_baseline_ratchet(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    bad = tmp_path / "bad.py"
    bad.write_text("import asyncio\n"
                   "async def f():\n"
                   "    asyncio.ensure_future(f())\n")
    base = str(tmp_path / "b.json")
    assert lint_tool.main(["baseline", "write", str(bad),
                           "--baseline", base]) == 0
    assert lint_tool.main(["check", str(bad), "--baseline", base]) == 0
    assert lint_tool.main(["baseline", "show", "--baseline", base]) == 0
    assert "detached-task" in capsys.readouterr().out
    bad.write_text("x = 1\n")                       # fix the finding
    assert lint_tool.main(["baseline", "prune", str(bad),
                           "--baseline", base]) == 0
    assert core.load_baseline(base) == set()        # ratchet shrank to zero


# -- changed-only mode (incremental builder runs) ----------------------------

def test_changed_only_restricts_file_rules(tmp_path):
    def git(*a):
        subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                        *a], cwd=tmp_path, check=True, capture_output=True)
    bad_src = ("import asyncio\n"
               "async def f():\n"
               "    asyncio.create_task(f())\n")
    git("init", "-q")
    (tmp_path / "committed.py").write_text(bad_src)
    git("add", ".")
    git("commit", "-qm", "seed")
    (tmp_path / "dirty.py").write_text(bad_src)     # untracked
    findings = core.run_lint([str(tmp_path)], root=str(tmp_path),
                             rules=["detached-task"], changed_only=True)
    assert {f.path for f in findings} == {"dirty.py"}
    full = core.run_lint([str(tmp_path)], root=str(tmp_path),
                         rules=["detached-task"])
    assert {f.path for f in full} == {"committed.py", "dirty.py"}

    # root below the git top-level: `git diff --name-only` reports
    # toplevel-relative paths, which must be re-anchored to root (a
    # naive match lints NOTHING here and the gate exits 0 on real bugs)
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text("x = 1\n")
    git("add", ".")
    git("commit", "-qm", "pkg")
    (pkg / "mod.py").write_text(bad_src)            # worktree change
    findings = core.run_lint([str(pkg)], root=str(pkg),
                             rules=["detached-task"], changed_only=True)
    assert {f.path for f in findings} == {"mod.py"}


def test_changed_only_handles_renames_and_deletes(tmp_path):
    """`git diff` on a renamed file must contribute only the NEW name
    and a deleted file nothing at all — the old --name-only parse
    handed the analyzer paths that no longer exist, and a committed-
    then-renamed finding escaped the incremental gate entirely."""
    def git(*a):
        subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                        *a], cwd=tmp_path, check=True, capture_output=True)
    bad_src = ("import asyncio\n"
               "async def f():\n"
               "    asyncio.create_task(f())\n")
    git("init", "-q")
    (tmp_path / "old_name.py").write_text(bad_src)
    (tmp_path / "doomed.py").write_text(bad_src)
    (tmp_path / "clean.py").write_text("x = 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    # rename one bad file, delete the other — both via git so the diff
    # reports R and D statuses
    git("mv", "old_name.py", "new_name.py")
    git("rm", "-q", "doomed.py")
    findings = core.run_lint([str(tmp_path)], root=str(tmp_path),
                             rules=["detached-task"], changed_only=True)
    # the rename's new name is linted; the deleted path neither crashes
    # the run nor appears in findings
    assert {f.path for f in findings} == {"new_name.py"}
    # worktree-only delete (no index involvement) is just as graceful
    (tmp_path / "clean.py").unlink()
    findings = core.run_lint([str(tmp_path)], root=str(tmp_path),
                             rules=["detached-task"], changed_only=True)
    assert {f.path for f in findings} == {"new_name.py"}


# -- module entry point (the CI gate invocation) -----------------------------

def test_module_entry_point_json():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.tools.radoslint",
         os.path.join(FIXTURES, "detached_task_pos.py"), "--json",
         "--baseline", os.path.join(FIXTURES, "no_such_baseline.json")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stderr
    data = json.loads(proc.stdout)
    assert len(data["findings"]) == 2


# -- runtime sanitizer (the dynamic half) ------------------------------------

def test_sanitizer_records_spawn_site():
    from ceph_tpu.utils import sanitizer

    async def main():
        loop = asyncio.get_running_loop()
        sanitizer.install(loop, slow_callback_s=0.5)
        try:
            t = asyncio.create_task(asyncio.sleep(0))
            site = sanitizer.spawn_site(t)
            assert site is not None and "test_radoslint" in site
            await t
        finally:
            sanitizer.uninstall(loop)

    asyncio.run(main())


def test_sanitizer_config_hot_toggle():
    from ceph_tpu.utils import sanitizer
    from ceph_tpu.utils.config import Config

    config = Config()
    sanitizer.register_config(config)
    assert config.get("sanitizer_enabled") is False

    async def main():
        loop = asyncio.get_running_loop()
        try:
            config.set("sanitizer_enabled", True)
            assert loop.get_debug()
            config.set("sanitizer_slow_callback_s", 0.25)
            assert loop.slow_callback_duration == 0.25
            config.set("sanitizer_enabled", False)
            assert not loop.get_debug()
        finally:
            sanitizer.uninstall(loop)

    asyncio.run(main())


def test_sanitizer_toggle_from_foreign_thread():
    """`config set sanitizer_enabled true` over the admin socket runs
    the observer on the admin-socket THREAD (no running loop there):
    the change must still arm the daemon's tracked loop via
    call_soon_threadsafe."""
    import threading

    from ceph_tpu.utils import sanitizer
    from ceph_tpu.utils.config import Config

    config = Config()
    sanitizer.register_config(config)

    async def main():
        loop = asyncio.get_running_loop()
        sanitizer.maybe_install(config)     # tracks the loop, stays off
        assert not loop.get_debug()
        t = threading.Thread(target=config.set,
                             args=("sanitizer_enabled", True))
        t.start()
        t.join()
        await asyncio.sleep(0.05)           # call_soon_threadsafe lands
        try:
            assert loop.get_debug()
        finally:
            sanitizer.uninstall(loop)

    asyncio.run(main())


# -- the tier-1 gate: zero non-baselined findings over ceph_tpu/ -------------

def test_tier1_gate_zero_findings():
    findings = core.run_lint([os.path.join(REPO, "ceph_tpu")], root=REPO)
    baseline_path = os.path.join(REPO, core.BASELINE_NAME)
    baseline = core.load_baseline(baseline_path)
    fresh = [f.render() for f in findings if f.key not in baseline]
    assert fresh == [], \
        "non-baselined radoslint findings:\n" + "\n".join(fresh)
    # the ratchet: grandfathered entries must stay near zero and only
    # ever shrink — justify any addition in the baseline file itself
    assert len(baseline) <= 5
