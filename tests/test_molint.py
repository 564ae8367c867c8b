"""molint (tools/molint): the AST-driven invariant checker suite.

Three layers of coverage:

  * **tier-1 gate** — the whole suite over the real `matrixone_tpu/`
    tree must be clean (this is the test that fails the build when a
    new subsystem re-breaks a cross-cutting convention);
  * **per-checker fixture pairs** — every rule fires on its violating
    snippet under tests/molint_fixtures/ and stays quiet on the clean
    one;
  * **machinery** — suppression round-trip (justified comment silences,
    missing justification is itself a finding), CLI exit codes on a
    planted violation in a temp tree, the lint_excepts shim, and the
    mo_ctl('lint', ...) ops surface.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import molint  # noqa: E402

FIX = os.path.join(REPO, "tests", "molint_fixtures")


def _run(paths, rules=None, config=None, tests_dir=None):
    return molint.run_checks(REPO, src_paths=paths, rules=rules,
                             config=config, tests_dir=tests_dir,
                             record=False)


def _rules_of(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------ tier-1 gate
def test_repo_tree_is_clean():
    """THE gate: every checker over the real package, zero findings.
    A finding here means a new invariant violation landed — fix it or
    suppress it with a written justification."""
    findings, stats = molint.run_checks(REPO)
    assert stats["checkers"] >= 7
    assert not findings, "\n" + "\n".join(f.format() for f in findings)


def test_suite_shape():
    rules = [r for r, _ in molint.rule_table()]
    assert sorted(rules) == [
        "broad-except", "cache-invalidation", "deadline-propagation",
        "fault-coverage", "jit-purity", "knob-doc", "lock-discipline",
        "metric-hygiene", "san-adoption", "span-hygiene"]


# ------------------------------------------------- per-checker fixtures
def _fixture_pair(rule, bad_paths, good_paths, config=None,
                  bad_tests=None, good_tests=None):
    bad, _ = _run(bad_paths, rules=[rule], config=config,
                  tests_dir=bad_tests)
    good, _ = _run(good_paths, rules=[rule], config=config,
                   tests_dir=good_tests)
    assert any(f.rule == rule for f in bad), \
        f"{rule}: no finding on violating fixture"
    assert not good, (f"{rule}: clean fixture flagged:\n"
                      + "\n".join(f.format() for f in good))
    return bad


def test_jit_purity_fixtures():
    d = os.path.join(FIX, "jit_purity")
    bad = _fixture_pair("jit-purity",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")])
    msgs = " | ".join(f.message for f in bad)
    assert "time.perf_counter" in msgs          # via reachability
    assert "stateful RNG" in msgs
    assert "module-level" in msgs or "global" in msgs
    assert "float()" in msgs
    assert ".item()" in msgs


def test_jit_purity_attribute_wrapped_roots():
    """Fused-fragment-style trace roots wrapped via an attribute
    reference (`jax.jit(self._traced_step)`) are discovered and walked;
    the same shape with a pure body stays quiet."""
    d = os.path.join(FIX, "jit_purity")
    bad = _fixture_pair("jit-purity",
                        [os.path.join(d, "frag_bad.py")],
                        [os.path.join(d, "frag_good.py")])
    assert any("_traced_step" in f.message
               and "time.perf_counter" in f.message for f in bad)


def test_jit_purity_alias_and_factory_roots():
    """Fused-join-fragment-style trace roots where the jit target is a
    local variable — a direct alias of a nested def (`fn = _build_step;
    jax.jit(fn)`) or a factory-returned closure (`fn =
    self._make_probe_step(); jax.jit(fn)`) — are discovered and walked;
    the same shapes with pure bodies stay quiet."""
    d = os.path.join(FIX, "jit_purity")
    bad = _fixture_pair("jit-purity",
                        [os.path.join(d, "alias_bad.py")],
                        [os.path.join(d, "alias_good.py")])
    assert any("_build_step" in f.message
               and "time.perf_counter" in f.message for f in bad)
    assert any("_probe_step" in f.message
               and "time.perf_counter" in f.message for f in bad)


def test_jit_purity_cross_module_factory_roots():
    """A base-class jit site whose traced fn comes from a
    `self._make_step()` factory overridden in ANOTHER module (the fused
    window idiom: fusion.py wraps, fusion_window.py makes the step,
    window.py owns the kernel body reached through `wop = self._window`)
    is followed across both hops; the pure twin stays quiet."""
    d = os.path.join(FIX, "jit_purity")
    bad = _fixture_pair(
        "jit-purity",
        [os.path.join(d, "xmod_bad_base.py"),
         os.path.join(d, "xmod_bad_sub.py")],
        [os.path.join(d, "xmod_good_base.py"),
         os.path.join(d, "xmod_good_sub.py")])
    assert any("Kernel.compute" in f.message
               and "time.perf_counter" in f.message for f in bad)


def test_lock_discipline_fixtures():
    d = os.path.join(FIX, "lock_discipline")
    bad = _fixture_pair("lock-discipline",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")])
    msgs = " | ".join(f.message for f in bad)
    assert ".acquire()" in msgs
    assert "under the commit lock" in msgs
    assert "lock-order cycle" in msgs


def test_deadline_fixtures():
    d = os.path.join(FIX, "deadline")
    bad = _fixture_pair("deadline-propagation",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")])
    msgs = " | ".join(f.message for f in bad)
    assert "settimeout(5)" in msgs
    assert "retry loop" in msgs
    assert "deadline_ms" in msgs


def test_deadline_flat_sleep_not_excused_by_sibling_backoff(tmp_path):
    """Each sleep is judged on its own argument: one jittered sleep in
    a retry loop must not excuse a flat one next to it."""
    p = tmp_path / "mod.py"
    p.write_text(
        "import time\n"
        "from matrixone_tpu.cluster.rpc import backoff_delay\n"
        "def retry(fn):\n"
        "    for attempt in range(5):\n"
        "        try:\n"
        "            return fn()\n"
        "        except ConnectionError:\n"
        "            time.sleep(backoff_delay(attempt))\n"
        "        except OSError:\n"
        "            time.sleep(1.0)\n")
    findings, _ = _run([str(p)], rules=["deadline-propagation"])
    assert len(findings) == 1 and findings[0].lineno == 10
    # a name bound to a backoff-derived expression is fine
    p2 = tmp_path / "mod2.py"
    p2.write_text(
        "import time\n"
        "from matrixone_tpu.cluster.rpc import backoff_delay\n"
        "def retry(fn, dl):\n"
        "    for attempt in range(5):\n"
        "        try:\n"
        "            return fn()\n"
        "        except ConnectionError:\n"
        "            delay = min(backoff_delay(attempt), dl)\n"
        "            time.sleep(delay)\n")
    findings2, _ = _run([str(p2)], rules=["deadline-propagation"])
    assert not findings2


def test_cache_invalidation_fixtures():
    d = os.path.join(FIX, "cache_invalidation")
    bad = _fixture_pair("cache-invalidation",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")])
    msgs = " | ".join(f.message for f in bad)
    assert "ddl_gen" in msgs
    assert "index_obj" in msgs
    # one finding per mutation site in bad.py: tables, stages, sources,
    # index_obj
    assert len(bad) >= 4


def test_cache_invalidation_mview_fixtures():
    """View-state mutations must advance the watermark (or bump
    ddl_gen) — the mview analogue of the catalog rule."""
    d = os.path.join(FIX, "cache_invalidation")
    bad = _fixture_pair("cache-invalidation",
                        [os.path.join(d, "mview_bad.py")],
                        [os.path.join(d, "mview_good.py")])
    msgs = " | ".join(f.message for f in bad)
    assert "watermark" in msgs
    # one finding per mutation site: subscript store, pop, rebind
    assert len(bad) >= 3


def test_cache_invalidation_mview_planted_violation(tmp_path):
    """Planted regression: removing the watermark advance from an
    otherwise-clean maintainer is caught."""
    p = tmp_path / "mod.py"
    p.write_text(
        "class ViewRuntime:\n"
        "    def __init__(self):\n"
        "        self.groups = {}\n"
        "        self.watermark = 0\n"
        "\n"
        "    def merge(self, key, part, ts):\n"
        "        self.groups[key] = part\n"
        "        self.watermark = max(self.watermark, ts)\n")
    findings, _ = _run([str(p)], rules=["cache-invalidation"])
    assert not findings
    p.write_text(
        "class ViewRuntime:\n"
        "    def __init__(self):\n"
        "        self.groups = {}\n"
        "        self.watermark = 0\n"
        "\n"
        "    def merge(self, key, part, ts):\n"
        "        self.groups[key] = part\n")
    findings, _ = _run([str(p)], rules=["cache-invalidation"])
    assert any("watermark" in f.message for f in findings)


def test_cache_invalidation_is_branch_aware(tmp_path):
    """A bumping branch of a dispatcher must not whitelist a sibling
    branch's mutation (the WAL-replay apply() shape)."""
    p = tmp_path / "mod.py"
    p.write_text(
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.ddl_gen = 0\n"
        "        self.stages = {}\n"
        "def apply(eng, header):\n"
        "    if header['op'] == 'create_table':\n"
        "        eng.create_table(header)\n"          # bumps, arm 1
        "    elif header['op'] == 'create_stage':\n"
        "        eng.stages[header['name']] = header['url']\n")
    findings, _ = _run([str(p)], rules=["cache-invalidation"])
    assert len(findings) == 1 and "stages" in findings[0].message
    # bump in the SAME branch (or enclosing scope) covers it
    p2 = tmp_path / "mod2.py"
    p2.write_text(
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.ddl_gen = 0\n"
        "        self.stages = {}\n"
        "def apply(eng, header):\n"
        "    if header['op'] == 'create_stage':\n"
        "        eng.stages[header['name']] = header['url']\n"
        "        eng.ddl_gen += 1\n")
    findings2, _ = _run([str(p2)], rules=["cache-invalidation"])
    assert not findings2


def test_lock_order_cycle_through_multi_item_with(tmp_path):
    """`with a, b:` acquires a then b — it must contribute the a->b
    edge and close cycles against the nested form."""
    p = tmp_path / "mod.py"
    p.write_text(
        "import threading\n"
        "a_lock = threading.Lock()\n"
        "b_lock = threading.Lock()\n"
        "def f1():\n"
        "    with a_lock, b_lock:\n"
        "        pass\n"
        "def f2():\n"
        "    with b_lock:\n"
        "        with a_lock:\n"
        "            pass\n")
    findings, _ = _run([str(p)], rules=["lock-discipline"])
    assert any("lock-order cycle" in f.message for f in findings)


def test_metric_hygiene_fixtures():
    d = os.path.join(FIX, "metric_hygiene")
    cfg = {"metric-hygiene": {"registry_suffix": "_registry.py",
                              "extra_driver_paths": (),
                              "corpus_complete": True}}
    bad = _fixture_pair(
        "metric-hygiene",
        [os.path.join(d, "bad_registry.py"),
         os.path.join(d, "bad_user.py")],
        [os.path.join(d, "good_registry.py"),
         os.path.join(d, "good_user.py")],
        config=cfg)
    msgs = " | ".join(f.message for f in bad)
    assert "registered twice" in msgs
    assert "does not match" in msgs              # naming convention
    assert "f-string label" in msgs
    assert "differing label" in msgs
    assert "outside the registry" in msgs
    assert "never driven" in msgs


def test_fault_coverage_fixtures():
    d = os.path.join(FIX, "fault_coverage")
    bad = _fixture_pair(
        "fault-coverage",
        [os.path.join(d, "src_bad.py")],
        [os.path.join(d, "src_good.py")],
        config={"fault-coverage": {"corpus_complete": True}},
        bad_tests=os.path.join(d, "tests_bad"),
        good_tests=os.path.join(d, "tests_good"))
    msgs = " | ".join(f.message for f in bad)
    assert "'cover.me'" in msgs and "never armed" in msgs
    assert "'no.such'" in msgs and "no-op" in msgs


def test_broad_except_fixtures():
    d = os.path.join(FIX, "broad_except")
    bad = _fixture_pair("broad-except",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")])
    assert len(bad) == 2                 # except Exception + bare except


def test_san_adoption_fixtures():
    d = os.path.join(FIX, "san_adoption")
    bad = _fixture_pair("san-adoption",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")])
    # direct + RLock + Condition + module-alias + two from-imports
    assert len(bad) == 6
    msgs = " | ".join(f.message for f in bad)
    assert "san.lock" in msgs
    assert "san.rlock" in msgs
    assert "san.condition" in msgs


def test_knob_doc_fixtures():
    """Read-site side: every undocumented MO_* read fires (environ.get,
    getenv, subscript, env_* helper); documented reads, justified
    suppressions and prose mentions stay quiet."""
    d = os.path.join(FIX, "knob_doc")
    cfg = {"knob-doc": {"readme": os.path.join(d, "README_fixture.md"),
                        "extra_src_dirs": (),
                        "extra_driver_paths": (),
                        "corpus_complete": False}}
    bad = _fixture_pair("knob-doc",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")],
                        config=cfg)
    knobs = {f.message.split("'")[1] for f in bad}
    assert knobs == {"MO_FIX_UNDOCUMENTED", "MO_FIX_GETENV",
                     "MO_FIX_SUBSCRIPT", "MO_FIX_HELPER"}


def test_knob_doc_dead_knob():
    """Inventory side: a documented knob with no read site anywhere in
    the corpus is a finding anchored at the README table row; the
    sub-rule needs the full corpus (corpus_complete)."""
    d = os.path.join(FIX, "knob_doc")
    cfg = {"knob-doc": {"readme": os.path.join(d, "README_dead.md"),
                        "extra_src_dirs": (),
                        "extra_driver_paths": (),
                        "corpus_complete": True}}
    findings, _ = _run([os.path.join(d, "good.py")],
                       rules=["knob-doc"], config=cfg)
    dead = [f for f in findings if "MO_FIX_DEAD" in f.message]
    assert len(dead) == 1 and dead[0].path.endswith("README_dead.md")
    assert not any("MO_FIX_DOCUMENTED" in f.message for f in findings)
    # partial scan: the dead-knob sub-rule skips itself
    cfg["knob-doc"]["corpus_complete"] = False
    findings2, _ = _run([os.path.join(d, "good.py")],
                        rules=["knob-doc"], config=cfg)
    assert not findings2, [f.format() for f in findings2]


def test_knob_doc_planted_violation(tmp_path):
    """A knob read planted in a temp tree fires against the real
    README; a justified suppression silences it."""
    cfg = {"knob-doc": {"extra_src_dirs": (),
                        "extra_driver_paths": ()}}
    p = tmp_path / "feature.py"
    p.write_text("import os\n"
                 "N = int(os.environ.get('MO_PLANTED_KNOB', '4'))\n")
    findings, _ = _run([str(p)], rules=["knob-doc"], config=cfg)
    assert len(findings) == 1 and "MO_PLANTED_KNOB" in \
        findings[0].message
    p2 = tmp_path / "feature2.py"
    p2.write_text(
        "import os\n"
        "N = int(os.environ.get('MO_PLANTED_KNOB', '4'))  # mol"
        "int: disable=knob-doc -- baking behind a private flag\n")
    findings2, stats2 = _run([str(p2)], rules=["knob-doc"], config=cfg)
    assert not findings2 and stats2["suppressions_used"] == 1


def test_san_adoption_planted_violation(tmp_path):
    """Planted raw lock in a temp tree fires; a justified suppression
    silences it (the escape hatch stays disciplined)."""
    p = tmp_path / "svc.py"
    p.write_text("import threading\n"
                 "class Svc:\n"
                 "    def __init__(self):\n"
                 "        self._mu = threading.Lock()\n")
    findings, _ = _run([str(p)], rules=["san-adoption"],
                       tests_dir=str(tmp_path))
    assert len(findings) == 1 and findings[0].rule == "san-adoption"
    p2 = tmp_path / "svc2.py"
    p2.write_text(
        "import threading\n"
        "class Svc:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()  # mol"
        "int: disable=san-adoption -- bootstraps before san imports\n")
    findings2, stats2 = _run([str(p2)], rules=["san-adoption"],
                             tests_dir=str(tmp_path))
    assert not findings2 and stats2["suppressions_used"] == 1


def test_lock_discipline_reconciles_runtime_edges(tmp_path):
    """The mosan handshake: a static lexical edge unioned with the
    OPPOSITE edge observed at runtime (observed_lock_edges.json) closes
    a mixed cycle and fails the gate; a runtime edge AGREEING with the
    static order stays clean."""
    p = tmp_path / "mod.py"
    p.write_text("import threading\n"
                 "class C:\n"
                 "    def f(self):\n"
                 "        with self._a_lock:\n"
                 "            with self._b_lock:\n"
                 "                pass\n")
    contradicting = tmp_path / "observed.json"
    contradicting.write_text(json.dumps({"edges": [
        {"from": "C._b_lock", "to": "C._a_lock",
         "count": 3, "site": "runtime drill"}]}))
    cfg = {"lock-discipline":
           {"runtime_edges_path": str(contradicting)}}
    findings, _ = _run([str(p)], rules=["lock-discipline"], config=cfg)
    assert any("lock-order cycle" in f.message for f in findings), \
        [f.format() for f in findings]

    agreeing = tmp_path / "observed2.json"
    agreeing.write_text(json.dumps({"edges": [
        {"from": "C._a_lock", "to": "C._b_lock",
         "count": 3, "site": "runtime drill"}]}))
    cfg2 = {"lock-discipline": {"runtime_edges_path": str(agreeing)}}
    findings2, _ = _run([str(p)], rules=["lock-discipline"],
                        config=cfg2)
    assert not findings2, [f.format() for f in findings2]

    # unreadable export: static graph only, never a crashed gate
    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    cfg3 = {"lock-discipline": {"runtime_edges_path": str(broken)}}
    findings3, _ = _run([str(p)], rules=["lock-discipline"],
                        config=cfg3)
    assert not findings3


# ------------------------------------------------- suppression machinery
def test_suppression_round_trip(tmp_path):
    # NB: the marker is spelled split ("# mol" "int:") throughout this
    # test — test files are themselves in the suppression meta-rule's
    # corpus, and these embedded snippets must not parse as THIS file's
    # suppression comments
    bad = open(os.path.join(FIX, "broad_except", "bad.py")).read()
    # justified suppression on the offending line: silenced + counted
    sup = bad.replace(
        "except Exception:",
        "except Exception:  # mol" "int: disable=broad-except -- "
        "fixture round-trip: swallow() is the documented fallback", 1)
    p = tmp_path / "mod.py"
    p.write_text(sup)
    findings, stats = _run([str(p)], rules=["broad-except"],
                           tests_dir=str(tmp_path))
    assert stats["suppressions_used"] == 1
    assert len(findings) == 1            # only the bare except remains
    assert "except:" in findings[0].message

    # standalone comment (line above) covers the next code line
    sup2 = bad.replace(
        "    except Exception:",
        "    # mol" "int: disable=broad-except -- fixture round-trip:\n"
        "    # justification wraps over two comment lines\n"
        "    except Exception:", 1)
    p2 = tmp_path / "mod2.py"
    p2.write_text(sup2)
    findings2, stats2 = _run([str(p2)], rules=["broad-except"],
                             tests_dir=str(tmp_path))
    assert stats2["suppressions_used"] == 1
    assert len(findings2) == 1

    # suppression WITHOUT justification: not honored + flagged itself
    nosup = bad.replace(
        "except Exception:",
        "except Exception:  # mol" "int: disable=broad-except", 1)
    p3 = tmp_path / "mod3.py"
    p3.write_text(nosup)
    findings3, stats3 = _run([str(p3)], rules=["broad-except"],
                             tests_dir=str(tmp_path))
    assert stats3["suppressions_used"] == 0
    assert any(f.rule == "suppression"
               and "no justification" in f.message for f in findings3)
    assert sum(f.rule == "broad-except" for f in findings3) == 2

    # unknown rule name in a disable comment is flagged
    p4 = tmp_path / "mod4.py"
    p4.write_text("x = 1  # mol" "int: disable=not-a-rule -- whatever\n")
    findings4, _ = _run([str(p4)], tests_dir=str(tmp_path))
    assert any(f.rule == "suppression" and "unknown rule" in f.message
               for f in findings4)

    # disable-file past the 20-line window is inert: flagged, not
    # silently downgraded
    p5 = tmp_path / "mod5.py"
    p5.write_text("\n" * 24
                  + "x = 1  # mol" "int: disable-file=jit-purity -- "
                    "too late in the file\n")
    findings5, _ = _run([str(p5)], tests_dir=str(tmp_path))
    assert any(f.rule == "suppression" and "first" in f.message
               and "20" in f.message for f in findings5)


# --------------------------------------------------- CLI / planted tree
def _cli(args, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "tools.molint"] + args,
        capture_output=True, text=True, cwd=cwd, env=env)


def test_cli_gate_fails_on_planted_violation(tmp_path):
    """The tier-1 gate actually gates: a violation planted in a temp
    tree flips the CLI to exit 1; cleaning the tree flips it back."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    shutil.copy(os.path.join(FIX, "broad_except", "bad.py"),
                pkg / "mod.py")
    r = _cli([str(pkg), "--root", str(tmp_path)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "broad-except" in r.stdout
    assert "finding(s)" in r.stderr
    shutil.copy(os.path.join(FIX, "broad_except", "good.py"),
                pkg / "mod.py")
    r2 = _cli([str(pkg), "--root", str(tmp_path)])
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_cli_json_and_rule_filter(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    shutil.copy(os.path.join(FIX, "broad_except", "bad.py"),
                pkg / "mod.py")
    shutil.copy(os.path.join(FIX, "deadline", "bad.py"),
                pkg / "dl.py")
    r = _cli([str(pkg), "--root", str(tmp_path), "--json",
              "--rule", "deadline-propagation"])
    assert r.returncode == 1
    out = json.loads(r.stdout)
    assert out and all(f["rule"] == "deadline-propagation" for f in out)
    r2 = _cli(["--list-rules"])
    assert r2.returncode == 0
    assert "jit-purity" in r2.stdout
    r3 = _cli([str(pkg), "--rule", "no-such-rule"])
    assert r3.returncode == 2


def test_cli_unparseable_file_is_a_finding(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "broken.py").write_text("def f(:\n")
    # mis-encoded bytes must also land as a parse finding, not a crash
    (pkg / "latin.py").write_bytes(b"# caf\xe9\nx = 1\n")
    r = _cli([str(pkg), "--root", str(tmp_path)])
    assert r.returncode == 1
    assert "broken.py" in r.stdout and "latin.py" in r.stdout
    assert "parse" in r.stdout


def test_partial_scan_skips_corpus_global_rules():
    """Linting a single file (the developer loop) must not mass-report
    the corpus-global gaps: armed-spec resolution needs every trigger
    site, dead-metric detection needs every driver."""
    findings, _ = _run(
        [os.path.join(REPO, "matrixone_tpu", "worker", "client.py")],
        tests_dir=os.path.join(REPO, "tests"))
    assert not findings, "\n".join(f.format() for f in findings)
    findings2, _ = _run(
        [os.path.join(REPO, "matrixone_tpu", "utils", "metrics.py")])
    assert not findings2, "\n".join(f.format() for f in findings2)


def test_unparseable_test_file_surfaces_as_parse_finding(tmp_path):
    """A broken TEST file must be reported itself — silently dropping
    it would erase its armed fault specs and misblame source sites."""
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "mod.py").write_text("x = 1\n")
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "broken.py").write_text("def f(:\n")
    findings, _ = molint.run_checks(
        str(tmp_path), src_paths=[str(src)], tests_dir=str(tdir),
        record=False)
    assert any(f.rule == "parse" and f.path.endswith("broken.py")
               for f in findings)


def test_malformed_suppression_in_test_file_is_flagged(tmp_path):
    """The suppression meta-rule covers the test corpus too: a
    justification-less disable in a test file is reported, not
    silently ignored."""
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "mod.py").write_text("x = 1\n")
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "helper.py").write_text(
        "y = 2  # mol" "int: disable=fault-coverage\n")
    findings, _ = molint.run_checks(
        str(tmp_path), src_paths=[str(src)], tests_dir=str(tdir),
        record=False)
    assert any(f.rule == "suppression"
               and "no justification" in f.message
               and f.path.endswith("helper.py") for f in findings)


# ----------------------------------------------------- shim + precheck
def test_lint_excepts_shim_cli(tmp_path):
    """The legacy CLI still works: exit 0 on the clean repo (also
    asserted by test_chaos), exit 1 + old output format on a planted
    violation."""
    root = tmp_path / "repo"
    (root / "matrixone_tpu").mkdir(parents=True)
    shutil.copy(os.path.join(FIX, "broad_except", "bad.py"),
                root / "matrixone_tpu" / "mod.py")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_excepts.py"),
         str(root)], capture_output=True, text=True)
    assert r.returncode == 1
    assert "unjustified broad except" in r.stdout
    assert "finding(s)" in r.stderr


def test_precheck_runs_molint(tmp_path):
    """precheck wires molint + exit codes; a tiny synthetic root keeps
    this out of the tier-1 wall-clock budget (the REAL repo gate is
    test_repo_tree_is_clean + mo_ctl('lint','run'))."""
    pkg = tmp_path / "matrixone_tpu"
    pkg.mkdir()
    shutil.copy(os.path.join(FIX, "broad_except", "good.py"),
                pkg / "mod.py")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "tools.precheck", "--skip-bench",
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "molint: ok" in r.stdout
    shutil.copy(os.path.join(FIX, "broad_except", "bad.py"),
                pkg / "mod.py")
    r2 = subprocess.run(
        [sys.executable, "-m", "tools.precheck", "--skip-bench",
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert r2.returncode == 1
    assert "broad-except" in r2.stdout


# -------------------------------------------------------- mo_ctl surface
def test_mo_ctl_lint_status_and_run():
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import MemoryFS
    s = Session(catalog=Engine(MemoryFS()))
    st = json.loads(
        s.execute("select mo_ctl('lint','status')").rows()[0][0])
    assert st["checkers"] >= 7
    assert "jit-purity" in st["rules"]
    run = json.loads(
        s.execute("select mo_ctl('lint','run')").rows()[0][0])
    assert run["findings"] == 0
    assert run["files"] > 100
    st2 = json.loads(
        s.execute("select mo_ctl('lint','status')").rows()[0][0])
    assert st2["last_run"]["findings"] == 0
    assert st2["last_run"]["suppressions_used"] >= 3
    with pytest.raises(Exception):
        s.execute("select mo_ctl('lint','bogus')")


# ---------------------------------------------------------- span-hygiene
def test_span_hygiene_fixtures():
    d = os.path.join(FIX, "span_hygiene")
    bad = _fixture_pair("span-hygiene",
                        [os.path.join(d, "bad.py")],
                        [os.path.join(d, "good.py")])
    msgs = " | ".join(f.message for f in bad)
    assert "outside a `with`" in msgs          # unbalanced enter/exit
    assert "outside the RPC fabric" in msgs    # forked injection
    assert "hand-built" in msgs                # clobbered wire key
    assert "`yield` inside" in msgs            # span held across a yield


def test_span_hygiene_good_fixture_uses_a_suppression():
    """The clean fixture carries ONE justified suppression (a
    deliberate out-of-fabric injection) — the rule must honor it."""
    d = os.path.join(FIX, "span_hygiene")
    findings, stats = _run([os.path.join(d, "good.py")],
                           rules=["span-hygiene"])
    assert not findings
    assert stats["suppressions_used"] == 1


def test_span_hygiene_planted_violation(tmp_path):
    """A bare-span plant in a temp tree fires; aliased imports resolve;
    fabric modules stay exempt."""
    p = tmp_path / "feature.py"
    p.write_text("from matrixone_tpu.utils import motrace as _mt\n"
                 "def f(work):\n"
                 "    sp = _mt.span('planted')\n"
                 "    sp.__enter__()\n"
                 "    return work()\n")
    findings, _ = _run([str(p)], rules=["span-hygiene"])
    assert len(findings) == 1 and "_mt.span" in findings[0].message
    # the fabric's OWN definition modules are exempt by config
    fabric = tmp_path / "cluster"
    fabric.mkdir()
    q = fabric / "rpc.py"
    q.write_text("from matrixone_tpu.utils import motrace\n"
                 "def attempt(wire):\n"
                 "    motrace.inject(wire)\n")
    findings2, _ = _run([str(q)], rules=["span-hygiene"])
    assert not findings2


def test_span_hygiene_yield_inside_span(tmp_path):
    """motrace's rule for generators: a `yield` (or `yield from`) in the
    body of `with motrace.span(...)` fires; a nested generator's own
    yield and a span that ends before the yield do not."""
    p = tmp_path / "gen.py"
    p.write_text("from matrixone_tpu.utils import motrace\n"
                 "def held(src):\n"
                 "    for x in src:\n"
                 "        with motrace.span('held'):\n"
                 "            yield x\n"
                 "def delegated(src):\n"
                 "    with motrace.span('delegated'), open('f'):\n"
                 "        yield from src\n"
                 "def clean(src):\n"
                 "    for x in src:\n"
                 "        with motrace.span('clean'):\n"
                 "            y = [i for i in (lambda: (yield))()]\n"
                 "            def inner():\n"
                 "                yield x\n"
                 "        yield y, inner\n")
    findings, _ = _run([str(p)], rules=["span-hygiene"])
    assert sorted(f.lineno for f in findings) == [5, 8]
    assert all("`yield` inside" in f.message for f in findings)


# --------------------------------------------------- framework perf (PR 14)
def test_per_checker_timings_reported():
    """run_checks times every checker (the suite keeps growing — the
    next slow checker must be visible) and surfaces the table through
    stats and mo_ctl('lint','status'), slowest first."""
    findings, stats = molint.run_checks(REPO)
    secs = stats["checker_seconds"]
    assert set(secs) == set(stats["rules"])
    assert all(isinstance(v, float) and v >= 0 for v in secs.values())
    vals = list(secs.values())
    assert vals == sorted(vals, reverse=True)
    st = molint.last_run_status()
    assert st["last_run"]["checker_seconds"] == secs


def test_parse_cache_shares_modules_across_runs():
    """Each file parses ONCE per process: two Project constructions
    over the same tree hand back the SAME PyModule objects (the AST is
    shared across all checkers and across every run_checks caller —
    the per-invocation re-parse was O(invocations x files))."""
    p1 = molint.Project(REPO, [os.path.join(REPO, "matrixone_tpu")])
    p2 = molint.Project(REPO, [os.path.join(REPO, "matrixone_tpu")])
    assert len(p1.modules) == len(p2.modules) > 50
    assert all(a is b for a, b in zip(p1.modules, p2.modules))


def test_parse_cache_invalidates_on_edit(tmp_path):
    """An edited file re-parses (mtime/size keyed) — the cache can
    never serve a stale AST for a changed source."""
    p = tmp_path / "m.py"
    p.write_text("x = 1\n")
    m1 = molint._load_module(str(p), "m.py")
    m2 = molint._load_module(str(p), "m.py")
    assert m1 is m2
    os.utime(str(p), (0, 0))          # force a different mtime
    p.write_text("x = 2  # changed\n")
    m3 = molint._load_module(str(p), "m.py")
    assert m3 is not m1
    assert "changed" in m3.text
