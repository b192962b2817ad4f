"""Repo-native analysis suite gate (docs/ANALYSIS.md, `make analyze`).

Two halves:

1. **counter-proofs** — every checker must FLAG its planted violation
   under tests/fixtures/analysis/ (a checker that cannot find the bug
   it exists for is worse than no checker: it certifies silence);
   negative controls prove the clean twins stay clean.
2. **the gate itself** — the full suite over the live repo must pass
   with an empty-or-justified baseline, inside the fast budget
   (<60 s, no jax import, no model loads).
"""

import _thread
import os
import pathlib
import threading
import time

import pytest

from semantic_router_tpu.analysis import (
    BASELINE_PATH,
    REPO_ROOT,
    run_all,
    static_lock_edges,
)
from semantic_router_tpu.analysis import jitpurity, knobs, locks
from semantic_router_tpu.analysis import metrics_xref, witness
from semantic_router_tpu.analysis.findings import (
    Finding,
    Suppression,
    apply_baseline,
    parse_baseline,
)

FIXDIR = str(pathlib.Path(__file__).parent / "fixtures" / "analysis")


# -- static lock analysis --------------------------------------------------


class TestLockChecker:
    def test_flags_planted_cycle(self):
        findings, graph = locks.check(FIXDIR, subdirs=("lockfix",))
        cycles = [f for f in findings if f.key.startswith("cycle:")]
        assert cycles, "planted a→b / b→a inversion must be flagged"
        assert any("mod_a.py" in f.key for f in cycles)

    def test_flags_lock_held_foreign_call(self):
        findings, _ = locks.check(FIXDIR, subdirs=("lockfix",))
        held = [f for f in findings if f.key.startswith("held-call:")]
        assert held, "lock-held call into mod_c.Helper must be flagged"
        assert any("Helper.bump" in f.key for f in held)

    def test_clean_nesting_not_flagged(self):
        findings, graph = locks.check(FIXDIR, subdirs=("lockfix",))
        # clean.py's one-directional nesting contributes edges but no
        # cycle and no held-call
        clean_keys = [f for f in findings if "clean.py" in f.key]
        assert clean_keys == []
        assert any("clean.py" in a for (a, b) in graph.edges)

    def test_census_sees_condition_alias(self):
        # the batcher's Condition(self._lock) must resolve to the SAME
        # site as the lock it wraps, not a phantom second lock
        an = locks.LockAnalyzer(
            os.path.join(REPO_ROOT, "semantic_router_tpu"))
        an.collect()
        batcher = [c for c in an.census.classes
                   if c.name == "DynamicBatcher"]
        assert batcher and batcher[0].aliases.get("_wake") == "_lock"

    def test_repo_graph_populates(self):
        _f, graph = locks.check(
            os.path.join(REPO_ROOT, "semantic_router_tpu"))
        assert len(graph.sites) >= 20, "lock census lost the repo"


# -- jit purity ------------------------------------------------------------


class TestJitPurity:
    def test_flags_planted_impurities(self):
        findings = jitpurity.check(FIXDIR, subdirs=("jitfix",))
        keys = {f.key for f in findings}
        # keys are churn-stable: file:function:pattern, NO line numbers
        # (a baselined suppression must survive unrelated edits)
        assert "jitfix/impure.py:entry:item" in keys, keys
        assert "jitfix/impure.py:entry:time.time" in keys, keys
        # float() on a traced value inside the transitively-reached
        # helper — proves cross-function reachability
        assert "jitfix/impure.py:_inner:float" in keys, keys
        assert all(os.path.basename(f.path) != "pure.py"
                   for f in findings)
        # the display line still rides on the finding
        assert all(f.line > 0 for f in findings)

    def test_shape_arithmetic_exempt(self):
        findings = jitpurity.check(FIXDIR, subdirs=("jitfix",))
        assert not [f for f in findings
                    if os.path.basename(f.path) == "pure.py"]

    def test_repo_roots_resolved(self):
        # the real engine's jit'd closures must be discovered (the
        # checker silently finding zero roots would certify nothing)
        root = os.path.join(REPO_ROOT, "semantic_router_tpu")
        mods = {}
        for p in jitpurity._iter_py(root, jitpurity.DEFAULT_SUBDIRS):
            m = jitpurity._collect_module(root, p,
                                          "semantic_router_tpu")
            if m is not None:
                mods[m.rel] = m
        roots = [(rel, name) for rel, m in mods.items()
                 for name, _ln in jitpurity._jit_roots(m)
                 if name in m.defs]
        assert len(roots) >= 8, roots


# -- knob wiring -----------------------------------------------------------


def _knobfix_cfg():
    return knobs.KnobCheckConfig(
        root=os.path.join(FIXDIR, "knobfix"),
        schema=os.path.join("pkg", "config", "schema.py"),
        package="pkg",
        bootstrap=os.path.join("pkg", "runtime", "bootstrap.py"),
        docs="docs")


class TestKnobChecker:
    def test_flags_planted_violations(self):
        keys = {f.key for f in knobs.check(_knobfix_cfg())}
        assert "dead-field:orphan_block" in keys
        assert "normalizer-unapplied:ghost_config" in keys
        assert "apply-once:apply_foo_knobs" in keys
        assert ("undocumented-knob:foo_config:"
                "undocumented_secret_knob") in keys
        assert any(k.startswith("knob-bypass:") and "app.py" in k
                   for k in keys)

    def test_wired_surface_stays_clean(self):
        keys = {f.key for f in knobs.check(_knobfix_cfg())}
        assert "dead-field:wired_block" not in keys
        assert "normalizer-unapplied:foo_config" not in keys
        assert ("undocumented-knob:foo_config:documented_knob"
                not in keys)


# -- metric xref -----------------------------------------------------------


def _metricfix_cfg():
    return metrics_xref.XrefConfig(
        root=os.path.join(FIXDIR, "metricfix"),
        package="pkg",
        reference_sources=(("docs", "docs", (".md",)),))


class TestMetricsXref:
    def test_flags_ghost_and_orphan(self):
        keys = {f.key for f in metrics_xref.check(_metricfix_cfg())}
        assert "ghost:llm_fix_ghost_total" in keys
        assert "undocumented:llm_fix_orphan_total" in keys
        assert "ghost:llm_fix_requests_total" not in keys
        assert "undocumented:llm_fix_requests_total" not in keys

    def test_histogram_suffixes_resolve(self):
        declared = {"llm_x_seconds": ("m.py", 1)}
        assert metrics_xref._base_name("llm_x_seconds_bucket",
                                       declared) == "llm_x_seconds"
        assert metrics_xref._base_name("llm_x_seconds_count",
                                       declared) == "llm_x_seconds"

    def test_repo_declarations_found(self):
        declared = metrics_xref.collect_declared(
            REPO_ROOT, "semantic_router_tpu")
        assert "llm_model_requests_total" in declared
        assert "llm_queue_pressure" in declared  # external-metrics item


# -- baseline hygiene ------------------------------------------------------


class TestBaseline:
    def test_parse_roundtrip(self):
        entries = parse_baseline(
            '# comment\n[[suppress]]\nchecker = "locks"\n'
            'key = "cycle:x"\nreason = "probe ordering is guarded"\n')
        assert len(entries) == 1 and entries[0].checker == "locks"

    def test_malformed_rejected(self):
        with pytest.raises(ValueError):
            parse_baseline("[[suppress]]\nchecker = unquoted\n")
        with pytest.raises(ValueError):
            parse_baseline('key = "orphan line"\n')

    def test_missing_reason_is_gate_error(self):
        rep = apply_baseline(
            [Finding("locks", "cycle:x", "m")],
            [Suppression("locks", "cycle:x", reason="")])
        assert rep.errors and not rep.findings

    def test_stale_suppression_is_gate_error(self):
        rep = apply_baseline(
            [], [Suppression("locks", "cycle:gone", reason="old")])
        assert any("stale" in e for e in rep.errors)

    def test_match_suppresses(self):
        rep = apply_baseline(
            [Finding("knobs", "dead-field:x", "m")],
            [Suppression("knobs", "dead-field:x", reason="migration")])
        assert rep.ok and len(rep.suppressed) == 1


# -- runtime witness -------------------------------------------------------


def _wl(site):
    return witness._WitnessLock(_thread.allocate_lock(), site,
                                reentrant=False)


class TestWitness:
    def test_records_inversion_across_threads(self):
        a = _wl("fx/wa.py:1")
        b = _wl("fx/wb.py:2")
        with witness.capture() as cap:
            def t1():
                with a:
                    with b:
                        pass

            def t2():
                with b:
                    with a:
                        pass

            for fn in (t1, t2):
                th = threading.Thread(target=fn)
                th.start()
                th.join()
        assert ("fx/wa.py:1", "fx/wb.py:2") in cap.edges
        assert ("fx/wb.py:2", "fx/wa.py:1") in cap.edges
        finds = locks.cycle_findings(cap.edges, checker="lock-order")
        assert any(f.key.startswith("cycle:") for f in finds)

    def test_capture_removes_planted_edges_from_global(self):
        a = _wl("fx/ca.py:1")
        b = _wl("fx/cb.py:2")
        with witness.capture() as cap:
            with a:
                with b:
                    pass
        assert cap.edges
        assert ("fx/ca.py:1", "fx/cb.py:2") not in witness.runtime_edges()

    def test_merged_static_runtime_cycle(self):
        a = _wl("fx/ma.py:1")
        b = _wl("fx/mb.py:2")
        with witness.capture() as cap:
            with a:
                with b:
                    pass
        merged = dict(cap.edges)
        # the opposite direction exists only STATICALLY — neither graph
        # alone has the cycle
        merged[("fx/mb.py:2", "fx/ma.py:1")] = "static"
        finds = locks.cycle_findings(merged, checker="lock-order")
        assert any(f.key.startswith("cycle:") for f in finds)
        assert not locks.cycle_findings(cap.edges)

    def test_reentrant_rlock_no_self_edge(self):
        r = witness._WitnessLock(threading._PyRLock(), "fx/r.py:1",
                                 reentrant=True)
        with witness.capture() as cap:
            with r:
                with r:   # reentrant: must not record anything
                    pass
        assert cap.edges == {}

    def test_condition_over_witnessed_lock(self):
        was = witness.enabled()
        if not was:
            witness.install()
        try:
            lk = threading.Lock()
            assert isinstance(lk, witness._WitnessLock)
            cond = threading.Condition(lk)
            with witness.capture():
                with cond:
                    cond.notify_all()
                    assert cond.wait(0.01) is False
            # default Condition (wrapped RLock) too
            cond2 = threading.Condition()
            with witness.capture():
                with cond2:
                    assert cond2.wait(0.01) is False
        finally:
            if not was:
                witness.uninstall()

    def test_out_of_repo_locks_stay_raw(self):
        was = witness.enabled()
        if not was:
            witness.install()
        try:
            # simulate a foreign caller: exec a Lock() construction
            # from a synthetic out-of-repo filename
            ns = {"threading": threading}
            code = compile("lk = threading.Lock()",
                           "/usr/lib/python3.10/foreign.py", "exec")
            exec(code, ns)
            assert not isinstance(ns["lk"], witness._WitnessLock)
        finally:
            if not was:
                witness.uninstall()

    def test_thread_leak_gate(self):
        base = witness.thread_snapshot()
        stop = threading.Event()
        t = threading.Thread(target=stop.wait, name="leaky-fixture",
                             daemon=True)
        t.start()
        finds = witness.check_thread_leaks(base, grace_s=0.2)
        assert any("leaky-fixture" in f.key for f in finds)
        stop.set()
        t.join()
        assert witness.check_thread_leaks(base, grace_s=2.0) == []


# -- the gate itself -------------------------------------------------------


class TestAnalyzeGate:
    def test_repo_passes_with_justified_baseline(self):
        report = run_all()
        assert report.ok, "\n" + report.render()

    def test_budget_under_60s_no_jax(self):
        t0 = time.perf_counter()
        run_all()
        wall = time.perf_counter() - t0
        assert wall < 60.0, f"analysis suite took {wall:.1f}s"
        # the suite must never pull jax into a process that didn't
        # already have it (conftest imports jax; check the module
        # graph of the analysis package instead)
        import semantic_router_tpu.analysis as pkg
        src_dir = os.path.dirname(pkg.__file__)
        for fn in os.listdir(src_dir):
            if fn.endswith(".py"):
                with open(os.path.join(src_dir, fn)) as f:
                    src = f.read()
                assert "import jax" not in src, fn

    def test_static_edges_exported_for_witness(self):
        edges = static_lock_edges()
        assert isinstance(edges, dict)

    def test_static_and_witness_keys_share_one_root(self):
        """The cross-proof merge only works if both graphs name a lock
        site identically: static keys must be REPO-root-relative
        (semantic_router_tpu/...), exactly what the witness derives
        from a construction frame in the same file."""
        _f, graph = locks.check(
            os.path.join(REPO_ROOT, "semantic_router_tpu"),
            rel_root=REPO_ROOT)
        assert graph.sites, "lock census empty"
        for key in graph.sites:
            assert key.startswith("semantic_router_tpu" + os.sep), key
        # witness side: construct a lock attributed to a repo file via
        # a compiled filename and confirm the same keying convention
        site_holder = {}
        real = os.path.join(REPO_ROOT, "semantic_router_tpu",
                            "engine", "batcher.py")
        was = witness.enabled()
        if not was:
            witness.install()
        try:
            ns = {"threading": threading, "out": site_holder}
            code = compile("out['lk'] = threading.Lock()", real, "exec")
            exec(code, ns)
            lk = site_holder["lk"]
            assert isinstance(lk, witness._WitnessLock)
            assert lk.site.startswith(
                os.path.join("semantic_router_tpu", "engine",
                             "batcher.py") + ":"), lk.site
        finally:
            if not was:
                witness.uninstall()

    def test_baseline_file_entries_all_reasoned(self):
        if not os.path.exists(BASELINE_PATH):
            return
        with open(BASELINE_PATH) as f:
            entries = parse_baseline(f.read())
        for e in entries:
            assert e.reason.strip(), (
                f"baseline entry ({e.checker}, {e.key}) lacks a "
                f"justification")


# -- shared-state race detector: static lockset half -----------------------


class TestRaceChecker:
    def _findings(self):
        from semantic_router_tpu.analysis import races

        return races.check(FIXDIR, subdirs=("racefix",))

    def test_flags_guard_violation(self):
        keys = {f.key for f in self._findings()}
        assert ("guard-violation:racefix/mod.py:Guarded._items"
                "@put_fast") in keys, keys

    def test_flags_publish_race(self):
        keys = {f.key for f in self._findings()}
        assert ("publish-race:racefix/mod.py:Counting.hits"
                "@record") in keys, keys

    def test_flags_escaped_collection(self):
        keys = {f.key for f in self._findings()}
        assert "escape:racefix/mod.py:Escaping._rows@rows" in keys, keys

    def test_flags_annotated_escape(self):
        # `self._table: dict = {}` — the AnnAssign flavor the live
        # repo uses for most collections must census identically
        keys = {f.key for f in self._findings()}
        assert ("escape:racefix/mod.py:AnnotatedEscape._table"
                "@table") in keys, keys

    def test_clean_twins_stay_clean(self):
        # the fully-guarded class, the locked RMW, the RCU snapshot,
        # the copy-return, and the _locked-helper idiom: zero findings
        bad = [f for f in self._findings() if "clean.py" in f.key]
        assert bad == [], [f.key for f in bad]

    def test_guard_inference_majority(self):
        from semantic_router_tpu.analysis import races

        an = races.RaceAnalyzer(FIXDIR, subdirs=("racefix",))
        an.analyze()
        prof = an.profiles[("racefix/mod.py", "Guarded", "_items")]
        assert prof.guard is not None
        assert "mod.py" in prof.guard

    def test_locked_helper_inlined_under_guard(self):
        from semantic_router_tpu.analysis import races

        an = races.RaceAnalyzer(FIXDIR, subdirs=("racefix",))
        an.analyze()
        prof = an.profiles[("racefix/clean.py", "LockedHelperClean",
                            "_pending")]
        assert prof.accesses and all(a.held for a in prof.accesses), \
            sorted((a.method, a.kind, tuple(a.held))
                   for a in prof.accesses)

    def test_repo_profiles_populate(self):
        from semantic_router_tpu.analysis import races

        an = races.RaceAnalyzer(
            os.path.join(REPO_ROOT, "semantic_router_tpu"),
            rel_root=REPO_ROOT)
        an.analyze()
        assert len(an.profiles) >= 50, "lockset pass lost the repo"
        guarded = [p for p in an.profiles.values()
                   if p.guard is not None]
        assert len(guarded) >= 10, "no guards inferred on the live repo"

    # -- module-level globals (ISSUE 15 satellite) -----------------------

    def test_flags_module_global_guard_violation(self):
        # bare module state (the _MEMO + _MEMO_LOCK idiom) written
        # without its majority lock — the class pass's blind spot
        keys = {f.key for f in self._findings()}
        assert ("guard-violation:racefix/modglobal.py:_REGISTRY"
                "@put_fast") in keys, keys

    def test_nested_scope_does_not_shadow_module_global(self):
        # a nested def binding the name in ITS scope must not mask the
        # outer function's unguarded write (ast.walk would leak the
        # nested local into the outer scope set)
        keys = {f.key for f in self._findings()}
        assert ("guard-violation:racefix/modglobal.py:_REGISTRY"
                "@put_fast_shadowed") in keys, keys

    def test_tuple_unpack_global_write_recorded(self):
        # `_STATE, _rest = ...` writes the declared global exactly like
        # the plain-assign form — a Tuple target must not slip past
        keys = {f.key for f in self._findings()}
        assert ("guard-violation:racefix/modglobal.py:_STATE"
                "@swap_state") in keys, keys

    def test_flags_module_global_publish_race(self):
        keys = {f.key for f in self._findings()}
        assert ("publish-race:racefix/modglobal.py:_HITS"
                "@record_hit") in keys, keys

    def test_module_global_clean_twins_stay_clean(self):
        # guarded access, locked RMW, module-RCU whole-object publish,
        # the locked-helper inline, and a read-only constant: zero
        # findings (covered by test_clean_twins_stay_clean's filter
        # too — this pins the module file explicitly)
        bad = [f for f in self._findings()
               if "modglobal_clean.py" in f.key]
        assert bad == [], [f.key for f in bad]

    def test_module_global_guard_inference(self):
        from semantic_router_tpu.analysis import races

        an = races.ModuleGlobalAnalyzer(FIXDIR, subdirs=("racefix",))
        an.analyze()
        prof = an.profiles[("racefix/modglobal.py", "_REGISTRY")]
        assert prof.guard is not None and "modglobal.py" in prof.guard

    def test_module_global_live_repo_sees_leaf_digest_memo(self):
        # the live-repo anchor: engine/classify.py's content-digest
        # memo is exactly the module-global shape — the pass must see
        # it AND infer its lock as the guard (every access is locked)
        from semantic_router_tpu.analysis import races

        an = races.ModuleGlobalAnalyzer(
            os.path.join(REPO_ROOT, "semantic_router_tpu"),
            rel_root=REPO_ROOT)
        an.analyze()
        prof = an.profiles.get(
            (os.path.join("semantic_router_tpu", "engine",
                          "classify.py"), "_LEAF_DIGESTS"))
        assert prof is not None, sorted(an.profiles)
        assert prof.guard is not None

    def test_merge_runtime_adopts_static_key(self):
        from semantic_router_tpu.analysis import races
        from semantic_router_tpu.analysis.findings import Finding

        static = [Finding("races", "guard-violation:m.py:C.x@w",
                          "static msg", path="m.py", line=7)]
        runtime = [
            Finding("races", "lockset:C.x", "runtime msg",
                    path="m.py", line=7),      # same site: cross-proof
            Finding("races", "lockset:D.y", "runtime only",
                    path="n.py", line=3),
        ]
        merged = races.merge_runtime(static, runtime)
        assert merged[0].key == "guard-violation:m.py:C.x@w"
        assert "CROSS-PROVEN" in merged[0].message
        assert merged[1].key == "lockset:D.y"


# -- API-surface cross-check -----------------------------------------------


def _apifix_cfg():
    from semantic_router_tpu.analysis import api_xref

    return api_xref.ApiXrefConfig(
        root=os.path.join(FIXDIR, "apifix"),
        server=os.path.join("pkg", "server.py"),
        openapi=os.path.join("pkg", "openapi.py"),
        docs_sources=("docs",))


class TestApiXref:
    def test_flags_planted_drift(self):
        from semantic_router_tpu.analysis import api_xref

        keys = {f.key for f in api_xref.check(_apifix_cfg())}
        assert "ghost-route:GET /debug/ghost" in keys, keys
        assert "unregistered-route:/debug/hidden" in keys, keys
        assert "unspecified-route:GET /debug/nometa" in keys, keys
        assert "undocumented-route:GET /debug/nodocs" in keys, keys
        assert "ghost-meta:GET /debug/removed" in keys, keys

    def test_clean_routes_not_flagged(self):
        from semantic_router_tpu.analysis import api_xref

        keys = {f.key for f in api_xref.check(_apifix_cfg())}
        for k in keys:
            assert "/debug/ok" not in k, keys
            assert "/debug/items" not in k, keys   # template route
            assert "/metrics" not in k, keys

    def test_repo_catalog_and_handlers_found(self):
        from semantic_router_tpu.analysis import api_xref

        server = os.path.join(REPO_ROOT, "semantic_router_tpu",
                              "router", "server.py")
        catalog = api_xref.collect_catalog(
            server, api_xref._SCOPE_PREFIXES)
        assert ("GET", "/debug/runtime") in catalog
        assert ("GET", "/metrics/external") in catalog
        exact, starts = api_xref.collect_handlers(
            server, api_xref._SCOPE_PREFIXES)
        assert "/debug/runtime" in exact
        assert any(p.startswith("/debug/decisions") for p in starts)

    def test_repo_meta_covers_debug_surface(self):
        from semantic_router_tpu.analysis import api_xref

        meta = api_xref.collect_meta(
            os.path.join(REPO_ROOT, "semantic_router_tpu", "router",
                         "openapi.py"),
            api_xref._SCOPE_PREFIXES)
        # the landing fix: every catalog debug route has real metadata
        for route in [("GET", "/debug/runtime"), ("GET", "/debug/slo"),
                      ("GET", "/debug/flywheel"),
                      ("POST", "/debug/decisions/{id}/replay")]:
            assert route in meta, route

    def test_pipe_group_docs_shorthand_expands(self):
        from semantic_router_tpu.analysis import api_xref

        text = api_xref.collect_doc_mentions(REPO_ROOT, ("docs",))
        # OBSERVABILITY.md documents the profiler POSTs as
        # start|stop|xla-dump — the expansion must cover each
        assert "/debug/profiler/stop" in text
        assert "/debug/profiler/xla-dump" in text


# -- runtime-event cross-ref -----------------------------------------------


def _eventfix_cfg():
    from semantic_router_tpu.analysis import events_xref

    return events_xref.EventsXrefConfig(
        root=os.path.join(FIXDIR, "eventfix"),
        package="pkg",
        events_module=os.path.join("pkg", "events.py"),
        docs=(os.path.join("docs", "OBSERVABILITY.md"),))


class TestEventsXref:
    def test_flags_orphan_publish_and_ghost_subscription(self):
        from semantic_router_tpu.analysis import events_xref

        keys = {f.key for f in events_xref.check(_eventfix_cfg())}
        assert "orphan-publish:fix_orphan_stage" in keys, keys
        assert "ghost-subscription:fix_ghost_stage" in keys, keys

    def test_consumed_and_documented_stages_clean(self):
        from semantic_router_tpu.analysis import events_xref

        keys = {f.key for f in events_xref.check(_eventfix_cfg())}
        assert "orphan-publish:fix_clean_stage" not in keys
        assert "orphan-publish:fix_documented_stage" not in keys

    def test_repo_stages_collected(self):
        from semantic_router_tpu.analysis import events_xref

        stages = events_xref.collect_stages(
            os.path.join(REPO_ROOT, "semantic_router_tpu", "runtime",
                         "events.py"))
        assert "ENGINE_READY" in stages
        assert stages["ENGINE_READY"][0] == "engine_ready"
        assert len(stages) >= 10

    def test_repo_publishers_and_consumers_found(self):
        from semantic_router_tpu.analysis import events_xref

        cfg = events_xref.EventsXrefConfig(root=REPO_ROOT)
        stages = events_xref.collect_stages(
            os.path.join(REPO_ROOT, cfg.events_module))
        pubs, subs = events_xref.scan_usage(cfg, stages)
        assert "engine_ready" in pubs
        assert "engine_failed" in subs, \
            "the resilience controller's engine_failed filter is gone"


# -- runtime access witness (the race detector's runtime half) -------------


class _RaceyBox:
    """Fixture class for the access-witness drives."""

    def __init__(self):
        self.lock = threading.Lock()
        self.value = 0


def _drive_threads(*fns):
    """Run the writer callables on OVERLAPPING threads (a barrier keeps
    both alive at once: sequential start/join lets CPython recycle the
    dead thread's ident, which would make two writers look like one to
    the per-thread access bookkeeping)."""
    barrier = threading.Barrier(len(fns))

    def wrap(fn):
        def run():
            barrier.wait(timeout=5)
            fn()
        return run

    threads = [threading.Thread(target=wrap(fn)) for fn in fns]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


class TestAccessWitness:
    def _installed(self):
        was = witness.enabled()
        if not was:
            witness.install()
        return was

    def test_two_thread_unlocked_writes_record_empty_lockset(self):
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1)
            box = _RaceyBox()
            with witness.access_capture() as cap:
                def writer():
                    for _ in range(4):
                        box.value = 1

                _drive_threads(writer, writer)
            assert "_RaceyBox.value" in cap.races, cap.races
            pair = cap.races["_RaceyBox.value"]
            assert "test_analysis.py" in pair["site"]
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_common_lock_suppresses_race(self):
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1)
            box = _RaceyBox()
            # the box's lock must be a WITNESSED lock for the lockset
            # to be visible — construct it here (repo-relative site)
            box.lock = threading.Lock()
            with witness.access_capture() as cap:
                def writer():
                    for _ in range(4):
                        with box.lock:
                            box.value = 1

                _drive_threads(writer, writer)
            assert "_RaceyBox.value" not in cap.races, cap.races
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_exclusive_single_thread_never_flags(self):
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1)
            box = _RaceyBox()
            with witness.access_capture() as cap:
                for _ in range(50):
                    box.value += 1   # one thread, no locks: exclusive
            assert cap.races == {}
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_watched_dict_mutation_recorded(self):
        was = self._installed()
        try:
            box = _RaceyBox()
            box.table = {}
            proxy = witness.watch_dict_attr(box, "table")
            with witness.access_capture() as cap:
                def writer(k):
                    def run():
                        for i in range(4):
                            proxy[k] = i
                    return run

                _drive_threads(writer("a"), writer("b"))
            assert "_RaceyBox.table" in cap.races, cap.races
        finally:
            if not was:
                witness.uninstall()

    def test_check_access_races_findings_shape(self):
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1)
            box = _RaceyBox()
            with witness.access_capture() as cap:
                def writer():
                    box.value = 2

                _drive_threads(writer, writer)
                finds = witness.check_access_races()
                assert any(f.key == "lockset:_RaceyBox.value"
                           and f.checker == "races"
                           and f.path.startswith("tests")
                           and f.line > 0
                           for f in finds), [f.key for f in finds]
            # capture scope: the planted race left the global store
            assert "_RaceyBox.value" in cap.races
            assert not any(f.key == "lockset:_RaceyBox.value"
                           for f in witness.check_access_races())
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_sampling_paces_recording(self):
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1000)
            box = _RaceyBox()
            with witness.access_capture() as cap:
                def writer():
                    for _ in range(10):
                        box.value = 3   # 20 writes << sample period

                _drive_threads(writer, writer)
            assert cap.races == {}   # nothing sampled, nothing tracked
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_read_write_race_surfaces(self):
        """The read-instrumentation satellite (ISSUE 15): a lock-free
        WRITE racing a lock-free READ on another thread must flag —
        write-write pairs were the only shape the witness saw before.
        Sequenced deterministically: a reader thread flips the object
        shared (read transition → no writer yet), then the main thread
        writes in the shared phase."""
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1)
            box = _RaceyBox()
            with witness.access_capture() as cap:
                t = threading.Thread(
                    target=lambda: [box.value for _ in range(8)])
                t.start()
                t.join()
                box.value = 5   # shared-phase write, no lock
            pair = cap.races.get("_RaceyBox.value")
            assert pair is not None, cap.races
            assert {pair["kind"], pair["other_kind"]} == \
                {"read", "write"}, pair
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_guarded_publish_with_raw_readers_stays_clean(self):
        """The RCU-snapshot idiom live: a writer that always publishes
        under its lock, raw lock-free readers — the exact shape PR 12
        converted the hot paths TO.  The read witness must share the
        static pass's write bias and stay quiet (caught live on
        StatePlane.last_members before this gate existed)."""
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1)
            box = _RaceyBox()
            box.lock = threading.Lock()  # witnessed construction site
            stop = threading.Event()

            def publisher():
                while not stop.is_set():
                    with box.lock:
                        box.value = object()

            with witness.access_capture() as cap:
                t = threading.Thread(target=publisher)
                t.start()
                for _ in range(200):
                    _ = box.value   # raw read, no lock
                stop.set()
                t.join(timeout=5)
            assert "_RaceyBox.value" not in cap.races, cap.races
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_read_only_sharing_never_flags(self):
        """Init-written then read-only-shared objects stay clean: the
        exclusive-phase write never counts as a racy writer (Eraser's
        shared vs shared-modified split)."""
        was = self._installed()
        try:
            witness.watch_class(_RaceyBox, sample=1)
            box = _RaceyBox()   # __init__ writes .value on this thread
            with witness.access_capture() as cap:
                def reader():
                    for _ in range(8):
                        _ = box.value

                _drive_threads(reader, reader)
            assert "_RaceyBox.value" not in cap.races, cap.races
        finally:
            witness.unwatch(_RaceyBox)
            if not was:
                witness.uninstall()

    def test_late_read_arming_upgrades_write_only_watch(self):
        """Per-dunder idempotency: a class first watched write-only
        must still gain read instrumentation from a later reads=True
        arming (the session-start re-arm path)."""
        was = self._installed()
        try:
            class _Local:
                pass

            witness.watch_class(_Local, sample=1, reads=False)
            assert not getattr(_Local.__getattribute__,
                               "_vsr_watched", False)
            witness.watch_class(_Local, sample=1)
            assert getattr(_Local.__getattribute__, "_vsr_watched",
                           False)
            witness.unwatch(_Local)
            assert not getattr(_Local.__getattribute__,
                               "_vsr_watched", False)
            assert not getattr(_Local.__setattr__, "_vsr_watched",
                               False)
        finally:
            if not was:
                witness.uninstall()

    def test_unwatch_restores_getattribute(self):
        was = self._installed()
        try:
            class _Local:
                pass

            witness.watch_class(_Local, sample=1)
            assert getattr(_Local.__getattribute__, "_vsr_watched",
                           False)
            witness.unwatch(_Local)
            assert not getattr(_Local.__getattribute__, "_vsr_watched",
                               False)
            assert not getattr(_Local.__setattr__, "_vsr_watched",
                               False)
        finally:
            if not was:
                witness.uninstall()

    def test_overhead_within_witness_bound(self, monkeypatch):
        """What bounds the watch's cost is the SAMPLING: of the 200
        attribute writes of a smoke-shaped workload (they ride lock
        acquisitions and real compute) an armed class sends one in
        ``sample`` down ``record_access`` and the others past it, its
        reads one in ``sample * _READ_SAMPLE_FACTOR``, and an unarmed
        class none.  Counted, not timed: the wall-clock ratio of the two
        (the witness's <=5% envelope, about 1.03 alone on a core) read
        1.05-1.06 under six test workers; it is printed."""
        was = self._installed()
        recorded = {"write": 0, "read": 0}
        record = witness.record_access

        def counting(obj, attr, depth=2, label=None, kind="write"):
            recorded[kind] += 1
            # one frame deeper than the watch's wrapper reckons
            record(obj, attr, depth + 1, label, kind)

        monkeypatch.setattr(witness, "record_access", counting)
        monkeypatch.delenv("VSR_READ_SAMPLE", raising=False)

        def workload(box):
            acc = 0
            for i in range(200):
                with box.lock:
                    # ~50us of work per attribute write: the smoke
                    # suites do far MORE per write (a device step)
                    for j in range(1000):
                        acc += j * j
                    box.value = i
            return acc

        def timed(fn, *a):
            t0 = time.perf_counter()
            fn(*a)
            return time.perf_counter() - t0

        try:
            base_box = _RaceyBox()

            class _ArmedBox(_RaceyBox):
                pass

            armed_box = _ArmedBox()
            witness.watch_class(_ArmedBox, sample=8)
            base = timed(workload, base_box)
            assert recorded == {"write": 0, "read": 0}
            armed = timed(workload, armed_box)
            # 200 writes of ``value`` and 200 reads of ``lock``
            assert recorded == {
                "write": 200 // 8,
                "read": 200 // (8 * witness._READ_SAMPLE_FACTOR)}
            for _ in range(4):  # interleaved, the least of five
                base = min(base, timed(workload, base_box))
                armed = min(armed, timed(workload, armed_box))
        finally:
            witness.unwatch(_ArmedBox)
            witness.reset_access()
            if not was:
                witness.uninstall()
        print(f"sampled access watch cost {armed / base:.3f}x on the "
              f"smoke-shaped workload, this test's counter included (the "
              f"envelope is 1.05x)")
