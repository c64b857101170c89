"""Concurrency and invariant analysis (ISSUE 15): the veles_lint
static passes, the lock-order witness, and the shutdown-ordering
contract they pin.

Three layers under test:

- the LINTER itself, against fixture modules with seeded violations
  (``tests/lint_fixtures/``): each must be caught at exactly the
  marked file:line, the clean fixture at zero findings, and the
  suppression hygiene (reason required, stale suppressions flagged)
  must hold;
- the FULL TREE: ``tools/veles_lint.py --check`` semantics ride
  tier-1 here, so a future unguarded access or impure traced body
  fails the suite, not a review round;
- the RUNTIME witness (``serving/lockcheck.py``): a deliberately
  inverted acquisition order and a lock held across a device-dispatch
  site are caught with both stacks, and the serving stack's stop()
  ordering — retry timers, the hedge loop, the health prober, the
  telemetry sampler — runs under an armed witness without violations
  or wedged futures.
"""

import os
import re
import sys
import threading
import time

import numpy
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

import veles_lint  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")

EXPECT_RE = re.compile(r"#\s*EXPECT-LINT\s+([\w-]+)")


def _expected(name):
    """[(line, check)] markers in a fixture module."""
    out = []
    with open(os.path.join(FIXTURES, name), "r",
              encoding="utf-8") as f:
        for i, line in enumerate(f, start=1):
            m = EXPECT_RE.search(line)
            if m:
                out.append((i, m.group(1)))
    return out


def _run_fixture(name, purity=False, census=(), fixtures=()):
    """(findings, suppressions) for one fixture module through the
    full check (lock + purity + recompile + host-sync + lifecycle +
    suppression hygiene).  ``census``/``fixtures`` point the census
    cross-check at fixture stand-ins."""
    findings, sups, _stats = veles_lint.run_check(
        root=FIXTURES, modules=(name,),
        purity_modules=(name,) if purity else (), registry=(),
        census_modules=census, jit_guard_fixtures=fixtures,
        hot_path_registry=())
    return findings, sups


class TestLintFixtures:
    def test_clean_fixture_zero_findings(self):
        findings, sups = _run_fixture("clean_module.py", purity=True)
        assert findings == [], "\n".join(map(repr, findings))
        assert sups == []

    def test_unlocked_guarded_access_caught_at_line(self):
        findings, _ = _run_fixture("bad_guarded.py")
        got = sorted((f.line, f.check) for f in findings)
        assert got == sorted(_expected("bad_guarded.py")), \
            "\n".join(map(repr, findings))
        assert all(f.file == "bad_guarded.py" for f in findings)
        # the messages name the attribute AND the missing lock
        assert any("_items" in f.message and "_lock" in f.message
                   for f in findings)

    def test_broken_caller_holds_chain_caught(self):
        findings, _ = _run_fixture("bad_chain.py")
        got = [(f.line, f.check) for f in findings]
        assert got == _expected("bad_chain.py"), \
            "\n".join(map(repr, findings))
        assert "caller-holds chain broken" in findings[0].message

    def test_purity_violations_caught_at_line(self):
        findings, _ = _run_fixture("bad_purity.py", purity=True)
        got = sorted((f.line, f.check) for f in findings)
        assert got == sorted(_expected("bad_purity.py")), \
            "\n".join(map(repr, findings))
        msgs = " | ".join(f.message for f in findings)
        assert "time.time" in msgs
        assert "np.random" in msgs
        assert "print" in msgs
        assert "TRACE_LOG" in msgs and "mutates" in msgs

    def test_reasoned_suppression_silences_and_is_listed(self):
        findings, sups = _run_fixture("suppressed.py")
        assert findings == [], "\n".join(map(repr, findings))
        assert len(sups) == 1
        assert sups[0].check == "lock-discipline"
        assert "benign racy peek" in sups[0].reason
        assert sups[0].used

    def test_trailing_suppression_covers_only_its_own_line(self):
        """A trailing `# lint: allow` must not reach the next line —
        else one reasoned exception could silently swallow a second,
        unrelated violation."""
        findings, sups = _run_fixture("trailing_suppression.py")
        got = [(f.line, f.check) for f in findings]
        assert got == _expected("trailing_suppression.py"), \
            "\n".join(map(repr, findings))
        assert len(sups) == 1 and sups[0].used
        assert not sups[0].standalone

    def test_reasonless_suppression_is_a_finding(self):
        findings, sups = _run_fixture("bad_suppression.py")
        assert sups == []          # rejected, never registered
        checks = sorted(f.check for f in findings)
        # the malformed suppression AND the access it failed to cover
        assert checks == ["lock-discipline", "suppression"]
        sup = next(f for f in findings if f.check == "suppression")
        assert "no reason" in sup.message

    def test_recompile_hazards_caught_at_line(self):
        """ISSUE 17: traced-body closure/shape/concretization hazards
        plus the program-family census — including both directions of
        the census↔jit-guard-fixture agreement check — each at the
        exact marked file:line."""
        findings, _ = _run_fixture(
            "bad_recompile.py", purity=True,
            census=("bad_recompile.py",),
            fixtures=("jitguard_fixture.py",))
        got = sorted((f.file, f.line, f.check) for f in findings)
        want = sorted(
            [("bad_recompile.py", line, check)
             for line, check in _expected("bad_recompile.py")]
            + [("jitguard_fixture.py", line, check)
               for line, check in _expected("jitguard_fixture.py")])
        assert got == want, "\n".join(map(repr, findings))
        msgs = " | ".join(f.message for f in findings)
        assert "closes over self.scale" in msgs
        assert ".shape" in msgs
        assert "census" in msgs
        assert "silently-compiled twin" in msgs
        assert "fixture drift" in msgs

    def test_hostsync_violations_caught_at_line(self):
        """ISSUE 17: implicit device→host coercions, jnp staging,
        un-fenced timing and dispatch-under-lock in hot-path methods;
        the xfer.to_device/to_host shapes pass clean."""
        findings, _ = _run_fixture("bad_hostsync.py")
        got = sorted((f.line, f.check) for f in findings)
        assert got == sorted(_expected("bad_hostsync.py")), \
            "\n".join(map(repr, findings))
        msgs = " | ".join(f.message for f in findings)
        assert "int(...)" in msgs
        assert ".item()" in msgs
        assert "jnp.asarray" in msgs
        assert "timing read with a dispatch in flight" in msgs
        assert "inside a `with self.<lock>:`" in msgs

    def test_lifecycle_violations_caught_at_line(self):
        """ISSUE 17: dropped futures and straight-line span/page
        resolution flagged; finally/except ownership and handoff
        escapes pass clean."""
        findings, _ = _run_fixture("bad_lifecycle.py")
        got = sorted((f.line, f.check) for f in findings)
        assert got == sorted(_expected("bad_lifecycle.py")), \
            "\n".join(map(repr, findings))
        msgs = " | ".join(f.message for f in findings)
        assert "leaked on every path" in msgs
        assert "exception path" in msgs

    def test_hot_path_registry_drift_is_a_finding(self):
        """A rename (or a dropped marker) must not silently shrink
        the host-sync analysis set."""
        findings, _, _ = veles_lint.run_check(
            root=FIXTURES, modules=("bad_hostsync.py",),
            purity_modules=(), registry=(), census_modules=(),
            jit_guard_fixtures=(),
            hot_path_registry=(("bad_hostsync.py", "_renamed_away"),))
        drift = [f for f in findings
                 if f.check == "host-sync"
                 and "registry drift" in f.message]
        assert len(drift) == 1
        assert "_renamed_away" in drift[0].message


class TestFullTree:
    def test_full_tree_lint_clean(self):
        """THE tier-1 enforcement: the shipped tree has zero findings
        and every suppression carries a reason — a future unguarded
        access or impure traced body fails here, not in review."""
        findings, sups, stats = veles_lint.run_check()
        assert findings == [], (
            "veles_lint found %d problem(s) in the tree:\n%s"
            % (len(findings), "\n".join(map(repr, findings))))
        assert all(s.reason for s in sups)
        # the ISSUE 17 suppression budget: at most 6 named+reasoned
        # exceptions tree-wide
        assert len(sups) <= 6
        # the analysis actually covered the serving tier (a silently
        # empty pass must not read as a clean one)
        assert stats["files"] >= 10
        assert stats["guarded_attrs"] >= 50
        assert stats["module_globals"] >= 2
        assert stats["traced_functions"] >= 40
        # (chunk, step, page_copy, verify, megastep: the engine's five)
        assert stats["census_sites"] >= 5
        assert stats["hot_path_methods"] >= 12
        assert stats["lifecycle_sites"] >= 1
        # the shared-parse satellite: one ast.parse per file, under
        # the 10s budget
        assert stats["parses"] <= 2 * stats["files"] + 10
        assert stats["wall_s"] < 10.0

    def test_summary_record_shape(self):
        rec = veles_lint.summary_record(
            {"findings": 0, "stats": {"files": 11}})[0]
        for key in ("metric", "value", "unit", "vs_baseline",
                    "configs"):
            assert key in rec
        assert rec["metric"] == "lint_findings"
        assert "wall_s" in rec["configs"]
        # the empty-results worst case conforms too (the
        # check_stream_records builtin contract)
        empty = veles_lint.summary_record({})[0]
        assert empty["value"] == 0

    def test_clean_record_shape(self):
        """The bench-leg `lint_clean` record (lm_bench/chaos_bench
        stream it after their lint leg)."""
        rec = veles_lint.clean_record(
            0, {"files": 11, "wall_s": 0.8})[0]
        for key in ("metric", "value", "unit", "vs_baseline",
                    "configs"):
            assert key in rec
        assert rec["metric"] == "lint_clean"
        assert rec["value"] == 1
        assert rec["configs"]["wall_s"] == 0.8
        dirty = veles_lint.clean_record(
            [veles_lint.Finding("x.py", 1, "host-sync", "m")], {})[0]
        assert dirty["value"] == 0
        assert dirty["configs"]["findings"] == 1


class TestCLIContract:
    """ISSUE 17 CI/tooling satellite: one entry point, every pass in
    the default set, per-pass exit codes — pinned so a pass silently
    dropping out fails loudly here."""

    def test_every_pass_has_a_distinct_exit_bit(self):
        assert set(veles_lint.PASS_BITS) == set(veles_lint.CHECKS)
        bits = sorted(veles_lint.PASS_BITS.values())
        assert len(set(bits)) == len(bits)
        for b in bits:
            assert b > 0 and (b & (b - 1)) == 0   # one bit each

    def test_default_pass_set_is_complete(self):
        assert veles_lint.CHECKS == (
            "lock-discipline", "traced-purity", "suppression",
            "recompile-hazard", "host-sync", "resource-lifecycle")

    def test_exit_code_is_a_per_pass_bitmask(self):
        mk = lambda check: veles_lint.Finding("x.py", 1, check, "m")
        assert veles_lint.exit_code([]) == 0
        assert veles_lint.exit_code([mk("lock-discipline")]) == 1
        assert veles_lint.exit_code([mk("host-sync")]) == 16
        assert veles_lint.exit_code(
            [mk("recompile-hazard"), mk("host-sync"),
             mk("host-sync")]) == 24
        assert veles_lint.exit_code(
            [mk(c) for c in veles_lint.CHECKS]) == 63

    def test_main_all_runs_clean_and_streams_record(self, capsys):
        """`--all` == `--check`: every pass over the shipped tree,
        exit 0, one conforming record on stdout."""
        import json
        rc = veles_lint.main(["--all"])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        rec = json.loads(out)
        assert rec["metric"] == "lint_findings"
        assert rec["value"] == 0
        assert rec["configs"]["hot_path_methods"] >= 12
        assert rec["configs"]["wall_s"] < 10.0


class TestLockOrderWitness:
    def test_deliberate_inversion_caught_with_both_stacks(self):
        from veles_tpu.serving import lockcheck
        w = lockcheck.LockOrderWitness(name="t_invert")
        lockcheck.arm(w)
        try:
            a = lockcheck.make_lock("fixture.A")
            b = lockcheck.make_lock("fixture.B")
            with a:
                with b:
                    pass
            with b:                # the documented order, inverted
                with a:
                    pass
        finally:
            lockcheck.disarm()
        assert len(w.violations) == 1
        report = w.violations[0]
        assert "cycle" in report
        assert "fixture.A" in report and "fixture.B" in report
        # both stacks: where the held lock was taken, where the
        # conflicting acquire happened
        assert report.count("test_lint.py") >= 2

    def test_inversion_raises_when_asked(self):
        from veles_tpu.serving import lockcheck
        w = lockcheck.LockOrderWitness(raise_on_violation=True)
        lockcheck.arm(w)
        try:
            a = lockcheck.make_lock("fixture.C")
            b = lockcheck.make_lock("fixture.D")
            with a:
                with b:
                    pass
            with pytest.raises(lockcheck.LockOrderViolation):
                with b:
                    with a:
                        pass
        finally:
            lockcheck.disarm()

    def test_lock_held_across_dispatch_caught(self):
        from veles_tpu.serving import lockcheck
        w = lockcheck.LockOrderWitness(name="t_dispatch")
        lockcheck.arm(w)
        try:
            lock = lockcheck.make_lock("fixture.E")
            lockcheck.note_dispatch("engine.step")   # lock-free: fine
            with lock:
                lockcheck.note_dispatch("engine.step")
        finally:
            lockcheck.disarm()
        assert len(w.violations) == 1
        assert "held across device dispatch" in w.violations[0]
        assert "engine.step" in w.violations[0]

    def test_nonreentrant_reacquire_caught(self):
        from veles_tpu.serving import lockcheck
        w = lockcheck.LockOrderWitness(name="t_reent",
                                       raise_on_violation=True)
        lockcheck.arm(w)
        try:
            lock = lockcheck.make_lock("fixture.F")
            with lock:
                with pytest.raises(lockcheck.LockOrderViolation):
                    with lock:
                        pass
        finally:
            lockcheck.disarm()

    def test_condition_wait_notify_under_witness(self):
        """The Condition wrapper keeps primitive semantics while
        armed: wait releases (held-stack popped — a concurrent
        notifier acquiring is no violation) and re-acquires."""
        from veles_tpu.serving import lockcheck
        w = lockcheck.LockOrderWitness(name="t_cond")
        lockcheck.arm(w)
        try:
            cond = lockcheck.make_condition("fixture.cond")
            seen = []

            def waiter():
                with cond:
                    while not seen:
                        cond.wait(5.0)
                    seen.append("woke")

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.05)
            with cond:
                seen.append("go")
                cond.notify_all()
            t.join(timeout=10)
            assert not t.is_alive()
            assert seen == ["go", "woke"]
        finally:
            lockcheck.disarm()
        assert w.violations == []
        assert w.acquisitions >= 2

    def test_unarmed_shims_are_inert(self):
        from veles_tpu.serving import lockcheck
        assert lockcheck.armed() is None
        lock = lockcheck.make_lock("fixture.G")
        with lock:
            lockcheck.note_dispatch("engine.step")
        cond = lockcheck.make_condition("fixture.H")
        with cond:
            cond.notify_all()


def _tiny_params(max_len=48, vocab=16, n_heads=2, n_layers=2):
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.ops.transformer import init_transformer_params
    host = init_transformer_params(prng.get("init"), vocab, d_model=32,
                                   n_heads=n_heads, n_layers=n_layers,
                                   max_len=max_len)
    return jax.tree.map(jnp.asarray, host)


class TestStopOrderingUnderWitness:
    def test_serving_stack_stop_ordering(self):
        """The ISSUE 15 shutdown audit, pinned: a fleet with a parked
        retry timer (long backoff), a live hedge loop, a health
        prober and the telemetry sampler+SLO listener stops in the
        serve_lm order — every outstanding future resolves loudly
        (never wedges on a cancelled timer), every daemon joins, and
        the armed witness sees no ordering violation across the whole
        teardown."""
        from veles_tpu.serving import (FaultPlan, HealthChecker,
                                       LMEngine, Router, SLOMonitor,
                                       lockcheck, telemetry_for)
        params = _tiny_params()
        plan = FaultPlan(seed=0)
        # replica 0 poisons every step dispatch: the first attempt
        # faults and schedules a retry with a deliberately HUGE
        # backoff, so stop() runs with the timer still parked
        plan.arm("engine.step", kind="error")
        witness = lockcheck.LockOrderWitness(name="t_stop")
        lockcheck.arm(witness)
        try:
            replicas = [
                LMEngine(params, n_heads=2, max_len=48, slots=2,
                         name="lint_stop0", faults=plan),
                LMEngine(params, n_heads=2, max_len=48, slots=2,
                         name="lint_stop1"),
            ]
            router = Router(replicas, retries=3,
                            retry_backoff_s=30.0,
                            retry_backoff_cap_s=60.0,
                            hedge_after_s=5.0, seed=0)
            router.start()
            checker = HealthChecker(router, interval_s=0.2,
                                    stall_s=60.0).warm_probes()
            checker.start()
            store = telemetry_for(router, interval_s=0.2)
            monitor = SLOMonitor(store,
                                 SLOMonitor.default_objectives(),
                                 windows_s=(1.0, 5.0), min_events=1,
                                 checker=checker)
            store.add_listener(monitor.sample_once)
            store.start()
            # exclude the healthy replica so the first placement hits
            # the poisoned one and schedules the long-backoff retry
            with router._lock:
                router._live[1] = False
            fut = router.submit([1, 2, 3], 4)
            deadline = time.monotonic() + 30.0
            while router.metrics.counter("requests_retried") < 1:
                assert time.monotonic() < deadline, \
                    "retry was never scheduled"
                time.sleep(0.01)
            with router._lock:
                router._live[1] = True
            # the serve_lm stop order: telemetry → publisher (none) →
            # health prober → router (timers, hedge, replicas)
            store.stop()
            checker.stop()
            router.stop()
            # the parked-timer job fails LOUDLY instead of wedging
            with pytest.raises(Exception):
                fut.result(timeout=10)
            assert fut.done()
            assert router._hedge_thread is None
            with router._lock:
                assert not router._timers
            assert store._thread is None
            assert checker._thread is None
            for e in replicas:
                assert e._thread is None
        finally:
            plan.release()
            lockcheck.disarm()
        assert witness.violations == [], \
            "\n\n".join(witness.violations)
        assert witness.acquisitions > 0


class TestStreamRecordIntegration:
    def test_check_stream_records_validates_lint_record(self):
        """The <1s builtin path: check_stream_records --tool
        veles_lint validates exactly this tool's record without
        importing the jax-heavy benches."""
        import check_stream_records
        problems = check_stream_records.check_tool("veles_lint")
        assert problems == []


class TestTransferGuardWitness:
    """The runtime half of the host-sync pass (ISSUE 17): the serving
    suites run with ``jax.transfer_guard("disallow")`` armed via
    serving/xfer.py, entered on the engine worker thread itself."""

    def test_unarmed_guard_is_inert(self):
        from veles_tpu.serving import xfer
        assert not xfer.armed()
        with xfer.guard():
            pass                     # a null context, zero jax work

    def test_arm_rejects_unknown_mode(self):
        from veles_tpu.serving import xfer
        with pytest.raises(ValueError):
            xfer.arm("explode")
        assert not xfer.armed()

    def test_explicit_shims_pass_under_armed_guard(self):
        from veles_tpu.serving import xfer
        xfer.arm("disallow")
        try:
            with xfer.guard():
                dev = xfer.to_device([1, 2, 3], numpy.int32)
                host = xfer.to_host(dev)
        finally:
            xfer.disarm()
        assert list(host) == [1, 2, 3]

    def test_implicit_transfer_fails_the_request_loudly(self):
        """Deliberately poison a decode step with an implicit
        host→device transfer: under the armed guard the worker-loop
        dispatch raises and the request future carries the loud
        transfer-guard error — the PR 15 witness discipline, applied
        to transfers."""
        import jax.numpy as jnp
        from veles_tpu.serving import LMEngine, xfer
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=2,
                          name="xfer_witness")
        xfer.arm("disallow")
        try:
            engine.start()     # warmup runs clean under the guard
            real_step = engine._step_jit

            def poisoned(*args):
                # jnp.asarray of a python scalar is an implicit
                # host→device transfer — exactly what the static
                # host-sync pass bans from hot-path methods
                return real_step(*args) + jnp.asarray(0, jnp.int32)

            engine._step_jit = poisoned
            fut = engine.submit([1, 2, 3], n_new=4)
            with pytest.raises(Exception) as ei:
                fut.result(timeout=60)
            msg = str(ei.value).lower()
            assert "transfer" in msg or "disallow" in msg
        finally:
            engine.stop()
            xfer.disarm()


class TestTruePositivePins:
    """The PR 15 precedent: every true positive a new pass finds in
    the shipped tree gets fixed in the same PR *with a pin*, so the
    fix cannot quietly revert."""

    def test_batcher_dispatch_routes_through_xfer_shims(self):
        """The one true positive the host-sync pass found: batcher
        ``_dispatch`` coerced the dispatched result with
        ``numpy.asarray(self.forward(chunk))`` — an implicit
        device→host sync on the hot path.  Zero-copy on CPU (so the
        runtime transfer guard cannot see it here), a full device
        round-trip stall on TPU — exactly the class the STATIC pass
        exists for.  Pin the fix at both levels: the dispatch hot
        path is audited (marked + registered, so a clean result is
        not clean-by-omission) and moves data through the explicit
        shims."""
        findings, _sups, _stats = veles_lint.run_check()
        assert [f for f in findings
                if f.file.endswith("batcher.py")] == []
        registered = {m for r, m in veles_lint.HOT_PATH_REGISTRY
                      if r.endswith("serving/batcher.py")}
        assert {"_take_batch", "_dispatch",
                "_serve_batches"} <= registered
        src = open(os.path.join(
            os.path.dirname(FIXTURES), "..", "veles_tpu", "serving",
            "batcher.py"), encoding="utf-8").read()
        assert "xfer.to_host(self.forward(xfer.to_device(" in src
        assert "= numpy.asarray(self.forward(chunk" not in src
