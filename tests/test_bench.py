"""bench.py contract tests: the LAST JSON line of stdout is always a
well-formed summary record (streamed after every completed leg, so even
a SIGKILL preserves what was measured), per-config watchdog isolation,
and the summary_record metric selection.

These run the host-side configs only (records is pure host work;
convergence math is covered elsewhere) so the suite stays fast.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCH = os.path.join(REPO, "bench.py")


def _run(args, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, BENCH] + args, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, env=env, cwd=REPO, timeout=timeout)
    lines = proc.stdout.decode().strip().splitlines()
    json_lines = [ln for ln in lines if ln.startswith("{")]
    return proc.returncode, json_lines


def test_orchestrated_final_record_last_line():
    """The default (subprocess-orchestrated) mode: every stdout JSON
    line is a parseable summary record (per-leg partials stream as legs
    complete) and the LAST line is the final well-formed record."""
    rc, lines = _run(["--configs", "records", "--seconds", "0.2",
                      "--smoke"])
    assert rc == 0
    assert lines
    for ln in lines:                      # partials share the shape
        partial = json.loads(ln)
        assert "metric" in partial and "configs" in partial
    rec = json.loads(lines[-1])
    assert rec["metric"] == "records_pipeline_samples_per_sec"
    assert rec["value"] > 0
    assert "records_pipeline" in rec["configs"]


def test_watchdog_records_timeout_and_still_emits():
    """A hung/slow config is killed and recorded as an error; the JSON
    line still appears and the exit code flags the failure.  --seconds
    9999 makes the worker's timing window provably longer than the 2 s
    deadline on ANY machine (deterministic kill, not a startup race)."""
    rc, lines = _run(["--configs", "records", "--seconds", "9999"],
                     env_extra={"VELES_BENCH_CONFIG_TIMEOUT_S": "2"})
    assert rc == 1
    assert lines
    rec = json.loads(lines[-1])
    assert rec["metric"] == "bench_failed"
    assert "records_error" in rec["configs"]
    assert "killed after" in rec["configs"]["records_error"]


def test_unknown_config_rejected():
    proc = subprocess.run(
        [sys.executable, BENCH, "--configs", "nope"],
        capture_output=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO, timeout=60)
    assert proc.returncode == 2
    assert b"unknown configs" in proc.stderr


def test_convergence_sub_config_addressable():
    """convergence:<sub> tokens are valid --configs entries (the
    expansion the orchestrator uses for per-sub watchdogs)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.expand_configs(["convergence"]) == [
        "convergence:" + s for s in bench.CONVERGENCE_SUBS]
    assert bench.expand_configs(["mnist", "lm"]) == ["mnist", "lm"]


def test_compile_cache_rule(tmp_path, monkeypatch):
    """veles_tpu.compile_cache.enable — the one rule every entry point
    shares: JAX_COMPILATION_CACHE_DIR set -> nothing is set in code (jax
    reads the variable); unset on an accelerator -> <checkout>/.jax_cache,
    a fixed path; on the CPU, pinned there or because jax found nothing
    else -> no cache (conftest.py says why)."""
    import jax
    from veles_tpu import compile_cache
    before = jax.config.jax_compilation_cache_dir
    pinned = jax.config.jax_platforms
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() is None          # this suite: cpu
        # not pinned, but the backend jax fell to is the CPU: still none,
        # unless jax.distributed.initialize is still ahead (the backend
        # cannot be asked then)
        jax.config.update("jax_platforms", None)
        assert compile_cache.enable() is None
        assert jax.config.jax_compilation_cache_dir == before
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable(before_distributed_init=True) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_platforms", pinned)
        jax.config.update("jax_compilation_cache_dir", before)


def test_device_config_without_chip_fails():
    """A measurement path that finds no chip fails: without --smoke a
    device config on a CPU-only host is an error and the run exits
    non-zero, while the host-side config beside it still measures."""
    rc, lines = _run(["--configs", "mnist,records", "--seconds", "0.2"])
    assert rc == 1
    rec = json.loads(lines[-1])
    assert "no TPU device" in rec["configs"]["mnist_error"]
    assert rec["configs"]["records_pipeline"]["samples_per_sec"] > 0


def test_emit_summary_priority_and_fallbacks():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_mod2", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    import io
    import contextlib

    def emit(results):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = bench.emit_summary(dict(results))
        return rc, json.loads(buf.getvalue().strip())

    # model result wins and computes the records pipeline ratio
    rc, rec = emit({
        "mnist_fc": {"samples_per_sec": 10.0, "vs_numpy_floor": 2.0},
        "alexnet": {"samples_per_sec": 100.0},
        "alexnet_records": {"samples_per_sec": 90.0},
    })
    assert rc == 0
    assert rec["metric"].startswith("mnist_fc")
    assert rec["configs"]["alexnet_records"][
        "pipeline_ratio_vs_hbm"] == 0.9
    # skipped scaling alone is a success, not a failure
    rc, rec = emit({"dp_scaling": {"skipped": "single device"}})
    assert rc == 0 and rec["metric"] == "dp_scaling_skipped"
    # all-errors still yields the one line with rc=1
    rc, rec = emit({"mnist_error": "boom"})
    assert rc == 1 and rec["metric"] == "bench_failed"


def test_worker_streams_partials_and_collect_merges():
    """Workers stream each completed record as a {"partial": ...} line
    (VELES_BENCH_STREAM=1) so a later watchdog kill cannot discard
    already-measured records; collect_worker_output merges partials and
    lets the final results line win."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", VELES_BENCH_STREAM="1")
    proc = subprocess.run(
        [sys.executable, BENCH, "--worker", "records", "--smoke",
         "--seconds", "0.2"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        cwd=REPO, timeout=300)
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.startswith("{")]
    partials = [json.loads(ln) for ln in lines if "partial" in ln]
    assert partials, "worker emitted no partial lines"
    assert any("records_pipeline" in p["partial"] for p in partials)

    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_mod3", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    # full output: the final results line wins
    got, complete = bench.collect_worker_output(proc.stdout)
    assert complete and got["records_pipeline"]["samples_per_sec"] > 0
    # truncated output (simulated kill mid-worker): partials survive
    cut = proc.stdout[:proc.stdout.rfind(b'{"worker"')]
    got, complete = bench.collect_worker_output(cut)
    assert not complete
    assert got["records_pipeline"]["samples_per_sec"] > 0


def test_sigterm_emits_partial_json_and_exits_nonzero():
    """The driver wraps the bench in an outer `timeout`; TERM must
    produce the one JSON line (partial results) — and a non-zero exit,
    because the run did not finish."""
    import signal
    import time as time_mod
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, BENCH, "--configs", "records",
         "--seconds", "9999"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        cwd=REPO)
    time_mod.sleep(5)                    # handler installed; worker busy
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 1
    lines = [ln for ln in out.decode().splitlines() if ln.startswith("{")]
    assert lines
    rec = json.loads(lines[-1])
    assert "bench_error" in rec["configs"]
    assert "partial results" in rec["configs"]["bench_error"]


@pytest.mark.slow
def test_sigkill_mid_run_leaves_parsed_record():
    """`timeout -k` follows TERM with KILL, and a KILLed bench runs no
    handler at all.
    Per-leg summary streaming means the stdout captured up to the kill
    still ENDS with a parseable record carrying every completed leg.
    (slow-marked: spawns a non-smoke worker; the streaming contract
    itself stays tier-1 via test_orchestrated_final_record_last_line)"""
    import time as time_mod
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # orchestrated mode (not --smoke): leg 1 (records, tiny window)
    # completes and streams its summary line; the KILL lands during or
    # after leg 2 (mnist: no device on this host, so it fails fast)
    proc = subprocess.Popen(
        [sys.executable, BENCH, "--configs", "records,mnist",
         "--seconds", "0.2"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        cwd=REPO)
    streamed = []
    deadline = time_mod.monotonic() + 280
    while time_mod.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        line = line.decode().strip()
        if line.startswith("{"):
            streamed.append(line)
            break                       # leg 1's summary arrived
    assert streamed, "no per-leg summary streamed before the kill"
    proc.kill()                         # SIGKILL — no handler runs
    rest, _ = proc.communicate(timeout=60)
    lines = streamed + [ln for ln in rest.decode().splitlines()
                        if ln.startswith("{")]
    rec = json.loads(lines[-1])         # the driver's "last line wins"
    assert rec["configs"]["records_pipeline"]["samples_per_sec"] > 0


def test_total_deadline_skips_and_exits_nonzero():
    """VELES_BENCH_TOTAL_S bounds the whole run: configs that would
    start past the deadline are recorded as skipped, the summary still
    emits, and a config that was not measured fails the run."""
    rc, lines = _run(["--configs", "records", "--seconds", "9999"],
                     env_extra={"VELES_BENCH_TOTAL_S": "1"}, timeout=120)
    assert rc == 1
    assert lines
    rec = json.loads(lines[-1])
    assert "total bench deadline" in rec["configs"]["records_error"]
