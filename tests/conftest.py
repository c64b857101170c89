"""Test harness config: force CPU with 8 virtual devices.

This is the TPU analogue of the reference's loopback master/slave trick
(SURVEY §4): distributed semantics are exercised on a virtual 8-device mesh
without hardware.
"""

import os

#: VELES_TEST_TPU=1 leaves the platform alone so the TPU-only tests (the
#: Pallas PRNG kernel, flash_attention_tpu) run on the chip, as their own
#: chip command; everything else in the suite stays on the CPU mesh.
_tpu_mode = os.environ.get("VELES_TEST_TPU", "0") not in ("", "0")

_flags = os.environ.get("XLA_FLAGS", "")
if not _tpu_mode and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _tpu_mode:
    jax.config.update("jax_platforms", "cpu")

# NOTE: the CPU suite does not arm the persistent compile cache
# (veles_tpu.compile_cache): this jaxlib's CPU executable
# deserialization segfaulted mid-suite when a warm cache was reused
# across pytest processes.

import functools  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402


@functools.lru_cache(maxsize=1)
def _forced_device_count_probe():
    """Spawn ONE subprocess that forces a 2-device CPU host platform
    and report whether this jaxlib honors the flag — the serving-mesh
    analogue of test_multihost's cached collective probe: every
    sharded-serving test shares this single cheap check instead of
    each discovering (or flaking on) a single-device jaxlib on its
    own.  Returns (ok, detail)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    code = ("import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "print('DEVICES=%d' % jax.device_count())\n")
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True,
                             timeout=120)
    except Exception as e:   # noqa: BLE001 — probe infra failure
        return False, "probe subprocess failed: %s" % e
    for line in out.stdout.splitlines():
        if line.startswith("DEVICES="):
            n = int(line.split("=", 1)[1])
            return n >= 2, "forced-CPU subprocess saw %d device(s)" % n
    return False, ("probe printed no device count (rc %s): %s"
                   % (out.returncode, (out.stderr or "").strip()[-200:]))


@pytest.fixture(scope="session")
def serving_mesh():
    """Loud, cached gate for sharded-serving tests: ``serving_mesh(n)``
    returns the in-process device count when >= n and otherwise skips
    with a reason that says WHY this environment cannot host an
    n-device serving mesh (platform pinned vs jaxlib ignoring
    xla_force_host_platform_device_count) — a deterministic skip, not
    a flaky failure, on single-device jaxlibs."""
    import jax

    def require(n):
        have = jax.device_count()
        if have >= n:
            return have
        ok, detail = _forced_device_count_probe()
        why = ("the jaxlib CAN force host devices — this process's "
               "platform/flags pin it smaller" if ok else
               "this jaxlib ignores xla_force_host_platform_"
               "device_count")
        pytest.skip("serving-mesh test needs %d devices; this process "
                    "has %d (%s; %s)" % (n, have, why, detail))

    return require


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: sustained/heavy tests excluded from tier-1 "
                   "(deselected by -m 'not slow')")
    config.addinivalue_line(
        "markers", "kernel_parity: interpret-mode Pallas-vs-XLA parity "
                   "tests for the serving attention kernels (ISSUE 7) "
                   "— tier-1, and runnable standalone in <60s via "
                   "tools/check_kernel_parity.py")


@pytest.fixture(autouse=True)
def _fresh_prng():
    from veles_tpu import prng
    prng.reset()
    prng.seed_all(1)
    yield
    prng.reset()


#: suites the lock-order witness (ISSUE 15) is armed around: the
#: concurrency-heavy serving tests.  Everything else keeps the
#: unarmed one-None-check shims; tests/test_lint.py manages its own
#: witness (it asserts deliberate violations ARE caught).
_WITNESSED_SUITES = frozenset((
    "test_serving", "test_kv_pool", "test_tracing", "test_timeseries",
))


#: suites the TRANSFER-GUARD witness (ISSUE 17) is armed around: the
#: engine-worker hot path must only move data through the explicit
#: xfer shims.  Arming is via serving/xfer.py module state — the
#: engine worker thread enters ``jax.transfer_guard("disallow")``
#: itself (JAX guard state is thread-local), so the armed suites catch
#: implicit transfers exactly where they matter: inside the serving
#: loop and warmup, not in test-helper host math.
_TRANSFER_GUARDED_SUITES = frozenset((
    "test_serving", "test_lm_fastpath", "test_lm_paged",
    "test_lm_kernels", "test_lm_megastep", "test_kv_pool",
))


@pytest.fixture(autouse=True)
def _transfer_guard_witness(request):
    """Arm ``jax.transfer_guard("disallow")`` for the serving suites:
    every LMEngine worker loop (and ``start()`` warmup) started during
    the test runs under the guard, so an implicit device↔host
    transfer on the hot path raises with the offending stack instead
    of silently syncing."""
    module = getattr(request.node, "module", None)
    name = getattr(module, "__name__", "")
    if name.rsplit(".", 1)[-1] not in _TRANSFER_GUARDED_SUITES:
        yield
        return
    from veles_tpu.serving import xfer
    xfer.arm("disallow")
    try:
        yield
    finally:
        xfer.disarm()


@pytest.fixture(autouse=True)
def _lock_order_witness(request):
    """Arm the serving lock-order witness for the serving suites: a
    fresh witness per test, disarmed at teardown, and any recorded
    violation — an acquisition-order cycle or a lock held across a
    device dispatch — fails the test loudly with both stacks."""
    module = getattr(request.node, "module", None)
    name = getattr(module, "__name__", "")
    if name.rsplit(".", 1)[-1] not in _WITNESSED_SUITES:
        yield
        return
    from veles_tpu.serving import lockcheck
    witness = lockcheck.LockOrderWitness(name="conftest:%s" % name)
    lockcheck.arm(witness)
    try:
        yield
    finally:
        lockcheck.disarm()
    assert not witness.violations, (
        "lock-order witness recorded %d violation(s) during %s:\n\n%s"
        % (len(witness.violations), request.node.nodeid,
           "\n\n".join(witness.violations)))


@pytest.fixture
def page_step_census():
    """``watch(engine)`` wraps the chunk and the decode program of a
    STARTED engine (its warm-up has run) to note the table and the
    positions every dispatch is handed, and returns ``count()``: the page
    steps those dispatches gave the attention kernels and the live ones
    among them, ``(given, live)``, by brute force over
    ``attention.chunk_live_mask`` (the XLA twin's own mask) — what the
    engine's ``attn_page_steps`` and ``attn_page_steps_live`` must read
    (ISSUE 29)."""
    import numpy
    from veles_tpu import model_config
    from veles_tpu.ops.attention import chunk_live_mask

    def watch(engine):
        calls = []

        def spy(name, span, walked=False):
            real = getattr(engine, name)

            @functools.wraps(real)
            def noted(params, pools, table, tokens, pos, *rest):
                calls.append((span, walked,
                              jax.tree.map(numpy.array, table),
                              numpy.atleast_1d(numpy.array(pos))))
                return real(params, pools, table, tokens, pos, *rest)
            setattr(engine, name, noted)
        cfg, page = engine.cfg, engine.prefill_chunk
        # the history below a chunk's frontier; the latent kind's chunk
        # kernel also walks the chunk's own page, written before it
        spy("_chunk_jit", page if cfg.latent is not None else 0)
        # the decode kernels walk a lane's own live pages and are handed
        # no other (ISSUES 41, 43): the live ones are all they are given
        spy("_step_jit", 1, walked=True)

        @functools.lru_cache(maxsize=None)
        def live_pages(pos, span, width, window):
            mask = numpy.asarray(chunk_live_mask(
                pos, span or page, width * page, window, cfg.sinks))
            if not span:
                mask = mask & (numpy.arange(width * page) < pos)
            return int(mask.any(0).reshape(width, page).any(1).sum())

        def count(per=None):
            """``(given, live)`` over the dispatches made; with ``per``,
            the pages a block of the flash-decode kernel's walk,
            ``(given, live, blocks)`` over the DECODE dispatches alone: a
            lane's live pages in whole blocks, at least one, at most a
            table's width to a block (ISSUE 43)."""
            given = live = blocks = 0
            for span, walked, table, pos in calls:
                if per and not walked:
                    continue
                tables, base = table if isinstance(table, tuple) \
                    else ({model_config.FULL: table}, 0)
                for layer in range(len(engine.params["blocks"])):
                    kind = cfg.kind(layer)
                    width = tables[kind].shape[-1]
                    rel = pos - (base if kind == model_config.SLIDING
                                 else 0)
                    seen = [live_pages(int(p), span, width,
                                       cfg.layer_window(layer))
                            for p in rel]
                    live += sum(seen)
                    given += sum(seen) if walked else len(rel) * width
                    if per:
                        blocks += sum(max(-(-n // min(per, width)), 1)
                                      for n in seen)
            return (given, live, blocks) if per else (given, live)
        return count
    return watch
