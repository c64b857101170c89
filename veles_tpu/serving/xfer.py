"""Explicit device↔host transfer shims + the transfer-guard witness
(ISSUE 17).

The serving hot path must never transfer data between host and device
IMPLICITLY: an unnoticed ``jnp.asarray(python_scalar)`` in a dispatch
argument, or a ``numpy.asarray`` / ``int()`` readback of a jit output,
is a synchronous round-trip the profiler attributes to nothing — the
host silently re-enters the compiled program's loop (the dataflow
thesis this repo reproduces forbids exactly that).  This module makes
every legitimate boundary EXPLICIT and makes everything else fail
loudly:

- :func:`to_device` — host value (python scalar / list / numpy array)
  → committed device array via ``jax.device_put``, the transfer JAX's
  ``transfer_guard`` classifies as explicit.  THE way a hot-path
  method builds a dispatch argument.
- :func:`to_host` — device array (or tree) → numpy via
  ``jax.device_get``, the explicit device→host read.  THE way a
  hot-path method reads a jit output.  Also the static host-sync
  pass's taint sink: a value routed through ``to_host`` is host data,
  so a following ``int()`` / ``numpy.asarray`` is not a finding.
- :func:`start_to_host` — the same read begun and not waited for: the
  copy is queued behind the program that makes the value, NOW, so a
  program dispatched later cannot come between it and the host; a
  following :func:`to_host` waits for it and transfers nothing twice.
- :func:`arm` / :func:`disarm` / :func:`guard` — the RUNTIME WITNESS
  (same discipline as ``lockcheck``'s lock-order witness): the serving
  test suites arm a ``jax.transfer_guard`` mode via
  ``tests/conftest.py``, and the engine worker loop (plus ``start()``
  warmup) enters ``with xfer.guard():`` — JAX's guard state is
  THREAD-LOCAL, so the context must be entered on the worker thread
  itself, which is exactly where the hot path runs.  Unarmed,
  ``guard()`` is a null context: zero overhead in production.

An implicit transfer under the armed guard raises a loud
``jax.errors.TransferGuardError`` (surfaced through the failing
request's future) with the offending stack — the runtime half of
``tools/veles_lint.py``'s static host-sync pass.
"""

from __future__ import annotations

import contextlib

import numpy

#: armed transfer-guard mode ("disallow" / "log") or None (unarmed);
#: written by arm()/disarm() from test setup BEFORE worker threads
#: start, read once per guard() entry — no lock needed
_mode = None


def arm(mode="disallow"):
    """Arm the transfer-guard witness: every ``guard()`` context
    entered after this (engine worker loops, warmup) enforces
    ``jax.transfer_guard(mode)``.  Call before ``LMEngine.start()`` so
    the worker thread picks it up."""
    global _mode
    if mode not in ("disallow", "log", "allow"):
        raise ValueError("transfer-guard mode must be disallow/log/"
                         "allow (got %r)" % (mode,))
    _mode = mode


def disarm():
    global _mode
    _mode = None


def armed():
    return _mode is not None


@contextlib.contextmanager
def _host_boundary_guard(mode):
    # host↔device ONLY: the blanket jax.transfer_guard also polices
    # device→device moves, but a replica jit pulling an uncommitted
    # arg onto its own device slice (router placement) is legitimate
    # dataflow, not a host sync — the witness guards the host edge.
    import jax
    with jax.transfer_guard_host_to_device(mode), \
         jax.transfer_guard_device_to_host(mode):
        yield


def guard():
    """The context a worker loop runs under: the host↔device
    transfer guards when armed, a null context otherwise (one
    module-global None-check — the lockcheck/faults discipline)."""
    if _mode is None:
        return contextlib.nullcontext()
    return _host_boundary_guard(_mode)


def boundary():
    """A DECLARED user-code transfer boundary: within it, host↔device
    transfers are allowed even under an armed witness.  The batcher
    wraps its ``forward`` call in this — forward is USER code (a
    jitted model in production, a plain host function in tests) whose
    internal transfer policy is the user's own; the witness polices
    the serving loop AROUND the boundary, not inside it.  Unarmed: a
    null context."""
    if _mode is None:
        return contextlib.nullcontext()
    return _host_boundary_guard("allow")


def to_device(x, dtype=None, device=None):
    """EXPLICIT host→device transfer: the one way hot-path code turns
    a host value (scalar, list, numpy array) into a dispatch argument.
    ``numpy.asarray`` first (host-side, free for arrays already of
    ``dtype``), then ``jax.device_put`` — explicit under any
    transfer-guard mode."""
    import jax
    return jax.device_put(numpy.asarray(x, dtype), device)


def to_host(x):
    """EXPLICIT device→host transfer: materialize a jit output (array
    or tree of arrays) as numpy via ``jax.device_get``.  Blocks until
    the device value is ready — the fence the host-sync pass's
    unfenced-timing rule credits."""
    import jax
    return jax.device_get(x)


def start_to_host(x):
    """Begin the EXPLICIT device→host transfer of a jit output (array
    or tree of arrays) without waiting for it: the copy takes its place
    behind the dispatch that produces the value, ahead of whatever is
    dispatched after this call.  :func:`to_host` of the same value
    later waits for this copy and returns it."""
    import jax
    # (explicit like ``to_host``'s: allowed under an armed witness; one
    # None-check unarmed)
    with (contextlib.nullcontext() if _mode is None
          else jax.transfer_guard_device_to_host("allow")):
        for leaf in jax.tree.leaves(x):
            leaf.copy_to_host_async()
