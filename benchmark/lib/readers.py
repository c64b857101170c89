"""What the per-layer readers share.  Every function takes the traced run's
artefacts ``art`` and returns a number, or None where it finds nothing to
read (the harness then leaves the metric out; a share is never 0 by
default).

``art`` holds: ``trace`` (``lib/trace.py::read``), ``trace_window_s`` (host
seconds traced), ``client_log`` / ``latencies_ms`` (serving), ``counters``
(counts taken at the window's edges), ``window_s``."""

from benchmark.lib import stats, trace as trace_lib


def busy(art):
    return trace_lib.busy_seconds(art["trace"])


def device_idle_share(art):
    """Share (%) of the traced window in which no operation ran."""
    if not art.get("trace_window_s") or not art["trace"]["devices"]:
        return None
    return 100.0 * (1.0 - busy(art) / art["trace_window_s"])


def compiles_in_window(art):
    """Backend compilations (jax's own monitoring events) between the
    window's edges; must read 0."""
    return art["counters"].get("compiles")


def latency_percentile(art, q):
    return stats.percentile(art.get("latencies_ms") or [], q)


def module_mean_ms(art, name):
    """Mean device milliseconds of one execution of program ``name``."""
    runs = trace_lib.module_executions(art["trace"], name)
    if not runs:
        return None
    return sum(m.dur for m in runs) / len(runs) / 1e6


def module_share(art, name):
    """Share (%) of the device's busy time inside program ``name``."""
    runs = trace_lib.module_executions(art["trace"], name)
    total = busy(art)
    if not runs or not total:
        return None
    return 100.0 * sum(m.dur for m in runs) / 1e9 / total


def op_share(art, keep):
    """Share (%) of the device's busy time in operations ``keep`` accepts."""
    total = busy(art)
    seconds = trace_lib.self_seconds(art["trace"], keep)
    if not total or not seconds:
        return None
    return 100.0 * seconds / total
