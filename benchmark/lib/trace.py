"""Reduction of a jax profiler trace (``*.xplane.pb``) to what the per-layer
readers need: per device, the operations with their self time and the
program (XLA module) executions; the busy union; the idle gaps.

Extended from ``tools/trace_analyze.py`` / ``tools/trace_step.py`` (which
read the Chrome JSON): this reads the xplane with ``jax.profiler.ProfileData``
alone, keeps a nested parent (a ``while`` around a scan's body) from being
counted twice by giving every operation its SELF time, and names idle gaps by
the programs on either side.

On a TPU the device planes are ``/device:TPU:<n>`` with the lines ``XLA Ops``
and ``XLA Modules``.  Without one (the CPU rehearsal) the host plane's
``hlo_module``-tagged events stand in, so that every reader is exercised; a
rehearsal prints counts, never rates."""

from __future__ import annotations

import collections
import glob
import os
import re

Op = collections.namedtuple(
    "Op", "name start dur self_dur module")
Module = collections.namedtuple("Module", "name start dur")

_FINGERPRINT = re.compile(r"\(\d+\)$")
_HLO = re.compile(
    r"^%?([A-Za-z_][\w\-]*?)(?:\.[\w\-]+)*\s*=\s*\(?\s*(\w+\[[\d,]*\])?")


def short_name(name):
    """An operation's kind and result shape: the profiler names a TPU op by
    its whole HLO line (``%copy.585 = f32[321,32,32,64]{3,2,1,0:T(8,128)}
    copy(...)``); ``copy f32[321,32,32,64]`` says as much in a line that the
    ledger can keep, and is the same for every layer's copy."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"(\.\d+)+$", "", name.lstrip("%"))
    return ("%s %s" % (m.group(1), m.group(2))) if m.group(2) else m.group(1)


def newest_xplane(logdir):
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no xplane under %s" % logdir)
    return max(paths, key=os.path.getmtime)


def _stats(event, wanted):
    out = {}
    for key, value in event.stats:
        if key in wanted:
            out[key] = value
    return out


def _self_times(raw):
    """``raw``: [(start, dur, ...)] on one timeline.  Returns the self time
    of each (its duration less what its direct children cover), in order."""
    order = sorted(range(len(raw)), key=lambda i: (raw[i][0], -raw[i][1]))
    self_dur = [r[1] for r in raw]
    stack = []
    for i in order:
        start, dur = raw[i][0], raw[i][1]
        while stack and raw[stack[-1]][0] + raw[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            self_dur[stack[-1]] -= dur
        stack.append(i)
    return [max(s, 0.0) for s in self_dur]


def _assign_modules(raw, modules):
    """Name of the module execution that contains each op's start."""
    mods = sorted(modules, key=lambda m: m.start)
    owner = [None] * len(raw)
    j = 0
    for i in sorted(range(len(raw)), key=lambda i: raw[i][0]):
        start = raw[i][0]
        while j + 1 < len(mods) and mods[j + 1].start <= start:
            j += 1
        if mods and mods[j].start <= start < mods[j].start + mods[j].dur:
            owner[i] = mods[j].name
    return owner


def _raw_op(event):
    """(start, dur, short name, hlo_module, run_id).  A TPU op event carries
    no more than its times and its HLO line; ``hlo_module`` and ``run_id`` are
    what the CPU's stand-in events have."""
    s = _stats(event, ("hlo_module", "run_id"))
    return (float(event.start_ns), float(event.duration_ns),
            short_name(event.name), s.get("hlo_module"), s.get("run_id"))


def _device(raw, modules):
    """``raw``: [_raw_op]; ``modules``: [Module] (may be empty: then an op's
    own ``hlo_module`` stat names its program)."""
    self_dur = _self_times(raw)
    owner = (_assign_modules(raw, modules) if modules
             else [r[3] for r in raw])
    ops = [Op(r[2], r[0], r[1], s, m)
           for r, s, m in zip(raw, self_dur, owner)]
    return {"ops": ops, "modules": modules}


def _tpu_device(lines):
    modules = [Module(_FINGERPRINT.sub("", e.name), float(e.start_ns),
                      float(e.duration_ns))
               for e in (lines["XLA Modules"].events
                         if "XLA Modules" in lines else ())]
    return _device([_raw_op(e) for e in lines["XLA Ops"].events], modules)


def _host_standin(plane):
    """CPU rehearsal: ``hlo_module``-tagged host events as the operations, and
    one synthetic module execution per (module, run_id)."""
    raw = [r for line in plane.lines for r in map(_raw_op, line.events)
           if r[3] is not None]
    runs = {}
    for r in raw:
        lo, hi = runs.get((r[3], r[4]), (float("inf"), 0.0))
        runs[(r[3], r[4])] = (min(lo, r[0]), max(hi, r[0] + r[1]))
    modules = [Module(k[0], lo, hi - lo) for k, (lo, hi) in runs.items()]
    return _device(raw, modules)


def read(logdir):
    """{"devices": [{"ops": [Op], "modules": [Module]}]} of the newest trace
    under ``logdir``; times in nanoseconds on the trace's own clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(newest_xplane(logdir))
    devices = []
    host = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(_tpu_device(lines))
        elif plane.name == "/host:CPU":
            host = plane
    if not devices and host is not None:
        devices.append(_host_standin(host))
    return {"devices": devices}


def busy_intervals(ops):
    """Union of the operations' intervals, as sorted (start, end)."""
    out = []
    for start, end in sorted((o.start, o.start + o.dur) for o in ops):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def busy_seconds(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    per = [sum(e - s for s, e in busy_intervals(d["ops"])) / 1e9
           for d in trace["devices"]]
    return sum(per) / len(per) if per else 0.0


def self_seconds(trace, keep):
    """Self seconds of the operations ``keep(op)`` accepts, averaged over
    the devices."""
    per = [sum(o.self_dur for o in d["ops"] if keep(o)) / 1e9
           for d in trace["devices"]]
    return sum(per) / len(per) if per else 0.0


def top_ops(trace, n=10):
    """[[name, self seconds]] of the first device, longest first."""
    if not trace["devices"]:
        return []
    total = collections.defaultdict(float)
    for o in trace["devices"][0]["ops"]:
        total[o.name] += o.self_dur / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace, n=10):
    """[[what the host moved between, seconds]]: idle time of the first
    device summed by the programs before and after each gap, longest first.
    The benchmark's clock is outside the program, so a gap is named by the
    two program executions it lies between, or ``unattributed``."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][0]
    mods = sorted(dev["modules"], key=lambda m: m.start)
    total = collections.defaultdict(float)
    if len(mods) >= 2:
        end = mods[0].start + mods[0].dur
        prev = mods[0].name
        for m in mods[1:]:
            if m.start > end:
                total["%s->%s" % (prev, m.name)] += (m.start - end) / 1e9
            if m.start + m.dur > end:
                end, prev = m.start + m.dur, m.name
    else:
        busy = busy_intervals(dev["ops"])
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            total["unattributed"] += (s1 - e0) / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def module_executions(trace, name):
    """[Module] of the first device whose name is ``name`` (``jit_`` prefix
    optional)."""
    if not trace["devices"]:
        return []
    return [m for m in trace["devices"][0]["modules"]
            if m.name in (name, "jit_" + name)]
