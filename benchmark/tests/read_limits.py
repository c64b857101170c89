"""Readings for a cell's limits, on the chip, at the cell's own size: for each
seed one short window of the cell's own traffic, then what ``correct``
compares, beside the control's reading (the reference computed in the
configuration's ``control_precision``, put in the program's place).  All
seeds in one process, because set-up is most of a run.

    python3 benchmark/tests/read_limits.py --workload opt-1.3b.chat \
        --seeds 11,12,13 --seconds 30 [--control high] [--trace]

``--trace`` also traces the first seed's window and prints what the trace
holds (programs, longest operations, the stat names an event has), which is
what the per-layer readers are written against.  Not run by the benchmark."""

import argparse
import collections
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness                      # noqa: E402
from benchmark.lib.files import load_module               # noqa: E402


def stat_keys(logdir):
    """The stat names the profiler gives a device op, for whoever writes a
    reader."""
    from jax.profiler import ProfileData
    from benchmark.lib.trace import newest_xplane
    for plane in ProfileData.from_file(newest_xplane(logdir)).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            events = list(line.events)[:400]
            keys = sorted({k for e in events for k, _ in e.stats})
            print("line %r: %d+ events, stats %s" % (line.name, len(events),
                                                     keys))
            for e in events[200:203]:
                print("   ", e.name[:160], dict(e.stats))
        break


def describe(trace):
    for i, dev in enumerate(trace["devices"]):
        print("device %d: %d ops, %d program executions"
              % (i, len(dev["ops"]), len(dev["modules"])))
        by_mod = collections.Counter()
        for m in dev["modules"]:
            by_mod[m.name] += m.dur / 1e9
        for name, s in by_mod.most_common(12):
            n = sum(1 for m in dev["modules"] if m.name == name)
            print("  program %-40s %6d runs %9.4f s" % (name, n, s))
        tot = collections.defaultdict(lambda: [0.0, 0, None])
        for o in dev["ops"]:
            t = tot[o.name]
            t[0] += o.self_dur / 1e9
            t[1] += 1
            t[2] = o.module
        for name, t in sorted(tot.items(), key=lambda kv: -kv[1][0])[:45]:
            print("  op %-44s %9.4f s %6d x  in %s"
                  % (name[:44], t[0], t[1], t[2]))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", default=None)
    p.add_argument("--trace", action="store_true")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                   help="lay a value over the configuration, to try one")
    a = p.parse_args()
    _, cell, config, traffic = harness.load_cell(a.workload, a.rehearse,
                                                  parked=True)
    for item in a.set:
        key, _, value = item.partition("=")
        config = harness.overlay(config, {key: json.loads(value)})
    harness.device_record(cell, a.rehearse)
    control = a.control or config["control_precision"]
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        args = types.SimpleNamespace(seed=seed, seconds=a.seconds,
                                     trace=int(a.trace and i == 0),
                                     rehearse=a.rehearse)
        ctx = harness.Context(args, cell, config, traffic)
        try:
            if i == 0:
                ctx.watch_compiles()
            driver = load_module("drivers", config["driver"]).Driver(ctx)
            driver.setup()
            art = driver.measure()
            driver.release()
            readings = {}
            for c in control.split(","):
                compared = driver.check(art, control=c)
                readings[c] = art.get("control")
            if ctx.trace:
                stat_keys(ctx._trace_dir)
                describe(ctx.read_trace())
            print("READING", json.dumps({
                "seed": seed, "compared": compared, "control_readings": readings,
                "checked": art.get("checked"),
                "end_to_end": art["end_to_end"],
                "attempted": art["attempted"], "failed": art["failed"]}),
                flush=True)
        finally:
            ctx.close()


if __name__ == "__main__":
    main()
