"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that owns the chip: it loads, warms up, measures for
``--seconds``, checks what the timed path produced against the plain
reference, prints one JSON line last on standard output, exits.

Everything that belongs to one cell is data or a file found by name (see
``benchmark/README.md``): ``BENCHMARK.json`` names the cell's configuration
and traffic; the configuration file names its driver under
``benchmark/drivers/``; each per-layer metric has a reader under
``benchmark/layer_metrics/``.  ``--rehearse`` runs the whole path on the CPU
at the tiny sizes of each file's ``rehearsal`` block and prints counts, never
rates; it is never what the driver of the checks runs."""

import time
T0 = time.monotonic()          # process start, as near as Python can say

import argparse                # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402
import tempfile                # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib.files import (HERE, load_json, load_module,  # noqa: E402
                                 overlay)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def for_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Context:
    """What a driver and the readers get from the harness."""

    def __init__(self, args, cell, config, traffic):
        self.root = ROOT
        self.t0 = T0
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.tmp = tempfile.mkdtemp(prefix="bench-")
        self._compiles = 0

    # -- compilations, from jax's own monitoring events
    def watch_compiles(self):
        from jax import monitoring

        def on_duration(event, _seconds, **_kw):
            if event == COMPILE_EVENT:
                self._compiles += 1
        monitoring.register_event_duration_secs_listener(on_duration)

    def compiles(self):
        return self._compiles

    # -- profiler
    def start_trace(self):
        import jax
        self._trace_dir = os.path.join(self.tmp, "trace")
        options = jax.profiler.ProfileOptions()
        # the device's planes are all the readers use; with the host tracer
        # on, the training launcher stopped dispatching for the length of the
        # trace (5.5 s idle of 5.5 s traced; my chip run, PR 25)
        options.python_tracer_level = 0
        # (a rehearsal's "device" operations are host events, so it keeps it)
        options.host_tracer_level = 1 if self.rehearse else 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._trace_begin = time.monotonic()

    def stop_trace(self):
        """Seconds traced; the reduced trace is read later (``read_trace``),
        outside the measured window."""
        import jax
        window_s = time.monotonic() - self._trace_begin
        jax.profiler.stop_trace()
        return window_s

    def read_trace(self):
        from benchmark.lib import trace
        return trace.read(self._trace_dir)

    def peaks(self):
        """The published peaks of the chip the run is on.  A rehearsal has no
        chip: it takes the v5e row so that the roofline readers run, and
        prints none of what they return."""
        import jax
        from benchmark.lib import peaks
        if self.rehearse:
            return peaks.PEAKS["TPU v5 lite"]
        return peaks.peaks(jax.devices()[0].device_kind)

    def close(self):
        shutil.rmtree(self.tmp, ignore_errors=True)


def device_record(cell, rehearse):
    import jax
    devices = jax.devices()
    first = devices[0]
    if not rehearse and (first.platform != "tpu"
                         or len(devices) < cell["chips"]):
        print("benchmark: needs %d TPU chip(s); jax found %d x %s"
              % (cell["chips"], len(devices), first.platform),
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": first.platform, "kind": first.device_kind,
            "count": cell["chips"] if not rehearse else 1}


def memory_peak(n):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def load_cell(workload, rehearse, parked=False):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic)
    found by name; a rehearsal lays each file's ``rehearsal`` block on top.
    A rehearsal and the tools under ``benchmark/tests/`` (``parked``) also
    find the cells that ``benchmark/parked.json`` keeps until they are proven.
    A real run also fixes the compile cache's directory inside the checkout,
    unless the machine names one; the program's own rule
    (``compile_cache.enable``) agrees."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if rehearse or parked:
        kept = load_json(os.path.join(HERE, "parked.json"))
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            spec[key] = spec[key] + kept[key]
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json" % workload)
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    if rehearse:
        config = overlay(config, config.get("rehearsal", {}))
        traffic = overlay(traffic, traffic.get("rehearsal", {}))
    else:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    return spec, cell, config, traffic


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    spec, cell, config, traffic = load_cell(args.workload, args.rehearse)
    device = device_record(cell, args.rehearse)
    ctx = Context(args, cell, config, traffic)
    try:
        return run(ctx, spec, cell, device)
    finally:
        ctx.close()


def run(ctx, spec, cell, device):
    ctx.watch_compiles()
    driver = load_module("drivers", ctx.config["driver"]).Driver(ctx)
    driver.setup()
    art = driver.measure()           # the window; opens at art["t_open"]
    device["memory_peak_bytes"] = memory_peak(cell["chips"])
    driver.release()                 # the program's state is freed
    compared = driver.check(art)     # the reference runs now
    art["setup_s"] = art["t_open"] - ctx.t0

    correct = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    metrics = {}
    if ctx.trace:
        art["trace"] = ctx.read_trace()
        from benchmark.lib import trace as trace_lib
        device["busy_s"] = trace_lib.busy_seconds(art["trace"])
        device["window_s"] = art["trace_window_s"]
        for m in spec["per_layer"]:
            if not for_cell(m, cell["name"]):
                continue
            value = load_module("layer_metrics", m["name"]).read(art, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(art["end_to_end"], setup_s=art["setup_s"])
        for m in spec["end_to_end"]:
            if for_cell(m, cell["name"]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": correct, "attempted": art["attempted"],
            "failed": art["failed"], "metrics": metrics, "device": device}
    if ctx.trace:
        line["breakdown"] = {
            "device_ops": trace_lib.top_ops(art["trace"]),
            "idle_gaps": trace_lib.idle_gaps(art["trace"])}
    if ctx.rehearse:
        # a CPU run says what was read and counted, never a rate
        line["rehearsal"] = True
        line["metrics"] = {k: {"read": True, "unit": v["unit"]}
                           for k, v in metrics.items()}
        line["device"] = {k: v for k, v in device.items()
                          if k in ("platform", "kind", "count")}
        line.pop("breakdown", None)
    line["compared"] = compared
    for name, c in compared.items():
        print("compared %s: %r (limit %r)" % (name, c["value"], c["limit"]),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
