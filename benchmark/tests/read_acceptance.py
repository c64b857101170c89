"""Readings for ``mtp_init`` of a configuration whose module drafts
(``benchmark/configs/joyai-llm-flash-ep8.json``), on the chip, at the cell's
own size, in ONE process (the programs are the same whatever the weights, so
they compile once):

1. for each candidate ``mtp_init`` one short window of the cell's own traffic
   with the weights drawn so, and the share of drafts the program accepted in
   it (no reference runs, no reply is waited for);
2. with ``--seeds``: the candidate whose share lies nearest ``--target`` is
   taken, and for each seed one whole window with it, then what ``correct``
   compares beside the control's readings, as ``read_limits.py`` prints them,
   and the window's acceptance beside those.

    python3 benchmark/tests/read_acceptance.py --workload \\
        joyai-llm-flash-ep8.reason --seed 2147485001 --seconds 20 \\
        --init '{"residual_std": 2e-06, "h_mix": 0.005}' \\
        --init '{"residual_std": 2e-06, "h_mix": 0.01}' \\
        [--seeds 2147485011,2147485012 --window 51 --target 0.75]

Not run by the benchmark."""

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness                      # noqa: E402
from benchmark.lib.files import load_module               # noqa: E402
from benchmark.lib.window import counters_moved           # noqa: E402


def accepted(moved):
    drafts = moved.get("draft_tokens", 0)
    return {"accepted": moved.get("draft_accepted", 0), "drafts": drafts,
            "share": moved.get("draft_accepted", 0) / drafts if drafts
            else None,
            "tokens_per_lane_step": (
                moved.get("spec_tokens_kept", 0)
                / moved["spec_lane_steps"]
                if moved.get("spec_lane_steps") else None),
            "discarded": moved.get("spec_tokens_discarded")}


def short_window(driver, seconds):
    """The counters' movement over ``seconds`` of the running cell, and the
    tokens a second; the clients are then told to stop and left behind."""
    first = driver.snapshot()
    t0 = time.monotonic()
    time.sleep(seconds)
    last = driver.snapshot()
    took = time.monotonic() - t0
    driver.clients._stop.set()
    moved = {k: v - first["counters"].get(k, 0)
             for k, v in last["counters"].items()}
    return moved, moved.get("tokens_out", 0) / took


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--init", action="append", required=True, metavar="JSON")
    p.add_argument("--seeds", default="")
    p.add_argument("--window", type=float, default=51.0)
    p.add_argument("--target", type=float, default=0.75)
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args()
    _, cell, config, traffic = harness.load_cell(a.workload, a.rehearse,
                                                  parked=True)
    harness.device_record(cell, a.rehearse)
    spec = load_module("drivers", "serve_lm_spec")
    read = []
    for init in (json.loads(s) for s in a.init):
        tried = dict(config, mtp_init=init)
        args = types.SimpleNamespace(seed=a.seed, seconds=a.seconds,
                                     trace=0, rehearse=a.rehearse)
        ctx = harness.Context(args, cell, tried, traffic)
        try:
            driver = spec.Driver(ctx)
            driver.setup()
            moved, rate = short_window(driver, a.seconds)
            peak = harness.memory_peak(cell["chips"])
            driver.release()
            got = dict(accepted(moved), mtp_init=init, seed=a.seed,
                       out_tok_s=rate, memory_peak_bytes=peak)
            read.append(got)
            print("ACCEPTANCE", json.dumps(got), flush=True)
        finally:
            ctx.close()
    if not a.seeds:
        return
    best = min((r for r in read if r["share"] is not None),
               key=lambda r: abs(r["share"] - a.target))
    print("TAKEN", json.dumps(best["mtp_init"]), flush=True)
    config = dict(config, mtp_init=best["mtp_init"])
    control = config["control_precision"]
    for seed in (int(s) for s in a.seeds.split(",")):
        args = types.SimpleNamespace(seed=seed, seconds=a.window, trace=0,
                                     rehearse=a.rehearse)
        ctx = harness.Context(args, cell, config, traffic)
        try:
            driver = spec.Driver(ctx)
            driver.setup()
            art = driver.measure()
            driver.release()
            compared = driver.check(art, control=control)
            print("READING", json.dumps({
                "seed": seed, "mtp_init": best["mtp_init"],
                "compared": compared,
                "control_readings": {control: art.get("control")},
                "checked": art.get("checked"), "drafts": art.get("drafts"),
                "window": accepted(counters_moved(art)),
                "end_to_end": art["end_to_end"],
                "attempted": art["attempted"], "failed": art["failed"],
                "compiles": art["counters"]["compiles"]}), flush=True)
        finally:
            ctx.close()


if __name__ == "__main__":
    main()
