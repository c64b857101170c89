"""CLI entry point: ``python -m veles_tpu <workflow> [<config>] [flags]``.

Ref: veles/__main__.py::Main + scripts/velescli.py [H] (SURVEY §2.1, §3.1).
Reference ergonomics preserved:

- ``<workflow>`` is a Python file or a dotted module (e.g.
  ``veles_tpu.samples.mnist``) exposing ``run(load, main)``;
- ``<config>`` is a Python file executed against the global ``root`` tree;
- any argument of the form ``root.a.b=value`` overrides a config leaf;
- ``--random-seed`` seeds every named PRNG stream;
- ``--snapshot`` resumes from a snapshot file;
- ``-d/--device`` picks the backend (tpu/cpu) — the reference's
  OpenCL/CUDA/numpy selection collapsed onto JAX platforms.

The master/slave flags of the reference became ``--distributed`` (SPMD over
``jax.distributed``; see veles_tpu/launcher.py).
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys


def build_argparser():
    parser = argparse.ArgumentParser(
        prog="veles_tpu",
        description="TPU-native dataflow ML framework "
                    "(capability parity with VELES)")
    parser.add_argument("workflow",
                        help="workflow .py file or dotted module with "
                             "run(load, main)")
    parser.add_argument("config", nargs="?", default=None,
                        help="config .py file executed against `root`")
    parser.add_argument("overrides", nargs="*", metavar="root.a.b=value",
                        help="config leaf overrides")
    parser.add_argument("--random-seed", type=int, default=None,
                        help="seed every named PRNG stream")
    parser.add_argument("-s", "--snapshot", default=None,
                        help="resume from this snapshot file, or 'auto' to "
                             "resume from the latest snapshot in the "
                             "workflow's snapshot directory (fresh run if "
                             "none exists) — crash recovery")
    parser.add_argument("-d", "--device", default=None,
                        choices=("tpu", "cpu"),
                        help="JAX platform to run on (default: jax's own "
                             "choice, which is the CPU where it finds no "
                             "chip — the launcher logs what it got)")
    parser.add_argument("--epoch-scan", type=int, default=0, nargs="?",
                        const=1, metavar="CHUNK",
                        help="train via the epoch-scan driver: each "
                             "CHUNK epochs run as ONE device program "
                             "(default CHUNK=1 when the flag is bare); "
                             "identical decision/metrics semantics, "
                             "snapshot granularity = CHUNK epochs — the "
                             "fast path when dispatch latency is high")
    parser.add_argument("--stream-window", type=int, default=0,
                        metavar="MINIBATCHES",
                        help="stream the dataset through device memory "
                             "in windows of this many minibatches: each "
                             "window's minibatches run as ONE device "
                             "program while a host thread stages the "
                             "next window (out-of-core epoch-scan for "
                             "RecordsLoader/LMDB datasets; implies "
                             "--epoch-scan)")
    parser.add_argument("--stage-ahead", type=int, default=1,
                        metavar="N",
                        help="with --stream-window: windows staged "
                             "ahead of the device (default 1 = classic "
                             "double buffering; more overlaps deeper at "
                             "N+1 windows of HBM)")
    parser.add_argument("--no-fused", action="store_true",
                        help="run the unit graph without the fused "
                             "compiled step (debugging)")
    parser.add_argument("--precision", default=None,
                        choices=("float32", "default", "bfloat16"),
                        help="matmul/conv operand precision: float32 = "
                             "fp32-HIGHEST (bit-parity with the reference"
                             "'s fp32 GEMMs), bfloat16 = bf16 operand "
                             "casts with fp32 accumulation — the "
                             "TPU-idiomatic fast path, ~4x on conv nets "
                             "at measured convergence parity (see "
                             "docs/PERF.md)")
    parser.add_argument("--distributed", action="store_true",
                        help="join a multi-host SPMD run "
                             "(jax.distributed.initialize)")
    parser.add_argument("--coordinator-address", default=None)
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--snapshot-dir", default=None,
                        help="enable periodic snapshotting into this dir")
    parser.add_argument("--snapshot-interval", type=int, default=1)
    parser.add_argument("--snapshot-compression", default="gz",
                        choices=("", "gz", "bz2", "xz"))
    parser.add_argument("--snapshot-keep-last", type=int, default=0,
                        help="retain only the newest N epoch snapshots "
                             "(0 keeps all; the *_current resume pointer "
                             "always survives)")
    parser.add_argument("--result-file", default=None,
                        help="write a JSON run summary here")
    parser.add_argument("--dump-config", action="store_true",
                        help="print the effective config tree and exit")
    parser.add_argument("--graph", default=None, metavar="FILE.dot",
                        help="write the unit graph as graphviz dot")
    parser.add_argument("--no-stats", action="store_true",
                        help="skip the per-unit run-time table")
    parser.add_argument("--profile", default=None, metavar="DIR",
                        help="capture a jax.profiler trace of the run into "
                             "DIR (view with tensorboard/xprof)")
    parser.add_argument("--optimize", default=None, metavar="GENERATIONS",
                        help="genetic hyperparameter search over Tune() "
                             "leaves: '<generations>' or "
                             "'<generations>:<population>'")
    parser.add_argument("--list-units", action="store_true",
                        help="list registered unit classes and exit")
    class _Version(argparse.Action):
        """Lazy: importing veles_tpu pulls in jax, and the platform env
        handling in main() must run before the first jax import."""
        def __call__(self, parser, *unused_a, **unused_k):
            import veles_tpu
            print("veles_tpu %s" % veles_tpu.__version__)
            parser.exit()

    parser.add_argument("--version", action=_Version, nargs=0,
                        help="print the framework version and exit")
    parser.add_argument("--events-file", default=None, metavar="FILE",
                        help="append structured log events (JSON lines) to "
                             "FILE — the dependency-free form of the "
                             "reference's mongo event sink")
    parser.add_argument("--events-mongo", default=None, metavar="ADDR",
                        help="stream structured log events to MongoDB at "
                             "ADDR (mongodb://...; requires pymongo)")
    parser.add_argument("--evaluate", action="store_true",
                        help="evaluation-only: one pass over every "
                             "dataset split with weight updates gated "
                             "off (pair with --snapshot to score a "
                             "trained model)")
    parser.add_argument("--web-status", type=int, default=None,
                        metavar="PORT",
                        help="serve the live dashboard (0 = ephemeral "
                             "port; prints WEBSTATUS <url>): per-process "
                             "rows, per-epoch metrics, workflow graph "
                             "view at /graph/<row>.svg")
    parser.add_argument("--web-status-url", default=None, metavar="URL",
                        help="report this process's rows to ANOTHER "
                             "dashboard instead of serving one (worker "
                             "processes of a multi-host run)")
    parser.add_argument("--web-status-host", default="127.0.0.1",
                        metavar="HOST",
                        help="interface --web-status binds (use 0.0.0.0 "
                             "so other hosts' workers can POST /report)")
    parser.add_argument("--serve", type=int, default=None, metavar="PORT",
                        help="after the run completes, serve the trained "
                             "workflow over HTTP (REST /predict; 0 = "
                             "ephemeral port) until interrupted — the "
                             "reference's snapshot-to-serving flow in one "
                             "command (train or --snapshot restore, then "
                             "serve)")
    parser.add_argument("--serve-batch", type=int, default=0,
                        metavar="MAX_BATCH",
                        help="with --serve: coalesce concurrent /predict "
                             "requests through the dynamic micro-batcher "
                             "(veles_tpu.serving) into padded batches of "
                             "up to MAX_BATCH rows; 0 = direct "
                             "one-dispatch-per-request serving")
    parser.add_argument("--serve-slots", type=int, default=0,
                        metavar="SLOTS",
                        help="with --serve on an LM workflow: decode up "
                             "to SLOTS prompts concurrently over one "
                             "shared KV cache (continuous batching); "
                             "0 = one prompt batch at a time")
    parser.add_argument("--serve-max-new", type=int, default=256,
                        metavar="TOKENS",
                        help="with --serve on an LM workflow: the most "
                             "new tokens one request may ask for (a "
                             "larger n_new is cut to it)")
    parser.add_argument("--serve-prefix-cache", type=int, default=0,
                        metavar="CHUNKS",
                        help="with --serve-slots: radix prefix cache "
                             "over prompt KV, capacity CHUNKS cached "
                             "chunks (LRU) — requests sharing a system "
                             "prompt / few-shot header reference its "
                             "KV pages instead of recomputing them "
                             "(no copy); 0 = off")
    parser.add_argument("--serve-prefill-chunk", type=int, default=0,
                        metavar="TOKENS",
                        help="with --serve-slots: the KV page and "
                             "the prompt chunk in tokens — prompt "
                             "prefill runs as TOKENS-sized chunks "
                             "interleaved with decode steps (one chunk "
                             "program for every prompt length, no "
                             "head-of-line blocking behind long "
                             "prompts); must divide max_len; 0 = the "
                             "largest divisor of max_len not above 32")
    parser.add_argument("--serve-spec-k", type=int, default=0,
                        metavar="K",
                        help="with --serve-slots: prompt-lookup "
                             "speculative decoding — draft K tokens "
                             "from the sequence's own n-grams and "
                             "verify them in one dispatch (multiple "
                             "tokens/dispatch on repetitive text, "
                             "output bit-identical to greedy); a "
                             "model that carries a multi-token-"
                             "prediction module drafts with it "
                             "instead (K = 1: the step verifies the "
                             "draft and makes the next in the graph); "
                             "0 = one token per dispatch")
    parser.add_argument("--serve-paged-kv", type=int, default=0,
                        metavar="PAGES",
                        help="with --serve-slots: the size of the KV "
                             "pool in PAGES (page = the prefill "
                             "chunk) — the engine keeps decode KV in "
                             "fixed-size pages shared by every lane "
                             "through per-lane page tables, so "
                             "prefix-cache hits are zero-copy page "
                             "references and the slot count is "
                             "bounded by the pool, not by "
                             "slots*max_len memory; 0 (default) or -1 "
                             "= every lane's whole table (slots * "
                             "max_len / chunk pages, + the reserved "
                             "scratch page)")
    parser.add_argument("--serve-megastep", type=int, default=0,
                        metavar="K",
                        help="with --serve-slots: fused multi-step "
                             "decode — advance every live lane K "
                             "tokens per device dispatch via one "
                             "jitted lax.scan program (with "
                             "--serve-spec-k the draft proposal and "
                             "verification fold in-graph too), moving "
                             "admission/deadline/completion/swap "
                             "handling to megastep boundaries; output "
                             "stays bit-identical to greedy.  0/1 = "
                             "one dispatch per token (default)")
    parser.add_argument("--serve-attn-kernel", default="off",
                        choices=("off", "auto", "force"),
                        metavar="MODE",
                        help="with --serve-slots: "
                             "run the engine's attention through the "
                             "Pallas serving kernels (flash-decode "
                             "over the paged KV pool + fused chunked "
                             "prefill; ops/pallas_kernels.py). 'auto' "
                             "= kernels on real TPU hardware, XLA "
                             "fallback elsewhere (logged once, "
                             "metered as attn_kernel_fallbacks); "
                             "'force' = kernels even off-TPU via "
                             "interpret mode (tests only — orders of "
                             "magnitude slower than the fallback); "
                             "'off' = the XLA path (default)")
    parser.add_argument("--serve-tp", type=int, default=0,
                        metavar="N",
                        help="with --serve-slots: tensor-parallel "
                             "decode — run every engine program over "
                             "an N-device mesh (weights head-sharded, "
                             "KV pool sharded head-wise; N must "
                             "divide the model's attention and KV "
                             "head counts; greedy output stays "
                             "bit-identical).  0 = single-device "
                             "(default)")
    parser.add_argument("--serve-replicas", type=int, default=1,
                        metavar="R",
                        help="with --serve-slots: R independent "
                             "data-parallel engine replicas (each on "
                             "its own device slice — R×max(tp,1) "
                             "devices when --serve-tp >= 2) behind a "
                             "metrics-driven router; /metrics gains "
                             "{replica=\"i\"} labels and responses a "
                             "per-row replica id")
    parser.add_argument("--serve-router", default="metrics",
                        choices=("metrics", "round_robin"),
                        help="with --serve-replicas: placement policy "
                             "— 'metrics' (default) weighs each "
                             "replica's live queue depth, resident KV "
                             "pages and TTFT/decode-step EWMAs; "
                             "'round_robin' ignores them (the skew "
                             "baseline)")
    parser.add_argument("--serve-health", action="store_true",
                        help="with --serve-slots: background health "
                             "prober per replica (staleness watch on "
                             "busy replicas, synthetic 1-token probe "
                             "on idle ones) that auto-quarantines a "
                             "failing replica via the router's drain "
                             "path and re-admits it after a cooldown "
                             "(half-open circuit breaker; "
                             "replica_health_state / "
                             "circuit_open_total on /metrics)")
    parser.add_argument("--serve-hedge", type=float, default=0.0,
                        metavar="SECONDS",
                        help="with --serve-slots: duplicate a request "
                             "still outstanding past SECONDS on a "
                             "second replica — first complete wins, "
                             "the loser is cancelled (greedy replicas "
                             "are bit-identical, so hedging moves "
                             "tail latency, never output); negative = "
                             "dynamic threshold (1.5x the live "
                             "latency p95); 0 = off (default)")
    parser.add_argument("--serve-retries", type=int, default=0,
                        metavar="N",
                        help="with --serve-slots: re-place a request "
                             "whose replica FAULTED (engine error — "
                             "not 429/503 sheds, not client errors) "
                             "on a different replica up to N times "
                             "with exponential jittered backoff; "
                             "0 = off (default, the fault fails to "
                             "the client)")
    parser.add_argument("--serve-model-dir", default=None,
                        metavar="DIR",
                        help="with --serve-slots: continuous "
                             "training→serving — watch DIR for the "
                             "snapshotter's *_current.* checkpoints "
                             "and hot-swap each new one across the "
                             "fleet with zero downtime (canary-first "
                             "deploy, parity probe, automatic "
                             "rollback; in-flight requests finish on "
                             "the weights they started on; replies "
                             "stamp the serving weights_version)")
    parser.add_argument("--serve-canary", type=int, default=1,
                        metavar="N",
                        help="with --serve-model-dir: swap N canary "
                             "replica(s) first and watch the live "
                             "health signals before ramping the rest "
                             "of the fleet (default 1)")
    parser.add_argument("--serve-publish-interval", type=float,
                        default=5.0, metavar="SECONDS",
                        help="with --serve-model-dir: how often the "
                             "publisher loop polls the snapshot "
                             "directory (default 5s)")
    parser.add_argument("--serve-canary-watch", type=float,
                        default=2.0, metavar="SECONDS",
                        help="with --serve-model-dir: how long the "
                             "deploy observes the canary's live "
                             "health signals (errors, decode-step/"
                             "TTFT EWMAs, the health circuit) with "
                             "traffic steered at it before ramping "
                             "the rest of the fleet; 0 = one "
                             "instantaneous signal check (default 2s)")
    parser.add_argument("--serve-trace", default="off",
                        metavar="MODE",
                        help="with --serve: end-to-end request "
                             "tracing (veles_tpu/serving/tracing.py) "
                             "— off|errors|all|sample:P.  Spans cover "
                             "the whole request path (HTTP root, "
                             "router attempts, queue wait, prefill "
                             "chunks, decode ticks, spec verify, COW "
                             "copies), the last N requests stay "
                             "reconstructable in a flight-recorder "
                             "ring (errors auto-dump a waterfall), "
                             "and GET /trace.json exports Chrome-"
                             "trace/Perfetto JSON "
                             "(tools/trace_report.py renders "
                             "waterfalls + the per-op cost ledger).  "
                             "'errors' retains only errored/deadline-"
                             "blown requests; 'sample:0.01' traces "
                             "1%% of traffic (default: off — zero "
                             "overhead)")
    parser.add_argument("--serve-trace-last", type=int, default=256,
                        metavar="N",
                        help="with --serve-trace: flight-recorder "
                             "ring size in requests (default 256)")
    parser.add_argument("--serve-telemetry", type=float, default=0.0,
                        nargs="?", const=1.0, metavar="SECONDS",
                        help="with --serve-slots: continuous "
                             "telemetry (veles_tpu/serving/"
                             "timeseries.py) — sample every serving "
                             "metrics family into bounded time-series "
                             "rings every SECONDS (bare flag = 1s): "
                             "counters as windowed rates, gauges, "
                             "histogram-delta p50/p95, plus runtime "
                             "gauges (live jit compile_programs, "
                             "process RSS, device memory, live MFU, "
                             "megastep waste fraction).  Served at "
                             "GET /timeseries.json?window=S; the "
                             "serving hot path has zero telemetry "
                             "sites (default: off)")
    parser.add_argument("--serve-slo", default=None, metavar="FILE",
                        help="with --serve-slots: declarative SLO "
                             "objectives (veles_tpu/serving/slo.py) "
                             "from a JSON file ('default' = the stock "
                             "availability/TTFT/decode-step/shed set) "
                             "— evaluated as multi-window error-"
                             "budget burn rates over the telemetry "
                             "store (implied on at 1s), ok/warn/page "
                             "state machine at GET /slo.json; with "
                             "--serve-health a page-level burn on one "
                             "replica feeds the health checker's "
                             "quarantine path")
    parser.add_argument("--serve-no-auto-rollback",
                        action="store_true",
                        help="with --serve-model-dir: do NOT roll a "
                             "failed canary back automatically — "
                             "leave the mixed fleet for the operator "
                             "(default: auto-rollback)")
    parser.add_argument("--fault-plan", default=None, metavar="FILE",
                        help="with --serve: arm the deterministic "
                             "fault-injection layer from a JSON plan "
                             "(veles_tpu/serving/faults.py — injected "
                             "dispatch errors, latency spikes, "
                             "freezes, admission storms, transient "
                             "HTTP errors at named sites).  Chaos/"
                             "test gear: every site is a no-op "
                             "without this flag")
    return parser


def load_workflow_module(spec):
    """Import the workflow module from a file path or dotted name."""
    if spec.endswith(".py") or os.path.sep in spec:
        name = os.path.splitext(os.path.basename(spec))[0]
        mod_spec = importlib.util.spec_from_file_location(name, spec)
        if mod_spec is None:
            raise ImportError("cannot load workflow file %r" % spec)
        module = importlib.util.module_from_spec(mod_spec)
        sys.modules[name] = module
        mod_spec.loader.exec_module(module)
        return module
    return importlib.import_module(spec)


def exec_config_file(path):
    """Execute a config file against the global root (reference semantics)."""
    from veles_tpu.config import root, Tune
    namespace = {"root": root, "Tune": Tune, "__file__": path}
    with open(path, "r", encoding="utf-8") as f:
        code = compile(f.read(), path, "exec")
    exec(code, namespace)


def main(argv=None):
    parser = build_argparser()
    # this image's argparse (3.10) cannot allocate positionals that
    # TRAIL optionals to the `overrides` nargs="*" slot ("prog wf
    # --flag x root.a.b=1" dies with "unrecognized arguments"):
    # collect override-shaped leftovers ourselves, reject the rest
    args, extra = parser.parse_known_args(argv)
    bad = [t for t in extra if t.startswith("-") or "=" not in t]
    if bad:
        parser.error("unrecognized arguments: %s" % " ".join(bad))
    args.overrides = list(args.overrides) + extra

    if args.device:
        # the env var covers a jax not imported yet, the config knob one
        # that is; a platform jax cannot start is an error, not a fallback
        os.environ["JAX_PLATFORMS"] = args.device
        import jax
        jax.config.update("jax_platforms", args.device)
    from veles_tpu import compile_cache
    compile_cache.enable(before_distributed_init=args.distributed)

    if args.list_units:
        from veles_tpu.units import UnitRegistry
        import veles_tpu.ops  # noqa: F401 — populate the registry
        for name in sorted(UnitRegistry.units):
            print(name)
        return 0

    from veles_tpu import prng
    from veles_tpu.config import root, parse_override
    from veles_tpu.launcher import Launcher

    if args.events_file or args.events_mongo:
        from veles_tpu.logger import setup_logging
        try:
            setup_logging(events_file=args.events_file,
                          events_mongo=args.events_mongo)
        except (RuntimeError, OSError) as e:
            # missing pymongo / unreachable server / unwritable events file
            parser.error(str(e))

    if args.random_seed is not None:
        prng.seed_all(args.random_seed)

    if args.precision:
        from veles_tpu.ops import functional as F
        F.set_matmul_precision(args.precision)

    # tolerate overrides being swallowed into `config` when no config file
    overrides = list(args.overrides)
    if args.config and "=" in args.config and not os.path.exists(args.config):
        overrides.insert(0, args.config)
        args.config = None
    if args.config:
        exec_config_file(args.config)
    for token in overrides:
        parse_override(token)

    if args.dump_config:
        root.print_()
        return 0

    module = load_workflow_module(args.workflow)
    if not hasattr(module, "run"):
        raise SystemExit("workflow module %r has no run(load, main)"
                         % args.workflow)

    if args.optimize and (args.evaluate or args.serve is not None):
        parser.error("--optimize cannot be combined with --evaluate or "
                     "--serve (the GA drives its own training runs)")
    if args.optimize:
        try:
            from veles_tpu.genetics import optimize_cli
        except ImportError as e:
            raise SystemExit("--optimize requires veles_tpu.genetics: %s" % e)
        return optimize_cli(module, args)

    holder = {}

    def load(workflow_cls, **kwargs):
        if args.snapshot_dir:
            # CLI flags outrank any snapshotter section in the config file,
            # same precedence as root.a.b=value overrides
            # MERGE over any config-file snapshotter settings (e.g.
            # root.<name>.snapshotter.keep_last) instead of replacing —
            # flags win only for the keys they actually set
            cfg_snap = dict(kwargs.get("snapshotter_config") or {})
            cfg_snap.update({
                "directory": args.snapshot_dir,
                "interval": args.snapshot_interval,
                "compression": args.snapshot_compression,
            })
            if args.snapshot_keep_last:
                cfg_snap["keep_last"] = args.snapshot_keep_last
            kwargs["snapshotter_config"] = cfg_snap
        kwargs.setdefault("fused", not args.no_fused)
        wf = workflow_cls(None, **kwargs)
        holder["workflow"] = wf
        return wf

    def _servable(wf):
        """True when --serve will find a serving surface after training:
        an LM trainer (token continuation) or a forward chain."""
        if getattr(wf, "trainer", None) is not None and \
                hasattr(wf.trainer, "n_heads"):
            return True
        return bool(getattr(wf, "forwards", None))

    def main_():
        wf = holder["workflow"]
        if args.graph:
            wf.generate_graph(args.graph)
        if args.serve is not None and not _servable(wf):
            # fail BEFORE launcher.boot(): discovering an unservable
            # workflow only after the whole training run completes would
            # discard the session on a misconfiguration knowable up front
            parser.error("--serve: workflow %r has no forward chain or "
                         "LM trainer to serve" % wf.name)
        if args.web_status is not None or args.web_status_url:
            from veles_tpu.web_status import attach_web_status
            status = attach_web_status(
                wf, port=args.web_status or 0,
                report_url=args.web_status_url,
                host=args.web_status_host)
            if status is not None:
                print("WEBSTATUS http://%s:%d/"
                      % (args.web_status_host, status.port), flush=True)
        launcher = Launcher(
            wf, snapshot=args.snapshot, distributed=args.distributed,
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes, process_id=args.process_id,
            stats=not args.no_stats, profile=args.profile,
            evaluate=args.evaluate, epoch_scan=args.epoch_scan,
            stream_window=args.stream_window,
            stage_ahead=args.stage_ahead)
        holder["launcher"] = launcher
        launcher.boot()

    module.run(load, main_)

    launcher = holder.get("launcher")
    if launcher is not None and args.result_file:
        with open(args.result_file, "w", encoding="utf-8") as f:
            json.dump(launcher.result_summary(), f, indent=2, default=str)
    if launcher is not None and args.serve is not None:
        import threading
        import jax
        from veles_tpu.restful_api import RESTfulAPI
        if jax.process_index() != 0:
            # multi-host runs: exactly one serving endpoint (the same
            # single-writer rule the snapshotter follows)
            return 0
        wf = launcher.workflow
        if not _servable(wf):
            # unreachable for launcher-built workflows (checked before
            # boot); kept as the safety net for snapshot-restored ones
            parser.error("--serve: workflow %r has no forward chain or "
                         "LM trainer to serve" % wf.name)
        fault_plan = None
        if args.fault_plan:
            from veles_tpu.serving import FaultPlan
            fault_plan = FaultPlan.from_file(args.fault_plan)
        if getattr(wf, "trainer", None) is not None and \
                hasattr(wf.trainer, "n_heads"):
            # transformer-trainer workflows serve token continuation
            from veles_tpu.restful_api import serve_lm
            api = serve_lm(wf, port=args.serve, slots=args.serve_slots,
                           max_new=args.serve_max_new,
                           prefix_cache=args.serve_prefix_cache,
                           prefill_chunk=args.serve_prefill_chunk,
                           spec_k=args.serve_spec_k,
                           paged_kv=(True if args.serve_paged_kv < 0
                                     else args.serve_paged_kv),
                           attn_kernel=(0 if args.serve_attn_kernel
                                        == "off"
                                        else args.serve_attn_kernel),
                           megastep=args.serve_megastep,
                           tp=args.serve_tp,
                           replicas=args.serve_replicas,
                           router=args.serve_router,
                           health=args.serve_health,
                           hedge=args.serve_hedge,
                           retries=args.serve_retries,
                           fault_plan=fault_plan,
                           model_dir=args.serve_model_dir,
                           publish_interval_s=(
                               args.serve_publish_interval),
                           canary=args.serve_canary,
                           canary_watch_s=args.serve_canary_watch,
                           trace=args.serve_trace,
                           trace_last=args.serve_trace_last,
                           telemetry=args.serve_telemetry,
                           slo=(True if args.serve_slo == "default"
                                else args.serve_slo),
                           auto_rollback=(
                               not args.serve_no_auto_rollback))
        else:
            api = RESTfulAPI(
                wf, normalizer=getattr(wf.loader, "normalizer", None),
                faults=fault_plan)
            if args.serve_batch > 0:
                # enable_batching forwards api.faults, so the plan's
                # batcher.* sites arm alongside http.request
                api.enable_batching(max_batch=args.serve_batch)
            api.start(port=args.serve)
        # parseable by wrappers/tests; flushed before blocking
        print("SERVING http://127.0.0.1:%d/predict" % api.port, flush=True)
        try:
            threading.Event().wait()        # until SIGINT/SIGTERM
        except KeyboardInterrupt:
            pass
        finally:
            api.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
