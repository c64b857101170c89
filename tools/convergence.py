"""Convergence runs for BASELINE.md rows 0-1: train MNIST-FC and CIFAR
at FULL dataset size with pinned seeds, record final val-acc + wall.

Usage: python tools/convergence.py [mnist] [cifar] [cifar_bf16]
Prints one summary line per config:
  <config>: best val_err <n>/<N> (<pct>%), ..., @<git-sha>

Protocol (BASELINE.md): fixed seed; train until no val improvement for
``patience`` epochs (the sample Decision's criterion); wall time covers
the whole run.  Runs the SAME pure step functions the Decision-driven
unit graph runs, via bench.bench_convergence's epoch-scan path (one
dispatch per chunk of epochs, not 600 per epoch); numerics are
identical by construction (compiled.py composes one set of
step fns for both paths, pinned by tests/test_parallel.py).
"""
import argparse
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def git_sha():
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=REPO).decode().strip()
    except Exception:
        return "unknown"


def run_config(name, seed=1, max_epochs=25, patience=8):
    import bench
    from veles_tpu import compile_cache
    compile_cache.enable()

    # the builders thread the seed through to prng.seed_all, so the
    # printed ``seed=%d`` is the seed that actually governed init and
    # shuffle order (it was silently dead before)
    if name == "mnist":
        build = lambda: bench.build_mnist(60000, 10000, 100,  # noqa: E731
                                          seed=seed)
    elif name == "cifar":
        build = lambda: bench.build_cifar(50000, 10000, 100,  # noqa: E731
                                          seed=seed)
    elif name == "cifar_bf16":
        def build():
            from veles_tpu.ops import functional as F
            F.set_matmul_precision("bfloat16")
            return bench.build_cifar(50000, 10000, 100, seed=seed)
    else:
        raise SystemExit("unknown config %r" % name)

    begin = time.perf_counter()
    try:
        rec = bench.bench_convergence(build, max_epochs=max_epochs,
                                      patience=patience)
    finally:
        if name.endswith("_bf16"):
            from veles_tpu.ops import functional as F
            F.set_matmul_precision("float32")
    wall = time.perf_counter() - begin
    import jax
    print("%s: best val_err %s/%d (%.2f%%), best@%d of %d epochs, "
          "%.1fs wall, device=%s, seed=%d, @%s"
          % (name, rec.get("best_val_err"), rec["val_count"],
             rec.get("best_val_err_pct", float("nan")),
             rec["best_epoch"], rec["epochs_run"], wall,
             jax.devices()[0].device_kind, seed, git_sha()), flush=True)
    return rec


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("configs", nargs="*",
                        default=["mnist", "cifar", "cifar_bf16"])
    parser.add_argument("--max-epochs", type=int, default=25)
    parser.add_argument("--patience", type=int, default=8)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--worker", default=None, metavar="CONFIG",
                        help=argparse.SUPPRESS)   # internal: one config
    parser.add_argument("--in-process", action="store_true",
                        help="no per-config watchdog subprocesses")
    args = parser.parse_args()
    configs = args.configs or ["mnist", "cifar", "cifar_bf16"]
    if args.worker is not None:
        run_config(args.worker, seed=args.seed,
                   max_epochs=args.max_epochs, patience=args.patience)
        return
    if args.in_process:
        for name in configs:
            run_config(name, seed=args.seed, max_epochs=args.max_epochs,
                       patience=args.patience)
        return
    # per-config watchdog subprocesses, like bench.py's orchestrator (the
    # parent stays off jax: one process per chip): a hang mid-config
    # costs that config, not the ones behind it (each summary line
    # prints from the worker the moment it lands)
    per_config = float(os.environ.get("VELES_CONV_CONFIG_TIMEOUT_S",
                                      3600))
    failed = 0
    for name in configs:
        cmd = [sys.executable, os.path.abspath(__file__), "--worker",
               name, "--seed", str(args.seed),
               "--max-epochs", str(args.max_epochs),
               "--patience", str(args.patience)]
        try:
            rc = subprocess.call(cmd, timeout=per_config)
            if rc:
                failed += 1
                print("%s: worker failed (rc=%d)" % (name, rc),
                      flush=True)
        except subprocess.TimeoutExpired:
            failed += 1
            print("%s: killed after %.0fs (hung device dispatch/compile)"
                  % (name, per_config), flush=True)
    # a failed/hung leg must surface in the exit code
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
