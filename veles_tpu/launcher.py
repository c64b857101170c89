"""Launcher — runs a workflow standalone or multi-host SPMD.

Ref: veles/launcher.py::Launcher [H] (SURVEY §2.1, §3.1): the reference's
launcher owned the Twisted reactor, created the device, ran the workflow in
standalone / ``--master`` / ``--slave`` modes and wired the auxiliary
services (graphics, web status).

TPU-native redesign (SURVEY §5.8): the master/slave control plane collapses
into SPMD — every host runs the SAME program under
``jax.distributed.initialize``; gradient averaging is the all-reduce XLA
inserts over ICI, and the loader shards its index space by
``process_index`` instead of receiving shards from a master.  Standalone is
the 1-process special case of the same code path.
"""

from __future__ import annotations

import time

from veles_tpu.logger import Logger


class Launcher(Logger):
    """Owns the workflow lifecycle: initialize → (restore) → run → report.

    Parameters
    ----------
    workflow: a built (not yet initialized) Workflow.
    snapshot: optional path — restore state after initialize (resume).
    distributed: join a multi-host run via ``jax.distributed`` and train
        lock-step SPMD over the global mesh — the loader yields each
        process's rows of the same global minibatch sequence
        (``shard_spmd``) and FusedStep routes through ShardedTrainer, so
        gradient averaging is the GSPMD all-reduce (the reference's
        ``--master``/``--slave`` pair, collapsed; the strided
        independent-shard mode stays available via ``Loader.shard`` for
        screening workloads).
    stats: print the per-unit run-time table at the end.
    """

    def __init__(self, workflow, snapshot=None, distributed=False,
                 coordinator_address=None, num_processes=None,
                 process_id=None, stats=True, profile=None,
                 evaluate=False, epoch_scan=0, stream_window=0,
                 stage_ahead=1):
        self.workflow = workflow
        self.snapshot = snapshot
        #: > 0: train via the epoch-scan driver (k-epoch chunks as one
        #: device program each) instead of the per-minibatch graph loop —
        #: see veles_tpu/epoch_driver.py for the exact semantics
        self.epoch_scan = int(epoch_scan or 0)
        #: > 0: stream the dataset through HBM in windows of this many
        #: minibatches (one scan dispatch per window, the next window
        #: staged concurrently) — the epoch-scan driver's out-of-core
        #: mode; implies epoch_scan when set alone
        self.stream_window = int(stream_window or 0)
        if self.stream_window and not self.epoch_scan:
            self.epoch_scan = 1
        #: windows staged ahead of the device (staging thread pool size)
        self.stage_ahead = int(stage_ahead or 1)
        #: evaluation-only run (SURVEY §3.3 "resume/EVALUATE from
        #: snapshot"): one pass over every dataset split with ALL weight
        #: updates gated off — metrics come out, parameters don't move
        self.evaluate = evaluate
        self.distributed = distributed
        self.coordinator_address = coordinator_address
        self.num_processes = num_processes
        self.process_id = process_id
        self.stats = stats
        #: directory for a jax.profiler trace of the run (open with
        #: tensorboard / xprof, or tools/trace_step.py's parser)
        self.profile = profile
        self.restored_payload = None
        self.run_seconds = None

    def boot(self, **kwargs):
        """The reference's Launcher.boot(): bring everything up and run."""
        wf = self.workflow
        mesh = None
        if self.distributed:
            from veles_tpu.parallel import (initialize_multihost,
                                            make_mesh, spmd_loader_shard)
            index, count = initialize_multihost(
                self.coordinator_address, self.num_processes,
                self.process_id)
            # lock-step SPMD over ALL devices of the run: every process
            # plans the same global minibatch sequence and feeds its
            # local rows; gradient averaging is the all-reduce GSPMD
            # inserts over the sharded batch axis (the documented
            # --distributed semantics; the strided independent-shard
            # mode stays available programmatically via Loader.shard
            # for screening workloads)
            mesh = make_mesh()
            loader = getattr(wf, "loader", None)
            if loader is not None:
                loader.shard_spmd(*spmd_loader_shard(mesh))
            self.info("joined distributed run as process %d/%d "
                      "(%d-device mesh)", index, count,
                      mesh.devices.size)
        import jax
        device = jax.devices()[0]
        self.info("running on %s (%s), %d device(s)", device.platform,
                  device.device_kind, jax.device_count())
        wf.initialize(**kwargs)
        if mesh is not None:
            runner = getattr(wf, "_fused_runner", None)
            if runner is None:
                raise ValueError("--distributed training needs a fused "
                                 "workflow (drop --no-fused)")
            from veles_tpu.parallel import ShardedTrainer
            wf._sharded_trainer = ShardedTrainer(runner, mesh)
        snapshot = self.snapshot
        if snapshot == "auto":
            # resume from the latest published snapshot of this workflow's
            # snapshotter directory, or start fresh if none exists yet —
            # the crash-recovery half of SURVEY §5.3 (drop_slave downgrade:
            # kill-and-resume instead of master-side job reissue)
            from veles_tpu import snapshotter
            snap_unit = getattr(wf, "snapshotter", None)
            if snap_unit is None:
                raise ValueError("--snapshot auto needs a workflow with a "
                                 "snapshotter (set --snapshot-dir)")
            snapshot = snapshotter.find_current(snap_unit.directory,
                                                snap_unit.prefix)
            if snapshot is None:
                self.info("no snapshot in %s — starting fresh",
                          snap_unit.directory)
        if snapshot:
            from veles_tpu import snapshotter
            self.restored_payload = snapshotter.restore(wf, snapshot)
            self.info("resumed from %s (epoch %s)", snapshot,
                      self.restored_payload.get("epoch"))
            trainer = getattr(wf, "_sharded_trainer", None)
            if trainer is not None:
                # restore rewrote the unit Vectors + runner state on the
                # host; push it back out over the mesh
                trainer.reload_from_runner()
        if self.evaluate:
            from veles_tpu.mutable import Bool
            always = Bool(True)
            #: units and the fused step consult this flag: every
            #: minibatch takes the EVAL path (no dropout, no backward,
            #: no PRNG draws) regardless of its dataset split
            wf.eval_only = True
            for gd in getattr(wf, "gds", []):
                gd.gate_skip = always
            commit = getattr(wf, "fused_commit", None)
            if commit is not None:
                commit.gate_skip = always       # belt-and-braces
            snap = getattr(wf, "snapshotter", None)
            if snap is not None:
                snap.skip.set(True)   # scoring must not touch lineage
            dec = getattr(wf, "decision", None)
            if dec is None:
                raise ValueError("--evaluate needs a Decision-driven "
                                 "workflow")
            # exactly one more pass over the epoch plan, however many
            # epochs the (restored) run already saw; best_* bookkeeping
            # stays whatever training left it at
            dec.max_epochs = int(wf.loader.epoch_number) + 1
            dec.fail_iterations = None
            dec.freeze_best = True
            dec.complete.set(False)
        if self.epoch_scan and self.evaluate:
            raise ValueError("--epoch-scan is a TRAINING driver; "
                             "--evaluate already runs one scoring pass")
        runner = None
        if self.epoch_scan:
            from veles_tpu.epoch_driver import EpochScanDriver
            driver = EpochScanDriver(wf, chunk=self.epoch_scan,
                                     stream_window=self.stream_window,
                                     stage_ahead=self.stage_ahead)
            runner = driver.run
        begin = time.perf_counter()
        if self.profile:
            import jax.profiler
            with jax.profiler.trace(self.profile):
                (runner or wf.run)()
            self.info("profiler trace written to %s", self.profile)
        else:
            (runner or wf.run)()
        self.run_seconds = time.perf_counter() - begin
        self.info("workflow %r finished in %.2fs", wf.name, self.run_seconds)
        if self.stats:
            wf.print_stats()
        return wf

    # ------------------------------------------------------------------ intro
    def result_summary(self):
        """JSON-friendly run summary (the reference wrote --result-file)."""
        wf = self.workflow
        decision = getattr(wf, "decision", None)
        out = {"workflow": wf.name, "run_seconds": self.run_seconds}
        if decision is not None:
            out["best_metric"] = decision.best_metric
            out["best_epoch"] = decision.best_epoch
            if decision.epoch_metrics:
                out["last_epoch_metrics"] = {
                    set_name: {k: v for k, v in metrics.items()
                               if isinstance(v, (int, float))}
                    for set_name, metrics in decision.epoch_metrics[-1].items()
                }
        snap = getattr(wf, "snapshotter", None)
        if snap is not None and snap.destination:
            out["snapshot"] = snap.destination
        return out
