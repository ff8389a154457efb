"""Trainer — the DDP elastic ``Trainer`` twin (`mnist_ddp_elastic.py:30-130`).

Same surface (snapshot load on start, per-epoch train + test, periodic
snapshot save), TPU-native internals: the model is not "wrapped in DDP" —
the train step is SPMD over the mesh's data axis with an explicit grad
``pmean`` (see :mod:`tpudist.parallel.data_parallel`).

Deliberate upgrades over the reference, each flagged in SURVEY.md:
* snapshots carry optimizer state + RNG + step, so resume is exact
  (reference saves only MODEL_STATE/EPOCHS_RUN, `mnist_ddp_elastic.py:99-102`);
* only the coordinator process writes snapshots (the reference's
  ``local_rank == 0`` gate writes once *per node*, `mnist_ddp_elastic.py:113`);
* evaluation psums exact correct-counts instead of per-rank prints.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import time

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from tpudist import obs
from tpudist.obs import xla as obs_xla
from tpudist.data.device_prefetch import device_prefetch
from tpudist.data.loader import ShardedLoader
from tpudist.elastic.checkpoint import Checkpointer, restore_pytree
from tpudist.ops.losses import cross_entropy
from tpudist.parallel.data_parallel import (
    broadcast_params,
    make_dp_masked_eval_step,
    make_dp_train_loop,
    make_dp_train_step,
)
from tpudist.parallel.mesh import (
    MeshSpec,
    make_composed_eval_step,
    make_composed_state,
    make_composed_train_step,
)
from tpudist.train.state import TrainState
from tpudist.utils.config import config_field
from tpudist.utils.logging import get_logger
from tpudist.utils.metrics import MetricLogger, ThroughputMeter, maybe_profile

log = get_logger(__name__)


@dataclasses.dataclass
class TrainerConfig:
    """CLI-overridable twin of the reference's argparse surface
    (`mnist_ddp_elastic.py:203-208`)."""

    total_epochs: int = config_field(5, "epochs to train")
    save_every: int = config_field(1, "snapshot period in epochs")
    batch_size: int = config_field(128, "GLOBAL batch size (reference default 128)")
    snapshot_path: str = config_field("snapshot.npz", "snapshot file")
    log_every: int = config_field(50, "log every N steps")
    eval_every_epoch: bool = config_field(True, "run test() after every epoch")
    profile_dir: str = config_field(
        "", "write a jax.profiler trace of epoch 0 here (XProf/TensorBoard)"
    )
    steps_per_dispatch: int = config_field(
        1,
        "optimizer steps fused per device dispatch (lax.scan); >1 keeps "
        "small models compute-bound instead of dispatch-bound, numerics "
        "identical to stepwise",
    )
    device_prefetch: int = config_field(
        2,
        "train batches whose host->device transfers are kept in flight "
        "ahead of the step (tpudist.data.device_prefetch); 0 = pull "
        "batches synchronously; numerics identical either way",
    )
    async_snapshot: bool = config_field(
        True,
        "snapshot saves block only to initiate device-side copies; d2h "
        "and the disk write overlap the next epoch (Checkpointer "
        "async_save); False restores fully synchronous saves",
    )
    mesh_axes: str = config_field(
        "",
        "composed-mesh axis sizes, e.g. 'dp=2,fsdp=2,tp=2' — selects HOW "
        "the model trains by axis size instead of strategy function "
        "(tpudist.parallel.mesh.MeshSpec); empty keeps the legacy "
        "data-parallel path over the provided mesh",
    )


class Trainer:
    def __init__(
        self,
        config: TrainerConfig,
        model_apply: Callable,
        params: Any,
        tx: optax.GradientTransformation,
        mesh: Mesh | MeshSpec,
        train_loader: ShardedLoader,
        test_loader: ShardedLoader | None = None,
        loss_fn: Callable = cross_entropy,
        train_kwargs: dict | None = None,
        seed: int = 0,
    ) -> None:
        self.config = config
        # One declarative knob for HOW the model trains: a MeshSpec (passed
        # directly or parsed from config.mesh_axes) selects axis sizes; the
        # strategy follows from them (make_composed_train_step).  No spec =
        # the legacy data-parallel path over the provided mesh, unchanged.
        self.mesh_spec: MeshSpec | None = None
        if isinstance(mesh, MeshSpec):
            self.mesh_spec = mesh
            mesh = mesh.build()
        elif config.mesh_axes:
            self.mesh_spec = MeshSpec.parse(config.mesh_axes)
            for name, size in self.mesh_spec.axis_sizes().items():
                if mesh.shape.get(name) != size:
                    raise ValueError(
                        f"config.mesh_axes={config.mesh_axes!r} wants axis "
                        f"{name}={size} but the provided mesh has "
                        f"{dict(mesh.shape)}; build it with "
                        "MeshSpec.parse(config.mesh_axes).build()")
        if self.mesh_spec is not None and self.mesh_spec.pp > 1:
            raise ValueError(
                "Trainer's epoch/eval/snapshot loop assumes a "
                "(state, inputs, labels) step; pipeline (pp > 1) training "
                "uses stage-stacked params and a schedule-specific batch "
                "layout — drive tpudist.parallel.mesh."
                "make_composed_train_step directly")
        self.mesh = mesh
        self.train_loader = train_loader
        self.test_loader = test_loader
        self.epochs_run = 0
        train_kwargs = train_kwargs or {}
        if config.batch_size != train_loader.global_batch:
            raise ValueError(
                f"TrainerConfig.batch_size={config.batch_size} does not match "
                f"train_loader.global_batch={train_loader.global_batch}; the "
                "config value is the single source of truth for the CLI surface"
            )

        def dp_loss(params, batch, rng):
            inputs, labels = batch
            logits = model_apply(
                {"params": params}, inputs, rngs={"dropout": rng}, **train_kwargs
            )
            return loss_fn(logits, labels), {}

        def dp_predict(params, inputs):
            return model_apply({"params": params}, *inputs)

        spec = self.mesh_spec
        if spec is None:
            self.state = TrainState.create(
                apply_fn=model_apply,
                params=broadcast_params(params, mesh),
                tx=tx,
                rng=jax.random.key(seed),
            )
        else:
            self.state, self._param_specs = make_composed_state(
                model_apply, params, tx, spec, mesh,
                rng=jax.random.key(seed))
        # ONE save path shared with the elastic runtime: the flat layout
        # keeps the reference's rolling snapshot.npz contract while async
        # saves overlap d2h + disk write with the next epoch's compute
        self._ckpt = Checkpointer(config.snapshot_path,
                                  async_save=config.async_snapshot,
                                  layout="flat")
        self._maybe_load_snapshot()
        if spec is None:
            self.train_step = make_dp_train_step(dp_loss, mesh)
            self.train_loop = (
                make_dp_train_loop(dp_loss, mesh)
                if config.steps_per_dispatch > 1 else None
            )
            self.eval_step = make_dp_masked_eval_step(dp_predict, mesh)
        else:
            pure_dp = spec.fsdp == spec.tp == spec.ep == 1
            self.train_step = make_composed_train_step(
                spec, mesh, dp_loss, params=self.state.params)
            if config.steps_per_dispatch > 1:
                if not pure_dp:
                    raise ValueError(
                        "steps_per_dispatch > 1 (the fused dp scan loop) "
                        "is data-parallel only; set it to 1 for "
                        "fsdp/tp/ep specs")
                self.train_loop = make_dp_train_loop(dp_loss, mesh,
                                                     axis="dp")
            else:
                self.train_loop = None
            self.eval_step = make_composed_eval_step(dp_predict, mesh)
        self.metrics = MetricLogger()
        self.throughput = ThroughputMeter(warmup_steps=2)
        # obs handles cached once: the hot loop touches them by attribute,
        # not by registry lookup.  Recording stays lazy — the loss gauge
        # takes the device array as-is; counters take host ints; the
        # step-time histogram takes host floats — so nothing here adds a
        # sync to the step path (snapshot() pays the one batched sync).
        self._obs_steps = obs.counter("train/steps", unit="steps")
        self._obs_examples = obs.counter("train/examples", unit="examples")
        self._obs_epochs = obs.counter("train/epochs", unit="epochs")
        self._obs_loss = obs.gauge("train/loss")
        self._obs_tput = obs.gauge("train/images_per_sec", unit="img/s")
        self._obs_step_time = obs.histogram("train/step_time", unit="s")
        # per-optimizer-step program FLOPs, filled by the one-time cost
        # probe on the first dispatch; feeds the live MFU gauge
        self._step_flops: float | None = None
        self._cost_probed = False

    def _probe_cost(self, fn, steps_per_dispatch: int, *args) -> None:
        """One-time lower() of the step program: cost_analysis() FLOPs for
        the live ``xla/mfu`` gauge, HLO text for the flight recorder's
        post-mortem bundle.  No dispatch; no compile either where the
        lowered stage has a cost analysis (the CPU) — on the TPU only
        the compiled program has one (see ``obs.xla.cost_flops``)."""
        if self._cost_probed:
            return
        self._cost_probed = True
        lower = getattr(fn, "lower", None)
        if lower is None:
            return
        try:
            with obs.span("cost_probe"):
                lowered = lower(self.state, *args)
            flops = obs_xla.cost_flops(lowered)
            if flops is not None:
                self._step_flops = flops / steps_per_dispatch
            try:
                hlo = lowered.as_text(dialect="hlo")
            except Exception:  # noqa: BLE001 - dialect arg may vanish
                hlo = lowered.as_text()
            obs.recorder.note_hlo(hlo)
        except Exception as e:  # noqa: BLE001 - telemetry must not stop training
            log.debug("cost probe failed: %s", e)

    # -- snapshotting (`_save_snapshot`/`_load_snapshot` parity, with full state)

    def _maybe_load_snapshot(self) -> None:
        import os

        if os.path.exists(self.config.snapshot_path):
            tree, meta = restore_pytree(
                self.config.snapshot_path,
                {
                    "params": self.state.params,
                    "opt_state": self.state.opt_state,
                    "rng": self.state.rng,
                },
            )
            if self.mesh_spec is None:
                params = broadcast_params(tree["params"], self.mesh)
                opt_state = broadcast_params(tree["opt_state"], self.mesh)
            else:
                # restore into the composed layout: every leaf goes back
                # where its live counterpart lives (fsdp/tp/ep shards
                # included), not broadcast-replicated
                place = lambda new, like: jax.device_put(new, like.sharding)
                params = jax.tree.map(place, tree["params"],
                                      self.state.params)
                opt_state = jax.tree.map(place, tree["opt_state"],
                                         self.state.opt_state)
            self.state = self.state.replace(
                params=params,
                opt_state=opt_state,
                rng=tree["rng"],
                step=jnp.asarray(meta.get("step", 0), jnp.int32),
            )
            self.epochs_run = int(meta.get("epochs_run", 0))
            log.info("Resuming from snapshot at epoch %d", self.epochs_run)

    def _save_snapshot(self, epoch: int) -> None:
        if jax.process_index() != 0:
            return
        # step stays a DEVICE scalar: Checkpointer stages an on-device
        # copy at initiation (so the next epoch's donating dispatch can't
        # delete it) and resolves it on the writer thread — the epoch
        # boundary never syncs on it
        with obs.span("snapshot_save", epoch=epoch):
            self._ckpt.save(
                epoch,
                {
                    "params": self.state.params,
                    "opt_state": self.state.opt_state,
                    "rng": self.state.rng,
                },
                meta={"epochs_run": epoch + 1, "step": self.state.step},
            )
        log.info("Epoch %d | snapshot save initiated to %s (async=%s)",
                 epoch, self.config.snapshot_path, self._ckpt.async_save)

    # -- the hot loop (`_run_epoch`/`_run_batch` parity)

    def _feed(self, batches):
        """Device-input pipelining for the hot loop: keep
        ``config.device_prefetch`` batches' transfers in flight ahead of
        the step (0 = plain synchronous pull).  Skipped when the loader's
        Python-thread fallback already drives the stream ahead
        (``ShardedLoader.thread_prefetch``): one prefetch layer only —
        wrapping twice would double the buffered batches and the worker
        threads.  Consumer stalls surface as the ``data/input_stall``
        counter."""
        if (self.config.device_prefetch > 0
                and not self.train_loader.thread_prefetch):
            return device_prefetch(batches, depth=self.config.device_prefetch)
        return batches

    def _run_epoch(self, epoch: int) -> dict:
        self.throughput.start()
        n = self.config.steps_per_dispatch
        start_step = 0
        if self.train_loop is not None:
            # Fused path: n optimizer steps per compiled dispatch.
            groups = self.train_loader.stacked_groups(n)
            start_step = groups * n
            for g, batch in enumerate(
                    self._feed(self.train_loader.epoch_stacked(epoch, n))):
                self._probe_cost(self.train_loop, n, *batch)
                t0 = time.perf_counter()
                with obs.span("train_dispatch", steps=n):
                    self.state, metrics = self.train_loop(self.state, *batch)
                # stacked [n] metrics accumulate lazily; MetricLogger
                # weights every optimizer step equally
                self.metrics.update(**metrics)
                self.throughput.step(n * self.train_loader.global_batch)
                # the loss gauge keeps the stacked DEVICE array; its last
                # element is folded out at snapshot time, never here
                self._obs_loss.set(metrics["loss"])
                self._obs_steps.inc(n)
                self._obs_examples.inc(n * self.train_loader.global_batch)
                self._obs_step_time.record((time.perf_counter() - t0) / n)
                if (g * n) % self.config.log_every < n:
                    loss = float(metrics["loss"][-1])
                    log.info("epoch %d step %d loss %.4f", epoch,
                             g * n + n - 1, loss)
                    # flight-recorder breadcrumb at log granularity: the
                    # loss is already on host here, so this adds no sync
                    obs.recorder.record("train_log", epoch=epoch,
                                        step=g * n + n - 1, loss=loss)
        for step, batch in enumerate(
                self._feed(self.train_loader.epoch(epoch,
                                                   start_step=start_step)),
                start=start_step):
            self._probe_cost(self.train_step, 1, *batch)
            t0 = time.perf_counter()
            with obs.span("train_step", step=step):
                self.state, metrics = self.train_step(self.state, *batch)
            # device scalars accumulate lazily; the host sync happens once per
            # epoch (and at log points), not per step
            self.metrics.update(**metrics)
            self.throughput.step(self.train_loader.global_batch)
            self._obs_loss.set(metrics["loss"])
            self._obs_steps.inc()
            self._obs_examples.inc(self.train_loader.global_batch)
            # dispatch time unless TPUDIST_OBS_FENCE=1 makes spans fence
            self._obs_step_time.record(time.perf_counter() - t0)
            if step % self.config.log_every == 0:
                loss = float(metrics["loss"])
                log.info("epoch %d step %d loss %.4f", epoch, step, loss)
                obs.recorder.record("train_log", epoch=epoch, step=step,
                                    loss=loss)
        return self.metrics.reset()

    def train(self, max_epochs: int | None = None) -> dict:
        # any unhandled exception dumps a post-mortem bundle (event ring,
        # final snapshot, HLO) before propagating
        with obs.recorder.guard("trainer", epochs_run=self.epochs_run):
            return self._train(max_epochs)

    def _train(self, max_epochs: int | None = None) -> dict:
        max_epochs = max_epochs or self.config.total_epochs
        summary: dict = {}
        start_epoch = self.epochs_run
        for epoch in range(start_epoch, max_epochs):
            profiling = self.config.profile_dir and epoch == start_epoch
            with maybe_profile(self.config.profile_dir if profiling else None):
                # obs spans nest inside the XProf trace (TraceAnnotation)
                with obs.span("train_epoch", epoch=epoch):
                    epoch_metrics = self._run_epoch(epoch)
            self._obs_epochs.inc()
            self._obs_tput.set(self.throughput.items_per_sec)
            # live efficiency gauges, refreshed per epoch: MFU from the
            # cost probe's FLOPs over the measured mean step time, and
            # the per-device HBM stats (no-ops off-TPU)
            obs_xla.note_step(self.throughput.mean_step_time,
                              self._step_flops)
            obs_xla.update_memory_gauges()
            summary = {"epoch": epoch, **epoch_metrics}
            obs.recorder.record(
                "epoch_end", epoch=epoch,
                loss=epoch_metrics.get("loss"),
                images_per_sec=round(self.throughput.items_per_sec, 2))
            if self.config.eval_every_epoch and self.test_loader is not None:
                summary["test_accuracy"] = self.test()
                log.info(
                    "epoch %d done | loss %.4f | test acc %.2f%%",
                    epoch, epoch_metrics.get("loss", float("nan")),
                    100 * summary["test_accuracy"],
                )
            if epoch % self.config.save_every == 0:
                self._save_snapshot(epoch)
            self.epochs_run = epoch + 1
        # join the in-flight async snapshot write: train() returning
        # means the last snapshot is durable on disk
        self._ckpt.wait()
        summary["images_per_sec"] = self.throughput.items_per_sec
        return summary

    def test(self) -> float:
        """Exact test accuracy: each real sample counted once — the
        validity mask zeroes wrap-around padding from ``drop_last=False``
        sharding, and the denominator is the true number of evaluated
        samples, not batches × batch-size (the reference divides by the
        padded sampler length, `mnist_ddp_elastic.py:117-130`)."""
        assert self.test_loader is not None
        correct: list = []
        seen: list = []
        for step, batch in enumerate(self.test_loader.epoch(0)):
            mask = self.test_loader.valid_mask(step)
            c, t = self.eval_step(self.state.params, *batch, mask)
            # accumulate DEVICE scalars; steps chain async without the
            # two per-step host syncs the reference-era loop paid
            correct.append(c)
            seen.append(t)
        if not seen:
            return 0.0
        cs, ts = jax.device_get((correct, seen))  # ONE sync per evaluation
        return int(sum(int(x) for x in cs)) / max(int(sum(int(x) for x in ts)), 1)
