"""Training loop: jit + shardings, NaN guards, anomaly rollback, periodic +
emergency checkpointing, automatic resume.  Runs identically on 1 CPU device
(examples) and under the production mesh (launch/train.py).

Fault-tolerance model (DESIGN.md §Training robustness):

* **NaN guard** (train_step): a non-finite loss/grad-norm suppresses the
  update inside the jitted step; the Trainer counts the skip and either
  continues (``nan_policy="skip"``) or halts with a tagged checkpoint.
* **Anomaly guard** (train.anomaly): an EWMA/z-score detector over the loss
  and grad-norm streams catches *finite* divergence.  On a spike the
  Trainer rolls params+opt back to the last **verified** checkpoint and
  does NOT rewind the data stream — the deterministic stream is already
  positioned past the offending window, so the bad batch is never replayed.
  Consecutive rollbacks without a new checkpoint in between are bounded by
  ``AnomalyConfig.max_rollbacks``; exhausting them raises
  :class:`~repro.train.anomaly.AnomalyHalt` after a ``-anomaly-halt``
  tagged save.
* **Verified resume** (train.checkpoint): construction resumes from the
  newest checkpoint that passes manifest verification, counting any
  torn/corrupt ones it skipped in ``counters["torn_ckpt_fallbacks"]``.
* **Emergency save**: an escaping exception triggers a best-effort
  ``-emergency`` tagged save — tag-suffixed so it can never clobber a good
  periodic checkpoint at the same step — and a save failure is *logged and
  counted*, never silently discarded.
* **Fault injection** (repro.faults): the train-domain points ``nan_grad``,
  ``loss_spike``, ``data_shard_corrupt`` are consulted once per step and
  ``ckpt_torn_write`` once per save, so the chaos suite
  (tests/test_train_chaos.py) can drive every recovery path
  deterministically.
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as shd
from repro.faults import NULL_INJECTOR
from repro.obs.clock import resolve_clock
from repro.obs.trace import get_recorder
from repro.models import lm
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt_mod
from repro.train.anomaly import AnomalyConfig, AnomalyDetector, AnomalyHalt
from repro.train.elastic import counters_view
from repro.train.train_step import make_train_step
from repro.distributed.sharding import maybe_set_mesh

import os


#: Loss/grad-norm multiplier an injected ``loss_spike`` applies when its
#: spec leaves ``scale`` unset.
DEFAULT_SPIKE_SCALE = 64.0


def _scramble_labels(batch: dict, step: int, vocab: int) -> dict:
    """Deterministic stand-in for a corrupt data shard: the labels become
    uniform random tokens (keyed by step), decoupled from the inputs — the
    loss excursion that results is the anomaly guard's to catch."""
    rng = np.random.Generator(np.random.Philox(key=[0xDA7A ^ step, 0]))
    bad = dict(batch)
    labels = np.asarray(batch["labels"])
    bad["labels"] = rng.integers(0, vocab, labels.shape).astype(labels.dtype)
    return bad


class Trainer:
    def __init__(
        self,
        cfg,
        opt_cfg: opt_mod.OptimizerConfig,
        dataset,
        *,
        workdir: str,
        mesh=None,
        seed: int = 0,
        log_every: int = 10,
        ckpt_every: int = 200,
        ckpt_keep: int = 3,
        nan_policy: str = "skip",  # skip | halt
        anomaly: AnomalyConfig | None = None,
        faults=None,
        clock=None,
        trace=None,
    ):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.dataset = dataset
        self.workdir = workdir
        self.mesh = mesh
        self.log_every = log_every
        self.ckpt_every = ckpt_every
        self.ckpt_keep = ckpt_keep
        self.nan_policy = nan_policy
        self.anomaly = anomaly or AnomalyConfig()
        self.faults = faults or NULL_INJECTOR
        self.clock = resolve_clock(clock)
        self.trace = trace if trace is not None else get_recorder()
        self.ckpt_dir = os.path.join(workdir, "checkpoints")
        os.makedirs(self.ckpt_dir, exist_ok=True)

        self.counters: Counter = Counter()
        self._detector = AnomalyDetector(self.anomaly)
        self._ckpts_written = 0
        self._rollback_streak = 0
        self._rollback_ckpt_mark = -1

        key = jax.random.PRNGKey(seed)
        self._p_shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg), key)
        self._o_shapes = jax.eval_shape(opt_mod.adamw_init, self._p_shapes)
        self._step_fn = jax.jit(
            make_train_step(cfg, opt_cfg), donate_argnums=(0, 1)
        )

        self.step = 0
        self.history: list[dict] = []
        if ckpt.latest_step(self.ckpt_dir) is not None:
            step, params, opt_state, meta = ckpt.load_checkpoint(
                self.ckpt_dir, self._p_shapes, self._o_shapes
            )
            self.counters["torn_ckpt_fallbacks"] += meta.get(
                "_fallback_skipped", 0
            )
            self.step = step
            self._set_state(params, opt_state)
            if meta.get("data_state"):
                self.dataset.restore(meta["data_state"])
            print(f"[trainer] resumed from step {self.step} "
                  f"({meta.get('_name')})")
        else:
            params = lm.init_params(key, cfg)
            opt_state = opt_mod.adamw_init(params)
            self._set_state(params, opt_state)
            # Baseline checkpoint: the anomaly guard always has a verified
            # rollback target, even before the first periodic save.
            self._checkpoint()

    # ------------------------------------------------------------------
    def _set_state(self, params, opt_state) -> None:
        """Install (host or device) params/opt, sharded under the mesh."""
        if self.mesh is not None:
            axes = lm.param_axes(self.cfg)
            p_shard = shd.param_shardings(
                axes, self._p_shapes, self.mesh, fsdp=self.cfg.fsdp
            )
            o_shard = {
                "m": p_shard,
                "v": p_shard,
                "count": shd.replicated(self.mesh),
            }
            self.params = jax.tree_util.tree_map(jax.device_put, params, p_shard)
            self.opt_state = jax.tree_util.tree_map(
                jax.device_put, opt_state, o_shard
            )
        else:
            self.params = params
            self.opt_state = opt_state

    def _checkpoint(self, tag: str = "") -> None:
        ckpt.save_checkpoint(
            self.ckpt_dir,
            self.step,
            self.params,
            self.opt_state,
            self.dataset.state(),
            extra_meta={"arch": self.cfg.name},
            keep=self.ckpt_keep,
            tag=tag,
            faults=self.faults,
        )
        self._ckpts_written += 1
        self.trace.instant("ckpt", step=self.step, tag=tag)

    def counters_snapshot(self) -> dict:
        """Robustness counters, zero-filled to the frozen schema
        (train.elastic.COUNTER_KEYS)."""
        return counters_view(self.counters)

    # ------------------------------------------------------------------
    def restore_from_checkpoint(self, *, restore_data: bool = True) -> int:
        """Reload params+opt (and optionally the data cursor) from the
        newest *verified* checkpoint; rewinds ``step`` and trims history.
        ``restore_data=False`` is the anomaly-rollback mode: the data
        stream stays where it is — already advanced past the offending
        window — so the bad batch is never replayed.  Returns the restored
        step."""
        step, params, opt_state, meta = ckpt.load_checkpoint(
            self.ckpt_dir, self._p_shapes, self._o_shapes
        )
        self.counters["torn_ckpt_fallbacks"] += meta.get("_fallback_skipped", 0)
        self.step = step
        self._set_state(params, opt_state)
        if restore_data and meta.get("data_state"):
            self.dataset.restore(meta["data_state"])
        self.history = [r for r in self.history if r["step"] <= step]
        # The detector's EWMA stats are deliberately KEPT: restored params
        # re-live the pre-spike loss regime those stats describe.  Resetting
        # here would let a *persistent* divergence launder itself into the
        # warmup as the new baseline and never flag again.
        return step

    def _rollback_or_halt(self, loss: float, report: dict) -> None:
        """Anomaly response: bounded rollback to the last verified
        checkpoint, else :class:`AnomalyHalt` with a tagged forensic save."""
        if self._ckpts_written > self._rollback_ckpt_mark >= 0:
            # a checkpoint landed since the last rollback — that's forward
            # progress, so the retry budget resets
            self._rollback_streak = 0
        if self._rollback_streak >= self.anomaly.max_rollbacks:
            self.counters["anomaly_halts"] += 1
            self.trace.instant("anomaly_halt", step=self.step)
            self._checkpoint(tag="anomaly-halt")
            raise AnomalyHalt(
                self.step, self._rollback_streak,
                f"loss={loss:.4g}, z={report}",
            )
        self._rollback_streak += 1
        self._rollback_ckpt_mark = self._ckpts_written
        self.counters["rollbacks"] += 1
        at = self.step
        restored = self.restore_from_checkpoint(restore_data=False)
        self.trace.instant("rollback", at=at, restored=restored)
        print(
            f"[trainer] anomaly at step {at} (loss {loss:.4g}, {report}): "
            f"rolled back to step {restored}, data stream advanced past "
            f"the window (retry {self._rollback_streak}/"
            f"{self.anomaly.max_rollbacks})"
        )

    # ------------------------------------------------------------------
    def step_once(self) -> dict | None:
        """One training step with all guards.  Returns the history record,
        or None when the step was consumed by an anomaly rollback (``step``
        then rewound to the restored checkpoint)."""
        with self.trace.span("train/step", step=self.step):
            return self._step_once_inner()

    def _step_once_inner(self) -> dict | None:
        with self.trace.span("data", step=self.step):
            batch = self.dataset.next_batch()
        if self.faults.fires("data_shard_corrupt") is not None:
            batch = _scramble_labels(batch, self.step, self.cfg.vocab)
            self.counters["data_corrupt_batches"] += 1
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        inject = (
            float("nan")
            if self.faults.fires("nan_grad") is not None
            else 0.0
        )
        t0 = self.clock()
        # The mesh context is what lets trace-time dispatch see the
        # mesh: sharding constraints in the model and the ring
        # context-parallel attention (core.api._active_context_mesh)
        # both read the active mesh.
        with self.trace.span("fwd_bwd", step=self.step), \
                maybe_set_mesh(self.mesh):
            new_params, new_opt, metrics = self._step_fn(
                self.params, self.opt_state, batch,
                jnp.asarray(self.step, jnp.int32),
                jnp.asarray(inject, jnp.float32),
            )
        loss = float(metrics["loss"])
        gnorm = float(metrics["grad_norm"])
        spec = self.faults.fires("loss_spike")
        if spec is not None:
            scale = spec.scale if spec.scale > 0 else DEFAULT_SPIKE_SCALE
            loss *= scale
            gnorm *= scale
        skipped = float(metrics.get("skipped", 0.0)) > 0
        self.params, self.opt_state = new_params, new_opt
        if skipped:
            # update was suppressed inside the jitted step (NaN guard)
            self.counters["nan_skips"] += 1
            self.trace.instant("nan_skip", step=self.step)
            if self.nan_policy == "halt":
                self._checkpoint(tag="nan-halt")
                raise FloatingPointError(f"NaN loss at step {self.step}")
            print(f"[trainer] step {self.step}: non-finite loss, skipped")
        else:
            report = self._detector.update(loss, gnorm)
            if report is not None:
                self._rollback_or_halt(loss, report)
                return None
        dt = self.clock() - t0
        self.step += 1
        rec = {"step": self.step, "loss": loss,
               "grad_norm": gnorm,
               "lr": float(metrics["lr"]), "sec": dt}
        self.history.append(rec)
        if self.step % self.log_every == 0:
            print(
                f"[trainer] step {rec['step']:>6} "
                f"loss {rec['loss']:.4f} gnorm {rec['grad_norm']:.3f} "
                f"lr {rec['lr']:.2e} {dt*1e3:.0f} ms"
            )
        if self.step % self.ckpt_every == 0:
            self._checkpoint()
        return rec

    def run(self, num_steps: int) -> list[dict]:
        target = self.step + num_steps
        try:
            while self.step < target:
                self.step_once()
        except KeyboardInterrupt:
            self._checkpoint(tag="interrupt")
            raise
        except (AnomalyHalt, FloatingPointError):
            # already checkpointed under their own tag; no emergency dance
            raise
        except Exception:
            # fault tolerance: best-effort emergency save before
            # propagating.  The tag-suffixed name can never clobber a good
            # periodic checkpoint at the same step, and a failed save is
            # logged + counted — never silently discarded.
            try:
                self._checkpoint(tag="emergency")
                self.counters["emergency_saves"] += 1
                self.trace.instant("emergency_save", step=self.step)
            except Exception as save_err:  # noqa: BLE001
                self.counters["emergency_save_failures"] += 1
                print(
                    f"[trainer] EMERGENCY SAVE FAILED at step {self.step}: "
                    f"{save_err!r}"
                )
            raise
        self._checkpoint(tag="final")
        return self.history
    # run() returns the post-rollback history: records past a rolled-back
    # step are trimmed, so the list always reads as one coherent trajectory.
