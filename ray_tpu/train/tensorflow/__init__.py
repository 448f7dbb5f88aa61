"""TensorflowTrainer — TF_CONFIG distributed Keras on the WorkerGroup.

Reference: python/ray/train/tensorflow/config.py (`TensorflowConfig`,
`_setup_tensorflow_environment`: every worker gets a TF_CONFIG env var
naming the full worker cluster + its own task index, which
`tf.distribute.MultiWorkerMirroredStrategy` reads at construction) and
python/ray/train/tensorflow/tensorflow_trainer.py:25 (`TensorflowTrainer`).
Keras report callback analog of python/ray/train/tensorflow/keras.py
(`ReportCheckpointCallback`).

TPU-first note: this trainer exists for CPU/host-side TF workloads and
API parity (reference users bring `train_loop_per_worker` unchanged).
The TPU compute path is JaxTrainer/GSPMD — TF-on-TPU is deliberately not
wired (one compiler stack on the chips: XLA via JAX).

Keras 3 (bundled with TF >= 2.16) removed `model.fit` support under
MultiWorkerMirroredStrategy: multi-worker loops must use
`strategy.run` + `strategy.experimental_distribute_dataset` (the custom
training loop in tests/test_tensorflow_trainer.py is the template).
`ReportCheckpointCallback` remains for single-worker `model.fit`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional

from ray_tpu.train.backend import Backend, BackendConfig
from ray_tpu.train.data_parallel_trainer import DataParallelTrainer

__all__ = [
    "TensorflowConfig",
    "TensorflowTrainer",
    "prepare_dataset_shard",
    "ReportCheckpointCallback",
]


@dataclasses.dataclass
class TensorflowConfig(BackendConfig):
    @property
    def backend_cls(self):
        return _TensorflowBackend


def _set_tf_config(cluster_workers: List[str], index: int) -> None:
    """Runs inside each train worker BEFORE the user loop imports TF."""
    os.environ["TF_CONFIG"] = json.dumps({
        "cluster": {"worker": cluster_workers},
        "task": {"type": "worker", "index": index},
    })
    # Workers are CPU hosts here; keep TF off any accelerator.
    os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")


class _TensorflowBackend(Backend):
    def on_start(self, worker_group, backend_config: TensorflowConfig):
        if len(worker_group) <= 1:
            return
        import ray_tpu

        infos = worker_group.execute("get_node_info")
        cluster = [f"{i['ip']}:{i['free_port']}" for i in infos]
        ray_tpu.get([
            w.run_fn.remote(_set_tf_config, cluster, rank)
            for rank, w in enumerate(worker_group.workers)
        ])


class TensorflowTrainer(DataParallelTrainer):
    def __init__(self, train_loop_per_worker, *,
                 tensorflow_config: Optional[TensorflowConfig] = None,
                 **kwargs):
        super().__init__(train_loop_per_worker,
                         backend_config=tensorflow_config
                         or TensorflowConfig(),
                         **kwargs)


def prepare_dataset_shard(tf_dataset_shard):
    """Disable auto-sharding on a per-worker tf.data pipeline (the shard
    is already per-worker; reference train/tensorflow/train_loop_utils.py).
    """
    import tensorflow as tf

    options = tf.data.Options()
    options.experimental_distribute.auto_shard_policy = (
        tf.data.experimental.AutoShardPolicy.OFF)
    return tf_dataset_shard.with_options(options)


def ReportCheckpointCallback(checkpoint_on: Optional[str] = "epoch_end",
                             metrics: Optional[List[str]] = None):
    """Keras callback: stream epoch logs (and optionally a weights
    checkpoint) through `train.report`. Factory instead of a module-level
    class so `import ray_tpu.train.tensorflow` stays TF-free.

    checkpoint_on: "epoch_end" (every epoch), "train_end" (once, at the
    end), or None (metrics only).
    """
    import tensorflow as tf

    from ray_tpu import train
    from ray_tpu.train._internal.snapshots import RotatingSnapshots

    if checkpoint_on not in ("epoch_end", "train_end", None):
        raise ValueError(
            f"checkpoint_on={checkpoint_on!r}: expected 'epoch_end', "
            "'train_end', or None")

    class _Callback(tf.keras.callbacks.Callback):
        # Reports are queued and persisted asynchronously by the driver
        # poll, so snapshot dirs rotate (RotatingSnapshots) instead of
        # being deleted inline.
        def __init__(self):
            super().__init__()
            self._snapshots = RotatingSnapshots()

        def _save_checkpoint(self):
            if train.get_context().get_world_rank() != 0:
                return None
            d = self._snapshots.make("keras_ckpt_")
            # Keras 3 requires the .weights.h5 suffix.
            self.model.save_weights(
                os.path.join(d, "model.weights.h5"))
            return train.Checkpoint.from_directory(d)

        def on_epoch_end(self, epoch: int, logs: Optional[Dict] = None):
            logs = dict(logs or {})
            out = ({k: logs[k] for k in metrics if k in logs}
                   if metrics else logs)
            out["epoch"] = epoch
            ckpt = (self._save_checkpoint()
                    if checkpoint_on == "epoch_end" else None)
            train.report(out, checkpoint=ckpt)

        def on_train_end(self, logs: Optional[Dict] = None):
            if checkpoint_on == "train_end":
                train.report({"train_end": True},
                             checkpoint=self._save_checkpoint())

    return _Callback()
