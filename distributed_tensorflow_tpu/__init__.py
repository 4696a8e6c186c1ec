"""distributed_tensorflow_tpu — a TPU-native (JAX/XLA/Pallas) framework.

Re-implements, TPU-first, every capability of the reference repo
BonneyBB/distributed_tensorflow (four TF 1.x scripts: single/distributed MNIST
CNN training and single/distributed Inception-v3 transfer learning — see
SURVEY.md). Design principles:

* Compute path is JAX/XLA: models are pure ``init/apply`` pairs, training steps
  are jitted, data-parallelism is SPMD over a ``jax.sharding.Mesh`` with
  explicit ``lax.psum`` collectives over ICI — replacing the reference's
  parameter-server/gRPC architecture (``demo2/train.py:18-29``).
* No per-step host→device feed_dict: batches are device-resident, inputs are
  prefetched (reference stalls on ``sess.run(..., feed_dict=...)`` every step,
  ``demo1/train.py:155``).
* Checkpointing is Orbax (replacing ``tf.train.Saver`` / ``Supervisor``
  autosave, ``demo2/train.py:166-176``); observability is a self-contained
  TensorBoard event writer (replacing ``tf.summary``).
"""

__version__ = "0.1.0"

from distributed_tensorflow_tpu import config  # noqa: F401
