"""The name the benchmark's harness imports the train step by.

``perfbench/kinds/*.py`` say ``import bench; bench.train_step_fn(...)``; the
step itself lives in the package, ``apex_tpu.train``. This file defines
nothing of its own and goes when the harness imports the package directly
(ROADMAP D3a).
"""

from apex_tpu.train import abstract_train_args, train_step_fn  # noqa: F401
