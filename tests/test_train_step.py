"""The benchmark's harness imports the train step as ``bench.train_step_fn``
(``perfbench/kinds/``); the step lives in ``apex_tpu.train``. What the cells
time must be the package's own step, and ``bench.py`` nothing but that name."""
import bench
import apex_tpu.train


def test_the_harness_times_the_packages_train_step():
    assert bench.train_step_fn is apex_tpu.train.train_step_fn


def test_the_harness_describes_arguments_as_the_package_does():
    assert bench.abstract_train_args is apex_tpu.train.abstract_train_args


def test_bench_defines_nothing_of_its_own():
    public = sorted(n for n in vars(bench) if not n.startswith("_"))
    assert public == ["abstract_train_args", "train_step_fn"]
