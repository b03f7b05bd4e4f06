"""Test bootstrap: force an 8-device virtual CPU mesh (SURVEY.md §4.2-d).

Tests never want a chip — they want 8 virtual CPU devices so
sharding/mesh tests run hardware-free (the reference's analog is
TestDistBase spawning localhost trainers), and a test process that took
the chip would hold it against everything else on the host. Backend
selection is lazy in jax, so flipping config here (before any test touches
a backend) is sufficient; XLA_FLAGS is read when the CPU client initializes.
"""
import os

os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') +
                           ' --xla_force_host_platform_device_count=8').strip()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import pytest  # noqa: E402


def _register_benchmark_standins():
    """The tiny stand-ins of the benchmark cells added since PR 30 under
    the names `bench_testlib.tiny_benchmark()` looks up (PR 30's are in
    tests/benchmark/conftest.py: a PR adds benchmark files and edits
    none, and a directory has one conftest). Here, so that any one
    benchmark test file still runs alone."""
    import sys
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'benchmark')
    if here not in sys.path:
        sys.path.insert(0, here)
    import bench_testlib
    bench_testlib.TINY_CONFIG['kimi-linear-48b-serve-1chip'] = \
        'tiny-kimi-linear'
    bench_testlib.TINY_TRAFFIC['long-answers'] = 'tiny-long-answers'


_register_benchmark_standins()


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'slow: scale/perf datapoints excluded from the tier-1 '
        "run (-m 'not slow')")
    config.addinivalue_line(
        'markers', 'chaos: fault-injection tests (testing/chaos.py) that '
        'exercise failure paths against live loopback servers')


@pytest.fixture
def seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    return 2024
