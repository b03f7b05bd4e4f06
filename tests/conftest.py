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
