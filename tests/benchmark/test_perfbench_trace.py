"""The trace reduction on the small recorded v5e trace."""
import os

import pytest

from bench_testlib import BENCH
from benchlib import trace as T


@pytest.fixture(scope='module')
def recorded():
    return T.load_json(os.path.join(BENCH, 'data', 'recorded_trace.json'))


def test_busy_is_the_union_of_intervals():
    assert T.merged([(0, 10), (5, 12), (20, 30), (30, 31)]) == [
        [0, 12], [20, 31]]


def test_self_times_take_nested_operations_out():
    got = dict(T.self_times([('while', 0, 100), ('a', 10, 40),
                             ('b', 50, 90), ('c', 120, 130)]))
    assert got == {'while': 30e-9, 'a': 30e-9, 'b': 40e-9, 'c': 10e-9}


def test_operation_names():
    assert T.op_name('%fusion.12 = bf16[8]{0} fusion(%p)') == 'fusion'
    assert T.op_name('%checkpoint.3 = bf16[2] custom-call(%a), '
                     'custom_call_target="tpu_custom_call"') == \
        'tpu_custom_call:checkpoint'
    assert T.module_name('jit__decode_fn(3081035848265034443)') == \
        '_decode_fn'


def test_recorded_trace_reduces(recorded):
    red = T.reduce_trace(recorded)
    assert red['chips'] == 1
    assert 0 < red['busy_s'] < red['window_s']
    # three traced rounds of one decode program and one train program
    assert len(red['modules']['_train_fn']) == 3
    assert len(red['modules']['_decode_fn']) >= 2
    # self times add up to the busy time: nothing is counted twice
    assert sum(red['ops'].values()) == pytest.approx(red['busy_s'], rel=1e-6)
    # the Pallas flash kernels are found by their custom-call target
    assert T.ops_matching(red, 'tpu_custom_call') > 0
    # every idle gap lands on one of the benchmark's spans
    assert set(red['gaps']) <= {'bench.engine_step', 'bench.next_batch',
                                'bench.step_dispatch', 'bench.loss_fetch',
                                'no bench span'}
    idle = red['window_s'] - red['busy_s']
    assert sum(red['gaps'].values()) <= idle + 1e-9
    assert sum(red['gaps'].values()) > 0.9 * idle


def test_breakdown_shape(recorded):
    b = T.breakdown(T.reduce_trace(recorded))
    assert set(b) == {'device_ops', 'idle_gaps'}
    assert len(b['device_ops']) <= 10 and len(b['idle_gaps']) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in b['device_ops'])


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError):
        T.reduce_trace({'planes': [{'name': '/host:CPU', 'lines': []}]})
