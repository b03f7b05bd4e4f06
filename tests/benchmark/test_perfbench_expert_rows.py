"""The expert layer's product behind the two cells that run it: the
programs' spans say which product ran (`experts`), the counter
`moe_expert_rows` reaches the two readers of rows a held pair, and a
program without the counter reads None, not an error."""
import importlib.util
import os

import pytest

from bench_testlib import BENCH, benchmark_json, rehearse
from benchlib import program_spans as P

READERS = {'serve-trinity.window-docs': 'wdocs_expert_rows_per_pair',
           'serve-kimi-linear.long-answers': 'answers_expert_rows_per_pair'}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        'perfbench_metric_' + name, os.path.join(BENCH, 'metrics',
                                                 name + '.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize('cell', sorted(READERS))
def test_the_product_and_its_rows_reach_the_readers(cell):
    """A traced rehearsal: every prefill call and decode burst names the
    product its expert layers took, and the reader gives the rows a held
    pair, at least one (each held pair is multiplied) and a whole row
    tile or the dense product's rows over the pairs."""
    from paddle_tpu.text.models import moe
    result, obs = rehearse(cell, seed=2 ** 31 + 31, trace=1)
    name = READERS[cell]
    value = result['metrics'][name]['value']
    assert value >= 1.0
    spans = {n: P.window_spans(obs, n) for n in
             ('serving.prefill_call', P.BURST)}
    assert spans[P.BURST]
    for n, found in spans.items():
        paths = {s['tags']['experts'] for s in found or ()}
        assert paths and paths <= {'dense', 'grouped'}, n
    m = obs['model']
    top_k = m.get('num_experts_per_tok', m.get('num_experts_per_token'))
    want = moe.expert_path(obs['engine']['num_seqs'], top_k,
                           m['num_experts_published'])
    assert {s['tags']['experts'] for s in spans[P.BURST]} == {want}
    # the entry names its one cell, after every entry that was there
    entry = next(e for e in benchmark_json()['per_layer']
                 if e['name'] == name)
    assert entry['workloads'] == [cell] and entry['unit'] == 'rows'


@pytest.mark.parametrize('name', sorted(READERS.values()))
def test_a_program_without_the_counter_reads_none(name, monkeypatch):
    """The parent's program counts the routing but not the rows."""
    tags = {'moe_pairs': 64, 'moe_pairs_held': 16,
            'moe_experts_touched': 9, 'moe_load_max': 3}
    monkeypatch.setattr(P, 'window_spans',
                        lambda obs, n: [{'tags': dict(tags)}])
    assert reader(name).read({}) is None
    tags['moe_expert_rows'] = 96
    assert reader(name).read({}) == 6.0
