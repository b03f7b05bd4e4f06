"""Concurrent serving over the inference front doors.

Two concurrency surfaces, each asserting output parity AND no
cross-talk between simultaneous users:
  1. a Python thread pool where every worker serves its own
     predictor.clone() (the AnalysisPredictor::Clone serving pattern —
     clones share the artifact, not mutable run state);
  2. the C ABI with TWO predictor handles driven from two pthreads in
     one client process (every entry point is GIL-guarded, so
     interleaved Run calls must not mix handles' buffers).
"""
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope='module')
def saved_mlp(tmp_path_factory):
    paddle.seed(2024)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    model.eval()
    path = str(tmp_path_factory.mktemp('concurrent') / 'mlp')
    from paddle_tpu.static import InputSpec
    paddle.jit.save(model, path,
                    input_spec=[InputSpec([2, 8], name='features')])
    return path, model


def _inputs_for(worker):
    # distinct per worker so cross-talk shows up as wrong VALUES, not
    # just races
    return (0.1 * (worker + 1)
            * (np.arange(16, dtype=np.float32) - 8)).reshape(2, 8)


def test_thread_pool_over_predictor_clones(saved_mlp):
    path, model = saved_mlp
    from paddle_tpu import inference
    root = inference.create_predictor(inference.Config(path))
    n_workers, iters = 4, 6
    expect = [model(paddle.to_tensor(_inputs_for(w))).numpy()
              for w in range(n_workers)]

    def worker(w):
        p = root.clone()           # own run state, shared artifact
        x = _inputs_for(w)
        outs = []
        for _ in range(iters):
            outs.append(p.run([x])[0])
        return outs

    with ThreadPoolExecutor(n_workers) as ex:
        results = list(ex.map(worker, range(n_workers)))
    for w, outs in enumerate(results):
        for out in outs:           # every iteration, not just the last:
            # an interleaved write from another clone would corrupt a
            # middle run
            np.testing.assert_allclose(out, expect[w], rtol=1e-5,
                                       atol=1e-6)
    # sanity: the workloads really were distinct
    assert not np.allclose(expect[0], expect[1])


def test_live_metrics_scrape_during_concurrent_serving():
    """Scrape /metrics WHILE a continuous-batching engine serves
    concurrent requests: every mid-flight scrape must be valid
    Prometheus text (the exporter reads under the family locks), and the
    final counters must account for exactly the work done."""
    import threading
    import urllib.request

    from paddle_tpu import monitor
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    from test_monitor import _parse_exposition

    # counters and scrape validity are the subject here, not parity, so
    # the model is as small as the engine accepts
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                    num_heads=2, max_position_embeddings=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(3)
    prompts = [[int(t) for t in rng.randint(0, 97, n)]
               for n in (3, 11, 7, 9, 5, 13)]
    mnt = 4

    reg = monitor.default_registry()

    def counter(name):
        return reg.get(name).labels().value() if reg.get(name) else 0.0

    # engine construction registers the families; baselines AFTER it
    eng = PagedContinuousBatchingEngine(model, num_seqs=3, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4)
    base = {n: counter(n) for n in
            ('serving_requests_total', 'serving_requests_admitted_total',
             'serving_requests_retired_total', 'serving_tokens_total')}

    results = [None] * 3
    bodies = []
    done = threading.Event()

    def worker(i):
        results[i] = eng.generate(prompts[2 * i:2 * i + 2],
                                  max_new_tokens=mnt)

    with monitor.MetricsServer(registry=reg) as srv:
        def scraper():
            while not done.is_set():
                bodies.append(urllib.request.urlopen(
                    srv.url + '/metrics', timeout=5).read().decode())

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        s = threading.Thread(target=scraper)
        s.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        done.set()
        s.join()
        final = urllib.request.urlopen(srv.url + '/metrics',
                                       timeout=5).read().decode()

    # every scrape taken mid-serving parses as valid exposition
    assert bodies, 'scraper never ran'
    for body in bodies:
        _parse_exposition(body)
    types, samples = _parse_exposition(final)
    assert types['serving_tokens_total'] == 'counter'
    assert types['serving_ttft_seconds'] == 'histogram'

    # outputs are untouched by the scraping, and the counters account
    # for exactly the work done
    assert all(len(toks) == mnt for pair in results for toks in pair)
    assert counter('serving_requests_total') - \
        base['serving_requests_total'] == len(prompts)
    assert counter('serving_requests_admitted_total') - \
        base['serving_requests_admitted_total'] == len(prompts)
    assert counter('serving_requests_retired_total') - \
        base['serving_requests_retired_total'] == len(prompts)
    assert counter('serving_tokens_total') - \
        base['serving_tokens_total'] == len(prompts) * mnt
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}
    # the zero-retrace invariant is itself scrapeable
    trace = {(l['program'], v) for n, l, v in samples
             if n == 'serving_trace_count'}
    assert trace >= {('prefill', 1.0), ('decode', 1.0)}


CLIENT_MT_C = r'''
#include <pthread.h>
#include <stdio.h>
#include <stdlib.h>
#include "pd_capi.h"

typedef struct {
  PD_Predictor* pred;
  float scale;
  int iters;
  float out[64];
  int64_t n;
  int rc;
} Job;

static void* worker(void* arg) {
  Job* j = (Job*)arg;
  char name[128];
  if (PD_PredictorGetInputName(j->pred, 0, name, 128) < 0) {
    j->rc = 1;
    return NULL;
  }
  float data[16];
  int64_t shape[2] = {2, 8};
  for (int i = 0; i < 16; ++i) data[i] = j->scale * (float)(i - 8);
  for (int it = 0; it < j->iters; ++it) {
    if (PD_PredictorSetInputFloat(j->pred, name, data, shape, 2) != 0 ||
        PD_PredictorRun(j->pred) != 0) {
      j->rc = 2;
      return NULL;
    }
    j->n = PD_PredictorGetOutputFloat(j->pred, 0, j->out, 64);
    if (j->n < 0 || j->n > 64) {
      j->rc = 3;
      return NULL;
    }
  }
  j->rc = 0;
  return NULL;
}

int main(int argc, char** argv) {
  if (argc < 3) { fprintf(stderr, "usage: client repo model\n"); return 2; }
  if (PD_Init(argv[1]) != 0) {
    fprintf(stderr, "init: %s\n", PD_GetLastError());
    return 3;
  }
  PD_Config* cfg = PD_ConfigCreate();
  PD_ConfigSetModel(cfg, argv[2]);
  PD_ConfigSetDevice(cfg, "cpu");
  PD_Predictor* p1 = PD_PredictorCreate(cfg);
  PD_Predictor* p2 = PD_PredictorCreate(cfg);
  PD_ConfigDestroy(cfg);
  if (p1 == NULL || p2 == NULL) {
    fprintf(stderr, "create: %s\n", PD_GetLastError());
    return 4;
  }
  Job jobs[2] = {{p1, 0.125f, 8, {0}, 0, -1}, {p2, -0.25f, 8, {0}, 0, -1}};
  pthread_t threads[2];
  pthread_create(&threads[0], NULL, worker, &jobs[0]);
  pthread_create(&threads[1], NULL, worker, &jobs[1]);
  pthread_join(threads[0], NULL);
  pthread_join(threads[1], NULL);
  for (int w = 0; w < 2; ++w) {
    if (jobs[w].rc != 0) {
      fprintf(stderr, "worker %d rc=%d: %s\n", w, jobs[w].rc,
              PD_GetLastError());
      return 6;
    }
    printf("worker %d n %lld\n", w, (long long)jobs[w].n);
    for (int64_t i = 0; i < jobs[w].n; ++i)
      printf("w%d %.8e\n", w, jobs[w].out[i]);
  }
  PD_PredictorDestroy(p1);
  PD_PredictorDestroy(p2);
  return 0;
}
'''


@pytest.fixture(scope='module')
def capi_lib():
    from paddle_tpu.capi import build_capi
    try:
        return build_capi()
    except RuntimeError as e:
        pytest.skip('capi build unavailable: %s' % e)


def test_c_abi_two_handles_concurrent_run(capi_lib, saved_mlp, tmp_path):
    path, model = saved_mlp
    from paddle_tpu.capi import header_path
    src = os.path.join(str(tmp_path), 'client_mt.c')
    with open(src, 'w') as f:
        f.write(CLIENT_MT_C)
    exe = os.path.join(str(tmp_path), 'client_mt')
    proc = subprocess.run(
        ['gcc', '-O1', '-pthread', '-o', exe, src,
         '-I', os.path.dirname(header_path()), capi_lib,
         '-Wl,-rpath,' + os.path.dirname(capi_lib)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    env = dict(os.environ)
    env['PYTHONPATH'] = os.pathsep.join(
        [p for p in sys.path if p and os.path.isdir(p)])
    env.pop('XLA_FLAGS', None)  # no virtual-device mesh inside the client
    proc = subprocess.run([exe, REPO, path], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    got = {0: [], 1: []}
    for line in proc.stdout.strip().splitlines():
        if line.startswith('w0 '):
            got[0].append(float(line.split()[1]))
        elif line.startswith('w1 '):
            got[1].append(float(line.split()[1]))
    for w, scale in ((0, 0.125), (1, -0.25)):
        x = (scale * (np.arange(16, dtype=np.float32) - 8)).reshape(2, 8)
        ref = model(paddle.to_tensor(x)).numpy()
        assert len(got[w]) == ref.size
        np.testing.assert_allclose(
            np.array(got[w], np.float32).reshape(ref.shape), ref,
            rtol=1e-5, atol=1e-6,
            err_msg='worker %d output drifted under concurrency' % w)
    assert not np.allclose(got[0], got[1])   # two jobs, two answers
