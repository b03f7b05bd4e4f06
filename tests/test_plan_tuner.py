"""Sharding autotuner: content-addressed plan artifacts, strict key
resolution, the cfg5 search pin, and the persistent-compile-cache /
CompileWatchdog composition.

The pure layers (keys, spec codec, scoring, artifact round-trip,
resolution) are tested without compiling; the search itself runs ONCE
per module on the cfg5 mesh (pp2 x sharding4 — the config whose
involuntary reshards the whole subsystem exists to eliminate) and two
tests share the artifact.
"""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed import auto_parallel as ap
from paddle_tpu.distributed.auto_parallel import tuner
from paddle_tpu.distributed.auto_parallel.planner import _U

# one REAL involuntary-reshard warning (the r05 dialect) so score_report
# is fixture-tested against the text the auditor actually parses
WARN_LINE = (
    'W0802 18:00:41.692990    3516 spmd_partitioner.cc:652] [SPMD] '
    'Involuntary full rematerialization. The compiler cannot go from '
    'sharding {devices=[4,1]0,2,1,3} to {devices=[1,2,2]<=[2,2]T(1,0) '
    'last_tile_dim_replicate} efficiently for HLO operation %squeeze.67 '
    '= f32[128,128]{1,0} copy(%squeeze.66), sharding={devices=[4,1]'
    '0,2,1,3}, metadata={op_name="while/body/squeeze" stack_frame_id=99}'
    '. As the last resort, SPMD will replicate the tensor and then '
    'partition it to obtain the target sharding, which is inefficient.')


def _mesh_pp_sharding():
    dev = np.array(jax.devices()[:8]).reshape(1, 2, 4)
    return Mesh(dev, ('dp', 'pp', 'sharding'))


def _toy_artifact(model=None):
    """A hand-built artifact for the cfg5 mesh carrying the analytic
    planner's own specs — key-compatible with resolve_plan's live key
    (same mesh sizes, axis, batch axes, jaxlib, fingerprint)."""
    boundaries = {
        'micro': {'spec': [None, ['sharding']],
                  'score': {'involuntary_bytes': 0}},
        'stacked': {'spec': ['pp'], 'score': {'involuntary_bytes': 0}},
        'batch': {'spec': [['sharding']],
                  'score': {'involuntary_bytes': 0}},
    }
    return tuner.build_artifact({'dp': 1, 'pp': 2, 'sharding': 4}, 'pp',
                                ('sharding',), boundaries,
                                model_fingerprint=model)


# ---------------- keys + codec (pure) ----------------

def test_key_of_config_is_content_addressed():
    base = tuner.current_config({'dp': 1, 'pp': 2, 'sharding': 4}, 'pp',
                                ('sharding',))
    assert tuner.key_of_config(base) == tuner.key_of_config(dict(base))
    assert len(tuner.key_of_config(base)) == 16
    for mutate in (lambda c: c['mesh'].update(sharding=8),
                   lambda c: c.update(axis='mp'),
                   lambda c: c.update(batch_axes=['dp']),
                   lambda c: c.update(jaxlib='9.9.9'),
                   lambda c: c.update(model='gpt-13b')):
        other = json.loads(json.dumps(base))
        mutate(other)
        assert tuner.key_of_config(other) != tuner.key_of_config(base)


def test_entry_codec_roundtrip():
    entries = (None, ('dp', 'sharding'), 'pp', _U)
    enc = tuner.encode_entries(entries)
    assert enc == [None, ['dp', 'sharding'], 'pp', '*']
    assert tuner.decode_entries(enc) == entries
    assert tuner.encode_entries(None) is None
    assert tuner.decode_entries(None) is None


# ---------------- scoring (fixture-driven, no compile) ----------------

def test_score_report_and_key_ordering():
    dirty = tuner.score_report(ap.audit_from_text(WARN_LINE, label='d'))
    clean = tuner.score_report(ap.audit_from_text('all quiet', label='c'))
    assert dirty['involuntary_bytes'] >= 128 * 128 * 4
    assert clean['involuntary_bytes'] == 0
    assert tuner.score_key(clean) < tuner.score_key(dirty)
    # involuntary bytes dominate any collective traffic...
    loud = dict(clean, collective_bytes=10 ** 9)
    assert tuner.score_key(loud) < tuner.score_key(dirty)
    # ...and collective bytes dominate the analytic tiebreaker
    slow = dict(clean, ideal_step_s=99.0)
    assert tuner.score_key(slow) < tuner.score_key(loud)


# ---------------- artifact round-trip + verification ----------------

def test_artifact_roundtrip_byte_identical(tmp_path):
    art = _toy_artifact()
    blob = tuner.dump_plan(art)
    path = tuner.save_plan(art, str(tmp_path))
    assert os.path.basename(path) == 'plan_%s.json' % art['key']
    with open(path) as f:
        assert f.read() == blob
    reloaded = tuner.load_plan(path)
    assert tuner.dump_plan(reloaded) == blob          # emit == re-emit
    assert tuner.verify_artifact(reloaded) is reloaded
    # saving the reload writes the identical file again
    assert tuner.save_plan(reloaded, str(tmp_path)) == path
    with open(path) as f:
        assert f.read() == blob


def test_verify_artifact_rejections():
    art = _toy_artifact()
    with pytest.raises(tuner.PlanKeyError, match='version'):
        tuner.verify_artifact(dict(art, version=99))
    with pytest.raises(tuner.PlanKeyError, match='re-derive'):
        tuner.verify_artifact(dict(art, key='deadbeefdeadbeef'))
    with pytest.raises(tuner.PlanKeyError, match='stale'):
        tuner.verify_artifact(art, expect_key='0' * 16)
    assert tuner.verify_artifact(art, expect_key=art['key']) is art


# ---------------- resolution (engines' plan source) ----------------

def test_resolve_plan_loads_matching_artifact(tmp_path, monkeypatch):
    art = _toy_artifact()
    tuner.save_plan(art, str(tmp_path))
    monkeypatch.setenv('PADDLE_TPU_PLAN_DIR', str(tmp_path))
    mesh = _mesh_pp_sharding()
    plan = tuner.resolve_plan(mesh, 'pp')
    assert isinstance(plan, tuner.TunedPlan)
    assert plan.key == art['key']
    micro = plan.micro_spec((2, 4, 64, 128))
    # jax 0.9 PartitionSpec canonicalises a one-axis tuple to the bare name
    assert micro[0] is None and micro[1] == 'sharding'
    # the planner's shape guards survive the artifact
    assert plan.micro_spec((2, 3, 64)) is None
    # the engines' call-site helper resolves the same artifact
    from paddle_tpu.distributed.pipeline import make_pp_state
    st = make_pp_state(mesh, n_stages=2)
    assert isinstance(tuner.resolve_plan_for_state(st), tuner.TunedPlan)
    assert tuner.resolve_plan_for_state(None) is None


def test_resolve_plan_stale_key_strict_vs_fallback(tmp_path, monkeypatch):
    # the dir holds a plan for ANOTHER config (different fingerprint)
    tuner.save_plan(_toy_artifact(model='other-model'), str(tmp_path))
    monkeypatch.setenv('PADDLE_TPU_PLAN_DIR', str(tmp_path))
    mesh = _mesh_pp_sharding()
    plan = tuner.resolve_plan(mesh, 'pp')      # non-strict: fall back
    assert plan is not None
    assert not isinstance(plan, tuner.TunedPlan)
    monkeypatch.setenv('PADDLE_TPU_PLAN_STRICT', '1')
    with pytest.raises(tuner.PlanKeyError, match='stale artifacts'):
        tuner.resolve_plan(mesh, 'pp')


def test_resolve_plan_corrupt_artifact_strict_vs_fallback(
        tmp_path, monkeypatch):
    art = _toy_artifact()
    path = tuner.save_plan(art, str(tmp_path))
    # corrupt IN PLACE at the live key's path: stored key no longer
    # re-derives from the stored config
    with open(path, 'w') as f:
        f.write(tuner.dump_plan(dict(art, key='deadbeefdeadbeef')))
    monkeypatch.setenv('PADDLE_TPU_PLAN_DIR', str(tmp_path))
    mesh = _mesh_pp_sharding()
    plan = tuner.resolve_plan(mesh, 'pp')
    assert not isinstance(plan, tuner.TunedPlan)
    monkeypatch.setenv('PADDLE_TPU_PLAN_STRICT', '1')
    with pytest.raises(tuner.PlanKeyError):
        tuner.resolve_plan(mesh, 'pp')


# ---------------- the cfg5 search pin (compiles: 5 + 1) ----------------

@pytest.fixture(scope='module')
def cfg5_artifact():
    return tuner.tune_pipeline(_mesh_pp_sharding(), axis='pp')


def test_tuner_cfg5_reproduces_or_beats_planner(cfg5_artifact):
    art = cfg5_artifact
    assert art is not None and art['key']
    assert art['probe_compiles'] == 5
    bounds = art['boundaries']
    assert set(bounds) == set(tuner.BOUNDARIES)
    # the planner's micro pin (the r05 fix) is rediscovered by search:
    # GSPMD's transposed guess scores involuntary bytes, the time-axis
    # layout scores none
    assert bounds['micro']['spec'] == [None, ['sharding']]
    micro_cands = {json.dumps(t['spec']): t['score']
                   for t in bounds['micro']['candidates']}
    assert micro_cands[json.dumps([['sharding'], None])][
        'involuntary_bytes'] > 0
    for b in tuner.BOUNDARIES:
        chosen = bounds[b]['score']
        planner = bounds[b]['candidates'][0]['score']  # index 0 = planner
        assert chosen['involuntary_bytes'] == 0
        assert tuner.score_key(chosen) <= tuner.score_key(planner)


def test_tuned_plan_probe_compiles_clean(cfg5_artifact):
    mesh = _mesh_pp_sharding()
    plan = tuner.plan_from_artifact(cfg5_artifact, mesh)
    assert isinstance(plan, tuner.TunedPlan)
    fn, args = tuner.default_probe(plan)
    rep = ap.assert_no_involuntary_resharding(fn, args=args,
                                              label='tuned-cfg5')
    assert rep.passed
    assert plan.describe()['plan_key'] == cfg5_artifact['key']


# -------- persistent cache x watchdog composition (satellite fix) -------

def test_cache_hit_after_warmup_is_not_a_recompile(tmp_path, monkeypatch):
    """The satellite-6 regression pin: jax fires the backend-compile
    duration event even when the persistent cache served the
    executable, so a cache-hit reload after declare_warmup() used to
    trip the watchdog. strict=True makes a misclassification raise
    RecompileError right here."""
    from paddle_tpu.framework import compile_cache
    from paddle_tpu import monitor

    x = jnp.arange(8.0)
    jnp.multiply(x, 1.0).block_until_ready()   # aux compiles out of the way
    # a cache placed from outside would win over the test's own dir
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    compile_cache.configure(str(tmp_path / 'cc'))
    reg = monitor.MetricRegistry()
    wd = monitor.CompileWatchdog(registry=reg, strict=True, name='cc')
    try:
        jax.jit(lambda x: x * 2.0 + 1.0)(x).block_until_ready()  # miss
        wd.declare_warmup('cache-hit test')
        # an IDENTICAL program under a fresh jit wrapper: the in-memory
        # jit cache can't serve it, the persistent cache does
        jax.jit(lambda x: x * 2.0 + 1.0)(x).block_until_ready()
        assert wd.recompiles == 0
        assert reg.get('perf_recompiles_total').value() == 0
        assert reg.get('perf_persistent_cache_hits_total').value() >= 1
        assert reg.get('perf_persistent_cache_misses_total').value() >= 1
    finally:
        wd.close()
        compile_cache.disable()


def test_compile_cache_configure_idempotent(tmp_path, monkeypatch):
    from paddle_tpu.framework import compile_cache
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    d = str(tmp_path / 'cc2')
    try:
        got = compile_cache.configure(d)
        assert got == d and compile_cache.enabled()
        assert compile_cache.cache_dir() == d
        assert compile_cache.configure(d) == d     # repeat: no-op
        s = compile_cache.stats()
        assert set(s) == {'hits', 'misses'}
    finally:
        compile_cache.disable()
        assert not compile_cache.enabled()
