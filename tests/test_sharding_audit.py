"""auto_parallel subsystem: warning parser (fixture-driven, no
compilation), auditor end-to-end, planner specs, and the HLO pin for
the MULTICHIP r05 config-5 fix.

The parser fixtures are the REAL tail of MULTICHIP_r05.json — the
capture whose three spmd_partitioner.cc:652 warnings this subsystem
exists to eliminate — so the detector is regression-tested against the
exact text the regression gate must keep recognizing.
"""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed import auto_parallel as ap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_R05 = os.path.join(_REPO, 'MULTICHIP_r05.json')

# the r05 capture tail, embedded verbatim so the fixture test survives
# the stored file advancing to r06+ (which SHOULD go clean)
R05_TAIL = r'''devices=[1,2,2]<=[2,2]T(1,0) last_tile_dim_replicate} efficiently for HLO operation %squeeze.63 = f32[32,512]{1,0} copy(%squeeze.62), sharding={devices=[4,1]0,2,1,3}, metadata={op_name="while/body/closed_call/while/body/squeeze" stack_frame_id=99}. As the last resort, SPMD will replicate the tensor and then partition it to obtain the target sharding, which is inefficient.
W0802 18:00:41.692990    3516 spmd_partitioner.cc:652] [SPMD] Involuntary full rematerialization. The compiler cannot go from sharding {devices=[4,1]0,2,1,3} to {devices=[1,2,2]<=[2,2]T(1,0) last_tile_dim_replicate} efficiently for HLO operation %squeeze.67 = f32[128,128]{1,0} copy(%squeeze.66), sharding={devices=[4,1]0,2,1,3}, metadata={op_name="while/body/closed_call/while/body/squeeze" stack_frame_id=99}. As the last resort, SPMD will replicate the tensor and then partition it to obtain the target sharding, which is inefficient.
W0802 18:00:41.878208    3516 spmd_partitioner.cc:652] [SPMD] Involuntary full rematerialization. The compiler cannot go from sharding {devices=[1,2,4]<=[8] last_tile_dim_replicate} to {devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate} efficiently for HLO operation %all-reduce = f32[512,64]{1,0} all-reduce(%dynamic-slice), channel_id=257, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%region_121.125.clone.1.clone, sharding={devices=[1,2,4]<=[8] last_tile_dim_replicate}. As the last resort, SPMD will replicate the tensor and then partition it to obtain the target sharding, which is inefficient.
dryrun_multichip(8)[pp/sharding3 cfg5]: pp=2 sharding=4 loss=6.4444'''

# the OTHER warning dialect (older XLA, spmd_partitioner.cc:613,
# E-level) — what the locally-installed jaxlib emits
OLD_DIALECT_LINE = (
    'E0805 04:10:00.000000   999 spmd_partitioner.cc:613] [spmd] '
    'Involuntary full rematerialization. The compiler was not able to go '
    'from sharding {devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate} '
    'to {devices=[1,2,4]<=[8] last_tile_dim_replicate} without doing a '
    'full rematerialization of the tensor for HLO operation: %copy.1 = '
    'f32[32,512]{1,0} copy(f32[32,512]{1,0} %reshape.0), '
    'sharding={devices=[1,2,4]<=[8] last_tile_dim_replicate}, '
    'metadata={op_name="jit(f)/jit(main)/while/body/sharding_constraint" '
    'source_file="/tmp/repro.py" source_line=18}. You probably want to '
    'enrich the sharding annotations to prevent this from happening.')

CLEAN_TAIL = ('dryrun_multichip(8)[dp/mp/sharding fused-ce]: loss=6.45\n'
              'dryrun_multichip(8)[pp/sharding3 cfg5]: pp=2 sharding=4 '
              'loss=6.4444\n')


# ---------------- parser fixtures (no compilation) ----------------

def test_parser_r05_tail_finds_all_three_events():
    evs = ap.parse_spmd_warnings(R05_TAIL)
    assert len(evs) == 3
    # the tail-truncated first line still yields an event (dst + op)
    assert evs[0].src_sharding is None
    assert evs[0].shape == [32, 512]
    assert evs[0].dst_sharding == \
        'devices=[1,2,2]<=[2,2]T(1,0) last_tile_dim_replicate'
    assert evs[0].op_name == 'while/body/closed_call/while/body/squeeze'
    # full squeeze line: both shardings, opcode, stack frame
    assert evs[1].op == 'squeeze.67'
    assert evs[1].opcode == 'copy'
    assert evs[1].shape == [128, 128]
    assert evs[1].src_sharding == 'devices=[4,1]0,2,1,3'
    assert evs[1].stack_frame_id == 99
    assert evs[1].bytes == 128 * 128 * 4
    # the all-reduce line has no metadata= section at all
    assert evs[2].op == 'all-reduce'
    assert evs[2].op_name is None
    assert evs[2].shape == [512, 64]
    assert evs[2].bytes == 512 * 64 * 4


def test_parser_r05_stored_file_still_matches_embedded_fixture():
    """Guard: if the stored capture is still r05-era (3 warnings), the
    parser must see exactly them; once the capture goes clean this test
    asserts the parser agrees it is clean."""
    with open(_R05) as f:
        tail = json.load(f)['tail']
    evs = ap.parse_spmd_warnings(tail)
    assert len(evs) in (0, 3)
    if evs:
        assert {tuple(e.shape) for e in evs} == \
            {(32, 512), (128, 128), (512, 64)}


def test_parser_old_dialect_line():
    evs = ap.parse_spmd_warnings(OLD_DIALECT_LINE)
    assert len(evs) == 1
    e = evs[0]
    assert e.opcode == 'copy'
    assert e.shape == [32, 512]
    assert e.src_sharding == \
        'devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate'
    assert e.source_file == '/tmp/repro.py'
    assert e.source_line == 18
    assert 'sharding_constraint' in e.op_name


def test_parser_clean_tail_is_clean():
    assert ap.parse_spmd_warnings(CLEAN_TAIL) == []
    rep = ap.audit_from_text(CLEAN_TAIL, label='clean')
    assert rep.passed and rep.involuntary_bytes == 0


def test_event_key_ignores_hlo_value_numbering():
    evs = ap.parse_spmd_warnings(R05_TAIL)
    renum = R05_TAIL.replace('squeeze.67', 'squeeze.123')
    evs2 = ap.parse_spmd_warnings(renum)
    assert [e.key() for e in evs] == [e.key() for e in evs2]


def test_report_roundtrips_through_dict():
    rep = ap.audit_from_text(R05_TAIL, label='r05')
    rep2 = ap.ShardingAuditReport.from_dict(rep.to_dict())
    assert [e.key() for e in rep2.events] == [e.key() for e in rep.events]
    assert rep2.involuntary_bytes == rep.involuntary_bytes


def test_hlo_collective_stats():
    hlo = '\n'.join([
        '%all-reduce.1 = f32[512,64]{1,0} all-reduce(f32[512,64]{1,0} %x)',
        '%ag = f32[128,128]{1,0} all-gather(f32[32,128]{1,0} %y)',
        '%cp = f32[4,64]{1,0} collective-permute(f32[4,64]{1,0} %z)',
        '%add = f32[4,64]{1,0} add(%cp, %cp)',
    ])
    stats = ap.parse_hlo_collectives(hlo)
    assert stats['all-reduce'] == {'count': 1, 'bytes': 512 * 64 * 4}
    assert stats['all-gather']['count'] == 1
    assert stats['collective-permute']['count'] == 1
    assert 'add' not in stats


# ---------------- auditor end-to-end (compiles) ----------------

def _mesh_ab():
    dev = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(dev, ('a', 'b'))


def test_auditor_detects_involuntary_reshard():
    """A program whose while-body demands a transposed retiling of the
    same tensor MUST trip the partitioner's last-resort path — and the
    auditor must see it through the fd-level capture."""
    mesh = _mesh_ab()
    w = jax.device_put(jnp.ones((16, 128, 512), jnp.float32),
                       NamedSharding(mesh, P(None, 'b', None)))

    def bad(w):
        def body(c, i):
            s = lax.dynamic_index_in_dim(w, i, 0, keepdims=False)
            s = lax.with_sharding_constraint(
                s, NamedSharding(mesh, P('b', None)))
            s = jnp.tanh(s)
            s = lax.with_sharding_constraint(
                s, NamedSharding(mesh, P(None, 'a')))
            return c + s.sum(), None
        out, _ = lax.scan(body, 0.0, jnp.arange(16))
        return out

    rep = ap.audit_callable(bad, args=(w,), label='bad')
    assert not rep.passed
    assert any(e.shape == [32, 512] for e in rep.events)
    assert rep.involuntary_bytes >= 32 * 512 * 4
    with pytest.raises(AssertionError):
        ap.assert_no_involuntary_resharding(bad, args=(w,))


def test_auditor_clean_program_passes():
    mesh = _mesh_ab()
    w = jax.device_put(jnp.ones((16, 128, 512), jnp.float32),
                       NamedSharding(mesh, P(None, 'b', None)))

    def good(w):
        def body(c, i):
            s = lax.dynamic_index_in_dim(w, i, 0, keepdims=False)
            s = lax.with_sharding_constraint(
                s, NamedSharding(mesh, P('b', None)))
            return c + jnp.tanh(s).sum(), None
        out, _ = lax.scan(body, 0.0, jnp.arange(16))
        return out

    rep = ap.assert_no_involuntary_resharding(good, args=(w,))
    assert rep.passed
    # a real compile happened: the optimized HLO was parsed
    assert isinstance(rep.collectives, dict)


# ---------------- planner ----------------

def _mesh_pp_sharding():
    dev = np.array(jax.devices()[:8]).reshape(1, 2, 4)
    return Mesh(dev, ('dp', 'pp', 'sharding'))


def test_planner_specs_and_trivial_meshes():
    mesh = _mesh_pp_sharding()
    plan = ap.plan_pipeline(mesh, 'pp')
    assert plan is not None
    assert plan.batch_axes == ('sharding',)
    assert plan.batch_div == 4
    micro = plan.micro_spec((2, 4, 64, 128))
    assert micro is not None and micro[0] is None
    # jax 0.9 PartitionSpec canonicalises a one-axis tuple to the bare name
    assert micro[1] == 'sharding'
    # indivisible microbatch rows -> no constraint rather than a bad one
    assert plan.micro_spec((2, 3, 64)) is None
    st = plan.stacked_spec((2, 2, 128, 128))
    assert st is not None and st[0] == 'pp'
    # wrong leading dim (not the pp extent) -> refuse
    assert plan.stacked_spec((3, 2, 128)) is None
    # pure-pp mesh: nothing to plan
    dev = np.array(jax.devices()[:2])
    assert ap.plan_pipeline(Mesh(dev, ('pp',)), 'pp') is None
    # no pp axis at all
    dev = np.array(jax.devices()[:4])
    assert ap.plan_pipeline(Mesh(dev, ('dp',)), 'pp') is None


def test_planner_state_helper():
    from paddle_tpu.distributed.pipeline import make_pp_state
    mesh = _mesh_pp_sharding()
    st = make_pp_state(mesh, n_stages=2)
    assert ap.plan_for_state(st) is not None
    assert ap.plan_for_state(None) is None


# -------- the cfg5 HLO pin: planner boundaries stay warning-free ------

def test_cfg5_analog_boundaries_compile_clean():
    """Pure-auto analog of the config-5 (pp2 x ZeRO-sharding4) region:
    batch sharded over ('dp','sharding') reshaped to microbatches, a
    while loop dynamic-slicing stacked ZeRO-tiled stage weights — the
    exact producer/consumer structure whose unpinned version produced
    the three r05 involuntary-reshard warnings. With the planner's
    boundary constraints the compile must be CLEAN, and the loop body
    must keep collective-permute-free access to the microbatch stream
    (regression pin for the fixed transitions)."""
    mesh = _mesh_pp_sharding()
    plan = ap.plan_pipeline(mesh, 'pp')
    x = jax.device_put(jnp.ones((8, 64, 128), jnp.float32),
                       NamedSharding(mesh, P(('dp', 'sharding'))))
    w = jax.device_put(
        jnp.ones((2, 2, 128, 128), jnp.float32),
        NamedSharding(mesh, P(None, None, 'sharding', None)))

    def f(x, w):
        micro = plan.constrain_micro(x.reshape((2, 4) + x.shape[1:]))
        wts = plan.constrain_stacked({'w': w})['w']

        def tick(carry, t):
            def layer(c, j):
                lw = lax.dynamic_index_in_dim(
                    lax.dynamic_index_in_dim(wts, t % 2, 0,
                                             keepdims=False),
                    j, 0, keepdims=False)
                return jnp.tanh(c @ lw), None
            y, _ = lax.scan(layer, micro[t % 2], jnp.arange(2))
            return carry + y.sum(), None
        out, _ = lax.scan(tick, 0.0, jnp.arange(3))
        return out

    rep = ap.assert_no_involuntary_resharding(f, args=(x, w),
                                              label='cfg5-analog')
    # pinned transitions: stage weights stay tiled (the all-gather that
    # feeds the matmul is voluntary and appears as a real collective),
    # and nothing in the body needed replicate-then-repartition
    assert rep.passed


# ---------------- regression gate (tools/) ----------------

def _gate():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'check_sharding_regression',
        os.path.join(_REPO, 'tools', 'check_sharding_regression.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


AUDIT_CLEAN = ('sharding_audit(8)[pp/sharding3 cfg5]: {"ok":true,'
               '"n_events":0,"involuntary_bytes":0,"events":[],'
               '"collectives":{}}\n')
AUDIT_BAD = ('sharding_audit(8)[pp/sharding3 cfg5]: {"ok":false,'
             '"n_events":1,"involuntary_bytes":4096,"events":['
             '{"kind":"involuntary-full-rematerialization","opcode":"copy",'
             '"dtype":"f32","shape":[32,32],"bytes":4096,'
             '"src_sharding":"devices=[4,1]","dst_sharding":"devices=[1,4]",'
             '"op_name":"while/body/new_thing"}],"collectives":{}}\n')


def test_gate_clean_vs_r05_passes():
    gate = _gate()
    assert gate.check(AUDIT_CLEAN, R05_TAIL) == []


def test_gate_new_event_fails_with_diff():
    gate = _gate()
    findings = gate.check(AUDIT_BAD, R05_TAIL)
    assert len(findings) == 1
    assert findings[0]['config'] == 'pp/sharding3 cfg5'
    assert findings[0]['event']['op_name'] == 'while/body/new_thing'


def test_gate_raw_baseline_covers_same_raw_events():
    gate = _gate()
    # a new capture still in the raw-warning format, identical events:
    # not a regression (value numbering differences must not matter)
    renum = R05_TAIL.replace('squeeze.67', 'squeeze.91')
    assert gate.check(renum, R05_TAIL) == []


def test_gate_extract_reads_both_encodings():
    gate = _gate()
    by_label = gate.extract_events(AUDIT_BAD + R05_TAIL)
    assert len(by_label['pp/sharding3 cfg5']) == 1
    assert len(by_label['_raw']) == 3


@pytest.mark.skipif(not hasattr(jax, 'shard_map'),
                    reason='partial-auto shard_map needs the modern '
                           'jax.shard_map API (the installed 0.4.x line '
                           'lowers axis_index under partial-auto to an '
                           'unpartitionable PartitionId)')
def test_cfg5_full_train_step_audits_clean():
    """The REAL config-5 step (pp2 x sharding3, fused loss) compiles
    with zero involuntary-reshard warnings — the acceptance criterion,
    runnable wherever the modern shard_map API exists (the MULTICHIP
    driver environment)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        'dp_degree': 1, 'mp_degree': 1, 'pp_degree': 2,
        'sharding_degree': 4, 'sp_degree': 1, 'ep_degree': 1}
    strategy.sharding = True
    strategy.sharding_configs.update({'stage': 3})
    fleet.init(is_collective=True, strategy=strategy)

    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=128, num_layers=4, num_heads=4,
        max_position_embeddings=64, fused_loss=True))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = fleet.fleet_train_step(model, lambda lg, lb: model.loss(lg, lb),
                                  opt, strategy=strategy)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 512, (8, 64)).astype(np.int32))
    lbl = paddle.to_tensor(rng.randint(0, 512, (8, 64)).astype(np.int32))
    rep = ap.audit_train_step(step, ids, lbl, label='cfg5')
    assert rep.passed, rep.summary()
