"""Sharding autotuner: close the audit -> plan loop into search.

The planner (planner.py) pins the pipeline boundaries it KNOWS GSPMD
guesses wrong, from first principles. This module searches instead of
asserting: it enumerates candidate PartitionSpec entries per boundary
(micro / stacked / batch — the same three the planner names), compiles
a small probe program under each candidate, scores it with

  1. audit-reported involuntary-reshard bytes (the failure signal the
     whole subsystem exists to eliminate),
  2. HLO collective bytes from the optimized module (parser.py), and
  3. the analytic cost model's ideal step time (monitor/perf/costmodel)
     as the tiebreaker,

ranked lexicographically in that order, and emits a versioned,
content-addressed **plan artifact**: canonical JSON keyed by a sha256
of {mesh axis sizes, pipeline axis, batch axes, jaxlib version, model
fingerprint}. The pipeline engines resolve their constraint plans
through :func:`resolve_plan` — when ``PADDLE_TPU_PLAN_DIR`` holds an
artifact for the live key they apply ITS specs (a :class:`TunedPlan`),
otherwise they fall back to the analytic planner exactly as before.
``PADDLE_TPU_PLAN_STRICT=1`` turns a key mismatch (stale artifact, or
a dir with plans for other configs only) into a hard error instead of
a silent fallback.

Probe compiles run with the persistent compile cache suspended (a
cache hit skips the partitioner and would score every candidate as
clean), so tuning always measures real partitioner behavior.
"""
import glob
import hashlib
import json
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...framework import compile_cache
from . import audit as ap_audit
from .planner import (PipelinePlan, plan_pipeline, _axis_sizes, _pad, _U)

__all__ = ['PLAN_VERSION', 'BOUNDARIES', 'PlanKeyError', 'TunedPlan',
           'current_config', 'key_of_config', 'encode_entries',
           'decode_entries', 'score_report', 'score_key',
           'candidate_entries', 'default_probe', 'tune_pipeline',
           'build_artifact', 'dump_plan', 'save_plan', 'load_plan',
           'verify_artifact', 'plan_from_artifact', 'plan_path',
           'resolve_plan', 'resolve_plan_for_state']

PLAN_VERSION = 1
BOUNDARIES = ('micro', 'stacked', 'batch')

_ENV_DIR = 'PADDLE_TPU_PLAN_DIR'
_ENV_STRICT = 'PADDLE_TPU_PLAN_STRICT'


class PlanKeyError(RuntimeError):
    """A loaded plan artifact does not match the live configuration
    (or its content hash), under PADDLE_TPU_PLAN_STRICT=1."""


# ---------------------------------------------------------------- keys

def current_config(mesh_sizes, axis, batch_axes, model_fingerprint=None):
    """The content-address payload for one live configuration. Mesh
    axis sizes + pipeline axis + batch axes fix the search space;
    jaxlib pins the partitioner generation (a jaxlib upgrade must
    invalidate tuned plans); the model fingerprint is the caller's
    hook for plans tuned against a specific program."""
    try:
        import jaxlib
        jl = getattr(jaxlib, '__version__', 'unknown')
    except Exception:
        jl = 'unknown'
    return {'version': PLAN_VERSION,
            'mesh': {str(k): int(v) for k, v in dict(mesh_sizes).items()},
            'axis': str(axis),
            'batch_axes': [str(a) for a in batch_axes],
            'jaxlib': jl,
            'model': model_fingerprint}


def key_of_config(config):
    """sha256 content address of a config payload (16 hex chars —
    collision space is tiny: a handful of configs per deployment)."""
    blob = json.dumps(config, sort_keys=True, separators=(',', ':'))
    return hashlib.sha256(blob.encode('utf-8')).hexdigest()[:16]


# ------------------------------------------------- spec (de)serialization

def encode_entries(entries):
    """Per-dim spec entries -> JSON: None stays null, UNCONSTRAINED
    becomes '*', an axis name stays a string, an axis tuple a list."""
    if entries is None:
        return None
    out = []
    for e in entries:
        if e is None:
            out.append(None)
        elif _U is not None and e is _U:
            out.append('*')
        elif isinstance(e, (list, tuple)):
            out.append([str(a) for a in e])
        else:
            out.append(str(e))
    return out


def decode_entries(enc):
    if enc is None:
        return None
    out = []
    for e in enc:
        if e is None:
            out.append(None)
        elif e == '*':
            out.append(_U)
        elif isinstance(e, list):
            out.append(tuple(e))
        else:
            out.append(e)
    return tuple(out)


# ---------------------------------------------------------------- plan

class TunedPlan(PipelinePlan):
    """A PipelinePlan whose boundary entries come from a plan artifact.

    Substitutable everywhere the engines use the analytic plan: the
    shape guards (divisibility, pp extent) stay the planner's — an
    artifact can change WHAT is pinned, never make an unpinnable shape
    pinned — only the per-dim entries are swapped."""

    def __init__(self, mesh, axis, batch_axes, entries, key=None,
                 path=None):
        super().__init__(mesh, axis, batch_axes)
        self.entries = {b: (tuple(e) if e is not None else None)
                        for b, e in dict(entries).items()}
        self.key = key
        self.path = path

    def _entry_spec(self, boundary, shape, fallback):
        e = self.entries.get(boundary)
        if e is None:
            return fallback(shape)
        if fallback(shape) is None:     # planner refuses -> we refuse
            return None
        return _pad(e, len(shape))

    def micro_spec(self, shape):
        return self._entry_spec('micro', shape,
                                super().micro_spec)

    def stacked_spec(self, shape):
        return self._entry_spec('stacked', shape,
                                super().stacked_spec)

    def batch_spec(self, shape):
        return self._entry_spec('batch', shape,
                                super().batch_spec)

    def describe(self):
        out = super().describe()
        out['tuned'] = {b: encode_entries(e)
                        for b, e in sorted(self.entries.items())}
        if self.key:
            out['plan_key'] = self.key
        return out


# ------------------------------------------------------------- scoring

def score_report(report, cost=None):
    """Pure scoring of one candidate from its audit report (a
    ShardingAuditReport or its to_dict form) plus optional cost-model
    fields — fixture-testable without compiling anything."""
    d = report.to_dict() if hasattr(report, 'to_dict') else dict(report)
    colls = d.get('collectives') or {}
    score = {
        'involuntary_bytes': int(d.get('involuntary_bytes', 0) or 0),
        'collective_bytes': int(sum(
            int((v or {}).get('bytes', 0) or 0) for v in colls.values())),
        'collective_count': int(sum(
            int((v or {}).get('count', 0) or 0) for v in colls.values())),
    }
    if cost and cost.get('ideal_step_s') is not None:
        score['ideal_step_s'] = float(cost['ideal_step_s'])
    return score


def score_key(score):
    """Lexicographic rank: involuntary bytes dominate (the audit's
    failure signal), collective bytes second (real per-step traffic),
    analytic ideal step time as the tiebreaker. Lower is better."""
    return (score.get('involuntary_bytes', 0),
            score.get('collective_bytes', 0),
            float(score.get('ideal_step_s') or 0.0))


# ------------------------------------------------------------ search

def candidate_entries(plan):
    """Closed candidate sets per boundary. Index 0 is always the
    analytic planner's own choice, so score ties resolve to it."""
    ba = tuple(plan.batch_axes)
    micro = [(None, ba),        # planner: micro index is a TIME axis
             (ba, None),        # the transposed guess GSPMD makes
             (None, None)]      # fully replicated rows
    if len(ba) > 1:
        micro.append((None, (ba[0],)))   # batch tiling on one axis only
    stacked = [(plan.axis,),    # planner: pp-sharded stage dim
               (None,)]         # replicated stages
    batch = [(ba,),             # planner: rows carry full batch tiling
             (None,)]
    return {'micro': micro, 'stacked': stacked, 'batch': batch}


def default_probe(plan):
    """cfg5-analog probe for one candidate plan: batch activations
    sharded over the batch axes, reshaped into microbatches, a scan
    dynamic-slicing ZeRO-tiled stacked stage weights — the exact
    producer/consumer structure of the pipeline while-body (the
    tests/test_sharding_audit.py cfg5 pin, shrunk for search). Returns
    (fn, args)."""
    mesh = plan.mesh
    sizes = _axis_sizes(mesh)
    pp = sizes[plan.axis]
    n_micro = max(pp, 2)
    b = n_micro * plan.batch_div
    hidden = 32
    x = jax.device_put(jnp.ones((b, 8, hidden), jnp.float32),
                       NamedSharding(mesh, P(tuple(plan.batch_axes))))
    # stage weights enter ZeRO-tiled on a weight dim, like stage-3
    # sharding leaves them
    w = jax.device_put(
        jnp.ones((pp, 2, hidden, hidden), jnp.float32),
        NamedSharding(mesh, P(None, None, tuple(plan.batch_axes), None)))

    def f(x, w):
        micro = plan.constrain_micro(
            x.reshape((n_micro, b // n_micro) + x.shape[1:]))
        wts = plan.constrain_stacked({'w': w})['w']

        def tick(carry, t):
            def layer(c, j):
                lw = lax.dynamic_index_in_dim(
                    lax.dynamic_index_in_dim(wts, t % pp, 0,
                                             keepdims=False),
                    j, 0, keepdims=False)
                return jnp.tanh(c @ lw), None
            y, _ = lax.scan(layer, micro[t % n_micro],
                            jnp.arange(w.shape[1]))
            return carry + y.sum(), None
        out, _ = lax.scan(tick, 0.0, jnp.arange(3))
        merged = plan.constrain_batch(x + out)
        return merged.sum()

    return f, (x, w)


def _audit_probe(fn, args, mesh, label):
    """Fresh-compile fn under the stderr capture WITH the persistent
    compile cache suspended; returns (report, compiled) so the cost
    model can score the same executable the audit saw."""
    wrapped = jax.jit(lambda *a: fn(*a))
    with ap_audit._mesh_scope(mesh):
        lowered = wrapped.lower(*args)
        with compile_cache.suspended(), \
                ap_audit.capture_compiler_stderr() as cap:
            compiled = lowered.compile()
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = None
    return ap_audit.audit_from_text(cap['text'], hlo, label=label), compiled


def _cost_fields(compiled):
    from ...monitor.perf import costmodel
    cost = costmodel.cost_of(compiled)
    if not cost:
        return None
    rf = costmodel.roofline(cost['flops'], cost['bytes_accessed'])
    return {'flops': cost['flops'],
            'bytes_accessed': cost['bytes_accessed'],
            'ideal_step_s': rf['ideal_step_s']}


def tune_pipeline(mesh, axis='pp', batch_axes=None, probe=None,
                  model_fingerprint=None, use_costmodel=True):
    """Greedy per-boundary coordinate search over candidate_entries.

    Starts from the analytic planner's choices and, boundary by
    boundary, keeps any alternative that strictly improves the score
    (compile count is 1 + sum(len(candidates)-1), not the product).
    Returns the plan artifact dict (save with save_plan), or None on a
    mesh with nothing to plan."""
    plan = plan_pipeline(mesh, axis, batch_axes)
    if plan is None:
        return None
    probe = probe or default_probe
    cands = candidate_entries(plan)
    chosen = {b: cands[b][0] for b in BOUNDARIES}
    trials = {b: [] for b in BOUNDARIES}
    n_compiles = [0]

    def evaluate(entries, label):
        tp = TunedPlan(mesh, axis, plan.batch_axes, entries)
        fn, args = probe(tp)
        report, compiled = _audit_probe(fn, args, mesh, label)
        n_compiles[0] += 1
        cost = _cost_fields(compiled) if use_costmodel else None
        return score_report(report, cost)

    base_score = evaluate(chosen, 'base')
    for b in BOUNDARIES:
        trials[b].append({'spec': encode_entries(chosen[b]),
                          'score': base_score, 'chosen': True})
        best = (score_key(base_score), chosen[b], base_score)
        for alt in cands[b][1:]:
            trial = dict(chosen)
            trial[b] = alt
            s = evaluate(trial, '%s=%s' % (b, encode_entries(alt)))
            trials[b].append({'spec': encode_entries(alt), 'score': s,
                              'chosen': False})
            if score_key(s) < best[0]:
                best = (score_key(s), alt, s)
        if best[1] is not chosen[b]:
            for t in trials[b]:
                t['chosen'] = t['spec'] == encode_entries(best[1])
            chosen[b] = best[1]
        base_score = best[2]

    boundaries = {b: {'spec': encode_entries(chosen[b]),
                      'score': next(t['score'] for t in trials[b]
                                    if t['chosen']),
                      'candidates': trials[b]}
                  for b in BOUNDARIES}
    return build_artifact(_axis_sizes(mesh), axis, plan.batch_axes,
                          boundaries, model_fingerprint=model_fingerprint,
                          extra={'probe_compiles': n_compiles[0],
                                 'final_score': base_score})


# ------------------------------------------------------------ artifact

def build_artifact(mesh_sizes, axis, batch_axes, boundaries,
                   model_fingerprint=None, extra=None):
    """Assemble + canonicalize the artifact dict. `boundaries` maps
    boundary -> {'spec': encoded entries, 'score': {...}, ...}."""
    config = current_config(mesh_sizes, axis, batch_axes,
                            model_fingerprint)
    art = {'version': PLAN_VERSION,
           'key': key_of_config(config),
           'config': config,
           'boundaries': dict(boundaries)}
    if extra:
        art.update(extra)
    # normalize to JSON-native types so emit == re-emit, byte for byte
    return json.loads(dump_plan(art))


def dump_plan(artifact):
    """Canonical serialization: sorted keys, fixed indent, trailing
    newline — load_plan + dump_plan is byte-identical to the file."""
    return json.dumps(artifact, sort_keys=True, indent=1) + '\n'


def plan_path(dirpath, key):
    return os.path.join(dirpath, 'plan_%s.json' % key)


def save_plan(artifact, dirpath):
    """Write the artifact into `dirpath` under its content address
    (atomic rename). Returns the path."""
    os.makedirs(dirpath, exist_ok=True)
    path = plan_path(dirpath, artifact['key'])
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        f.write(dump_plan(artifact))
    os.replace(tmp, path)
    return path


def load_plan(path):
    with open(path) as f:
        return json.load(f)


def verify_artifact(art, expect_key=None):
    """Content check: the stored key must re-derive from the stored
    config, and (when given) match the live config's key. Raises
    PlanKeyError with the mismatch spelled out."""
    if art.get('version') != PLAN_VERSION:
        raise PlanKeyError('plan version %r != supported %d'
                           % (art.get('version'), PLAN_VERSION))
    stored = art.get('key')
    derived = key_of_config(art.get('config') or {})
    if stored != derived:
        raise PlanKeyError('plan key %r does not re-derive from its own '
                           'config (%r) — artifact edited or corrupt'
                           % (stored, derived))
    if expect_key is not None and stored != expect_key:
        raise PlanKeyError('plan key %r is stale for live config key %r '
                           '(mesh/jaxlib/model changed since tuning)'
                           % (stored, expect_key))
    return art


def plan_from_artifact(art, mesh, path=None):
    cfg = art['config']
    entries = {b: decode_entries(spec.get('spec'))
               for b, spec in (art.get('boundaries') or {}).items()}
    return TunedPlan(mesh, cfg['axis'], tuple(cfg['batch_axes']),
                     entries, key=art.get('key'), path=path)


# ----------------------------------------------------------- resolution

def _strict():
    return os.environ.get(_ENV_STRICT) == '1'


def resolve_plan(mesh, axis='pp', batch_axes=None, model_fingerprint=None):
    """The engines' plan source: a TunedPlan from PADDLE_TPU_PLAN_DIR
    when an artifact matches the live content key, else the analytic
    planner's PipelinePlan (or None on trivial meshes). Under
    PADDLE_TPU_PLAN_STRICT=1 a mismatching or missing-but-expected
    artifact raises PlanKeyError instead of falling back."""
    plan = plan_pipeline(mesh, axis, batch_axes)
    dirpath = os.environ.get(_ENV_DIR)
    if not dirpath or plan is None:
        return plan
    config = current_config(_axis_sizes(mesh), axis, plan.batch_axes,
                            model_fingerprint)
    key = key_of_config(config)
    path = plan_path(dirpath, key)
    if os.path.exists(path):
        try:
            art = verify_artifact(load_plan(path), expect_key=key)
        except (PlanKeyError, ValueError, OSError, KeyError) as e:
            if _strict():
                if isinstance(e, PlanKeyError):
                    raise
                raise PlanKeyError('unreadable plan artifact %s: %s'
                                   % (path, e))
            return plan
        return plan_from_artifact(art, mesh, path=path)
    others = sorted(os.path.basename(p) for p in
                    glob.glob(os.path.join(dirpath, 'plan_*.json')))
    if others and _strict():
        raise PlanKeyError(
            'no plan for live config key %s in %s (stale artifacts: %s) '
            '— re-run the tuner or unset %s'
            % (key, dirpath, ', '.join(others), _ENV_STRICT))
    return plan


def resolve_plan_for_state(pp_state):
    """resolve_plan for a pipeline state dict (make_pp_state output) —
    the drop-in for planner.plan_for_state at the engine call sites."""
    if pp_state is None:
        return None
    return resolve_plan(pp_state['mesh'], pp_state['axis'])
