"""Named predictor-zoo presets: known-good engine configs by name.

A ReplicaWorker process has to build an engine from nothing but argv.
Hand-assembling a config + seeded weights + engine kwargs in every
spawn site (tests, benches, ops runbooks) is exactly the drift the
model registry exists to prevent, so the zoo pins 2–3 named presets:

    ReplicaWorker --preset gpt-nano          # seeded weights, no registry
    publish_preset(registry, 'gpt-nano')     # ship the weights as a
                                             # CRC-manifested artifact

`publish_preset` stamps `meta={'preset': name}` on the registry entry,
so a worker that pulls the artifact by fingerprint knows which config
to rebuild around the weights — the preset name IS the architecture
pointer, the artifact IS the weights. `host_factory()` closes the loop
for ModelHost: entry -> engine, loading the entry's state dict into
the preset's model skeleton.

Determinism contract: build_model(name) seeds the global RNG with the
preset's pinned seed before construction, so two processes building
the same preset hold bit-identical weights — which is what lets the
fabric chaos tests compare a SIGKILL'd worker's re-generated tokens
against a single-engine reference without shipping weights at all.
"""
from ...framework import io_save

__all__ = ['PRESETS', 'preset', 'build_model', 'build_engine',
           'publish_preset', 'host_factory']

# model: GPTConfig kwargs. engine_kwargs: engine constructor kwargs.
# seed: global RNG seed pinned per preset.
PRESETS = {
    # the test-suite workhorse: matches the serving test fixtures so a
    # worker process and an in-proc reference engine are token-identical
    'gpt-nano': {
        'model': dict(vocab_size=211, hidden_size=64, num_layers=2,
                      num_heads=4, max_position_embeddings=128,
                      dropout=0.0),
        'engine_kwargs': dict(num_seqs=2, max_len=32, page_size=8,
                              prefill_chunk=8, decode_block=2),
        'seed': 7,
    },
    # same weights, more and longer sequences — the preset the
    # prefix-affinity routing bench runs, where directory hits matter
    'gpt-nano-paged': {
        'model': dict(vocab_size=211, hidden_size=64, num_layers=2,
                      num_heads=4, max_position_embeddings=128,
                      dropout=0.0),
        'engine_kwargs': dict(num_seqs=4, max_len=64, page_size=8,
                              prefill_chunk=8, decode_block=2,
                              prefix_cache=True),
        'seed': 7,
    },
    # bench-sized: the CPU serving-bench config (bench_extra), big
    # enough for Poisson bursts over real sockets
    'gpt-micro': {
        'model': dict(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, max_position_embeddings=256,
                      dropout=0.0),
        'engine_kwargs': dict(num_seqs=8, max_len=128, page_size=16,
                              prefill_chunk=16, decode_block=4,
                              prefix_cache=True),
        'seed': 11,
    },
}


def preset(name):
    """The named preset spec (a copy), KeyError listing the zoo."""
    try:
        spec = PRESETS[name]
    except KeyError:
        raise KeyError('unknown preset %r; available: %s'
                       % (name, sorted(PRESETS))) from None
    return {'model': dict(spec['model']),
            'engine_kwargs': dict(spec['engine_kwargs']),
            'seed': spec['seed']}


def build_model(name, state_dict=None):
    """The preset's model, eval mode. With no state_dict the global RNG
    is seeded with the preset's pin first, so every process building
    the same preset holds bit-identical weights."""
    import paddle_tpu as paddle
    from ...text.models.gpt import GPTConfig, GPTForCausalLM
    spec = preset(name)
    if state_dict is None:
        paddle.seed(spec['seed'])
    m = GPTForCausalLM(GPTConfig(**spec['model']))
    if state_dict is not None:
        m.set_state_dict(state_dict)
    m.eval()
    return m


def build_engine(name, model=None, state_dict=None, **overrides):
    """The preset's engine around `model` (built fresh if omitted).
    `overrides` patch engine kwargs (e.g. spec_k for a spec-decode
    variant) without forking the preset."""
    from ..engine import PagedContinuousBatchingEngine
    spec = preset(name)
    if model is None:
        model = build_model(name, state_dict=state_dict)
    kwargs = spec['engine_kwargs']
    kwargs.update(overrides)
    return PagedContinuousBatchingEngine(model, **kwargs)


def publish_preset(registry, name, version='v0'):
    """Ship the preset's seeded weights into `registry` as a
    CRC-manifested artifact under (name, version), meta-stamped with
    the preset name so pullers can rebuild the architecture."""
    state = build_model(name).state_dict()
    return registry.publish(name, version, state,
                            meta={'preset': name})


def host_factory(default_preset=None):
    """entry -> engine factory for ModelHost: loads the entry's state
    dict (CRC-checked by io_save) into the preset named by the entry's
    meta — or `default_preset` for entries published outside the zoo."""
    def _factory(entry):
        pname = entry.meta.get('preset', default_preset)
        if pname is None:
            raise KeyError(
                'registry entry (%r, %r) has no preset meta and no '
                'default_preset was given' % (entry.model, entry.version))
        return build_engine(pname, state_dict=io_save.load(entry.path))
    return _factory
