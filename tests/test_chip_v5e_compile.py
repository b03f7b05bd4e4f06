"""The main path's kernels and programs compile for a TPU v5e — without one.

The TPU compiler is installed here and compiles for a chip that is DESCRIBED
(jax.experimental.topologies), not attached, so what Mosaic or XLA:TPU would
refuse on the chip — a misaligned slice, a kernel over its VMEM, a program
over 16 GB of HBM — is refused here, at no chip time. Nothing runs: a pass
says nothing about results or speed (chip_smoke.py does, on the chip).

Code that asks the backend still sees the CPU under such a compile and would
take its CPU branch, so THIS FILE steers it: `flash_attention.is_available`
is patched to say yes and the engines get their existing `donate=True`. The
persistent compile cache is off around every compile: an entry written for
an unattached device cannot be read back.

Tier-1 compiles the kernels at the benchmark's shapes, the train step at full
WIDTH with the depth cut to two layers (what the compiler checks per layer
does not change with depth) and the three serving programs (8 - 11 s each:
none sorts the vocabulary any more, which alone took ~25 s a program); the
12-layer train step, whose memory is read against the chip's 16 GB, is
marked slow.

The paged programs are also held to the K/V pools' invariant (`_pool_invariant`):
no program copies or re-lays a pool, and a pool keeps the default layout of
its shape from the parameters to the outputs — tier-1 for `paged_attention`
alone at the benchmark's two head shapes and for the three engine programs,
slow for the decode programs at the page counts the configurations want next
(1 024 and 3 072), read against the chip's memory.
"""
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu as paddle
from paddle_tpu.framework import compile_cache
from paddle_tpu.framework import functional as func_mod
from paddle_tpu.framework import random as rng_mod
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

os.environ.setdefault('TPU_LOG_DIR', 'disabled')   # or the compiler logs

HBM_BYTES = 16e9
WIDTHS = dict(vocab_size=30528, hidden_size=768, num_heads=12, dropout=0.0)


@pytest.fixture(scope='module')
def chip():
    """Sharding on one described v5e chip; skip where it can't be described."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip('cannot describe a v5e topology here: %r' % (e,))
    assert topo.devices[0].device_kind == 'TPU v5 lite'
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip_dispatch(monkeypatch):
    """The dispatch the chip takes: flash available, strict (an ineligible
    shape raises instead of routing to blockwise), cache off."""
    monkeypatch.setattr(fa, 'is_available', lambda: True)
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '1')
    with compile_cache.suspended():
        yield


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a),
                                       sharding=sharding), tree)


def _hbm_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)


# ---- flash kernels at the benchmark's shapes --------------------------------

# seq 512 is one 512x512 tile: forward 1 kernel, fused backward 1. Longer
# sequences run dq and dk/dv as two kernels; >= 4096 takes the long path.
@pytest.mark.parametrize('seq,grad,kernels', [
    (512, False, 1), (512, True, 2), (2048, False, 1), (2048, True, 3),
    (4096, False, 1), (4096, True, 3), (8192, False, 1), (8192, True, 3)])
def test_flash_kernel_compiles_for_v5e(chip, on_chip_dispatch, seq, grad,
                                       kernels):
    x = jax.ShapeDtypeStruct((32, 12, seq, 64), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return fa.flash_attention_bhnd(q, k, v, causal=True)

    fn = fwd
    if grad:
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert compiled.as_text().count('tpu_custom_call') == kernels
    assert _hbm_bytes(compiled) < HBM_BYTES


# ---- the whole train step ---------------------------------------------------

def _compile_train_step(chip, layers, batch=32, seq=512):
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        num_layers=layers, max_position_embeddings=seq, fused_loss=True,
        **WIDTHS))
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = func_mod.TrainStep(model, lambda o, l: model.loss(o, l), opt)
    ids = np.zeros((batch, seq), np.int32)
    batch_arrays = step._step_args(ids, ids)
    step._build(batch_arrays)
    # the argument tree TrainStep.__call__ / trace_jaxpr assemble
    args = (func_mod.extract_params(model), func_mod.extract_buffers(model),
            step._opt_state(), batch_arrays, step._lr_array(),
            rng_mod.default_generator()._key)
    return jax.jit(step._pure_step, donate_argnums=(0, 2)).lower(
        *_abstract(args, chip)).compile()


def test_train_step_compiles_for_v5e(chip, on_chip_dispatch):
    compiled = _compile_train_step(chip, layers=2)
    # the Pallas flash forward and the fused backward, once per layer
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 4
    assert _hbm_bytes(compiled) < HBM_BYTES
    # the chip's compiler keeps the scope names on the ops it emits
    for scope in ('gpt.embed', 'gpt.ln', 'gpt.attn.qkv', 'gpt.attn.core',
                  'flash.fwd', 'flash.bwd', 'gpt.attn.out', 'gpt.mlp',
                  'gpt.loss', 'optimizer.adamw'):
        assert scope in text, scope


@pytest.mark.slow
def test_bert_base_train_step_fits_v5e(chip, on_chip_dispatch):
    compiled = _compile_train_step(chip, layers=12)
    assert compiled.as_text().count('tpu_custom_call') == 24
    assert _hbm_bytes(compiled) < HBM_BYTES


# ---- the serving engine's programs -------------------------------------------

def _serving_model(layers):
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        num_layers=layers, max_position_embeddings=1024, **WIDTHS))
    model.bfloat16()
    model.eval()
    return model


def _engine_programs(layers):
    """{name: (jitted program, the argument tuple its dispatch site builds)}
    for the engine at chip_smoke.py's serving shape."""
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    model = _serving_model(layers)
    key = np.zeros((2,), np.uint32)
    ids = np.zeros((1, 32), np.int32)
    sampling = (key, np.float32(1.0), np.int32(0), np.asarray(False))
    paged = PagedContinuousBatchingEngine(
        model, num_seqs=8, max_len=256, page_size=16, num_pages=65,
        prefill_chunk=32, decode_block=8, spec_k=4, donate=True)
    state = (paged._last, paged._gen, paged._budgets, paged._active,
             paged._keys, paged._temps, paged._topks, paged._sample)
    tables = paged.scheduler.block_tables
    return {
        # serving/engine.py _prefill_call / _decode_step / _spec_step
        'paged_prefill': (paged._prefill_jit, (
            paged._params, paged._bufs, paged._pools, tables[0:1],
            np.zeros((1,), np.int32), ids, np.int32(32)) + sampling),
        'paged_decode': (paged._decode_jit, (
            paged._params, paged._bufs, paged._pools, tables, paged._lens)
            + state),
        'paged_verify': (paged._verify_jit, (
            paged._params, paged._bufs, paged._pools, tables, paged._lens,
            np.zeros((8, 5), np.int32))),
    }


def _pool_invariant(text, pool_shape):
    """What `PagedKVCache` promises of a compiled program: no `copy` or
    `transpose` gives an array of a pool's element count, every mention of
    the pool's shape carries ONE minor-to-major order, the default `{2,1,0}`
    (whatever tiling or memory space follows it), and an asynchronous
    `copy-start` of a pool only moves it between memory spaces in that
    layout (XLA's prefetch of the attention's operand into fast memory: the
    read itself, which it schedules for pools that fit)."""
    count = math.prod(pool_shape)
    shape = r'bf16\[%s\]' % ','.join(str(d) for d in pool_shape)
    op = re.compile(r'= \(?\w+\[([\d,]+)\]\{[^ ]*\}?.* (copy|transpose|copy-start)\(')
    for line in text.splitlines():
        m = op.search(line)
        if not m or math.prod(map(int, m.group(1).split(','))) != count:
            continue
        orders = set(re.findall(r'\[[\d,]+\]\{([\d,]+)',
                                line.split(m.group(2) + '(')[0]))
        rank = m.group(1).count(',') + 1      # (3, or 4 for the page view)
        default = ','.join(str(d) for d in reversed(range(rank)))
        assert m.group(2) == 'copy-start' and orders == {default}, line[:300]
    orders = set(re.findall(shape + r'\{([\d,]+)', text))
    assert orders == {'2,1,0'}, orders


# GPT-2 XL's and Olmo-Hybrid's full-attention layers at the benchmark's sizes:
# (heads, head_dim, pages, blocks a row, decode rows, prefill chunk)
POOLS = {'gpt2-xl': (25, 64, 512, 64, 24, 256),
         'olmo-hybrid': (30, 128, 2432, 512, 16, 512)}


@pytest.mark.parametrize('program', ['decode', 'verify', 'prefill'])
@pytest.mark.parametrize('widths', sorted(POOLS))
def test_paged_attention_leaves_the_pools_where_they_lie(chip, on_chip_dispatch,
                                                         widths, program):
    """Four layers of `paged_attention` alone — a decode burst's 4-step scan
    over donated pools, a verify call over 4 drafts, a one-row prefill chunk —
    compile to programs that never copy a pool (3 - 13 s each)."""
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.text.models import cache as C
    heads, dh, pages, nb, rows, chunk = POOLS[widths]
    page, layers = 16, 4
    b, n = {'decode': (rows, 1), 'verify': (rows, 5),
            'prefill': (1, chunk)}[program]
    pool = C.paged_pool_shape(heads, dh, pages, page)

    def forward(pools, bt, lens, x):
        out = []
        for ck, cv in pools:
            cache = C.PagedKVCache(Tensor(ck), Tensor(cv), bt, lens, page)
            o, new = C.paged_attention(x, x * 0.5, x * 0.25, cache, 'x.attn')
            x = (x + o._data).astype(x.dtype)
            out.append((new.k._data, new.v._data))
        return out, x

    def burst(pools, bt, lens, x):
        def body(carry, _):
            pools, lens, x = carry
            pools, x = forward(pools, bt, lens, x)
            return (pools, lens + 1, x), x[:, 0, 0, 0]
        (pools, _, _), ys = jax.lax.scan(body, (pools, lens, x), None,
                                         length=4)
        return pools, ys

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    args = ([(sds(pool, jnp.bfloat16),) * 2 for _ in range(layers)],
            sds((b, nb), jnp.int32), sds((b,), jnp.int32),
            sds((b, n, heads, dh), jnp.bfloat16))
    compiled = jax.jit(burst if program == 'decode' else forward,
                       donate_argnums=(0,)).lower(*args).compile()
    _pool_invariant(compiled.as_text(), pool)
    # ... and holds them once: the outputs are the donated inputs, and the
    # temporaries (scores, a chunk's gathered views) could not hold the
    # pools a second time
    m = compiled.memory_analysis()
    held = 2 * layers * 2 * math.prod(pool)
    assert m.alias_size_in_bytes >= held
    assert m.temp_size_in_bytes < held / 2


# no program sorts the 30528-wide vocabulary row to pick a token
# (serving/engine.py _pick_tokens: the argmax, or a threshold by selection);
# while two of them did, that sort alone took ~25 s to compile for the chip
@pytest.mark.parametrize('name', ['paged_verify', 'paged_prefill',
                                  'paged_decode'])
def test_engine_program_compiles_for_v5e(chip, on_chip_dispatch, name):
    jitted, args = _engine_programs(layers=12)[name]
    compiled = jitted.lower(*_abstract(args, chip)).compile()
    # serving never reaches the flash kernel (chunks and decode rows are
    # far under its 512-row floor): these are pure XLA programs
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 0
    assert not re.search(r'\bsort\(', text)
    assert _hbm_bytes(compiled) < HBM_BYTES
    scopes = ['gpt.attn.paged_write']
    if name != 'paged_verify':
        scopes.append('serving.pick_token')
    for scope in scopes + ['gpt.attn.mask', 'gpt.attn.core', 'gpt.lm_head']:
        assert scope in text, scope
    # 8 rows of 256 against a pool of 65 x 16: the decode and verify
    # batches read the pool in place, the one-row chunk gathers its view
    assert ('gpt.attn.paged_gather' in text) == (name == 'paged_prefill')
    from paddle_tpu.text.models.cache import paged_pool_shape
    _pool_invariant(text, paged_pool_shape(12, 64, 65, 16))


def _described(make_model):
    """A model of bfloat16 parameters that are described, not built."""
    from paddle_tpu import nn
    with nn.skip_init():
        model = make_model()
    for param in model.parameters():
        param._data = jax.ShapeDtypeStruct(param._data.shape, jnp.bfloat16)
    model.eval()
    return model


def _decode_program(eng, pages, chip):
    """The engine's decode program lowered over pools of `pages` pages,
    described and not built (the engine itself holds a two-page pool)."""
    from paddle_tpu.serving.kv_cache import build_paged_pools
    pools = jax.eval_shape(lambda: build_paged_pools(
        eng._model, pages, eng.page_size, eng.num_slots))
    args = (eng._params, eng._bufs, pools, eng.scheduler.block_tables,
            eng._lens, eng._last, eng._gen, eng._budgets, eng._active,
            eng._keys, eng._temps, eng._topks, eng._sample)
    return eng._decode_jit.lower(*_abstract(args, chip)).compile()


@pytest.mark.slow
def test_gpt2_xl_decode_program_fits_1024_pages(chip, on_chip_dispatch):
    """The page count `gpt2-xl-serve-1chip` asked for (ISSUE 25) and could not
    have while the decode program held every pool twice, padded 2.56x."""
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.text.models.cache import paged_pool_shape
    model = _described(lambda: GPTForCausalLM(GPTConfig(
        vocab_size=50257, hidden_size=1600, num_layers=48, num_heads=25,
        max_position_embeddings=1024, dropout=0.0)))
    eng = PagedContinuousBatchingEngine(
        model, num_seqs=24, max_len=1024, page_size=16, num_pages=2,
        prefill_chunk=256, decode_block=4, donate=True)
    compiled = _decode_program(eng, 1024, chip)
    _pool_invariant(compiled.as_text(), paged_pool_shape(25, 64, 1024, 16))
    assert _hbm_bytes(compiled) < HBM_BYTES - 1.5e9


@pytest.mark.slow
def test_olmo_hybrid_decode_program_fits_3072_pages(chip, on_chip_dispatch):
    """... and the 3 072 `olmo-hybrid-7b-serve-1chip` asked for: the model at
    the configuration's own sizes (16 layers, 4 of them with pools)."""
    import json
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.text.models import OlmoHybridConfig, OlmoHybridForCausalLM
    from paddle_tpu.text.models.cache import paged_pool_shape
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, '..', 'benchmarks', 'configs',
                           'olmo-hybrid-7b-serve-1chip.json')) as f:
        cfg = json.load(f)
    m, e = cfg['model'], cfg['engine']
    fields = OlmoHybridConfig.__init__.__code__.co_varnames
    model = _described(lambda: OlmoHybridForCausalLM(OlmoHybridConfig(
        **{k: v for k, v in m.items() if k in fields})))
    eng = PagedContinuousBatchingEngine(
        model, num_seqs=e['num_seqs'], max_len=e['max_len'],
        page_size=e['page_size'], num_pages=2,
        prefill_chunk=e['prefill_chunk'], decode_block=e['decode_block'],
        prefix_cache=False, donate=True)
    compiled = _decode_program(eng, 3072, chip)
    _pool_invariant(compiled.as_text(), paged_pool_shape(
        m['num_attention_heads'], m['hidden_size'] // m['num_attention_heads'],
        3072, e['page_size']))
    assert _hbm_bytes(compiled) < HBM_BYTES - 1.5e9



@pytest.mark.slow
def test_kimi_linear_programs_fit_128_slots(chip, on_chip_dispatch):
    """`kimi-linear-48b-serve-1chip` at its own sizes: 128 slots of KDA
    state, 20 480 pages of latent rows, 64 of 256 experts a layer. Both
    programs leave 1.5 GB of the chip, and neither copies a latent pool
    (a row of whole lanes keeps the pool's layout) nor an expert's
    weights (`[held, f, d]`: with the width d innermost the decode step's
    products take them as they lie)."""
    import json
    from paddle_tpu import nn
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.serving import engine as engine_mod
    from paddle_tpu.text.models import KimiLinearConfig, KimiLinearForCausalLM
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, '..', 'benchmarks', 'configs',
                           'kimi-linear-48b-serve-1chip.json')) as f:
        cfg = json.load(f)
    m, e = cfg['model'], cfg['engine']
    fields = KimiLinearConfig.__init__.__code__.co_varnames
    keys = dict({k: v for k, v in m.items() if k in fields},
                num_experts=m['num_experts_published'],
                experts_held=tuple(m['experts_held']))
    with nn.skip_init():
        model = KimiLinearForCausalLM(KimiLinearConfig(**keys))
    for name, param in model.named_parameters():
        router = name.endswith(('router', 'e_score_correction_bias'))
        param._data = jax.ShapeDtypeStruct(
            param._data.shape, jnp.float32 if router else jnp.bfloat16)
    model.eval()
    build = engine_mod.build_paged_pools
    engine_mod.build_paged_pools = lambda *a: jax.eval_shape(
        lambda: build(*a))               # 2.8 GB of state: described
    try:
        eng = PagedContinuousBatchingEngine(
            model, donate=True,
            **{k: v for k, v in e.items() if k != 'class'})
    finally:
        engine_mod.build_paged_pools = build
    decode = eng._decode_jit.lower(*_abstract((
        eng._params, eng._bufs, eng._pools, eng.scheduler.block_tables,
        eng._lens, eng._last, eng._gen, eng._budgets, eng._active,
        eng._keys, eng._temps, eng._topks, eng._sample), chip)).compile()
    prefill = eng._prefill_jit.lower(*_abstract((
        eng._params, eng._bufs, eng._pools, eng.scheduler.block_tables[:1],
        np.zeros((1,), np.int32),
        np.zeros((1, e['prefill_chunk']), np.int32), np.int32(1),
        np.zeros((2,), np.uint32), np.float32(1), np.int32(0),
        np.asarray(False), np.int32(0)), chip)).compile()
    for compiled in (decode, prefill):
        assert _hbm_bytes(compiled) < HBM_BYTES - 1.5e9 - 0.25e9
        text = compiled.as_text()
        entry = text[text.index('\nENTRY '):]
        assert not re.search(r'bf16\[1,327680,640\]\S* copy\(', entry)
        assert not re.search(r'bf16\[64,1024,2304\]\S* copy\(', entry)
