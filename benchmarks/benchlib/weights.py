"""Seed-made weights, made on the device in one jitted call in the type
they are served or trained in. The canonical form is STACKED: one array
per kind of leaf with the layers on the first axis; `program_leaves`
slices it into the leaf names of `paddle_tpu`'s GPTForCausalLM. The
program's own initializers are not used, and the references take these
arrays, never the program's.

GPT-2's published initialisation is N(0, 0.02) for matrices and tables,
the residual projections scaled by 1/sqrt(2 L), zero biases and unit
LayerNorm gains. Departure (stated in the configuration files under
`assumed`): biases are drawn N(0, 0.02) and LayerNorm gains 1 + N(0,
0.02), so that every leaf is non-trivial in the comparison, as in a
trained model."""
import math
from functools import partial

import jax
import jax.numpy as jnp

from . import counts

# kind -> (shape builder over (d, f), std rule, mean); per layer
LAYER_KINDS = (
    ('ln_1.w', lambda d, f: (d,), 'plain', 1.0),
    ('ln_1.b', lambda d, f: (d,), 'plain', 0.0),
    ('qkv.w', lambda d, f: (d, 3 * d), 'plain', 0.0),
    ('qkv.b', lambda d, f: (3 * d,), 'plain', 0.0),
    ('out.w', lambda d, f: (d, d), 'resid', 0.0),
    ('out.b', lambda d, f: (d,), 'plain', 0.0),
    ('ln_2.w', lambda d, f: (d,), 'plain', 1.0),
    ('ln_2.b', lambda d, f: (d,), 'plain', 0.0),
    ('fc_in.w', lambda d, f: (d, f), 'plain', 0.0),
    ('fc_in.b', lambda d, f: (f,), 'plain', 0.0),
    ('fc_out.w', lambda d, f: (f, d), 'resid', 0.0),
    ('fc_out.b', lambda d, f: (d,), 'plain', 0.0),
)
TOP_KINDS = ('wte', 'wpe', 'ln_f.w', 'ln_f.b')

# stacked kind -> the program's leaf name inside a block
PROGRAM_NAMES = {
    'ln_1.w': 'ln_1.weight', 'ln_1.b': 'ln_1.bias',
    'qkv.w': 'attn.qkv_proj.weight', 'qkv.b': 'attn.qkv_proj.bias',
    'out.w': 'attn.out_proj.weight', 'out.b': 'attn.out_proj.bias',
    'ln_2.w': 'ln_2.weight', 'ln_2.b': 'ln_2.bias',
    'fc_in.w': 'mlp.fc_in.weight', 'fc_in.b': 'mlp.fc_in.bias',
    'fc_out.w': 'mlp.fc_out.weight', 'fc_out.b': 'mlp.fc_out.bias',
}


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@partial(jax.jit, static_argnames=('dims', 'dtype'))
def _make(key, dims, dtype):
    vocab, npos, d, f, n_layer, std = dims
    out = {}

    def draw(k, shape, scale, mean):
        x = jax.random.normal(k, shape, jnp.float32) * scale + mean
        return x.astype(dtype)

    keys = jax.random.split(key, len(TOP_KINDS) + len(LAYER_KINDS))
    out['wte'] = draw(keys[0], (vocab, d), std, 0.0)
    out['wpe'] = draw(keys[1], (npos, d), std, 0.0)
    out['ln_f.w'] = draw(keys[2], (d,), std, 1.0)
    out['ln_f.b'] = draw(keys[3], (d,), std, 0.0)
    for i, (kind, shape, rule, mean) in enumerate(LAYER_KINDS):
        scale = std / math.sqrt(2 * n_layer) if rule == 'resid' else std
        lk = jax.random.split(keys[len(TOP_KINDS) + i], n_layer)
        out[kind] = jax.vmap(
            lambda k: draw(k, shape(d, f), scale, mean))(lk)
    return out


def make_stacked(m, seed, dtype):
    """The stacked weights of model section `m` from `seed`, on the
    default device, in `dtype` (a jnp dtype name)."""
    dims = (m['vocab_size'], m['n_positions'], m['n_embd'], counts.inner(m),
            m['n_layer'], float(m['initializer_range']))
    return _make(seed_key(seed), dims, jnp.dtype(dtype).name)


@jax.jit
def _slice_layers(stacked):
    n_layer = stacked['qkv.w'].shape[0]
    out = {'gpt.wte.weight': stacked['wte'], 'gpt.wpe.weight': stacked['wpe'],
           'gpt.ln_f.weight': stacked['ln_f.w'],
           'gpt.ln_f.bias': stacked['ln_f.b']}
    for kind, name in PROGRAM_NAMES.items():
        for i in range(n_layer):
            out['gpt.h.%d.%s' % (i, name)] = stacked[kind][i]
    return out


def program_leaves(stacked):
    """{program leaf name: array}, the same values leaf by leaf."""
    return _slice_layers(stacked)


def leaf_names(n_layer):
    """Every program leaf as (stacked kind, layer or None, program name)."""
    out = [('wte', None, 'gpt.wte.weight'), ('wpe', None, 'gpt.wpe.weight'),
           ('ln_f.w', None, 'gpt.ln_f.weight'),
           ('ln_f.b', None, 'gpt.ln_f.bias')]
    for kind, name in PROGRAM_NAMES.items():
        for i in range(n_layer):
            out.append((kind, i, 'gpt.h.%d.%s' % (i, name)))
    return out
