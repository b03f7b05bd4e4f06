"""The Olmo-Hybrid family (`"family": "olmo_hybrid"` in a configuration
file): what the serving driver, the readers and `tools/control.py` need
of one architecture and find by that name. `m` is the model section of a
configuration file, in the key names of the published `config.json`
(https://huggingface.co/allenai/Olmo-Hybrid-7B). It serves only: there
is no `TrainReference`.

  weights    make_stacked, program_leaves, leaf_names
  the model  build_model (the program's own class at these sizes)
  counts     n_params, n_matmul_params, weight_bytes, kv_bytes_per_token,
             state_bytes_per_resident, serve_flops_token,
             decode_step_least_seconds, prefill_call_least_seconds
  reference  forward_logits, served_gaps

**The architecture** (x_t the block input of width d; no projection has
a bias). Layers are of two kinds by `layer_types`.

Linear layer (gated delta rule: Yang et al., "Gated Delta Networks",
arXiv:2412.06464; `linear_allow_neg_eigval` after Grazzi et al.,
arXiv:2411.12537): u_t = [W_q x_t; W_k x_t; W_v x_t]; each channel goes
through a causal depthwise convolution of width K over time (c_t = sum_j
w[j] u_{t-K+1+j}, zeros before the first token, no bias), then SiLU. Per
head q, k of `linear_key_head_dim` and v of `linear_value_head_dim`;
q <- q / |q| * dk^-1/2, k <- k / |k| with |x| = sqrt(sum x^2 + 1e-6);
beta_t = 2 sigmoid(w_b . x_t) (the 2 is `linear_allow_neg_eigval`),
alpha_t = exp(-exp(A_log) softplus(w_a . x_t + dt_bias)); state S
[dk, dv], float32, zero before the first token:

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    y_t = W_o [RMSNorm_dv(o_t) * SiLU(W_g x_t)]

Full layer: q = RMSNorm(W_q x), k = RMSNorm(W_k x) over the whole width,
v = W_v x; heads of d / num_attention_heads; causal softmax(q k^T /
sqrt(head size)) v; W_o; no rotary embedding (`rope_theta` is null).

Block, both kinds: h = x + RMSNorm(mixer(x)); out = h +
RMSNorm(W_down(SiLU(W_gate h) * W_up h)). Final RMSNorm, untied head.

**Weights** are made on the device from the seed, one jitted call per
kind of leaf (a 16-layer stack of one MLP matrix is 1.35 GB: all kinds
in one program would hold their float32 draws together), in the type
they are served in. STACKED: one array per kind, the layers of that kind
on the first axis (a linear layer's leaves over the linear layers, a
full layer's over the full ones, the MLP and the block norms over all).
Matrices and tables N(0, initializer_range); norm weights 1 + N(0,
0.02); what else the published config leaves open is in the
configuration files under `assumed` (`GATES`, `CONV_STD` below).

**The plain reference**: float32 `jax.numpy`, every projection and both
attention products through `benchlib.reference.product` (which the
control rounds to float8); the linear mixer is the literal per-token
recurrence above in a `lax.scan`, full attention a masked softmax in
blocks of query rows, layer by layer and one sequence at a time so that
16 layers at width 3840 fit the chip. The recurrence's own contractions
(S^T k, the rank-one write, S^T q) are float32 in the configuration, not
bf16, and the control leaves them in float32. It imports nothing of the
program. Call it under `benchlib.reference.highest()`.
"""
import collections
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import reference as R
from benchlib.weights import load_leaves, seed_key

F32 = jnp.float32
product = R.product
LINEAR, FULL = 'linear_attention', 'full_attention'

# assumed (the published config does not fix them; the configuration
# files say why): the two gates' projections are drawn narrower than the
# rest so that alpha and beta are not saturated by a residual stream
# that grows with depth under random weights; the convolution's taps at
# the scale of a default depthwise width-4 convolution, U(-1/2, 1/2);
# alpha at w_a . x = 0 log-uniform over (0.5, 0.999)
GATE_STD_SHARE = 1.0 / 16.0
CONV_STD = 0.3
ALPHA_SPAN = (0.5, 0.999)
NORM_STD = 0.02


# ---- shapes ---------------------------------------------------------------

def conv_dim(m):
    return m['linear_num_value_heads'] * (2 * m['linear_key_head_dim']
                                          + m['linear_value_head_dim'])


def n_of(m, kind):
    return sum(1 for t in m['layer_types'] if t == kind)


def leaf_table(m):
    """stacked kind -> (which layers: LINEAR | FULL | 'all' | None,
    shape of one layer's leaf, draw rule, the program's name inside a
    block or at the top)."""
    d, f = m['hidden_size'], m['intermediate_size']
    h, dk, dv = (m['linear_num_value_heads'], m['linear_key_head_dim'],
                 m['linear_value_head_dim'])
    return {
        'embed': (None, (m['vocab_size'], d), 'plain',
                  'model.embed_tokens.weight'),
        'norm_f': (None, (d,), 'norm', 'model.norm.weight'),
        'head': (None, (d, m['vocab_size']), 'plain', 'lm_head.weight'),
        'mixer_norm': ('all', (d,), 'norm', 'mixer_norm.weight'),
        'mlp_norm': ('all', (d,), 'norm', 'mlp_norm.weight'),
        'gate.w': ('all', (d, f), 'plain', 'mlp.gate_proj.weight'),
        'up.w': ('all', (d, f), 'plain', 'mlp.up_proj.weight'),
        'down.w': ('all', (f, d), 'plain', 'mlp.down_proj.weight'),
        'lin.q.w': (LINEAR, (d, h * dk), 'plain', 'mixer.q_proj.weight'),
        'lin.k.w': (LINEAR, (d, h * dk), 'plain', 'mixer.k_proj.weight'),
        'lin.v.w': (LINEAR, (d, h * dv), 'plain', 'mixer.v_proj.weight'),
        'lin.g.w': (LINEAR, (d, h * dv), 'plain', 'mixer.g_proj.weight'),
        'lin.a.w': (LINEAR, (d, h), 'gate', 'mixer.a_proj.weight'),
        'lin.b.w': (LINEAR, (d, h), 'gate', 'mixer.b_proj.weight'),
        'lin.o.w': (LINEAR, (h * dv, d), 'plain', 'mixer.o_proj.weight'),
        'lin.conv': (LINEAR, (m['linear_conv_kernel_dim'], conv_dim(m)),
                     'conv', 'mixer.conv_weight'),
        'lin.A_log': (LINEAR, (h,), 'a_log', 'mixer.A_log'),
        'lin.dt_bias': (LINEAR, (h,), 'dt_bias', 'mixer.dt_bias'),
        'lin.o_norm': (LINEAR, (dv,), 'norm', 'mixer.o_norm.weight'),
        'full.q.w': (FULL, (d, d), 'plain', 'mixer.q_proj.weight'),
        'full.k.w': (FULL, (d, d), 'plain', 'mixer.k_proj.weight'),
        'full.v.w': (FULL, (d, d), 'plain', 'mixer.v_proj.weight'),
        'full.o.w': (FULL, (d, d), 'plain', 'mixer.o_proj.weight'),
        'full.q_norm': (FULL, (d,), 'norm', 'mixer.q_norm.weight'),
        'full.k_norm': (FULL, (d,), 'norm', 'mixer.k_norm.weight'),
    }


def layers_of(m, where):
    """The model's layer indices a stacked kind runs over, in order."""
    return [i for i, t in enumerate(m['layer_types'])
            if where == 'all' or t == where]


# ---- weights from the seed ------------------------------------------------

class Stacked(dict):
    """The stacked weights, and the model section they were made for
    (`program_leaves` needs the layer pattern, which no array holds)."""
    m = None


@partial(jax.jit, static_argnames=('shape', 'rule', 'std', 'dtype'))
def _draw(key, shape, rule, std, dtype):
    scale, mean = {'plain': (std, 0.0), 'norm': (NORM_STD, 1.0),
                   'gate': (std * GATE_STD_SHARE, 0.0),
                   'conv': (CONV_STD, 0.0), 'a_log': (0.5, 0.0)}[rule]
    return (jax.random.normal(key, shape, F32) * scale + mean).astype(dtype)


@partial(jax.jit, static_argnames=('dtype',))
def _dt_bias(key, a_log, dtype):
    """dt_bias such that alpha at w_a . x = 0, exp(-exp(A_log)
    softplus(dt_bias)), lies log-uniformly inside ALPHA_SPAN: with
    `A_log` as stored (rounded), so that the span holds in the type
    served."""
    lo, hi = (math.log(-math.log(a)) for a in reversed(ALPHA_SPAN))
    rate = jnp.exp(lo + jax.random.uniform(key, a_log.shape, F32) * (hi - lo))
    return jnp.log(jnp.expm1(rate / jnp.exp(a_log.astype(F32)))).astype(dtype)


def make_stacked(m, seed, dtype):
    """The stacked weights of model section `m` from `seed`, on the
    default device, in `dtype` (a jnp dtype name)."""
    table = leaf_table(m)
    keys = dict(zip(sorted(table),
                    jax.random.split(seed_key(seed), len(table))))
    std, dtype = float(m['initializer_range']), jnp.dtype(dtype).name
    out = Stacked()
    out.m = m
    for kind, (where, shape, rule, _) in table.items():
        if where is not None:
            shape = (len(layers_of(m, where)),) + shape
        if rule != 'dt_bias':
            out[kind] = _draw(keys[kind], shape, rule, std, dtype)
    out['lin.dt_bias'] = _dt_bias(keys['lin.dt_bias'], out['lin.A_log'],
                                  dtype)
    return out


def leaf_names(m):
    """Every program leaf as (stacked kind, index on the kind's first
    axis or None, program name)."""
    out = []
    for kind, (where, _, _, name) in leaf_table(m).items():
        if where is None:
            out.append((kind, None, name))
            continue
        for j, layer in enumerate(layers_of(m, where)):
            out.append((kind, j, 'model.layers.%d.%s' % (layer, name)))
    return out


def program_leaves(stacked):
    """{program leaf name: array}, the same values leaf by leaf. It
    CONSUMES `stacked`: each kind is dropped from the dict and freed as
    soon as it is sliced, so that the leaves of an 8 GB model never sit
    on the device beside the whole of their stacked form."""
    m, out = stacked.m, {}
    names = leaf_names(m)
    for kind in list(stacked):
        arr = stacked.pop(kind)
        for k, j, name in names:
            if k == kind:
                out[name] = arr if j is None else arr[j]
        if leaf_table(m)[kind][0] is not None:
            arr.delete()
    return out


# ---- the program's model --------------------------------------------------

def build_model(m, dtype, leaves):
    """The program's OlmoHybridForCausalLM at the configuration's sizes
    holding the benchmark's weights (`dtype` is theirs already). Built
    without an initialisation of its own (`nn.skip_init`): 8 GB of leaves
    and as much again in random initial values do not fit one chip."""
    from paddle_tpu import nn
    from paddle_tpu.text.models import OlmoHybridConfig, OlmoHybridForCausalLM
    keys = ('vocab_size', 'hidden_size', 'intermediate_size',
            'num_hidden_layers', 'num_attention_heads',
            'num_key_value_heads', 'hidden_act', 'max_position_embeddings',
            'attention_bias', 'rms_norm_eps', 'tie_word_embeddings',
            'layer_types', 'linear_num_key_heads', 'linear_num_value_heads',
            'linear_key_head_dim', 'linear_value_head_dim',
            'linear_conv_kernel_dim', 'linear_allow_neg_eigval',
            'rope_parameters', 'initializer_range')
    with nn.skip_init():
        model = OlmoHybridForCausalLM(OlmoHybridConfig(
            **{k: m[k] for k in keys}))
    load_leaves(model, leaves)
    return model


# ---- counts from shapes ---------------------------------------------------

def _mixer_matmul_params(m, kind):
    d = m['hidden_size']
    if kind == FULL:
        return 4 * d * d
    h, dk, dv = (m['linear_num_value_heads'], m['linear_key_head_dim'],
                 m['linear_value_head_dim'])
    return d * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * d


Counts = collections.namedtuple(
    'Counts', 'n_params n_matmul kv_elems state_f32 tail_elems rule_flops')
_COUNTS = {}


def counts(m):
    """The counts of model section `m`, computed once per section: the
    serving driver asks for a token's operations at every position of
    every prompt, between engine steps, with the device idle. (A section
    is not edited after it has been read.)"""
    hit = _COUNTS.get(id(m))
    if hit is not None and hit[0] is m:
        return hit[1]
    d, f = m['hidden_size'], m['intermediate_size']
    h, dk, dv = (m['linear_num_value_heads'], m['linear_key_head_dim'],
                 m['linear_value_head_dim'])
    n_matmul = sum(_mixer_matmul_params(m, t) + 3 * d * f
                   for t in m['layer_types']) + d * m['vocab_size']
    vectors = {LINEAR: m['linear_conv_kernel_dim'] * conv_dim(m) + 2 * h
               + dv + 2 * d, FULL: 4 * d}
    n_params = n_matmul + m['vocab_size'] * d + d \
        + sum(vectors[t] for t in m['layer_types'])
    c = Counts(n_params, n_matmul, 2 * n_of(m, FULL) * d,
               n_of(m, LINEAR) * h * dk * dv,
               n_of(m, LINEAR) * (m['linear_conv_kernel_dim'] - 1)
               * conv_dim(m),
               n_of(m, LINEAR) * 3 * 2 * h * dk * dv)
    _COUNTS[id(m)] = (m, c)
    return c


def n_matmul_params(m):
    """Parameters a token multiplies in a forward pass: every layer's
    projections and MLP and the untied head; the embedding lookup is a
    read, and the convolution's taps and the per-head vectors are not
    matrices."""
    return counts(m).n_matmul


def n_params(m):
    return counts(m).n_params


def weight_bytes(m, itemsize=2):
    return counts(m).n_params * itemsize


def kv_bytes_per_token(m, itemsize=2):
    """What one held token keeps: its K and V rows over the FULL layers."""
    return counts(m).kv_elems * itemsize


def state_bytes_per_resident(m, itemsize=2):
    """What one resident sequence keeps over the LINEAR layers, whatever
    its length: the float32 state S per head and the last K-1 inputs of
    the convolution."""
    c = counts(m)
    return c.state_f32 * 4 + c.tail_elems * itemsize


def serve_flops_token(m, context):
    """Forward of one token that attends to `context` held tokens: two
    operations per multiplied parameter, QK^T and PV over the context in
    the full layers (4 H per held token and layer), the rule's three
    products (S^T k, the rank-one write, S^T q, each 2 H dk dv) in the
    linear ones."""
    c = counts(m)
    return 2 * c.n_matmul + 2 * c.kv_elems * context + c.rule_flops


def _least(flops, nbytes, peak_flops, peak_bw):
    tc, tb = flops / peak_flops, nbytes / peak_bw
    return max(tc, tb), ('compute' if tc >= tb else 'bandwidth')


def decode_step_least_seconds(m, contexts, peak_flops, peak_bw, itemsize=2):
    """Least time of ONE decode step for rows holding `contexts` tokens:
    max(FLOPs / peak, bytes / bandwidth) with bytes = the weights once +
    the K/V of the tokens HELD + the state of each resident read and
    written. Returns (seconds, 'compute' | 'bandwidth')."""
    flops = sum(serve_flops_token(m, c) for c in contexts)
    nbytes = weight_bytes(m, itemsize) \
        + kv_bytes_per_token(m, itemsize) * sum(contexts) \
        + 2 * state_bytes_per_resident(m, itemsize) * len(contexts)
    return _least(flops, nbytes, peak_flops, peak_bw)


def prefill_call_least_seconds(m, start, valid, peak_flops, peak_bw,
                               itemsize=2):
    """Least time of one prefill call that takes `valid` tokens of a
    sequence that already holds `start`: each token's forward against
    the context it sees, or the weights once + the K/V then held + the
    sequence's state read and written, whichever takes longer."""
    flops = sum(serve_flops_token(m, p + 1)
                for p in range(start, start + valid))
    nbytes = weight_bytes(m, itemsize) \
        + kv_bytes_per_token(m, itemsize) * (start + valid) \
        + 2 * state_bytes_per_resident(m, itemsize)
    return _least(flops, nbytes, peak_flops, peak_bw)


# ---- the plain reference --------------------------------------------------

# the sizes the blocks read, hashable: a static argument of the jitted
# pieces (heads of the linear layers, their key and value sizes, the
# convolution's width, heads of the full layers, epsilon, the factor 2)
Sizes = collections.namedtuple('Sizes', 'h dk dv kern heads eps neg')


def sizes(m):
    return Sizes(m['linear_num_value_heads'], m['linear_key_head_dim'],
                 m['linear_value_head_dim'], m['linear_conv_kernel_dim'],
                 m['num_attention_heads'], float(m['rms_norm_eps']),
                 bool(m['linear_allow_neg_eigval']))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def linear_mixer(x, p, sz, quant='none'):
    """x [B, T, d] float32 -> [B, T, d]: the recurrence, token by token."""
    b, t, _ = x.shape
    h, dk, dv, kern = sz.h, sz.dk, sz.dv, sz.kern
    proj = lambda w: product('btd,df->btf', x, w, quant)
    u = jnp.concatenate([proj(p['lin.q.w']), proj(p['lin.k.w']),
                         proj(p['lin.v.w'])], axis=-1)
    back = jnp.pad(u, [(0, 0), (kern - 1, 0), (0, 0)])
    c = jax.nn.silu(sum(p['lin.conv'][j] * back[:, j:j + t]
                        for j in range(kern)))
    q = l2_norm(c[..., :h * dk].reshape(b, t, h, dk)) * dk ** -0.5
    k = l2_norm(c[..., h * dk:2 * h * dk].reshape(b, t, h, dk))
    v = c[..., 2 * h * dk:].reshape(b, t, h, dv)
    beta = jax.nn.sigmoid(proj(p['lin.b.w'])) * (2.0 if sz.neg else 1.0)
    alpha = jnp.exp(-jnp.exp(p['lin.A_log']) * jax.nn.softplus(
        proj(p['lin.a.w']) + p['lin.dt_bias']))

    def token(s, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs          # [B, H, *]
        s = alpha_t[..., None, None] * s
        wrote = jnp.sum(s * k_t[..., None], axis=-2)             # S^T k
        s = s + k_t[..., None] * (beta_t[..., None]
                                  * (v_t - wrote))[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)           # S^T q

    first = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((b, h, dk, dv), F32),
                        tuple(first(a) for a in (q, k, v, alpha, beta)))
    o = rms_norm(first(o), p['lin.o_norm'], sz.eps)
    gate = jax.nn.silu(proj(p['lin.g.w'])).reshape(b, t, h, dv)
    return product('btf,fd->btd', (o * gate).reshape(b, t, h * dv),
                   p['lin.o.w'], quant)


QUERY_ROWS = 512     # full attention's scores, [B, H, QUERY_ROWS, T] a block


def full_mixer(x, p, sz, quant='none'):
    """x [B, T, d] float32 -> [B, T, d]: masked softmax attention, the
    queries in blocks of rows (T = 7 k would otherwise hold 30 x T x T
    scores at once)."""
    b, t, d = x.shape
    hd = d // sz.heads
    proj = lambda w: product('btd,df->btf', x, w, quant)
    q = rms_norm(proj(p['full.q.w']), p['full.q_norm'], sz.eps)
    k = rms_norm(proj(p['full.k.w']), p['full.k_norm'], sz.eps)
    q, k, v = (a.reshape(b, t, sz.heads, hd)
               for a in (q, k, proj(p['full.v.w'])))
    rows = QUERY_ROWS if t % QUERY_ROWS == 0 else t

    def block_of_rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = product('bqhd,bkhd->bhqk', qb, k, quant) / math.sqrt(hd)
        sees = (i * rows + jnp.arange(rows))[:, None] >= jnp.arange(t)[None]
        a = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), -1)
        return product('bhqk,bkhd->bqhd', a, v, quant)

    o = jax.lax.map(block_of_rows, jnp.arange(t // rows))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, d)       # [blocks, B, rows, ..]
    return product('btd,df->btf', o, p['full.o.w'], quant)


def block(x, p, kind, sz, quant='none'):
    """One block. x [B, T, d] float32; p: the layer's leaves in float32."""
    mixer = linear_mixer if kind == LINEAR else full_mixer
    h = x + rms_norm(mixer(x, p, sz, quant), p['mixer_norm'], sz.eps)
    u = jax.nn.silu(product('btd,df->btf', h, p['gate.w'], quant)) \
        * product('btd,df->btf', h, p['up.w'], quant)
    return h + rms_norm(product('btf,fd->btd', u, p['down.w'], quant),
                        p['mlp_norm'], sz.eps)


@partial(jax.jit, static_argnames=('kind', 'sz', 'quant'))
def _block_fwd(x, shared, own, layer, nth, kind, sz, quant):
    """Block `layer` of the model, the `nth` of its kind: its float32
    leaves out of the arrays stacked over all layers (`shared`) and over
    the layers of its kind (`own`). One compile per kind and shape."""
    take = lambda tree, i: {
        k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False).astype(F32)
        for k, v in tree.items()}
    return block(x, dict(take(shared, layer), **take(own, nth)), kind, sz,
                 quant)


@partial(jax.jit, static_argnames=('eps', 'quant'))
def _logits(x, norm_f, head, eps, quant):
    return product('...d,dv->...v', rms_norm(x, norm_f.astype(F32), eps),
                   head.astype(F32), quant)


@jax.jit
def _embed(embed, ids):
    return embed.astype(F32)[ids]


def forward_hidden(stacked, m, ids, quant='none'):
    """[B, T] ids -> [B, T, d] float32, the input of the final norm,
    layer by layer."""
    table, sz = leaf_table(m), sizes(m)
    over = lambda where: {k: v for k, v in stacked.items()
                          if table[k][0] == where}
    shared, own = over('all'), {LINEAR: over(LINEAR), FULL: over(FULL)}
    seen = {LINEAR: 0, FULL: 0}
    x = _embed(stacked['embed'], ids)
    for layer, kind in enumerate(m['layer_types']):
        x = _block_fwd(x, shared, own[kind], layer, seen[kind], kind, sz,
                       quant)
        seen[kind] += 1
    return x


def forward_logits(stacked, m, ids, quant='none'):
    """[B, T] ids -> [B, T, vocab] float32 logits."""
    return _logits(forward_hidden(stacked, m, ids, quant),
                   stacked['norm_f'], stacked['head'],
                   float(m['rms_norm_eps']), quant)


@jax.jit
def _gaps(logits, nxt, alt):
    best = jnp.max(logits, axis=-1)
    pick = lambda t: jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
    return best - pick(nxt), best - pick(alt)


PAD_TO = 512         # sequences are padded to a multiple: few shapes
HEAD_ROWS = 128      # ... and so are the rows the head is applied to


def served_gaps(stacked, m, sequences, quant_control=None):
    """`benchlib.reference.served_gaps`'s numbers for this family: for
    each (prompt, served tokens) pair the gaps, one per served token, by
    which the served token's reference logit lies below the reference's
    best at that position; with `quant_control` also the gaps of the
    token the lower-precision forward of the same ids puts first there.
    One sequence at a time, padded to a multiple of PAD_TO (causal:
    padding after the real tokens changes nothing before it), and the
    head is applied at the served positions only — [T, vocab] float32 at
    T = 6.5 k would be 2.6 GB beside 8 GB of weights."""
    eps = float(m['rms_norm_eps'])
    gaps, cgaps = [], [] if quant_control else None
    for prompt, toks in sequences:
        full = list(prompt) + list(toks)
        lo, n = len(prompt) - 1, len(toks)
        ids = np.zeros((1, -(-len(full) // PAD_TO) * PAD_TO), np.int32)
        ids[0, :len(full)] = full
        # the rows that predict the served tokens, padded with the last
        rows = np.minimum(lo + np.arange(-(-n // HEAD_ROWS) * HEAD_ROWS),
                          lo + n - 1)
        at = lambda quant: _logits(
            forward_hidden(stacked, m, jnp.asarray(ids), quant)[0][rows],
            stacked['norm_f'], stacked['head'], eps, quant)
        nxt = jnp.asarray(ids[0][rows + 1])
        alt = jnp.argmax(at(quant_control), axis=-1) if quant_control \
            else nxt
        g, cg = jax.device_get(_gaps(at('none'), nxt, alt))
        gaps.append(np.asarray(g[:n], np.float64))
        if quant_control:
            cgaps.append(np.asarray(cg[:n], np.float64))
    return gaps, cgaps
