"""The Kimi-Linear family (`"family": "kimi_linear"` in a configuration
file): what the serving driver, the readers and `tools/control.py` need
of one architecture and find by that name. `m` is the model section of a
configuration file, in the key names of the published `config.json`
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct), with
this chip's share beside them: `num_experts` counts the experts HELD,
`experts_held` = [lo, hi) says which of the `num_experts_published` they
are (the router keeps that many outputs), and `vocab_size` is the slice
of the vocabulary held. It serves only: there is no `TrainReference`.

  weights    make_stacked, program_leaves, leaf_table
  the model  build_model (the program's own class at these sizes)
  counts     n_params, weight_bytes, kv_bytes_per_token,
             state_bytes_per_resident, serve_flops_token,
             decode_step_least_seconds, prefill_call_least_seconds
  reference  forward_logits, served_gaps, and the layers one by one
             (kda_mixer, mla_mixer, expert_layer, block)

**The architecture** (x the block input of width d; no projection has a
bias). Block, pre-norm (assumed: the DeepSeek-V3 lineage's): h = x +
mixer(RMSNorm(x)); out = h + ffn(RMSNorm(h)); final RMSNorm; untied
head. Layer i (1-based) is KDA or MLA by `linear_attn_config.kda_layers`
/ `full_attn_layers`; layer i <= `first_k_dense_replace` has the dense
SwiGLU of `intermediate_size`, every other the expert layer.

KDA layer (Kimi Delta Attention, arXiv:2510.26692; H heads, dk = dv =
`linear_attn_config.head_dim`): [q; k; v] = SiLU(conv(W_{q,k,v} x)), a
causal depthwise convolution of width `short_conv_kernel_size` (c_t =
sum_j w[j] u_{t-K+1+j}, zeros before the first token); per head q <- q /
|q| * dk^-1/2, k <- k / |k| with |x| = sqrt(sum x^2 + 1e-6); beta_t =
sigmoid(w_b . x_t) per head; per-CHANNEL alpha_t = exp(-exp(A_log_h)
softplus(W_f2 W_f1 x_t + dt_bias)) in R^{H x dk}; state S [dk, dv],
float32, zero before the first token:

    S'  = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    y_t = W_o [RMSNorm_dv(o_t) * sigmoid(W_g2 W_g1 x_t)]

MLA layer (`q_lora_rank` null; `mla_use_nope` true, read as: the
`qk_rope_head_dim` channels exist and are not rotated): q = W_q x, H
heads of nope + rope; [c; k_pe] = W_kva x, c <- RMSNorm(c); [k_nope; v]
= W_kvb c per head; k = [k_nope; k_pe], k_pe shared by the heads; causal
softmax(q k^T (nope + rope)^-1/2) v; W_o.

Expert layer (DeepSeek-V3's gate): s = sigmoid(W_r x) in float32 over
all `num_experts_published`; the `num_experts_per_token` largest of s +
e_score_correction_bias are chosen; weights s at the chosen, divided by
their sum, times `routed_scaling_factor`; y = shared(x) + sum over the
chosen experts THAT ARE HELD of w_e E_e(x), E and shared SwiGLU of
`moe_intermediate_size`. What the experts held elsewhere would add is
left out, here and in the program alike.

**Weights** are made on the device from the seed, one jitted call per
leaf (a layer's 64 experts are one leaf of 151 M values; drawn kind by
kind over the layers their float32 draws would not fit), in the type
they are served in, the router and its correction in float32. They are
kept leaf by leaf under the program's own names: `make_stacked` stacks
nothing, and the reference streams a layer's leaves into float32 as it
reaches the layer, an expert at a time, so that the 4.27 B parameters
never stand in float32 at once. Matrices and tables N(0,
initializer_range); norm weights 1 + N(0, 0.02); what else the published
config leaves open is in the configuration files under `assumed`.

**The plain reference**: float32 `jax.numpy`, every projection, both
attention products and every expert's products through
`benchlib.reference.product` (which the control rounds to float8); KDA
is the literal per-token recurrence above in a `lax.scan`; MLA expands K
and V for every token (no latent trick) and takes a masked softmax in
blocks of query rows; every token goes through each held expert it
chose by a plain loop over the held experts; layer by layer, one
sequence at a time. The router and the recurrence's own contractions are
float32 in the configuration and the control leaves them so. It imports
nothing of the program. Call it under `benchlib.reference.highest()`.
"""
import collections
import importlib.util
import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import reference as R
from benchlib.weights import load_leaves, seed_key

F32 = jnp.float32
product = R.product
KDA, MLA = 'kda', 'mla'

# a tree whose program has no such model cannot run this family: say so
# when the family is loaded, before 8.5 GB of weights are drawn for it
PROGRAM = 'paddle_tpu.text.models.kimi_linear'
if importlib.util.find_spec(PROGRAM) is None:
    raise SystemExit('the program has no %s: this tree cannot run the '
                     'kimi_linear family' % PROGRAM)

# assumed (the published config does not fix them; the configuration
# files say why): the convolution's taps at the scale of a default
# depthwise width-4 convolution; alpha at a zero gate projection
# log-uniform over (0.5, 0.999), channel by channel; the correction the
# router adds before it chooses, small but not absent
CONV_STD = 0.3
ALPHA_SPAN = (0.5, 0.999)
NORM_STD = 0.02
BIAS_STD = 0.01
# the latent's columns of `kv_a_proj` are drawn at a quarter of the other
# matrices' scale: a mixer's input has rms 1 (pre-norm), so at the same
# scale c would leave the projection with rms 1 already and its RMSNorm
# would change nothing that a comparison could see
LATENT_SHARE = 0.25


# ---- shapes ---------------------------------------------------------------

def layer_kinds(m):
    """KDA | MLA for each layer, from the two 1-based lists."""
    lac = m['linear_attn_config']
    kinds = {i: KDA for i in lac['kda_layers']}
    kinds.update({i: MLA for i in lac['full_attn_layers']})
    return [kinds[i + 1] for i in range(m['num_hidden_layers'])]


def is_sparse(m, layer):
    return layer >= m['first_k_dense_replace']


def held(m):
    """(lo, hi): the expert ids this chip holds."""
    lo, hi = m.get('experts_held', (0, m['num_experts']))
    if hi - lo != m['num_experts']:
        raise ValueError('experts_held %r is not num_experts = %d wide'
                         % ((lo, hi), m['num_experts']))
    return int(lo), int(hi)


def published_experts(m):
    return int(m.get('num_experts_published', m['num_experts']))


def leaf_table(m):
    """{program leaf name: (shape, draw rule)}, in the program's order of
    layers."""
    d, lac = m['hidden_size'], m['linear_attn_config']
    h, dk, kern = lac['num_heads'], lac['head_dim'], \
        lac['short_conv_kernel_size']
    heads, nope, rope, dv, lora = (
        m['num_attention_heads'], m['qk_nope_head_dim'],
        m['qk_rope_head_dim'], m['v_head_dim'], m['kv_lora_rank'])
    f, fe, n_held = (m['intermediate_size'], m['moe_intermediate_size'],
                     m['num_experts'])
    experts = published_experts(m)
    out = {'model.embed_tokens.weight': ((m['vocab_size'], d), 'plain'),
           'model.norm.weight': ((d,), 'norm'),
           'lm_head.weight': ((d, m['vocab_size']), 'plain')}
    mixers = {
        KDA: {'q_proj.weight': ((d, h * dk), 'plain'),
              'k_proj.weight': ((d, h * dk), 'plain'),
              'v_proj.weight': ((d, h * dk), 'plain'),
              'b_proj.weight': ((d, h), 'plain'),
              'f_a_proj.weight': ((d, dk), 'plain'),
              'f_b_proj.weight': ((dk, h * dk), 'plain'),
              'g_a_proj.weight': ((d, dk), 'plain'),
              'g_b_proj.weight': ((dk, h * dk), 'plain'),
              'o_proj.weight': ((h * dk, d), 'plain'),
              'conv_weight': ((kern, 3 * h * dk), 'conv'),
              'A_log': ((h,), 'a_log'),
              'dt_bias': ((h * dk,), 'dt_bias'),
              'o_norm.weight': ((dk,), 'norm')},
        MLA: {'q_proj.weight': ((d, heads * (nope + rope)), 'plain'),
              'kv_a_proj.weight': ((d, lora + rope), 'latent:%d' % lora),
              'kv_a_norm.weight': ((lora,), 'norm'),
              'kv_b_proj.weight': ((lora, heads * (nope + dv)), 'plain'),
              'o_proj.weight': ((heads * dv, d), 'plain')}}
    swiglu = lambda pre, width: {
        pre + 'gate_proj.weight': ((d, width), 'plain'),
        pre + 'up_proj.weight': ((d, width), 'plain'),
        pre + 'down_proj.weight': ((width, d), 'plain')}
    sparse = dict(swiglu('shared.', fe * m['num_shared_experts']), **{
        'router': ((d, experts), 'router'),
        'e_score_correction_bias': ((experts,), 'bias'),
        'gate_proj': ((n_held, fe, d), 'plain'),
        'up_proj': ((n_held, fe, d), 'plain'),
        'down_proj': ((n_held, fe, d), 'plain')})
    for i, kind in enumerate(layer_kinds(m)):
        pre = 'model.layers.%d.' % i
        out[pre + 'mixer_norm.weight'] = ((d,), 'norm')
        out[pre + 'mlp_norm.weight'] = ((d,), 'norm')
        for name, spec in mixers[kind].items():
            out[pre + 'mixer.' + name] = spec
        ffn = sparse if is_sparse(m, i) else swiglu('', f)
        for name, spec in ffn.items():
            out[pre + 'mlp.' + name] = spec
    return out


# ---- weights from the seed ------------------------------------------------

@partial(jax.jit, static_argnames=('shape', 'rule', 'std', 'dtype'))
def _draw(key, shape, rule, std, dtype):
    """`rule` 'latent:<n>': a matrix whose first n columns (the latent's)
    are drawn at LATENT_SHARE of `std`."""
    rule, _, lead = rule.partition(':')
    scale, mean = {'plain': (std, 0.0), 'router': (std, 0.0),
                   'latent': (std, 0.0),
                   'norm': (NORM_STD, 1.0), 'conv': (CONV_STD, 0.0),
                   'a_log': (0.5, 0.0), 'bias': (BIAS_STD, 0.0)}[rule]
    x = jax.random.normal(key, shape, F32) * scale + mean
    if lead:
        x = x.at[..., :int(lead)].multiply(LATENT_SHARE)
    return x.astype(dtype)


@partial(jax.jit, static_argnames=('n', 'dtype'))
def _dt_bias(key, a_log, n, dtype):
    """dt_bias `[H dk]` such that alpha at a zero gate projection,
    exp(-exp(A_log_h) softplus(dt_bias)), lies log-uniformly inside
    ALPHA_SPAN on every channel: with `A_log` as stored (rounded), so
    that the span holds in the type served."""
    lo, hi = (math.log(-math.log(a)) for a in reversed(ALPHA_SPAN))
    h = a_log.shape[0]
    rate = jnp.exp(lo + jax.random.uniform(key, (h, n // h), F32) * (hi - lo))
    return jnp.log(jnp.expm1(rate / jnp.exp(a_log.astype(F32))[:, None])) \
        .reshape(n).astype(dtype)


def make_stacked(m, seed, dtype):
    """{program leaf name: array} of model section `m` from `seed`, on
    the default device, in `dtype` (a jnp dtype name); the router and its
    correction in float32 whatever `dtype` is."""
    std, dtype = float(m['initializer_range']), jnp.dtype(dtype).name
    root, out = seed_key(seed), {}
    for name, (shape, rule) in leaf_table(m).items():
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        kept = 'float32' if rule in ('router', 'bias') else dtype
        if rule == 'dt_bias':
            out[name] = _dt_bias(
                key, out[name.replace('dt_bias', 'A_log')], shape[0], kept)
        else:
            out[name] = _draw(key, shape, rule, std, kept)
    return out


def program_leaves(stacked):
    """{program leaf name: array}: the leaves are kept under the
    program's names already. It CONSUMES `stacked`, as every family's
    does."""
    out = dict(stacked)
    stacked.clear()
    return out


# ---- the program's model --------------------------------------------------

PUBLISHED_KEYS = (
    'vocab_size', 'hidden_size', 'intermediate_size', 'num_hidden_layers',
    'num_attention_heads', 'num_key_value_heads', 'head_dim', 'hidden_act',
    'rms_norm_eps', 'tie_word_embeddings', 'model_max_length',
    'linear_attn_config', 'kv_lora_rank', 'q_lora_rank', 'qk_nope_head_dim',
    'qk_rope_head_dim', 'v_head_dim', 'mla_use_nope', 'rope_theta',
    'rope_scaling', 'first_k_dense_replace', 'moe_layer_freq',
    'moe_intermediate_size', 'num_experts_per_token', 'num_shared_experts',
    'moe_renormalize', 'moe_router_activation_func', 'routed_scaling_factor',
    'num_expert_group', 'topk_group', 'use_grouped_topk',
    'num_nextn_predict_layers', 'model_type', 'initializer_range')


def build_model(m, dtype, leaves):
    """The program's KimiLinearForCausalLM at the configuration's sizes
    holding the benchmark's weights (`dtype` is theirs already): the
    router of the published width, told which experts it holds. Built
    without an initialisation of its own (`nn.skip_init`): 8.5 GB of
    leaves and as much again in initial values do not fit one chip."""
    from paddle_tpu import nn
    from paddle_tpu.text.models import (KimiLinearConfig,
                                        KimiLinearForCausalLM)
    with nn.skip_init():
        model = KimiLinearForCausalLM(KimiLinearConfig(
            num_experts=published_experts(m), experts_held=held(m),
            **{k: m[k] for k in PUBLISHED_KEYS}))
    load_leaves(model, leaves)
    return model


# ---- counts from shapes ---------------------------------------------------

Counts = collections.namedtuple(
    'Counts', 'n_params fixed_params f32_params expert_params n_sparse '
    'fixed_matmul pairs_held share latent_elems absorbed_row expanded_row '
    'expand_row state_f32 tail_elems rule_flops')
_COUNTS = {}


def counts(m):
    """The counts of model section `m`, computed once per section: the
    serving driver asks for a token's operations at every position of
    every prompt, between engine steps, with the device idle. (A section
    is not edited after it has been read.)

    fixed_*: everything outside the routed experts (a token multiplies
    all of it: mixers, the shared expert, the router, the dense layers,
    the head's slice; the embedding is a read). pairs_held: the (token,
    expert) pairs a token sends to THIS chip in expectation,
    experts-per-token times the share of the experts held; the program's
    counter `moe_pairs_held` gives what the routing really sent."""
    hit = _COUNTS.get(id(m))
    if hit is not None and hit[0] is m:
        return hit[1]
    d, lac = m['hidden_size'], m['linear_attn_config']
    h, dk, kern = lac['num_heads'], lac['head_dim'], \
        lac['short_conv_kernel_size']
    heads, nope, rope, dv, lora = (
        m['num_attention_heads'], m['qk_nope_head_dim'],
        m['qk_rope_head_dim'], m['v_head_dim'], m['kv_lora_rank'])
    table = leaf_table(m)
    size = lambda name: int(np.prod(table[name][0]))
    routed = [n for n in table
              if n.endswith(('mlp.gate_proj', 'mlp.up_proj', 'mlp.down_proj'))]
    f32 = [n for n, (_, rule) in table.items() if rule in ('router', 'bias')]
    n_params = sum(size(n) for n in table)
    n_routed = sum(size(n) for n in routed)
    vectors = [n for n, (shape, rule) in table.items()
               if rule in ('norm', 'conv', 'a_log', 'dt_bias', 'bias')]
    kinds = layer_kinds(m)
    n_kda, n_mla = kinds.count(KDA), kinds.count(MLA)
    n_sparse = sum(is_sparse(m, i) for i in range(len(kinds)))
    expert_params = 3 * d * m['moe_intermediate_size']
    c = Counts(
        n_params=n_params, fixed_params=n_params - n_routed,
        f32_params=sum(size(n) for n in f32),
        expert_params=expert_params, n_sparse=n_sparse,
        fixed_matmul=n_params - n_routed - sum(size(n) for n in vectors)
        - size('model.embed_tokens.weight'),
        pairs_held=m['num_experts_per_token'] * m['num_experts']
        / published_experts(m),
        share=m['num_experts_per_token'] / published_experts(m),
        latent_elems=n_mla * (lora + rope),
        absorbed_row=n_mla * 2 * heads * (2 * lora + rope),
        expanded_row=n_mla * 2 * heads * (nope + rope + dv),
        expand_row=n_mla * 2 * lora * heads * (nope + dv),
        state_f32=n_kda * h * dk * dk,
        tail_elems=n_kda * (kern - 1) * 3 * h * dk,
        rule_flops=n_kda * 3 * 2 * h * dk * dk)
    _COUNTS[id(m)] = (m, c)
    return c


def n_params(m):
    """Parameters held on this chip."""
    return counts(m).n_params


def _fixed_bytes(c, itemsize):
    """Bytes of the weights outside the routed experts: the router and
    its correction are float32 whatever the rest is."""
    return c.fixed_params * itemsize + c.f32_params * (4 - itemsize)


def weight_bytes(m, itemsize=2):
    c = counts(m)
    return _fixed_bytes(c, itemsize) \
        + c.n_sparse * m['num_experts'] * c.expert_params * itemsize


def kv_bytes_per_token(m, itemsize=2):
    """What one held token keeps: its latent row over the MLA layers."""
    return counts(m).latent_elems * itemsize


def state_bytes_per_resident(m, itemsize=2):
    """What one resident sequence keeps over the KDA layers, whatever
    its length: the float32 state S per head and the last K-1 inputs of
    the three convolutions."""
    c = counts(m)
    return c.state_f32 * 4 + c.tail_elems * itemsize


# (section, operations of a token that attends to nothing, operations a
# held row adds): `serve_flops_token` is linear in the context, and the
# serving driver calls it a thousand times between two engine steps
_LINEAR = [None, 0.0, 0]


def _linear(m):
    if _LINEAR[0] is not m:
        c = counts(m)
        _LINEAR[:] = [m, 2 * c.fixed_matmul
                      + 2 * c.n_sparse * c.pairs_held * c.expert_params
                      + c.rule_flops, c.absorbed_row]
    return _LINEAR


def serve_flops_token(m, context):
    """Forward of one token that attends to `context` held tokens, the
    work THIS chip must do: two operations per parameter outside the
    routed experts, the same for each (token, expert) pair that falls on
    an expert held here (in expectation: `counts`), attention in the
    latent over the context in the MLA layers (2 H (2 lora + rope) a
    held row and layer: the decode step's form, the cheaper one for
    fewer than about 170 queries a call), and the rule's three products
    (S^T k, the rank-one write, S^T q, each 2 H dk dv) in the KDA
    ones."""
    lin = _LINEAR if _LINEAR[0] is m else _linear(m)
    return lin[1] + lin[2] * context


def experts_touched(m, tokens):
    """Held experts of one layer that `tokens` tokens touch, in
    expectation: each is missed by a token with chance 1 - k / E."""
    return m['num_experts'] * (1.0 - (1.0 - counts(m).share) ** tokens)


def _least(flops, nbytes, peak_flops, peak_bw):
    tc, tb = flops / peak_flops, nbytes / peak_bw
    return max(tc, tb), ('compute' if tc >= tb else 'bandwidth')


def _step_bytes(m, tokens, rows_held, residents, itemsize):
    """Bytes a call of `tokens` tokens must move: the weights outside
    the routed experts once, each held expert's once if a token touches
    it, the latent rows held, each resident's state read and written."""
    c = counts(m)
    return _fixed_bytes(c, itemsize) \
        + c.n_sparse * experts_touched(m, tokens) * c.expert_params \
        * itemsize \
        + kv_bytes_per_token(m, itemsize) * rows_held \
        + 2 * state_bytes_per_resident(m, itemsize) * residents


def decode_step_least_seconds(m, contexts, peak_flops, peak_bw, itemsize=2):
    """Least time of ONE decode step for rows holding `contexts` tokens:
    max(FLOPs / peak, bytes / bandwidth). Returns (seconds, 'compute' |
    'bandwidth')."""
    n, rows = len(contexts), sum(contexts)
    _, token, row = _linear(m)
    nbytes = _step_bytes(m, n, rows, n, itemsize)
    return _least(n * token + row * rows, nbytes, peak_flops, peak_bw)


def prefill_call_least_seconds(m, start, valid, peak_flops, peak_bw,
                               itemsize=2):
    """Least time of one prefill call that takes `valid` tokens of a
    sequence that already holds `start`: K and V expanded once from the
    rows then held (2 lora H (nope + dv) a row and MLA layer) and each
    token's attention over the context it sees (2 H (nope + rope + dv) a
    row), beside everything else a token costs; or the bytes of
    `_step_bytes`, whichever takes longer."""
    c = counts(m)
    seen = sum(p + 1 for p in range(start, start + valid))
    flops = valid * serve_flops_token(m, 0) + c.expanded_row * seen \
        + c.expand_row * (start + valid)
    nbytes = _step_bytes(m, valid, start + valid, 1, itemsize)
    return _least(flops, nbytes, peak_flops, peak_bw)


# ---- the plain reference --------------------------------------------------

# the sizes the blocks read, hashable: a static argument of the jitted
# pieces
Sizes = collections.namedtuple(
    'Sizes', 'h dk kern heads nope rope dv lora eps top_k scale lo hi')


def sizes(m, experts_held=None):
    """`experts_held`: another share than the section's (the test that
    adds the shares up), or None."""
    lac = m['linear_attn_config']
    lo, hi = experts_held or held(m)
    return Sizes(lac['num_heads'], lac['head_dim'],
                 lac['short_conv_kernel_size'], m['num_attention_heads'],
                 m['qk_nope_head_dim'], m['qk_rope_head_dim'],
                 m['v_head_dim'], m['kv_lora_rank'],
                 float(m['rms_norm_eps']), m['num_experts_per_token'],
                 float(m['routed_scaling_factor']), lo, hi)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def kda_mixer(x, p, sz, quant='none'):
    """x [B, T, d] float32 -> [B, T, d]: the recurrence, token by token.
    `p`: the mixer's leaves by their names inside it."""
    b, t, _ = x.shape
    h, dk, kern = sz.h, sz.dk, sz.kern
    proj = lambda v, w: product('btd,df->btf', v, p[w + '.weight'], quant)
    u = jnp.concatenate([proj(x, 'q_proj'), proj(x, 'k_proj'),
                         proj(x, 'v_proj')], axis=-1)
    back = jnp.pad(u, [(0, 0), (kern - 1, 0), (0, 0)])
    c = jax.nn.silu(sum(p['conv_weight'][j] * back[:, j:j + t]
                        for j in range(kern))).reshape(b, t, 3, h, dk)
    q = l2_norm(c[:, :, 0]) * dk ** -0.5
    k, v = l2_norm(c[:, :, 1]), c[:, :, 2]
    beta = jax.nn.sigmoid(proj(x, 'b_proj'))                    # [B, T, H]
    gate = proj(proj(x, 'f_a_proj'), 'f_b_proj') + p['dt_bias']
    alpha = jnp.exp(-jnp.exp(p['A_log'])[:, None]
                    * jax.nn.softplus(gate).reshape(b, t, h, dk))

    def token(s, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs          # [B, H, *]
        s = alpha_t[..., None] * s                   # each key channel's row
        wrote = jnp.sum(s * k_t[..., None], axis=-2)             # S'^T k
        s = s + k_t[..., None] * (beta_t[..., None]
                                  * (v_t - wrote))[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)           # S^T q

    first = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((b, h, dk, dk), F32),
                        tuple(first(a) for a in (q, k, v, alpha, beta)))
    o = rms_norm(first(o), p['o_norm.weight'], sz.eps)
    out_gate = jax.nn.sigmoid(proj(proj(x, 'g_a_proj'), 'g_b_proj'))
    return proj((o * out_gate.reshape(b, t, h, dk)).reshape(b, t, h * dk),
                'o_proj')


QUERY_ROWS = 512     # attention's scores, [B, H, QUERY_ROWS, T] a block


def mla_mixer(x, p, sz, quant='none'):
    """x [B, T, d] float32 -> [B, T, d]: K and V expanded for every
    token, masked softmax attention, the queries in blocks of rows."""
    b, t, _ = x.shape
    heads, nope, rope, dv, lora = sz.heads, sz.nope, sz.rope, sz.dv, sz.lora
    proj = lambda v, w: product('btd,df->btf', v, p[w + '.weight'], quant)
    q = proj(x, 'q_proj').reshape(b, t, heads, nope + rope)
    kva = proj(x, 'kv_a_proj')
    c = rms_norm(kva[..., :lora], p['kv_a_norm.weight'], sz.eps)
    kv = proj(c, 'kv_b_proj').reshape(b, t, heads, nope + dv)
    k_pe = jnp.broadcast_to(kva[:, :, None, lora:], (b, t, heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    v = kv[..., nope:]
    rows = QUERY_ROWS if t % QUERY_ROWS == 0 else t

    def block_of_rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = product('bqhd,bkhd->bhqk', qb, k, quant) / math.sqrt(nope + rope)
        sees = (i * rows + jnp.arange(rows))[:, None] >= jnp.arange(t)[None]
        a = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), -1)
        return product('bhqk,bkhd->bqhd', a, v, quant)

    o = jax.lax.map(block_of_rows, jnp.arange(t // rows))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, heads * dv)
    return proj(o, 'o_proj')


def swiglu(x, p, pre, quant='none'):
    proj = lambda v, w: product('...d,df->...f', v, p[pre + w + '.weight'],
                                quant)
    return proj(jax.nn.silu(proj(x, 'gate_proj')) * proj(x, 'up_proj'),
                'down_proj')


def routing(x, p, sz):
    """[T, E] float32: each token's weight on each of ALL the experts, 0
    where it did not choose it."""
    s = jax.nn.sigmoid(jnp.einsum('td,de->te', x, p['router']))
    order = jnp.argsort(-(s + p['e_score_correction_bias']), axis=-1)
    chosen = jax.nn.one_hot(order[:, :sz.top_k], s.shape[-1],
                            dtype=F32).sum(axis=1)               # 0 | 1
    w = s * chosen
    return w / jnp.sum(w, axis=-1, keepdims=True) * sz.scale


def expert_layer(x, p, sz, quant='none', shared=True):
    """x [B, T, d] float32 -> [B, T, d]: the shared expert (left out
    with `shared=False`) + the chosen experts held here (`sz.lo` ..
    `sz.hi`: `p`'s routed leaves hold those, in that order), each by the
    loop's own pass over the tokens."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    w = routing(x, p, sz)[:, sz.lo:sz.hi]

    def one(y, xs):
        w_gate, w_up, w_down, w_e = xs
        a = jax.nn.silu(product('td,fd->tf', x, w_gate.astype(F32), quant)) \
            * product('td,fd->tf', x, w_up.astype(F32), quant)
        return y + w_e[:, None] * product('tf,fd->td', a,
                                          w_down.astype(F32), quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p['gate_proj'], p['up_proj'], p['down_proj'], w.T))
    if shared:
        y = y + swiglu(x, p, 'shared.', quant)
    return y.reshape(shape)


def block(x, p, kind, sparse, sz, quant='none'):
    """One block. x [B, T, d] float32; p: the layer's leaves by their
    names inside it, float32 but for the routed experts' (which
    `expert_layer` takes an expert at a time)."""
    mixer = kda_mixer if kind == KDA else mla_mixer
    sub = lambda pre: {k[len(pre):]: v for k, v in p.items()
                       if k.startswith(pre)}
    h = x + mixer(rms_norm(x, p['mixer_norm.weight'], sz.eps),
                  sub('mixer.'), sz, quant)
    u = rms_norm(h, p['mlp_norm.weight'], sz.eps)
    ffn = expert_layer(u, sub('mlp.'), sz, quant) if sparse \
        else swiglu(u, sub('mlp.'), '', quant)
    return h + ffn


ROUTED = ('mlp.gate_proj', 'mlp.up_proj', 'mlp.down_proj')


@partial(jax.jit, static_argnames=('kind', 'sparse', 'sz', 'quant'))
def _block_fwd(x, leaves, kind, sparse, sz, quant):
    """One compile per kind of block and shape: the layer's leaves come
    in the type they are stored in."""
    p = {k: v if k in ROUTED else v.astype(F32) for k, v in leaves.items()}
    return block(x, p, kind, sparse, sz, quant)


@partial(jax.jit, static_argnames=('eps', 'quant'))
def _logits(x, norm_f, head, eps, quant):
    return product('...d,dv->...v', rms_norm(x, norm_f.astype(F32), eps),
                   head.astype(F32), quant)


@jax.jit
def _embed(embed, ids):
    return embed.astype(F32)[ids]


def layer_leaves(stacked, layer):
    pre = 'model.layers.%d.' % layer
    return {k[len(pre):]: v for k, v in stacked.items() if k.startswith(pre)}


def forward_hidden(stacked, m, ids, quant='none'):
    """[B, T] ids -> [B, T, d] float32, the input of the final norm,
    layer by layer."""
    sz = sizes(m)
    x = _embed(stacked['model.embed_tokens.weight'], ids)
    for layer, kind in enumerate(layer_kinds(m)):
        x = _block_fwd(x, layer_leaves(stacked, layer), kind,
                       is_sparse(m, layer), sz, quant)
    return x


def forward_logits(stacked, m, ids, quant='none'):
    """[B, T] ids -> [B, T, vocab] float32 logits over the slice held."""
    return _logits(forward_hidden(stacked, m, ids, quant),
                   stacked['model.norm.weight'], stacked['lm_head.weight'],
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
    padding after the real tokens changes nothing before it), the head
    applied at the served positions only."""
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
            stacked['model.norm.weight'], stacked['lm_head.weight'], eps,
            quant)
        nxt = jnp.asarray(ids[0][rows + 1])
        alt = jnp.argmax(at(quant_control), axis=-1) if quant_control \
            else nxt
        g, cg = jax.device_get(_gaps(at('none'), nxt, alt))
        gaps.append(np.asarray(g[:n], np.float64))
        if quant_control:
            cgaps.append(np.asarray(cg[:n], np.float64))
    return gaps, cgaps
