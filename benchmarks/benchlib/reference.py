"""The plain reference of the GPT-2 family: forward, loss, gradients and
the AdamW step in straightforward float32 `jax.numpy` — no kernels, no
cache, no batching tricks. It imports nothing of the program and takes
the benchmark's own seed-made weights (`weights.py`, stacked form).

It follows the published description (Radford et al. 2019 and the
`openai-community/gpt2*` `config.json`): learned token and position
embeddings, pre-LayerNorm blocks, causal multi-head attention with
scores scaled by 1/sqrt(head size), a `gelu_new` (tanh) MLP of four
times the width, a final LayerNorm, and the token embedding tied as the
output head. Departures, each for a stated reason:

  * dropout is absent (the configurations run with dropout 0);
  * the loss takes `labels` as given and does not shift them: the
    benchmark's feed already holds the next token of every position;
  * parameters are STORED in the type the configuration states (bf16)
    and every operation on them is float32: the AdamW step computes in
    float32 from the stored value and rounds the result once, to the
    stored type. A float32 master copy is not part of the configuration;
  * it runs layer by layer and in blocks of rows so that the real
    widths fit one chip beside nothing else.

Every matrix product goes through `product`, which the CONTROL (`fp8`)
computes as a float8 path would: the same mathematics with both operands
of every product rounded to e4m3 (one scale per tensor), float32
accumulation, and in the backward pass the cotangent rounded to e5m2 —
the nearest precision below the bf16 the configurations state.

Call everything here under `jax.default_matmul_precision('highest')`
(`highest()` below): on a TPU a float32 product is otherwise computed
in bf16 passes.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

F32 = jnp.float32


def highest():
    return jax.default_matmul_precision('highest')


def _scaled_cast(x, dtype, top):
    """Round to a float8 type with one scale per tensor, back to float32."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


def e4m3(x):
    return _scaled_cast(x, jnp.float8_e4m3fn, 448.0)


def e5m2(x):
    return _scaled_cast(x, jnp.float8_e5m2, 57344.0)


def bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


# precision -> (rounding of a product's operands, rounding of the
# cotangent that enters the two products of its backward pass)
ROUNDING = {'fp8': (e4m3, e5m2), 'bf16': (bf16, bf16)}


def product(spec, a, b, quant='none'):
    """`einsum(spec, a, b)` in float32 — or, for the control, as a
    lower-precision path computes it: both operands rounded going in,
    float32 accumulation, and in the backward pass the saved operands are
    the rounded ones and the incoming cotangent is rounded too (float8
    training's e4m3 forward / e5m2 gradient recipe)."""
    if quant == 'none':
        return jnp.einsum(spec, a, b)
    fwd_round, bwd_round = ROUNDING[quant]

    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, fwd_round(a), fwd_round(b))

    def f_fwd(a, b):
        qa, qb = fwd_round(a), fwd_round(b)
        return jnp.einsum(spec, qa, qb), (qa, qb)

    def f_bwd(saved, dy):
        _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y), *saved)
        return vjp(bwd_round(dy))

    f.defvjp(f_fwd, f_bwd)
    return f(a, b)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head, eps, quant='none'):
    """One transformer block. x [B, T, d] float32; p: the layer's leaves
    in float32, keyed by the stacked kind names."""
    b, t, d = x.shape
    hd = d // n_head
    h = layer_norm(x, p['ln_1.w'], p['ln_1.b'], eps)
    qkv = product('btd,df->btf', h, p['qkv.w'], quant) + p['qkv.b']
    qh, kh, vh = (qkv[..., i * d:(i + 1) * d].reshape(b, t, n_head, hd)
                  for i in range(3))
    s = product('bthd,bshd->bhts', qh, kh, quant) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = product('bhts,bshd->bthd', a, vh, quant).reshape(b, t, d)
    x = x + product('btd,df->btf', o, p['out.w'], quant) + p['out.b']
    h = layer_norm(x, p['ln_2.w'], p['ln_2.b'], eps)
    u = gelu_new(product('btd,df->btf', h, p['fc_in.w'], quant)
                 + p['fc_in.b'])
    return x + product('btf,fd->btd', u, p['fc_out.w'], quant) + p['fc_out.b']


def layer_of(stacked, layer):
    """The float32 leaves of one layer out of the stacked weights."""
    return {k: jax.lax.dynamic_index_in_dim(stacked[k], layer, 0,
                                            keepdims=False).astype(F32)
            for k, _, _, _ in W.LAYER_KINDS}


def embed(wte, wpe, ids):
    t = ids.shape[1]
    return wte.astype(F32)[ids] + wpe.astype(F32)[:t][None]


def head_logits(x, lnw, lnb, wte, eps, quant='none'):
    h = layer_norm(x, lnw.astype(F32), lnb.astype(F32), eps)
    return product('...d,vd->...v', h, wte.astype(F32), quant)


def token_loss_sum(logits, labels):
    """Sum over positions of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


# ---- jitted pieces, one compile per shape ---------------------------------

@partial(jax.jit, static_argnames=('n_head', 'eps', 'quant'))
def _block_fwd(x, stacked, layer, n_head, eps, quant):
    return block(x, layer_of(stacked, layer), n_head, eps, quant)


@partial(jax.jit, static_argnames=('eps', 'quant'))
def _logits(x, stacked, eps, quant):
    return head_logits(x, stacked['ln_f.w'], stacked['ln_f.b'],
                       stacked['wte'], eps, quant)


@jax.jit
def _embed(stacked, ids):
    return embed(stacked['wte'], stacked['wpe'], ids)


def forward_logits(stacked, m, ids, quant='none'):
    """[B, T] ids -> [B, T, vocab] float32 logits, layer by layer."""
    x = _embed(stacked, ids)
    for layer in range(m['n_layer']):
        x = _block_fwd(x, stacked, layer, m['n_head'],
                       m['layer_norm_epsilon'], quant)
    return _logits(x, stacked, m['layer_norm_epsilon'], quant)


@jax.jit
def _gap_rows(logits, nxt, alt):
    """Per position: reference's best logit minus the reference's logit
    of token `nxt` (served) and of token `alt` (another path's pick)."""
    best = jnp.max(logits, axis=-1)
    pick = lambda t: jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
    return best - pick(nxt), best - pick(alt)


def served_gaps(stacked, m, sequences, quant_control=None, rows=4):
    """For each (prompt, served tokens) pair: the gaps, one per served
    token, by which the served token's reference logit lies below the
    reference's best at that position. With `quant_control`, also the
    gaps of the token that the lower-precision forward of the same
    prompts and tokens puts first at each of those positions.

    Sequences are padded to `n_positions` (causal attention: padding
    after the real tokens changes nothing before it) and run `rows` at a
    time. Returns (list of np arrays, list of np arrays or None)."""
    t_max = m['n_positions']
    gaps, cgaps = [], [] if quant_control else None
    for r0 in range(0, len(sequences), rows):
        part = sequences[r0:r0 + rows]
        ids = np.zeros((len(part), t_max), np.int32)
        for i, (prompt, toks) in enumerate(part):
            full = list(prompt) + list(toks)
            ids[i, :len(full)] = full
        nxt = np.roll(ids, -1, axis=1)
        logits = forward_logits(stacked, m, jnp.asarray(ids))
        if quant_control:
            alt = jnp.argmax(forward_logits(
                stacked, m, jnp.asarray(ids), quant_control), axis=-1)
        else:
            alt = jnp.asarray(nxt)
        g, cg = jax.device_get(_gap_rows(logits, jnp.asarray(nxt), alt))
        del logits
        for i, (prompt, toks) in enumerate(part):
            lo, hi = len(prompt) - 1, len(prompt) - 1 + len(toks)
            gaps.append(np.asarray(g[i, lo:hi], np.float64))
            if quant_control:
                cgaps.append(np.asarray(cg[i, lo:hi], np.float64))
    return gaps, cgaps


# ---- training: loss, gradients layer by layer, AdamW ----------------------

@partial(jax.jit, static_argnames=('eps', 'quant'))
def _head_loss_grad(x, stacked, labels, denom, eps, quant):
    def f(x, lnw, lnb, wte):
        lg = head_logits(x, lnw, lnb, wte, eps, quant)
        return token_loss_sum(lg, labels) / denom
    loss, (dx, dlnw, dlnb, dwte) = jax.value_and_grad(f, (0, 1, 2, 3))(
        x, stacked['ln_f.w'].astype(F32), stacked['ln_f.b'].astype(F32),
        stacked['wte'].astype(F32))
    return loss, dx, dlnw, dlnb, dwte


@partial(jax.jit, static_argnames=('n_head', 'eps', 'quant'),
         donate_argnums=(4,))
def _block_bwd_acc(x, stacked, layer, dy, grads, n_head, eps, quant):
    p = layer_of(stacked, layer)
    _, vjp = jax.vjp(
        lambda x, p: block(x, p, n_head, eps, quant), x, p)
    dx, dp = vjp(dy)
    grads = dict(grads)
    for k in dp:
        grads[k] = grads[k].at[layer].add(dp[k])
    return dx, grads


@partial(jax.jit, donate_argnums=(0,))
def _top_acc(grads, ids, dx0, dlnw, dlnb, dwte):
    grads = dict(grads)
    t = ids.shape[1]
    grads['wte'] = (grads['wte'] + dwte).at[ids.reshape(-1)].add(
        dx0.reshape(-1, dx0.shape[-1]))
    grads['wpe'] = grads['wpe'].at[:t].add(jnp.sum(dx0, axis=0))
    grads['ln_f.w'] = grads['ln_f.w'] + dlnw
    grads['ln_f.b'] = grads['ln_f.b'] + dlnb
    return grads


@partial(jax.jit, static_argnames=('hyper',), donate_argnums=(0, 1, 2))
def _adamw(params, mom1, mom2, grads, t, hyper):
    lr, b1, b2, eps, wd = hyper
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        p32 = p.astype(F32) * (1.0 - lr * wd)
        m1 = b1 * mom1[k] + (1.0 - b1) * g
        m2 = b2 * mom2[k] + (1.0 - b2) * g * g
        mhat = m1 / (1.0 - b1 ** t)
        vhat = m2 / (1.0 - b2 ** t)
        new_p[k] = (p32 - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype)
        new_m[k], new_v[k] = m1, m2
    return new_p, new_m, new_v


SPLIT = {'qkv.w': ('qkv.w/q', 'qkv.w/k', 'qkv.w/v'),
         'qkv.b': ('qkv.b/q', 'qkv.b/k', 'qkv.b/v')}


def comparison_leaves(kind, arr):
    """The leaves the comparison sees of one stacked kind. The packed
    query/key/value projection is three parameters of the published
    model held in one array: each third is a leaf of its own (a key's
    bias has no gradient under softmax; its packed neighbours do)."""
    if kind not in SPLIT:
        return {kind: arr}
    d = arr.shape[-1] // 3
    return {name: arr[..., i * d:(i + 1) * d]
            for i, name in enumerate(SPLIT[kind])}


@jax.jit
def leaf_norms(tree):
    """Per-leaf L2 norms of a stacked tree: {leaf: scalar or [L]}."""
    out = {}
    for kind, whole in tree.items():
        for name, v in comparison_leaves(kind, whole).items():
            v = v.astype(F32)
            axes = tuple(range(1, v.ndim)) if kind not in W.TOP_KINDS \
                else None
            out[name] = jnp.sqrt(jnp.sum(v * v, axis=axes))
    return out


@jax.jit
def change_norms(after, before):
    return leaf_norms({k: after[k].astype(F32) - before[k].astype(F32)
                       for k in after})


class TrainReference:
    """AdamW training of the plain model, one step per `step()` call,
    rows in blocks of `micro_rows`, gradients layer by layer.

    `hyper` = (lr, beta1, beta2, epsilon, weight_decay): decoupled decay
    on every leaf, bias-corrected moments, as the configuration states.
    `quant` makes it the control (see `product`)."""

    def __init__(self, m, stacked, hyper, micro_rows=2, quant='none'):
        self.m = m
        self.params = stacked
        self.hyper = tuple(float(h) for h in hyper)
        self.micro = int(micro_rows)
        self.quant = quant
        zeros = lambda: {k: jnp.zeros(v.shape, F32)
                         for k, v in stacked.items()}
        self.mom1, self.mom2 = zeros(), zeros()
        self.t = 0
        self.first_grad_norms = None

    def gradients(self, ids, labels):
        """(loss, grads): mean token loss over all rows, float32 grads."""
        m, q = self.m, self.quant
        eps, nh = m['layer_norm_epsilon'], m['n_head']
        ids, labels = np.asarray(ids), np.asarray(labels)
        denom = float(ids.shape[0] * ids.shape[1])
        grads = {k: jnp.zeros(v.shape, F32) for k, v in self.params.items()}
        loss = 0.0
        for r0 in range(0, ids.shape[0], self.micro):
            mi = jnp.asarray(ids[r0:r0 + self.micro])
            ml = jnp.asarray(labels[r0:r0 + self.micro])
            xs = [_embed(self.params, mi)]
            for layer in range(m['n_layer']):
                xs.append(_block_fwd(xs[-1], self.params, layer, nh, eps, q))
            part, dx, dlnw, dlnb, dwte = _head_loss_grad(
                xs.pop(), self.params, ml, denom, eps, q)
            loss += float(part)
            for layer in reversed(range(m['n_layer'])):
                dx, grads = _block_bwd_acc(xs.pop(), self.params, layer, dx,
                                           grads, nh, eps, q)
            grads = _top_acc(grads, mi, dx, dlnw, dlnb, dwte)
        return loss, grads

    def step(self, ids, labels):
        loss, grads = self.gradients(ids, labels)
        if self.first_grad_norms is None:
            self.first_grad_norms = jax.device_get(leaf_norms(grads))
        self.t += 1
        self.params, self.mom1, self.mom2 = _adamw(
            self.params, self.mom1, self.mom2, grads, float(self.t),
            self.hyper)
        return loss
