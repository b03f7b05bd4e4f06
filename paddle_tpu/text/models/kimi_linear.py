"""Kimi-Linear: a decoder whose layers mix tokens in two ways — KDA
(Kimi Delta Attention: a gated delta rule whose decay is a vector over
the key channels of each head, with a fixed float32 state per sequence)
and MLA (latent attention: one row of `kv_lora_rank + qk_rope_head_dim`
values a token, shared by every head) — in the published 3:1 pattern,
and whose feed-forward is an expert layer after the leading dense ones.

Configuration keys are those of the published `config.json`
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct). With x
the block input of width d, no projection has a bias unless said:

Block (pre-norm, the DeepSeek-V3 lineage's; assumed): h = x +
mixer(RMSNorm(x)); out = h + ffn(RMSNorm(h)); final RMSNorm; untied
head. Layer i (1-based) is a KDA layer when `linear_attn_config.
kda_layers` lists it and an MLA layer when `full_attn_layers` does;
layer i <= `first_k_dense_replace` has the dense SwiGLU of
`intermediate_size`, every other the expert layer.

KDA layer (arXiv:2510.26692; H heads, dk = dv = `linear_attn_config.
head_dim`): [q; k; v] = SiLU(conv(W_{q,k,v} x)), a causal depthwise
convolution of width `short_conv_kernel_size` (c_t = sum_j w[j]
u_{t-K+1+j}, zeros before the first token); per head q <- q / |q| *
dk^-1/2, k <- k / |k| (|x| = sqrt(sum x^2 + 1e-6)); beta_t = sigmoid(w_b
. x_t) per head; the PER-CHANNEL log decay g_t = -exp(A_log_h) *
softplus(W_f2 W_f1 x_t + dt_bias) in R^{H x dk} (W_f1 d -> dk, W_f2 dk
-> H dk), alpha_t = exp(g_t). State S [dk, dv], float32, a head:

    S'  = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    y_t = W_o [RMSNorm_dv(o_t) * sigmoid(W_g2 W_g1 x_t)]

(W_g1 d -> dv, W_g2 dv -> H dv). A one-token call runs this recurrence
(`kda_step`); a longer one runs it chunk-parallel (`chunked_kda_rule`).

MLA layer (`q_lora_rank` null; `mla_use_nope` true: the
`qk_rope_head_dim` channels exist and are NOT rotated): q = W_q x, H
heads of `qk_nope_head_dim + qk_rope_head_dim`; [c; k_pe] = W_kva x
(`kv_lora_rank + qk_rope_head_dim`), c <- RMSNorm(c); [k_nope; v] =
W_kvb c, a head `qk_nope_head_dim + v_head_dim`; k = [k_nope; k_pe]
(k_pe shared by the heads); causal softmax(q k^T * (nope + rope)^-1/2)
v; W_o. Serving keeps [c; k_pe] a token (`PagedLatentSpec`) and a
one-token call attends IN the latent: q_nope W_kvb,k^T against c, the
weighted sum of c lifted by W_kvb,v afterwards; a longer call expands K
and V from the rows the sequence holds.

Expert layer (`text/models/moe.py`, DeepSeek-V3's gate, scopes
`kimi.moe.*`): the `num_experts_per_token` largest of sigmoid(W_r x) +
e_score_correction_bias over all `num_experts` are chosen, weighted by
their scores divided by their sum (`moe_renormalize`) times
`routed_scaling_factor`; E and shared SwiGLU of
`moe_intermediate_size`. `experts_held` = (lo, hi) says which expert ids
this program holds (all by default).
"""
import jax
import jax.numpy as jnp

from ... import nn
from ...framework.core import Tensor, is_grad_enabled, run_op
from ...nn import functional as F
from ...tensor import manipulation as M
from .cache import (PagedLatentCache, PagedLatentSpec, RecurrentCache,
                    RecurrentSpec, paged_latent_rows)
from .moe import SparseMoe, SwiGLU, route  # noqa: F401
from .olmo_hybrid import _begin, _l2norm

__all__ = ['KimiLinearConfig', 'KimiLinearModel', 'KimiLinearForCausalLM',
           'chunked_kda_rule', 'kda_step', 'route', 'RULE_CHUNK',
           'RULE_SUB']

# names of the device ops, from a closed set (docs/observability.md)
_scope = jax.named_scope
_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

RULE_CHUNK = 64          # tokens one pass of the chunked rule takes
RULE_SUB = 16            # ... worked in sub-blocks of this many
KDA, MLA = 'kda', 'mla'


class KimiLinearConfig:
    """The published keys; defaults are Kimi-Linear-48B-A3B's own.
    `experts_held` = (lo, hi): the expert ids [lo, hi) this program
    holds of `num_experts`, all of them by default."""

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 intermediate_size=9216, num_hidden_layers=27,
                 num_attention_heads=32, num_key_value_heads=32,
                 head_dim=72, hidden_act='silu', rms_norm_eps=1e-5,
                 tie_word_embeddings=False, model_max_length=1048576,
                 linear_attn_config=None, kv_lora_rank=512,
                 q_lora_rank=None, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, mla_use_nope=True,
                 rope_theta=10000, rope_scaling=None,
                 first_k_dense_replace=1, moe_layer_freq=1,
                 moe_intermediate_size=1024, num_experts=256,
                 num_experts_per_token=8, num_shared_experts=1,
                 moe_renormalize=True, moe_router_activation_func='sigmoid',
                 routed_scaling_factor=2.446, num_expert_group=1,
                 topk_group=1, use_grouped_topk=True,
                 num_nextn_predict_layers=0, model_type='kimi_linear',
                 experts_held=None, initializer_range=0.02):
        if linear_attn_config is None:
            linear_attn_config = {
                'full_attn_layers': [i for i in range(
                    1, num_hidden_layers + 1)
                    if i % 4 == 0 or i == num_hidden_layers],
                'head_dim': 128, 'num_heads': 32,
                'short_conv_kernel_size': 4}
            linear_attn_config['kda_layers'] = [
                i for i in range(1, num_hidden_layers + 1)
                if i not in linear_attn_config['full_attn_layers']]
        lac = dict(linear_attn_config)
        kinds = {}
        for kind, key in ((KDA, 'kda_layers'), (MLA, 'full_attn_layers')):
            for i in lac[key]:
                if i in kinds or not 1 <= i <= num_hidden_layers:
                    raise ValueError(
                        'linear_attn_config: layer %d is named twice or '
                        'lies outside 1..%d' % (i, num_hidden_layers))
                kinds[i] = kind
        if len(kinds) != num_hidden_layers:
            raise ValueError(
                'linear_attn_config names %d of %d layers'
                % (len(kinds), num_hidden_layers))
        # what the published row does not need is not built: say so
        # rather than compute something else under its name
        if q_lora_rank is not None:
            raise NotImplementedError('a low-rank query is not built')
        if not mla_use_nope:
            raise NotImplementedError(
                'rotary positions in a latent layer are not built')
        if num_key_value_heads != num_attention_heads:
            raise NotImplementedError('grouped K/V heads are not built')
        if num_expert_group != 1 or topk_group != 1:
            raise NotImplementedError('grouped routing is not built')
        if moe_layer_freq != 1 or num_nextn_predict_layers:
            raise NotImplementedError(
                'only an expert layer in every layer after the dense ones, '
                'and no next-token-prediction layers, are built')
        if moe_router_activation_func != 'sigmoid' or not moe_renormalize:
            raise NotImplementedError(
                'only the sigmoid router with renormalised weights is built')
        if hidden_act != 'silu' or tie_word_embeddings:
            raise NotImplementedError(
                'only hidden_act="silu" and an untied head are built')
        lo, hi = experts_held if experts_held is not None \
            else (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError('experts_held %r is no range of the %d experts'
                             % (experts_held, num_experts))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.hidden_act = hidden_act
        self.rms_norm_eps = rms_norm_eps
        self.tie_word_embeddings = tie_word_embeddings
        self.model_max_length = model_max_length
        self.linear_attn_config = lac
        self.kv_lora_rank = kv_lora_rank
        self.q_lora_rank = q_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.mla_use_nope = mla_use_nope
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.first_k_dense_replace = first_k_dense_replace
        self.moe_layer_freq = moe_layer_freq
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_token = num_experts_per_token
        self.num_shared_experts = num_shared_experts
        self.moe_renormalize = moe_renormalize
        self.moe_router_activation_func = moe_router_activation_func
        self.routed_scaling_factor = routed_scaling_factor
        self.num_expert_group = num_expert_group
        self.topk_group = topk_group
        self.use_grouped_topk = use_grouped_topk
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.model_type = model_type
        self.experts_held = (int(lo), int(hi))
        self.initializer_range = initializer_range
        self.layer_kinds = [kinds[i + 1] for i in range(num_hidden_layers)]

    @property
    def max_position_embeddings(self):
        """The name the serving engine asks a model's longest sequence
        by; this family publishes it as `model_max_length`."""
        return self.model_max_length


# ---- the per-channel gated delta rule --------------------------------------

def kda_step(q, k, v, g, beta, state):
    """One token of the recurrence for every row and head. q, k, g (log
    alpha, per key channel) `[B, H, dk]`, v `[B, H, dv]`, beta `[B, H]`,
    state `[B, H, dk, dv]`, all float32. Returns (o `[B, H, dv]`, new
    state)."""
    s = state * jnp.exp(g)[..., None]
    kv = jnp.einsum('bhkv,bhk->bhv', s, k, precision=_HI)
    s = s + k[..., :, None] * (beta[..., None] * (v - kv))[..., None, :]
    return jnp.einsum('bhkv,bhk->bhv', s, q, precision=_HI), s


def _decayed_products(x, k, gc, sub):
    """P[j, i] = sum_c x[j, c] k[i, c] exp(gc[j, c] - gc[i, c]) for i <=
    j and 0 above the diagonal; x, k, gc `[..., C, dk]`, gc the running
    sum of the log decays (never rising). exp(-gc[i]) alone overflows
    float32 once a channel has decayed by e^88, a few dozen tokens at
    alpha = 0.05, so the chunk is worked in sub-blocks of `sub` rows and
    no exponent is ever positive: a pair in two sub-blocks takes the
    decay up to the later one's first row on the column's side and from
    there on the row's side (a matrix product of two decayed copies); a
    pair inside one sub-block takes exp of the difference itself,
    channel by channel."""
    lead, (c, dk) = x.shape[:-2], x.shape[-2:]
    ns = c // sub
    blocks = lambda a: a.reshape(lead + (ns, sub, dk))
    xs, ks, gs = blocks(x), blocks(k), blocks(gc)
    # the running sum before each sub-block's first row
    ref = jnp.concatenate([jnp.zeros_like(gs[..., :1, 0, :]),
                           gs[..., :-1, -1, :]], axis=-2)   # [.., ns, dk]
    rows = xs * jnp.exp(gs - ref[..., None, :])
    earlier = jnp.arange(c)[None, :] < (jnp.arange(ns) * sub)[:, None]
    cols = k[..., None, :, :] * jnp.exp(jnp.where(
        earlier[..., None], ref[..., None, :] - gc[..., None, :, :],
        -jnp.inf))                                          # [.., ns, C, dk]
    across = jnp.einsum('...nak,...nik->...nai', rows, cols, precision=_HI)
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    diff = gs[..., :, None, :] - gs[..., None, :, :]        # [.., a, b, dk]
    inside = jnp.sum(xs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(
        jnp.where(lower[..., None], diff, -jnp.inf)), axis=-1)
    own = jnp.eye(ns, dtype=bool)[:, None, :, None]         # [n, 1, n, 1]
    p = across.reshape(lead + (ns, sub, ns, sub)) + jnp.where(
        own, inside[..., :, :, None, :], 0.0)
    return p.reshape(lead + (c, c))


def chunked_kda_rule(q, k, v, g, beta, state, chunk=RULE_CHUNK,
                     sub=RULE_SUB):
    """The recurrence of `kda_step` over T tokens, chunk-parallel (the WY
    form of the gated delta rule: inside a chunk of C tokens every
    product is a matrix product, and the state crosses chunk boundaries
    in a scan), with the decay per key channel (`_decayed_products`).
    q, k, g `[B, T, H, dk]`, v `[B, T, H, dv]`, beta `[B, T, H]`, state
    `[B, H, dk, dv]`, float32. A token with beta 0 and g 0 leaves the
    state as it is (so a padded tail is masked through its gates).
    Returns (o `[B, T, H, dv]`, the state after the last token)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    sub = min(int(sub), int(chunk))
    c = min(int(chunk), -(-t // sub) * sub)
    pad = -t % c
    if pad:
        widen = lambda x: jnp.pad(x, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (x.ndim - 2))
        q, k, v, g, beta = (widen(x) for x in (q, k, v, g, beta))
    n = (t + pad) // c
    # [N, B, H, C, *]: chunks first, for the scan
    split = lambda x: jnp.moveaxis(
        x.reshape((b, n, c, h) + x.shape[3:]), (1, 3), (0, 2))
    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)                # log decay from the chunk's
    kb = k * beta[..., None]                   # start, per channel
    a = jnp.tril(_decayed_products(kb, k, gc, sub), -1)
    # (I + A)^-1 applied to [beta v | beta k exp(gc)]: each token's value
    # less what the chunk's earlier tokens already wrote along its key
    rhs = jnp.concatenate([v * beta[..., None], kb * jnp.exp(gc)], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    u, w = sol[..., :dv], sol[..., dv:]
    qk = _decayed_products(q, k, gc, sub)

    def step(s, xs):
        q_i, k_i, u_i, w_i, gc_i, qk_i = xs
        v_new = u_i - jnp.einsum('bhck,bhkv->bhcv', w_i, s, precision=_HI)
        o = jnp.einsum('bhck,bhkv->bhcv', q_i * jnp.exp(gc_i), s,
                       precision=_HI) \
            + jnp.einsum('bhij,bhjv->bhiv', qk_i, v_new, precision=_HI)
        last = gc_i[..., -1:, :]                            # [B, H, 1, dk]
        s = s * jnp.exp(last[..., 0, :])[..., None] + jnp.einsum(
            'bhck,bhcv->bhkv', k_i * jnp.exp(last - gc_i), v_new,
            precision=_HI)
        return s, o

    state, o = jax.lax.scan(step, state, (q, k, u, w, gc, qk))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t + pad, h, dv)
    return o[:, :t], state


def _mask_gates(g, beta, real):
    """A position that is not real leaves the state as it is: no decay
    (log alpha 0 on every channel) and no write (beta 0). `real` `[B, T]`
    bool, g `[B, T, H, dk]`, beta `[B, T, H]`."""
    return (jnp.where(real[..., None, None], g, 0.0),
            jnp.where(real[..., None], beta, 0.0))


def _log_decay(f, a_log, dt_bias):
    """g `[B, n, H, dk]` from the gate projection f `[B, n, H dk]`."""
    h = a_log.shape[0]
    g = jax.nn.softplus(f.astype(F32) + dt_bias.astype(F32))
    g = g.reshape(g.shape[:-1] + (h, -1))
    return -jnp.exp(a_log.astype(F32))[:, None] * g


class KimiDeltaAttention(nn.Layer):
    """The KDA mixer."""

    def __init__(self, config):
        super().__init__()
        d, lac = config.hidden_size, config.linear_attn_config
        self.num_heads = h = lac['num_heads']
        self.dk = self.dv = dk = lac['head_dim']
        self.kernel = lac['short_conv_kernel_size']
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)
        self.q_proj, self.k_proj = lin(d, h * dk), lin(d, h * dk)
        self.v_proj, self.b_proj = lin(d, h * dk), lin(d, h)
        # the two low-rank gates, of rank head_dim (assumed)
        self.f_a_proj, self.f_b_proj = lin(d, dk), lin(dk, h * dk)
        self.g_a_proj, self.g_b_proj = lin(d, dk), lin(dk, h * dk)
        self.o_proj = lin(h * dk, d)
        self.conv_dim = 3 * h * dk
        init = nn.initializer
        # w[j] multiplies the input K-1-j tokens back: the last row is
        # the current token's; q's, k's and v's channels side by side
        self.conv_weight = self.create_parameter(
            [self.kernel, self.conv_dim],
            default_initializer=init.Normal(0.0, config.initializer_range))
        # alpha = exp(-exp(A_log) softplus(f + dt_bias)): at f = 0 these
        # span (0.5, 0.999) over heads and channels
        self.A_log = self.create_parameter(
            [h], default_initializer=init.Uniform(-3.0, 0.0))
        self.dt_bias = self.create_parameter(
            [h * dk], default_initializer=init.Uniform(-3.0, 0.5))
        self.o_norm = nn.RMSNorm(dk, config.rms_norm_eps)

    def cache_spec(self, dtype):
        return RecurrentSpec(
            arrays=(((self.num_heads, self.dk, self.dv), 'float32'),
                    ((self.kernel - 1, self.conv_dim), dtype)))

    def _mix(self, q, k, v, f, b, conv_w, a_log, dt_bias, state, tail,
             lengths, valid):
        """Raw arrays in, (o `[B, n, H, dv]` in q's dtype, new state, new
        tail) out. `lengths` / `valid` `[B]` as `RecurrentCache` has
        them."""
        bsz, n = q.shape[0], q.shape[1]
        h, dk, kern = self.num_heads, self.dk, self.kernel
        state0, tail0 = state, tail
        state, tail = _begin((state, tail), lengths)
        state = state.astype(F32)
        real = jnp.arange(n)[None, :] < valid[:, None]           # [B, n]
        with _scope('kimi.kda.conv'):
            u = jnp.concatenate([q, k, v], axis=-1)
            ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
            wf = conv_w.astype(F32)
            conv = sum(ext[:, j:j + n].astype(F32) * wf[j]
                       for j in range(kern))
            conv = jax.nn.silu(conv).reshape(bsz, n, 3, h, dk)
            qf = _l2norm(conv[:, :, 0]) * dk ** -0.5
            kf, vf = _l2norm(conv[:, :, 1]), conv[:, :, 2]
        with _scope('kimi.kda.gates'):
            g, beta = _mask_gates(_log_decay(f, a_log, dt_bias),
                                  jax.nn.sigmoid(b.astype(F32)), real)
        if n == 1:
            with _scope('kimi.kda.step'):
                o, new_state = kda_step(qf[:, 0], kf[:, 0], vf[:, 0],
                                        g[:, 0], beta[:, 0], state)
                o = o[:, None]
        else:
            with _scope('kimi.kda.rule'):
                o, new_state = chunked_kda_rule(qf, kf, vf, g, beta, state)
        with _scope('kimi.kda.state_write'):
            # the tail after `valid` tokens: the last K-1 real inputs;
            # a row that took none keeps what it had, bit for bit
            new_tail = jax.vmap(
                lambda e, s: jax.lax.dynamic_slice_in_dim(e, s, kern - 1, 0)
            )(ext, valid).astype(tail0.dtype)
            took = valid > 0
            new_state = jnp.where(took[:, None, None, None],
                                  new_state.astype(state0.dtype), state0)
            new_tail = jnp.where(took[:, None, None], new_tail, tail0)
        return o.astype(q.dtype), new_state, new_tail

    def forward(self, x, cache=None):
        bsz, n = x.shape[0], x.shape[1]
        with _scope('kimi.kda.proj'):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            f = self.f_b_proj(self.f_a_proj(x))
            gate = self.g_b_proj(self.g_a_proj(x))
            b = self.b_proj(x)
        weights = (self.conv_weight, self.A_log, self.dt_bias)
        if cache is None:
            # the normal path: every row starts from zeros, all n real
            def from_zeros(q, k, v, f, b, conv_w, a_log, dt_bias):
                state = jnp.zeros((bsz, self.num_heads, self.dk, self.dv),
                                  F32)
                tail = jnp.zeros((bsz, self.kernel - 1, self.conv_dim),
                                 q.dtype)
                return self._mix(q, k, v, f, b, conv_w, a_log, dt_bias,
                                 state, tail, jnp.zeros((bsz,), jnp.int32),
                                 jnp.full((bsz,), n, jnp.int32))[0]
            o = run_op('kimi_delta_attention', from_zeros, q, k, v, f, b,
                       *weights)
            new_cache = None
        else:
            if self.training and is_grad_enabled():
                raise RuntimeError(
                    'RecurrentCache is an inference-only serving path — '
                    'call model.eval() / no_grad')
            state, tail = cache.arrays
            o, state, tail = self._mix(
                *(t._data for t in (q, k, v, f, b) + weights),
                state, tail, cache.lengths, cache.valid)
            o = Tensor(o)
            new_cache = RecurrentCache((state, tail), cache.lengths,
                                       cache.valid)
        with _scope('kimi.kda.out'):
            o = self.o_norm(o) * M.reshape(
                F.sigmoid(gate), [bsz, n, self.num_heads, self.dv])
            y = self.o_proj(M.reshape(o, [bsz, n, self.num_heads * self.dv]))
        return y if cache is None else (y, new_cache)


# ---- latent attention ------------------------------------------------------

def _latent_attend(q, rows, w_kvb, mask, nope, lora, absorb):
    """q `[B, n, H, nope + rope]` against the latent rows `[B, L, W]` a
    sequence holds (`[c; k_pe]`, then zeros up to the pool's width W),
    under an additive mask `[B, n, L]`; `w_kvb` `[lora, H, nope + dv]`. Products in the operands' dtype, float32
    softmax. `absorb`: attend in the latent (the keys' half of `w_kvb`
    folded into the query, the values' half applied after the weighted
    sum: 2 H (2 lora + rope) operations a (query, row) pair and nothing
    per row), else expand K and V from the rows (2 lora H (nope + dv) a
    row once, then 2 H (nope + rope + dv) a pair). Returns `[B, n, H,
    dv]`."""
    scale, rope = q.shape[-1] ** -0.5, q.shape[-1] - nope
    c, k_pe = rows[..., :lora], rows[..., lora:lora + rope]
    w_k, w_v = w_kvb[..., :nope], w_kvb[..., nope:]
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    soft = lambda s: jax.nn.softmax(
        (s * scale + mask[:, None]).astype(F32), axis=-1).astype(q.dtype)
    if absorb:
        with _scope('kimi.mla.absorb'):
            # as wide as the rows: their lanes past [c; k_pe] meet zeros
            q_lat = jnp.concatenate(
                [jnp.einsum('bnhd,chd->bnhc', q_nope, w_k), q_pe,
                 jnp.zeros(q.shape[:-1] + (rows.shape[-1] - lora - rope,),
                           q.dtype)], axis=-1)
        with _scope('kimi.mla.core'):
            p = soft(jnp.einsum('bnhc,blc->bhnl', q_lat, rows))
            o_lat = jnp.einsum('bhnl,blc->bnhc', p, c)
        with _scope('kimi.mla.absorb'):
            return jnp.einsum('bnhc,chd->bnhd', o_lat, w_v)
    with _scope('kimi.mla.expand'):
        k_nope = jnp.einsum('blc,chd->blhd', c, w_k)
        v = jnp.einsum('blc,chd->blhd', c, w_v)
    with _scope('kimi.mla.core'):
        s = jnp.einsum('bnhd,blhd->bhnl', q_nope, k_nope) \
            + jnp.einsum('bnhr,blr->bhnl', q_pe, k_pe)
        return jnp.einsum('bhnl,blhd->bnhd', soft(s), v)


class KimiLatentAttention(nn.Layer):
    """MLA without a low-rank query and without rotation."""

    def __init__(self, config):
        super().__init__()
        d = config.hidden_size
        self.num_heads = h = config.num_attention_heads
        self.nope, self.rope = (config.qk_nope_head_dim,
                                config.qk_rope_head_dim)
        self.dv, self.lora = config.v_head_dim, config.kv_lora_rank
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)
        self.q_proj = lin(d, h * (self.nope + self.rope))
        self.kv_a_proj = lin(d, self.lora + self.rope)
        self.kv_a_norm = nn.RMSNorm(self.lora, config.rms_norm_eps)
        self.kv_b_proj = lin(self.lora, h * (self.nope + self.dv))
        self.o_proj = lin(h * self.dv, d)

    def cache_spec(self, dtype):
        return PagedLatentSpec(self.lora + self.rope, dtype)

    def _rows(self, x):
        """(q `[B, n, H, nope + rope]`, the latent rows `[B, n, lora +
        rope]` this call adds: [RMSNorm(c); k_pe])."""
        bsz, n = x.shape[0], x.shape[1]
        with _scope('kimi.mla.proj'):
            q = M.reshape(self.q_proj(x),
                          [bsz, n, self.num_heads, self.nope + self.rope])
            kva = self.kv_a_proj(x)
            rows = M.concat([self._latent_norm(self.kv_a_norm,
                                               kva[:, :, :self.lora]),
                             kva[:, :, self.lora:]], axis=-1)
        return q, rows

    @staticmethod
    @_scope('kimi.mla.latent_norm')
    def _latent_norm(norm, c):
        return norm(c)

    def forward(self, x, cache=None):
        bsz, n = x.shape[0], x.shape[1]
        q, rows = self._rows(x)
        sizes = dict(nope=self.nope, lora=self.lora)
        w_shape = (self.lora, self.num_heads, self.nope + self.dv)
        if cache is None:
            def whole(q, rows, w_kvb):
                mask = jnp.where(jnp.tril(jnp.ones((n, n), bool)), 0.0,
                                 -1e9).astype(F32)[None]
                return _latent_attend(q, rows, w_kvb.reshape(w_shape), mask,
                                      absorb=False, **sizes)
            out = run_op('latent_attention', whole, q, rows,
                         self.kv_b_proj.weight)
            new_cache = None
        else:
            if self.training and is_grad_enabled():
                raise RuntimeError(
                    'PagedLatentCache is an inference-only serving path — '
                    'call model.eval() / no_grad')
            held, new_cache = paged_latent_rows(rows._data, cache,
                                                'kimi.mla')
            with _scope('kimi.mla.mask'):
                qpos = cache.lengths[:, None] + jnp.arange(n)[None, :]
                sees = qpos[:, :, None] >= jnp.arange(held.shape[1])
                mask = jnp.where(sees, 0.0, -1e9).astype(F32)
            out = Tensor(_latent_attend(
                q._data, held, self.kv_b_proj.weight._data.reshape(w_shape),
                mask, absorb=n == 1, **sizes))
        with _scope('kimi.mla.out'):
            y = self.o_proj(M.reshape(out, [bsz, n,
                                            self.num_heads * self.dv]))
        return y if cache is None else (y, new_cache)


# ---- the block ------------------------------------------------------------

class KimiLinearBlock(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        self.kind = config.layer_kinds[index]
        self.mixer = (KimiDeltaAttention if self.kind == KDA
                      else KimiLatentAttention)(config)
        self.sparse = index >= config.first_k_dense_replace
        self.mlp = SparseMoe(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_token,
            config.routed_scaling_factor, config.experts_held,
            config.num_shared_experts, config.initializer_range,
            # this module's `route`, looked up as the layer is built: the
            # planted faults of tools/faults_kimi_linear.py replace it here
            scope='kimi.moe', router=route) if self.sparse else SwiGLU(
                config.hidden_size, config.intermediate_size)
        self.mixer_norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp_norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, x, cache=None):
        new_cache = None
        if cache is None:
            y = self.mixer(self._norm(self.mixer_norm, x))
        else:
            y, new_cache = self.mixer(self._norm(self.mixer_norm, x),
                                      cache=cache)
        h = x + y
        u = self._norm(self.mlp_norm, h)
        if self.sparse and cache is not None:
            # the tokens the counters count: the call's real ones
            y, new_cache.counters = self.mlp(
                u, jnp.arange(x.shape[1])[None, :] < cache.valid[:, None])
            new_cache.experts = self.mlp.path
        elif self.sparse:
            y = self.mlp(u)
        else:
            with _scope('kimi.mlp'):
                y = self.mlp(u)
        out = h + y
        return out if cache is None else (out, new_cache)

    @staticmethod
    @_scope('kimi.norm')
    def _norm(norm, x):
        return norm(x)


class KimiLinearModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([KimiLinearBlock(config, i)
                                    for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        with _scope('kimi.embed'):
            x = self.embed_tokens(input_ids)
        if caches is None:
            for block in self.layers:
                x = block(x)
            return KimiLinearBlock._norm(self.norm, x)
        kinds = {KDA: RecurrentCache, MLA: PagedLatentCache}
        new_caches = []
        for block, cache in zip(self.layers, caches):
            if not isinstance(cache, kinds[block.kind]):
                raise TypeError('a %s layer takes a %s, got %s' % (
                    block.kind, kinds[block.kind].__name__,
                    type(cache).__name__))
            x, new_cache = block(x, cache=cache)
            new_caches.append(new_cache)
        return KimiLinearBlock._norm(self.norm, x), new_caches


class KimiLinearForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or KimiLinearConfig(**kwargs)
        self.config = config
        self.model = KimiLinearModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def cache_specs(self):
        """What each layer keeps while it serves: (state, convolution
        tail) per sequence for a KDA layer, one latent row a token for
        an MLA layer; the activations' dtype is the token embedding's."""
        dtype = str(self.model.embed_tokens.weight.dtype).replace(
            'paddle.', '')
        return [block.mixer.cache_spec(dtype) for block in self.model.layers]

    def forward(self, input_ids, caches=None):
        if caches is None:
            hidden = self.model(input_ids)
        else:
            hidden, new_caches = self.model(input_ids, caches=caches)
        with _scope('kimi.lm_head'):
            logits = self.lm_head(hidden)
        return logits if caches is None else (logits, new_caches)
