"""Olmo-Hybrid: a decoder whose layers are of two kinds, by a per-layer
type list — `linear_attention` (a gated delta-rule mixer with a fixed
float32 state per sequence) and `full_attention` (softmax attention over
all earlier tokens) — in the published 3:1 pattern.

Configuration keys are those of the published `config.json`
(https://huggingface.co/allenai/Olmo-Hybrid-7B). With x_t the block
input of width d, no projection has a bias:

Linear layer (gated delta rule: Yang et al., "Gated Delta Networks",
arXiv:2412.06464; `linear_allow_neg_eigval` after Grazzi et al.,
arXiv:2411.12537). u_t = [W_q x_t; W_k x_t; W_v x_t]; every channel
goes through a causal depthwise convolution of width K over time
(c_t = sum_j w[j] u_{t-K+1+j}, no bias), then SiLU. Per head: q, k of
`linear_key_head_dim`, v of `linear_value_head_dim`; q <- q / |q| *
dk^-1/2, k <- k / |k| (|x| = sqrt(sum x^2 + 1e-6)); scalars beta_t = 2 *
sigmoid(w_b . x_t) (the 2 is `linear_allow_neg_eigval`) and alpha_t =
exp(-exp(A_log) * softplus(w_a . x_t + dt_bias)). State S [dk, dv],
float32:

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    y_t = W_o [RMSNorm_dv(o_t) * SiLU(W_g x_t)]

Full layer: q = RMSNorm(W_q x), k = RMSNorm(W_k x) (over the whole
width), v = W_v x; heads of d / num_attention_heads; causal softmax(q k^T
/ sqrt(head size)) v; W_o. `rope_parameters.rope_theta` is null: no
rotary embedding is applied.

Block, both kinds (the reordered norm of Olmo 2/3): h = x +
RMSNorm(mixer(x)); out = h + RMSNorm(W_down(SiLU(W_gate h) * W_up h)).
Final RMSNorm, untied `lm_head`.

Serving (`caches=[...]`, one per layer, text/models/cache.py): a full
layer writes and reads `PagedKVCache` through `cache.paged_attention`,
GPT's own; a linear layer takes a `RecurrentCache` of (S, the last K-1
inputs of the convolution) per sequence. Prefill runs the rule
chunk-parallel (the paper's WY form, chunks of `RULE_CHUNK` tokens, the
state carried from chunk to chunk and from call to call); a one-token
call runs the recurrence as written above.
"""
import jax
import jax.numpy as jnp

from ... import nn
from ...framework.core import Tensor, is_grad_enabled, run_op
from ...nn import functional as F
from ...tensor import manipulation as M
from .cache import (PagedKVCache, PagedKVSpec, RecurrentCache, RecurrentSpec,
                    paged_attention)

__all__ = ['OlmoHybridConfig', 'OlmoHybridModel', 'OlmoHybridForCausalLM',
           'chunked_delta_rule', 'delta_rule_step', 'RULE_CHUNK']

# names of the device ops, from a closed set (docs/observability.md)
_scope = jax.named_scope
_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

RULE_CHUNK = 64          # tokens one pass of the chunked rule takes
LINEAR, FULL = 'linear_attention', 'full_attention'


class OlmoHybridConfig:
    """The published keys; defaults are Olmo-Hybrid-7B's own."""

    def __init__(self, vocab_size=100352, hidden_size=3840,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=30, num_key_value_heads=30,
                 hidden_act='silu', max_position_embeddings=65536,
                 attention_bias=False, rms_norm_eps=1e-6,
                 tie_word_embeddings=False, layer_types=None,
                 linear_num_key_heads=30, linear_num_value_heads=30,
                 linear_key_head_dim=96, linear_value_head_dim=192,
                 linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
                 rope_parameters=None, initializer_range=0.02):
        if layer_types is None:
            layer_types = [FULL if i % 4 == 3 else LINEAR
                           for i in range(num_hidden_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {LINEAR, FULL}:
            raise ValueError(
                'layer_types must name %d layers, each %r or %r'
                % (num_hidden_layers, LINEAR, FULL))
        if hidden_size % num_attention_heads:
            raise ValueError('hidden_size must divide into the heads')
        # what the published row does not need is not built: say so
        # rather than compute something else under its name
        if num_key_value_heads != num_attention_heads:
            raise NotImplementedError('grouped K/V heads are not built')
        if linear_num_key_heads != linear_num_value_heads:
            raise NotImplementedError(
                'linear layers with fewer key heads than value heads are '
                'not built')
        if (rope_parameters or {}).get('rope_theta') is not None:
            raise NotImplementedError('rotary positions are not built')
        if hidden_act != 'silu' or attention_bias or tie_word_embeddings:
            raise NotImplementedError(
                'only hidden_act="silu", no attention bias and an untied '
                'head are built')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.hidden_act = hidden_act
        self.max_position_embeddings = max_position_embeddings
        self.attention_bias = attention_bias
        self.rms_norm_eps = rms_norm_eps
        self.tie_word_embeddings = tie_word_embeddings
        self.layer_types = layer_types
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_allow_neg_eigval = linear_allow_neg_eigval
        self.rope_parameters = rope_parameters or {'rope_theta': None}
        self.initializer_range = initializer_range


# ---- the gated delta rule --------------------------------------------------

def delta_rule_step(q, k, v, g, beta, state):
    """One token of the recurrence for every row and head. q, k
    `[B, H, dk]`, v `[B, H, dv]`, g (log alpha) and beta `[B, H]`, state
    `[B, H, dk, dv]`, all float32. Returns (o `[B, H, dv]`, new state)."""
    s = state * jnp.exp(g)[..., None, None]
    kv = jnp.einsum('bhkv,bhk->bhv', s, k, precision=_HI)
    s = s + k[..., :, None] * (beta[..., None] * (v - kv))[..., None, :]
    return jnp.einsum('bhkv,bhk->bhv', s, q, precision=_HI), s


def chunked_delta_rule(q, k, v, g, beta, state, chunk=RULE_CHUNK):
    """The same recurrence over T tokens, chunk-parallel (the WY form of
    the paper, section 3.3): inside a chunk of C tokens every product is
    a matrix product, and the state crosses chunk boundaries in a scan.
    q, k `[B, T, H, dk]`, v `[B, T, H, dv]`, g and beta `[B, T, H]`,
    state `[B, H, dk, dv]`, float32. A token with beta 0 and g 0 leaves
    the state as it is (so a padded tail is masked through its gates).
    Returns (o `[B, T, H, dv]`, the state after the last token)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(int(chunk), t)
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
    gc = jnp.cumsum(g, axis=-1)                       # log decay from
    lower = jnp.tril(jnp.ones((c, c), bool))          # the chunk's start
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))  # i >= j, else 0
    kb = k * beta[..., None]
    a = jnp.einsum('...ik,...jk->...ij', kb, k, precision=_HI) * decay
    a = jnp.where(jnp.tril(lower, -1), a, 0.0)
    # (I + A)^-1 applied to [beta v | beta k exp(gc)]: each token's value
    # less what the chunk's earlier tokens already wrote along its key
    rhs = jnp.concatenate([v * beta[..., None],
                           kb * jnp.exp(gc)[..., None]], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    u, w = sol[..., :dv], sol[..., dv:]
    qk = jnp.where(lower, jnp.einsum('...ik,...jk->...ij', q, k,
                                     precision=_HI) * decay, 0.0)

    def step(s, xs):
        q_i, k_i, u_i, w_i, gc_i, qk_i = xs
        v_new = u_i - jnp.einsum('bhck,bhkv->bhcv', w_i, s, precision=_HI)
        o = jnp.einsum('bhck,bhkv->bhcv', q_i * jnp.exp(gc_i)[..., None], s,
                       precision=_HI) \
            + jnp.einsum('bhij,bhjv->bhiv', qk_i, v_new, precision=_HI)
        last = gc_i[..., -1:]
        s = s * jnp.exp(last)[..., None] + jnp.einsum(
            'bhck,bhcv->bhkv', k_i * jnp.exp(last - gc_i)[..., None], v_new,
            precision=_HI)
        return s, o

    state, o = jax.lax.scan(step, state, (q, k, u, w, gc, qk))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t + pad, h, dv)
    return o[:, :t], state


def _beta(b, allow_neg_eigval):
    """The write strength: sigmoid, doubled where the transition may
    have negative eigenvalues."""
    return jax.nn.sigmoid(b) * (2.0 if allow_neg_eigval else 1.0)


def _mask_gates(g, beta, real):
    """A position that is not real leaves the state as it is: no decay
    (log alpha 0) and no write (beta 0). `real` `[B, T]` bool."""
    return (jnp.where(real[..., None], g, 0.0),
            jnp.where(real[..., None], beta, 0.0))


def _begin(arrays, lengths):
    """The state and convolution tail a call starts from: a row that has
    consumed nothing starts from zeros, whatever its slot held."""
    fresh = lengths == 0
    return tuple(jnp.where(fresh.reshape((-1,) + (1,) * (a.ndim - 1)),
                           jnp.zeros((), a.dtype), a) for a in arrays)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


class OlmoHybridLinearAttention(nn.Layer):
    """The gated delta-rule mixer."""

    def __init__(self, config):
        super().__init__()
        d = config.hidden_size
        self.num_heads = config.linear_num_value_heads
        self.dk = config.linear_key_head_dim
        self.dv = config.linear_value_head_dim
        self.kernel = config.linear_conv_kernel_dim
        self.allow_neg_eigval = config.linear_allow_neg_eigval
        h, dk, dv = self.num_heads, self.dk, self.dv
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)
        self.q_proj, self.k_proj = lin(d, h * dk), lin(d, h * dk)
        self.v_proj, self.g_proj = lin(d, h * dv), lin(d, h * dv)
        self.a_proj, self.b_proj = lin(d, h), lin(d, h)
        self.o_proj = lin(h * dv, d)
        self.conv_dim = 2 * h * dk + h * dv
        init = nn.initializer
        # w[j] multiplies the input K-1-j tokens back: the last row is
        # the current token's
        self.conv_weight = self.create_parameter(
            [self.kernel, self.conv_dim],
            default_initializer=init.Normal(0.0, config.initializer_range))
        # alpha = exp(-exp(A_log) softplus(a + dt_bias)): at a = 0 these
        # span (0.5, 0.999) over the heads
        self.A_log = self.create_parameter(
            [h], default_initializer=init.Uniform(-3.0, 0.0))
        self.dt_bias = self.create_parameter(
            [h], default_initializer=init.Uniform(-3.0, 0.5))
        self.o_norm = nn.RMSNorm(dv, config.rms_norm_eps)

    def cache_spec(self, dtype):
        return RecurrentSpec(
            arrays=(((self.num_heads, self.dk, self.dv), 'float32'),
                    ((self.kernel - 1, self.conv_dim), dtype)))

    def _mix(self, q, k, v, a, b, conv_w, a_log, dt_bias, state, tail,
             lengths, valid):
        """Raw arrays in, (o `[B, n, H, dv]` in q's dtype, new state, new
        tail) out. `lengths` / `valid` `[B]` as `RecurrentCache` has
        them."""
        bsz, n = q.shape[0], q.shape[1]
        h, dk, dv, kern = self.num_heads, self.dk, self.dv, self.kernel
        state0, tail0 = state, tail
        state, tail = _begin((state, tail), lengths)
        state = state.astype(F32)
        real = jnp.arange(n)[None, :] < valid[:, None]           # [B, n]
        with _scope('olmo.gdn.conv'):
            u = jnp.concatenate([q, k, v], axis=-1)
            ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
            wf = conv_w.astype(F32)
            conv = sum(ext[:, j:j + n].astype(F32) * wf[j]
                       for j in range(kern))
            conv = jax.nn.silu(conv)
            qf = conv[..., :h * dk].reshape(bsz, n, h, dk)
            kf = conv[..., h * dk:2 * h * dk].reshape(bsz, n, h, dk)
            vf = conv[..., 2 * h * dk:].reshape(bsz, n, h, dv)
            qf = _l2norm(qf) * dk ** -0.5
            kf = _l2norm(kf)
        with _scope('olmo.gdn.gates'):
            beta = _beta(b.astype(F32), self.allow_neg_eigval)
            g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
                a.astype(F32) + dt_bias.astype(F32))
            g, beta = _mask_gates(g, beta, real)
        with _scope('olmo.gdn.core'):
            if n == 1:
                o, new_state = delta_rule_step(
                    qf[:, 0], kf[:, 0], vf[:, 0], g[:, 0], beta[:, 0], state)
                o = o[:, None]
            else:
                o, new_state = chunked_delta_rule(qf, kf, vf, g, beta, state)
        with _scope('olmo.gdn.state_write'):
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
        with _scope('olmo.gdn.proj'):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            gate, a, b = self.g_proj(x), self.a_proj(x), self.b_proj(x)
        weights = (self.conv_weight, self.A_log, self.dt_bias)
        if cache is None:
            # the normal path: every row starts from zeros, all n real
            def from_zeros(q, k, v, a, b, conv_w, a_log, dt_bias):
                state = jnp.zeros((bsz, self.num_heads, self.dk, self.dv),
                                  F32)
                tail = jnp.zeros((bsz, self.kernel - 1, self.conv_dim),
                                 q.dtype)
                return self._mix(q, k, v, a, b, conv_w, a_log, dt_bias,
                                 state, tail, jnp.zeros((bsz,), jnp.int32),
                                 jnp.full((bsz,), n, jnp.int32))[0]
            o = run_op('gated_delta_rule', from_zeros, q, k, v, a, b,
                       *weights)
            new_cache = None
        else:
            if self.training and is_grad_enabled():
                raise RuntimeError(
                    'RecurrentCache is an inference-only serving path — '
                    'call model.eval() / no_grad')
            state, tail = cache.arrays
            o, state, tail = self._mix(
                *(t._data for t in (q, k, v, a, b) + weights),
                state, tail, cache.lengths, cache.valid)
            o = Tensor(o)
            new_cache = RecurrentCache((state, tail), cache.lengths,
                                       cache.valid)
        with _scope('olmo.gdn.norm_gate'):
            o = self.o_norm(o) * M.reshape(
                F.silu(gate), [bsz, n, self.num_heads, self.dv])
        with _scope('olmo.gdn.out'):
            y = self.o_proj(M.reshape(o, [bsz, n, self.num_heads * self.dv]))
        return y if cache is None else (y, new_cache)


class OlmoHybridAttention(nn.Layer):
    """Softmax attention over every earlier token, q and k normalised
    over the whole width, no positions."""

    def __init__(self, config):
        super().__init__()
        d = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = d // config.num_attention_heads
        self.hidden_size = d
        lin = lambda: nn.Linear(d, d, bias_attr=False)
        self.q_proj, self.k_proj = lin(), lin()
        self.v_proj, self.o_proj = lin(), lin()
        self.q_norm = nn.RMSNorm(d, config.rms_norm_eps)
        self.k_norm = nn.RMSNorm(d, config.rms_norm_eps)

    def cache_spec(self, dtype):
        return PagedKVSpec(self.num_heads, self.head_dim, dtype)

    def forward(self, x, cache=None):
        bsz, n = x.shape[0], x.shape[1]
        heads = [bsz, n, self.num_heads, self.head_dim]
        with _scope('olmo.attn.qkv'):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with _scope('olmo.attn.qk_norm'):
            q, k = self.q_norm(q), self.k_norm(k)
        q, k, v = (M.reshape(t, heads) for t in (q, k, v))
        if cache is None:
            with _scope('olmo.attn.core'):
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, dropout_p=0.0)
            new_cache = None
        else:
            if self.training and is_grad_enabled():
                raise RuntimeError(
                    'PagedKVCache is an inference-only serving path — '
                    'call model.eval() / no_grad')
            out, new_cache = paged_attention(q, k, v, cache, 'olmo.attn')
        with _scope('olmo.attn.out'):
            y = self.o_proj(M.reshape(out, [bsz, n, self.hidden_size]))
        return y if cache is None else (y, new_cache)


class OlmoHybridMLP(nn.Layer):
    def __init__(self, config):
        super().__init__()
        d, f = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(d, f, bias_attr=False)
        self.up_proj = nn.Linear(d, f, bias_attr=False)
        self.down_proj = nn.Linear(f, d, bias_attr=False)

    @_scope('olmo.mlp')
    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class OlmoHybridBlock(nn.Layer):
    def __init__(self, config, layer_type):
        super().__init__()
        self.layer_type = layer_type
        self.mixer = (OlmoHybridLinearAttention if layer_type == LINEAR
                      else OlmoHybridAttention)(config)
        self.mixer_norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = OlmoHybridMLP(config)
        self.mlp_norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, x, cache=None):
        new_cache = None
        if cache is None:
            y = self.mixer(x)
        else:
            y, new_cache = self.mixer(x, cache=cache)
        h = x + self._norm(self.mixer_norm, y)
        out = h + self._norm(self.mlp_norm, self.mlp(h))
        return out if cache is None else (out, new_cache)

    @staticmethod
    @_scope('olmo.norm')
    def _norm(norm, x):
        return norm(x)


class OlmoHybridModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([OlmoHybridBlock(config, kind)
                                    for kind in config.layer_types])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        with _scope('olmo.embed'):
            x = self.embed_tokens(input_ids)
        if caches is None:
            for block in self.layers:
                x = block(x)
            return OlmoHybridBlock._norm(self.norm, x)
        kinds = {LINEAR: RecurrentCache, FULL: PagedKVCache}
        new_caches = []
        for block, cache in zip(self.layers, caches):
            if not isinstance(cache, kinds[block.layer_type]):
                raise TypeError('a %s layer takes a %s, got %s' % (
                    block.layer_type, kinds[block.layer_type].__name__,
                    type(cache).__name__))
            x, new_cache = block(x, cache=cache)
            new_caches.append(new_cache)
        return OlmoHybridBlock._norm(self.norm, x), new_caches


class OlmoHybridForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or OlmoHybridConfig(**kwargs)
        self.config = config
        self.model = OlmoHybridModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def cache_specs(self):
        """What each layer keeps while it serves: K and V rows for a
        full layer, (state, convolution tail) per sequence for a linear
        one; the activations' dtype is the token embedding's."""
        dtype = str(self.model.embed_tokens.weight.dtype).replace(
            'paddle.', '')
        return [block.mixer.cache_spec(dtype) for block in self.model.layers]

    def forward(self, input_ids, caches=None):
        if caches is None:
            hidden = self.model(input_ids)
        else:
            hidden, new_caches = self.model(input_ids, caches=caches)
        with _scope('olmo.lm_head'):
            logits = self.lm_head(hidden)
        return logits if caches is None else (logits, new_caches)
