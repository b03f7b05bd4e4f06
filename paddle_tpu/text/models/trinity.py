"""Trinity-Large (Arcee's `afmoe`): a decoder whose attention layers are
of two kinds by a per-layer type list — `sliding_attention` (rotary
positions, a window of the last `sliding_window` tokens) and
`full_attention` (no positions, every earlier token) — with grouped K/V
heads, an elementwise output gate, QK norm and norms on both sides of
each sub-layer, and an expert layer after the leading dense ones.

Configuration keys are those of the published `config.json`
(https://huggingface.co/arcee-ai/Trinity-Large-Preview). With x the
block input of width d, H query heads and G K/V heads of Dh (query head h
reads K/V head h // (H / G)), W = `sliding_window`, no projection has a
bias:

    a  = RMSNorm_in(x)
    q, k, v = W_q a, W_k a, W_v a;  q, k <- RMSNorm_q(q), RMSNorm_k(k)
             (per head, over its Dh channels)
    sliding layer: q, k <- RoPE(q), RoPE(k) (theta `rope_theta`, the
             rotate-half form over all Dh channels, absolute positions);
             query i sees key j for i - W < j <= i
    full layer: no positions; query i sees key j <= i
    o  = concat_h softmax(q_h k^T / sqrt(Dh) + mask) v  *  sigmoid(W_g a)
    x' = x + RMSNorm_post_attn(W_o o)
    x_out = x' + RMSNorm_post_mlp(ffn(RMSNorm_pre_mlp(x')))

Layer i < `num_dense_layers` has the dense SwiGLU of `intermediate_size`,
every other the expert layer (`text/models/moe.py`: sigmoid router over
all `num_experts`, the `num_experts_per_tok` largest of score + bias,
weights renormalised (`route_norm`) times `route_scale`, one shared
expert; `experts_held` = (lo, hi) says which this program holds). The
embedding is scaled by sqrt(d) (`mup_enabled`); final RMSNorm, untied
head. Read from the `afmoe` modeling code as remembered (no network to
check): RoPE on sliding layers only, the sqrt(d) embedding scale, the
gate elementwise and from the normed input; "depth-scaled" sandwich norm
is an initialisation of the norms' gains and changes no equation.

Serving (`caches=[...]`, text/models/cache.py): a full layer writes and
reads `PagedKVCache` through `cache.paged_attention` (grouped heads: the
pool holds the G K/V heads); a sliding layer keeps a ring of the last W
tokens' K and V per sequence (`WindowKVSpec`, `cache.window_attention`),
rotated at write, so its memory and the rows a decode step reads are
bounded by the window whatever the context.
"""
import math

import jax
import jax.numpy as jnp

from ... import nn
from ...framework.core import is_grad_enabled, run_op
from ...nn import functional as F
from ...tensor import manipulation as M
from .cache import (PagedKVCache, PagedKVSpec, RecurrentCache, WindowKVSpec,
                    paged_attention, window_attention, window_masks)
from .moe import SparseMoe, SwiGLU

__all__ = ['TrinityConfig', 'TrinityModel', 'TrinityForCausalLM', 'rope']

# names of the device ops, from a closed set (docs/observability.md)
_scope = jax.named_scope
F32 = jnp.float32
SLIDING, FULL = 'sliding_attention', 'full_attention'


class TrinityConfig:
    """The published keys; defaults are Trinity-Large-Preview's own.
    `experts_held` = (lo, hi): the expert ids [lo, hi) this program holds
    of `num_experts`, all of them by default."""

    def __init__(self, vocab_size=200192, hidden_size=3072,
                 intermediate_size=12288, num_hidden_layers=60,
                 num_attention_heads=48, num_key_value_heads=8, head_dim=128,
                 hidden_act='silu', rms_norm_eps=1e-5,
                 tie_word_embeddings=False, max_position_embeddings=262144,
                 layer_types=None, global_attn_every_n_layers=4,
                 sliding_window=4096, rope_theta=10000, rope_scaling=None,
                 num_dense_layers=6, moe_intermediate_size=3072,
                 num_experts=256, num_experts_per_tok=4,
                 num_shared_experts=1, route_norm=True, route_scale=2.448,
                 score_func='sigmoid', n_group=1, topk_group=1,
                 num_expert_groups=1, num_limited_groups=1,
                 mup_enabled=True, use_grouped_mm=True,
                 load_balance_coeff=5e-05, model_type='afmoe',
                 experts_held=None, initializer_range=0.02):
        if layer_types is None:
            layer_types = [FULL if (i + 1) % global_attn_every_n_layers == 0
                           else SLIDING for i in range(num_hidden_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {SLIDING, FULL}:
            raise ValueError('layer_types must name %d layers, each %r or %r'
                             % (num_hidden_layers, SLIDING, FULL))
        if num_attention_heads % num_key_value_heads:
            raise ValueError('the query heads must share the K/V heads '
                             'evenly')
        # what the published row does not need is not built: say so
        # rather than compute something else under its name
        if rope_scaling is not None:
            raise NotImplementedError('scaled rotary positions are not built')
        if max(n_group, topk_group, num_expert_groups,
               num_limited_groups) != 1:
            raise NotImplementedError('grouped routing is not built')
        if score_func != 'sigmoid' or not route_norm:
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
        self.max_position_embeddings = max_position_embeddings
        self.layer_types = layer_types
        self.global_attn_every_n_layers = global_attn_every_n_layers
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.num_dense_layers = num_dense_layers
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.score_func = score_func
        self.n_group = n_group
        self.topk_group = topk_group
        self.num_expert_groups = num_expert_groups
        self.num_limited_groups = num_limited_groups
        self.mup_enabled = mup_enabled
        self.use_grouped_mm = use_grouped_mm
        self.load_balance_coeff = load_balance_coeff
        self.model_type = model_type
        self.experts_held = (int(lo), int(hi))
        self.initializer_range = initializer_range


def rope(x, pos, theta):
    """Rotary positions, the rotate-half form over all of x's `Dh`
    channels: x `[B, n, H, Dh]` at absolute positions `pos` `[B, n]`, the
    angles and the rotation in float32."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, :, None, None] * inv              # [B,n,1,Dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _whole(q, k, v, window):
    """Attention over one whole sequence (the normal path): q `[B, n, H,
    Dh]`, k and v `[B, n, G, Dh]`, keys within `window` of the query,
    float32 scores."""
    b, n, h, dh = q.shape
    g = k.shape[2]
    pos = jnp.arange(n)[None]
    sees = window_masks(pos, pos, window)[:, None, None]       # [1,1,1,n,n]
    qg = q.reshape(b, n, g, h // g, dh)
    s = jnp.einsum('bngrd,bmgd->bgrnm', qg, k, preferred_element_type=F32)
    p = jax.nn.softmax(jnp.where(sees, s * dh ** -0.5, -1e9), axis=-1)
    o = jnp.einsum('bgrnm,bmgd->bngrd', p.astype(q.dtype), v,
                   preferred_element_type=F32)
    return o.astype(q.dtype).reshape(b, n, h, dh)


class TrinityAttention(nn.Layer):
    """Grouped-head attention with QK norm and an output gate; a sliding
    layer rotates q and k and sees the last `sliding_window` tokens."""

    def __init__(self, config, layer_type):
        super().__init__()
        d = config.hidden_size
        self.num_heads = h = config.num_attention_heads
        self.num_kv_heads = g = config.num_key_value_heads
        self.head_dim = dh = config.head_dim
        self.sliding = layer_type == SLIDING
        self.window = config.sliding_window
        self.theta = config.rope_theta
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)
        self.q_proj, self.k_proj = lin(d, h * dh), lin(d, g * dh)
        self.v_proj, self.o_proj = lin(d, g * dh), lin(h * dh, d)
        self.gate_proj = lin(d, h * dh)
        self.q_norm = nn.RMSNorm(dh, config.rms_norm_eps)
        self.k_norm = nn.RMSNorm(dh, config.rms_norm_eps)

    def cache_spec(self, dtype):
        g, dh = self.num_kv_heads, self.head_dim
        return WindowKVSpec(g, dh, self.window, dtype) if self.sliding \
            else PagedKVSpec(g, dh, dtype)

    def forward(self, a, cache=None):
        bsz, n = a.shape[0], a.shape[1]
        h, g, dh = self.num_heads, self.num_kv_heads, self.head_dim
        with _scope('trinity.attn.qkv'):
            q = M.reshape(self.q_proj(a), [bsz, n, h, dh])
            k = M.reshape(self.k_proj(a), [bsz, n, g, dh])
            v = M.reshape(self.v_proj(a), [bsz, n, g, dh])
        with _scope('trinity.attn.qk_norm'):
            q, k = self.q_norm(q), self.k_norm(k)
        if cache is None:
            window = self.window if self.sliding else n

            def whole(q, k, v):
                if self.sliding:
                    pos = jnp.broadcast_to(jnp.arange(n), (bsz, n))
                    q, k = rope(q, pos, self.theta), rope(k, pos, self.theta)
                return _whole(q, k, v, window)
            out = run_op('trinity_attention', whole, q, k, v)
            new_cache = None
        else:
            if self.training and is_grad_enabled():
                raise RuntimeError(
                    'a serving cache is an inference-only path — call '
                    'model.eval() / no_grad')
            q, k, v = q._data, k._data, v._data
            if self.sliding:
                with _scope('trinity.attn.rope'):
                    pos = cache.lengths[:, None] + jnp.arange(n)[None, :]
                    q, k = rope(q, pos, self.theta), rope(k, pos, self.theta)
                out, new_cache = window_attention(q, k, v, cache,
                                                  'trinity.attn.window')
            else:
                out, new_cache = paged_attention(q, k, v, cache,
                                                 'trinity.attn.full',
                                                 held=True)
        with _scope('trinity.attn.gate'):
            out = out * M.reshape(F.sigmoid(self.gate_proj(a)),
                                  [bsz, n, h, dh])
        with _scope('trinity.attn.out'):
            y = self.o_proj(M.reshape(out, [bsz, n, h * dh]))
        return y if cache is None else (y, new_cache)


class TrinityBlock(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        d, eps = config.hidden_size, config.rms_norm_eps
        self.layer_type = config.layer_types[index]
        self.self_attn = TrinityAttention(config, self.layer_type)
        self.sparse = index >= config.num_dense_layers
        self.mlp = SparseMoe(
            d, config.moe_intermediate_size, config.num_experts,
            config.num_experts_per_tok, config.route_scale,
            config.experts_held, config.num_shared_experts,
            config.initializer_range, scope='trinity.moe') if self.sparse \
            else SwiGLU(d, config.intermediate_size)
        self.input_layernorm = nn.RMSNorm(d, eps)
        self.post_attention_layernorm = nn.RMSNorm(d, eps)
        self.pre_mlp_layernorm = nn.RMSNorm(d, eps)
        self.post_mlp_layernorm = nn.RMSNorm(d, eps)

    def forward(self, x, cache=None):
        a = self._norm(self.input_layernorm, x)
        if cache is None:
            y, new_cache = self.self_attn(a), None
        else:
            y, new_cache = self.self_attn(a, cache=cache)
        h = x + self._norm(self.post_attention_layernorm, y)
        b = self._norm(self.pre_mlp_layernorm, h)
        if self.sparse and cache is not None:
            # the tokens the counters count: the call's real ones
            y, counted = self.mlp(
                b, jnp.arange(x.shape[1])[None, :] < cache.valid[:, None])
            new_cache.counters = dict(
                getattr(new_cache, 'counters', None) or {}, **counted)
            new_cache.experts = self.mlp.path
        elif self.sparse:
            y = self.mlp(b)
        else:
            with _scope('trinity.mlp'):
                y = self.mlp(b)
        out = h + self._norm(self.post_mlp_layernorm, y)
        return out if cache is None else (out, new_cache)

    @staticmethod
    @_scope('trinity.norm')
    def _norm(norm, x):
        return norm(x)


class TrinityModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([TrinityBlock(config, i)
                                    for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.embed_scale = math.sqrt(config.hidden_size) \
            if config.mup_enabled else 1.0

    def forward(self, input_ids, caches=None):
        with _scope('trinity.embed'):
            x = self.embed_tokens(input_ids) * self.embed_scale
        if caches is None:
            for block in self.layers:
                x = block(x)
            return TrinityBlock._norm(self.norm, x)
        kinds = {SLIDING: RecurrentCache, FULL: PagedKVCache}
        new_caches = []
        for block, cache in zip(self.layers, caches):
            if not isinstance(cache, kinds[block.layer_type]):
                raise TypeError('a %s layer takes a %s, got %s' % (
                    block.layer_type, kinds[block.layer_type].__name__,
                    type(cache).__name__))
            x, new_cache = block(x, cache=cache)
            new_caches.append(new_cache)
        return TrinityBlock._norm(self.norm, x), new_caches


class TrinityForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or TrinityConfig(**kwargs)
        self.config = config
        self.model = TrinityModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def cache_specs(self):
        """What each layer keeps while it serves: a ring of the last
        `sliding_window` tokens' K and V per sequence for a sliding layer,
        K and V rows in pages for a full one; the activations' dtype is
        the token embedding's."""
        dtype = str(self.model.embed_tokens.weight.dtype).replace(
            'paddle.', '')
        return [block.self_attn.cache_spec(dtype)
                for block in self.model.layers]

    def forward(self, input_ids, caches=None):
        if caches is None:
            hidden = self.model(input_ids)
        else:
            hidden, new_caches = self.model(input_ids, caches=caches)
        with _scope('trinity.lm_head'):
            logits = self.lm_head(hidden)
        return logits if caches is None else (logits, new_caches)
