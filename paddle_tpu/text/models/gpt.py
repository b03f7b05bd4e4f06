"""GPT-style decoder-only LM — the flagship model (BASELINE configs 3/5).

Built from paddle_tpu.nn layers so the distributed strategies (TP layer
placements, ZeRO sharding specs, pipeline stages) apply uniformly. Causal
attention routes through F.scaled_dot_product_attention → pallas flash
kernel on TPU.
"""
import os

import jax
import jax.numpy as jnp

from ... import nn
from ...framework.core import Tensor, no_grad_guard
from ...nn import functional as F
from ...tensor import manipulation as M
from .cache import PagedKVCache as GPTPagedCache
from .cache import PagedKVSpec, _raw_leaf, _tensor_leaf, paged_attention

__all__ = ['GPTConfig', 'GPTModel', 'GPTForCausalLM']

# jax.named_scope is metadata on the compiled ops and nothing else: a
# device trace then reads `gpt.attn.core` where it read `fusion`. Names
# from a closed set (docs/observability.md), no layer index in a name.
_scope = jax.named_scope


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None,
                 max_position_embeddings=1024, dropout=0.1,
                 layer_norm_epsilon=1e-5, initializer_range=0.02,
                 use_rmsnorm=False, tie_word_embeddings=True,
                 recompute=False, num_experts=0, moe_capacity_factor=1.5,
                 fused_loss=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        self.initializer_range = initializer_range
        self.use_rmsnorm = use_rmsnorm
        self.tie_word_embeddings = tie_word_embeddings
        self.recompute = recompute
        # num_experts > 0 swaps each block's MLP for an expert-parallel
        # SwitchMoE (incubate/moe.py) routed over the 'ep' mesh axis
        self.num_experts = num_experts
        self.moe_capacity_factor = moe_capacity_factor
        # fused_loss=True changes the TRAINING forward contract: forward()
        # (without caches) returns the final hidden states and loss()
        # fuses head matmul + CE via F.linear_cross_entropy, never
        # materializing [batch*seq, vocab] logits (ops/fused_ce.py).
        # Decode/generate paths (caches=...) still produce logits.
        # loss() tells the two apart by the trailing dim, so the fusion
        # is only safe when vocab and hidden differ — refuse the
        # ambiguous configuration up front rather than misroute a real
        # logits tensor into the fused head at runtime.
        if fused_loss and vocab_size == hidden_size:
            raise ValueError(
                'fused_loss=True requires vocab_size != hidden_size '
                '(loss() distinguishes hidden states from logits by '
                'their trailing dimension); got both = %d' % vocab_size)
        self.fused_loss = fused_loss

    @staticmethod
    def gpt2_small():
        return GPTConfig()

    @staticmethod
    def bert_base_equiv():
        return GPTConfig(vocab_size=30522, hidden_size=768, num_layers=12,
                         num_heads=12, max_position_embeddings=512)

    @staticmethod
    def gpt3_13b():
        return GPTConfig(vocab_size=50304, hidden_size=5120, num_layers=40,
                         num_heads=40, max_position_embeddings=2048)


class GPTStaticCache:
    """Fixed-shape KV cache for decode: preallocated [B, max_len, H, Dh]
    buffers plus the current valid length. Every decode step writes via
    dynamic_update_slice and attends with a validity mask, so all steps
    share one set of shapes — per-op executables are reused across
    tokens, and the step is jit-able without retracing per token (a
    concat-growing cache changes shape every step). Inference-only: the
    buffer writes bypass the autograd tape."""

    def __init__(self, k_buf, v_buf, length, fresh=False):
        self.k = k_buf
        self.v = v_buf
        self.length = length  # scalar int32 (traced under jit)
        # python-level marker: no write has happened yet, so a multi-
        # token prefill may use the plain causal fast path (flash/
        # blockwise-eligible) instead of masked attention over the
        # zero-padded buffer
        self.fresh = fresh

    @staticmethod
    def empty(batch, max_len, num_heads, head_dim, dtype='float32'):
        import paddle_tpu as paddle
        k = paddle.zeros([batch, max_len, num_heads, head_dim], dtype)
        v = paddle.zeros([batch, max_len, num_heads, head_dim], dtype)
        return GPTStaticCache(k, v, jnp.zeros((), jnp.int32), fresh=True)


# registered as a pytree so cache stacks cross jit boundaries (the jitted
# decode step takes and returns them); `fresh` is static aux data — a
# fresh (prefill) cache and a decode cache intentionally trace differently
def _cache_flatten(c):
    return (_raw_leaf(c.k), _raw_leaf(c.v), c.length), c.fresh


def _cache_unflatten(fresh, children):
    k, v, length = children
    return GPTStaticCache(_tensor_leaf(k), _tensor_leaf(v), length,
                          fresh=fresh)


jax.tree_util.register_pytree_node(GPTStaticCache, _cache_flatten,
                                   _cache_unflatten)


def _cache_get(cache, key, build, cap=8):
    """Bounded per-model compiled-executable cache: a serving loop with
    naturally varying prompt/generation shapes must not pin one XLA
    executable per distinct shape forever. Eviction happens only on a
    miss (FIFO, before insert) — a hit must never evict, least of all
    the entry being requested."""
    hit = cache.get(key)
    if hit is not None:
        return hit
    while len(cache) >= cap:
        cache.pop(next(iter(cache)))
    val = cache[key] = build()
    return val


class GPTAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        self.hidden_size = config.hidden_size
        self.qkv_proj = nn.Linear(config.hidden_size, 3 * config.hidden_size)
        self.out_proj = nn.Linear(config.hidden_size, config.hidden_size)
        self.dropout = config.dropout
        # TP placement hints consumed by distributed/strategy.py
        self.qkv_proj.weight.placement = (None, 'mp')
        self.qkv_proj.bias.placement = ('mp',)
        self.out_proj.weight.placement = ('mp', None)
        # bench A/B knob, latched at construction: reading the env per
        # forward call costs in eager mode and lets a mid-process env
        # change mix layouts across traced vs eager executions
        self._qkv_split_last = os.environ.get('PADDLE_TPU_QKV_SPLIT') == 'last'

    def forward(self, x, cache=None):
        b, n = x.shape[0], x.shape[1]
        with _scope('gpt.attn.qkv'):
            qkv = self.qkv_proj(x)
            if self._qkv_split_last:
                # experimental A/B (bench rung): slice the packed minor axis
                # at 128-aligned offsets instead of reshaping to 5-D and
                # slicing the middle axis. The round-4 profile shows
                # ~5 ms/step of [b,n,3,h,d] layout-copy traffic on the
                # middle-axis path; whether last-axis slicing removes it is
                # measured in-window, not assumed. NOT the default: under
                # tensor parallelism the packed 2304 axis is mp-sharded and
                # q/k/v offsets straddle shard boundaries (the [3, heads, d]
                # head-axis slicing keeps each shard self-contained).
                hs = self.hidden_size
                hd = [b, n, self.num_heads, self.head_dim]
                q = M.reshape(qkv[:, :, :hs], hd)
                k = M.reshape(qkv[:, :, hs:2 * hs], hd)
                v = M.reshape(qkv[:, :, 2 * hs:], hd)
            else:
                qkv = M.reshape(qkv, [b, n, 3, self.num_heads, self.head_dim])
                q = qkv[:, :, 0]
                k = qkv[:, :, 1]
                v = qkv[:, :, 2]
        if isinstance(cache, GPTPagedCache):
            from ...framework.core import is_grad_enabled
            if self.training and is_grad_enabled():
                raise RuntimeError(
                    'GPTPagedCache is an inference-only serving path — '
                    'call model.eval() / no_grad')
            # the paged write and read are every model's (cache.py)
            out, new_cache = paged_attention(q, k, v, cache, 'gpt.attn')
            return self._out(out, b, n), new_cache
        if isinstance(cache, GPTStaticCache):
            import jax
            from ...framework.core import is_grad_enabled
            if self.training and is_grad_enabled():
                # the buffer writes bypass the autograd tape: training
                # through this path would silently drop the k/v grads
                raise RuntimeError(
                    'GPTStaticCache is an inference-only decode path — '
                    'call model.eval() / no_grad / generate()')
            max_len = cache.k.shape[1]
            if not isinstance(cache.length, jax.core.Tracer) and \
                    int(cache.length) + n > max_len:
                # (under jit the length is a tracer; generate() guards
                # the budget up front instead)
                raise ValueError(
                    'static cache overflow: length %d + %d new tokens > '
                    'capacity %d' % (int(cache.length), n, max_len))
            t = cache.length
            k_buf = jax.lax.dynamic_update_slice(
                cache.k._data, k._data.astype(cache.k._data.dtype),
                (0, t, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(
                cache.v._data, v._data.astype(cache.v._data.dtype),
                (0, t, 0, 0))
            new_cache = GPTStaticCache(Tensor(k_buf), Tensor(v_buf), t + n)
            if cache.fresh and n > 1:
                # prefill on an untouched cache: plain causal attention
                # over the chunk itself (flash/blockwise-eligible) — the
                # masked full-buffer attention below would pay quadratic
                # cost against max_len-n empty slots
                with _scope('gpt.attn.core'):
                    out = F.scaled_dot_product_attention(
                        q, k, v, is_causal=True, dropout_p=0.0)
                return self._out(out, b, n), new_cache
            # validity mask over the fixed buffer: query row i (absolute
            # position t+i) sees buffer slots j <= t+i
            with _scope('gpt.attn.mask'):
                qpos = t + jnp.arange(n)
                kpos = jnp.arange(max_len)
                allow = qpos[:, None] >= kpos[None, :]
                mask = Tensor(jnp.where(allow, 0.0, -1e9)[
                    None, None].astype(jnp.float32))
            with _scope('gpt.attn.core'):
                out = F.scaled_dot_product_attention(
                    q, Tensor(k_buf), Tensor(v_buf), attn_mask=mask,
                    is_causal=False, dropout_p=0.0)
            return self._out(out, b, n), new_cache
        if cache is not None:
            k = M.concat([cache[0], k], axis=1)
            v = M.concat([cache[1], v], axis=1)
        with _scope('gpt.attn.core'):
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.dropout if self.training else 0.0)
        out = self._out(out, b, n)
        if cache is not None:
            return out, (k, v)
        return out

    @_scope('gpt.attn.out')
    def _out(self, out, b, n):
        return self.out_proj(M.reshape(out, [b, n, self.hidden_size]))


class GPTMLP(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.fc_in = nn.Linear(config.hidden_size, config.intermediate_size)
        self.fc_out = nn.Linear(config.intermediate_size, config.hidden_size)
        self.dropout = nn.Dropout(config.dropout)
        self.fc_in.weight.placement = (None, 'mp')
        self.fc_in.bias.placement = ('mp',)
        self.fc_out.weight.placement = ('mp', None)

    @_scope('gpt.mlp')
    def forward(self, x):
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x),
                                               approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, config):
        super().__init__()
        Norm = nn.RMSNorm if config.use_rmsnorm else nn.LayerNorm
        self.ln_1 = Norm(config.hidden_size, config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = Norm(config.hidden_size, config.layer_norm_epsilon)
        if getattr(config, 'num_experts', 0):
            from ...incubate.moe import SwitchMoE
            self.mlp = SwitchMoE(
                config.hidden_size, config.intermediate_size,
                num_experts=config.num_experts,
                capacity_factor=config.moe_capacity_factor)
        else:
            self.mlp = GPTMLP(config)

    def forward(self, x, cache=None):
        if cache is not None:
            a, new_cache = self.attn(self._ln(self.ln_1, x), cache=cache)
            x = x + a
            x = x + self.mlp(self._ln(self.ln_2, x))
            return x, new_cache
        x = x + self.attn(self._ln(self.ln_1, x))
        x = x + self.mlp(self._ln(self.ln_2, x))
        return x

    @staticmethod
    @_scope('gpt.ln')
    def _ln(norm, x):
        return norm(x)


class GPTModel(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or GPTConfig(**kwargs)
        self.config = config
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size)
        self.drop = nn.Dropout(config.dropout)
        self.h = nn.LayerList([GPTBlock(config)
                               for _ in range(config.num_layers)])
        Norm = nn.RMSNorm if config.use_rmsnorm else nn.LayerNorm
        self.ln_f = Norm(config.hidden_size, config.layer_norm_epsilon)
        self.wte.weight.placement = ('mp', None)
        self._recompute = config.recompute

    def enable_recompute(self, flag=True):
        """Per-block activation recompute (reference RecomputeOptimizer
        checkpoint segments = transformer blocks)."""
        self._recompute = flag

    def forward(self, input_ids, position_ids=None, caches=None):
        n = input_ids.shape[1]
        if position_ids is None:
            if caches is not None and isinstance(caches[0], GPTPagedCache):
                # serving: each row's positions continue from ITS length
                position_ids = Tensor(
                    caches[0].lengths[:, None] + jnp.arange(n)[None, :])
            elif caches is not None:
                # decode: positions continue from the cached length
                position_ids = Tensor(
                    (caches[0].length + jnp.arange(n))[None, :])
            else:
                position_ids = Tensor(
                    jnp.arange(n, dtype=jnp.int64)[None, :])
        with _scope('gpt.embed'):
            x = self.drop(self.wte(input_ids) + self.wpe(position_ids))
        if caches is not None:
            new_caches = []
            for block, c in zip(self.h, caches):
                x, nc = block(x, cache=c)
                new_caches.append(nc)
            return GPTBlock._ln(self.ln_f, x), new_caches
        from ...distributed import pipeline as pp_mod
        pp_state = pp_mod.pipeline_state()
        moe = getattr(self.config, 'num_experts', 0) > 0
        if moe and self.training and (pp_state is not None
                                      or self._recompute):
            # the aux-loss tracer would escape the checkpoint/shard_map
            # trace it was created in
            raise NotImplementedError(
                'MoE blocks do not compose with recompute or pipeline '
                'parallelism yet (aux-loss routing) — disable one of them')
        if pp_state is not None and self.training:
            # GPipe over the 'pp' mesh axis: embeddings above and ln_f/head
            # below stay replicated over pp; the block stack is the
            # pipelined region (stage params pp-sharded, ppermute rotation)
            x = pp_mod.pipeline_blocks(self.h, x, pp_state)
        elif self._recompute and self.training:
            from ...distributed.fleet.utils import recompute as _remat
            for block in self.h:
                x = _remat(block, x)
        else:
            for block in self.h:
                x = block(x)
        # collect MoE load-balancing aux losses for GPTForCausalLM.loss
        # (training only: eval perplexity must not carry the balance term)
        self._moe_aux = None
        if self.training:
            for block in self.h:
                aux = getattr(block.mlp, 'aux_loss', None)
                if aux is not None:
                    term = aux * block.mlp.aux_loss_weight
                    self._moe_aux = term if self._moe_aux is None \
                        else self._moe_aux + term
        return GPTBlock._ln(self.ln_f, x)


class GPTForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or GPTConfig(**kwargs)
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def cache_specs(self):
        """What each layer keeps while it serves (text/models/cache.py):
        K and V rows of every head, in the token embedding's dtype."""
        config = self.config
        dtype = str(self.gpt.wte.weight.dtype).replace('paddle.', '')
        return [PagedKVSpec(config.num_heads,
                            config.hidden_size // config.num_heads, dtype)
                for _ in self.gpt.h]

    def forward(self, input_ids, position_ids=None, caches=None):
        if caches is not None:
            hidden, new_caches = self.gpt(input_ids, position_ids,
                                          caches=caches)
        else:
            hidden = self.gpt(input_ids, position_ids)
            if getattr(self.config, 'fused_loss', False) and self.training:
                # fused-loss TRAINING contract: the head matmul lives
                # inside loss() (F.linear_cross_entropy) — returning
                # hidden here is what makes the fusion possible. Eval
                # and decode forwards keep producing logits.
                return hidden
        with _scope('gpt.lm_head'):
            if self.lm_head is None:
                logits = F.linear(hidden,
                                  M.transpose(self.gpt.wte.weight, [1, 0]))
            else:
                logits = self.lm_head(hidden)
        if caches is not None:
            return logits, new_caches
        return logits

    @no_grad_guard()
    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, do_sample=False, seed=0):
        """Autoregressive generation with a STATIC-shape KV cache.

        TPU-native decode: the per-token step (forward + next-token
        pick) is ONE jitted program over fixed-size cache buffers
        (GPTStaticCache, a registered pytree) updated by
        dynamic_update_slice — identical shapes every token, so XLA
        traces and compiles the step once and the loop replays the
        executable. The reference ecosystem reaches this via PaddleNLP's
        decoding; the framework here provides it natively. Greedy by
        default; do_sample=True draws from softmax(logits/temperature)
        restricted to top_k (0 = full vocab).
        """
        import jax
        model = self
        was_training = self.training
        self.eval()
        try:
            ids = input_ids if isinstance(input_ids, Tensor) \
                else Tensor(jnp.asarray(input_ids))
            if max_new_tokens <= 0:
                return Tensor(ids._data.astype(jnp.int32))
            b, n0 = ids.shape[0], ids.shape[1]
            max_len = n0 + max_new_tokens
            if max_len > self.config.max_position_embeddings:
                raise ValueError(
                    'prompt %d + max_new_tokens %d exceeds '
                    'max_position_embeddings %d' %
                    (n0, max_new_tokens, self.config.max_position_embeddings))
            dtype = self.gpt.wte.weight.dtype
            caches = [GPTStaticCache.empty(
                b, max_len, self.config.num_heads,
                self.config.hidden_size // self.config.num_heads,
                dtype=str(dtype).replace('paddle.', ''))
                for _ in self.gpt.h]
            def pick(last_logits, key):
                lg = last_logits.astype(jnp.float32)
                if not do_sample:
                    return jnp.argmax(lg, axis=-1).astype(jnp.int32)
                lg = lg / max(float(temperature), 1e-6)
                if top_k:
                    kth = jnp.sort(lg, axis=-1)[:, -int(top_k)][:, None]
                    lg = jnp.where(lg >= kth, lg, -1e30)
                return jax.random.categorical(key, lg, axis=-1).astype(
                    jnp.int32)

            from ...framework import functional as _fm
            _params = _fm.extract_params(self)
            _bufs = _fm.extract_buffers(self)

            # prefill: ONE jitted pass over the prompt seeds the caches
            # (eager prefill would dispatch every op separately)
            pre_cache = getattr(self, '_prefill_cache', None)
            if pre_cache is None:
                pre_cache = self._prefill_cache = {}

            def _build_prefill():
                def _prefill(p, bf, cs, ids_):
                    (lg, cs2), _ = _fm.functional_call(
                        self, p, bf, args=(Tensor(ids_),),
                        kwargs={'caches': cs}, training=False)
                    return lg[:, -1], cs2
                return jax.jit(_prefill)
            pre_jit = _cache_get(pre_cache, (b, n0, max_len), _build_prefill)
            last, caches = pre_jit(_params, _bufs, caches, ids._data)

            # the whole decode is ONE compiled program: a lax.scan whose
            # body is the static-shape cached step (params/buffers/caches
            # are pytree args; GPTStaticCache is a registered node). The
            # host dispatches once per generate() call, not once per
            # token — the same lever as TrainStep.multi_step for training.
            func_mod = _fm
            params, bufs = _params, _bufs

            # one compiled executable per (generation length, prompt
            # shape, sampling config) — cached on the model so repeated
            # generate() calls replay it instead of re-jitting (a fresh
            # closure every call would defeat jit's identity-keyed cache)
            cache_key = (max_new_tokens, b, n0, bool(do_sample),
                         int(top_k), float(temperature))
            decode_cache = getattr(self, '_decode_cache', None)
            if decode_cache is None:
                decode_cache = self._decode_cache = {}

            def _build_decode():
                def _decode(p, bf, cs, first, key):
                    def body(carry, _):
                        cs, tok, key = carry
                        key, sub = jax.random.split(key)
                        (lg, new_cs), _ = func_mod.functional_call(
                            self, p, bf, args=(Tensor(tok),),
                            kwargs={'caches': cs}, training=False)
                        nxt = pick(lg[:, -1], sub)
                        return (new_cs, nxt[:, None], key), nxt

                    (_, _, _), toks = jax.lax.scan(
                        body, (cs, first, key), None,
                        length=max_new_tokens - 1)
                    return toks  # [steps, b]
                return jax.jit(_decode)
            decode_jit = _cache_get(decode_cache, cache_key, _build_decode)

            key = jax.random.PRNGKey(seed)
            out = [ids._data.astype(jnp.int32)]
            key, sub = jax.random.split(key)
            nxt = pick(last, sub)[:, None]
            out.append(nxt)
            if max_new_tokens > 1:
                toks = decode_jit(params, bufs, caches, nxt, key)
                out.append(jnp.transpose(toks, (1, 0)))
            return Tensor(jnp.concatenate(out, axis=1))
        finally:
            if was_training:
                self.train()

    def enable_recompute(self, flag=True):
        self.gpt.enable_recompute(flag)

    def pp_decompose(self, loss_fn=None):
        """(pre, blocks, post) split for the 1F1B pipeline schedule
        (distributed/pipeline_1f1b.py): pre = embeddings (stage 0),
        blocks = the homogeneous transformer stack (pp-sharded), post =
        ln_f + tied/untied head + token loss (last stage). Mirrors the
        reference PipelineTrainer program split where the loss lives in
        the last section (section_worker.cc). The tied wte weight appears
        in both pre and post — its grads combine via the schedule's psum.
        loss_fn(logits, labels) overrides self.loss so the train step's
        objective is honored."""
        gpt = self.gpt
        loss_fn = loss_fn or self.loss

        def pre(ids):
            n = ids.shape[1]
            pos = Tensor(jnp.arange(n, dtype=jnp.int32)[None, :])
            return gpt.drop(gpt.wte(ids) + gpt.wpe(pos))

        def post(x, labels):
            h = gpt.ln_f(x)
            if getattr(self.config, 'fused_loss', False):
                # last pipeline stage hands the HIDDEN state to loss_fn —
                # the same fused-loss contract the non-pipelined training
                # forward has (loss_fn routes through model.loss, which
                # fuses head+CE off the hidden input). Gating on loss_fn
                # identity would silently disable the fusion for any
                # wrapper lambda around model.loss.
                return loss_fn(h, labels)
            if self.lm_head is None:
                logits = F.linear(h, M.transpose(gpt.wte.weight, [1, 0]))
            else:
                logits = self.lm_head(h)
            return loss_fn(logits, labels)

        return pre, gpt.h, post

    def loss(self, logits, labels):
        with _scope('gpt.loss'):
            if getattr(self.config, 'fused_loss', False) and self.training and \
                    logits.shape[-1] == self.config.hidden_size:
                # fused TRAINING contract: `logits` is the final HIDDEN state
                # (forward's training gate); head matmul + CE fuse in one
                # chunked op. Both gates mirror forward's, so eval-path real
                # logits never misroute here even when vocab == hidden.
                if self.lm_head is None:
                    ce = F.linear_cross_entropy(
                        logits, self.gpt.wte.weight, labels,
                        transpose_weight=True)
                else:
                    ce = F.linear_cross_entropy(
                        logits, self.lm_head.weight, labels)
            else:
                b, n, v = logits.shape
                ce = F.cross_entropy(M.reshape(logits, [b * n, v]),
                                     M.reshape(labels, [b * n]))
        aux = getattr(self.gpt, '_moe_aux', None)
        self.gpt._moe_aux = None  # consume once — never stale across calls
        if aux is not None:
            ce = ce + aux
        return ce

    def num_params(self):
        import numpy as np
        return int(sum(np.prod(p.shape) for p in self.parameters()))

    def flops_per_token(self, seq_len=None):
        """Approximate fwd+bwd FLOPs/token (6N + attention quadratic term).

        The quadratic term scales with the ACTUAL sequence length; pass it
        explicitly when benching seq < max_position_embeddings, otherwise
        the MFU computed from this is inflated.
        """
        c = self.config
        if seq_len is None:
            seq_len = c.max_position_embeddings
        n_params = self.num_params()
        attn = 12 * c.num_layers * c.hidden_size * int(seq_len)
        return 6 * n_params + attn
