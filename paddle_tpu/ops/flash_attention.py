"""Flash attention Pallas kernels (TPU): forward AND backward.

Blockwise streaming softmax (Dao et al.) with a custom VJP whose backward
is also a pair of Pallas kernels (dq, and dk/dv), so neither direction
materializes the [n, m] attention matrix in HBM — the replacement for the
reference's fused attention CUDA ops (operators/fused/).

head_dim needs only %64 == 0 (BERT/GPT-base d=64 runs the kernel; the MXU
contracts 64-wide fine, Mosaic pads lanes). Sequence lengths must divide
the block sizes. A shape the kernels cannot take is a ROUTING decision made
before any kernel is called (`unsupported_reason`): it goes to the
blockwise XLA path (ops/blockwise_attention.py), or raises under
PADDLE_TPU_FLASH_STRICT=1. An exception out of a kernel that WAS chosen
(a Mosaic compile error included) always propagates — nothing here
catches it and substitutes another implementation.

PADDLE_TPU_FLASH_INTERPRET=1 runs the kernels through the Pallas
interpreter on CPU — the hardware-free correctness path for tests.
"""
import contextlib
import contextvars
import functools
import math
import os

import jax
import jax.numpy as jnp

from . import flash_defaults as _fd

# knob values latched at import (each bench child re-imports); the
# defaults, their rationale, and the bwd-inherits-fwd rule live in ONE
# place: ops/flash_defaults.py (bench.py records/replays from the same
# table)
_knobs = _fd.resolve()
_DEFAULT_BLOCK_Q = _knobs['block_q']
_DEFAULT_BLOCK_K = _knobs['block_k']
_BLOCK_Q_BWD = _knobs['block_q_bwd']
_BLOCK_K_BWD = _knobs['block_k_bwd']
_BLOCK_Q_LONG = _knobs['block_q_long']
_BLOCK_K_LONG = _knobs['block_k_long']
_NEG_INF = -1e30


def is_available():
    """True when the kernels can run here: on a TPU, or anywhere under
    the Pallas interpreter. A backend that fails to initialise raises —
    it is not read as "no TPU"."""
    if os.environ.get('PADDLE_TPU_FLASH_DISABLE', '0') == '1':
        return False  # explicit off-switch
    if interpret_mode():
        return True
    return jax.devices()[0].platform == 'tpu'


def strict_mode():
    """PADDLE_TPU_FLASH_STRICT=1: a shape the kernels cannot take raises
    instead of routing to the blockwise path."""
    return os.environ.get('PADDLE_TPU_FLASH_STRICT', '0') == '1'


def interpret_mode():
    return os.environ.get('PADDLE_TPU_FLASH_INTERPRET', '0') == '1'


def unsupported_reason(q, k, v, causal=False):
    """None if the Pallas kernels take these [B, H, N, D] operands, else
    why not — the routing decision callers make BEFORE choosing flash."""
    if causal and q.shape[2] != k.shape[2]:
        # the kernels' causal block bounds assume self-attention (q_pos =
        # global q index); KV-cache decode and chunked prefill need the
        # bottom-right-aligned mask the blockwise path implements
        return 'cross-length causal (%d queries, %d keys)' % (
            q.shape[2], k.shape[2])
    return _supported(q, k, v)


def _supported(q, k, v):
    """None if the Pallas kernels can run on these shapes, else the reason."""
    b, h, n, d = q.shape
    m = k.shape[2]
    if not (q.dtype == k.dtype == v.dtype):
        # the kernels contract in the operands' native dtype (_mm_f32);
        # lax.dot_general has no implicit promotion, so mixed dtypes must
        # take the documented fallback path rather than an opaque error
        return 'mixed operand dtypes (%s, %s, %s)' % (q.dtype, k.dtype,
                                                      v.dtype)
    if d % 64:
        return 'head_dim %d %% 64 != 0' % d
    # validate against the blocks the dispatched path will actually use:
    # the long path has its own (wider) block defaults, and the standard
    # backward blocks are independently overridable
    if _use_long_path(n, m):
        if _long_blocks(n, m) is None:
            return 'seq (%d, %d) not tileable by any long-path block' \
                % (n, m)
    elif _std_blocks(n, m) is None or _std_bwd_blocks(n, m) is None:
        return 'seq (%d, %d) not tileable by any standard-path block' \
            % (n, m)
    if n % 8 or m % 128:
        return 'seq (%d, %d) below TPU tile granularity' % (n, m)
    if not interpret_mode():
        # the interpreter has no VMEM; the footprint gate only guards
        # real Mosaic compiles
        return _vmem_reason(n, m, d, q.dtype.itemsize)
    return None


def _ref_bhnd(q, k, v, causal, scale):
    """Quadratic jnp reference the tests compare the kernels against; no
    dispatch path reaches it."""
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    if causal:
        # bottom-right aligned: query i is at absolute position m-n+i
        # (KV-cache decode correctness; flash-attn convention)
        n, m = s.shape[-2], s.shape[-1]
        if n > m:
            raise ValueError(
                'causal attention with more queries (%d) than keys (%d)'
                % (n, m))
        s = jnp.where(jnp.tril(jnp.ones((n, m), bool), m - n), s,
                      _NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum('bhqk,bhkd->bhqd', p, v)


# -- forward -----------------------------------------------------------------

def _causal_mask(s, q_start, k_start):
    """Mask scores [bq, bk] whose global k position exceeds the global q
    position (top-left-aligned causal; the kernels' n == m contract —
    cross-length causal routes to blockwise before any kernel runs).
    q_start/k_start are the blocks' global offsets."""
    bq, bk = s.shape
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _mm_f32(a, b, transpose_a=False, transpose_b=False):
    """a @ b (with either operand logically transposed) in the operands'
    NATIVE dtype with f32 MXU accumulation (preferred_element_type).
    Upcasting the operands to f32 before the dot would run the systolic
    array at its f32 rate — ~8x slower than bf16 on v5e — for zero
    accuracy gain over f32-accumulated bf16, which is the standard
    flash-attention numeric contract. The transposes are expressed as
    contracting-dimension choices so Mosaic folds them into the MXU feed
    instead of materializing a relayout."""
    dims = (((0 if transpose_a else 1,), (1 if transpose_b else 0,)),
            ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# -- the causal tile plan ----------------------------------------------------
#
# A grid cell of a standard-path kernel walks one axis of the [n, n] score
# square: the K blocks of its query block (forward, dq) or the Q blocks of
# its key block (dk/dv). Under the causal mask (n == m) a tile of that walk
# with no pair key <= query contributes exactly 0, so it is not visited; a
# tile wholly on the visible side needs no mask; only the tiles the
# diagonal crosses pay _causal_mask. Neither a skipped tile nor a dropped
# select changes a value, so the plan changes no bit of a result.

def _causal_plan(i, block_q, block_k, n, walk):
    """Grid cell i's walk as [(lo, hi, masked), ...] in walk order: walk
    'k' visits the K blocks of query block i (the unmasked ones, then those
    the diagonal crosses), walk 'q' the Q blocks of key block i (those the
    diagonal crosses, then the unmasked ones). Plain arithmetic, so it
    runs on Python ints (the tile counter) and traced program ids alike."""
    if walk == 'k':
        split = (i * block_q + 1) // block_k
        end = ((i + 1) * block_q + block_k - 1) // block_k
        return [(0, split, False), (split, end, True)]
    first = i * block_k // block_q
    split = ((i + 1) * block_k + block_q - 2) // block_q
    return [(first, split, True), (split, n // block_q, False)]


def _diag_sub_block(block_q, block_k):
    """Rows (dk/dv: columns) per sub-block of a square diagonal tile, or
    None where the tile is not split. Sub-block r of the tile meets the
    diagonal at its own rows, so its walk ends there: at 512 / 512 the
    tile's masked half shrinks to a quarter. Needs block_q == block_k
    (the diagonal is then exactly one tile at a static offset) and a
    sub-block of whole 128-lane tiles."""
    if block_q != block_k or block_q < 256:
        return None
    return block_q // 2


def _count_tiles(pass_name, heads, n, block_q, block_k):
    """Add one traced causal call's plan to flash_kernel_tiles_total:
    every tile of each of its `heads` (batch x heads) score squares, as
    unmasked, diagonal (the mask applied) or skipped (not visited)."""
    from ..monitor.registry import default_registry
    from ..monitor.telemetry import record_kernel_schema
    walk = 'q' if pass_name == 'dkv' else 'k'
    cells, steps = ((n // block_k, n // block_q) if walk == 'q'
                    else (n // block_q, n // block_k))
    tiles = {'unmasked': 0, 'diagonal': 0}
    for i in range(cells):
        for lo, hi, masked in _causal_plan(i, block_q, block_k, n, walk):
            tiles['diagonal' if masked else 'unmasked'] += hi - lo
    tiles['skipped'] = cells * steps - tiles['unmasked'] - tiles['diagonal']
    fam = record_kernel_schema(default_registry())['flash_kernel_tiles_total']
    for kind, count in tiles.items():
        fam.labels(kind, pass_name).inc(heads * count)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, seq_k):
    from jax.experimental import pallas as pl

    q = q_ref[...]
    block_q, head_dim = q.shape
    qi = pl.program_id(2)

    def update(carry, q_rows, q_start, k_start, width, masked):
        # one online-softmax step of q_rows over keys [k_start, +width)
        m_prev, l_prev, acc_prev = carry
        k_blk = k_ref[pl.ds(k_start, width), :]
        v_blk = v_ref[pl.ds(k_start, width), :]
        s = _mm_f32(q_rows, k_blk, transpose_b=True) * scale  # f32
        if masked:
            s = _causal_mask(s, q_start, k_start)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc_cur = acc_prev * alpha[:, None] + \
            _mm_f32(p.astype(v_blk.dtype), v_blk)
        return m_cur, l_cur, acc_cur

    def walk(carry, lo, hi, masked):
        return jax.lax.fori_loop(lo, hi, lambda kb, c: update(
            c, q, qi * block_q, kb * block_k, block_k, masked), carry)

    def finish(carry, rows):
        m_i, l_i, acc = carry
        l_safe = jnp.maximum(l_i, 1e-30)
        o_ref[rows, :] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        # lse carries a trailing singleton dim: Mosaic wants >=2-D blocks
        # with an aligned (or full) minor dimension
        lse_ref[rows, :] = (m_i + jnp.log(l_safe))[:, None]

    carry = (jnp.full((block_q,), -jnp.inf, jnp.float32),
             jnp.zeros((block_q,), jnp.float32),
             jnp.zeros((block_q, head_dim), jnp.float32))
    if not causal:
        finish(walk(carry, 0, seq_k // block_k, False), slice(None))
        return
    unmasked, diagonal = _causal_plan(qi, block_q, block_k, seq_k, 'k')
    carry = walk(carry, *unmasked)
    sub = _diag_sub_block(block_q, block_k)
    if sub is None:
        finish(walk(carry, *diagonal), slice(None))
        return
    # the diagonal is tile qi: row sub-block r sees keys up to its last row
    q0 = qi * block_q
    for r in range(block_q // sub):
        rows = slice(r * sub, (r + 1) * sub)
        part = update(tuple(x[rows] for x in carry), q[rows], q0 + r * sub,
                      q0, (r + 1) * sub, True)
        finish(part, rows)


def _fwd_impl(q, k, v, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n, d = q.shape
    m = k.shape[2]
    block_q, block_k = _std_blocks(n, m)
    if causal:
        _count_tiles('fwd', b * h, n, block_q, block_k)

    grid = (b, h, n // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_k=m)
    kwargs = {}
    if interpret_mode():
        kwargs['interpret'] = True
    else:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    o, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b, h, n, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, n, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, m, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, m, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        **kwargs,
    )(q, k, v)
    return o, lse


# -- long-sequence kernels ---------------------------------------------------
#
# The short-seq kernels above stage the FULL K/V (and in the dk/dv pass,
# full Q/dO) into VMEM per grid cell and fori_loop over them — simple and
# fast at seq <= ~4k, but at 8192 the staged operands plus the loop-body
# temporaries exceed scoped VMEM (the r4 in-window failure:
# "kernel-vmem-stack-oom", docs/bench_inwindow_r4.jsonl 11:58). The long
# variants below use the canonical Mosaic structure instead: the KV (or
# Q) walk is the LAST grid dimension ("arbitrary" = sequential on TPU),
# each cell sees one [block, d] tile, and the online-softmax carry lives
# in VMEM scratch that persists across sequential grid steps. Staged
# bytes are then O(block) regardless of sequence length.

_LONG_SEQ = _knobs['long_seq']


def _use_long_path(n, m):
    if os.environ.get('PADDLE_TPU_FLASH_FORCE_LONG', '0') == '1':
        return True
    return max(n, m) >= _LONG_SEQ


def _fit_block(desired, dim):
    """Largest block <= desired that divides dim (halving from desired,
    floor 128 — the TPU lane tile; dims at/below 128 run as ONE block,
    preserving the old min(block, dim) behavior for short q). None if
    nothing fits: the caller routes to the fallback instead of
    truncating the walk."""
    if dim <= 128:
        return dim
    b = min(desired, dim)
    while b >= 128:
        if dim % b == 0:
            return b
        b //= 2
    return None


def _clamped(desired_q, desired_k, n, m):
    """(block_q, block_k) clamped so every sequence that divides SOME
    power-of-two block >= 128 stays on the kernel (e.g. seq 4608 runs
    the long path at 512/512 when the preferred 1024 KV block doesn't
    divide it; seq 768 runs the standard path at 256), or None if the
    shape can't tile."""
    bq = _fit_block(desired_q, n)
    bk = _fit_block(desired_k, m)
    if bq is None or bk is None:
        return None
    return bq, bk


def _long_blocks(n, m):
    return _clamped(_BLOCK_Q_LONG, _BLOCK_K_LONG, n, m)


def _std_blocks(n, m):
    return _clamped(_DEFAULT_BLOCK_Q, _DEFAULT_BLOCK_K, n, m)


def _std_bwd_blocks(n, m):
    return _clamped(_BLOCK_Q_BWD, _BLOCK_K_BWD, n, m)


# -- scoped-VMEM footprint gate ----------------------------------------------
#
# The block clamp above only guarantees DIVISIBILITY; it happily picks
# configs whose working set Mosaic cannot hold, and the compiler then fails
# the whole program with "Ran out of memory in memory space vmem". Which
# shapes take the kernels is a routing decision made before the call, so
# the footprint is estimated here: per pass, every BlockSpec window in and
# out (double-buffered by the pipeline), the f32 accumulator scratch, and
# one f32 [block_q, block_k] score tile. The loop walk adds nothing: its
# tiles are reused across iterations.
#
# The estimate is held to the compiler, not to memory of old failures:
# docs/flash_vmem_grid_v5e.jsonl records what libtpu 0.0.34 compiling for a
# described v5e accepted and refused over 252 (path, seq, head_dim, dtype,
# blocks) configs. Against a 12 MiB budget (v5e's scoped limit is 16 MiB;
# the margin covers Mosaic's own temporaries) the gate admits none of the
# 63 the compiler refused and refuses 23 of the 189 it accepted —
# tests/test_flash_vmem_clamp.py pins both counts. (The analytic gate this
# replaces charged one tile per loop step: it refused 69 configs the
# compiler accepts — the 4096-at-512/1024 capture it was fitted to among
# them — and admitted 3 it refuses.) Rejection routes through _supported,
# so strict mode raises and otherwise the shape takes the blockwise path.

_VMEM_BUDGET_MB_DEFAULT = 12


def _vmem_budget_bytes():
    return int(os.environ.get('PADDLE_TPU_FLASH_VMEM_BUDGET_MB',
                              _VMEM_BUDGET_MB_DEFAULT)) * 1024 * 1024


def _vmem_reason(n, m, d, itemsize):
    """None if every dispatched pass fits the scoped-VMEM budget, else a
    reason naming the worst pass, its estimate, and the knobs to turn."""
    row = d * itemsize      # bytes of one [1, d] operand row
    acc = d * 4             # ... and of one f32 accumulator row
    # (pass, block_q, block_k, window rows in+out, f32 scratch rows)
    if _use_long_path(n, m):
        bq, bk = _long_blocks(n, m)
        passes = [('long fwd', bq, bk, 2 * bq + 2 * bk, bq),
                  ('long dq', bq, bk, 3 * bq + 2 * bk, bq),
                  ('long dk/dv', bq, bk, 2 * bq + 4 * bk, 2 * bk)]
    else:
        bq, bk = _std_blocks(n, m)
        bqb, bkb = _std_bwd_blocks(n, m)
        passes = [('fwd', bq, bk, 2 * m + 2 * bq, 0)]
        if bqb == n and bkb == m and _fused_bwd_enabled():
            passes.append(('fused bwd', n, m, 3 * n + 4 * m, 0))
        else:
            passes.append(('dq', bqb, bkb, 2 * m + 3 * bqb, 0))
            passes.append(('dk/dv', bqb, bkb, 2 * n + 4 * bkb, 0))
    budget = _vmem_budget_bytes()
    for name, pbq, pbk, window_rows, scratch_rows in passes:
        est = 2 * window_rows * row + scratch_rows * acc + pbq * pbk * 4
        if est > budget:
            return ('blocks (%d, %d) at seq (%d, %d) cannot fit: the %s '
                    'pass needs ~%.1f MiB scoped VMEM (double-buffered '
                    'operand windows plus a %dx%d f32 score tile) but the '
                    'budget is %d MiB (PADDLE_TPU_FLASH_VMEM_BUDGET_MB); '
                    'shrink the PADDLE_TPU_FLASH_BLOCK_* knobs or lower '
                    'PADDLE_TPU_FLASH_LONG_SEQ to take the long-kernel '
                    'path'
                    % (pbq, pbk, n, m, name, est / 2 ** 20, pbq, pbk,
                       budget // 2 ** 20))
    return None


def _fwd_kernel_long(q_ref, k_ref, v_ref, o_ref, lse_ref,
                     m_scr, l_scr, acc_scr, *, scale, causal, num_kb,
                     block_q, block_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: whole block above the diagonal contributes nothing
    diag_ok = True
    if causal:
        diag_ok = kb * block_k <= (qi + 1) * block_q - 1

    @pl.when(diag_ok)
    def _step():
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        s = _mm_f32(q, k_blk, transpose_b=True) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, kb * block_k)
        m_prev = m_scr[...][:, 0]
        l_prev = l_scr[...][:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + \
            _mm_f32(p.astype(v_blk.dtype), v_blk)
        m_scr[...] = m_cur[:, None]
        l_scr[...] = l_cur[:, None]

    @pl.when(kb == num_kb - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[...] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...][:, 0] + jnp.log(l_safe))[:, None]


def _fwd_impl_long(q, k, v, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n, d = q.shape
    m = k.shape[2]
    block_q, block_k = _long_blocks(n, m)
    num_kb = m // block_k

    grid = (b, h, n // block_q, num_kb)
    kernel = functools.partial(_fwd_kernel_long, scale=scale, causal=causal,
                               num_kb=num_kb, block_q=block_q,
                               block_k=block_k)
    kwargs = {}
    if interpret_mode():
        kwargs['interpret'] = True
    else:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    o, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b, h, n, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, n, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        **kwargs,
    )(q, k, v)
    return o, lse


def _bwd_dq_kernel_long(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_scr, *, scale, causal, num_kb,
                        block_q, block_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    diag_ok = True
    if causal:
        diag_ok = kb * block_k <= (qi + 1) * block_q - 1

    @pl.when(diag_ok)
    def _step():
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[...]
        delta = delta_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        s = _mm_f32(q, k_blk, transpose_b=True) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, kb * block_k)
        p = jnp.exp(jnp.minimum(s - lse, 30.0))  # see _bwd_dq_kernel
        dp = _mm_f32(do, v_blk, transpose_b=True)
        ds = p * (dp - delta) * scale
        dq_scr[...] = dq_scr[...] + _mm_f32(ds.astype(k_blk.dtype), k_blk)

    @pl.when(kb == num_kb - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_long(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                         num_qb, block_q, block_k):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qb = pl.program_id(3)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    diag_ok = True
    if causal:
        # rows strictly above the diagonal see nothing of this k block
        diag_ok = (qb + 1) * block_q - 1 >= ki * block_k

    @pl.when(diag_ok)
    def _step():
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        q_b = q_ref[...]
        do_b = do_ref[...]
        lse_b = lse_ref[...]
        delta_b = delta_ref[...]
        s = _mm_f32(q_b, k_blk, transpose_b=True) * scale
        if causal:
            s = _causal_mask(s, qb * block_q, ki * block_k)
        p = jnp.exp(jnp.minimum(s - lse_b, 30.0))
        dv_scr[...] = dv_scr[...] + _mm_f32(p.astype(do_b.dtype), do_b,
                                            transpose_a=True)
        dp = _mm_f32(do_b, v_blk, transpose_b=True)
        ds = p * (dp - delta_b) * scale
        dk_scr[...] = dk_scr[...] + _mm_f32(ds.astype(q_b.dtype), q_b,
                                            transpose_a=True)

    @pl.when(qb == num_qb - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_impl_long(q, k, v, o, lse, do, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n, d = q.shape
    m = k.shape[2]
    block_q, block_k = _long_blocks(n, m)
    num_kb = m // block_k
    num_qb = n // block_q

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [b, h, n, 1]

    kwargs = {}
    if interpret_mode():
        kwargs['interpret'] = True
    else:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    qspec = pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kspec_q = pl.BlockSpec((None, None, block_k, d),
                           lambda bi, hi, qi, ki: (bi, hi, ki, 0))
    rowq = pl.BlockSpec((None, None, block_q, 1),
                        lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_long, scale=scale, causal=causal,
                          num_kb=num_kb, block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((b, h, n, d), q.dtype),
        grid=(b, h, num_qb, num_kb),
        in_specs=[qspec, kspec_q, kspec_q, qspec, rowq, rowq],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        **kwargs,
    )(q, k, v, do, lse, delta)

    # dk/dv: k block is the parallel axis, q walk is sequential
    qspec_k = pl.BlockSpec((None, None, block_q, d),
                           lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    kspec = pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    rowq_k = pl.BlockSpec((None, None, block_q, 1),
                          lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_long, scale=scale, causal=causal,
                          num_qb=num_qb, block_q=block_q, block_k=block_k),
        out_shape=[jax.ShapeDtypeStruct((b, h, m, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, m, d), v.dtype)],
        grid=(b, h, m // block_k, num_qb),
        in_specs=[qspec_k, kspec, kspec, qspec_k, rowq_k, rowq_k],
        out_specs=[kspec, kspec],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        **kwargs,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# -- backward ----------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_k, seq_k):
    from jax.experimental import pallas as pl

    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[...]     # [bq, 1]
    delta = delta_ref[...]  # [bq, 1]
    block_q, head_dim = q.shape
    qi = pl.program_id(2)

    def update(dq_prev, rows, q_start, k_start, width, masked):
        # rows: (q, do, lse, delta) of the query rows; keys [k_start, +width)
        q_r, do_r, lse_r, delta_r = rows
        k_blk = k_ref[pl.ds(k_start, width), :]
        v_blk = v_ref[pl.ds(k_start, width), :]
        s = _mm_f32(q_r, k_blk, transpose_b=True) * scale
        if masked:
            s = _causal_mask(s, q_start, k_start)
        # clamped exp: for valid rows s - lse <= ~0; the headroom only
        # matters when a caller (ring attention) zero-weights a block it
        # computed unmasked — without the clamp an overflowing exp would
        # turn 0 * inf into NaN
        p = jnp.exp(jnp.minimum(s - lse_r, 30.0))
        dp = _mm_f32(do_r, v_blk, transpose_b=True)
        ds = p * (dp - delta_r) * scale
        return dq_prev + _mm_f32(ds.astype(k_blk.dtype), k_blk)

    def walk(dq, lo, hi, masked):
        return jax.lax.fori_loop(lo, hi, lambda kb, acc: update(
            acc, (q, do, lse, delta), qi * block_q, kb * block_k, block_k,
            masked), dq)

    dq = jnp.zeros((block_q, head_dim), jnp.float32)
    if not causal:
        dq_ref[...] = walk(dq, 0, seq_k // block_k, False).astype(
            dq_ref.dtype)
        return
    unmasked, diagonal = _causal_plan(qi, block_q, block_k, seq_k, 'k')
    dq = walk(dq, *unmasked)
    sub = _diag_sub_block(block_q, block_k)
    if sub is None:
        dq_ref[...] = walk(dq, *diagonal).astype(dq_ref.dtype)
        return
    # the diagonal is tile qi: row sub-block r sees keys up to its last row
    q0 = qi * block_q
    for r in range(block_q // sub):
        rows = slice(r * sub, (r + 1) * sub)
        part = update(dq[rows], tuple(x[rows] for x in (q, do, lse, delta)),
                      q0 + r * sub, q0, (r + 1) * sub, True)
        dq_ref[rows, :] = part.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, seq_q):
    from jax.experimental import pallas as pl

    k_blk = k_ref[...]
    v_blk = v_ref[...]
    block_k, head_dim = k_blk.shape
    ki = pl.program_id(2)

    def update(carry, k_cols, v_cols, k_start, q_start, width, masked):
        # keys k_cols (from k_start) against queries [q_start, +width)
        dk_prev, dv_prev = carry
        q_b = q_ref[pl.ds(q_start, width), :]
        do_b = do_ref[pl.ds(q_start, width), :]
        lse_b = lse_ref[pl.ds(q_start, width), :]      # [w, 1]
        delta_b = delta_ref[pl.ds(q_start, width), :]  # [w, 1]
        s = _mm_f32(q_b, k_cols, transpose_b=True) * scale  # [w, bk]
        if masked:
            s = _causal_mask(s, q_start, k_start)
        p = jnp.exp(jnp.minimum(s - lse_b, 30.0))  # see the dq kernel
        dv_cur = dv_prev + _mm_f32(p.astype(do_b.dtype), do_b,
                                   transpose_a=True)
        dp = _mm_f32(do_b, v_cols, transpose_b=True)  # [w, bk]
        ds = p * (dp - delta_b) * scale
        dk_cur = dk_prev + _mm_f32(ds.astype(q_b.dtype), q_b,
                                   transpose_a=True)
        return dk_cur, dv_cur

    def walk(carry, lo, hi, masked):
        return jax.lax.fori_loop(lo, hi, lambda qb, c: update(
            c, k_blk, v_blk, ki * block_k, qb * block_q, block_q, masked),
            carry)

    def finish(carry, cols):
        dk, dv = carry
        dk_ref[cols, :] = dk.astype(dk_ref.dtype)
        dv_ref[cols, :] = dv.astype(dv_ref.dtype)

    carry = (jnp.zeros((block_k, head_dim), jnp.float32),
             jnp.zeros((block_k, head_dim), jnp.float32))
    if not causal:
        finish(walk(carry, 0, seq_q // block_q, False), slice(None))
        return
    diagonal, unmasked = _causal_plan(ki, block_q, block_k, seq_q, 'q')
    sub = _diag_sub_block(block_q, block_k)
    if sub is None:
        finish(walk(walk(carry, *diagonal), *unmasked), slice(None))
        return
    # the diagonal is tile ki, walked last: column sub-block c is seen by
    # the queries from its own first column on
    carry = walk(carry, *unmasked)
    k0 = ki * block_k
    for c in range(block_k // sub):
        cols = slice(c * sub, (c + 1) * sub)
        part = update(tuple(x[cols] for x in carry), k_blk[cols],
                      v_blk[cols], k0 + c * sub, k0 + c * sub,
                      block_k - c * sub, True)
        finish(part, cols)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, causal):
    """Single-cell backward: dq, dk, dv from ONE kernel invocation.

    When one (block_q, block_k) tile covers the whole [n, m] score
    matrix (the seq-512 training shape at the 512/512 defaults), the
    two-pass backward wastes work: the dq pass and the dk/dv pass each
    recompute s, p and dp (8 MXU contractions total). Computing them
    once and emitting all three grads needs 5. One pallas_call per
    (b, h) also halves the Mosaic dispatches."""
    q = q_ref[...]
    k_blk = k_ref[...]
    v_blk = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[...]      # [n, 1]
    delta = delta_ref[...]  # [n, 1]
    s = _mm_f32(q, k_blk, transpose_b=True) * scale
    if causal:
        s = _causal_mask(s, 0, 0)
    p = jnp.exp(jnp.minimum(s - lse, 30.0))  # clamp: see _bwd_dq_kernel
    dp = _mm_f32(do, v_blk, transpose_b=True)
    ds = p * (dp - delta) * scale
    dq_ref[...] = _mm_f32(ds.astype(k_blk.dtype),
                          k_blk).astype(dq_ref.dtype)
    dk_ref[...] = _mm_f32(ds.astype(q.dtype), q,
                          transpose_a=True).astype(dk_ref.dtype)
    dv_ref[...] = _mm_f32(p.astype(do.dtype), do,
                          transpose_a=True).astype(dv_ref.dtype)


def _bwd_impl_fused(q, k, v, lse, do, delta, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n, d = q.shape
    m = k.shape[2]
    kwargs = {}
    if interpret_mode():
        kwargs['interpret'] = True
    else:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    full_q = pl.BlockSpec((None, None, n, d), lambda bi, hi: (bi, hi, 0, 0))
    full_k = pl.BlockSpec((None, None, m, d), lambda bi, hi: (bi, hi, 0, 0))
    full_rowq = pl.BlockSpec((None, None, n, 1),
                             lambda bi, hi: (bi, hi, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal),
        out_shape=[jax.ShapeDtypeStruct((b, h, n, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, m, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, m, d), v.dtype)],
        grid=(b, h),
        in_specs=[full_q, full_k, full_k, full_q, full_rowq, full_rowq],
        out_specs=[full_q, full_k, full_k],
        **kwargs,
    )(q, k, v, do, lse, delta)


def _fused_bwd_enabled():
    # re-read the env (not the import-latched copy): tests A/B this knob
    # in-process, and a kernel choice — unlike a block size — changes no
    # traced shapes, so late reads can't mix layouts. The default comes
    # from the ONE knob table (ops/flash_defaults.py).
    return _fd.resolve()['fused_bwd']


def _bwd_impl(q, k, v, o, lse, do, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n, d = q.shape
    m = k.shape[2]
    block_q, block_k = _std_bwd_blocks(n, m)

    # delta = rowsum(do * o): one fused elementwise+reduce, tiny vs the
    # kernel FLOPs — leave it to XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [b, h, n, 1]

    if block_q == n and block_k == m and _fused_bwd_enabled():
        # one tile covers the whole score matrix: single fused kernel
        return _bwd_impl_fused(q, k, v, lse, do, delta, causal, scale)
    if causal:
        _count_tiles('dq', b * h, n, block_q, block_k)
        _count_tiles('dkv', b * h, n, block_q, block_k)

    kwargs = {}
    if interpret_mode():
        kwargs['interpret'] = True
    else:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    qspec = pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, i: (bi, hi, i, 0))
    full_q = pl.BlockSpec((None, None, n, d), lambda bi, hi, i: (bi, hi, 0, 0))
    full_k = pl.BlockSpec((None, None, m, d), lambda bi, hi, i: (bi, hi, 0, 0))
    rowq = pl.BlockSpec((None, None, block_q, 1),
                        lambda bi, hi, i: (bi, hi, i, 0))
    full_rowq = pl.BlockSpec((None, None, n, 1),
                             lambda bi, hi, i: (bi, hi, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=m),
        out_shape=jax.ShapeDtypeStruct((b, h, n, d), q.dtype),
        grid=(b, h, n // block_q),
        in_specs=[qspec, full_k, full_k, qspec, rowq, rowq],
        out_specs=qspec,
        **kwargs,
    )(q, k, v, do, lse, delta)

    kspec = pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, i: (bi, hi, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_q=n),
        out_shape=[jax.ShapeDtypeStruct((b, h, m, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, m, d), v.dtype)],
        grid=(b, h, m // block_k),
        in_specs=[full_q, kspec, kspec, full_q, full_rowq, full_rowq],
        out_specs=[kspec, kspec],
        **kwargs,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# -- custom-vjp wiring -------------------------------------------------------

# `flash.fwd` / `flash.bwd` are jax.named_scope metadata on the kernels'
# device ops (the caller's scope, `gpt.attn.core`, stays around them): a
# device trace then tells the forward kernel, its recomputation and the
# backward kernels apart by name, not by guess.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bhnd(q, k, v, causal, scale):
    o, _ = _dispatch_fwd(q, k, v, causal, scale)
    return o


def _dispatch_fwd(q, k, v, causal, scale):
    """Returns (o, lse_or_None); lse None means the blockwise path ran.

    Cross-length causal is a semantics contract, not a capability gap, so
    strict mode does not apply to it; every other ineligible shape raises
    under strict mode. A chosen kernel is called bare: what it raises,
    the caller sees."""
    cross_causal = causal and q.shape[2] != k.shape[2]
    reason = None if cross_causal else _supported(q, k, v)
    if reason is not None and strict_mode():
        raise RuntimeError(
            'PADDLE_TPU_FLASH_STRICT=1 but the Pallas flash kernel '
            'cannot run: ' + reason)
    if cross_causal or reason is not None:
        from .blockwise_attention import blockwise_attention_bnhd
        return blockwise_attention_bnhd(q, k, v, causal=causal,
                                        scale=scale), None
    impl = _fwd_impl_long if _use_long_path(q.shape[2], k.shape[2]) \
        else _fwd_impl
    with jax.named_scope('flash.fwd'):
        return impl(q, k, v, causal, scale)


def _fwd_rule(q, k, v, causal, scale):
    o, lse = _dispatch_fwd(q, k, v, causal, scale)
    return o, (q, k, v, o, lse)


def _bwd_rule(causal, scale, res, do):
    q, k, v, o, lse = res
    if lse is None:
        # the forward routed to blockwise: differentiate that same path
        from .blockwise_attention import blockwise_attention_bnhd
        _, vjp = jax.vjp(lambda a, b, c: blockwise_attention_bnhd(
            a, b, c, causal=causal, scale=scale), q, k, v)
        return vjp(do)
    impl = _bwd_impl_long if _use_long_path(q.shape[2], k.shape[2]) \
        else _bwd_impl
    with jax.named_scope('flash.bwd'):
        return impl(q, k, v, o, lse, do, causal, scale)


_flash_bhnd.defvjp(_fwd_rule, _bwd_rule)


# -- public API --------------------------------------------------------------

# GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map"), so a
# step jitted over a mesh names the layout its [B, N, H, D] attention
# operands have — batch over the data axes, heads over the tensor-parallel
# axis — and inside that scope every device runs the kernels on its own
# shard. A ContextVar for the reason fused_ce.logits_sharding is one:
# concurrent traces must not see each other's mesh.
_PARTITION = contextvars.ContextVar('flash_attention_partition',
                                    default=None)


@contextlib.contextmanager
def partitioned(sharding):
    """Inside this scope the kernels run under jax.shard_map over
    `sharding`, the NamedSharding of the [B, N, H, D] operands."""
    token = _PARTITION.set(sharding)
    try:
        yield
    finally:
        _PARTITION.reset(token)


def flash_attention_bnhd(q, k, v, causal=False, scale=None):
    """Paddle layout [B, N, H, D] in/out."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def local(q, k, v):
        o = _flash_bhnd(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), causal, scale)
        return jnp.swapaxes(o, 1, 2)

    part = _PARTITION.get()
    if part is None:
        return local(q, k, v)
    return jax.shard_map(local, mesh=part.mesh, in_specs=(part.spec,) * 3,
                         out_specs=part.spec, check_vma=False)(q, k, v)


def flash_attention_bhnd(q, k, v, causal=False, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_bhnd(q, k, v, causal, scale)
