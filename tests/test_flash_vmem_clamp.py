"""Scoped-VMEM footprint gate for the flash-attention block clamp.

The divisibility clamp can pick a config Mosaic cannot hold, and the compiler
then fails the whole program ("Ran out of memory in memory space vmem"). The
gate decides BEFORE the call, so such a shape routes to blockwise (or raises
under strict mode) — and it is held to the compiler: docs/
flash_vmem_grid_v5e.jsonl records what libtpu 0.0.34, compiling the bare
kernels for a described v5e, accepted and refused over 252 configs
(tests/test_chip_v5e_compile.py keeps compiling the benchmark's shapes live).
"""
import json
import os

import jax.numpy as jnp
import pytest

from paddle_tpu.ops import flash_attention as fa

_GRID = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'docs', 'flash_vmem_grid_v5e.jsonl')


def _pin_blocks(monkeypatch, path, blocks):
    """Pin the import-latched knobs: `path` 'std' keeps the long kernels
    off, 'long' forces them; `blocks` are that path's (block_q, block_k)."""
    std = blocks if path == 'std' else (512, 512)
    monkeypatch.setattr(fa, '_LONG_SEQ', 10 ** 9 if path == 'std' else 0)
    monkeypatch.setattr(fa, '_DEFAULT_BLOCK_Q', std[0])
    monkeypatch.setattr(fa, '_DEFAULT_BLOCK_K', std[1])
    monkeypatch.setattr(fa, '_BLOCK_Q_BWD', std[0])
    monkeypatch.setattr(fa, '_BLOCK_K_BWD', std[1])
    if path == 'long':
        monkeypatch.setattr(fa, '_BLOCK_Q_LONG', blocks[0])
        monkeypatch.setattr(fa, '_BLOCK_K_LONG', blocks[1])
    monkeypatch.delenv('PADDLE_TPU_FLASH_INTERPRET', raising=False)
    monkeypatch.delenv('PADDLE_TPU_FLASH_VMEM_BUDGET_MB', raising=False)


def _mk(n, d=64, dtype=jnp.bfloat16):
    return jnp.zeros((1, 1, n, d), dtype)


def test_gate_never_admits_what_the_v5e_compiler_refused(monkeypatch):
    with open(_GRID) as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) == 252
    unsafe, over = [], []
    for r in rows:
        _pin_blocks(monkeypatch, r['path'], r['blocks'])
        admitted = fa._vmem_reason(r['seq'], r['seq'], r['d'],
                                   r['itemsize']) is None
        compiled = r['fwd'] and r['bwd']
        if admitted and not compiled:
            unsafe.append(r)
        if compiled and not admitted:
            over.append(r)
    # no fallback catches a kernel that fails to compile: admitting one
    # the compiler refuses is an error at the user's first step
    assert unsafe == []
    # the price of a closed-form estimate: configs routed to blockwise
    # that the kernels could have taken. Pinned so it only shrinks.
    assert len(over) <= 23, over


def test_rejects_a_config_the_compiler_refuses(monkeypatch):
    # long kernels at 2048/2048: the f32 score tile alone is 16 MiB
    _pin_blocks(monkeypatch, 'long', (2048, 2048))
    q = _mk(8192)
    reason = fa._supported(q, q, q)
    assert reason is not None
    assert 'VMEM' in reason and 'long fwd' in reason
    assert 'PADDLE_TPU_FLASH_VMEM_BUDGET_MB' in reason
    # strict mode: refuse loudly instead of routing to blockwise
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '1')
    with pytest.raises(RuntimeError, match='scoped VMEM'):
        fa.flash_attention_bhnd(q, q, q)
    # default knobs, wide f32 heads: K and V staged whole are 14.7 MiB
    _pin_blocks(monkeypatch, 'std', (512, 512))
    q = _mk(3584, d=256, dtype=jnp.float32)
    assert 'fwd' in fa._supported(q, q, q)


def test_accepts_what_the_compiler_accepts_at_the_captured_shapes(
        monkeypatch):
    # std 4096 @ 512/1024: the 2026-08-01 capture's "kernel-vmem-stack-oom"
    # (docs/bench_inwindow_r5.jsonl 09:32:35Z) that the first gate was
    # fitted to. Today's compiler takes it, so the gate must too.
    _pin_blocks(monkeypatch, 'std', (512, 1024))
    q = _mk(4096)
    assert fa._supported(q, q, q) is None
    # std 2048 @ 512/1024 (longseq2048_flash_bq512_bk1024: 148 ms)
    q = _mk(2048)
    assert fa._supported(q, q, q) is None
    # std 4096 @ 256/512 (fused_flash_seq4096_b4_scan2)
    _pin_blocks(monkeypatch, 'std', (256, 512))
    q = _mk(4096)
    assert fa._supported(q, q, q) is None
    # the seq-512 fused-backward headline config
    _pin_blocks(monkeypatch, 'std', (512, 512))
    q = _mk(512)
    assert fa._supported(q, q, q) is None
    # stock knobs route 4096 to the LONG kernels, which stage O(block)
    # and ran at 197.8 ms (longseq4096_longkern_bq512_bk1024)
    monkeypatch.setattr(fa, '_LONG_SEQ', 4096)
    q = _mk(4096)
    assert fa._supported(q, q, q) is None
    # and the 8k long rung at the wide 512/2048 KV block
    monkeypatch.setattr(fa, '_BLOCK_K_LONG', 2048)
    q = _mk(8192)
    assert fa._supported(q, q, q) is None


def test_budget_knob_moves_the_gate(monkeypatch):
    _pin_blocks(monkeypatch, 'long', (2048, 2048))
    q = _mk(8192)
    assert fa._supported(q, q, q) is not None
    # a larger chip's budget admits the config the v5e budget refuses
    monkeypatch.setenv('PADDLE_TPU_FLASH_VMEM_BUDGET_MB', '64')
    assert fa._supported(q, q, q) is None
    # a starved budget rejects even the headline config
    _pin_blocks(monkeypatch, 'std', (512, 512))
    monkeypatch.setenv('PADDLE_TPU_FLASH_VMEM_BUDGET_MB', '1')
    q = _mk(512)
    assert fa._supported(q, q, q) is not None


def test_interpreter_mode_skips_the_gate(monkeypatch):
    """The CPU interpreter has no VMEM: the correctness tests must keep
    running shapes the hardware budget would refuse."""
    _pin_blocks(monkeypatch, 'long', (2048, 2048))
    monkeypatch.setenv('PADDLE_TPU_FLASH_INTERPRET', '1')
    q = _mk(8192)
    assert fa._supported(q, q, q) is None
