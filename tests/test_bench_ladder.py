"""bench.py capture-row helpers: a capture row maps back to the FULL knob
env that produced it (every knob pinned both ways, legacy rows at their
era's values), and two spellings of one effective config compare equal —
what tools/check_bench_regression.py buckets rows by."""
import importlib.util
import os


def _bench():
    spec = importlib.util.spec_from_file_location(
        'bench_mod', os.path.join(os.path.dirname(__file__), os.pardir,
                                  'bench.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_capture_replay_env_fully_pinned():
    b = _bench()
    env = b._capture_replay_env({
        'scan_steps': 8, 'fused_ce': True, 'flash_in_program': True,
        'qkv_split': 'last', 'attn_impl': 'auto', 'fused_ce_chunk': 8192,
        'flash_block_q': 128, 'flash_block_k': 128,
        'flash_block_q_bwd': 256, 'flash_block_k_bwd': 128,
        'flash_block_q_long': 512, 'flash_block_k_long': 2048,
        'flash_long_seq': 2048, 'batch': 32, 'seq': 512})
    assert env['PADDLE_TPU_BENCH_SCAN_STEPS'] == '8'
    assert env['PADDLE_TPU_FUSED_CE'] == '1'
    assert env['PADDLE_TPU_QKV_SPLIT'] == 'last'
    assert env['PADDLE_TPU_FUSED_CE_CHUNK'] == '8192'
    assert env['PADDLE_TPU_FLASH_BLOCK_Q'] == '128'
    assert env['PADDLE_TPU_FLASH_BLOCK_K'] == '128'
    assert env['PADDLE_TPU_FLASH_BLOCK_Q_BWD'] == '256'
    assert env['PADDLE_TPU_FLASH_BLOCK_K_BWD'] == '128'
    assert env['PADDLE_TPU_FLASH_BLOCK_Q_LONG'] == '512'
    assert env['PADDLE_TPU_FLASH_BLOCK_K_LONG'] == '2048'
    assert env['PADDLE_TPU_FLASH_LONG_SEQ'] == '2048'
    # flash ran: disable pinned OFF and strict pinned ON — an inherited
    # FLASH_DISABLE=1 or STRICT=0 must not survive the replay
    assert env['PADDLE_TPU_FLASH_DISABLE'] == '0'
    assert env['PADDLE_TPU_FLASH_STRICT'] == '1'
    assert env['PADDLE_TPU_BENCH_BATCH'] == '32'
    assert env['PADDLE_TPU_BENCH_SEQ'] == '512'

    env = b._capture_replay_env({
        'scan_steps': 0, 'fused_ce': False, 'flash_in_program': False,
        'attn_impl': 'blockwise', 'blockwise_block': 128,
        'batch': 32, 'seq': 512})
    assert env['PADDLE_TPU_FLASH_DISABLE'] == '1'
    assert env['PADDLE_TPU_FLASH_STRICT'] == '0'
    assert env['PADDLE_TPU_FUSED_CE'] == '0'
    assert env['PADDLE_TPU_ATTN_IMPL'] == 'blockwise'
    assert env['PADDLE_TPU_BLOCKWISE_BLOCK'] == '128'
    assert env['PADDLE_TPU_BENCH_SCAN_STEPS'] == '0'
    # knobs the row never recorded still get pinned — at the ERA
    # values (this row has no block fields, so it predates them:
    # 256/512 was that code's default)
    assert env['PADDLE_TPU_QKV_SPLIT'] == 'headaxis'
    assert env['PADDLE_TPU_FLASH_BLOCK_Q'] == '256'
    assert env['PADDLE_TPU_FLASH_BLOCK_Q_BWD'] == '256'
    assert env['PADDLE_TPU_FLASH_BLOCK_K_LONG'] == '512'


def test_capture_replay_env_legacy_rows_pin_era_values():
    b = _bench()
    env = b._capture_replay_env({
        'scan_steps': 8, 'fused_ce': False, 'flash_in_program': True,
        'batch': 32, 'seq': 512})  # r4-era row: block knobs predate it
    assert env['PADDLE_TPU_FLASH_BLOCK_Q'] == '256'
    assert env['PADDLE_TPU_FLASH_BLOCK_K'] == '512'
    assert env['PADDLE_TPU_FLASH_BLOCK_Q_BWD'] == '256'
    assert env['PADDLE_TPU_FLASH_BLOCK_Q_LONG'] == '256'
    assert env['PADDLE_TPU_FLASH_BLOCK_K_LONG'] == '512'
    # legacy router was '> 4096', i.e. today's '>= 4097'
    assert env['PADDLE_TPU_FLASH_LONG_SEQ'] == '4097'
    # the fused backward kernel postdates this row: two-pass pinned
    assert env['PADDLE_TPU_FLASH_FUSED_BWD'] == '0'


def test_effective_env_dedup():
    b = _bench()
    # a partial knob env and the full env of a capture it produced must
    # compare EQUAL as effective configs (one regression-gate bucket)
    ladder_head = {'PADDLE_TPU_BENCH_SCAN_STEPS': '8'}
    replay = b._capture_replay_env({
        'scan_steps': 8, 'fused_ce': True, 'flash_in_program': True,
        'qkv_split': 'headaxis', 'attn_impl': 'auto',
        'fused_ce_chunk': 4096, 'flash_block_q': 512,
        'flash_block_k': 512, 'flash_block_q_bwd': 512,
        'flash_block_k_bwd': 512, 'flash_block_q_long': 512,
        'flash_block_k_long': 1024, 'flash_long_seq': 4096,
        'flash_fused_bwd': True, 'batch': 32, 'seq': 512})
    assert b._effective_env(ladder_head) == b._effective_env(replay)
    # but a genuinely different config (qkv last) stays distinct
    replay2 = dict(replay, PADDLE_TPU_QKV_SPLIT='last')
    assert b._effective_env(ladder_head) != b._effective_env(replay2)
