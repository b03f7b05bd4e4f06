"""What the per-layer readers (`metrics/<name>.py`) share. A reader takes
the run's observation dict and returns a number, or None when it finds
nothing to read — never 0 for a share of a roofline or of a peak."""
from . import counts
from .stats import percentile
from .trace import ops_matching


def gen_lag_p99_ms(obs):
    return percentile(obs.get('gen_lag_ms'), 99)


def queue_wait_p90_ms(obs):
    return percentile(obs.get('queue_wait_ms'), 90)


def ttft_p50_ms(obs):
    return percentile(obs.get('ttft_ms'), 50)


def tpot_p50_ms(obs):
    return percentile(obs.get('tpot_ms'), 50)


def batch_occupancy(obs):
    """Mean share of the engine's slots in use, per engine step."""
    steps = obs.get('steps')
    if not steps:
        return None
    slots = obs['engine']['num_seqs']
    return 100.0 * sum(s[2] for s in steps) / (len(steps) * slots)


def pages_in_use_peak(obs):
    steps = obs.get('steps')
    return max(s[3] for s in steps) if steps else None


def prefix_hit_share(obs):
    """Prompt tokens served from the prefix cache over prompt tokens, of
    the requests due in the window."""
    if obs.get('kind') != 'serve':
        return None
    t0, t_end = obs['t0'], obs['t_end']
    recs = [r for r in obs['recs'] if t0 <= r.due < t_end
            and r.admit_t is not None]
    total = sum(len(obs['trace_obj'].prompts[r.idx]) for r in recs)
    if not total:
        return None
    return 100.0 * sum(r.prefix_hit for r in recs) / total


def decode_burst_ms_p50(obs):
    return obs.get('burst_ms_p50')


def serve_mfu_pct(obs):
    """Model FLOPs of the tokens processed in the window (2 per
    multiplied parameter a token + attention over the context held) over
    the time of the window's engine steps x peak."""
    if obs.get('kind') != 'serve' or not obs.get('flops'):
        return None
    return 100.0 * obs['flops'] / (obs['span_s'] * obs['peaks'][0]
                                   * obs['chips'])


def train_mfu_pct(obs):
    if obs.get('kind') != 'train':
        return None
    m = obs['model']
    per_token = counts.train_flops_per_token(m, obs['step_cfg']['seq_len'])
    return 100.0 * per_token * obs['train_tokens_per_s'] / (
        obs['peaks'][0] * obs['chips'])


def train_step_ms_p50(obs):
    return percentile(obs.get('step_ms'), 50)


def data_wait_share(obs):
    if obs.get('kind') != 'train':
        return None
    return 100.0 * obs['data_wait_s'] / obs['window_s']


def device_idle_share(obs):
    red = obs.get('reduced')
    if not red or red['busy_s'] <= 0:
        return None
    return 100.0 * (1.0 - red['busy_s'] / red['window_s'])


def decode_step_roofline(obs, program='_decode_fn'):
    """The least time of the decode steps the traced bursts made (weights
    once + the K/V of the tokens HELD, or the operations, whichever takes
    longer) over the device time of those bursts' program runs."""
    red = obs.get('reduced')
    if not red or obs.get('kind') != 'serve':
        return None
    runs = red['modules'].get(program)
    t0, t1 = obs.get('trace_t0'), obs.get('trace_t1')
    bursts = [b for b in obs.get('burst_least', ())
              if t0 <= b[0] and b[1] <= t1]
    if not runs or not bursts:
        return None
    least = sum(b[2] for b in bursts) / len(bursts)
    return 100.0 * least / (sum(runs) / len(runs))


def flash_seconds(obs, needle='tpu_custom_call'):
    """Device seconds of the Pallas kernels: in the train step they are
    the flash-attention forward and backward, and nothing else."""
    red = obs.get('reduced')
    return (ops_matching(red, needle) or None) if red else None


def flash_time_share(obs):
    got = flash_seconds(obs)
    if got is None:
        return None
    red = obs['reduced']
    return 100.0 * got / (red['busy_s'] * red['chips'])


def flash_roofline(obs):
    """The least time of the flash kernels the traced steps ran, counted
    from shapes (forward twice under recomputation, backward once, per
    layer and step), over their device time."""
    got = flash_seconds(obs)
    if got is None or obs.get('kind') != 'train' or \
            not obs.get('trace_steps'):
        return None
    m, sc = obs['model'], obs['step_cfg']
    calls = (2 if sc['recompute'] else 1, 1)
    least = counts.flash_least_seconds(
        m, sc['batch'], sc['seq_len'], obs['peaks'][0], obs['peaks'][1],
        calls) * m['n_layer'] * obs['trace_steps']
    return 100.0 * least / got


def compile_s(obs):
    return obs.get('compile_s')


def compile_cache_misses(obs):
    return obs.get('compile_cache_misses')


def recompiles_in_window(obs):
    return obs.get('recompiles_in_window')
