"""Record a small device trace and write it in the neutral form that
`benchlib/trace.py` reduces (the source of `data/recorded_trace.json`),
with a listing of the planes, lines and event statistics the profiler
gives on this machine. Run on the chip:

    python benchmarks/tools/record_trace.py chiprun_out/probe
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..'))
sys.path.insert(0, os.getcwd())


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from benchlib import trace as T
    os.makedirs(out_dir, exist_ok=True)
    dev = jax.devices()[0]
    print('device', dev.platform, dev.device_kind, len(jax.devices()))
    print('memory_stats keys', sorted((dev.memory_stats() or {}).keys()))
    print('cpu devices', jax.devices('cpu')[:1])
    print('big seed key', jax.random.PRNGKey((2 ** 31 + 12345) & 0x7FFFFFFF))

    from paddle_tpu.nn import functional as F
    from paddle_tpu.framework.core import Tensor

    def _decode_fn(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=4)[0]

    def _train_fn(q, k, v):
        def loss(q, k, v):
            o = F.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True,
                dropout_p=0.0)
            return jnp.sum(o._data.astype(jnp.float32) ** 2)
        return jax.grad(jax.checkpoint(loss), (0, 1, 2))(q, k, v)

    dec = jax.jit(_decode_fn)
    trn = jax.jit(_train_fn)
    w = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    x = jnp.ones((256, 2048), jnp.bfloat16)
    q = jnp.ones((2, 1024, 4, 64), jnp.bfloat16) * 0.1
    dec(w, x).block_until_ready()
    jax.block_until_ready(trn(q, q, q))

    tdir = tempfile.mkdtemp(prefix='probe_trace_')
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation('bench.window'):
        for i in range(3):
            with jax.profiler.TraceAnnotation('bench.engine_step'):
                y = dec(w, x)
                y.block_until_ready()
            with jax.profiler.TraceAnnotation('bench.next_batch'):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation('bench.step_dispatch'):
                g = trn(q, q, q)
            with jax.profiler.TraceAnnotation('bench.loss_fetch'):
                jax.block_until_ready(g)
    jax.profiler.stop_trace()

    path = T.find_xplane(tdir)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    with open(os.path.join(out_dir, 'listing.txt'), 'w') as f:
        for plane in data.planes:
            f.write('PLANE %r\n' % plane.name)
            for line in plane.lines:
                evs = list(line.events)
                f.write('  LINE %r events=%d\n' % (line.name, len(evs)))
                for ev in evs[:12]:
                    stats = {k: (v if not isinstance(v, (bytes, str))
                                 else str(v)[:120]) for k, v in ev.stats}
                    f.write('    %r start=%d dur=%d stats=%r\n'
                            % (ev.name, ev.start_ns, ev.duration_ns, stats))

    def keep(plane, line):
        return bool(T.DEVICE_PLANE.match(plane)) and line in (
            T.OPS_LINE, T.MODULES_LINE) or not plane.startswith('/device')
    neutral = T.load_xplane(path, keep)
    # keep host lines that carry bench spans only
    for plane in neutral['planes']:
        if not T.DEVICE_PLANE.match(plane['name']):
            for line in plane['lines']:
                line['events'] = [e for e in line['events']
                                  if e[0].startswith(T.SPAN_PREFIX)]
            plane['lines'] = [ln for ln in plane['lines'] if ln['events']]
    neutral['planes'] = [p for p in neutral['planes'] if p['lines']]
    neutral['device'] = {'platform': dev.platform, 'kind': dev.device_kind}
    with open(os.path.join(out_dir, 'recorded_trace.json'), 'w') as f:
        json.dump(neutral, f, separators=(',', ':'))
    red = T.reduce_trace(neutral)
    print(json.dumps({k: red[k] for k in ('window_s', 'busy_s', 'chips',
                                          'gaps')}))
    print(json.dumps(T.breakdown(red)))
    print({k: len(v) for k, v in red['modules'].items()})


if __name__ == '__main__':
    main(sys.argv[1])
