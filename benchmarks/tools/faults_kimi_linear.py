"""`tools/faults.py` for the faults a Kimi-Linear cell's mechanisms can
have: plant one in the program and read the numbers that decide
`correct`, on the chip at the cell's own size. The benchmark's own runs
never run this.

    python3 benchmarks/tools/faults_kimi_linear.py \
        --workload serve-kimi-linear.long-answers --seed 7 --seconds 12 \
        --faults state_not_carried,decay_scalar,renorm_off,latent_norm_skipped

  state_not_carried    the chunked KDA rule starts every prefill call
                       from a zero state (the one-token recurrence of
                       the decode step is left alone)
  decay_scalar         the per-channel log decay is averaged over a
                       head's key channels: one scalar a head, the rule
                       of a gated delta net
  renorm_off           the chosen experts' weights are not divided by
                       their sum (`moe_renormalize` ignored)
  latent_norm_skipped  the latent c enters the cache and the attention
                       without its RMSNorm
  none                 nothing planted: the run must be `correct`

A fault names what it replaces by its dotted path inside the program's
module (`KimiLatentAttention._latent_norm`: a class's attribute).
"""
import contextlib
import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.getcwd())

import faults as _faults  # noqa: E402

MODULE = 'paddle_tpu.text.models.kimi_linear'


def _state_not_carried(mod):
    import jax.numpy as jnp
    rule = mod.chunked_kda_rule
    return {'chunked_kda_rule':
            lambda q, k, v, g, beta, state, *a: rule(
                q, k, v, g, beta, jnp.zeros_like(state), *a)}


def _decay_scalar(mod):
    import jax.numpy as jnp
    decay = mod._log_decay

    def one_a_head(f, a_log, dt_bias):
        g = decay(f, a_log, dt_bias)
        return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    return {'_log_decay': one_a_head}


def _renorm_off(mod):
    import jax
    import jax.numpy as jnp

    def unnormalised(x, w_r, bias, top_k, scale):
        s = jax.nn.sigmoid(jnp.einsum(
            'td,de->te', x.astype(jnp.float32), w_r.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        return chosen, jnp.take_along_axis(s, chosen, axis=-1) * scale
    return {'route': unnormalised}


def _latent_norm_skipped(mod):
    return {'KimiLatentAttention._latent_norm':
            staticmethod(lambda norm, c: c)}


FAULTS = {'state_not_carried': _state_not_carried,
          'decay_scalar': _decay_scalar, 'renorm_off': _renorm_off,
          'latent_norm_skipped': _latent_norm_skipped}


def _holder(mod, path):
    *owners, name = path.split('.')
    return functools.reduce(getattr, owners, mod), name


@contextlib.contextmanager
def planted(fault):
    """The program with `fault` in it ('none': as it is)."""
    if fault == 'none':
        yield
        return
    import importlib
    mod = importlib.import_module(MODULE)
    swap = FAULTS[fault](mod)
    kept = {}
    for path, value in swap.items():
        owner, name = _holder(mod, path)
        kept[path] = owner.__dict__[name]
        setattr(owner, name, value)
    try:
        yield
    finally:
        for path, value in kept.items():
            owner, name = _holder(mod, path)
            setattr(owner, name, value)


def main(argv=None):
    # the general tool's command line, planting this file's faults
    _faults.planted, _faults.__doc__ = planted, __doc__
    return _faults.main(argv)


if __name__ == '__main__':
    main()
