"""fleet.utils (reference: fleet/utils/: recompute, fs, hybrid_parallel_util)."""
import os
import shutil

__all__ = ['recompute', 'LocalFS', 'HDFSClient']


def recompute(function, *args, **kwargs):
    """Activation recomputation (reference: fleet/utils/recompute.py:63
    RecomputeFunction). TPU-native: jax.checkpoint(remat) — XLA rematerializes
    the segment in backward instead of saving its activations.

    When `function` is a Layer, its parameters are passed as EXPLICIT vjp
    inputs (run_op only flows gradients to explicit inputs — closing over
    them would silently drop param grads in eager mode)."""
    import jax
    from ...framework.core import Tensor, run_op
    kwargs.pop('preserve_rng_state', True)
    tensor_args = [a for a in args if isinstance(a, Tensor)]

    if hasattr(function, 'named_parameters'):
        from ...framework import functional as func_mod
        named = list(function.named_parameters())
        pnames = [n for n, _ in named]
        ptensors = [p for _, p in named]
        buffers = func_mod.extract_buffers(function)
        bnames = list(buffers.keys())
        n_p = len(pnames)

        def layer_fn(*arrays):
            params = dict(zip(pnames, arrays[:n_p]))
            it = iter(arrays[n_p:])
            call_args = [Tensor(next(it), stop_gradient=False)
                         if isinstance(a, Tensor) else a for a in args]
            out, new_buf = func_mod.functional_call(
                function, params, buffers, args=call_args, kwargs=kwargs)
            outs = out if isinstance(out, tuple) else (out,)
            # buffer updates (BN running stats) ride along as extra
            # outputs; the caller writes them back into the live layer
            return tuple(outs) + tuple(new_buf[n] for n in bnames)

        def split_outs(flat):
            outs = flat[:len(flat) - len(bnames)]
            bmap = dict(function.named_buffers())
            for name, arr in zip(bnames, flat[len(flat) - len(bnames):]):
                arr = arr._data if isinstance(arr, Tensor) else arr
                if bmap.get(name) is not None:
                    bmap[name]._data = arr
            return outs[0] if len(outs) == 1 else tuple(outs)

        all_inputs = list(ptensors) + tensor_args
        if any(isinstance(t._data, jax.core.Tracer) for t in all_inputs):
            # inside an outer jax trace (TrainStep value_and_grad): call the
            # checkpointed fn DIRECTLY so the outer AD sees the remat
            # primitive — routing through run_op would jax.vjp it eagerly,
            # partial-evaluating the checkpoint into a plain
            # save-activations program (no memory win)
            flat = jax.checkpoint(layer_fn)(*[t._data for t in all_inputs])
            return split_outs(tuple(Tensor(o, stop_gradient=False)
                                    for o in flat))

        flat = run_op('recompute', jax.checkpoint(layer_fn),
                      *ptensors, *tensor_args)
        if not isinstance(flat, tuple):
            flat = (flat,)
        return split_outs(flat)

    def fn(*arrays):
        it = iter(arrays)
        call_args = [Tensor(next(it), stop_gradient=False)
                     if isinstance(a, Tensor) else a for a in args]
        out = function(*call_args, **kwargs)
        if isinstance(out, (tuple, list)):
            return tuple(o._data if isinstance(o, Tensor) else o for o in out)
        return out._data if isinstance(out, Tensor) else out

    return run_op('recompute', jax.checkpoint(fn), *tensor_args)


class LocalFS:
    """reference: fleet/utils/fs.py LocalFS."""

    def ls_dir(self, path):
        if not os.path.exists(path):
            return [], []
        dirs, files = [], []
        for name in os.listdir(path):
            (dirs if os.path.isdir(os.path.join(path, name))
             else files).append(name)
        return dirs, files

    def mkdirs(self, path):
        os.makedirs(path, exist_ok=True)

    def is_exist(self, path):
        return os.path.exists(path)

    def is_dir(self, path):
        return os.path.isdir(path)

    def is_file(self, path):
        return os.path.isfile(path)

    def delete(self, path):
        if os.path.isdir(path):
            shutil.rmtree(path, ignore_errors=True)
        elif os.path.exists(path):
            os.remove(path)

    def mv(self, src, dst, overwrite=False):
        if overwrite and os.path.exists(dst):
            self.delete(dst)
        shutil.move(src, dst)

    def upload(self, local, remote):
        shutil.copy(local, remote)

    def download(self, remote, local):
        shutil.copy(remote, local)

    def touch(self, path, exist_ok=True):
        open(path, 'a').close()


class HDFSClient(LocalFS):
    """HDFS via shell pipes in the reference (framework/io/fs.cc); this env
    has no HDFS. DECLARED shim: it warns at
    construction that it is LocalFS-backed (gcsfuse/NFS-mounted paths go
    through the LocalFS API) and raises on genuine `hdfs://` URIs rather
    than silently treating them as local paths."""

    _GUARDED = ('ls_dir', 'mkdirs', 'is_exist', 'is_dir', 'is_file',
                'delete', 'mv', 'upload', 'download', 'touch')

    def __init__(self, hadoop_home=None, configs=None):
        import warnings
        warnings.warn(
            'HDFSClient is LocalFS-backed in this build: paths are served '
            'by the local filesystem (mount HDFS via NFS/gcsfuse); '
            'hdfs:// URIs raise', stacklevel=2)
        # wrap once: instance attributes shadow the LocalFS methods
        for name in self._GUARDED:
            setattr(self, name, self._guard(getattr(self, name)))

    @staticmethod
    def _check_scheme(path):
        if isinstance(path, str) and path.startswith('hdfs://'):
            raise NotImplementedError(
                'no HDFS connectivity in this build — mount the data '
                'locally (NFS/gcsfuse) and pass the mounted path; got %r'
                % path)
        return path

    @classmethod
    def _guard(cls, fn):
        def guarded(*args, **kwargs):
            for a in args:
                cls._check_scheme(a)
            for a in kwargs.values():
                cls._check_scheme(a)
            return fn(*args, **kwargs)
        return guarded
