"""AnalysisPredictor-parity inference engine over AOT-compiled XLA.

Call stack parity (SURVEY.md §3.5): create_predictor(Config) loads the
jit.save artifact, "analysis" = jax.jit(...).lower().compile() per input
signature (cached), Run = cached-executable execution with buffer donation
of inputs (zero-copy contract).
"""
import os
import threading

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ['Config', 'AnalysisConfig', 'Predictor', 'AnalysisPredictor',
           'create_predictor', 'PrecisionType', 'PlaceType', 'Tensor']


class PrecisionType:
    Float32 = 0
    Half = 1
    Bfloat16 = 2
    Int8 = 3


class PlaceType:
    CPU = 0
    GPU = 1
    TPU = 4
    XPU = 2


class Config:
    """AnalysisConfig parity. The TensorRT/MKLDNN/IR switches are accepted;
    on TPU they all mean 'XLA compiles the whole graph' and only precision
    and device selection change behavior."""

    def __init__(self, model_path=None, params_path=None):
        self._model_path = model_path
        self._params_path = params_path
        self._device = 'tpu'
        self._device_id = 0
        self._precision = PrecisionType.Float32
        self._ir_optim = True
        self._memory_optim = True
        self._cache_dir = None
        self._trt = False
        self._cpu_math_threads = 1

    # -- model paths --------------------------------------------------------
    def set_model(self, model_path, params_path=None):
        self._model_path = model_path
        self._params_path = params_path

    def model_dir(self):
        return self._model_path

    def prog_file(self):
        return self._model_path

    def params_file(self):
        return self._params_path

    # -- device -------------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        # GPU request maps to the accelerator backend (TPU here)
        self._device = 'tpu'
        self._device_id = device_id

    def enable_tpu(self, device_id=0):
        self._device = 'tpu'
        self._device_id = device_id

    def disable_gpu(self):
        self._device = 'cpu'

    def use_gpu(self):
        return self._device == 'tpu'

    def gpu_device_id(self):
        return self._device_id

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_math_threads = n

    # -- optimization surface ------------------------------------------------
    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def ir_optim(self):
        return self._ir_optim

    def enable_memory_optim(self, flag=True):
        self._memory_optim = flag

    def switch_use_feed_fetch_ops(self, flag):
        pass

    def switch_specify_input_names(self, flag=True):
        pass

    def enable_tensorrt_engine(self, workspace_size=1 << 30, max_batch_size=1,
                               min_subgraph_size=3,
                               precision_mode=PrecisionType.Float32,
                               use_static=False, use_calib_mode=False):
        # TRT subgraph offload == whole-graph XLA on TPU; precision honored
        self._trt = True
        self._precision = precision_mode

    def tensorrt_engine_enabled(self):
        return self._trt

    def enable_mkldnn(self):
        pass

    def set_optim_cache_dir(self, path):
        self._cache_dir = path

    def enable_profile(self):
        pass

    def disable_glog_info(self):
        pass

    def summary(self):
        return ('device: %s, precision: %s, ir_optim(XLA): %s'
                % (self._device, self._precision, self._ir_optim))


AnalysisConfig = Config


class Tensor:
    """Input/output handle (ZeroCopyTensor parity)."""

    def __init__(self, name, predictor):
        self._name = name
        self._predictor = predictor

    def name(self):
        return self._name

    # input side
    def reshape(self, shape):
        self._predictor._input_shapes[self._name] = tuple(shape)

    def copy_from_cpu(self, data):
        self._predictor._check_input_name(self._name)
        self._predictor._inputs[self._name] = np.ascontiguousarray(data)

    def share_external_data(self, data):
        self._predictor._check_input_name(self._name)
        self._predictor._inputs[self._name] = np.asarray(data)

    # output side
    def copy_to_cpu(self):
        return np.asarray(self._predictor._outputs[self._name])

    def to_numpy(self):
        return self.copy_to_cpu()

    def shape(self):
        if self._name in self._predictor._outputs:
            return list(self._predictor._outputs[self._name].shape)
        return list(self._predictor._input_shapes.get(self._name, ()))

    def type(self):
        return PrecisionType.Float32


class Predictor:
    """AnalysisPredictor parity over a jit.save'd model."""

    def __init__(self, config):
        self._config = config
        self._inputs = {}
        self._outputs = {}
        self._input_shapes = {}
        self._compiled = {}
        self._lock = threading.Lock()
        self._load()

    @staticmethod
    def _is_fluid_artifact(path):
        """Reference-produced artifact? (__model__ / *.pdmodel ProgramDesc,
        analysis_predictor.cc:201 PrepareProgram's input format)."""
        if os.path.isdir(path):
            if os.path.exists(os.path.join(path, '__model__')):
                return True
            return any(f.endswith('.pdmodel') for f in os.listdir(path))
        return (path.endswith('.pdmodel')
                or os.path.basename(path) == '__model__')

    def _load_fluid(self, path):
        """Serve a reference-format model: ProgramDesc block 0 lowers to
        one XLA module via the fluid op table (fluid_program.py)."""
        from .fluid_program import load_fluid_model
        prog = load_fluid_model(path, self._config.params_file())
        self._fluid = prog
        self._layer = None
        self._translated = None
        self._buffers = {}
        self._params = prog.params
        self._input_names = list(prog.feed_names)

        def pure(params, *arrays):
            feeds = dict(zip(prog.feed_names, arrays))
            outs = prog._run_block(params, feeds)
            return tuple(outs) if len(outs) != 1 else outs[0]
        self._fn = pure

    def _enable_optim_cache(self):
        """Config.set_optim_cache_dir maps onto jax's persistent
        compilation cache (the reference persists its IR-pass/TensorRT
        engine cache there; here the compiled XLA executables persist, so
        a restarted server skips compilation entirely). Routed through
        framework.compile_cache — the one repo-wide configuration path —
        so several Predictors (or a Predictor plus the bench harness) in
        one process configure the cache once, idempotently, and a cache
        placed from outside (JAX_COMPILATION_CACHE_DIR) wins over this
        directory."""
        cache_dir = self._config._cache_dir
        if not cache_dir:
            return
        from ..framework import compile_cache
        compile_cache.configure(cache_dir)

    def _load(self):
        from .. import jit as jit_mod
        from ..framework import functional as func_mod
        path = self._config.model_dir()
        if path is None:
            raise ValueError('Config.set_model(path) required')
        self._enable_optim_cache()
        if self._is_fluid_artifact(path):
            self._load_fluid(path)
            return
        self._translated = jit_mod.load(path)
        layer = self._translated._layer
        if layer is None:
            raise RuntimeError('model artifact missing architecture payload')
        layer.eval()
        if self._config._precision == PrecisionType.Bfloat16:
            layer.bfloat16()
        self._layer = layer
        self._params = func_mod.extract_params(layer)
        self._buffers = func_mod.extract_buffers(layer)
        # input names from the saved input spec when available; otherwise
        # arity is unknown until run() and positional input_<i> names are
        # accepted open-endedly
        meta = getattr(self._translated, '_meta', None) or {}
        spec = meta.get('input_spec')
        if spec:
            self._input_names = [
                (s[2] if len(s) > 2 and s[2] else 'input_%d' % i)
                for i, s in enumerate(spec)]
        else:
            # no saved spec: derive arity from forward's required
            # positional params so get_input_names() stays discoverable;
            # variadic forwards stay fully dynamic (None)
            self._input_names = None
            import inspect
            try:
                sig = inspect.signature(layer.forward)
                ps = list(sig.parameters.values())
                if not any(p.kind == p.VAR_POSITIONAL for p in ps):
                    req = [p for p in ps
                           if p.kind in (p.POSITIONAL_ONLY,
                                         p.POSITIONAL_OR_KEYWORD)
                           and p.default is p.empty]
                    self._input_names = ['input_%d' % i
                                         for i in range(len(req))]
            except (TypeError, ValueError):
                pass
        self._fn = self._make_fn()

    def _make_fn(self):
        from ..framework import functional as func_mod
        layer = self._layer
        buffers = self._buffers

        def pure(params, *arrays):
            out, _ = func_mod.functional_call(layer, params, buffers,
                                              args=arrays, training=False)
            return out
        return pure

    def _check_input_name(self, name):
        if self._input_names is not None:
            if name not in self._input_names:
                raise ValueError(
                    'unknown input %r; model inputs are %s'
                    % (name, self._input_names))
        elif not (name.startswith('input_')
                  and name[len('input_'):].isdigit()):
            raise ValueError(
                'model was saved without an input spec; use positional '
                'names input_0..input_<n-1>, got %r' % name)

    def _gather_inputs(self):
        """Assemble run arguments in declared order, failing loudly on
        missing inputs instead of silently dropping them."""
        if self._input_names is not None:
            missing = [n for n in self._input_names if n not in self._inputs]
            if missing:
                raise ValueError('inputs not set: %s' % missing)
            return [self._inputs[n] for n in self._input_names]
        idx = sorted(int(n[len('input_'):]) for n in self._inputs)
        if idx != list(range(len(idx))):
            raise ValueError(
                'positional inputs must be contiguous input_0..input_%d, '
                'got %s' % (len(idx) - 1, sorted(self._inputs)))
        return [self._inputs['input_%d' % i] for i in idx]

    # -- handles -------------------------------------------------------------
    def get_input_names(self):
        if self._input_names is not None:
            return list(self._input_names)
        return sorted(self._inputs, key=lambda n: int(n[len('input_'):]))

    def get_input_handle(self, name):
        return Tensor(name, self)

    def get_input_tensor(self, name):
        return Tensor(name, self)

    def get_output_names(self):
        return list(self._outputs.keys()) or ['output_0']

    def get_output_handle(self, name):
        return Tensor(name, self)

    def get_output_tensor(self, name):
        return Tensor(name, self)

    # -- run ------------------------------------------------------------------
    def run(self, input_list=None):
        """ZeroCopyRun: compile-once per signature, then cached executes."""
        if input_list is not None:
            # paddle-inference python API: run([np arrays]) -> [np arrays]
            arrays = [np.asarray(a) for a in input_list]
        else:
            arrays = self._gather_inputs()
        sig = tuple((a.shape, str(a.dtype)) for a in arrays)
        with self._lock:
            if sig not in self._compiled:
                jitted = jax.jit(self._fn)
                lowered = jitted.lower(self._params,
                                       *[jnp.asarray(a) for a in arrays])
                self._compiled[sig] = lowered.compile()
            executable = self._compiled[sig]
        out = executable(self._params, *[jnp.asarray(a) for a in arrays])
        outs = out if isinstance(out, (list, tuple)) else [out]
        self._outputs = {'output_%d' % i: np.asarray(o)
                         for i, o in enumerate(outs)}
        if input_list is not None:
            return [self._outputs['output_%d' % i] for i in range(len(outs))]
        return True

    def clone(self):
        return Predictor(self._config)

    def decode_engine(self, **engine_kwargs):
        """Continuous-batching front door over the loaded model: a
        `serving.PagedContinuousBatchingEngine`, its constructor's
        keywords (num_seqs, max_len, page_size, num_pages,
        prefill_chunk, decode_block, spec_k, prefix_cache, ...) passed
        through.

        Only meaningful when the artifact is a causal LM with a cached
        decode path (GPTForCausalLM); anything else fails here with a
        clear error instead of deep inside the first step().
        """
        layer = self._layer
        if layer is None or not (hasattr(layer, 'generate')
                                 and hasattr(layer, 'gpt')
                                 and hasattr(layer, 'config')):
            raise TypeError(
                'decode_engine() needs a causal-LM artifact '
                '(GPTForCausalLM with a KV-cache decode path); loaded '
                'model is %s' % type(layer).__name__)
        from ..serving import PagedContinuousBatchingEngine
        return PagedContinuousBatchingEngine(layer, **engine_kwargs)

    def decode_gateway(self, replicas=2, router=None, autoscaler=None,
                       registry=None, **engine_kwargs):
        """Multi-replica serving front door: a ServingGateway whose
        replica factory clones this predictor's artifact into fresh
        decode engines (the reference's fleet-of-AnalysisPredictors
        deployment shape, in one process). Engine construction kwargs
        — num_seqs, max_len, page_size, ... — pass through to
        decode_engine() per replica."""
        # non-causal-LM artifacts fail in the first factory call (the
        # gateway builds its initial replicas eagerly), with
        # decode_engine()'s clear TypeError
        from ..serving import ServingGateway
        return ServingGateway(
            lambda: self.decode_engine(**engine_kwargs),
            replicas=replicas, router=router, autoscaler=autoscaler,
            registry=registry)

    def clear_intermediate_tensor(self):
        self._outputs = {}

    def try_shrink_memory(self):
        pass


AnalysisPredictor = Predictor


def create_predictor(config):
    return Predictor(config)


def create_paddle_predictor(config):
    return Predictor(config)


def get_version():
    from .. import __version__
    return __version__
