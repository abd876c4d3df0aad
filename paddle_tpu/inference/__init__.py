"""paddle_tpu.inference — deployment/serving facade.

Reference: paddle.inference (python/paddle/inference/wrapper.py;
engine: paddle/fluid/inference/api/analysis_predictor.h — Config →
AnalysisPredictor with named input/output handles).

TPU rendering: the "analysis + IR passes + engine" pipeline is XLA —
the artifact saved by jit.save IS the optimized program (portable
StableHLO, compiled on load for whatever chip is present). The
Predictor keeps the reference's handle-style API (get_input_names /
get_input_handle / run / get_output_handle) so serving code ports
directly.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


class Config:
    """ref: paddle/fluid/inference/api/paddle_analysis_config.h"""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        # paddle passes "model.pdmodel", "model.pdiparams"; accept that
        # or the bare prefix
        def strip(p, suf):
            return p[:-len(suf)] if p and p.endswith(suf) else p
        self._prefix = strip(prog_file, ".pdmodel") if prog_file else None
        if params_file:
            pp = strip(params_file, ".pdiparams")
            if self._prefix is None:
                self._prefix = pp
        self._device = "tpu"
        self._extra: Dict = {}

    def set_prog_file(self, path):
        self._prefix = path[:-len(".pdmodel")] \
            if path.endswith(".pdmodel") else path

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def enable_use_gpu(self, *a, **kw):  # parity; device is PJRT's
        self._device = "gpu"

    def disable_gpu(self):
        self._device = "cpu"

    def enable_memory_optim(self, *a, **kw):
        pass  # XLA owns buffer assignment

    def switch_ir_optim(self, *a, **kw):
        pass  # XLA passes always on

    def set_cpu_math_library_num_threads(self, n):
        self._extra["threads"] = n


class _Handle:
    """Named input/output tensor handle (ref ZeroCopyTensor)."""

    def __init__(self):
        self._value = None

    def copy_from_cpu(self, arr: np.ndarray):
        self._value = np.asarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._value)

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)

    @property
    def shape(self):
        return list(self._value.shape) if self._value is not None else None


class Predictor:
    """ref: AnalysisPredictor (analysis_predictor.h:59)."""

    def __init__(self, config: Config):
        from ..jit import load, TranslatedLayer
        if config._prefix is None:
            raise ValueError("Config needs a model path")
        layer = load(config._prefix)
        if not isinstance(layer, TranslatedLayer):
            raise ValueError(
                f"{config._prefix}.pdmodel has no serialized program; "
                "re-save with jit.save(layer, path, input_spec=[...])")
        self._layer = layer
        n_in = len(layer._exported.in_avals) - len(layer._consts)
        self._input_names = [f"x{i}" for i in range(n_in)]
        self._inputs = {n: _Handle() for n in self._input_names}
        self._output_names: List[str] = []
        self._outputs: Dict[str, _Handle] = {}

    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name) -> _Handle:
        return self._inputs[name]

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """Direct style: run([x, y]) -> [np arrays]; or handle style:
        fill input handles, run(), read output handles."""
        if inputs is not None:
            for n, x in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(x)
        args = [self._inputs[n]._value for n in self._input_names]
        out = self._layer(*args)
        import jax
        leaves = jax.tree_util.tree_leaves(out)
        self._output_names = [f"out{i}" for i in range(len(leaves))]
        self._outputs = {}
        results = []
        for n, t in zip(self._output_names, leaves):
            h = _Handle()
            h.copy_from_cpu(np.asarray(getattr(t, "_data", t)))
            self._outputs[n] = h
            results.append(h.copy_to_cpu())
        return results

    def get_output_names(self):
        return list(self._output_names)

    def get_output_handle(self, name) -> _Handle:
        return self._outputs[name]


def create_predictor(config: Config) -> Predictor:
    """ref: paddle_infer.create_predictor"""
    return Predictor(config)


# paged KV-cache serving runtime (native block allocator + manager;
# pairs with incubate.nn.functional.block_multihead_attention)
from .paged_cache import BlockAllocator, PagedKVCache  # noqa: E402,F401
# continuous-batching serving engine over the paged runtime
from .llm_engine import (LLMEngine, GenerationResult,  # noqa: E402,F401
                         calibrate_kv_scales)
# speculative decoding: draft proposers + config for
# LLMEngine(speculative_config=...)
from .speculative import (SpeculativeConfig,  # noqa: E402,F401
                          DraftProposer, NgramProposer,
                          DraftModelProposer)
# replicated serving: health-checked router over N engine replicas
# (prefix-cache affinity, failover, circuit breaking, load shedding)
from .router import (Router, ReplicaSet,  # noqa: E402,F401
                     ReplicaHandle, ReplicaGone)
# serving SLO control plane: SLO-driven elastic autoscaling over the
# router's add_replica/retire_replica surface, plus the heavy-tailed
# traffic generator that exercises it (see README "Serving SLO control
# plane")
from .autoscaler import (Autoscaler, RouterActuator,  # noqa: E402,F401
                         SCALE_ACTIONS)
from .traffic import (Cohort, TrafficModel,  # noqa: E402,F401
                      TrafficEvent)
# prefill/decode disaggregation: role-based replica pools with
# cross-process KV-page migration (see README "Prefill/decode
# disaggregation")
from .disagg import (DisaggRouter, DisaggActuator,  # noqa: E402,F401
                     ROLES, PROCESS_ROLES)
