"""paddle_tpu.fluid — the Fluid-compatible TPU-native API.

Parity: reference python/paddle/fluid/__init__.py.
"""
from . import core
from . import framework
from .framework import Program, Operator, Parameter, Variable, \
    default_startup_program, default_main_program, program_guard, \
    name_scope, recompute_guard, recompute_keep, RecomputeKeepError, \
    device_guard, get_var
from . import executor
from .executor import Executor, global_scope, scope_guard, _switch_scope, \
    Scope, anomaly_guard
from . import layers
from . import initializer
from . import optimizer
from . import backward
from .backward import append_backward
from . import regularizer
from . import clip
from .clip import ErrorClipByValue, GradientClipByValue, GradientClipByNorm, \
    GradientClipByGlobalNorm
from . import nets
from . import io
from . import evaluator
from . import metrics
from . import average
from .param_attr import ParamAttr, WeightNormParamAttr
from .data_feeder import DataFeeder
from .lod_tensor import LoDTensor, LoDTensorArray, create_lod_tensor, \
    create_random_int_lodtensor
# API parity re-export (reference fluid/__init__.py imports it by name);
# the patch itself is applied as math_op_patch's import side effect
from .layers.math_op_patch import monkey_patch_variable
from . import unique_name
from . import amp
from . import analysis
from .analysis import ProgramVerifyError
from . import passes
from . import annotations
from . import concurrency
from . import default_scope_funcs
from . import graphviz
from . import net_drawer
from . import recordio_writer
from .concurrency import (Go, make_channel, channel_send, channel_recv,
                          channel_close, Select)
from . import contrib
from . import profiler
from . import debugger
from .core import CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace
from .parallel_executor import ParallelExecutor, ExecutionStrategy, BuildStrategy
from . import transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig, \
    InferenceTranspiler, PipelineTranspiler, SequenceParallelTranspiler, \
    TensorParallelTranspiler, memory_optimize, release_memory
from . import trainer
from .trainer import Trainer, BeginEpochEvent, EndEpochEvent, \
    BeginStepEvent, EndStepEvent, CheckpointConfig
from . import inferencer
from .inferencer import Inferencer

Tensor = LoDTensor

__all__ = framework.__all__ + executor.__all__ + transpiler.__all__ + \
    trainer.__all__ + inferencer.__all__ + [
    'io', 'initializer', 'layers', 'transpiler', 'nets', 'optimizer',
    'learning_rate_decay', 'backward', 'regularizer', 'LoDTensor',
    'LoDTensorArray',
    'CPUPlace', 'TPUPlace', 'CUDAPlace', 'CUDAPinnedPlace', 'Tensor',
    'ParamAttr', 'WeightNormParamAttr', 'DataFeeder', 'clip', 'profiler',
    'unique_name',
]


def __bootstrap__():
    return True
