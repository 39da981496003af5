"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (reference: kuke/Paddle ~1.5).

Program-description IR + layers DSL + IR-level autodiff, executed by lowering
whole blocks to XLA via JAX; data/model parallelism via jax.sharding meshes
(GSPMD collectives over ICI instead of NCCL). See SURVEY.md at the repo root
for the capability map.
"""

import time as _time

# `setup/import` of the compile log: the clock at the first line and the last
_IMPORT_BEGIN_NS = _time.monotonic_ns()

from . import ops  # registers all op lowering rules
from .framework import (Program, Block, Operator, Variable, Parameter,
                        program_guard, default_main_program,
                        default_startup_program, unique_name, unique_name_guard,
                        name_scope,
                        Executor, Scope, global_scope, scope_guard,
                        append_backward, gradients, LayerHelper, ParamAttr,
                        WeightNormParamAttr)
from . import dygraph_grad_clip
from .compiler import CompiledProgram, BuildStrategy, ExecutionStrategy
from . import layers
from . import optimizer
from . import initializer
from . import regularizer
from . import clip
from . import io
from . import metrics
from . import analysis
from . import observability
from . import profiler
from . import contrib
from . import dygraph
from . import transpiler
from . import incubate
from . import distributed
from . import dataset
from .dataset import DatasetFactory
from . import inference
from . import serving
from . import server
from . import nets
from .data_feeder import DataFeeder
from .reader.py_reader import PyReader
from .framework import debugger
from . import utils
from . import install_check
from . import average
from . import lod_tensor
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor
from . import reader
from . import datasets
from .framework.executor import as_jax_function

__version__ = "0.1.0"

# fluid-style places: accepted and ignored (JAX manages devices)


class CPUPlace:
    pass


class TPUPlace:
    def __init__(self, device_id: int = 0):
        self.device_id = device_id


CUDAPlace = TPUPlace  # source compat for reference scripts

# from here on the persistent compile cache has its directory and the compile
# log hears jax: one installation each (what a caller jits before its first
# Executor or engine, its weights first of all, is part of its start too, and
# is loaded by the next start only if the cache knew where to keep it)
from .utils.compile_cache import ensure_compile_cache as _ensure_compile_cache
_ensure_compile_cache()
_IMPORT_END_NS = _time.monotonic_ns()
observability.compile_log().install()
