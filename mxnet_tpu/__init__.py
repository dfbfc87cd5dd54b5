"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet (incubating), re-designed for JAX/XLA/Pallas/pjit.

Import as ``import mxnet_tpu as mx``: the public surface mirrors the
reference's python/mxnet package (SURVEY.md §2.3) — mx.nd, mx.sym, mx.gluon,
mx.autograd, mx.mod, mx.io, mx.metric, mx.optimizer, mx.kv, contexts
(mx.cpu/mx.gpu/mx.tpu) — while execution is trace-and-compile on XLA:
the async C++ dependency engine, graph executor and kvstore of the reference
collapse into jax.jit / pjit / mesh collectives (SURVEY.md §7 table).
"""
__version__ = '1.5.0'  # capability parity target: reference v1.5.0-dev

# multi-host join first: jax.distributed.initialize must precede any
# backend-touching import below (tools/launch.py exports the env)
from . import _dist_init
_dist_init.ensure_distributed()

from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, \
    num_gpus, num_tpus, default_device
from .base import MXNetError
from . import base
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import random
from . import name
from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from . import lr_scheduler
from . import metric
from . import kvstore
from . import kvstore as kv
from . import recordio
from . import plugin
from . import io
from . import gluon
from . import parallel
from . import dist
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from .executor import Executor
from . import module
from . import module as mod
from . import model
from . import callback
from . import operator
from . import image
from . import config
from . import contrib
from . import attribute
from .attribute import AttrScope
from . import util
from . import registry
from . import engine
from . import rtc
from . import subgraph
from . import kvstore_server
from . import executor_manager
from . import resilience
from . import guardrail
from . import observability
from . import serving
from . import amp

# persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR, else
# <repo>/.jax_cache): applied before any program compiles so restarts
# warm-start from disk
config.configure_compile_cache()

# the join happened before observability existed; stamp it into the
# flight ring now so multi-host post-mortems see the membership event
if _dist_init.is_initialized() and observability.enabled():
    observability.record_event(
        'dist_join', process_id=_dist_init.process_info()[0],
        process_count=_dist_init.process_info()[1])
    observability.dist_instruments().joins.inc()

# env-driven global seed (docs/faq/env_var.md MXNET_SEED)
_seed = config.get('MXNET_SEED')
if _seed is not None:
    random.seed(_seed)
del _seed
if config.get('MXNET_PROFILER_AUTOSTART'):
    from . import profiler as _profiler
    _profiler.set_state('run')
from . import monitor
from .monitor import Monitor
from . import profiler
from . import runtime
from . import test_utils
from . import visualization
from . import rnn
