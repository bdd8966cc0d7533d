"""mxnet_tpu — a TPU-native deep-learning framework with the capability surface of
MXNet 0.9.4 (NNVM era), redesigned for JAX/XLA/Pallas rather than ported.

See SURVEY.md for the reference layer map this package mirrors and README.md for
the architecture.
"""
from time import perf_counter as _perf_counter
_t_import = _perf_counter()
from .base import MXNetError  # noqa: E402
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context
from . import base
from . import telemetry
from . import sanitize
from . import metrics_server
from . import diagnostics
from . import sentinel
from . import ndarray
from . import ndarray as nd
from . import random
from . import ops
from . import symbol
from . import symbol as sym
from .symbol import Variable, Group
from . import executor
from .executor import Executor
from .attribute import AttrScope
from . import name
from . import io
from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from . import amp
from . import metric
from . import lr_scheduler
from . import callback
from . import kvstore
from . import kvstore as kv
from . import model
from . import module
from . import parallel
from .module import Module
from . import monitor
from . import operator
from . import image
from .monitor import Monitor
from . import visualization
from . import visualization as viz
from . import recordio
from . import profiler
from . import engine
from . import predictor
from . import serving
from . import checkpoint
from . import rtc
from .predictor import Predictor
from . import rnn
from . import test_utils

__version__ = "0.1.0"

# the set-up account's import interval; jax is imported by now, so the
# account's jax.monitoring feed is installed here too
sanitize.install_setup_feed(_t_import, _perf_counter())
del _perf_counter, _t_import
