"""flink_tpu: a TPU-native stream & batch dataflow framework.

A from-scratch rebuild of the capabilities of Apache Flink (reference:
JMIsham/flink @ 1.5-SNAPSHOT) designed TPU-first: keyed state lives in
TPU HBM as key-group-vectorized struct-of-arrays, per-record
``AggregateFunction.add/merge`` calls are micro-batched into
``jax.jit``/Pallas kernels, and the keyBy exchange between parallel
subtasks maps onto XLA collectives over a ``jax.sharding.Mesh``.

Layer map (mirrors SURVEY.md §1):

  core/       config, functions, type serialization, state descriptors,
              key groups              (ref: flink-core)
  state/      keyed/operator state backends: heap + TPU-HBM
              (ref: flink-runtime state SPI + RocksDB backend)
  ops/        device kernels: hashing, HLL, Count-Min, quantile
              sketches, segment aggregation (ref: none — the TPU
              replacement for per-record JVM aggregation)
  streaming/  StreamElement model, operators, windowing, timers,
              DataStream API, graph translation
              (ref: flink-streaming-java)
  runtime/    jobgraph, local/mini-cluster execution, checkpoint
              coordination, metrics     (ref: flink-runtime)
  parallel/   device-mesh sharding of key groups, collective keyBy
              exchange, mesh-sharded multi-window aggregation
              (ref: network stack / §2.8)
  table/      Table API + SQL slice lowering onto the window operator
              (ref: flink-libraries/flink-table)
  cep/        pattern matching: Pattern builder + NFA + keyed operator
              (ref: flink-libraries/flink-cep)
  batch/      DataSet API + plan optimizer (ref: flink-java /
              flink-optimizer)
  graph/      graph library: Graph API, scatter-gather/GSA/pregel
              supersteps as jitted segment ops, PageRank/CC/SSSP/
              triangles/label-propagation/HITS (ref: flink-gelly)
  ml/         ML pipelines: scalers, linear regression, SVM, KNN, ALS,
              distance metrics — fits as jitted device loops
              (ref: flink-libraries/flink-ml)
  connectors/ sources/sinks             (ref: flink-connectors)
  native/     C++ host runtime: hashing, slot index, compiled
              baselines (ref: the rocksdbjni native role, §2.2)

Plus: cli.py (`python -m flink_tpu run|info|bench|jobmanager|
taskmanager`, ref: CliFrontend + cluster entrypoints), runtime/rpc.py +
runtime/netchannel.py + runtime/cluster.py (distributed control plane:
Dispatcher/JobMaster/ResourceManager/TaskExecutor over TCP with
credit-based data-plane flow control), runtime/rest.py (web monitor),
runtime/queryable.py (queryable state client), examples/ (runnable
quickstarts incl. SocketWindowWordCount).
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

#: where this checkout keeps XLA's persistent compile cache when the
#: environment names no other place (git-ignored; the path is part of
#: what makes a cache entry findable, so it never moves)
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")

# Every process that can compile imports this package first.  A
# directory given from outside (JAX_COMPILATION_CACHE_DIR, which jax
# reads itself) is left alone; setting the option initialises no
# backend.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)

from flink_tpu.core.config import ConfigOption, ConfigOptions, Configuration
from flink_tpu.core.functions import (
    AggregateFunction,
    FilterFunction,
    FlatMapFunction,
    KeySelector,
    MapFunction,
    ReduceFunction,
)

__all__ = [
    "ConfigOption",
    "ConfigOptions",
    "Configuration",
    "AggregateFunction",
    "FilterFunction",
    "FlatMapFunction",
    "KeySelector",
    "MapFunction",
    "ReduceFunction",
    "__version__",
]
