"""pinot_tpu — a TPU-native real-time OLAP framework.

A ground-up re-design of Apache Pinot's capability set (reference surveyed in
/root/repo/SURVEY.md) for TPU hardware: immutable columnar segments pinned in
HBM as JAX device arrays, filter->project->aggregate compiled as jax.jit/Pallas
kernels, per-segment combine as psum/shard_map collectives over ICI, and the
surrounding system (stream ingestion, upsert, indexes, SQL, multi-stage joins,
cluster control plane) rebuilt idiomatically.

Layer map (mirrors SURVEY.md section 1, re-architected):
  spi/       - schema, table config, column types        (pinot-spi analog)
  segment/   - columnar segment format, build/load       (pinot-segment-* analog)
  indexes/   - inverted/range/bloom/star-tree/...        (index SPI analog)
  query/     - IR, planner, jit kernels, executor        (pinot-core SSE analog)
  sql/       - SQL parser -> IR                          (CalciteSqlParser analog)
  parallel/  - device mesh, shard_map combine            (scatter-gather analog)
  realtime/  - mutable segments, stream consumption      (realtime analog)
  mse/       - multi-stage engine: joins, exchanges      (pinot-query-* analog)
  cluster/   - coordinator, broker, server, minion, MVs  (controller/broker/server)
  timeseries/- bucketed series engine                    (pinot-timeseries analog)
  ingest/    - CSV/JSON record readers                   (input-format analog)
  tools/     - admin CLI                                 (pinot-tools analog)
(plus native/ at the repo root: first-party C++ bitmap codec + CSV scanner)
"""

# OLAP semantics require 64-bit LONG/DOUBLE (Pinot aggregates into long/double;
# golden tests compare against 64-bit sqlite). Hot-path code arrays stay int32/
# uint8/16; only reductions widen.  Must run before any jax array creation.
import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compile cache, placed from outside: JAX_COMPILATION_CACHE_DIR
# wins untouched (JAX reads it itself); otherwise a FIXED directory in the
# checkout — the path is part of the cache key, so it must never move.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"),
    )

# Compiles of a quarter second or more are kept, not only those of a second
# or more (JAX's default): on the chip a dense template's per-segment and
# narrow group programs compile in 0.3-1 s each, a plan has up to six
# programs, and a process that starts with a warm cache should compile none
# of them again (PERF.md, PR 29).  Not everything: the CPU backend's many
# tiny programs are cheaper to compile than to look up.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)

__version__ = "0.1.0"

from pinot_tpu.spi.schema import DataType, FieldSpec, FieldRole, Schema  # noqa: E402,F401
from pinot_tpu.spi.config import TableConfig  # noqa: E402,F401


def __getattr__(name):  # lazy top-level conveniences (avoid import cycles)
    if name == "QueryEngine":
        from pinot_tpu.query.engine import QueryEngine

        return QueryEngine
    if name == "build_segment":
        from pinot_tpu.segment.builder import build_segment

        return build_segment
    raise AttributeError(f"module 'pinot_tpu' has no attribute {name!r}")
