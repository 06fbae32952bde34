"""Config #5: the configuration's SQL query over a table built on the
benchmark's source the way ``StreamTableEnvironment.from_columns``
builds one (``table/api.py``: ``Table(t_env, stream, Schema(cols))``,
``rowtime``, ``columnar=True``, ``col_dtypes``), read back through
``to_append_stream(batched=True)`` — ``chip_smoke.py`` leg 3a.
"""

import numpy as np

from flink_tpu.ops import link_probe
from flink_tpu.table import StreamTableEnvironment
from flink_tpu.table.api import Schema, Table

COLUMNS = ("k", "u", "ts")


def build(env, source, sink, config):
    source.configure(COLUMNS, as_elements=False)
    t_env = StreamTableEnvironment.create(env)
    stream = env.add_source(source, name="columnar_source")
    table = Table(t_env, stream, Schema(list(COLUMNS)))
    table.rowtime = "ts"
    table.columnar = True
    table.col_dtypes = {name: np.dtype(np.int64) for name in COLUMNS}
    t_env.register_table("ev", table)
    out = t_env.sql_query(config["query"])
    out.to_append_stream(batched=True).add_sink(sink)


def describe(op):
    """Facts about the route that ran, for an earlier line."""
    engine = op.engine
    h2d = link_probe.measure()["h2d_gbps"]
    return {"route": "SQL TUMBLE + APPROX_COUNT_DISTINCT -> "
                     f"{type(op).__name__} -> {type(engine).__name__}",
            "finish_tier": getattr(getattr(engine, "mode", None),
                                   "finish_tier", None),
            "h2d_gbps": float(h2d) if np.isfinite(h2d) else str(h2d)}
