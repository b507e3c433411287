"""pathway_tpu — a TPU-native incremental stream-processing framework.

A ground-up rebuild of the capabilities of Pathway (reference mounted at
/root/reference) designed for TPU hardware: the dataflow control plane runs on
host CPU; the numeric data plane (embedding, KNN retrieval, reranking,
generation) is jit-compiled JAX sharded over a `jax.sharding.Mesh`.

Usage mirrors the reference::

    import pathway_tpu as pw

    class InputSchema(pw.Schema):
        value: int

    t = pw.debug.table_from_markdown('''
    value
    1
    2
    ''')
    result = t.select(doubled=pw.this.value * 2)
    pw.debug.compute_and_print(result)
"""

from __future__ import annotations

import datetime as _datetime

# -- core DSL ---------------------------------------------------------------
from pathway_tpu.internals import dtype as _dt
from pathway_tpu.internals.api import (
    apply,
    apply_async,
    apply_fully_async,
    apply_with_type,
    assert_table_has_schema,
    cast,
    coalesce,
    declare_type,
    fill_error,
    if_else,
    iterate,
    make_tuple,
    require,
    table_transformer,
    unwrap,
)
from pathway_tpu.internals.config import (
    pathway_config,
    set_license_key,
    set_monitoring_config,
)
from pathway_tpu.internals.expression import (
    ColumnExpression,
    ColumnReference,
)
from pathway_tpu.internals.joins import (
    GroupedJoinResult,
    JoinMode,
    JoinResult,
    OuterJoinResult,
    join,
    join_inner,
    join_left,
    join_outer,
    join_right,
)
from pathway_tpu.internals.joins import groupby as groupby  # noqa: PLC0414
from pathway_tpu.internals.groupbys import GroupedTable
from pathway_tpu.internals.api import (
    PathwayType as Type,
    PersistenceMode,
)
from pathway_tpu.internals.schema import SchemaProperties
from pathway_tpu.internals.iterate import iterate_universe
from pathway_tpu.internals.parse_graph import G as parse_graph_G
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.reducers import BaseCustomAccumulator, reducers
from pathway_tpu.internals.runner import run, run_all
from pathway_tpu.internals.schema import (
    ColumnDefinition,
    Schema,
    column_definition,
    schema_builder,
    schema_from_csv,
    schema_from_dict,
    schema_from_pandas,
    schema_from_types,
)
from pathway_tpu.internals.async_transformer import AsyncTransformer
from pathway_tpu.internals.row_transformer import (
    ClassArg,
    attribute,
    input_attribute,
    input_method,
    method,
    output_attribute,
    transformer,
)
from pathway_tpu.internals.table import Table, TableSlice
from pathway_tpu.internals.thisclass import left, right, this
from pathway_tpu.engine.value import (
    Json,
    Pointer,
    PyObjectWrapper,
    ref_scalar,
    wrap_py_object,
)

# -- type aliases (reference: pw.DateTimeNaive etc.) ------------------------
DateTimeNaive = _datetime.datetime
DateTimeUtc = _datetime.datetime
Duration = _datetime.timedelta
Date = _datetime.date

DATE_TIME_NAIVE = _dt.DATE_TIME_NAIVE
DATE_TIME_UTC = _dt.DATE_TIME_UTC
DURATION = _dt.DURATION


# -- subpackages ------------------------------------------------------------
from pathway_tpu import debug  # noqa: E402
from pathway_tpu import io  # noqa: E402
from pathway_tpu import stdlib  # noqa: E402
from pathway_tpu import universes  # noqa: E402
from pathway_tpu.internals import udfs  # noqa: E402
from pathway_tpu.internals.udfs import UDF, udf  # noqa: E402
from pathway_tpu.stdlib import indexing, ml, ordered, stateful, statistical  # noqa: E402
from pathway_tpu.stdlib import temporal  # noqa: E402
from pathway_tpu.stdlib import utils  # noqa: E402
from pathway_tpu.stdlib import viz  # noqa: E402
from pathway_tpu.stdlib.utils.pandas_transformer import pandas_transformer  # noqa: E402
from pathway_tpu.stdlib.temporal import (  # noqa: E402
    intervals_over,
    session,
    sliding,
    tumbling,
)

# graft frequently-used stdlib entry points onto the pw namespace, as the
# reference does (reference: python/pathway/__init__.py:155-176)
windowby = temporal.windowby

# Table.diff (reference grafts it the same way: pathway/__init__.py:207)
from pathway_tpu.stdlib import ordered as _ordered  # noqa: E402

Table.diff = _ordered.diff

# graft the temporal join/window surface onto Table, exactly as the
# reference does (reference: python/pathway/__init__.py:184-214)
Table.asof_join = temporal.asof_join
Table.asof_join_left = temporal.asof_join_left
Table.asof_join_right = temporal.asof_join_right
Table.asof_join_outer = temporal.asof_join_outer

Table.asof_now_join = temporal.asof_now_join
Table.asof_now_join_inner = temporal.asof_now_join_inner
Table.asof_now_join_left = temporal.asof_now_join_left

Table.window_join = temporal.window_join
Table.window_join_inner = temporal.window_join_inner
Table.window_join_left = temporal.window_join_left
Table.window_join_right = temporal.window_join_right
Table.window_join_outer = temporal.window_join_outer

Table.interval_join = temporal.interval_join
Table.interval_join_inner = temporal.interval_join_inner
Table.interval_join_left = temporal.interval_join_left
Table.interval_join_right = temporal.interval_join_right
Table.interval_join_outer = temporal.interval_join_outer

Table.windowby = temporal.windowby
Table.interpolate = statistical.interpolate
Table.inactivity_detection = temporal.inactivity_detection

# type exports (reference: pathway/__init__.py __all__ — Joinable/
# TableLike are base classes there; independent classes here, so the
# names are virtual base classes preserving isinstance semantics)
import abc as _abc  # noqa: E402


class Joinable(metaclass=_abc.ABCMeta):
    """Anything join()-able: Table or JoinResult (reference: joins.py
    Joinable:46 — a real base class there, a virtual one here)."""


class TableLike(metaclass=_abc.ABCMeta):
    """reference: table_like.py TableLike."""


Joinable.register(Table)
Joinable.register(JoinResult)
TableLike.register(Table)

# the reference lists these in __all__ without binding them (stale
# entries); bind them to their historical meanings so both names resolve
asynchronous = udfs  # the pre-rename name of the udfs module
window = temporal  # window types live in the temporal namespace
from pathway_tpu.stdlib.temporal import (  # noqa: E402
    AsofJoinResult,
    IntervalJoinResult,
    WindowJoinResult,
)


def __getattr__(name):
    if name == "xpacks":
        import pathway_tpu.xpacks as xp

        return xp
    if name == "persistence":
        import pathway_tpu.persistence as p

        return p
    if name == "demo":
        import pathway_tpu.demo as d

        return d
    if name == "sql":
        from pathway_tpu.internals.sql import sql as s

        return s
    if name == "graphs":
        from pathway_tpu.stdlib import graphs as g

        return g
    if name in ("enable_interactive_mode", "LiveTable"):
        from pathway_tpu.internals import interactive

        return getattr(interactive, name)
    if name == "MonitoringLevel":
        from pathway_tpu.internals.monitoring import MonitoringLevel as m

        return m
    if name == "load_yaml":
        # lazy: keeps PyYAML an optional dependency
        from pathway_tpu.internals.yaml_loader import load_yaml as ly

        return ly
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def global_error_log() -> Table:
    """Error log as a queryable table (reference: pw.global_error_log,
    Graph::error_log graph.rs:932)."""
    from pathway_tpu.internals.error_log import global_error_log as _gel

    return _gel()


local_error_log = global_error_log


class udf_async:  # legacy alias (reference had pw.udf_async)
    def __new__(cls, *args, **kwargs):
        from pathway_tpu.internals.udfs import udf

        return udf(*args, executor="async", **kwargs)


Json = Json
Error = None  # populated below to avoid import cycle at module top

from pathway_tpu.engine.value import ERROR as _ERROR_VALUE  # noqa: E402

Error = _ERROR_VALUE

__version__ = "0.1.0"

__all__ = [
    "Table",
    "Schema",
    "Json",
    "Pointer",
    "PyObjectWrapper",
    "wrap_py_object",
    "this",
    "left",
    "right",
    "apply",
    "apply_with_type",
    "apply_async",
    "apply_fully_async",
    "cast",
    "declare_type",
    "if_else",
    "coalesce",
    "require",
    "unwrap",
    "fill_error",
    "make_tuple",
    "iterate",
    "udf",
    "UDF",
    "reducers",
    "run",
    "run_all",
    "debug",
    "io",
    "indexing",
    "temporal",
    "windowby",
    "session",
    "sliding",
    "tumbling",
    "intervals_over",
    "column_definition",
    "schema_from_types",
    "schema_builder",
    "universes",
]

# the end of `import pathway_tpu`: the first mark of a start's wall clock
# (`setup.at.imported`, /status "spans")
from pathway_tpu.internals import tracing as _tracing  # noqa: E402

_tracing.mark("imported")
