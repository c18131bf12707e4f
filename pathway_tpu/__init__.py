"""pathway_tpu — a TPU-native stream-processing / live-data framework.

A ground-up re-design of the capabilities of Pathway (reference mounted at
/root/reference): declarative Table/expression API over an incremental
dataflow engine, built on JAX/XLA for dense compute with host-side
arrangements for irregular state. See SURVEY.md for the layer map.

Import as ``import pathway_tpu as pw`` — the API surface mirrors
``python/pathway/__init__.py``.
"""

from __future__ import annotations

from . import reducers, udfs
from .internals import dtype as _dt
from .internals.custom_reducers import BaseCustomAccumulator
from .internals.expression import (
    ColumnExpression,
    ColumnReference,
    apply,
    apply_async,
    apply_with_type,
    cast,
    coalesce,
    declare_type,
    fill_error,
    if_else,
    make_tuple,
    require,
    unwrap,
)
from .internals.json import Json
from .internals.error_log_table import global_error_log, local_error_log
from .internals.py_object_wrapper import PyObjectWrapper
from .internals.parse_graph import G, Universe
from .internals.run import MonitoringLevel, request_stop, run, run_all
from .internals.sql import sql
from .internals.config import PathwayConfig, get_pathway_config
from .internals.yaml_loader import load_yaml
from .internals.schema import (
    Schema,
    SchemaProperties,
    assert_table_has_schema,
    column_definition,
    schema_builder,
    schema_from_dict,
    schema_from_types,
)
from .internals.table import (
    Table,
    TableLike,
    groupby,
    join,
    join_inner,
    join_left,
    join_outer,
    join_right,
)
from .internals.groupbys import GroupedTable
from .internals.joins import Joinable, JoinMode, JoinResult
from .internals.thisclass import left, right, this
from .udfs import UDF, udf, udf_async

from . import debug, demo, io, persistence, stdlib, universes  # noqa: E402
from .stdlib import graphs, indexing, ml, ordered, stateful, statistical, temporal, utils, viz  # noqa: E402

__version__ = "0.1.0"


class Type:
    """Engine-level type tags (reference ``PathwayType``)."""

    ANY = _dt.ANY
    STRING = _dt.STR
    INT = _dt.INT
    BOOL = _dt.BOOL
    FLOAT = _dt.FLOAT
    POINTER = _dt.POINTER
    DATE_TIME_NAIVE = _dt.DATE_TIME_NAIVE
    DATE_TIME_UTC = _dt.DATE_TIME_UTC
    DURATION = _dt.DURATION
    ARRAY = _dt.Array()
    JSON = _dt.JSON
    BYTES = _dt.BYTES


Pointer = _dt.Pointer  # pointer typehint (engine keys are 64-bit ints)
DateTimeNaive = _dt.DATE_TIME_NAIVE
DateTimeUtc = _dt.DATE_TIME_UTC
Duration = _dt.DURATION


from .internals.iterate import iterate, iterate_universe  # noqa: E402
from .internals.interactive import (  # noqa: E402
    LiveTable,
    enable_interactive_mode,
    is_interactive_mode_enabled,
)
from .stdlib.utils.async_transformer import AsyncTransformer  # noqa: E402
from .internals.row_transformer import (  # noqa: E402
    ClassArg,
    attribute,
    input_attribute,
    input_method,
    method,
    output_attribute,
    transformer,
)


from .analysis import analyze  # noqa: E402


def set_license_key(key: str | None) -> None:  # compatibility no-op
    pass


def set_monitoring_config(*args, **kwargs) -> None:
    pass


__all__ = [
    "AsyncTransformer",
    "BaseCustomAccumulator",
    "ClassArg",
    "ColumnExpression",
    "ColumnReference",
    "GroupedTable",
    "JoinMode",
    "JoinResult",
    "Joinable",
    "Json",
    "MonitoringLevel",
    "Pointer",
    "Schema",
    "SchemaProperties",
    "Table",
    "TableLike",
    "Type",
    "UDF",
    "Universe",
    "analyze",
    "apply",
    "apply_async",
    "attribute",
    "apply_with_type",
    "assert_table_has_schema",
    "cast",
    "coalesce",
    "column_definition",
    "debug",
    "declare_type",
    "demo",
    "fill_error",
    "global_error_log",
    "local_error_log",
    "PyObjectWrapper",
    "graphs",
    "groupby",
    "if_else",
    "indexing",
    "input_attribute",
    "input_method",
    "io",
    "iterate",
    "iterate_universe",
    "LiveTable",
    "enable_interactive_mode",
    "is_interactive_mode_enabled",
    "join",
    "method",
    "output_attribute",
    "transformer",
    "join_inner",
    "join_left",
    "join_outer",
    "join_right",
    "left",
    "make_tuple",
    "ml",
    "ordered",
    "persistence",
    "reducers",
    "require",
    "right",
    "request_stop",
    "run",
    "run_all",
    "schema_builder",
    "sql",
    "universes",
    "viz",
    "PathwayConfig",
    "get_pathway_config",
    "load_yaml",
    "schema_from_dict",
    "schema_from_types",
    "stateful",
    "statistical",
    "stdlib",
    "temporal",
    "this",
    "udf",
    "udf_async",
    "udfs",
    "unwrap",
    "utils",
]
