"""Analyzer core: compute State from data, Metric from State.

reference: analyzers/Analyzer.scala:56-272. The TPU twist
(SURVEY.md §7): a scan-shareable analyzer declares
  * host-prep  — which named arrays it needs (columns/masks/match codes),
  * device_reduce — a traced function turning those arrays into a partial
    state pytree for one batch,
  * device_merge  — a traced semigroup combine for cross-device merging,
and the planner fuses every requested analyzer's reduce into ONE compiled
XLA computation per pass (the analogue of the reference's single
`df.agg(...)` with offset bookkeeping, runners/AnalysisRunner.scala:279-326;
offsets become pytree structure here).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu import observe
from deequ_tpu.core.exceptions import (
    EmptyStateException,
    NoColumnsSpecifiedException,
    NoSuchColumnException,
    NumberOfSpecifiedColumnsException,
    WrongColumnTypeException,
    wrap_if_necessary,
)
from deequ_tpu.core.metrics import DoubleMetric, Entity, Metric
from deequ_tpu.core.maybe import Failure
from deequ_tpu.analyzers.states import State
from deequ_tpu.data.expr import Predicate
from deequ_tpu.data.table import ColumnType, Table

COUNT_COL = "com_amazon_deequ_dq_metrics_count"


def render_where(where: Optional[str]) -> str:
    """Scala Option rendering — part of the analyzer identity string used
    in EmptyStateException messages and state-provider keys
    (reference: NullHandlingTests.scala:131-140)."""
    return f"Some({where})" if where is not None else "None"


def entity_from(columns: Sequence[str]) -> Entity:
    """reference: analyzers/Analyzer.scala:381-382."""
    return Entity.COLUMN if len(columns) == 1 else Entity.MULTICOLUMN


# ---------------------------------------------------------------------------
# Preconditions (reference: analyzers/Analyzer.scala:275-335)
# ---------------------------------------------------------------------------

NUMERIC_TYPES = (ColumnType.LONG, ColumnType.DOUBLE, ColumnType.DECIMAL)


class Preconditions:
    @staticmethod
    def has_column(column: str) -> Callable[[Table], None]:
        def check(table: Table) -> None:
            if not table.has_column(column):
                raise NoSuchColumnException(
                    f"Input data does not include column {column}!"
                )

        return check

    @staticmethod
    def is_numeric(column: str) -> Callable[[Table], None]:
        def check(table: Table) -> None:
            ctype = table.column(column).ctype
            if ctype not in NUMERIC_TYPES:
                raise WrongColumnTypeException(
                    f"Expected type of column {column} to be one of "
                    f"(ByteType,ShortType,IntegerType,LongType,FloatType,"
                    f"DoubleType,DecimalType), but found {ctype.value} instead!"
                )

        return check

    @staticmethod
    def is_string(column: str) -> Callable[[Table], None]:
        def check(table: Table) -> None:
            ctype = table.column(column).ctype
            if ctype != ColumnType.STRING:
                raise WrongColumnTypeException(
                    f"Expected type of column {column} to be StringType, "
                    f"but found {ctype.value} instead!"
                )

        return check

    @staticmethod
    def at_least_one(columns: Sequence[str]) -> Callable[[Table], None]:
        def check(table: Table) -> None:
            if len(columns) == 0:
                raise NoColumnsSpecifiedException(
                    "At least one column needs to be specified!"
                )

        return check

    @staticmethod
    def exactly_n_columns(columns: Sequence[str], n: int) -> Callable[[Table], None]:
        def check(table: Table) -> None:
            if len(columns) != n:
                raise NumberOfSpecifiedColumnsException(
                    f"{n} columns have to be specified! "
                    f"Currently, columns contains only {len(columns)} column(s): "
                    f"{','.join(columns)}!"
                )

        return check

    @staticmethod
    def find_first_failing(
        table: Table, checks: Sequence[Callable[[Table], None]]
    ) -> Optional[BaseException]:
        for check in checks:
            try:
                check(table)
            except Exception as e:  # noqa: BLE001
                return e
        return None


# ---------------------------------------------------------------------------
# Analyzer
# ---------------------------------------------------------------------------


class Analyzer:
    """Computes a State from data and a Metric from the State
    (reference: analyzers/Analyzer.scala:56-155)."""

    # -- identity ------------------------------------------------------------

    @property
    def name(self) -> str:
        raise NotImplementedError

    @property
    def instance(self) -> str:
        raise NotImplementedError

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN

    # -- contract ------------------------------------------------------------

    def preconditions(self) -> List[Callable[[Table], None]]:
        return []

    def compute_state_from(self, table: Table) -> Optional[State]:
        raise NotImplementedError

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        raise NotImplementedError

    def to_failure_metric(self, exception: BaseException) -> Metric:
        return DoubleMetric(
            self.entity, self.name, self.instance,
            Failure(wrap_if_necessary(exception)),
        )

    # -- orchestration (reference: Analyzer.scala:88-153) --------------------

    def calculate(
        self,
        table: Table,
        aggregate_with: Optional["StateLoader"] = None,
        save_states_with: Optional["StatePersister"] = None,
    ) -> Metric:
        failing = Preconditions.find_first_failing(table, self.preconditions())
        if failing is not None:
            return self.to_failure_metric(failing)
        try:
            state = self.compute_state_from(table)
        except Exception as e:  # noqa: BLE001
            return self.to_failure_metric(e)
        return self.calculate_metric(state, aggregate_with, save_states_with)

    def calculate_metric(
        self,
        state: Optional[State],
        aggregate_with: Optional["StateLoader"] = None,
        save_states_with: Optional["StatePersister"] = None,
    ) -> Metric:
        # the state layer: `state_load` (read and deserialize),
        # `state_merge`, `state_save` (serialize and write); no span opens
        # without a loader or a persister
        if aggregate_with is not None:
            with observe.span("state_load", cat="state", analyzer=self.name):
                loaded = aggregate_with.load(self)
            if loaded is not None:
                if state is None:
                    state = loaded
                else:
                    with observe.span("state_merge", cat="state", analyzer=self.name):
                        state = loaded.merge(state)
        if save_states_with is not None and state is not None:
            with observe.span("state_save", cat="state", analyzer=self.name):
                save_states_with.persist(self, state)
        return self.compute_metric_from(state)

    def aggregate_state_to(
        self,
        source_a: "StateLoader",
        source_b: "StateLoader",
        target: "StatePersister",
    ) -> None:
        """reference: Analyzer.scala:130-147."""
        a = source_a.load(self)
        b = source_b.load(self)
        merged = a.merge(b) if (a is not None and b is not None) else (a or b)
        if merged is not None:
            target.persist(self, merged)

    def load_state_and_compute_metric(self, source: "StateLoader") -> Metric:
        return self.compute_metric_from(source.load(self))

    def empty_state_failure(self) -> Metric:
        return self.to_failure_metric(
            EmptyStateException(
                f"Empty state for analyzer {self!r}, all input values were NULL."
            )
        )

    # analyzers are used as dict keys; identity is their repr
    def __eq__(self, other) -> bool:
        return type(self) is type(other) and repr(self) == repr(other)

    def __hash__(self) -> int:
        return hash(repr(self))


# ---------------------------------------------------------------------------
# Scan-shareable analyzers: the fused-pass device protocol
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputSpec:
    """One named host-prepped array. Keys are globally deduplicated across
    all analyzers in a pass: two analyzers over the same column share one
    device array (the offset-bookkeeping analogue, but by name).

    `columns` names the table columns the build reads — the pass unions
    them for column pruning, so a streaming source only decodes what the
    pass actually consumes (the Parquet analogue of Spark's column
    pruning). None = unknown reads; pruning is disabled for the pass."""

    key: str
    build: Callable[[Table], np.ndarray]
    columns: Optional[Tuple[str, ...]] = None


def col_values_spec(column: str) -> InputSpec:
    return InputSpec(
        key=f"num:{column}",
        build=lambda t: t.column(column).numeric_values()[0],
        columns=(column,),
    )


def col_valid_spec(column: str) -> InputSpec:
    return InputSpec(
        key=f"valid:{column}",
        build=lambda t: t.column(column).valid,
        columns=(column,),
    )


def where_key(where: Optional[str]) -> str:
    """Input key for a where mask — no predicate parsing, safe to call
    inside traced code."""
    return f"where:{where}" if where is not None else "where:<all>"


_ALL_TRUE_CACHE: dict = {}


def _all_true(n: int) -> np.ndarray:
    """Shared all-true mask per batch length (READ-ONLY: consumers treat
    masks as immutable); saves one 1-byte-per-row allocation per batch."""
    mask = _ALL_TRUE_CACHE.get(n)
    if mask is None:
        mask = np.ones(n, dtype=np.bool_)
        mask.setflags(write=False)
        if len(_ALL_TRUE_CACHE) >= 4:  # a scan sees at most a few sizes
            _ALL_TRUE_CACHE.pop(next(iter(_ALL_TRUE_CACHE)))
        _ALL_TRUE_CACHE[n] = mask
    return mask


def where_spec(where: Optional[str]) -> InputSpec:
    """Row mask for an optional filter; None = all (real) rows. Padding rows
    are False either way (the conditionalSelection analogue,
    reference: Analyzer.scala:385-402)."""
    if where is None:
        return InputSpec(
            key=where_key(None),
            build=lambda t: _all_true(t.num_rows),
            columns=(),
        )
    pred = Predicate(where)
    return InputSpec(
        key=where_key(where),
        build=lambda t: predicate_input(pred, t),
        columns=tuple(sorted(set(pred.referenced_columns()))),
    )


def predicate_input(pred: Predicate, t: Table, nonnull: bool = False) -> np.ndarray:
    """One predicate input build, counted by route: the row mask (NULL ->
    False), or with `nonnull` the mask of rows whose result is not NULL."""
    (v, null, _), route, entries = pred.eval_routed(t)
    observe.counters.record_predicate_eval(route, t.num_rows, entries)
    if nonnull:
        return ~null
    return np.asarray(v, dtype=bool) & ~null


class ScanShareableAnalyzer(Analyzer):
    """An analyzer whose per-batch work is expressible as a masked reduction
    that can be fused with others into one compiled pass
    (reference: analyzers/Analyzer.scala:159-216).

    Two flavors share the single scan: device-REDUCED analyzers contribute
    traced reductions whose outputs merge in-graph / cross-batch via
    `merge_agg`; device-ASSISTED analyzers (``device_assisted = True``,
    e.g. quantile sketches) contribute a traced per-batch computation
    (`device_batch` — the heavy part, e.g. the sort) whose fixed-size
    output is consumed on the host each batch (`host_consume`) instead of
    being merged in-graph — the host keeps only the sketch fold."""

    device_assisted = False

    def device_batch(self, inputs: Dict[str, Any], xp) -> Any:
        """Per-batch traced computation for a device-assisted analyzer.
        Output leaves must be 1-D arrays (scalars as shape-(1,)) so the
        mesh pass can gather per-device outputs along axis 0. Only called
        when device_assisted is True."""
        raise NotImplementedError

    def host_consume(self, state: Optional[State], batch_output: Any) -> Optional[State]:
        """Fold one batch's (or one device shard's) device_batch output
        into the running State. Only called when device_assisted."""
        raise NotImplementedError

    def input_specs(self) -> List[InputSpec]:
        raise NotImplementedError

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        """Named arrays -> partial-state pytree for one batch. `xp` is the
        array namespace: jnp when traced into the fused XLA pass, numpy for
        host-side evaluation."""
        raise NotImplementedError

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        """Semigroup combine of two aggregate pytrees. Same function serves
        the traced cross-device mesh merge (xp=jnp) and the driver-side
        float64 cross-batch fold (xp=numpy)."""
        raise NotImplementedError

    def unshift_agg(self, agg: Any, shifts: Dict[str, float]) -> Any:
        """Undo the f32 wire's per-column pre-centering (the engine ships
        x - shift so a float32 device resolves clustered data, e.g. mean
        ~1e7 with variance ~1e-2 — without the shift the variance signal
        is destroyed by f32 quantization before any kernel runs). Called
        once on the final aggregate; `shifts` maps input keys
        ("num:<col>") to the scan-constant shift. Default: no numeric
        value inputs, nothing to undo."""
        return agg

    def unshift_batch(self, out: Any, shifts: Dict[str, float]) -> Any:
        """Same, for a device-assisted member's per-batch output (applied
        before host_consume)."""
        return out

    def host_finish_batch(
        self, out: Any, host_inputs: Dict[str, Any], shifts: Dict[str, float]
    ) -> Any:
        """Optional single-device hook: turn a device-produced SUMMARY
        (e.g. the pallas hist16 radix histogram) into the regular
        per-batch output using the batch's host-resident inputs. Called
        before unshift_batch; default: pass through."""
        return out

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        """Folded (host, float64) pytree -> State; None = empty state."""
        raise NotImplementedError

    def compute_state_from(self, table: Table) -> Optional[State]:
        from deequ_tpu.ops.fused import FusedScanPass

        return FusedScanPass([self]).run(table)[0].state_or_raise()


# late import hook for typing only
from deequ_tpu.analyzers.state_provider import StateLoader, StatePersister  # noqa: E402,F401
