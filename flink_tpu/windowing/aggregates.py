"""Vectorized aggregate functions.

The reference's ``AggregateFunction`` contract (createAccumulator/add/merge/
getResult; reference: flink-core/.../api/common/functions/AggregateFunction.java)
is re-expressed for batched device execution: an aggregate declares its
accumulator as a tuple of *leaves* (flat device arrays, one per accumulator
component), each with a scatter-reduce kind. ``add`` over a whole micro-batch
becomes one donated-buffer XLA scatter per leaf; ``merge`` across window slices
becomes a gather + axis-reduce; ``getResult`` is a jitted elementwise
``finish``.

E.g. AVG = (sum leaf, count leaf), finish = sum/count — identical in spirit to
the reference's AverageAccumulator but with arrays of 2^20 accumulators updated
per kernel launch instead of one object per key.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from flink_tpu.core.records import RecordBatch
from flink_tpu.ops.segment_ops import (
    SCATTER_METHOD,
    identity_for,
    pad_values,
)
from flink_tpu.stateplane import families as _families


from flink_tpu.core.annotations import public

@public
@dataclasses.dataclass(frozen=True)
class AccLeaf:
    """One flat component of an accumulator pytree.

    ``const`` marks a leaf whose per-record input value is a compile-time
    constant (e.g. the ``1`` of COUNT): no host value array is built or
    transferred for it — the scatter kernel broadcasts the constant on
    device. Padded lanes target the reserved identity slot 0, so the
    constant contribution of padding never reaches a live accumulator.
    """

    name: str
    dtype: np.dtype
    reduce: str  # 'sum' | 'max' | 'min'
    const: object = None

    def __post_init__(self):
        object.__setattr__(self, "dtype", np.dtype(self.dtype))
        if self.reduce not in SCATTER_METHOD:
            raise ValueError(f"unsupported reduce {self.reduce!r}")

    @property
    def identity(self):
        return identity_for(self.reduce, self.dtype)


# Compiled steps live in the shared PROGRAM_CACHE via the stateplane
# family builders, keyed by aggregate *layout*, not instance, so two
# pipelines with the same aggregate shape (e.g. a warmup run and a
# measured run, or repeated jobs — or two tenants) share XLA
# executables. The ``_*_jit`` properties below are the engines' stable
# entry points; the program bodies moved verbatim to
# ``flink_tpu/stateplane/families.py`` (bit-identity pinned by
# tests/test_stateplane.py).


@public
class AggregateFunction:
    """Base class. Subclasses define ``leaves``, ``map_input`` and ``finish``."""

    #: accumulator layout
    leaves: Tuple[AccLeaf, ...] = ()
    #: names of the emitted result columns
    output_names: Tuple[str, ...] = ("result",)
    #: True for an exact 64-bit aggregate (:class:`Int64Aggregate`): its
    #: leaves are int32 words its operator folds from host partials
    wide: bool = False

    @property
    def parts(self) -> Tuple[Tuple[str, str, str], ...]:
        """``(name, reduce, dtype)`` of each accumulator part folded from
        host partials (a wide table's layout): one per leaf, unless the
        aggregate carries a 64-bit part in two leaves."""
        return tuple((l.name, l.reduce, l.dtype.str) for l in self.leaves)

    def part_values(self, batch: RecordBatch) -> List[np.ndarray]:
        """Each part's value per row (host)."""
        return list(self.map_input_valued(batch))

    def finish_host(self, parts: List[np.ndarray]) -> Dict[str, np.ndarray]:
        """``finish`` on the host, over each part's values: how a wide
        table (one holding a 64-bit part) finishes its aggregates."""
        raise TypeError(f"{type(self).__name__} has no host finish: it "
                        "cannot share a table with a 64-bit aggregate")

    def bind(self, dtypes) -> "AggregateFunction":
        """The aggregate for the input columns' types (``{column:
        dtype}``): itself, unless its accumulator takes the type of its
        input (``dtype=None``), in which case ``leaves`` is None until
        it is bound."""
        return self

    def cache_key(self) -> tuple:
        """Identity of the compiled programs this aggregate needs. Two
        aggregates with equal keys can share jitted executables.

        Includes every hashable-primitive instance attribute so that
        parameterized subclasses (e.g. a scale factor used inside
        ``finish``) do not alias each other's compiled kernels. Subclasses
        whose ``finish`` depends on non-primitive state must override this.
        """
        params = tuple(
            (k, v) for k, v in sorted(vars(self).items())
            if isinstance(v, (str, int, float, bool, bytes, tuple))
        )
        return (type(self).__module__, type(self).__qualname__,
                self.leaves, self.output_names, params)

    # -- host side ----------------------------------------------------------

    def map_input(self, batch: RecordBatch) -> Tuple[np.ndarray, ...]:
        """Extract one value array per leaf from an input batch (host)."""
        raise NotImplementedError

    # -- device side (jax-traceable) ----------------------------------------

    def finish(self, merged: Tuple[jnp.ndarray, ...]) -> Dict[str, jnp.ndarray]:
        """Accumulator leaves -> result columns (traced under jit)."""
        raise NotImplementedError

    # -- compiled steps (shared across operators via this instance) ---------

    def init_accumulators(self, capacity: int) -> Tuple[jnp.ndarray, ...]:
        return tuple(
            jnp.full((capacity,), leaf.identity, dtype=leaf.dtype)
            for leaf in self.leaves
        )

    @property
    def input_leaves(self) -> Tuple[AccLeaf, ...]:
        """Leaves that take a per-record host value array (``const is None``)."""
        return tuple(l for l in self.leaves if l.const is None)

    @property
    def _scatter_jit(self):
        return _families.flat_scatter_combine(self.leaves)

    @property
    def _fire_jit(self):
        """(accs, slot_matrix [w, k]) -> result columns [w] + merged leaves."""
        return _families.flat_segment_fire(self)

    def _fire_project_jit(self, projector):
        """(accs, slot_matrix [wp, k], w scalar) -> projected (row indices
        [n], result cols [n], valid [n]) — the fire merge+finish fused with
        a FireProjector so only n rows cross HBM->host instead of wp. The
        validity mask is derived on device from the scalar row count and
        keys never ship at all (the host resolves indices->keys), keeping
        the fire's host->device traffic to the slot matrix alone (see
        flink_tpu.windowing.fire_projectors)."""
        return _families.flat_segment_fire_projected(self, projector)

    @property
    def _gather_jit(self):
        """(accs, slots) -> per-leaf gathered values — the incremental-
        snapshot read path: only dirty slots leave the device instead of
        the whole [capacity] arrays (HBM->host bandwidth is the cost)."""
        return _families.flat_gather(self.leaves)

    @property
    def _merge_jit(self):
        """(accs, slot_matrix [w, k]) -> merged leaves [w] WITHOUT finish —
        the hybrid-fire read path: device-resident slices merge on device,
        spilled slices merge on host, finish runs on host over the union."""
        return _families.flat_segment_merge(self.leaves)

    @property
    def _put_jit(self):
        """(accs, slots, per-leaf values) -> accs with ``a[slots] = v`` —
        the spill-reload write path: values gathered to host at eviction
        time are placed back verbatim (identity-masked at the reserved
        slot 0 pad target)."""
        return _families.flat_put(self.leaves)

    @property
    def _reset_jit(self):
        return _families.flat_reset(self.leaves)

    # -- retraction (changelog) support -------------------------------------

    @property
    def retractable(self) -> bool:
        """True when every accumulator leaf folds by addition — the
        changelog retract of a row is then the scatter of its negated
        contribution (reference: AggregateFunction.retract / the
        *WithRetractAggFunction family). MAX/MIN leaves are not
        retractable."""
        return all(l.reduce == "sum" for l in self.leaves)

    def map_input_valued(self, batch: RecordBatch) -> Tuple[np.ndarray, ...]:
        """One value array per leaf with const leaves materialized — the
        form needed when every leaf must carry explicit per-row values
        (local pre-aggregation, retraction folds)."""
        vit = iter(self.map_input(batch))
        out = []
        for leaf in self.leaves:
            if leaf.const is not None:
                out.append(np.full(len(batch), leaf.const, dtype=leaf.dtype))
            else:
                out.append(np.asarray(next(vit), dtype=leaf.dtype))
        return tuple(out)

    def map_input_signed(self, batch: RecordBatch,
                         signs: np.ndarray) -> Tuple[np.ndarray, ...]:
        """One SIGNED value array per leaf (const leaves materialized):
        +v for accumulate rows, -v for retraction rows."""
        return tuple(v * signs.astype(v.dtype)
                     for v in self.map_input_valued(batch))

    @property
    def _scatter_valued_jit(self):
        """Scatter where EVERY leaf takes an explicit value array, each
        folded by its own reduce method — the merge of locally pre-
        aggregated partials (two-phase aggregation; reference: the
        local/global split of MiniBatchLocalGroupAggFunction +
        MiniBatchGlobalGroupAggFunction). Pad lanes must carry each leaf's
        identity at the reserved slot 0."""
        return _families.flat_scatter_valued(self.leaves)

    @property
    def _scatter_signed_jit(self):
        """Scatter where EVERY leaf takes a (sign-applied) host value array
        — the retraction fold. Only valid for retractable aggregates
        (pure-add leaves), where padding with 0 at the reserved slot is
        harmless."""
        if not self.retractable:
            raise TypeError(
                f"{type(self).__name__} is not retractable (non-additive "
                "accumulator leaf); an updating input cannot be folded")
        return _families.flat_scatter_signed(self.leaves)

    # -- convenience --------------------------------------------------------

    def pad_input_values(
        self, values: Sequence[np.ndarray], size: int
    ) -> Tuple[np.ndarray, ...]:
        """Pad the value arrays of the non-const leaves (``map_input`` returns
        one array per *input* leaf; const leaves are broadcast on device)."""
        return tuple(
            pad_values(np.asarray(v, dtype=l.dtype), size, l.identity)
            for v, l in zip(values, self.input_leaves)
        )


# ---------------------------------------------------------------------------
# Built-ins
# ---------------------------------------------------------------------------


@public
class SumAggregate(AggregateFunction):
    """SUM as one leaf of ``dtype``. ``dtype=None`` takes the input
    column's type at binding: an integer column's SUM is the exact
    :class:`Int64Aggregate` (a BIGINT: an int32 sum would wrap past
    2^31), a float32 one this float32 leaf."""

    def __init__(self, field: str, dtype=np.float32, output: str = None):
        self.field = field
        self.leaves = None if dtype is None else (
            AccLeaf("sum", dtype, "sum"),)
        self.output_names = (output or f"sum_{field}",)

    def bind(self, dtypes):
        if self.leaves is not None:
            return self
        dt = _bound_dtype("SUM", self.field, dtypes)
        if dt.kind == "f":
            return SumAggregate(self.field, np.float32, self.output_names[0])
        return Int64Aggregate("sum", self.field, self.output_names[0],
                              np.int64)

    @property
    def retractable(self) -> bool:
        return True

    def map_input(self, batch):
        return (batch[self.field],)

    def finish(self, merged):
        return {self.output_names[0]: merged[0]}

    def finish_host(self, parts):
        return {self.output_names[0]: parts[0]}


@public
class CountAggregate(AggregateFunction):
    def __init__(self, output: str = "count"):
        self.leaves = (AccLeaf("count", np.int32, "sum", const=1),)
        self.output_names = (output,)

    def map_input(self, batch):
        return ()

    def finish(self, merged):
        return {self.output_names[0]: merged[0]}


class _Extremum(AggregateFunction):
    """MAX / MIN. ``dtype=None`` takes the input column's type when the
    operator binds it to its first batch (SQL's ``MAX(x)`` has the type
    of ``x``): an integer column up to 32 bits keeps an int32 leaf, a
    64-bit one the exact two-word :class:`Int64Aggregate`, a float one
    the float32 leaf every float caller has."""

    reduce = ""

    def __init__(self, field: str, dtype=np.float32, output: str = None):
        self.field = field
        self.output_names = (output or f"{self.reduce}_{field}",)
        self.leaves = None if dtype is None else (
            AccLeaf(self.reduce, dtype, self.reduce),)

    def bind(self, dtypes):
        if self.leaves is not None:
            return self
        dt = _bound_dtype(self.reduce.upper(), self.field, dtypes)
        if dt.kind == "f":
            return type(self)(self.field, np.float32, self.output_names[0])
        if _fits_int32(dt):
            return type(self)(self.field, np.int32, self.output_names[0])
        return Int64Aggregate(self.reduce, self.field,
                              self.output_names[0], dt)

    @property
    def retractable(self) -> bool:
        return False

    def map_input(self, batch):
        return (batch[self.field],)

    def finish(self, merged):
        return {self.output_names[0]: merged[0]}

    def finish_host(self, parts):
        return {self.output_names[0]: parts[0]}


@public
class MaxAggregate(_Extremum):
    reduce = "max"


@public
class MinAggregate(_Extremum):
    reduce = "min"


@public
class AvgAggregate(AggregateFunction):
    """AVG as a float32 sum over a float32 count. ``dtype=None`` takes
    the input column's type at binding: an integer column's AVG is the
    exact :class:`Int64Aggregate` (a 64-bit sum and count, divided with
    Java's truncation, in the input's type: SQL's ``AVG(BIGINT)`` is a
    BIGINT), a float one this float32 form."""

    def __init__(self, field: str, output: str = None, dtype=np.float32):
        self.field = field
        self.output_names = (output or f"avg_{field}",)
        self.leaves = None if dtype is None else (
            AccLeaf("sum", np.float32, "sum"),
            AccLeaf("count", np.float32, "sum", const=1.0),
        )

    def bind(self, dtypes):
        if self.leaves is not None:
            return self
        dt = _bound_dtype("AVG", self.field, dtypes)
        if dt.kind == "f":
            return AvgAggregate(self.field, self.output_names[0])
        return Int64Aggregate("avg", self.field, self.output_names[0], dt)

    @property
    def retractable(self) -> bool:
        return True

    def map_input(self, batch):
        return (batch[self.field],)

    def finish(self, merged):
        s, c = merged
        return {self.output_names[0]: s / jnp.maximum(c, 1.0)}

    def finish_host(self, parts):
        s, c = parts
        return {self.output_names[0]: (s / np.maximum(c, 1.0)).astype(
            np.float32)}


def _bound_dtype(func: str, field: str, dtypes) -> np.dtype:
    """The type ``func(field)`` binds to: the column's, where the device
    carries it exactly (an integer type, or a float of at most 32 bits).
    A wider float would be carried as a float32 (JAX without x64): that
    is refused, never narrowed silently."""
    dt = np.dtype(dtypes[field])
    if dt.kind == "f" and device_truncates(dt):
        raise TypeError(
            f"{func}({field}) over {dt}: the device (JAX without x64) "
            "would accumulate it as a float32; cast the column to a "
            "float32 or an integer type first")
    if dt.kind not in "iubf":
        raise TypeError(f"{func}({field}) over {dt}: not a number")
    return dt


def _fits_int32(dt: np.dtype) -> bool:
    """An integer type whose every value an int32 leaf holds."""
    return dt.kind in "ib" and dt.itemsize <= 4 or \
        dt.kind == "u" and dt.itemsize <= 2


def device_truncates(dtype) -> bool:
    """Whether a device array of ``dtype`` would lose width: JAX runs
    without x64 and carries a 64-bit type as its 32-bit sibling."""
    import jax

    return np.dtype(dtype).itemsize > 4 and not jax.config.jax_enable_x64


@public
class Int64Aggregate(AggregateFunction):
    """An exact 64-bit integer aggregate on the x32 device: SQL's BIGINT
    ``MAX`` / ``MIN`` / ``SUM`` / ``AVG`` and a 64-bit ``COUNT``.

    Each 64-bit part of the accumulator rides two int32 leaves, its low
    word and then its high word. No scatter folds them (a word-wise add
    loses the carry, a word-wise max the order): the operator sums,
    maxes or mins each batch's rows per slot on the host in int64, and
    one device program combines those partials with the stored words,
    carry and order included (``stateplane.families.wide_fold``).
    ``finish_host`` turns the words back into int64 on the host: ``AVG``
    is the sum divided by the count, truncated toward zero as Java's
    ``long`` division is, in ``result_dtype`` (the input's type). A
    window engine folds leaves by scatter and cannot run it:
    ``map_input`` refuses."""

    wide = True
    PARTS = {"max": (("max", "max"),), "min": (("min", "min"),),
             "sum": (("sum", "sum"),),
             "avg": (("sum", "sum"), ("count", "sum")),
             "count": (("count", "sum"),)}

    def __init__(self, kind: str, field: str = None, output: str = None,
                 result_dtype=np.int64):
        if kind not in self.PARTS:
            raise ValueError(f"unknown 64-bit aggregate {kind!r}; known: "
                             f"{', '.join(self.PARTS)}")
        dt = np.dtype(result_dtype)
        if dt.kind == "u" and dt.itemsize == 8:
            raise TypeError(
                f"{kind.upper()}({field}) over uint64: values past 2^63 "
                "have no exact int64 accumulator")
        self.kind = kind
        self.field = field
        self.result_dtype = dt
        self.leaves = tuple(
            AccLeaf(f"{name}_{word}", np.int32, "sum")
            for name, _ in self.PARTS[kind] for word in ("lo", "hi"))
        self.output_names = (output or f"{kind}_{field}",)

    @property
    def parts(self):
        return tuple((name, reduce, "<i8")
                     for name, reduce in self.PARTS[self.kind])

    @property
    def retractable(self) -> bool:
        return all(reduce == "sum" for _, reduce, _ in self.parts)

    def map_input(self, batch):
        raise TypeError(
            f"{self.kind.upper()}({self.field}) is an exact 64-bit "
            "aggregate: it folds host partials (GroupAggOperator), not a "
            "window engine's scatter")

    def part_values(self, batch: RecordBatch) -> List[np.ndarray]:
        """Each part's int64 value per row (a count's is 1)."""
        out = []
        for name, _, _ in self.parts:
            if name == "count":
                out.append(np.ones(len(batch), dtype=np.int64))
            else:
                v = np.asarray(batch[self.field])
                if v.dtype.kind == "u" and v.dtype.itemsize == 8:
                    raise TypeError(
                        f"{self.kind.upper()}({self.field}) over uint64: "
                        "values past 2^63 have no exact int64 accumulator")
                out.append(v.astype(np.int64, copy=False))
        return out

    def finish_host(self, parts: List[np.ndarray]) -> Dict[str, np.ndarray]:
        """The result column from each part's int64 values."""
        if self.kind != "avg":
            return {self.output_names[0]: parts[0].astype(
                self.result_dtype, copy=False)}
        s, c = parts
        q = np.abs(s) // np.maximum(np.abs(c), 1)
        q = np.where((s < 0) != (c < 0), -q, q)
        return {self.output_names[0]: q.astype(self.result_dtype,
                                               copy=False)}


@public
class MultiAggregate(AggregateFunction):
    """Compose several aggregates over the same key/window into one state
    table (one scatter pass, multiple result columns)."""

    def __init__(self, aggs: Sequence[AggregateFunction]):
        self.aggs = list(aggs)
        outs: List[str] = []
        for a in self.aggs:
            outs.extend(a.output_names)
        self.output_names = tuple(outs)
        self.wide = any(getattr(a, "wide", False) for a in self.aggs)
        if self.wide:
            # a COUNT beside a 64-bit part is counted as one too
            self.aggs = [Int64Aggregate("count", None, a.output_names[0])
                         if isinstance(a, CountAggregate) else a
                         for a in self.aggs]
        if any(a.leaves is None for a in self.aggs):
            # bound to the input's types at the first batch (``bind``)
            self.leaves = None
            return
        leaves: List[AccLeaf] = []
        self._spans = []
        for i, a in enumerate(self.aggs):
            start = len(leaves)
            leaves.extend(
                AccLeaf(f"a{i}_{l.name}", l.dtype, l.reduce, l.const)
                for l in a.leaves
            )
            self._spans.append((start, len(leaves)))
        self.leaves = tuple(leaves)
        if self.wide:
            for a in self.aggs:
                if type(a).finish_host is AggregateFunction.finish_host:
                    a.finish_host([])   # the refusal, named

    def bind(self, dtypes):
        if self.leaves is not None:
            return self
        return MultiAggregate([a.bind(dtypes) for a in self.aggs])

    @property
    def retractable(self) -> bool:
        return all(a.retractable for a in self.aggs)

    def cache_key(self):
        return ("multi", tuple(a.cache_key() for a in self.aggs))

    def map_input(self, batch):
        vals: List[np.ndarray] = []
        for a in self.aggs:
            vals.extend(a.map_input(batch))
        return tuple(vals)

    @property
    def parts(self):
        return tuple(p for a in self.aggs for p in a.parts)

    def part_values(self, batch):
        return [v for a in self.aggs for v in a.part_values(batch)]

    def finish_host(self, parts):
        out: Dict[str, np.ndarray] = {}
        at = 0
        for a in self.aggs:
            out.update(a.finish_host(parts[at:at + len(a.parts)]))
            at += len(a.parts)
        return out

    def finish(self, merged):
        out: Dict[str, jnp.ndarray] = {}
        for a, (s, e) in zip(self.aggs, self._spans):
            out.update(a.finish(tuple(merged[s:e])))
        return out
