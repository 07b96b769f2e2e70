"""Device columnar batch currency.

Reference parity: sql-plugin/src/main/java/com/nvidia/spark/rapids/
GpuColumnVector.java (cudf ColumnVector wrapped as Spark ColumnVector) and
ColumnarBatch usage throughout the exec layer.

TPU-first design decisions, deliberately different from the cuDF model:

- **Arrow-ish planes as JAX arrays.** A column is (data, validity) device
  arrays; strings are (offsets, bytes, validity). XLA operates on whole
  planes; there is no per-element object model.
- **Bucketed static capacity.** Every batch's arrays are padded to a
  power-of-two row capacity. `num_rows` is a host-side int. This keeps XLA
  shapes static so each operator stage compiles once per size bucket instead
  of once per batch (cuDF has dynamic shapes; XLA must not).
- **Validity is a bool plane, True = valid.** Data lanes of invalid or padded
  rows are *defined garbage*: kernels must mask through validity. Padded rows
  (row >= num_rows) always have validity False.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.runtime import shapes as _shapes
from spark_rapids_tpu.runtime.obs.phases import device_wait

MIN_CAPACITY = 8


def round_capacity(n: int, minimum: Optional[int] = None,
                   itemsize: Optional[int] = None) -> int:
    """Round a row count up to its capacity bucket. The bucket policy
    (geometric growth factor, per-dtype tile alignment) lives in
    runtime/shapes.py — spark.rapids.compile.shapes.*; the default
    reproduces the historical next-power-of-two capacities exactly."""
    if minimum is None:
        minimum = MIN_CAPACITY
    return _shapes.bucket_rows(n, minimum, itemsize)


class LazyRowCount:
    """A row count that lives on device until a host consumer forces it.

    Every device->host scalar readback is a host sync with a fixed cost
    that stalls the dispatch queue, so operators with data-dependent
    output sizes
    (filter, join, group) keep the count as a device scalar. Traced code
    reads it via `traced_rows` with NO synchronization; host control flow
    that truly needs the int (capacity decisions, limits, empty checks)
    materializes it once through the int dunders below.

    The reference pays this as a stream sync per cudf kernel with a dynamic
    result; deferring it is the TPU-idiomatic answer (SURVEY.md §7.3.1).
    """

    __slots__ = ("_dev", "_val")

    def __init__(self, dev):
        self._dev = dev
        self._val: Optional[int] = None

    def traced(self):
        return self._dev if self._val is None else self._val

    def materialize(self) -> int:
        if self._val is None:
            self._val = host_int(self._dev, "count")
        return self._val

    @property
    def is_materialized(self) -> bool:
        return self._val is not None

    def __int__(self):
        return self.materialize()

    __index__ = __int__

    def __bool__(self):
        return self.materialize() != 0

    def __eq__(self, o):
        return self.materialize() == o

    def __ne__(self, o):
        return self.materialize() != o

    def __lt__(self, o):
        return self.materialize() < o

    def __le__(self, o):
        return self.materialize() <= o

    def __gt__(self, o):
        return self.materialize() > o

    def __ge__(self, o):
        return self.materialize() >= o

    def __add__(self, o):
        return self.materialize() + o

    __radd__ = __add__

    def __sub__(self, o):
        return self.materialize() - o

    def __rsub__(self, o):
        return o - self.materialize()

    def __mul__(self, o):
        return self.materialize() * o

    __rmul__ = __mul__

    def __hash__(self):
        return hash(self.materialize())

    def __repr__(self):
        return (f"LazyRowCount({self._val})" if self._val is not None
                else "LazyRowCount(<device>)")


def traced_rows(n):
    """num_rows as a trace-safe value (device scalar or python int)."""
    return n.traced() if isinstance(n, LazyRowCount) else n


def host_int(dev, site: str) -> int:
    """A device scalar as a host int: a sync at door `site`, so the
    query's phase account counts and times it (runtime/obs/phases.py);
    a host scalar as it is, with no sync. Hand it the already-enqueued
    scalar: `host_int(jnp.sum(x), "size")`."""
    if not isinstance(dev, jax.Array):
        return int(dev)
    with device_wait(site):
        return int(dev)


def host_planes(tree, site: str):
    """`tree` (planes, lists, tuples, dicts of them; None allowed) with
    every device array in it brought to the host in ONE read at door
    `site`; as it is, with no sync, where nothing in it is on the device."""
    if any(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(tree)):
        with device_wait(site):
            return jax.device_get(tree)
    return tree


def rows_int(n) -> int:
    """num_rows as a host int (synchronizes if lazy)."""
    return int(n)


def materialize_counts(batches: Sequence["ColumnarBatch"]) -> None:
    """Force all lazy row counts in ONE bulk device fetch instead of a
    serial sync per batch."""
    lazies = [b.num_rows for b in batches
              if isinstance(b.num_rows, LazyRowCount) and not b.num_rows.is_materialized]
    if not lazies:
        return
    vals = host_planes([lz._dev for lz in lazies], "count")
    for lz, v in zip(lazies, vals):
        lz._val = int(v)


def carry_host_stats(src_cols, dst_cols) -> None:
    """Carry the host-side column stats (`bounds`, `str_width`,
    `str_bytes`: metadata, not pytree leaves) from columns to their 1:1
    row subsets or permutations across a jit or device_put boundary."""
    for src, dst in zip(src_cols, dst_cols):
        dst.bounds = src.bounds
        dst.str_width = src.str_width
        dst.str_bytes = src.str_bytes


def _pad_to(arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    if arr.shape[0] == capacity:
        return arr
    out = np.full((capacity,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


@dataclasses.dataclass
class ColumnVector:
    """One device-resident column.

    data:
      - fixed-width types: jnp array[capacity] of the type's np_dtype
      - StringType flat: dict(offsets=int32[capacity+1], bytes=uint8[byte_cap])
      - StringType dict-encoded: dict(codes=int32[capacity],
        dict_offsets=int32[k+1], dict_bytes=uint8[m]) — the vocab is small
        and shared by all rows. Dictionary encoding is the default upload
        layout for strings: hashing/grouping/equality run over the vocab
        once and gather by code (string group-bys and joins become integer
        ops on the MXU/VPU instead of byte-plane work).
    validity: bool[capacity], True = valid. None means all rows < num_rows
      are valid (padded tail is implicitly invalid).
    """

    dtype: T.DataType
    data: Union[jax.Array, Dict[str, jax.Array]]
    validity: Optional[jax.Array] = None
    #: dict columns only: True when vocab entries are known distinct
    #: (dictionary_encode / unified concat). Transformed vocabs (upper()
    #: can merge 'a' and 'A') set False — bucket-by-code aggregation
    #: requires code uniqueness.
    dict_unique: bool = True
    #: flat string columns only: True when the host that built the planes
    #: saw every non-null string once (column_from_arrow: a column of
    #: names or ids). A gather sees a flat column as its own dictionary
    #: (ops/kernels.flat_string_as_dict), and its codes are then unique a
    #: string exactly when this holds. Part of the pytree's static data;
    #: a flat column an expression computes leaves it False.
    flat_distinct: bool = False
    #: optional host-side (min, max) int bounds (cache-time column stats,
    #: the ParquetCachedBatchSerializer-stats analog). NOT part of the
    #: pytree: consumed only host-side (radix packing skips its device
    #: range probe). Conservative bounds stay valid under any row subset.
    bounds: "Optional[Tuple[int, int]]" = None
    #: string columns only: optional host-side bound, in bytes, on the
    #: longest string (dict columns: the longest vocabulary entry),
    #: stamped where the host builds the planes (column_from_arrow, a
    #: unified concat). NOT part of the pytree, carried like `bounds`
    #: (carry_host_stats): the keyed sort reads its static key width
    #: from it instead of a device read-back (ops/kernels
    #: .static_string_chunks). Stays valid under any row subset.
    str_width: Optional[int] = None
    #: flat string columns only: the bytes of the byte plane that rows
    #: own (offsets[num_rows]), where the host that built the planes knew
    #: (column_from_arrow, a concat). NOT part of the pytree, carried like
    #: `bounds`: an upper bound under a row subset, which shares the plane
    str_bytes: Optional[int] = None

    @property
    def capacity(self) -> int:
        if isinstance(self.data, dict):
            if "codes" in self.data:
                return int(self.data["codes"].shape[0])
            if "children" in self.data:  # struct: first child's capacity
                return self.data["children"][0].capacity
            return int(self.data["offsets"].shape[0]) - 1
        return int(self.data.shape[0])

    @property
    def is_string(self) -> bool:
        return isinstance(self.dtype, T.StringType)

    @property
    def is_dict(self) -> bool:
        return isinstance(self.data, dict) and "codes" in self.data

    @property
    def is_nested(self) -> bool:
        return isinstance(self.dtype, (T.ArrayType, T.StructType, T.MapType))

    @property
    def dict_size(self) -> int:
        return int(self.data["dict_offsets"].shape[0]) - 1

    def validity_or_default(self, num_rows) -> jax.Array:
        """Materialize the validity plane (capacity-length bool)."""
        cap = self.capacity
        if self.validity is not None:
            return self.validity
        return jnp.arange(cap) < traced_rows(num_rows)

    def device_memory_size(self) -> int:
        def sz(a):
            if isinstance(a, ColumnVector):
                return a.device_memory_size()
            if isinstance(a, (list, tuple)):
                return sum(sz(x) for x in a)
            return int(np.prod(a.shape)) * a.dtype.itemsize
        total = 0
        if isinstance(self.data, dict):
            total += sum(sz(a) for a in self.data.values())
        else:
            total += sz(self.data)
        if self.validity is not None:
            total += sz(self.validity)
        return total


@dataclasses.dataclass
class ColumnarBatch:
    """A set of equal-capacity columns plus the true row count.

    row_mask (optional bool[capacity], True = live) is a selection vector:
    filters mark rows dead instead of gathering survivors (TPU gathers cost
    O(output); compaction of a mostly-surviving batch is the single most
    expensive thing you can do on this hardware, while masking is free and
    fuses into the next op). None means rows [0, num_rows) are live.
    Operators must treat dead rows as NONEXISTENT (not as null rows).
    """

    columns: List[ColumnVector]
    num_rows: int
    row_mask: Optional[jax.Array] = None

    def live_mask(self) -> jax.Array:
        """bool[capacity] marking live rows."""
        if self.row_mask is not None:
            return self.row_mask
        return jnp.arange(self.capacity) < traced_rows(self.num_rows)

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        if not self.columns:
            return round_capacity(self.num_rows)
        return self.columns[0].capacity

    def device_memory_size(self) -> int:
        return sum(c.device_memory_size() for c in self.columns)

    def column(self, i: int) -> ColumnVector:
        return self.columns[i]

    def select(self, indices: Sequence[int]) -> "ColumnarBatch":
        return ColumnarBatch([self.columns[i] for i in indices], self.num_rows)


# ---------------------------------------------------------------------------
# Host <-> device conversion (the R2C / C2R transition analog; reference
# GpuRowToColumnarExec / GpuColumnarToRowExec, here via Arrow planes).
# ---------------------------------------------------------------------------

def _np_valid_from_arrow(arr) -> Optional[np.ndarray]:
    import pyarrow as pa  # noqa: F401
    if arr.null_count == 0:
        return None
    # pyarrow validity bitmap -> bool array
    return np.asarray(arr.is_valid())


def _fixed_width_view(arr, np_dtype) -> np.ndarray:
    """Zero-copy view of a fixed-width pyarrow array's data buffer (a host
    `.astype()` round trip through object dtype is ~100x slower for
    date/timestamp columns)."""
    buf = arr.buffers()[1]
    view = np.frombuffer(buf, dtype=np_dtype, count=arr.offset + len(arr))
    out = view[arr.offset:]
    return out if out.dtype == np_dtype else out.astype(np_dtype)


def max_entry_len(offsets_np: np.ndarray) -> int:
    """Longest entry, in bytes, of a host offsets plane (`str_width`)."""
    return int(np.diff(offsets_np).max(initial=0))


def _pad_offsets(offsets_np: np.ndarray, n: int, capacity: int) -> np.ndarray:
    out = np.full(capacity + 1, offsets_np[n] if n < len(offsets_np)
                  else offsets_np[-1], dtype=np.int32)
    out[: n + 1] = offsets_np[: n + 1]
    return out


#: a string column longer than this is sampled before it is
#: dictionary-encoded whole: _SAMPLE_RUNS runs of _SAMPLE_RUN_ROWS
#: consecutive rows, evenly spread
_SAMPLE_ABOVE = 1 << 17
_SAMPLE_RUNS, _SAMPLE_RUN_ROWS = 16, 4096


def _mostly_distinct(arr, n: int) -> bool:
    """Whether a long string column is so nearly all different values that
    its vocabulary cannot be half its rows or fewer (the layout rule in
    column_from_arrow), judged from a sample: hashing a million distinct
    comments to learn that they should not have been hashed cost half a
    second a batch (PERF.md, PR 36). More than 31 of 32 sampled rows
    distinct means flat; anything less and the column is encoded whole and
    the rule applied to its true vocabulary, as for every shorter column.
    Values drawn evenly from a vocabulary of half the rows would show the
    sample some 94% distinct, skewed ones fewer.

    NOT the old rule's outcome in every case: a column whose repeats all
    lie further apart than the sample sees (each value twice, half the
    column apart) shows it no repeat and uploads flat, where its true
    vocabulary, half its rows, took the dictionary layout. Flat is right
    for any column, only larger there. And a column taken flat from the
    sample is not KNOWN distinct (`flat_distinct` False: its strings were
    not all seen), so a gather of it canonicalises its codes where a short
    column's would not (ops/kernels.flat_string_as_dict)."""
    if n <= _SAMPLE_ABOVE:
        return False
    import pyarrow as pa
    import pyarrow.compute as pc
    step = n // _SAMPLE_RUNS
    sample = pa.concat_arrays([arr.slice(i * step, _SAMPLE_RUN_ROWS)
                               for i in range(_SAMPLE_RUNS)])
    return len(pc.unique(sample)) > len(sample) - len(sample) // 32


def column_from_arrow(arr, dtype: T.DataType, capacity: int) -> ColumnVector:
    """Build a device ColumnVector from a pyarrow Array (one chunk)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    n = len(arr)
    valid_np = _np_valid_from_arrow(arr)
    str_width = str_bytes = None
    flat_distinct = False

    if isinstance(dtype, T.ArrayType):
        arr = _normalize_null_slices(arr, pa.list_(T.to_arrow(dtype.element)))
        off = np.asarray(arr.offsets, dtype=np.int64)
        base = int(off[0])
        values = arr.values[base: int(off[-1])]
        offsets_np = (off - base).astype(np.int32)
        child_cap = round_capacity(max(len(values), 1))
        child = column_from_arrow(values, dtype.element, child_cap)
        data = {"offsets": jnp.asarray(_pad_offsets(offsets_np, n, capacity)),
                "child": child}
        validity = None if valid_np is None else jnp.asarray(
            _pad_to(valid_np.astype(np.bool_), capacity, fill=False))
        return ColumnVector(dtype, data, validity)

    if isinstance(dtype, T.MapType):
        arr = _normalize_null_slices(
            arr, pa.map_(T.to_arrow(dtype.key), T.to_arrow(dtype.value)))
        off = np.asarray(arr.offsets, dtype=np.int64)
        base = int(off[0])
        keys = arr.keys[base: int(off[-1])]
        items = arr.items[base: int(off[-1])]
        offsets_np = (off - base).astype(np.int32)
        child_cap = round_capacity(max(len(keys), 1))
        data = {"offsets": jnp.asarray(_pad_offsets(offsets_np, n, capacity)),
                "keys": column_from_arrow(keys, dtype.key, child_cap),
                "values": column_from_arrow(items, dtype.value, child_cap)}
        validity = None if valid_np is None else jnp.asarray(
            _pad_to(valid_np.astype(np.bool_), capacity, fill=False))
        return ColumnVector(dtype, data, validity)

    if isinstance(dtype, T.StructType):
        if not dtype.fields:
            raise TypeError("empty struct columns are not supported")
        kids = [column_from_arrow(arr.field(i), f.dtype, capacity)
                for i, f in enumerate(dtype.fields)]
        validity = None if valid_np is None else jnp.asarray(
            _pad_to(valid_np.astype(np.bool_), capacity, fill=False))
        return ColumnVector(dtype, {"children": kids}, validity)

    if isinstance(dtype, T.StringType):
        if pa.types.is_dictionary(arr.type):
            denc = arr
        elif _mostly_distinct(arr, n):
            denc = None     # flat, and nobody hashed the whole column
        else:
            denc = arr.dictionary_encode()
        vocab = denc.dictionary if denc is not None else None
        # Dictionary layout pays off when the vocab is materially smaller
        # than the data; otherwise flat offsets+bytes (e.g. unique IDs).
        if vocab is not None and len(vocab) <= max(64, n // 2):
            codes = denc.indices
            if codes.null_count:
                codes = pc.fill_null(codes, 0)
            codes_np = np.asarray(codes).astype(np.int32)
            voc = vocab.cast(pa.large_string()) if not pa.types.is_large_string(vocab.type) else vocab
            voff = np.frombuffer(voc.buffers()[1], dtype=np.int64)
            voff = voff[voc.offset: voc.offset + len(voc) + 1]
            base = int(voff[0])
            vlen = int(voff[-1] - base)
            vbytes = np.frombuffer(voc.buffers()[2] or b"", dtype=np.uint8)[base: base + vlen]
            data = {
                "codes": jnp.asarray(_pad_to(codes_np, capacity)),
                "dict_offsets": jnp.asarray((voff - base).astype(np.int32)),
                "dict_bytes": jnp.asarray(np.ascontiguousarray(vbytes)
                                          if vlen else np.zeros(1, np.uint8)),
            }
            if valid_np is None:
                validity = None
            else:
                validity = jnp.asarray(_pad_to(valid_np.astype(np.bool_), capacity, fill=False))
            return ColumnVector(dtype, data, validity,
                                str_width=max_entry_len(voff))
        arr = arr.cast(pa.large_string()) if not pa.types.is_large_string(arr.type) else arr
        # fill nulls with "" so offsets stay monotone and bytes well-defined
        filled = pc.fill_null(arr, "")
        if isinstance(filled, pa.ChunkedArray):
            filled = filled.combine_chunks()
        off_buf = np.frombuffer(filled.buffers()[1], dtype=np.int64)
        buf_offsets = off_buf[filled.offset: filled.offset + n + 1]
        byte_len = int(buf_offsets[-1] - buf_offsets[0])
        data_buf = np.frombuffer(filled.buffers()[2] or b"", dtype=np.uint8)
        base = int(buf_offsets[0])
        bytes_np = data_buf[base: base + byte_len]
        offsets_np = (buf_offsets - base).astype(np.int32)
        byte_cap = round_capacity(max(byte_len, 1), itemsize=1)
        off_padded = np.full(capacity + 1, offsets_np[-1], dtype=np.int32)
        off_padded[: n + 1] = offsets_np
        data = {
            "offsets": jnp.asarray(off_padded),
            "bytes": jnp.asarray(_pad_to(bytes_np, byte_cap)),
        }
        str_width = max_entry_len(offsets_np)
        str_bytes = byte_len
        # a sampled column's strings were not all seen: not known distinct
        flat_distinct = vocab is not None \
            and len(vocab) == n - arr.null_count
    elif isinstance(dtype, T.BooleanType):
        np_arr = np.asarray(pc.fill_null(arr, False), dtype=np.bool_)
        data = jnp.asarray(_pad_to(np_arr, capacity))
    elif isinstance(dtype, T.NullType):
        data = jnp.zeros(capacity, dtype=np.int8)
        valid_np = np.zeros(n, dtype=np.bool_)
    elif isinstance(dtype, T.DecimalType):
        np_arr = np.zeros(n, dtype=np.int64)
        py = arr.to_pylist()
        scale = dtype.scale
        for i, v in enumerate(py):
            if v is not None:
                np_arr[i] = int((v.scaleb(scale)).to_integral_value())
        data = jnp.asarray(_pad_to(np_arr, capacity))
    elif isinstance(dtype, T.TimestampType):
        cast = arr.cast(pa.timestamp("us"))
        if cast.null_count:
            cast = pc.fill_null(cast, 0)
        data = jnp.asarray(_pad_to(_fixed_width_view(cast, np.int64), capacity))
    elif isinstance(dtype, T.DateType):
        if arr.null_count:
            arr = pc.fill_null(arr, 0)
        data = jnp.asarray(_pad_to(_fixed_width_view(arr, np.int32), capacity))
    else:
        if arr.null_count:
            arr = pc.fill_null(arr, 0)
        np_arr = _fixed_width_view(arr, np.dtype(dtype.np_dtype))
        data = jnp.asarray(_pad_to(np_arr, capacity))

    if valid_np is None:
        validity = None
    else:
        validity = jnp.asarray(_pad_to(valid_np.astype(np.bool_), capacity, fill=False))
    return ColumnVector(dtype, data, validity, str_width=str_width,
                        flat_distinct=flat_distinct, str_bytes=str_bytes)


def from_arrow(table, device=None) -> ColumnarBatch:
    """pyarrow Table -> device ColumnarBatch (single upload per plane).
    With `device` the planes are built on that device and committed to
    it: a cache placed over a mesh uploads each row range into place."""
    if device is not None:
        with jax.default_device(device):
            batch = from_arrow(table)
        cols = jax.device_put(batch.columns, device)
        carry_host_stats(batch.columns, cols)
        return ColumnarBatch(cols, batch.num_rows)
    table = table.combine_chunks()
    n = table.num_rows
    cap = round_capacity(n)
    cols = []
    for i, field in enumerate(table.schema):
        dtype = T.from_arrow(field.type)
        chunked = table.column(i)
        arr = chunked.chunk(0) if chunked.num_chunks else chunked.combine_chunks()
        cols.append(column_from_arrow(arr, dtype, cap))
    return ColumnarBatch(cols, n)


def _normalize_null_slices(arr, target_type):
    """Cast a list/map array to the canonical layout and ensure null rows
    own empty slices (so child planes carry no garbage elements). Arrow
    permits null entries with non-empty ranges; the device layout does not."""
    import pyarrow as pa
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if arr.type != target_type:
        arr = arr.cast(target_type)
    if arr.null_count:
        off = np.asarray(arr.offsets, dtype=np.int64)
        lengths = np.diff(off)
        valid = np.asarray(arr.is_valid())
        if (lengths[: len(valid)][~valid] != 0).any():
            # pa.array rebuilds with zero-length slices under null entries
            arr = pa.array(arr.to_pylist(), type=target_type)
    return arr


def _leaf_to_py(col: ColumnVector, vals, valid, i: int):
    """One leaf value as an arrow-acceptable python object."""
    if valid is not None and not valid[i]:
        return None
    v = vals[i]
    if isinstance(col.dtype, T.DecimalType):
        import decimal
        return decimal.Decimal(int(v)).scaleb(-col.dtype.scale)
    if isinstance(col.dtype, T.TimestampType):
        return int(v)
    if isinstance(col.dtype, T.DateType):
        return int(v)
    if isinstance(v, (np.generic,)):
        return v.item()
    return v


def column_to_pylist(col: ColumnVector, n: int) -> list:
    """Host materialization of the first n rows of a (possibly nested)
    column as python values (None = null). Planes must already be host
    arrays or cheap to fetch."""
    if isinstance(col.dtype, T.ArrayType):
        off = np.asarray(col.data["offsets"])
        child_vals = column_to_pylist(col.data["child"], int(off[n]))
        valid = None if col.validity is None else np.asarray(col.validity)
        return [None if (valid is not None and not valid[i])
                else child_vals[off[i]: off[i + 1]] for i in range(n)]
    if isinstance(col.dtype, T.MapType):
        off = np.asarray(col.data["offsets"])
        keys = column_to_pylist(col.data["keys"], int(off[n]))
        vals = column_to_pylist(col.data["values"], int(off[n]))
        valid = None if col.validity is None else np.asarray(col.validity)
        return [None if (valid is not None and not valid[i])
                else list(zip(keys[off[i]: off[i + 1]],
                              vals[off[i]: off[i + 1]]))
                for i in range(n)]
    if isinstance(col.dtype, T.StructType):
        kids = [column_to_pylist(ch, n) for ch in col.data["children"]]
        names = [f.name for f in col.dtype.fields]
        valid = None if col.validity is None else np.asarray(col.validity)
        return [None if (valid is not None and not valid[i])
                else {nm: kid[i] for nm, kid in zip(names, kids)}
                for i in range(n)]
    vals, valid = column_to_numpy(col, n)
    if col.is_string:
        return vals
    return [_leaf_to_py(col, vals, valid, i) for i in range(n)]


def column_to_numpy(col: ColumnVector, num_rows: int, sel=None):
    """Device -> host materialization of one column as (values, validity).
    sel: optional host int array of live row positions (selection-mask
    compaction happens here, on host, where it is a cheap numpy take)."""
    valid = None
    if col.validity is not None:
        valid = np.asarray(col.validity)
        valid = valid[sel] if sel is not None else valid[:num_rows]
    if col.is_dict:
        codes = np.asarray(col.data["codes"])
        codes = codes[sel] if sel is not None else codes[:num_rows]
        offsets = np.asarray(col.data["dict_offsets"])
        raw = np.asarray(col.data["dict_bytes"])
        vocab = [bytes(raw[offsets[i]: offsets[i + 1]]).decode("utf-8", "replace")
                 for i in range(len(offsets) - 1)]
        out = []
        for i in range(len(codes)):
            if valid is not None and not valid[i]:
                out.append(None)
            else:
                out.append(vocab[codes[i]])
        return out, valid
    if col.is_string:
        offsets = np.asarray(col.data["offsets"])
        raw = np.asarray(col.data["bytes"])
        rows = sel if sel is not None else range(num_rows)
        out = []
        for j, i in enumerate(rows):
            if valid is not None and not valid[j]:
                out.append(None)
            else:
                out.append(bytes(raw[offsets[i]: offsets[i + 1]]).decode("utf-8", "replace"))
        return out, valid
    vals = np.asarray(col.data)
    vals = vals[sel] if sel is not None else vals[:num_rows]
    return vals, valid


def fetch_batch_host(batch: ColumnarBatch, site: str) -> ColumnarBatch:
    """Pull every plane of a batch to host in ONE bulk transfer (a
    per-plane np.asarray costs a round trip each) at door `site`. Returns
    a batch whose planes are host numpy arrays; the lazy row count rides
    along."""
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    out = jax.tree_util.tree_unflatten(treedef, host_planes(leaves, site))
    n = int(out.num_rows)
    if isinstance(batch.num_rows, LazyRowCount):
        batch.num_rows._val = n
    return ColumnarBatch(out.columns, n, out.row_mask)


def to_arrow(batch: ColumnarBatch, names: Optional[Sequence[str]] = None,
             site: str = "fetch"):
    """Device ColumnarBatch -> pyarrow Table (C2R boundary), downloaded at
    door `site` (a result's rows by default; "host" where an operator
    takes the rows to the host mid-query). Selection-mask compaction
    happens host-side with numpy (free next to the transfer)."""
    import pyarrow as pa
    batch = fetch_batch_host(batch, site)
    n = batch.num_rows
    sel = None
    if batch.row_mask is not None:
        sel = np.flatnonzero(np.asarray(batch.row_mask))
        n = len(sel)
    arrays = []
    fields = []
    for i, col in enumerate(batch.columns):
        name = names[i] if names else f"c{i}"
        at = T.to_arrow(col.dtype)
        if col.is_nested:
            # sel holds raw capacity positions; materialize up to capacity
            full = column_to_pylist(col, col.capacity if sel is not None else n)
            vals = [full[i] for i in sel] if sel is not None else full
            arrays.append(pa.array(vals, type=at))
            fields.append(pa.field(name, at))
            continue
        vals, valid = column_to_numpy(col, n, sel)
        if col.is_string:
            arr = pa.array(vals, type=at)
        elif isinstance(col.dtype, T.NullType):
            arr = pa.nulls(n, type=at)
        elif isinstance(col.dtype, T.DecimalType):
            import decimal
            scale = col.dtype.scale
            py = [None if (valid is not None and not valid[j])
                  else decimal.Decimal(int(vals[j])).scaleb(-scale)
                  for j in range(n)]
            arr = pa.array(py, type=at)
        elif isinstance(col.dtype, T.TimestampType):
            mask = None if valid is None else ~valid
            arr = pa.array(vals.astype("datetime64[us]"), type=at,
                           mask=mask)
        elif isinstance(col.dtype, T.DateType):
            mask = None if valid is None else ~valid
            arr = pa.array(vals.astype("datetime64[D]"), type=at, mask=mask)
        else:
            mask = None if valid is None else ~valid
            arr = pa.array(vals, type=at, mask=mask)
        arrays.append(arr)
        fields.append(pa.field(name, at))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))


def from_pydict(d: dict, schema: Optional[T.Schema] = None) -> ColumnarBatch:
    import pyarrow as pa
    if schema is not None:
        pa_schema = pa.schema([pa.field(f.name, T.to_arrow(f.dtype)) for f in schema.fields])
        return from_arrow(pa.table(d, schema=pa_schema))
    return from_arrow(pa.table(d))


def to_pydict(batch: ColumnarBatch, names: Optional[Sequence[str]] = None) -> dict:
    return to_arrow(batch, names).to_pydict()


# ---------------------------------------------------------------------------
# JAX pytree registration: ColumnVector/ColumnarBatch/LazyRowCount pass
# straight through jax.jit, so a WHOLE operator (filter, group+aggregate,
# sort, join) fuses into one XLA computation — one dispatch per batch
# instead of one per kernel. Dtypes are static aux data; row counts are
# traced scalars (no recompile per batch size, no host sync).
# ---------------------------------------------------------------------------

def _cv_flatten(c: ColumnVector):
    if isinstance(c.data, dict):
        if "codes" in c.data:
            return ((c.data["codes"], c.data["dict_offsets"],
                     c.data["dict_bytes"], c.validity),
                    ("dict", c.dtype, c.dict_unique))
        if "child" in c.data:  # array: offsets + nested child CV
            return ((c.data["offsets"], c.data["child"], c.validity),
                    ("array", c.dtype))
        if "keys" in c.data:  # map: offsets + key/value child CVs
            return ((c.data["offsets"], c.data["keys"], c.data["values"],
                     c.validity), ("map", c.dtype))
        if "children" in c.data:  # struct: per-field child CVs
            return ((tuple(c.data["children"]), c.validity),
                    ("struct", c.dtype))
        return ((c.data["offsets"], c.data["bytes"], c.validity),
                ("str", c.dtype, c.flat_distinct))
    return (c.data, c.validity), ("fixed", c.dtype)


def _cv_unflatten(aux, children):
    kind, dtype = aux[0], aux[1]
    if kind == "dict":
        codes, doff, dby, validity = children
        return ColumnVector(dtype, {"codes": codes, "dict_offsets": doff,
                                    "dict_bytes": dby}, validity,
                            dict_unique=aux[2])
    if kind == "array":
        off, child, validity = children
        return ColumnVector(dtype, {"offsets": off, "child": child}, validity)
    if kind == "map":
        off, keys, values, validity = children
        return ColumnVector(dtype, {"offsets": off, "keys": keys,
                                    "values": values}, validity)
    if kind == "struct":
        kids, validity = children
        return ColumnVector(dtype, {"children": list(kids)}, validity)
    if kind == "str":
        off, by, validity = children
        return ColumnVector(dtype, {"offsets": off, "bytes": by}, validity,
                            flat_distinct=aux[2])
    data, validity = children
    return ColumnVector(dtype, data, validity)


def _lrc_flatten(lz: LazyRowCount):
    return (lz.traced(),), None


def _lrc_unflatten(aux, children):
    v = children[0]
    return v if isinstance(v, int) else LazyRowCount(v)


def _cb_flatten(b: ColumnarBatch):
    return (b.columns, b.num_rows, b.row_mask), None


def _cb_unflatten(aux, children):
    cols, n, row_mask = children
    if not isinstance(n, (int, LazyRowCount)):
        n = LazyRowCount(n)  # raw int leaves come back as device scalars
    return ColumnarBatch(cols, n, row_mask)


jax.tree_util.register_pytree_node(ColumnVector, _cv_flatten, _cv_unflatten)
jax.tree_util.register_pytree_node(LazyRowCount, _lrc_flatten, _lrc_unflatten)
jax.tree_util.register_pytree_node(ColumnarBatch, _cb_flatten, _cb_unflatten)


def empty_like_schema(schema: T.Schema, capacity: int = MIN_CAPACITY) -> ColumnarBatch:
    cols = []
    for f in schema.fields:
        if isinstance(f.dtype, T.StringType):
            data = {"offsets": jnp.zeros(capacity + 1, jnp.int32),
                    "bytes": jnp.zeros(MIN_CAPACITY, jnp.uint8)}
        else:
            data = jnp.zeros(capacity, dtype=f.dtype.np_dtype)
        cols.append(ColumnVector(f.dtype, data, jnp.zeros(capacity, jnp.bool_)))
    return ColumnarBatch(cols, 0)
