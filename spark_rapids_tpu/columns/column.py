"""Arrow-layout device columns on JAX arrays.

The reference operates on `cudf::column_view` (data ptr, packed validity bits,
int32 offsets, children).  Here a Column is an immutable pytree of jax arrays:

  data      fixed-width: (rows,) natural dtype — EXCEPT float64, which is
            stored as (rows,) uint64 raw IEEE754 bits: TPUs have no native
            f64 (the XLA X64 rewrite demotes f64 compute to f32, and
            f64<->u64 bitcasts don't lower at all), so the exact Spark
            DOUBLE bit patterns live in integer lanes and ops that need
            true f64 arithmetic decode explicitly (utils/floats.py).
            string:      (chars,) uint8 — the flattened char buffer
            decimal128:  (rows, 4) int32 little-endian limbs
  validity  (rows,) uint8, 1 = valid; None means all rows valid.  Unpacked on
            device (packed bits don't vectorize on 8x128 lanes); packed only at
            serialization boundaries (Kudo / Arrow interop).  A producer that
            holds the bits in another form may hand over a DeferredValidity
            in place of the vector (convert_from_rows does: the bits stay
            the row words they are); the first read of ``validity`` (and so
            ``has_validity``, ``null_count``, ``valid_mask``, a pytree
            flatten) resolves it, once, to the vector or to None, and None
            then says that no row is null.  A plain array or None assigned
            to ``validity`` is read back as it is.
  offsets   (rows+1,) int32 for STRING and LIST (CUDF_LARGE_STRINGS_DISABLED
            semantics: offsets are int32, <=2^31 chars per column).
  children  LIST: (element column,); STRUCT: field columns.

Columns are registered as jax pytrees, so whole Tables flow through jit /
shard_map unchanged.  Ops never mutate; they build new Columns.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.dtypes import DType, Kind


class DeferredValidity:
    """A column's validity that is not made yet.  ``resolve()`` returns
    the (rows,) uint8 vector, or None where every row is valid; it may
    read from the device, gives the same answer every time, and is safe
    from two threads.  ``Column.validity`` calls it at the first read
    and keeps the answer."""

    __slots__ = ()

    def resolve(self) -> Optional[jnp.ndarray]:
        raise NotImplementedError


class Column:
    __slots__ = ("dtype", "length", "data", "_validity", "offsets",
                 "children")

    def __init__(
        self,
        dtype: DType,
        length: int,
        data: Optional[jnp.ndarray] = None,
        validity: Optional[jnp.ndarray] = None,
        offsets: Optional[jnp.ndarray] = None,
        children: Tuple["Column", ...] = (),
    ):
        self.dtype = dtype
        self.length = int(length)
        self.data = data
        self.validity = validity
        self.offsets = offsets
        self.children = tuple(children)

    # ------------------------------------------------------------------ misc

    def __len__(self) -> int:
        return self.length

    def __repr__(self) -> str:
        return f"Column({self.dtype!r}, length={self.length})"

    @property
    def validity(self) -> Optional[jnp.ndarray]:
        v = self._validity
        if isinstance(v, DeferredValidity):
            v = self._validity = v.resolve()
        return v

    @validity.setter
    def validity(self, v) -> None:
        self._validity = v

    @property
    def has_validity(self) -> bool:
        return self.validity is not None

    def null_count(self) -> int:
        """Host-syncing null count (test/debug use; not for jitted paths)."""
        if self.validity is None:
            return 0
        return int(self.length - np.asarray(self.validity[: self.length]).sum())

    def valid_mask(self) -> jnp.ndarray:
        """(rows,) bool mask, materializing all-valid if validity is None."""
        if self.validity is None:
            return jnp.ones((self.length,), dtype=jnp.bool_)
        return self.validity.astype(jnp.bool_)

    # ---------------------------------------------------------- constructors

    @staticmethod
    def from_numpy(arr: np.ndarray, validity: Optional[np.ndarray] = None,
                   dtype: Optional[DType] = None) -> "Column":
        arr = np.asarray(arr)
        dt = dtype if dtype is not None else dtypes.from_numpy(arr.dtype)
        host = arr.astype(dt.np_dtype, copy=False)
        if dt.kind == Kind.FLOAT64:
            host = host.view(np.uint64)  # device buffer holds raw bits
        data = jnp.asarray(host)
        v = None
        if validity is not None:
            v = jnp.asarray(np.asarray(validity).astype(np.uint8))
        return Column(dt, arr.shape[0], data=data, validity=v)

    @staticmethod
    def from_pylist(values: Sequence, dtype: DType) -> "Column":
        """Build a column from a python list; None entries become nulls."""
        if dtype.is_string:
            return Column.from_strings(values)
        if dtype.kind == Kind.DECIMAL128:
            return Column._decimal128_from_pylist(values, dtype)
        n = len(values)
        has_null = any(v is None for v in values)
        np_dt = dtype.np_dtype
        fill = 0
        host = np.array([fill if v is None else v for v in values], dtype=np_dt)
        if dtype.kind == Kind.FLOAT64:
            host = host.view(np.uint64)
        v = None
        if has_null:
            v = jnp.asarray(
                np.array([0 if x is None else 1 for x in values], np.uint8))
        return Column(dtype, n, data=jnp.asarray(host), validity=v)

    @staticmethod
    def _decimal128_from_pylist(values: Sequence, dtype: DType) -> "Column":
        """(rows, 4) int32 little-endian limbs from python ints (the unscaled
        decimal value), two's complement across the 128-bit word."""
        n = len(values)
        limbs = np.zeros((n, 4), dtype=np.int32)
        vmask = np.ones(n, dtype=np.uint8)
        for i, v in enumerate(values):
            if v is None:
                vmask[i] = 0
                continue
            u = int(v) & ((1 << 128) - 1)
            for j in range(4):
                limbs[i, j] = np.uint32((u >> (32 * j)) & 0xFFFFFFFF).astype(
                    np.int32)
        validity = None if vmask.all() else jnp.asarray(vmask)
        return Column(dtype, n, data=jnp.asarray(limbs), validity=validity)

    @staticmethod
    def from_strings(values: Sequence[Optional[Union[str, bytes]]]) -> "Column":
        n = len(values)
        bufs: List[bytes] = []
        offs = np.zeros(n + 1, dtype=np.int32)
        vmask = np.ones(n, dtype=np.uint8)
        total = 0
        for i, s in enumerate(values):
            if s is None:
                vmask[i] = 0
                b = b""
            else:
                b = s.encode("utf-8") if isinstance(s, str) else bytes(s)
            bufs.append(b)
            total += len(b)
            offs[i + 1] = total
        chars = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
        validity = None if vmask.all() else jnp.asarray(vmask)
        return Column(
            dtypes.STRING, n,
            data=jnp.asarray(chars),
            validity=validity,
            offsets=jnp.asarray(offs),
        )

    @staticmethod
    def make_list(offsets: np.ndarray, child: "Column",
                  validity: Optional[np.ndarray] = None) -> "Column":
        offs = jnp.asarray(np.asarray(offsets, dtype=np.int32))
        v = None if validity is None else jnp.asarray(
            np.asarray(validity).astype(np.uint8))
        return Column(dtypes.LIST, len(offsets) - 1, validity=v,
                      offsets=offs, children=(child,))

    @staticmethod
    def make_list_from_parts(offsets: jnp.ndarray, byte_data: jnp.ndarray,
                             validity: Optional[jnp.ndarray] = None,
                             nbytes: Optional[int] = None) -> "Column":
        """LIST<UINT8> column from device offsets + flat byte buffer (the
        shape JCUDF rows and kudo blobs take).  `byte_data` may be uint8 or
        packed uint32 LE words (columns/bytesview.py) — uint8 minor dims
        tile terribly on TPU, so bulk producers pass words."""
        if byte_data.dtype == jnp.uint32:
            if nbytes is None:
                raise ValueError(
                    "packed uint32 byte_data requires explicit nbytes (the "
                    "word buffer may carry up to 3 tail pad bytes)")
            child = Column(dtypes.UINT8, nbytes, data=byte_data)
        else:
            child = Column(dtypes.UINT8, int(byte_data.shape[0]),
                           data=byte_data.astype(jnp.uint8))
        return Column(dtypes.LIST, int(offsets.shape[0]) - 1,
                      validity=validity, offsets=offsets.astype(jnp.int32),
                      children=(child,))

    @staticmethod
    def make_struct(length: int, children: Sequence["Column"],
                    validity: Optional[np.ndarray] = None) -> "Column":
        v = None if validity is None else jnp.asarray(
            np.asarray(validity).astype(np.uint8))
        return Column(dtypes.STRUCT, length, validity=v,
                      children=tuple(children))

    # ------------------------------------------------------------- host view

    def to_numpy(self) -> np.ndarray:
        """Data buffer to host in the logical dtype (no null masking)."""
        if self.data is None:
            raise ValueError(f"{self.dtype} column has no data buffer")
        host = np.asarray(self.data)
        if self.dtype.kind == Kind.FLOAT64:
            return host.view(np.float64)
        if self.dtype.kind == Kind.UINT8 and host.dtype == np.uint32:
            # packed byte column (columns/bytesview.py)
            return host.view(np.uint8)[: self.length]
        return host

    def to_pylist(self) -> list:
        """Host round-trip with None for nulls (test/debug use)."""
        mask = (np.ones(self.length, bool) if self.validity is None
                else np.asarray(self.validity).astype(bool)[: self.length])
        if self.dtype.is_string:
            chars = np.asarray(self.data).tobytes()
            offs = np.asarray(self.offsets)
            out: list = []
            for i in range(self.length):
                if not mask[i]:
                    out.append(None)
                else:
                    out.append(chars[offs[i]: offs[i + 1]].decode(
                        "utf-8", errors="replace"))
            return out
        if self.dtype.kind == Kind.LIST:
            offs = np.asarray(self.offsets)
            child = self.children[0].to_pylist()
            return [child[offs[i]: offs[i + 1]] if mask[i] else None
                    for i in range(self.length)]
        if self.dtype.kind == Kind.STRUCT:
            cols = [c.to_pylist() for c in self.children]
            return [tuple(c[i] for c in cols) if mask[i] else None
                    for i in range(self.length)]
        host = self.to_numpy()
        if self.dtype.kind == Kind.BOOL8:
            return [bool(host[i]) if mask[i] else None
                    for i in range(self.length)]
        if self.dtype.kind == Kind.DECIMAL128:
            out = []
            limbs = host.astype(np.uint32).astype(object)
            for i in range(self.length):
                if not mask[i]:
                    out.append(None)
                    continue
                u = sum(int(limbs[i, j]) << (32 * j) for j in range(4))
                if u >= 1 << 127:
                    u -= 1 << 128
                out.append(u)  # unscaled value
            return out
        return [host[i].item() if mask[i] else None
                for i in range(self.length)]

    # ------------------------------------------------------- string helpers

    def string_lengths(self) -> jnp.ndarray:
        """(rows,) int32 byte length per string row."""
        assert self.dtype.is_string
        return self.offsets[1:] - self.offsets[:-1]

    def max_string_length(self) -> int:
        """Host-syncing max byte length (used to size padded kernels)."""
        assert self.dtype.is_string
        if self.length == 0:
            return 0
        return int(np.asarray(self.string_lengths()).max())

    def to_padded_chars(self, pad_to: Optional[int] = None,
                        fill: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Dense (rows, pad_to) uint8 char matrix + (rows,) int32 lengths.

        The workhorse representation for TPU string kernels: fixed shape so
        XLA can tile it; `fill` bytes beyond each row's length.  Memory cost
        rows*pad_to — callers chunk via ops budgets for long tails (the
        reference's scratch-budget pattern, SURVEY.md §3.4).
        """
        assert self.dtype.is_string
        lens = self.string_lengths()
        if pad_to is None:
            pad_to = max(1, self.max_string_length())
        starts = self.offsets[:-1]
        idx = starts[:, None] + jnp.arange(pad_to, dtype=jnp.int32)[None, :]
        in_range = idx < self.offsets[1:, None]
        idx = jnp.clip(idx, 0, max(int(self.data.shape[0]) - 1, 0))
        chars = jnp.where(in_range,
                          self.data[idx] if self.data.shape[0] else
                          jnp.zeros_like(idx, dtype=jnp.uint8),
                          jnp.uint8(fill))
        return chars.astype(jnp.uint8), lens


def _col_flatten(c: Column):
    dyn = (c.data, c.validity, c.offsets, c.children)
    aux = (c.dtype, c.length)
    return dyn, aux


def _col_unflatten(aux, dyn):
    dtype, length = aux
    data, validity, offsets, children = dyn
    return Column(dtype, length, data=data, validity=validity,
                  offsets=offsets, children=children)


jax.tree_util.register_pytree_node(Column, _col_flatten, _col_unflatten)
