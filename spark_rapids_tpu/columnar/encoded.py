"""Encoded-on-device column vectors — the H2D payload of the scan-side chain.

The device-decode scan path (io/parquet_native.py) used to expand every page
to a dense column in its own fused program before any consumer ran. With
encoded upload the scan ships the ENCODED page — bit-packed dictionary
indices, definition levels, and the dictionary — and the expansion happens
lazily inside the first consuming kernel (exec/aggregate.py's scan-fused
partial agg), so PCIe carries encoded bytes instead of dense columns. The
expansion body is ops/parquet_decode.decode_page_cols — the same trace the
standalone decode kernel runs — so encoded-vs-dense results are bit-identical
by construction.

Two layers:

- ``EncodedCol``: the pytree that crosses jit boundaries. Children are the
  device buffers (packed bytes/words, dictionary, def levels, count scalars);
  aux is the static ``EncodedPageSpec`` + dtype + DictRef'd host dictionary.
  ``decode()`` is traceable and returns an expr ``Col``.
- ``EncodedColumnVector``: the batch-level vector. Pretends to be a normal
  ``TpuColumnVector`` — ``data``/``validity`` are lazy properties that run
  the fused decode on first touch — so every consumer that does NOT fuse the
  prologue still sees a correct dense column (degraded, never wrong).
"""

from __future__ import annotations

import jax

from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.ops import parquet_decode as PD


@jax.tree_util.register_pytree_node_class
class EncodedCol:
    """One encoded data page as a jit-crossable value."""

    __slots__ = ("packed", "dict_dev", "dl", "n_present_t", "n_t",
                 "spec", "dtype", "dictionary")

    def __init__(self, packed, dict_dev, dl, n_present_t, n_t,
                 spec: PD.EncodedPageSpec, dtype, dictionary=None):
        self.packed = packed            # padded bytes
        self.dict_dev = dict_dev        # device dictionary / sorted-rank map
        self.dl = dl                    # def levels as bool, (capacity,)
        self.n_present_t = n_present_t  # int32 scalar, device
        self.n_t = n_t                  # int32 scalar, device (live rows)
        self.spec = spec
        self.dtype = dtype
        self.dictionary = dictionary    # host sorted pa.Array for strings

    def tree_flatten(self):
        d = self.dictionary
        if d is not None:
            from spark_rapids_tpu.runtime.fuse import DictRef
            d = DictRef(d)
        return ((self.packed, self.dict_dev, self.dl, self.n_present_t,
                 self.n_t), (self.spec, self.dtype, d))

    @classmethod
    def tree_unflatten(cls, aux, children):
        d = aux[2]
        if d is not None and type(d).__name__ == "DictRef":
            d = d.arr
        return cls(*children, aux[0], aux[1], d)

    def decode(self):
        """Traceable expansion to a dense expr Col (values, validity)."""
        from spark_rapids_tpu.expr.core import Col
        v, m = PD.decode_page_cols(self.spec, self.packed, self.dict_dev,
                                   self.dl, self.n_present_t, self.n_t)
        return Col(v, m, self.dtype, self.dictionary)


@jax.named_scope("ParquetScan.decode")
def densify_cols(cols):
    """Traceable prologue for fused kernels that accept mixed dense/encoded
    inputs: expand every EncodedCol to a dense expr Col in-trace (the page
    decode fuses into the consumer's program), pass everything else through.
    Kernels keep their semantic cache key — jit's argument structure and the
    fuse-layer signature both distinguish encoded from dense pytrees."""
    return [c.decode() if isinstance(c, EncodedCol) else c for c in cols]


class EncodedColumnVector(TpuColumnVector):
    """A TpuColumnVector whose dense arrays are built lazily by the fused
    page-decode kernel. ``capacity``/``device_memory_size`` answer without
    materializing; any read of ``data``/``validity`` expands once and caches.
    NOTE: runtime/pipeline.py's spill registration requires ``type(c) is
    TpuColumnVector`` exactly, so encoded vectors never spill mid-decode."""

    __slots__ = ("_enc", "_mat")

    def __init__(self, enc: EncodedCol):
        # parent __init__ would assign through the data/validity properties;
        # set the remaining parent slots directly instead
        self.dtype = enc.dtype
        self.dictionary = enc.dictionary
        self._dict_device = None
        self._enc = enc
        self._mat = None

    @property
    def encoded(self) -> "EncodedCol | None":
        """The encoded payload while still unexpanded, else None (a consumer
        that already forced `data` gains nothing from re-fusing the decode)."""
        return None if self._mat is not None else self._enc

    def _materialize(self):
        if self._mat is None:
            from spark_rapids_tpu.runtime import fuse
            e = self._enc
            spec = e.spec
            key = ("pq_page_decode", spec)

            def build():
                def kernel(packed_d, dict_d, dl_d, np_t, n_t):
                    return PD.decode_page_cols(spec, packed_d, dict_d, dl_d,
                                               np_t, n_t)
                return kernel

            args = (e.packed, e.dict_dev, e.dl, e.n_present_t, e.n_t)
            v, m = fuse.call_fused(key, "ParquetScan.decode", build, args,
                                   lambda: build()(*args))
            self._mat = (v, m)
        return self._mat

    @property
    def data(self):
        return self._materialize()[0]

    @property
    def validity(self):
        return self._materialize()[1]

    @property
    def capacity(self) -> int:
        return self._enc.spec.capacity

    def device_memory_size(self) -> int:
        """Bytes this vector actually put on the device: the encoded payload
        while unexpanded (this is what the h2d ledger should price), the
        dense arrays once someone forced them."""
        if self._mat is not None:
            sz = self._mat[0].nbytes + self._mat[1].nbytes
        else:
            e = self._enc
            sz = (e.packed.nbytes + e.dl.nbytes
                  + e.n_present_t.nbytes + e.n_t.nbytes)
        sz += self._enc.dict_dev.nbytes
        if self._dict_device is not None:
            sz += sum(a.nbytes for a in self._dict_device)
        return sz

    def encoded_payload_bytes(self) -> int:
        """H2D bytes of the encoded page (what crossed PCIe), independent of
        whether a consumer has since expanded it."""
        e = self._enc
        return (e.packed.nbytes + e.dl.nbytes + e.dict_dev.nbytes
                + e.n_present_t.nbytes + e.n_t.nbytes)

    def __repr__(self):
        state = "dense" if self._mat is not None else "encoded"
        return (f"EncodedColumnVector({self.dtype}, "
                f"cap={self.capacity}, {state})")
