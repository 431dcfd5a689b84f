"""Whole-stage fusion: one jitted XLA program per (operator, shape bucket).

Reference contrast: the reference issues one cudf CUDA kernel per expression op
(GpuExpression columnarEval chains, SURVEY.md §1 L0/L4); kernel launches are
cheap on-node so that is fine there. On TPU every eager jax op is a separate
XLA program dispatch, and the per-op Python/trace overhead dominates small
batches (round-2 profile: ~5.4k primitive binds per TPC-H q1 batch, ~99% of
hot-run wall time). The TPU-native answer is whole-stage compilation, the same
move Spark itself makes for codegen: trace the operator's ENTIRE per-batch
computation (expression eval -> sort/segment/compact kernels) once per input
shape bucket, then replay one compiled XLA program per batch.

Kernels are cached at module level keyed by a SEMANTIC key (operator class +
expression-tree structure + static config), because the planner rebuilds exec
instances on every collect() — a per-instance `jax.jit` would recompile every
run. `jax.jit`'s own cache then handles shape/dtype/dictionary variation
under each kernel.

Also the home of the compile/dispatch accounting the tuning story needs:
`stage_metrics()` reports traces (XLA compiles) vs dispatches (program
replays); a healthy query does O(stages) traces and O(batches) dispatches.
"""

from __future__ import annotations

import dataclasses
import re
import threading
import types as _types

import jax

from spark_rapids_tpu.runtime import metrics as _M

_lock = threading.Lock()
_kernels: dict = {}
_MAX_KERNELS = 2048
# XLA:CPU's LLVM JIT owns a bounded code-memory region; ~3000 live
# executables exhaust it and later compiles fail with "LLVM compilation
# error: Cannot allocate memory" or SEGFAULT inside backend_compile_and_load
# (measured on XLA:CPU). A kernel holds one
# executable PER SHAPE SIGNATURE, so the backstop must budget executables,
# not kernel objects.
_MAX_EXECUTABLES = 900
_inserts = 0
# the get_kernel eviction only ran on INSERTS, so a long-lived multi-shape
# stage kernel could accumulate executables between inserts and silently
# blow the LLVM code-memory backstop; traces are the event that actually
# grows the executable population, so sweeps are also trace-driven
_SWEEP_EVERY_TRACES = 32
_last_sweep_traces = 0

# counters are module-global (queries share kernels); reset via reset_metrics()
_counts = {"traces": 0, "dispatches": 0}

def stage_metrics() -> dict:
    """{'traces': n_xla_compiles, 'dispatches': n_program_replays}."""
    with _lock:
        return dict(_counts)


def reset_metrics():
    global _last_sweep_traces
    with _lock:
        _counts["traces"] = 0
        _counts["dispatches"] = 0
        _last_sweep_traces = 0


class _Unset:
    __slots__ = ()

    def __repr__(self):
        return "<unset>"


_UNSET = _Unset()


@jax.tree_util.register_pytree_node_class
class Facts:
    """What a kernel decided while it was traced (the path it took, the
    operands of its sort): no array, the facts as static aux data, so the
    caller of a cached program reads what that program's trace decided."""

    def __init__(self, **facts):
        self.items = tuple(sorted(facts.items()))

    def tree_flatten(self):
        return (), self.items

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(**dict(aux))


def program_name(name: str) -> str:
    """``HashJoin.emit`` -> ``srt_HashJoin_emit``: what a kernel's XLA
    program is called in a device trace, and the outermost scope of every
    operation inside it."""
    return "srt_" + re.sub(r"\W", "_", name)


class BatchKernel:
    """A jitted per-batch function with trace/dispatch accounting.

    The wrapped python body runs once per (shape, dtype, aux) signature —
    counting its executions counts XLA compiles; counting __call__ counts
    dispatches.

    When the persistent stage cache is configured (runtime/stage_cache.py)
    and the semantic key has a stable cross-process digest, compiled
    executables are looked up / saved through `jax.jit(...).lower().compile()`
    + serialize_executable instead of the in-process jit cache: a fresh
    process replays the stored XLA executable with ZERO Python traces. Any
    undigestable key or argument signature quietly falls back to the plain
    jit path — the cache is an accelerator, never a correctness gate."""

    __slots__ = ("name", "_jit", "_key", "_digest", "_compiled")

    # bound per-kernel: a fused multi-shape stage kernel may legitimately
    # hold many signatures, but FIFO-dropping the oldest keeps any one
    # kernel from monopolizing the executable budget
    _MAX_SIGS = 64

    def __init__(self, fn, name: str, key=None):
        self.name = name
        self._key = key
        self._digest = _UNSET       # lazily: hex str, or None (undigestable)
        self._compiled: dict = {}   # sig digest -> AOT-loaded executable

        def program(*args):
            with _lock:
                _counts["traces"] += 1
            # per-query retrace attribution: the tracing thread runs inside
            # the query's collector scope, so the compile lands on the query
            # that paid for it (metrics.compile_add, the resilience pattern)
            _M.compile_add("compiles")
            return fn(*args)

        # the device trace names a program after its function: jit_srt_<name>
        # (the call site's name, ~30 of them; the fingerprint stays out)
        program.__name__ = program.__qualname__ = program_name(name)
        self._jit = jax.jit(program)

    def cache_size(self) -> int:
        """Live compiled-executable count: one per traced shape signature in
        the jit cache PLUS one per AOT executable held for the persistent
        stage cache (a fused stage kernel can hold many — the budget must see
        them all, not just the jit side)."""
        n = len(self._compiled)
        try:
            return max(int(self._jit._cache_size()) + n, 1)
        except Exception:
            return max(n, 1)

    def _dispatch(self, args):
        from spark_rapids_tpu.runtime import stage_cache as _SC
        store = _SC.get()
        if store is not None:
            if self._digest is _UNSET:
                self._digest = (key_digest(self._key)
                                if self._key is not None else None)
            if self._digest is not None:
                sig = _sig_digest(args)
                if sig is not None:
                    return self._dispatch_persistent(store, sig, args)
        return self._jit(*args)

    def _dispatch_persistent(self, store, sig, args):
        exe = self._compiled.get(sig)
        if exe is None:
            # platform + jax version namespace the entry: a shared cache dir
            # must never hand a CPU executable to a TPU session (or a new
            # jax an old serialization format)
            entry = f"{_backend_tag()}-{self._digest}-{sig}"
            data = store.load(entry)
            if data is not None:
                try:
                    exe = _deserialize_executable(data)
                except Exception as e:  # noqa: BLE001 — corrupt entry:
                    # degrade to retrace-with-warning, never failure
                    store.invalidate(entry, repr(e))
                    exe = None
            if exe is None:
                # cold: AOT-compile through the counting wrapper (the trace
                # lands in the ledger exactly like a jit-path trace)
                exe = self._jit.lower(*args).compile()
                try:
                    data = _serialize_executable(exe)
                    # round-trip validation before the entry lands on disk:
                    # an executable rehydrated from jax's own persistent
                    # compile cache serializes WITHOUT its object code
                    # ("Symbols not found" on the next load) — better a
                    # memory-only kernel now than a corrupt entry later
                    _deserialize_executable(data)
                    store.save(entry, data)
                except Exception as e:  # noqa: BLE001 — unserializable
                    store.note_unserializable(entry, repr(e))
            with _lock:
                while len(self._compiled) >= self._MAX_SIGS:
                    self._compiled.pop(next(iter(self._compiled)))
                self._compiled[sig] = exe
        return exe(*args)

    def __call__(self, *args):
        global _last_sweep_traces
        do_sweep = False
        with _lock:
            _counts["dispatches"] += 1
            # trace-driven executable sweep (multi-shape stage kernels grow
            # the executable population WITHOUT get_kernel inserts)
            if _counts["traces"] - _last_sweep_traces >= _SWEEP_EVERY_TRACES:
                _last_sweep_traces = _counts["traces"]
                do_sweep = True
        if do_sweep:
            _sweep_executables()
        _M.compile_add("dispatches")
        return self._dispatch(args)


_backend_tag_memo = None

# BUMP whenever any kernel BODY changes behavior under an unchanged semantic
# key: persistent entries are keyed by (semantic key, arg signature), not by
# the traced HLO, so a stale store replaying an old program would be a silent
# wrong answer — the version tag turns it into a cache miss instead.
# 2: programs carry their kernel's name (jit_srt_<name>) and operator scopes.
# 3: the join probe, the join chain and the single-page decode take fewer
# operands (no hash-table dummies, no static present count).
# 4: the join programs probe by the packed key tuple (one key or several).
KERNEL_CACHE_VERSION = 4


def _backend_tag() -> str:
    global _backend_tag_memo
    if _backend_tag_memo is None:
        import spark_rapids_tpu as _pkg
        _backend_tag_memo = (f"{jax.devices()[0].platform}-{jax.__version__}-"
                             f"{_pkg.__version__}-k{KERNEL_CACHE_VERSION}")
    return _backend_tag_memo


def _serialize_executable(exe) -> bytes:
    import pickle
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree = _se.serialize(exe)
    return pickle.dumps((payload, in_tree, out_tree))


def _deserialize_executable(data: bytes):
    import pickle
    from jax.experimental import serialize_executable as _se
    from spark_rapids_tpu.runtime.memory import DeviceManager
    payload, in_tree, out_tree = pickle.loads(data)
    # a kernel is compiled for one device; without execution_devices the
    # program would be loaded onto every local device of the host
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[DeviceManager.get().device])


def _sweep_locked() -> list:
    """Evict oldest kernels (insertion order) until the live-executable total
    is comfortably under budget. Caller holds _lock; returns the evicted
    kernels so their destructors can run outside it."""
    evicted = []
    total = sum(kk.cache_size() for kk in _kernels.values()
                if isinstance(kk, BatchKernel))   # skip _EAGER
    if total > _MAX_EXECUTABLES or len(_kernels) >= _MAX_KERNELS:
        order = list(_kernels)
        while order and (total > int(_MAX_EXECUTABLES * 0.75)
                         or len(_kernels) >= _MAX_KERNELS):
            victim = _kernels.pop(order.pop(0))
            if isinstance(victim, BatchKernel):
                total -= victim.cache_size()
                evicted.append(victim)
    return evicted


def _sweep_executables():
    with _lock:
        evicted = _sweep_locked()
    del evicted   # destructors run outside the lock


def get_kernel(key, name: str, build) -> BatchKernel:
    """Fetch-or-create the kernel for semantic key `key`. `build()` returns the
    pure per-batch function (it may close over expression trees — the key must
    capture everything that affects the traced program)."""
    global _inserts
    with _lock:
        k = _kernels.get(key)
    if k is not None:
        return k
    k = BatchKernel(build(), name, key=key)
    evicted = []
    with _lock:
        _inserts += 1
        if len(_kernels) >= _MAX_KERNELS or _inserts % 32 == 0:
            evicted = _sweep_locked()
        out = _kernels.setdefault(key, k)
    del evicted   # destructors run outside the lock
    return out


def clear_kernels():
    with _lock:
        _kernels.clear()


_EAGER = "eager"  # sentinel cache entry: this key cannot be traced

_TRACE_ERRORS = tuple(
    e for e in (getattr(jax.errors, n, None) for n in
                ("ConcretizationTypeError", "TracerArrayConversionError",
                 "TracerBoolConversionError", "TracerIntegerConversionError"))
    if e is not None)


def call_fused(key, name: str, build, args, eager):
    """Run the kernel for `key` over `args`, falling back PERMANENTLY to
    `eager()` if the computation turns out to be untraceable (host sync /
    data-dependent Python control flow inside eval). The fallback latches per
    key so the failed trace is paid once. Keys containing UNKEYABLE fields
    (objects with no stable content key) are never cached — fusing them would
    key compiled programs on object addresses."""
    if not key_is_cacheable(key):
        return eager()
    with _lock:
        k = _kernels.get(key)
    if k is _EAGER:
        return eager()
    try:
        if k is None:
            k = get_kernel(key, name, build)
        return k(*args)
    except _TRACE_ERRORS:
        with _lock:
            _kernels[key] = _EAGER
        return eager()


# -- semantic keys over expression trees -------------------------------------

def expr_key(e):
    """Stable hashable key for an expression tree: class identity + every
    constructor-visible field, recursively. Two expressions with equal keys
    must trace to the same program over equal-signature inputs."""
    from spark_rapids_tpu.expr.core import Expression
    if isinstance(e, Expression):
        parts = [type(e).__module__, type(e).__qualname__]
        d = vars(e) if hasattr(e, "__dict__") else {
            s: getattr(e, s, None) for s in getattr(e, "__slots__", ())}
        for k in sorted(d):
            parts.append((k, _value_key(d[k])))
        return tuple(parts)
    return _value_key(e)


class _Unkeyable:
    """Marker embedded in a semantic key when some field has no stable content
    key (e.g. an arbitrary object whose repr would embed id()). call_fused
    treats any key containing it as uncacheable and runs eagerly — a fresh
    repr()-based key would either collide across distinct objects after
    address reuse or never be shared, so neither caching behavior is safe."""

    __slots__ = ()

    def __repr__(self):
        return "<unkeyable>"


UNKEYABLE = _Unkeyable()


_fn_key_active = threading.local()


def _fn_key(v):
    """Stable content key for a plain Python function: bytecode + consts +
    names + defaults + closure contents + the referenced module globals. Two
    content-equal UDFs share one compiled kernel; anything address-dependent
    (instance state, unkeyable globals) degrades to UNKEYABLE."""
    if hasattr(v, "__func__"):          # bound method: instance state matters
        return ("bound", _value_key(v.__self__), _fn_key(v.__func__))
    # mutually-recursive globals (def a(): b(); def b(): a()) would recurse
    # forever; on re-entry the participant's own bytecode already contributes
    # at the outer level, so a name marker suffices
    active = getattr(_fn_key_active, "ids", None)
    if active is None:
        active = _fn_key_active.ids = set()
    if id(v) in active:
        return ("recursive-fn", getattr(v, "__qualname__", "?"))
    active.add(id(v))
    try:
        return _fn_key_inner(v)
    finally:
        active.discard(id(v))


def _fn_key_inner(v):
    code = v.__code__
    consts = tuple(_value_key(c) for c in code.co_consts)
    defaults = tuple(_value_key(d) for d in (v.__defaults__ or ()))
    closure = tuple(_value_key(c.cell_contents)
                    for c in (v.__closure__ or ()))
    # a global read (`FACTOR`, `jnp`) is baked into the traced program just
    # like a const — key its VALUE, not just its name, else two modules with
    # different FACTORs collide on one kernel. Modules key by name; names not
    # in __globals__ are builtins/attribute names (stable / covered by the
    # object they're read from).
    fglobals = getattr(v, "__globals__", {}) or {}
    gparts = []
    for name in code.co_names:
        if name in fglobals:
            g = fglobals[name]
            gparts.append((name, ("mod", g.__name__)
                           if isinstance(g, _types.ModuleType)
                           else _value_key(g)))
    return ("fn", code.co_code, consts, code.co_names, code.co_varnames,
            defaults, closure, tuple(gparts))


def _value_key(v):
    from spark_rapids_tpu.expr.core import Expression
    from spark_rapids_tpu import types as T
    if isinstance(v, Expression):
        return expr_key(v)
    if isinstance(v, (list, tuple)):
        return tuple(_value_key(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _value_key(x)) for k, x in v.items()))
    if isinstance(v, (str, int, float, bool, bytes, type(None))):
        return (type(v).__name__, v)
    if isinstance(v, T.DataType):
        return v
    if isinstance(v, type):              # class-valued fields (strategy
        return ("class", v.__module__, v.__qualname__)  # selectors etc.)
    if dataclasses.is_dataclass(v):      # a window's spec and frame
        return (type(v).__module__, type(v).__qualname__) + tuple(
            (f.name, _value_key(getattr(v, f.name)))
            for f in dataclasses.fields(v))
    if isinstance(v, _types.CodeType):   # nested function consts
        return ("code", v.co_code, tuple(_value_key(c) for c in v.co_consts),
                v.co_names)
    if callable(v) and hasattr(v, "__code__"):
        try:
            return _fn_key(v)
        except (AttributeError, ValueError):
            return UNKEYABLE
    return UNKEYABLE


def key_is_cacheable(key) -> bool:
    """False if any component of a (nested-tuple) semantic key is UNKEYABLE."""
    if key is UNKEYABLE:
        return False
    if isinstance(key, tuple):
        return all(key_is_cacheable(p) for p in key)
    return True


def schema_key(schema) -> tuple:
    return tuple((f.name, f.data_type, f.nullable) for f in schema)


class DictRef:
    """Hashable identity for a host string dictionary crossing a jit cache
    boundary (pa.Array itself is unhashable). Equality is CONTENT equality so
    per-batch dictionary objects with equal values hit the same compiled
    program; the hash is cheap (length only) — buckets stay small because
    dictionaries recur."""

    __slots__ = ("arr",)

    def __init__(self, arr):
        self.arr = arr

    def __hash__(self):
        return hash(len(self.arr))

    def __eq__(self, other):
        if not isinstance(other, DictRef):
            return NotImplemented
        if self.arr is other.arr:
            return True
        try:
            return self.arr.equals(other.arr)
        except (TypeError, AttributeError):
            return False

    def __repr__(self):
        return f"DictRef(len={len(self.arr)})"


# -- cross-process digests (persistent compiled-stage cache) ------------------
#
# The in-memory semantic keys above only need to be HASHABLE; the on-disk
# stage cache additionally needs keys that are STABLE ACROSS PROCESSES, so
# they are reduced to a sha256 over a canonical byte encoding. Anything
# without a stable content encoding (UNKEYABLE markers, foreign objects)
# makes the whole key undigestable and the kernel stays memory-only.

import hashlib as _hashlib


class _Undigestable(Exception):
    pass


def _hash_part(h, v):
    from spark_rapids_tpu import types as T
    if v is None or isinstance(v, (bool, int, float, str)):
        h.update(f"{type(v).__name__}:{v!r};".encode())
    elif isinstance(v, bytes):
        h.update(b"b:")
        h.update(v)
        h.update(b";")
    elif isinstance(v, tuple) or isinstance(v, list):
        h.update(f"t{len(v)}(".encode())
        for p in v:
            _hash_part(h, p)
        h.update(b")")
    elif isinstance(v, T.DataType):
        h.update(f"dt:{v!r};".encode())
    elif isinstance(v, DictRef):
        h.update(f"dr:{_dict_digest(v.arr)};".encode())
    elif v is _EAGER or isinstance(v, _Unkeyable):
        raise _Undigestable(v)
    else:
        raise _Undigestable(v)


def key_digest(key) -> str | None:
    """Stable cross-process hex digest of a semantic kernel key, or None when
    some component has no canonical byte encoding (those kernels never reach
    the persistent stage cache)."""
    h = _hashlib.sha256()
    try:
        _hash_part(h, key)
    except _Undigestable:
        return None
    return h.hexdigest()[:32]


# host string dictionaries recur across batches; content digests are memoized
# by (id, len) — the len guard keeps an address-reuse collision from pairing
# a freed array's digest with a different same-address dictionary of equal
# length (astronomically unlikely to ALSO hash-collide, and the persistent
# cache is advisory)
_dict_digest_memo: dict = {}


def _dict_digest(arr) -> str:
    k = (id(arr), len(arr))
    v = _dict_digest_memo.get(k)
    if v is None:
        h = _hashlib.sha256()
        for s in arr:
            h.update(repr(s).encode())
            h.update(b"\x00")
        v = h.hexdigest()[:16]
        if len(_dict_digest_memo) > 4096:
            _dict_digest_memo.clear()
        _dict_digest_memo[k] = v
    return v


def _sig_digest(args) -> str | None:
    """Per-call argument-signature digest: everything `jax.jit` keys its own
    cache on (pytree structure, array shapes/dtypes, static leaves) reduced
    to a stable string. Python scalars are weak-typed DYNAMIC jit arguments —
    their VALUE is not baked into the program, so they contribute type only.
    Returns None for unsupported leaves (that call falls back to plain jit)."""
    h = _hashlib.sha256()
    try:
        _sig_part(h, args)
    except _Undigestable:
        return None
    return h.hexdigest()[:32]


def _sig_part(h, v):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.core import Col
    from spark_rapids_tpu.columnar.encoded import EncodedCol
    if isinstance(v, Col):
        d = _dict_digest(v.dictionary) if v.dictionary is not None else None
        h.update(f"C:{v.dtype!r}:{v.values.shape}:{v.values.dtype}:"
                 f"{v.validity.shape}:{d};".encode())
    elif isinstance(v, EncodedCol):
        # aux (spec/dtype/dictionary) is STATIC — baked into the traced
        # program, so its VALUES discriminate signatures (via _hash_part);
        # children are ordinary dynamic arrays
        children, aux = v.tree_flatten()
        h.update(b"E(")
        _hash_part(h, aux)
        _sig_part(h, children)
        h.update(b")")
    elif isinstance(v, T.DataType):
        h.update(f"dt:{v!r};".encode())
    elif isinstance(v, DictRef):
        h.update(f"dr:{_dict_digest(v.arr)};".encode())
    elif isinstance(v, (tuple, list)):
        h.update(f"t{len(v)}(".encode())
        for p in v:
            _sig_part(h, p)
        h.update(b")")
    elif isinstance(v, bool) or isinstance(v, (int, float)):
        # weak-typed dynamic scalar: type matters, value does not
        h.update(f"s:{type(v).__name__};".encode())
    elif v is None:
        h.update(b"n;")
    elif hasattr(v, "shape") and hasattr(v, "dtype"):
        h.update(f"a:{v.shape}:{v.dtype};".encode())
    else:
        raise _Undigestable(v)
