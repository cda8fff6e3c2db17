"""Fingerprint-keyed LRU compile cache for the forecast-serving layer.

StencilFlow treats whole stencil programs as cacheable, schedulable units;
this module is that idea applied to serving: heterogeneous forecast
requests must never pay a re-trace when an equivalent program has already
been lowered. The key is everything that determines the traced computation
and nothing else:

    (program.fingerprint(), grid shape, dtype, mesh shape, k, backend,
     batch size)

``StencilProgram.fingerprint()`` is the content-addressed structural hash
(display-name-blind), so two tenants submitting structurally-equal programs
under different names share one entry, while a program differing in one
coefficient tap hashes — and therefore compiles — separately.

Accounting is exact and observable: ``hits`` / ``misses`` / ``evictions``
counts on the cache object, mirrored into the ``repro.obs`` metrics
registry as the ``cache.hits`` / ``cache.misses`` / ``cache.evictions``
counter trio (plus ``cache.traces``). Eviction is LRU at ``capacity``
entries.

The zero-retrace invariant is *assertable*, not aspirational: every cached
callable is wrapped in a trace-count probe — a closure whose Python body
runs only while jax traces it — so ``entry.traces`` counts actual traces.
A cache hit reuses the jitted callable at an already-seen (shape, dtype,
structure) signature (the key pins all of them), so a hit performs ZERO
retraces; the property suite drives arbitrary request sequences against
this.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Callable

import numpy as np

from repro.obs import events, metrics


@dataclasses.dataclass(frozen=True)
class CompileKey:
    """Everything that determines one lowered computation's trace.

    ``fingerprint`` is the program's canonical structural hash; ``k`` is
    its chain length (``program.steps`` — redundant with the fingerprint,
    kept explicit so cache introspection / eviction logs read well);
    ``batch`` is the ensemble-member count (None = unbatched single
    forecast); ``mesh`` is the (R, C) device-mesh factorization for the
    sharded backends (None = single device)."""

    fingerprint: str
    grid: tuple[int, ...]
    dtype: str
    mesh: tuple[int, int] | None
    k: int
    backend: str
    batch: int | None


def compile_key(
    program,
    *,
    grid: tuple[int, ...],
    dtype: Any = np.float32,
    backend: str = "reference",
    mesh_shape: tuple[int, int] | None = None,
    batch: int | None = None,
) -> CompileKey:
    """The :class:`CompileKey` of one request shape."""
    return CompileKey(
        fingerprint=program.fingerprint(),
        grid=tuple(int(g) for g in grid),
        dtype=np.dtype(dtype).name,
        mesh=tuple(int(m) for m in mesh_shape) if mesh_shape is not None else None,
        k=program.steps,
        backend=backend,
        batch=int(batch) if batch is not None else None,
    )


@dataclasses.dataclass
class CacheEntry:
    """One cached lowered callable + its trace-count probe state.

    ``marshal`` is free for the caller to hang a callable built around
    ``fn`` on (``ForecastServer`` keeps its one-call batch wrapper there),
    so that it lives and is evicted with the entry."""

    key: CompileKey
    fn: Callable
    program_name: str
    traces: int = 0
    hits: int = 0
    marshal: Callable | None = None


class CompileCache:
    """LRU cache of lowered (and trace-probed) program callables.

    ``get`` is the whole API surface the server uses: key the request,
    return the cached callable or build-and-insert it, evicting the least
    recently used entry past ``capacity``; ``get_entry`` is the same with
    the whole :class:`CacheEntry` returned. Like the metrics registry it is
    deliberately not thread-safe — one Python scheduler drives it.

    ``builder(program, key, **lower_kwargs) -> callable`` constructs a
    lowered callable on a miss; the default dispatches to
    :func:`repro.ir.lower_batched` (``key.batch`` set) or the matching
    single lowering (``key.batch is None``). Tests inject stub builders to
    drive the LRU bookkeeping without paying for real lowerings.
    """

    def __init__(
        self,
        capacity: int = 16,
        *,
        builder: Callable[..., Callable] | None = None,
        trace_probe: bool = True,
    ) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.builder = builder if builder is not None else _default_builder
        self.trace_probe = trace_probe
        self._entries: OrderedDict[CompileKey, CacheEntry] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- introspection -----------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: CompileKey) -> bool:
        return key in self._entries

    def keys(self) -> list[CompileKey]:
        """Keys in LRU order: least recently used first."""
        return list(self._entries)

    def lookup(self, key: CompileKey) -> CacheEntry | None:
        """The entry for ``key`` with NO accounting and NO recency bump —
        for tests/diagnostics only; the serving path goes through
        :meth:`get`."""
        return self._entries.get(key)

    def stats(self) -> dict[str, int]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "size": len(self._entries),
            "capacity": self.capacity,
        }

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def total_traces(self) -> int:
        """Traces across LIVE entries — evicted entries' counts are gone,
        which is exactly right: re-building an evicted entry is a miss, and
        its fresh trace is the miss's cost, not a hit's."""
        return sum(e.traces for e in self._entries.values())

    # -- the cache ---------------------------------------------------------
    def get(
        self,
        program,
        *,
        grid: tuple[int, ...],
        dtype: Any = np.float32,
        backend: str = "reference",
        mesh_shape: tuple[int, int] | None = None,
        batch: int | None = None,
        **lower_kwargs,
    ) -> Callable:
        """The lowered callable for one request shape (cached)."""
        return self.get_entry(
            program, grid=grid, dtype=dtype, backend=backend,
            mesh_shape=mesh_shape, batch=batch, **lower_kwargs,
        ).fn

    def get_entry(
        self,
        program,
        *,
        grid: tuple[int, ...],
        dtype: Any = np.float32,
        backend: str = "reference",
        mesh_shape: tuple[int, int] | None = None,
        batch: int | None = None,
        **lower_kwargs,
    ) -> CacheEntry:
        """:meth:`get`, returning the entry rather than its callable."""
        key = compile_key(
            program, grid=grid, dtype=dtype, backend=backend,
            mesh_shape=mesh_shape, batch=batch,
        )
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
            entry.hits += 1
            self.hits += 1
            metrics.inc("cache.hits")
            return entry
        self.misses += 1
        metrics.inc("cache.misses")
        built = self.builder(program, key, **lower_kwargs)
        entry = CacheEntry(key=key, fn=built, program_name=program.name)
        if self.trace_probe:
            entry.fn = _with_trace_probe(built, entry)
        self._entries[key] = entry
        events.record(
            "cache.insert", program=program.name, backend=backend,
            k=key.k, batch=key.batch, size=len(self._entries),
        )
        while len(self._entries) > self.capacity:
            old_key, old = self._entries.popitem(last=False)
            self.evictions += 1
            metrics.inc("cache.evictions")
            events.record(
                "cache.evict", program=old.program_name,
                backend=old_key.backend, k=old_key.k, batch=old_key.batch,
            )
        return entry


def _with_trace_probe(fn: Callable, entry: CacheEntry) -> Callable:
    """Wraps ``fn`` so every TRACE (not call) bumps ``entry.traces``.

    The closure body executes exactly when jax traces it — once per novel
    (structure, shape, dtype) signature of the outer jit — so the counter
    is a ground-truth retrace probe: a cache hit at an already-traced
    signature leaves it unchanged, which the conformance/property suites
    assert. The wrapped computation is untouched (the probe's side effect
    is host-only and trace-time-only).
    """
    import jax

    def probed(x):
        entry.traces += 1
        metrics.inc("cache.traces")
        return fn(x)

    probed.__name__ = f"cached_{getattr(fn, '__name__', 'lowering')}"
    return jax.jit(probed)


def _default_builder(program, key: CompileKey, **lower_kwargs) -> Callable:
    """Build the lowering ``key`` describes (the real, non-stub builder)."""
    from repro.ir import build_backend, lower_batched

    if key.batch is None:
        return build_backend(
            program, key.backend, mesh_shape=key.mesh, **lower_kwargs
        )
    return lower_batched(
        program, backend=key.backend, mesh_shape=key.mesh, **lower_kwargs
    )
