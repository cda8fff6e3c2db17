"""Forecast serving: admission, vmap-batched execution, per-request health.

The schedulable unit is a *forecast request* — one IR program applied to
one set of initial-condition fields. The scheduler
groups compatible pending requests (same :class:`repro.serve.cache
.CompileKey` modulo batch size: same program fingerprint, grid, dtype,
mesh, k, backend) into ONE vmapped step over the member axis, so N
tenants' scenarios — or N perturbed members of one ensemble — share one
compiled kernel per step. The compile cache guarantees a repeat batch
shape never re-traces.

Fault isolation rides the vmap bit-exactness guarantee: members do not
mix, so a request whose fields blow up (NaN/Inf, caught by a
``HealthMonitor`` post-step check) fails ALONE — its batchmates complete
with results identical to unbatched runs. The stress suite injects exactly
this.

Telemetry. Every phase of the server is a span (:func:`repro.obs.events
.span`), recorded always in the span ring and, while a profiler captures,
on its host plane:

  * per request: ``serve.forecast.submit`` (rid) and
    ``serve.forecast.queue_wait`` (rid, batch), from submit to dispatch,
    recorded after the fact;
  * per batch: ``serve.forecast.step`` (batch), the parent of
    ``serve.forecast.admit`` (group pick, cache lookup),
    ``serve.forecast.stack`` (gathering the members' fields into the
    call's arguments), ``serve.forecast.dispatch`` (the batch's one
    compiled call — member stack, batched kernel, per-member slices —
    until it returns), ``serve.forecast.device_wait``
    (``block_until_ready`` on the per-member results),
    ``serve.forecast.unstack`` (handing the per-member results on) and
    ``serve.forecast.retire`` (health check, stamps, events).

With a metrics registry installed each span is also a timer of its name,
beside the gauge ``serve.forecast.member_occupancy`` (members in the last
batch / ``max_batch``; 0.0 when idle, the lane gauge's staleness rule) and
the counters ``serve.forecast.{requests_submitted,batches,members,
completed,failed}``. ``serve.forecast.marshal_builds`` counts the one-call
wrappers built, one per cache entry (so one per batch size of a program
and grid), each with a ``serve.forecast.marshal_build`` event naming its
batch size. ``serve.forecast.field_bytes`` (also ``stats["field_bytes"]``)
counts the logical bytes of the fields each batch reads and writes. On a
mesh, ``serve.forecast.halo_bytes`` (also ``stats["halo_bytes"]``) counts
each batch's halo exchange: the per-chip model bytes of its one round
(:func:`repro.dist.halo.call_exchange_bytes`), times its members, beside
the counters ``halo.exchange_rounds`` and ``halo.model_bytes.<field>`` and
a ``halo.exchange`` event per batch. The batch's call is traced inside
:func:`_marshal`'s jit, where ``lower_sharded`` counts nothing, so the
server counts it on the host from the batch's key; a batch on one chip
counts none.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.dist.halo import call_exchange_bytes
from repro.obs import events, metrics
from repro.obs.health import HealthMonitor, NumericsError
from repro.serve.cache import CompileCache, CompileKey, compile_key

Array = jax.Array


@dataclasses.dataclass
class ForecastRequest:
    """One tenant's forecast: a program + its initial-condition fields.

    The server stamps a telemetry trio on it — submit / dispatch / done
    timestamps, queue latency, and per-request throughput
    (``items_per_sec``, where the item is one completed forecast)."""

    rid: int
    program: Any                      # StencilProgram
    fields: dict[str, Array]          # {input: (depth, rows, cols)}
    result: Any = None                # array, or {field: array} (multi-output)
    error: Exception | None = None
    done: bool = False
    submitted_ts: float | None = None
    dispatch_ts: float | None = None
    done_ts: float | None = None
    queue_latency_s: float | None = None
    items_per_sec: float | None = None
    _submitted_ns: int = dataclasses.field(init=False, repr=False, default=0)

    @property
    def failed(self) -> bool:
        return self.error is not None

    @property
    def group_key(self) -> CompileKey:
        """The admission key: everything the compile key pins EXCEPT the
        batch size — requests sharing it can ride one vmapped step."""
        return self._group_key

    _group_key: CompileKey = dataclasses.field(init=False, repr=False, default=None)


def _marshal(fn: Callable, inputs: tuple[str, ...]) -> Callable:
    """One batch as one jitted call around a cache entry's batched ``fn``.

    The call takes the members' ``{input: (D, R, C)}`` mappings, stacks
    each input along a fresh member axis, runs ``fn`` on the stacked
    mapping and slices the batched output back into one result per
    member: an array, or ``{field: array}`` for multi-output programs.
    Stack, kernel and slices are one program, so a batch is one dispatch
    with no host round trip between them; the slices are exact, so each
    member's result is bit-identical to ``fn``'s ``out[i]``. The batch
    size is the tuple's length, so one wrapper per cache entry traces once.
    Nothing is donated: a request's fields stay valid after its step.
    """

    def call(members: tuple[Mapping[str, Array], ...]):
        out = fn({f: jnp.stack([m[f] for m in members]) for f in inputs})
        if isinstance(out, Mapping):
            return tuple({f: v[i] for f, v in out.items()} for i in range(len(members)))
        return tuple(out[i] for i in range(len(members)))

    return jax.jit(call)


class ForecastServer:
    """Admission control + vmap-batched execution over a compile cache.

    One ``step()`` = one batched forecast: pop the oldest pending request,
    sweep the queue for up to ``max_batch - 1`` more requests with the SAME
    group key (FIFO within the group; incompatible requests keep their
    place for a later step), and run them as one compiled call that stacks
    their fields along a fresh member axis, runs the cached batched
    lowering and slices out per-member results (:func:`_marshal`, held on
    the cache entry and evicted with it).
    Heterogeneous tenants therefore interleave safely: each step is
    homogeneous, and no request is starved because group sweeps always
    start from the queue head.

    ``monitor`` (optional, a :class:`HealthMonitor`) is applied PER MEMBER
    post-step: each member's output fields are force-checked, and a member
    that trips the monitor retires with ``error`` set while its batchmates
    complete normally — the vmap path computes members independently, so a
    blown-up member cannot contaminate the others.

    Each ``step()`` is one ``serve.forecast.step`` span over its six phase
    spans, and each request adds a ``submit`` and a ``queue_wait`` span
    (module docstring): ``repro.obs.events.spans()`` then says where the
    server's time went, batch by batch, with nothing installed.
    """

    def __init__(
        self,
        *,
        backend: str = "reference",
        mesh_shape: tuple[int, int] | None = None,
        max_batch: int = 8,
        cache: CompileCache | None = None,
        cache_capacity: int = 16,
        monitor: HealthMonitor | None = None,
        lower_kwargs: Mapping[str, Any] | None = None,
    ) -> None:
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.backend = backend
        self.mesh_shape = tuple(mesh_shape) if mesh_shape is not None else None
        self.max_batch = max_batch
        self.cache = cache if cache is not None else CompileCache(cache_capacity)
        self.monitor = monitor
        self.lower_kwargs = dict(lower_kwargs or {})
        self._queue: list[ForecastRequest] = []
        self._next_rid = 0
        self.completed: list[ForecastRequest] = []
        self.stats = {"batches": 0, "members": 0, "completed": 0, "failed": 0,
                      "field_bytes": 0, "halo_bytes": 0}

    # -- admission ---------------------------------------------------------
    def submit(
        self,
        program,
        fields: Array | Mapping[str, Array],
    ) -> int:
        """Enqueue one forecast. ``fields`` is a ``{input: (D, R, C)}``
        mapping (or the bare array for single-input programs); shapes and
        dtypes join the admission key, so mixed grids never co-batch."""
        with events.span("serve.forecast.submit") as sp:
            req = self._enqueue(program, fields)
            # The span's recorder event is the request's submit event.
            sp.rid = req.rid
            sp.data.update(program=program.name, grid=list(req.group_key.grid),
                           k=program.steps)
        return req.rid

    def _enqueue(self, program, fields: Array | Mapping[str, Array]) -> ForecastRequest:
        if not isinstance(fields, Mapping):
            if len(program.inputs) != 1:
                raise ValueError(
                    f"program {program.name!r} has inputs "
                    f"{program.inputs}; pass a mapping"
                )
            fields = {program.inputs[0]: fields}
        missing = [f for f in program.inputs if f not in fields]
        if missing:
            raise ValueError(
                f"program {program.name!r} request is missing input(s) "
                f"{missing}; declared inputs are {list(program.inputs)}"
            )
        arrays = {f: jnp.asarray(fields[f]) for f in program.inputs}
        shapes = {tuple(a.shape) for a in arrays.values()}
        if len(shapes) != 1:
            raise ValueError(
                f"all fields of one forecast must share a grid, got {shapes}"
            )
        grid = shapes.pop()
        if len(grid) != program.ndim + 1:
            raise ValueError(
                f"program {program.name!r} wants a (depth, rows, cols) grid "
                f"({program.ndim + 1}-D), got shape {grid}"
            )
        rid = self._next_rid
        self._next_rid += 1
        req = ForecastRequest(rid=rid, program=program, fields=arrays)
        req._group_key = compile_key(
            program,
            grid=grid,
            dtype=next(iter(arrays.values())).dtype,
            backend=self.backend,
            mesh_shape=self.mesh_shape,
            batch=None,
        )
        req._submitted_ns = time.perf_counter_ns()
        req.submitted_ts = req._submitted_ns * 1e-9
        self._queue.append(req)
        metrics.inc("serve.forecast.requests_submitted")
        return req

    def pending(self) -> int:
        return len(self._queue)

    # -- execution ---------------------------------------------------------
    def _admit_group(self) -> list[ForecastRequest]:
        """Head-of-queue request plus every same-group-key follower, FIFO,
        up to ``max_batch``. Skipped requests keep their queue position."""
        head = self._queue[0]
        group = [head]
        for req in self._queue[1:]:
            if len(group) >= self.max_batch:
                break
            if req.group_key == head.group_key:
                group.append(req)
        picked = {id(r) for r in group}
        self._queue = [r for r in self._queue if id(r) not in picked]
        return group

    def step(self) -> bool:
        """One batched forecast step. Returns False when idle."""
        if not self._queue:
            metrics.set_gauge("serve.forecast.member_occupancy", 0.0)
            return False
        batch = self.stats["batches"]
        with events.span("serve.forecast.step", batch=batch):
            with events.span("serve.forecast.admit", batch=batch):
                group = self._admit_group()
                now_ns = time.perf_counter_ns()
                for req in group:
                    req.dispatch_ts = now_ns * 1e-9
                    req.queue_latency_s = (now_ns - req._submitted_ns) * 1e-9
                    events.record_span("serve.forecast.queue_wait", req._submitted_ns,
                                       now_ns, rid=req.rid, batch=batch)
                key = group[0].group_key
                program = group[0].program
                n = len(group)
                metrics.set_gauge("serve.forecast.member_occupancy", n / self.max_batch)
                entry = self.cache.get_entry(
                    program,
                    grid=key.grid,
                    dtype=key.dtype,
                    backend=key.backend,
                    mesh_shape=key.mesh,
                    batch=n,
                    **self.lower_kwargs,
                )
                if entry.marshal is None:
                    entry.marshal = _marshal(entry.fn, program.inputs)
                    metrics.inc("serve.forecast.marshal_builds")
                    events.record("serve.forecast.marshal_build",
                                  program=program.name, batch=n)
            with events.span("serve.forecast.stack", batch=batch):
                args = tuple(req.fields for req in group)
            with events.span("serve.forecast.dispatch", batch=batch):
                out = entry.marshal(args)
            with events.span("serve.forecast.device_wait", batch=batch):
                out = jax.block_until_ready(out)
            with events.span("serve.forecast.unstack", batch=batch):
                members = list(out)
            with events.span("serve.forecast.retire", batch=batch):
                self._retire(group, members, batch)
        return True

    def _retire(self, group: list[ForecastRequest], members: list, batch: int) -> None:
        """Health-checks each member's result and stamps its request done."""
        n = len(group)
        field_bytes = sum(a.nbytes for req in group for a in req.fields.values())
        field_bytes += sum(a.nbytes for a in jax.tree.leaves(members))
        self.stats["batches"] += 1
        self.stats["members"] += n
        self.stats["field_bytes"] += field_bytes
        metrics.inc("serve.forecast.batches")
        metrics.inc("serve.forecast.members", n)
        metrics.inc("serve.forecast.field_bytes", field_bytes)
        key = group[0].group_key
        if key.mesh is not None:
            self._record_exchange(group[0].program, key, n, batch)
        done = time.perf_counter()
        for req, member in zip(group, members):
            req.done_ts = done
            if done > req.dispatch_ts:
                req.items_per_sec = 1.0 / (done - req.dispatch_ts)
            try:
                self._check_member(req, member)
            except NumericsError as err:
                req.error = err
                self.stats["failed"] += 1
                metrics.inc("serve.forecast.failed")
                events.record(
                    "serve.forecast.fail", rid=req.rid,
                    program=req.program.name, field=err.field,
                )
            else:
                req.result = member
                self.stats["completed"] += 1
                metrics.inc("serve.forecast.completed")
            req.done = True
            self.completed.append(req)
            events.record(
                "serve.forecast.retire", rid=req.rid, batch=batch, members=n,
                failed=req.failed, queue_latency_s=req.queue_latency_s,
                items_per_sec=req.items_per_sec,
            )

    def _record_exchange(self, program, key: CompileKey, n: int, batch: int) -> None:
        """Counts a mesh batch's halo exchange: one round, in which each of
        the ``n`` members moves the per-chip model bytes of one call."""
        per_field = call_exchange_bytes(program, key.grid, (1, *key.mesh),
                                        itemsize=jnp.dtype(key.dtype).itemsize)
        if not per_field:
            return
        halo_bytes = n * sum(per_field.values())
        self.stats["halo_bytes"] += halo_bytes
        metrics.inc("serve.forecast.halo_bytes", halo_bytes)
        metrics.inc("halo.exchange_rounds")
        for f, nbytes in per_field.items():
            metrics.inc(f"halo.model_bytes.{f}", n * nbytes)
        events.record("halo.exchange", program=program.name, batch=batch,
                      mesh=list(key.mesh), bytes=halo_bytes)

    def _check_member(self, req: ForecastRequest, member) -> None:
        """Force-check every output field of ONE member's result against
        the monitor — this is where a NaN-injected request dies alone."""
        if self.monitor is None:
            return
        outputs = member if isinstance(member, Mapping) else {"out": member}
        for fname, arr in outputs.items():
            self.monitor.check(
                req.program.steps, arr,
                name=f"{req.program.name}[{req.rid}].{fname}", force=True,
            )

    def run_until_idle(self, max_steps: int = 10_000) -> list[ForecastRequest]:
        """Drain the queue; returns the requests retired by THIS drain (in
        retirement order)."""
        start = len(self.completed)
        for _ in range(max_steps):
            if not self.step():
                break
        return self.completed[start:]

    def metrics_text(self) -> str:
        """The scrape body: the active metrics registry in the Prometheus
        text exposition format (:func:`repro.obs.export.prometheus_text`),
        so the server's ``serve.forecast.*`` counters, gauges and span
        timers read as ``repro_serve_forecast_*`` series. With no registry
        installed it is the one comment line that says so, still
        well-formed."""
        from repro.obs.export import prometheus_text

        return prometheus_text()
