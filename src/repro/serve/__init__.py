"""repro.serve — forecast serving.

:class:`ForecastServer` admission-groups compatible stencil forecasts into
one vmapped step per batch (see ``repro.ir.lower_batched``), executed
through a fingerprint-keyed LRU :class:`CompileCache` whose hit path
provably never re-traces (``cache.{hits,misses,evictions}`` counters +
per-entry trace probes). Each request carries queue latency and
items/sec; the server reports member occupancy and serves the registry's
exposition (``metrics_text``).
"""

from repro.serve.cache import CacheEntry, CompileCache, CompileKey, compile_key
from repro.serve.forecast import ForecastRequest, ForecastServer
